"""Distributed batch scorer — the ``mlflow.pyfunc.spark_udf`` role.

The reference scores a table by wrapping the pyfunc in a Spark UDF applied to the
``content`` column over table partitions; executors each load the model once and
stream arrow batches through it
(``Part 2 - Distributed Tuning & Inference/03_pyfunc_distributed_inference.py:
466-472``; stack in SURVEY.md §3.5).

TPU-native equivalent: shards of the input table are the unit of work. Across
*hosts*, shards split by ``process_index`` (each host loads the packaged model
once); within a host, records are decoded on the loader thread pool and scored in
fixed-size device batches sharded across the host's **local** devices — model
replicated, batch split (batch-inference parallelism, SURVEY.md §2d). Scoring is
embarrassingly parallel, so no cross-host collectives are compiled in: each host's
jitted apply spans only addressable devices (a global-mesh program would force
every host to run the same number of batches — a deadlock when shard counts
differ). Results are written as a predictions table (path, label=prediction) via
the store: one table single-process, per-process table names multi-host.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.data.loader import preprocess_image
from ddw_tpu.data.store import Record, Table, TableStore, read_shard
from ddw_tpu.runtime.mesh import DATA_AXIS, make_mesh, MeshSpec
from ddw_tpu.serving.package import PackagedModel


def _scoring_run_id(table: Table, content_digest: str) -> str:
    """Deterministic scoring-run token — identical on every process for the
    same (input table version, packaged model), without communication.
    Shared by the image and LM scorers' part writes AND merge waits."""
    return TableStore.run_token(table.manifest["name"],
                                table.manifest["version"],
                                content_digest)


def _process_shards(table: Table) -> list[str]:
    """This process's disjoint shard subset (round-robin by rank); small
    tables fall to rank 0 — shared by the image and LM scorers."""
    shards = table.shard_paths
    n_proc = jax.process_count()
    if len(shards) >= n_proc:
        return shards[jax.process_index()::n_proc]
    return shards if jax.process_index() == 0 else []


def _local_mesh(mesh: Mesh | None) -> Mesh:
    """A 1-D data mesh over THIS process's addressable devices (scoring is
    shared-nothing: a global-mesh program would deadlock on unequal shard
    counts — module docstring)."""
    if mesh is None:
        mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)))
    local = [d for d in np.asarray(mesh.devices).flat
             if d.process_index == jax.process_index()]
    return Mesh(np.asarray(local), (DATA_AXIS,))


def _write_scored_table(out_store: TableStore, out_name: str, records,
                        meta: dict, table: Table, content_digest: str,
                        merge: bool) -> None:
    """The multi-host scores-table protocol, shared by both scorer families:
    per-process ``{out_name}_pN`` parts stamped with the run token, rank-0
    merge wait."""
    n_proc = jax.process_count()
    run_id = _scoring_run_id(table, content_digest)
    name = out_name if n_proc == 1 else f"{out_name}_p{jax.process_index()}"
    out_store.write(name, records,
                    meta={**meta, "source_table": table.manifest["name"],
                          "run_id": run_id})
    if merge and n_proc > 1 and jax.process_index() == 0:
        merge_predictions(out_store, out_name, n_proc, run_id)


class BatchScorer:
    """Score a table of JPEG-bytes records with a packaged model over the local
    devices of each participating host."""

    def __init__(self, model: PackagedModel | str, mesh: Mesh | None = None,
                 batch_per_device: int = 128, workers: int = 4):
        self.model = model if isinstance(model, PackagedModel) else PackagedModel(model)
        self.mesh = _local_mesh(mesh)
        self.n_devices = self.mesh.devices.size
        self.batch = batch_per_device * self.n_devices
        self.workers = workers
        self._sharding = NamedSharding(self.mesh, P(DATA_AXIS))

        pm = self.model

        def apply_fn(images):
            variables = {"params": pm.params}
            if pm.batch_stats:
                variables["batch_stats"] = pm.batch_stats
            return pm.model.apply(variables, images, train=False)

        self._apply = jax.jit(apply_fn,
                              in_shardings=self._sharding,
                              out_shardings=NamedSharding(self.mesh, P()))

    def score_table(self, table: Table, out_store: TableStore | None = None,
                    out_name: str = "predictions",
                    merge: bool = True) -> list[tuple[str, str]]:
        """Returns [(path, predicted_class)] for this process's shard subset; when
        ``out_store`` is given also writes them as a table (path, label=prediction).

        Decode runs the same hot path the training loader uses: one native C++
        thread-pool call per device batch (``decode_batch_native``), per-image
        PIL fallback — not one ctypes call per image. Multi-host with ``merge``:
        each process writes ``{out_name}_pN`` stamped with a run token derived
        from (input table version, packaged-model content digest); process 0
        waits for every part carrying that token and merges them into one
        ``out_name`` table (the reference's single spark_udf result table,
        ``03_pyfunc_distributed_inference.py:466-472``). The token keeps a
        re-score with a newer model or table from silently merging a previous
        run's parts for slower processes.
        """
        from ddw_tpu.native.decode import decode_batch_native

        h, w = self.model.height, self.model.width
        results: list[tuple[str, str]] = []

        raw_u8 = table.meta.get("encoding") == "raw_u8"
        if raw_u8 and (table.meta.get("height"), table.meta.get("width")) != (h, w):
            raise ValueError(
                f"materialized table is {table.meta.get('height')}x"
                f"{table.meta.get('width')} but the packaged model expects "
                f"{h}x{w} — re-materialize at the model's size or score the "
                f"JPEG silver table")

        def records():
            for sp in _process_shards(table):
                yield from read_shard(sp)

        def score(imgs: np.ndarray, n: int, paths: list[str]):
            pad = self.batch - n
            if pad:
                imgs = np.concatenate(
                    [imgs[:n], np.zeros((pad, h, w, 3), np.float32)])
            dev = jax.device_put(imgs, self._sharding)  # local-mesh sharding
            logits = np.asarray(self._apply(dev))[:n]
            idx = np.argmax(logits, axis=-1)
            results.extend((p, self.model.classes[i]) for p, i in zip(paths, idx))

        if raw_u8:
            # Pre-decoded pixels (prep.materialize_decoded): no JPEG work,
            # just reinterpret + dequantize — the loader's fast path,
            # serving-side, through the same shared scheme definition.
            from ddw_tpu.data.loader import dequantize_raw_u8, raw_u8_view

            imgs = np.empty((self.batch, h, w, 3), np.float32)
            paths: list[str] = []
            i = 0
            for rec in records():
                imgs[i] = raw_u8_view(rec.content, h, w)
                paths.append(rec.path)
                i += 1
                if i == self.batch:
                    dequantize_raw_u8(imgs)
                    score(imgs, i, paths)
                    paths, i = [], 0
            if i:
                dequantize_raw_u8(imgs[:i])
                score(imgs, i, paths)
        else:
            # Double-buffered pipeline: one background thread decodes batch
            # N+1 (C++ pool, GIL released) while the device scores batch N —
            # per-batch wall time ~max(decode, score) instead of their sum,
            # the same overlap the training loader gets from prefetch_to.
            from concurrent.futures import ThreadPoolExecutor

            bufs = [np.empty((self.batch, h, w, 3), np.float32)
                    for _ in range(2)]

            def decode_into(contents: list[bytes], buf: np.ndarray) -> int:
                n = len(contents)
                _, ok = decode_batch_native(contents, h, w,
                                            threads=self.workers, out=buf[:n])
                for j in np.nonzero(~ok)[0]:
                    buf[j] = preprocess_image(contents[j], h, w)
                return n

            def batches():
                paths: list[str] = []
                contents: list[bytes] = []
                for rec in records():
                    paths.append(rec.path)
                    contents.append(rec.content)
                    if len(contents) == self.batch:
                        yield paths, contents
                        paths, contents = [], []
                if contents:
                    yield paths, contents

            with ThreadPoolExecutor(max_workers=1) as decoder:
                in_flight = None  # (future, buffer, paths) of the decoding batch
                for i, (paths, contents) in enumerate(batches()):
                    submitted = (decoder.submit(decode_into, contents,
                                                bufs[i % 2]),
                                 bufs[i % 2], paths)
                    if in_flight is not None:
                        fut, buf, prev_paths = in_flight
                        score(buf, fut.result(), prev_paths)
                    in_flight = submitted
                if in_flight is not None:
                    fut, buf, prev_paths = in_flight
                    score(buf, fut.result(), prev_paths)

        if out_store is not None:
            _write_scored_table(
                out_store, out_name,
                (Record(path=p, content=b"", label=pred)
                 for p, pred in results),
                {"model_classes": self.model.classes}, table,
                self.model.content_digest, merge)
        return results


class LMBatchScorer:
    """Score a ``tokens_i32`` table with a packaged LM over the local devices
    — per-sequence mean next-token NLL (the ``spark_udf`` scoring role for
    the language family; the tokens analog of :class:`BatchScorer`, same
    shared-nothing host split and run-token part merge)."""

    def __init__(self, model, mesh: Mesh | None = None,
                 batch_per_device: int = 64):
        from ddw_tpu.serving.lm_package import load_lm_package

        self.model = (load_lm_package(model) if isinstance(model, str)
                      else model)
        self.mesh = _local_mesh(mesh)
        self.batch = batch_per_device * self.mesh.devices.size
        self._sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        from ddw_tpu.serving.lm_package import sequence_nll

        pm = self.model
        self._nll = jax.jit(
            lambda tokens: sequence_nll(pm.model, pm.params, tokens),
            in_shardings=self._sharding,
            out_shardings=NamedSharding(self.mesh, P()))

    def score_table(self, table: Table, out_store: TableStore | None = None,
                    out_name: str = "lm_scores",
                    merge: bool = True) -> list[tuple[str, float]]:
        """Returns [(path, nll)] for this process's shard subset; with
        ``out_store`` also writes a scores table (label = formatted NLL,
        content = f32 bytes) and process 0 merges the per-process parts
        under the same run-token discipline as the image scorer."""
        if table.meta.get("encoding") != "tokens_i32":
            raise ValueError(f"LMBatchScorer needs a tokens_i32 table, got "
                             f"encoding {table.meta.get('encoding')!r} — "
                             f"materialize with prep.write_token_table")
        t = table.meta["seq_plus_one"]
        if t - 1 > self.model.lm_cfg.max_len:
            raise ValueError(f"table sequences ({t - 1}) exceed the packaged "
                             f"model's max_len {self.model.lm_cfg.max_len}")
        results: list[tuple[str, float]] = []
        buf = np.zeros((self.batch, t), np.int32)
        paths: list[str] = []

        from ddw_tpu.serving.lm_package import check_token_ids

        def flush():
            if not paths:
                return
            n = len(paths)
            buf[n:] = 0  # padded rows: valid ids, sliced off below
            check_token_ids(buf[:n], self.model.lm_cfg.vocab_size)
            dev = jax.device_put(buf, self._sharding)
            nll = np.asarray(self._nll(dev))[:n]
            results.extend((p, float(v)) for p, v in zip(paths, nll))
            paths.clear()

        for sp in _process_shards(table):
            for rec in read_shard(sp):
                buf[len(paths)] = np.frombuffer(rec.content, np.int32,
                                                count=t)
                paths.append(rec.path)
                if len(paths) == self.batch:
                    flush()
        flush()

        if out_store is not None:
            _write_scored_table(
                out_store, out_name,
                (Record(path=p, content=np.float32(v).tobytes(),
                        label=f"{v:.6f}") for p, v in results),
                {"metric": "mean_next_token_nll"}, table,
                self.model.content_digest, merge)
        return results


def merge_predictions(out_store: TableStore, out_name: str, n_parts: int,
                      run_id: str, timeout_s: float = 300.0) -> Table:
    """Merge per-process ``{out_name}_pN`` tables into one ``out_name`` table.

    The spark_udf contract yields ONE result table (reference
    ``03_pyfunc_distributed_inference.py:466-472``); per-part tables are an
    implementation detail of shared-nothing scoring. Waits for every part
    stamped with this run's token (:meth:`TableStore.await_parts` — a bare
    existence check would match a previous run's parts), then commits the
    merged table by zero-copy manifest concat.
    """
    part_names = [f"{out_name}_p{i}" for i in range(n_parts)]
    parts = out_store.await_parts(part_names, run_id, timeout_s)
    return out_store.merge_shards(
        out_name, parts,
        meta={**parts[0].meta, "merged_from": part_names, "run_id": run_id})
