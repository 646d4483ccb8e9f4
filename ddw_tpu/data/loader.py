"""Per-host sharded loader — the Petastorm SparkDatasetConverter role.

The reference feeds training via Petastorm: a parquet cache materialized from the
table, then ``make_tf_dataset(batch_size, cur_shard=hvd.rank(),
shard_count=hvd.size(), num_epochs=None)`` with a reader thread pool
(``Part 1 - Distributed Training/03_model_training_distributed.py:137-144,200,332-337``).
Two semantics are load-bearing (SURVEY.md §2b.8, §7 hard-part 2):

- **shard selection by rank**: each worker reads a disjoint shard subset;
- **infinite repeat** (``num_epochs=None``): every worker can take the same floor
  -divided number of steps despite unequal shard sizes — the identical-step-count
  guarantee that under SPMD becomes "fixed shapes, same batch count on every host".

This loader reads ddw_tpu table shards directly (no intermediate cache: the store's
codec *is* the cache format), decodes/resizes JPEGs per batch in the native C++
pipeline (:mod:`ddw_tpu.native.decode` — libjpeg + std::thread pool, one GIL
release per batch — the tf.data/petastorm worker-pool role), and prefetches
batches to device HBM on a background thread (double buffering), so the TPU
never waits on host IO. That thread belongs to a :class:`LoaderStream`, one
pass over the loader's batches, and works from the moment the stream is
opened: a pass that will be asked for later (an epoch's validation batches)
is opened ahead and found waiting.

Preprocessing is THE shared implementation for training and serving —
:func:`preprocess_image` is the single decode path ``ddw_tpu.serving`` packages with
models — deliberately fixing the reference's train/serve skew (tf.image in training,
``02_model_training_single_node.py:119-126``, vs PIL at inference,
``03_pyfunc_distributed_inference.py:231-234``).
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from io import BytesIO
from typing import Iterator

import numpy as np

from ddw_tpu.data.store import Table, read_shard_contents
from ddw_tpu.obs.trace import span_lane


def bounded_map(pool: ThreadPoolExecutor, fn, iterable, window: int):
    """Ordered parallel map with a bounded in-flight window.

    ``Executor.map`` eagerly submits the whole iterable (decoding an entire shard
    set into memory); this keeps at most ``window`` items pending. Shared by the
    training loader and the batch scorer."""
    from collections import deque

    pending: deque = deque()
    for item in iterable:
        pending.append(pool.submit(fn, item))
        if len(pending) >= window:
            yield pending.popleft().result()
    while pending:
        yield pending.popleft().result()


def _preprocess_image_pil(content: bytes, height: int, width: int) -> np.ndarray:
    from PIL import Image

    img = Image.open(BytesIO(content))
    # JPEG DCT-scaled decode (decode directly at 1/2, 1/4, 1/8 scale when the
    # source is larger than the target) — the same trick the native pipeline's
    # libjpeg scale_denom uses; no-op for non-JPEG or already-small images.
    img.draft("RGB", (width, height))
    if img.mode != "RGB":
        img = img.convert("RGB")
    img = img.resize((width, height), Image.BILINEAR)
    arr = np.asarray(img, dtype=np.float32)
    return arr / 127.5 - 1.0


def raw_u8_view(content: bytes, height: int, width: int) -> np.ndarray:
    """Reinterpret a ``raw_u8`` record (prep.materialize_decoded) as a
    [H, W, 3] uint8 array — zero-copy view over the record bytes."""
    return np.frombuffer(content, np.uint8).reshape(height, width, 3)


def dequantize_raw_u8(batch: np.ndarray) -> None:
    """In-place inverse of materialize_decoded's quantization: a float batch
    holding uint8 pixel values becomes [-1, 1]. THE single definition of the
    raw_u8 scheme — loader, batch scorer and feature cache all call this, so a
    change to the quantization can never reintroduce train/serve skew (the
    bug class ``preprocess_image`` exists to prevent on the JPEG path).
    :func:`dequantize_raw_u8_device` is the jit-side twin — change BOTH or
    the equivalence test fails."""
    batch /= 127.5
    batch -= 1.0


def dequantize_raw_u8_device(x):
    """The same scheme as a jittable device op (u8 -> f32 in [-1, 1]).

    The prefetching loader transfers raw uint8 batches and dequantizes ON
    DEVICE: 4x fewer bytes over host->HBM (the usual input-pipeline
    bottleneck); the cast+scale then runs as one tiny fused device program on
    the prefetch thread, overlapped with training like the transfer itself.
    Same arithmetic as :func:`dequantize_raw_u8` up to 1 ULP (XLA lowers the
    divide to multiply-by-reciprocal), pinned by
    ``test_loader.py::test_raw_u8_device_dequant_matches_host``."""
    import jax.numpy as jnp

    return x.astype(jnp.float32) / 127.5 - 1.0


_DEQUANT_JIT = None


def _dequant_jitted():
    """Process-wide jitted dequantize — one compilation shared by every loader
    stream (a fresh validation pass per epoch must not re-trace)."""
    global _DEQUANT_JIT
    if _DEQUANT_JIT is None:
        import jax

        _DEQUANT_JIT = jax.jit(dequantize_raw_u8_device)
    return _DEQUANT_JIT


def active_decoder() -> str:
    """Which decode impl :func:`preprocess_image` uses: ``native`` (the libjpeg
    pipeline; it builds or raises). Serving packages record this at save time
    and warn when they were trained under another decoder (packages written by
    earlier builds may say ``pil``)."""
    return "native"


def preprocess_image(content: bytes, height: int, width: int) -> np.ndarray:
    """JPEG bytes -> float32 [H, W, 3] in [-1, 1].

    decode -> resize (bilinear) -> MobileNetV2-style scaling ``x/127.5 - 1``
    (the ``tf.image.decode_jpeg`` + ``resize`` + ``preprocess_input`` chain,
    reference ``02_model_training_single_node.py:119-126``). Single
    implementation shared by the training loader and the packaged model's
    predict path: the native libjpeg pipeline (:mod:`ddw_tpu.native.decode` —
    point-sampled bilinear, the ``tf.image.resize`` semantics of the
    reference), with PIL (area-filtered bilinear) for an image libjpeg
    refuses. Both sides of train/serve go through this same dispatch.
    """
    from ddw_tpu.native.decode import decode_one_native

    out = decode_one_native(content, height, width)
    if out is not None:
        return out
    return _preprocess_image_pil(content, height, width)


class ShardedLoader:
    """Iterate (images, labels) batches from a table, sharded by worker rank.

    Args:
      table: silver table with ``label_idx`` set.
      batch_size: per-worker batch size (reference semantics — global batch is
        ``batch_size * shard_count``).
      image_size: (height, width).
      cur_shard / shard_count: worker rank / world size (``make_tf_dataset``
        parameters, reference ``:332-337``). Defaults to 0/1 (single worker).
      num_epochs: None = infinite repeat (training default, reference ``:199-200``);
        an int for finite passes (eval).
      num_batches: None, or the number of batches after which a pass over
        the loader ends whatever ``num_epochs`` still holds: a validation
        pass of ``val_steps`` batches reads, decodes and transfers those and
        no more. Counted before ``super_batch`` stacks them.
      shuffle: shuffle shard order and a record-level buffer, seeded; epoch-varying.
      drop_remainder: keep shapes static for XLA (always True under jit).
      workers: decode thread pool size (petastorm ``workers_count`` role, ``:200``).
      prefetch_to: optional ``jax.sharding.Sharding`` — batches are transferred to
        device(s) on a background thread, ``prefetch`` deep.
      skip_records: fast-forward the (deterministic, seeded) record stream this
        many records before the first batch — exact resume of a consumed-batch
        position without decoding the skipped images. A trainer that consumed
        ``k`` batches before checkpointing resumes the identical stream with
        ``skip_records = k * batch_size``.
      super_batch: fused-dispatch super-batches (``TrainCfg.steps_per_dispatch``):
        an int K or a cyclic plan tuple (``ddw_tpu.train.step.chain_plan`` —
        e.g. ``(K, K, tail)`` covering one epoch). Successive already-
        transferred batches are stacked ON DEVICE on the prefetch thread into
        ``[k, B, ...]`` arrays (chain dim unsharded), so host->HBM bytes are
        exactly the per-batch path's — only the Python dispatch granularity
        changes. Requires ``prefetch_to``; ``None``/all-ones means plain
        per-step batches.
      tracer: optional :class:`ddw_tpu.obs.trace.Tracer` (the trainers pass
        their own). The prefetch thread then records, a batch, on
        ``tid="loader"``: ``loader_batch`` (read, decode and assemble on the
        host), ``loader_h2d`` (the transfer call and the ``raw_u8``
        dequantise dispatch) and, only when the queue was full,
        ``loader_blocked`` — the time the loader was ahead.
    """

    def __init__(
        self,
        table: Table,
        batch_size: int,
        image_size: tuple[int, int] = (224, 224),
        cur_shard: int = 0,
        shard_count: int = 1,
        num_epochs: int | None = None,
        num_batches: int | None = None,
        shuffle: bool = True,
        seed: int = 0,
        shuffle_buffer: int = 1024,
        workers: int = 4,
        prefetch: int = 2,
        prefetch_to=None,
        skip_records: int = 0,
        super_batch=None,
        tracer=None,
    ):
        if not 0 <= cur_shard < shard_count:
            raise ValueError(f"cur_shard {cur_shard} out of range for shard_count {shard_count}")
        if super_batch is not None:
            plan = ((int(super_batch),) if isinstance(super_batch, int)
                    else tuple(int(k) for k in super_batch))
            if not plan or any(k < 1 for k in plan):
                raise ValueError(f"super_batch must be a positive int or a "
                                 f"tuple of positive chain lengths, got "
                                 f"{super_batch!r}")
            if all(k == 1 for k in plan):
                plan = None  # K=1 everywhere: plain per-step batches
            elif prefetch_to is None:
                # refuse-loudly: the super-batch contract is DEVICE-side
                # stacking on the prefetch thread; silently stacking on host
                # would 1:1 change the H2D transfer granularity it promises
                # not to touch
                raise ValueError("super_batch needs prefetch_to (batches are "
                                 "stacked on device on the prefetch thread)")
            self._super_plan = plan
        else:
            self._super_plan = None
        self.table = table
        self.batch_size = batch_size
        self.height, self.width = image_size
        self.cur_shard = cur_shard
        self.shard_count = shard_count
        self.num_epochs = num_epochs
        self.num_batches = num_batches
        self.shuffle = shuffle
        self.seed = seed
        self.shuffle_buffer = shuffle_buffer
        self.workers = workers
        self.prefetch = prefetch
        self.prefetch_to = prefetch_to
        self.skip_records = skip_records
        self.tracer = tracer

        # Cached-feature table (train.transfer.materialize_features): content is
        # the frozen backbone's pooled feature vector (f32 bytes); batches are
        # (B, feature_dim) — the loader feeds a head-only model.
        self._feature_dim = (table.meta.get("feature_dim")
                             if table.meta.get("encoding") == "features_f32"
                             else None)

        # Token table (prep.write_token_table): content is an int32 [S+1]
        # sequence; batches are next-token pairs (inputs, targets) for the
        # LM family — a memcpy per record, no image work.
        self._token_len = (table.meta.get("seq_plus_one")
                           if table.meta.get("encoding") == "tokens_i32"
                           else None)

        # Pre-decoded table (prep.materialize_decoded): content is raw uint8
        # [H, W, 3] pixels; batches come from a memcpy + scale, no JPEG work.
        self._raw_u8 = table.meta.get("encoding") == "raw_u8"
        if self._raw_u8:
            th, tw = table.meta["height"], table.meta["width"]
            if (th, tw) != (self.height, self.width):
                raise ValueError(
                    f"loader image_size {(self.height, self.width)} != "
                    f"materialized table size {(th, tw)} — re-materialize or "
                    f"match DataCfg.img_height/img_width")
            # The record-count shuffle buffer was sized for ~KB JPEG records;
            # raw_u8 records are H*W*3 bytes (150 KB at 224²), so bound the
            # buffer by bytes (64 MB) instead of pinning shuffle_buffer
            # records of decoded pixels in host RAM.
            record_bytes = th * tw * 3
            self.shuffle_buffer = max(
                2, min(self.shuffle_buffer, (64 << 20) // record_bytes))

        shards = list(table.shard_paths)
        if len(shards) >= shard_count:
            # Shard-level selection (petastorm semantics): disjoint round-robin.
            plan = self.shard_plan(len(shards), shard_count)
            self._my_shards = [shards[i] for i in plan[cur_shard]]
            self._record_stride = None
        else:
            # Fewer shards than workers: fall back to record-level modulo sharding
            # (the reference instead repartitions >= worker count,
            # ``03_model_training_distributed.py:110-111``; prep normally makes
            # enough shards, this keeps small tables correct).
            self._my_shards = shards
            self._record_stride = (cur_shard, shard_count)

    @staticmethod
    def shard_plan(n_shards: int, shard_count: int) -> list[list[int]]:
        """Round-robin assignment of ``n_shards`` table shards to
        ``shard_count`` workers: worker ``r`` owns shard indices
        ``range(r, n_shards, shard_count)``. The plan is a partition — every
        shard index appears in exactly one worker's list — which is what makes
        an elastic shrink (re-deriving loaders at world size N−1) cover every
        sample exactly once per epoch: the N−1 plan re-partitions the same
        shard set, leaving no shard orphaned on the evicted rank."""
        if shard_count < 1:
            raise ValueError(f"shard_count must be >= 1, got {shard_count}")
        return [list(range(r, n_shards, shard_count)) for r in range(shard_count)]

    # -- sizing ----------------------------------------------------------------
    @property
    def records_per_worker(self) -> int:
        """Lower-bound records this worker owns (for step accounting; the trainer
        uses the *global* table size // (batch * world), reference ``:350-351``)."""
        if self._record_stride is None:
            # exact: manifest carries per-shard counts
            counts = {m["file"]: m["num_records"] for m in self.table.manifest["shards"]}
            import os

            return sum(counts[os.path.basename(p)] for p in self._my_shards)
        n, (r, k) = self.table.num_records, self._record_stride
        return n // k + (1 if r < n % k else 0)

    def steps_per_epoch(self) -> int:
        """Global-size floor accounting: ``table_size // (batch * shard_count)``
        (reference ``03_model_training_distributed.py:350-351``)."""
        return max(1, self.table.num_records // (self.batch_size * self.shard_count))

    # -- host pipeline ---------------------------------------------------------
    def _iter_raw(self) -> Iterator[tuple[bytes, int]]:
        """Infinite (or num_epochs-bounded) stream of raw (content, label_idx)
        records for this worker, with epoch-varying shard shuffle + record-level
        shuffle buffer. Shuffling raw bytes (not decoded arrays) keeps the
        buffer ~KB/record instead of ~MB/record."""
        epoch = 0
        while self.num_epochs is None or epoch < self.num_epochs:
            rng = np.random.RandomState((self.seed * 100003 + epoch * 7919 + self.cur_shard) & 0x7FFFFFFF)
            shards = list(self._my_shards)
            if self.shuffle:
                rng.shuffle(shards)

            def records():
                for sp in shards:
                    if self._record_stride is None:
                        yield from read_shard_contents(sp)
                    else:
                        r, k = self._record_stride
                        for i, entry in enumerate(read_shard_contents(sp)):
                            if i % k == r:
                                yield entry

            if not self.shuffle:
                yield from records()
            else:
                buf = []
                for item in records():
                    buf.append(item)
                    if len(buf) >= self.shuffle_buffer:
                        j = rng.randint(len(buf))
                        buf[j], buf[-1] = buf[-1], buf[j]
                        yield buf.pop()
                rng.shuffle(buf)
                yield from buf
            epoch += 1

    def _iter_raw_resumed(self) -> Iterator[tuple[bytes, int]]:
        """The raw stream, fast-forwarded ``skip_records`` records. Skipping
        advances the shuffle RNG identically to consuming, so the resumed
        stream is byte-for-byte the continuation of the original one; skipped
        records are never decoded (raw-bytes cost only)."""
        it = self._iter_raw()
        for _ in range(self.skip_records):
            next(it)
        return it

    def _iter_batches(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        from ddw_tpu.native.decode import decode_batch_native

        if self._token_len:
            # Token fast path: yield next-token pairs [B, S] — the LM step's
            # exact (inputs, targets) contract.
            t = self._token_len
            toks = np.empty((self.batch_size, t), np.int32)
            i = 0
            for content, _ in self._iter_raw_resumed():
                toks[i] = np.frombuffer(content, np.int32, count=t)
                i += 1
                if i == self.batch_size:
                    yield toks[:, :-1].copy(), toks[:, 1:].copy()
                    i = 0
            return  # drop remainder: static shapes for XLA

        if self._feature_dim:
            # Cached-feature fast path: batches are (B, D) f32 vectors — a
            # memcpy per record, no image work at all.
            d = self._feature_dim
            feats = np.empty((self.batch_size, d), np.float32)
            flbls = np.empty((self.batch_size,), np.int32)
            i = 0
            for content, label_idx in self._iter_raw_resumed():
                feats[i] = np.frombuffer(content, np.float32, count=d)
                flbls[i] = label_idx
                i += 1
                if i == self.batch_size:
                    yield feats.copy(), flbls.copy()
                    i = 0
            return  # drop remainder: static shapes for XLA

        lbls = np.empty((self.batch_size,), np.int32)

        if self._raw_u8:
            # Materialized fast path: reinterpret + dequantize, no JPEG work.
            # With a device prefetcher downstream, batches stay uint8 (pure
            # memcpy here; 4x smaller host->device transfer) and the
            # dequantize runs on device (see open/transfer).
            device_side = self.prefetch_to is not None
            buf = np.empty((self.batch_size, self.height, self.width, 3),
                           np.uint8 if device_side else np.float32)
            i = 0
            for content, label_idx in self._iter_raw_resumed():
                buf[i] = raw_u8_view(content, self.height, self.width)
                lbls[i] = label_idx
                i += 1
                if i == self.batch_size:
                    if not device_side:
                        dequantize_raw_u8(buf)
                    yield buf.copy(), lbls.copy()
                    i = 0
            return  # drop remainder: static shapes for XLA

        imgs = np.empty((self.batch_size, self.height, self.width, 3), np.float32)

        # One C++ thread-pool call per batch (one GIL release, real OS-thread
        # decode parallelism); an image libjpeg refuses is re-decoded by PIL.
        # A library that cannot be built raises here (native/build.py) — the
        # loader has no slower path to drop to.
        contents: list[bytes] = []
        for content, label_idx in self._iter_raw_resumed():
            lbls[len(contents)] = label_idx
            contents.append(content)
            if len(contents) == self.batch_size:
                _, ok = decode_batch_native(
                    contents, self.height, self.width,
                    threads=self.workers, out=imgs)
                for j in np.nonzero(~ok)[0]:
                    imgs[j] = _preprocess_image_pil(
                        contents[j], self.height, self.width)
                yield imgs.copy(), lbls.copy()
                contents = []
        # drop remainder: static shapes for XLA

    def _host_batches(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """The host pipeline's batches, ``num_batches`` of them at most."""
        return itertools.islice(self._iter_batches(), self.num_batches)

    def __iter__(self):
        """The batches: with ``prefetch_to`` the stream of :meth:`open`, at
        work from this call; without it the host pipeline, which does its
        work as it is asked. The host-only path stays lazy and threadless
        because its users iterate it in place and often leave early without
        a close (``tools/loader_bench.py``'s host leg, the loader's tests,
        ``islice`` over an endless loader): only a caller that wants the
        host's batches made AHEAD, as the epoch loop's validation does, asks
        for :meth:`open`."""
        if self.prefetch_to is None:
            return self._host_batches()
        return self.open()

    def open(self) -> "LoaderStream":
        """A new pass over the loader's batches whose producer thread is at
        work when this returns: it runs the host pipeline and the transfer
        until ``prefetch`` batches wait in its queue, rests there, and ends
        with the batches (``num_batches``, or the records). Without
        ``prefetch_to`` the queue holds the host's batches as they are."""
        import jax

        transfer = None
        if self.prefetch_to is not None:
            multihost = jax.process_count() > 1
            # raw_u8 tables arrive as uint8 (4x smaller transfer); dequantize
            # on device — one process-wide compilation (_dequant_jitted).
            dequant = _dequant_jitted() if self._raw_u8 else None

            def transfer(imgs, lbls):
                if multihost:
                    # Per-host local batches assemble into one global sharded
                    # array (global batch = local batch * process_count along
                    # dim 0).
                    imgs = jax.make_array_from_process_local_data(
                        self.prefetch_to, imgs)
                    lbls = jax.make_array_from_process_local_data(
                        self.prefetch_to, lbls)
                else:
                    imgs, lbls = jax.device_put((imgs, lbls),
                                                self.prefetch_to)
                if dequant is not None:
                    imgs = dequant(imgs)
                return imgs, lbls

        stack_fn = None
        if self._super_plan is not None:
            # Device-side super-batch stacking (steps_per_dispatch): K
            # already-transferred batches concatenate into [k, B, ...] with
            # the chain dim unsharded — one tiny fused device program per
            # chain, on the prefetch thread like the transfer itself. Jitted
            # once per distinct k (at most two: full chain + trailing tail).
            from jax.sharding import NamedSharding, PartitionSpec

            mesh = getattr(self.prefetch_to, "mesh", None)
            spec = getattr(self.prefetch_to, "spec", None)
            if mesh is None or spec is None:
                raise ValueError(
                    f"super_batch needs a NamedSharding prefetch_to to derive "
                    f"the stacked [k, B, ...] sharding, got "
                    f"{type(self.prefetch_to).__name__}")
            sup_sh = NamedSharding(mesh, PartitionSpec(None, *spec))
            stack_fn = jax.jit(
                lambda g: jax.tree.map(lambda *xs: jax.numpy.stack(xs), *g),
                out_shardings=(sup_sh, sup_sh))
        return LoaderStream(self, transfer, stack_fn)

    def _produce(self, q: queue.Queue, stop: threading.Event, transfer,
                 stack_fn) -> None:
        """The producer thread of one stream: batches into ``q`` until they
        end or ``stop`` is set, then the end mark; an exception goes to the
        consumer through the queue. Holds the loader, the queue and the
        event and never the stream, so a dropped stream is collected."""
        sp = span_lane(self.tracer, "data", "loader")

        def put_or_stop(item) -> bool:
            # Never block forever on a full queue: a closed or dropped stream
            # sets `stop`; re-check it between bounded put attempts so the
            # thread can exit.
            if stop.is_set():
                return False
            try:
                q.put_nowait(item)
                return True
            except queue.Full:
                t_full = time.monotonic()
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    sp.span("loader_blocked", t_full, time.monotonic())
                    return True
                except queue.Full:
                    continue
            return False

        def transferred():
            """Batches on the device until the stream ends or the consumer
            is gone; one stamp a boundary, so a batch's host work ends where
            its transfer starts."""
            batches = self._host_batches()
            while True:
                t0 = time.monotonic()
                try:
                    item = next(batches)
                except StopIteration:
                    return
                t1 = time.monotonic()
                sp.span("loader_batch", t0, t1)
                if stop.is_set():
                    return
                if transfer is not None:
                    item = transfer(*item)
                    sp.span("loader_h2d", t1, time.monotonic())
                yield item

        plan = self._super_plan
        try:
            if plan is None:
                for item in transferred():
                    if not put_or_stop(item):
                        return
            else:
                group: list = []
                ci = 0
                for item in transferred():
                    group.append(item)
                    if len(group) == plan[ci % len(plan)]:
                        if not put_or_stop(stack_fn(tuple(group))):
                            return
                        group = []
                        ci += 1
                # finite stream: a trailing incomplete group is dropped
                # (drop_remainder semantics at chain granularity)
            put_or_stop(_END)
        except Exception as e:  # surface errors on the consumer side
            put_or_stop(e)
        finally:
            if stop.is_set():
                # a put that was waiting when the consumer left may have
                # landed after the consumer's own drain
                _drain(q)


_END = object()     # after a stream's last batch


def _drain(q: queue.Queue) -> None:
    """Empty ``q`` so device-resident batches are released promptly."""
    while True:
        try:
            q.get_nowait()
        except queue.Empty:
            return


class LoaderStream:
    """One pass over a :class:`ShardedLoader`'s batches (``loader.open()``):
    an iterator whose producer thread works from construction, at most
    ``prefetch`` batches ahead of the consumer. ``ready()`` says whether a
    ``next`` now would be served from the queue without waiting; ``close()``
    stops the producer, waits for it and releases what it had queued. A
    stream that is dropped unclosed stops its producer too, without the
    wait."""

    def __init__(self, loader: ShardedLoader, transfer, stack_fn):
        self._q: queue.Queue = queue.Queue(maxsize=loader.prefetch)
        self._stop = threading.Event()
        self._ended = False
        self._thread = threading.Thread(
            target=loader._produce, name="loader (producer)", daemon=True,
            args=(self._q, self._stop, transfer, stack_fn))
        self._thread.start()

    def ready(self) -> bool:
        return not self._q.empty()

    def __iter__(self):
        return self

    def __next__(self):
        if self._ended:
            raise StopIteration
        item = self._q.get()
        if item is _END or isinstance(item, Exception):
            self._release()
            if item is _END:
                raise StopIteration
            raise item
        return item

    def _release(self) -> None:
        self._ended = True
        self._stop.set()
        _drain(self._q)

    def close(self) -> None:
        self._release()
        # the producer looks at `stop` after every batch and every 0.1 s of
        # a full queue, and drains what it put after the line above
        self._thread.join()

    def __del__(self):
        # no join: at interpreter exit a daemon thread may never run again;
        # an ended stream has nothing left to release (and at exit no
        # `queue.Empty` to drain with)
        if not self._ended:
            self._release()
