"""Loader contract tests: shard selection, infinite repeat, static shapes,
prefetch-to-device (the Petastorm make_tf_dataset semantics, SURVEY §2b.8)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddw_tpu.data.loader import ShardedLoader


def _take(loader, n):
    it = iter(loader)
    return [next(it) for _ in range(n)]


def test_batch_shapes_and_dtypes(silver):
    train, _, _ = silver
    ld = ShardedLoader(train, batch_size=8, image_size=(32, 32), shuffle=False,
                       num_epochs=1, workers=2)
    imgs, lbls = _take(ld, 1)[0]
    assert imgs.shape == (8, 32, 32, 3) and imgs.dtype == np.float32
    assert lbls.shape == (8,) and lbls.dtype == np.int32
    assert 0 <= lbls.min() and lbls.max() < 5


def test_drop_remainder_static_shapes(silver):
    train, _, _ = silver
    ld = ShardedLoader(train, batch_size=7, image_size=(16, 16), shuffle=False,
                       num_epochs=1, workers=2)
    batches = list(iter(ld))
    assert len(batches) == train.num_records // 7
    assert all(b[0].shape == (7, 16, 16, 3) for b in batches)


def test_shard_disjoint_cover(silver):
    """Workers' record sets are disjoint and cover the table (petastorm
    cur_shard/shard_count role)."""
    train, _, _ = silver
    seen = []
    for rank in range(3):
        ld = ShardedLoader(train, batch_size=1, image_size=(8, 8), shuffle=False,
                           num_epochs=1, cur_shard=rank, shard_count=3, workers=1)
        # count labels as identity proxy: collect record count per worker
        seen.append(sum(1 for _ in iter(ld)))
    assert sum(seen) == train.num_records


def test_shard_plan_partition_exactness():
    """The elastic-shrink rebalance property: for ANY (n_shards, world),
    shard_plan is a partition — every shard index owned by exactly one
    worker — and re-deriving the plan at world-1 re-partitions the SAME
    shard set, so an N-1 epoch covers every sample exactly once (nothing
    stays orphaned on the evicted rank, nothing is read twice)."""
    for n_shards in (1, 2, 3, 7, 8, 16, 31):
        for world in (1, 2, 3, 4, 7, 8):
            plan = ShardedLoader.shard_plan(n_shards, world)
            assert len(plan) == world
            flat = [i for part in plan for i in part]
            assert sorted(flat) == list(range(n_shards))   # exactly once
            # matches the legacy slicing (resume streams stay identical)
            assert plan == [list(range(r, n_shards, world))
                            for r in range(world)]
    with pytest.raises(ValueError, match="shard_count"):
        ShardedLoader.shard_plan(4, 0)


def test_shard_rebalance_after_shrink_covers_table(silver):
    """End-to-end rebalance exactness on a real table: the records seen by
    3 workers and, re-derived after a shrink, by 2 workers are the SAME
    multiset — each a disjoint exact cover of the table."""
    train, _, _ = silver

    def epoch_counts(world):
        return [sum(1 for _ in iter(
            ShardedLoader(train, batch_size=1, image_size=(8, 8),
                          shuffle=False, num_epochs=1, cur_shard=r,
                          shard_count=world, workers=1)))
            for r in range(world)]

    assert sum(epoch_counts(3)) == train.num_records
    assert sum(epoch_counts(2)) == train.num_records   # the N-1 epoch


def test_infinite_repeat(silver):
    """num_epochs=None yields more batches than one pass holds (identical-step-count
    guarantee, reference 03_model_training_distributed.py:199-200)."""
    _, val, _ = silver
    one_pass = val.num_records // 4
    ld = ShardedLoader(val, batch_size=4, image_size=(8, 8), shuffle=True,
                       num_epochs=None, workers=2, shuffle_buffer=8)
    batches = _take(ld, one_pass + 3)
    assert len(batches) == one_pass + 3


def test_shuffle_determinism_and_epoch_variation(silver):
    train, _, _ = silver
    def labels_of(seed, n=6):
        ld = ShardedLoader(train, batch_size=8, image_size=(8, 8), shuffle=True,
                           seed=seed, num_epochs=None, workers=2, shuffle_buffer=32)
        return np.concatenate([b[1] for b in _take(ld, n)])

    a, b = labels_of(3), labels_of(3)
    c = labels_of(4)
    assert np.array_equal(a, b)          # seeded determinism
    assert not np.array_equal(a, c)      # seed changes order


def test_prefetch_to_device(silver):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    train, _, _ = silver
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    ld = ShardedLoader(train, batch_size=8, image_size=(16, 16), shuffle=False,
                       num_epochs=1, workers=2, prefetch_to=sharding)
    imgs, lbls = _take(ld, 1)[0]
    assert isinstance(imgs, jax.Array)
    assert imgs.sharding == sharding
    assert imgs.shape == (8, 16, 16, 3)


def test_steps_per_epoch_accounting(silver):
    """Global floor accounting (reference :350-351)."""
    train, _, _ = silver
    ld = ShardedLoader(train, batch_size=8, image_size=(8, 8), shard_count=2, cur_shard=0)
    assert ld.steps_per_epoch() == train.num_records // (8 * 2)


def test_materialized_table_matches_silver(silver, store):
    """Loader batches from a pre-decoded raw_u8 table equal the silver-table
    batches up to the uint8 quantization step (half-ULP of 2/255)."""
    from ddw_tpu.data.prep import materialize_decoded

    train_tbl, _, _ = silver
    gold = materialize_decoded(train_tbl, store, "gold_train", 32, 32,
                               shard_size=16)
    assert gold.meta["encoding"] == "raw_u8"
    assert gold.num_records == train_tbl.num_records

    kw = dict(batch_size=8, image_size=(32, 32), shuffle=False, workers=2)
    silver_batches = list(ShardedLoader(train_tbl, num_epochs=1, **kw))
    gold_batches = list(ShardedLoader(gold, num_epochs=1, **kw))
    assert len(gold_batches) == len(silver_batches) > 0
    for (gi, gl), (si, sl) in zip(gold_batches, silver_batches):
        np.testing.assert_array_equal(gl, sl)
        np.testing.assert_allclose(gi, si, atol=1.01 / 255)


def test_raw_u8_device_dequant_matches_host(silver, store):
    """Prefetching loader transfers uint8 + dequantizes ON DEVICE (4x smaller
    host->HBM transfer); output must match the host-dequantized f32 batches to
    1 ULP (XLA lowers /127.5 to multiply-by-reciprocal; numpy divides)."""
    from ddw_tpu.data.prep import materialize_decoded
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec
    from ddw_tpu.train.step import batch_sharding

    train_tbl, _, _ = silver
    gold = materialize_decoded(train_tbl, store, "gold_dev", 32, 32,
                               shard_size=16)
    mesh = make_mesh(MeshSpec((("data", 8),)))
    sharding = batch_sharding(mesh, "data")
    kw = dict(batch_size=8, image_size=(32, 32), shuffle=False)
    host_batches = list(ShardedLoader(gold, num_epochs=1, **kw))
    dev_batches = list(ShardedLoader(gold, num_epochs=1, prefetch_to=sharding,
                                     **kw))
    assert len(dev_batches) == len(host_batches) > 0
    for (di, dl), (hi, hl) in zip(dev_batches, host_batches):
        assert isinstance(di, jax.Array) and di.dtype == jnp.float32
        assert di.sharding == sharding
        np.testing.assert_array_equal(np.asarray(dl), hl)
        np.testing.assert_allclose(np.asarray(di), hi, rtol=0, atol=2.4e-7)


def test_materialized_table_size_mismatch_raises(silver, store):
    from ddw_tpu.data.prep import materialize_decoded

    train_tbl, _, _ = silver
    gold = materialize_decoded(train_tbl, store, "gold_mismatch", 32, 32,
                               shard_size=16)
    with pytest.raises(ValueError, match="materialized table size"):
        ShardedLoader(gold, batch_size=8, image_size=(64, 64))


@pytest.mark.slow  # ~8s; tier-1 reps: materialized_table_matches_silver
# (pixel identity) + raw_u8_device_dequant (device path) cover the cache
def test_materialized_training_is_drop_in(silver, store):
    """Trainer.fit on the materialized table tracks silver-table training
    epoch-for-epoch (the cache is a drop-in: same stream order, pixels within
    uint8 quantization)."""
    from ddw_tpu.data.prep import materialize_decoded
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec
    from ddw_tpu.train.trainer import Trainer
    from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg

    train_tbl, val_tbl, _ = silver
    gtrain = materialize_decoded(train_tbl, store, "gold_t2", 32, 32, 16)
    gval = materialize_decoded(val_tbl, store, "gold_v2", 32, 32, 16)
    data = DataCfg(img_height=32, img_width=32, shard_size=16)
    model = ModelCfg(name="small_cnn", num_classes=5, dropout=0.1,
                     dtype="float32")
    train = TrainCfg(batch_size=8, epochs=4, learning_rate=1e-3,
                     warmup_epochs=0)
    mesh = make_mesh(MeshSpec((("data", 8),)))
    silver_res = Trainer(data, model, train, mesh=mesh).fit(train_tbl, val_tbl)
    gold_res = Trainer(data, model, train, mesh=mesh).fit(gtrain, gval)
    assert gold_res.epochs_run == silver_res.epochs_run
    for g, s in zip(gold_res.history, silver_res.history):
        np.testing.assert_allclose(g["loss"], s["loss"], atol=0.05)
        np.testing.assert_allclose(g["val_loss"], s["val_loss"], atol=0.05)
    assert abs(gold_res.val_accuracy - silver_res.val_accuracy) <= 0.1


def test_token_table_loader(tmp_path):
    """tokens_i32 tables: the loader yields next-token pairs that exactly
    reconstruct the written corpus (unshuffled), shuffles deterministically
    by seed, and shard-selects disjointly by rank."""
    from ddw_tpu.data.loader import ShardedLoader
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore

    store = TableStore(str(tmp_path / "store"))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 99, size=(24, 9)).astype(np.int32)
    tbl = write_token_table(store, "toks", toks, shard_size=6)
    assert tbl.meta == {"encoding": "tokens_i32", "seq_plus_one": 9}

    def collect(**kw):
        rows = []
        for inp, tgt in ShardedLoader(tbl, batch_size=4, num_epochs=1,
                                      **kw):
            assert inp.shape == (4, 8) and tgt.shape == (4, 8)
            assert inp.dtype == np.int32 and tgt.dtype == np.int32
            np.testing.assert_array_equal(inp[:, 1:], tgt[:, :-1])
            rows.append(np.concatenate([inp, tgt[:, -1:]], axis=1))
        return np.concatenate(rows) if rows else np.empty((0, 9), np.int32)

    got = collect(shuffle=False)
    np.testing.assert_array_equal(got, toks)

    s1, s2 = collect(shuffle=True, seed=7), collect(shuffle=True, seed=7)
    np.testing.assert_array_equal(s1, s2)  # seeded shuffle is deterministic
    assert not np.array_equal(s1, toks)    # ...and actually shuffles

    a = collect(shuffle=False, cur_shard=0, shard_count=2)
    b = collect(shuffle=False, cur_shard=1, shard_count=2)
    assert len(a) + len(b) == len(toks)
    merged = {row.tobytes() for row in np.concatenate([a, b])}
    assert merged == {row.tobytes() for row in toks}


# -- a stream: one pass, at work from the moment it is opened ------------------
def _sharding(n=2):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:n]).reshape(n), ("data",))
    return NamedSharding(mesh, P("data"))


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


def _validation_loader(table, **kw):
    return ShardedLoader(table, batch_size=8, image_size=(16, 16),
                         shuffle=False, num_epochs=None, workers=2,
                         prefetch=2, **kw)


def test_an_opened_stream_works_before_it_is_asked(silver):
    """``open()`` starts the producer: with no ``next`` at all the queue
    fills to ``prefetch`` batches, the producer rests there, and ``ready()``
    reads the queue."""
    from ddw_tpu.obs.trace import Tracer

    tracer = Tracer(capacity=256)
    stream = _validation_loader(silver[0], prefetch_to=_sharding(),
                                tracer=tracer).open()
    def made():
        return sum(e["name"] == "loader_batch" for e in tracer.drain())

    try:
        assert _until(lambda: stream._q.full()) and stream.ready()
        assert stream._thread.is_alive()
        assert "(producer)" in stream._thread.name
        # two rest in the queue and one in the producer's hand: no more are
        # made while nobody asks
        assert _until(lambda: made() == 3)
        time.sleep(0.1)
        assert made() == 3
        imgs, _ = next(stream)
        assert isinstance(imgs, jax.Array) and imgs.shape == (8, 16, 16, 3)
        # one taken, one more made
        assert _until(lambda: made() == 4) and _until(stream._q.full)
        time.sleep(0.1)
        assert made() == 4
    finally:
        stream.close()
    assert not stream._thread.is_alive() and stream._q.empty()


@pytest.mark.parametrize("n", [1, 3, 5])
def test_a_stream_makes_num_batches_and_no_more(silver, n):
    """``num_batches``: the pass reads, decodes and transfers exactly that
    many batches (a ``loader_batch`` and a ``loader_h2d`` span each), the
    leading ones of the unbounded pass, then ends by itself."""
    from ddw_tpu.obs.trace import Tracer

    tracer = Tracer(capacity=256)
    stream = _validation_loader(silver[1], num_batches=n, tracer=tracer,
                                prefetch_to=_sharding()).open()
    got = [(np.asarray(i), np.asarray(l)) for i, l in stream]
    assert _until(lambda: not stream._thread.is_alive()) and not stream.ready()
    names = [e["name"] for e in tracer.drain() if e["tid"] == "loader"]
    assert names.count("loader_batch") == n == names.count("loader_h2d")
    # n = 5 wraps around the 12-record validation table, as the unbounded
    # host pass does
    want = _take(_validation_loader(silver[1]), n)
    assert len(got) == n
    for (gi, gl), (wi, wl) in zip(got, want):
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gl, wl)
    # the host pipeline alone honours the bound too
    assert len(list(_validation_loader(silver[1], num_batches=n))) == n


@pytest.mark.parametrize("how", ["close", "drop"])
def test_a_stream_left_early_leaves_nothing_behind(silver, how):
    """Closed with its queue full (``close`` waits for the producer) or
    dropped unclosed (the producer notices within one bounded put): no
    thread, no queued device batch."""
    import gc
    import weakref

    stream = _validation_loader(silver[0], prefetch_to=_sharding()).open()
    assert _until(lambda: stream._q.full())
    thread, q = stream._thread, stream._q
    if how == "close":
        stream.close()
        assert not thread.is_alive()
        with pytest.raises(StopIteration):
            next(stream)
    else:
        gone = weakref.ref(stream)
        del stream
        gc.collect()
        assert gone() is None       # the producer does not hold the stream
        assert _until(lambda: not thread.is_alive())
    assert q.empty()


def test_an_ended_stream_is_left_alone_when_collected(silver, monkeypatch):
    """What ``close`` released is not released again by ``__del__``: at
    interpreter exit, where a stream can outlive ``queue``'s names, an ended
    stream is collected without a word."""
    from ddw_tpu.data import loader as loader_mod

    stream = _validation_loader(silver[0], num_batches=1).open()
    assert len(list(stream)) == 1
    stream.close()

    def gone(q):
        raise TypeError("queue.Empty is gone")

    monkeypatch.setattr(loader_mod, "_drain", gone)
    stream.__del__()


def test_a_producers_error_reaches_the_consumer(silver, monkeypatch):
    def broken(self):
        raise OSError("shard unreadable")
        yield

    monkeypatch.setattr(ShardedLoader, "_iter_batches", broken)
    stream = _validation_loader(silver[0], prefetch_to=_sharding()).open()
    with pytest.raises(OSError, match="shard unreadable"):
        next(stream)
    stream.close()
    assert not stream._thread.is_alive()
