"""KV block migration (serve/blocks.py wire format) + disaggregated
prefill/decode (EngineCfg.role, the router's TTFT-aware splitter).

The acceptance pins, all deterministic on the 8-fake-CPU-device backend:

- **wire round-trip is bit-exact**: export → import into a cold pool →
  re-export reproduces the ORIGINAL wire byte-for-byte (base64 payload
  equality IS K/V byte identity), fuzzed across block-boundary prompt
  lengths; a second import dedupes (``skipped``), and sub-block prompts
  export ``None`` (nothing worth migrating);
- **the prefix directory names skip blocks**: ``skip_hashes`` ships a
  warm prefix hash-only (``start_block`` > 0, shorter payload) and the
  receiver — already holding that prefix — lands only the tail, after
  which its re-export matches the donor's full wire;
- **rejection is atomic**: version / block-size / geometry / hash-chain /
  truncation defects each raise a structured ``KVWireError`` BEFORE the
  pool changes at all (free blocks, registered hashes, gauges pinned
  before/after), an over-budget import raises ``OutOfBlocks`` equally
  unchanged, and the same pool still lands the clean wire afterwards;
- **equal-tp transfer**: tp=2 → tp=2 round-trips bit-exactly under the
  model-axis mesh, and the SAME wire lands in a tp=1 pool (payloads are
  full-shape; ``tp`` on the wire is advisory) — layout-independence;
- **disaggregation is invisible in tokens**: a prefill-role + decode-role
  ReplicaSet answers bit-identically to the sequential path, greedy AND
  seeded, THROUGH out-of-blocks mid-decode preemption on the decode
  replica and an in-place prefill-replica restart (handoffs resume with
  fresh migrations); the prefill replica never runs a decode tick, warm
  repeats migrate zero blocks, and handoffs / kv_blocks_migrated /
  kv_bytes_migrated / handoff_ms flow through the fleet snapshot;
- **role config + match(with_hashes)**: structured EngineCfg.role errors
  at construction; PrefixIndex.match returns the chain-hex transfer
  directory alongside matches (pure, no jax).

Tier-1 cost discipline: pool-level tests pad suffix prefills to ONE
shape (one compiled program per pool), the disagg drills share one
module-scoped 2-replica fleet, and the process-level disagg chaos drill
(supervisor restart of a crashed prefill replica under DDW_FAULT) rides
tools/load_gen.py --disagg / tier-2 with the other process-fleet boots.
"""

import json
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from ddw_tpu.gateway import PrefixIndex, ReplicaSet, chain_hash_hexes
from ddw_tpu.models.lm import build_lm
from ddw_tpu.runtime.mesh import MODEL_AXIS
from ddw_tpu.serve import BlockPool, EngineCfg, ServingEngine
from ddw_tpu.serve.blocks import KV_WIRE_VERSION, KVWireError, OutOfBlocks
from ddw_tpu.serving.lm_package import load_lm_package, save_lm_package
from ddw_tpu.utils.config import LMCfg

VOCAB = 64
BS = 8          # kv_block_size under test (divides tile = min(256, 96))
PAD = 40        # one suffix-prefill shape for every pool-level seed


def _lm_pkg(out_dir, seed=0):
    cfg = LMCfg(vocab_size=VOCAB, max_len=96, hidden=32, depth=2,
                num_heads=2, mlp_dim=64, dropout=0.0, dtype="float32")
    model = build_lm(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        np.zeros((1, 8), np.int32))["params"]
    d = save_lm_package(str(out_dir), cfg, params, quantize=None)
    return load_lm_package(d)


@pytest.fixture(scope="module")
def pm(tmp_path_factory):
    return _lm_pkg(tmp_path_factory.mktemp("kv_mig_pkg") / "pkg")


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, size=(n,)).astype(np.int32)
            for n in lengths]


def _pool(pm, n_blocks=32, block_size=BS, max_resident=2, mesh=None):
    return BlockPool(pm.model, pm.params, n_blocks=n_blocks,
                     block_size=block_size, max_resident=max_resident,
                     steps_per_tick=1, decode_buckets=False, mesh=mesh)


def _seed(pool, p):
    """Prefill + register + release ``p`` so its full blocks are parked
    registered in the cached LRU — the donor state export reads. One PAD
    shape keeps the whole module on a single compiled prefill program."""
    row, _ = pool.admit(p, 2)
    suf = np.zeros((1, PAD), np.int32)
    suf[0, :len(p)] = p
    pool.prefill([row], suf, np.array([len(p)], np.int32),
                 np.zeros((1,), np.float32), np.zeros((1, 2), np.uint32))
    pool.register(row, p)
    pool.note_prefilled(row)
    pool.release(row)


def _state(pool):
    """The atomicity witness: anything an import could touch."""
    g = pool.gauges()
    return (pool.free_blocks_effective, len(pool._full_map),
            g["blocks_used"], g["blocks_cached"], g["blocks_free"])


# -- wire round-trip ----------------------------------------------------------

def test_wire_roundtrip_fuzz_across_block_boundaries(pm):
    """export → cold import → re-export is byte-identical for prompt
    lengths straddling every block boundary; re-import dedupes."""
    donor = _pool(pm)
    for n, p in zip([BS - 1, BS, BS + 1, 2 * BS, 3 * BS - 1, 3 * BS],
                    _prompts([BS - 1, BS, BS + 1, 2 * BS, 3 * BS - 1,
                              3 * BS], seed=3)):
        _seed(donor, p)
        wire = donor.export_blocks(p)
        full = n // BS
        if full == 0:
            assert wire is None      # sub-block: nothing worth migrating
            continue
        assert wire["version"] == KV_WIRE_VERSION
        assert wire["block_size"] == BS and wire["start_block"] == 0
        assert len(wire["hashes"]) == full == len(wire["payload"])
        assert wire["tokens"] == [int(t) for t in p[:full * BS]]
        recv = _pool(pm)
        res = recv.import_blocks(wire)
        assert res == {"imported": full, "skipped": 0,
                       "bytes": res["bytes"]} and res["bytes"] > 0
        # re-export from the receiver: the SAME wire, byte for byte
        # (base64 payload equality is K/V byte identity)
        assert recv.export_blocks(p) == wire
        # a second import is a pure dedupe — nothing lands twice
        assert recv.import_blocks(wire) == {"imported": 0, "skipped": full,
                                            "bytes": 0}


def test_skip_hashes_ship_warm_prefix_hash_only(pm):
    """The transfer directory's contract: blocks the receiver already
    holds cross the wire as hashes alone, and the landed tail completes
    the chain — the receiver's re-export equals the donor's FULL wire."""
    (p,) = _prompts([3 * BS], seed=5)
    donor = _pool(pm)
    _seed(donor, p)
    full = donor.export_blocks(p)
    assert len(full["payload"]) == 3
    skip = full["hashes"][:1]
    thin = donor.export_blocks(p, skip_hashes=skip)
    assert thin["start_block"] == 1 and len(thin["payload"]) == 2
    assert thin["hashes"] == full["hashes"]   # chain still fully named
    # receiver holds exactly the skipped prefix warm already
    recv = _pool(pm)
    _seed(recv, p[:BS + 1])                   # one full block registered
    res = recv.import_blocks(thin)
    assert res["imported"] == 2 and res["skipped"] == 0
    assert res["bytes"] > 0
    assert recv.export_blocks(p) == full


def test_rejection_is_structured_and_atomic(pm):
    """Every malformed wire raises KVWireError BEFORE the pool changes;
    an over-budget import raises OutOfBlocks equally unchanged; the same
    pool still lands the clean wire afterwards (never poisoned)."""
    (p,) = _prompts([3 * BS], seed=7)
    donor = _pool(pm)
    _seed(donor, p)
    wire = donor.export_blocks(p)
    recv = _pool(pm)

    def corrupt(**mut):
        w = json.loads(json.dumps(wire))   # deep copy, JSON-clean by spec
        w.update(mut)
        return w

    bad_tokens = list(wire["tokens"])
    bad_tokens[BS + 2] ^= 1
    short_leaf = corrupt()
    short_leaf["payload"][1][0] = short_leaf["payload"][1][0][:8]
    thin_row = corrupt()
    thin_row["payload"][0] = thin_row["payload"][0][:-1]
    cases = [
        ("version", corrupt(version=KV_WIRE_VERSION + 1)),
        ("block_size", corrupt(block_size=BS * 2)),
        ("leaf geometry", corrupt(leaves=[[s, d] for s, d in
                                          [( [1, 2, 3], "float32")]])),
        ("chain hash mismatch", corrupt(tokens=bad_tokens)),
        ("token list length", corrupt(tokens=wire["tokens"][:-1])),
        ("truncated payload", corrupt(payload=wire["payload"][:-1])),
        ("truncated leaf payload", short_leaf),
        ("truncated payload row", thin_row),
        ("start_block", corrupt(start_block=7)),
        ("must be a dict", "not-a-wire"),
        ("no chain hashes", corrupt(hashes=[])),
    ]
    before = _state(recv)
    for why, bad in cases:
        with pytest.raises(KVWireError):
            recv.import_blocks(bad)
        assert _state(recv) == before, why
    # over-budget: validation passes, capacity check refuses PRE-landing
    tiny = _pool(pm, n_blocks=2, max_resident=1)
    t_before = _state(tiny)
    with pytest.raises(OutOfBlocks):
        tiny.import_blocks(wire)
    assert _state(tiny) == t_before
    # the receiver was never poisoned: the clean wire still lands whole
    assert recv.import_blocks(wire)["imported"] == 3
    assert recv.export_blocks(p) == wire


def test_equal_tp_roundtrip_and_layout_independence(pm):
    """tp=2 → tp=2 round-trips bit-exactly (per-shard copy under the
    mesh); the SAME wire lands in a tp=1 pool — payloads are full-shape,
    so the wire is layout-independent and ``tp`` is advisory."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), (MODEL_AXIS,))
    (p,) = _prompts([2 * BS], seed=9)
    donor = _pool(pm, n_blocks=8, mesh=mesh)
    _seed(donor, p)
    wire = donor.export_blocks(p)
    assert wire["tp"] == 2
    recv2 = _pool(pm, n_blocks=8, mesh=mesh)
    assert recv2.import_blocks(wire)["imported"] == 2
    assert recv2.export_blocks(p) == wire
    recv1 = _pool(pm, n_blocks=8)
    assert recv1.import_blocks(wire)["imported"] == 2
    out = recv1.export_blocks(p)
    assert out.pop("tp") == 1 and dict(wire, tp=None) == dict(out, tp=None)


# -- role config + transfer directory (pure / cheap) --------------------------

def test_role_validation_messages():
    with pytest.raises(ValueError, match="role must be"):
        EngineCfg(role="draft")
    with pytest.raises(ValueError, match="requires the paged pool"):
        EngineCfg(role="prefill", paged=False)
    with pytest.raises(ValueError, match="requires the paged pool"):
        EngineCfg(role="decode", paged=False)
    assert EngineCfg(role="both", paged=False).role == "both"


def test_match_with_hashes_is_the_transfer_directory():
    """match(with_hashes=True) hands the router matches AND the prompt's
    chain-hex list in one walk — the names kv_export skips by."""
    idx = PrefixIndex(hot_k=4)
    toks = list(range(1, 9))
    hexes = chain_hash_hexes(toks, 4)
    idx.observe(0, {"seq": 1, "reset": False, "events": [
        ["register", hexes[0], toks[:4]], ["register", hexes[1], toks]]})
    m, hx = idx.match(toks + [9], count_hit=False, with_hashes=True)
    assert m == {0: 8} and hx == chain_hash_hexes(toks + [9], 4)
    assert hx[:2] == hexes
    # the matched depth in blocks names exactly the skippable prefix
    assert hx[:m[0] // idx.block_size] == hexes
    # impossible match still shapes the tuple
    assert idx.match([1], count_hit=False, with_hashes=True) == ({}, [])


# -- disaggregated fleet: tokens never change ---------------------------------

@pytest.fixture(scope="module")
def disagg(pm):
    """One prefill-role + one decode-role replica behind the router's
    splitter. The decode replica's pool is deliberately tight with
    overcommit so the preemption drill runs out of blocks mid-decode."""
    P = ServingEngine(lm=pm, cfg=EngineCfg(
        n_slots=2, steps_per_tick=4, role="prefill", kv_block_size=BS,
        decode_buckets=False, default_timeout_s=600.0))
    D = ServingEngine(lm=pm, cfg=EngineCfg(
        n_slots=2, steps_per_tick=4, role="decode", kv_block_size=BS,
        kv_cache_blocks=10, max_resident=4, block_overcommit=3.0,
        decode_buckets=False, default_timeout_s=600.0))
    rs = ReplicaSet([P, D], cooldown_s=30.0)
    rs.prefix_index.poll_interval_s = 0.0
    rs.start()
    yield rs, P, D
    rs.stop()


def test_disagg_greedy_identity_counters_and_warm_skip(disagg, pm):
    """A routed request hands off prefill→decode yet answers exactly the
    sequential path; the prefill replica never decodes; a warm repeat
    re-migrates NOTHING (the directory skipped every full block)."""
    rs, P, D = disagg
    (p,) = _prompts([2 * BS + 4], seed=11)
    ref = np.asarray(pm.generate(p[None, :], 8))[0]
    assert np.array_equal(rs.generate(p, 8, timeout_s=120.0).tokens, ref)
    snap = rs.snapshot()
    assert snap["serve.handoffs"] >= 1
    assert snap["serve.handoff_ms"] > 0
    assert snap["serve.kv_blocks_migrated"] >= 2
    assert snap["serve.kv_bytes_migrated"] > 0
    assert P.snapshot()["serve.decode_ticks"] == 0.0   # a PURE prefiller
    migrated = D.snapshot()["serve.kv_blocks_migrated"]
    assert np.array_equal(rs.generate(p, 8, timeout_s=120.0).tokens, ref)
    assert D.snapshot()["serve.kv_blocks_migrated"] == migrated
    assert rs.snapshot()["serve.handoffs"] >= 2


def test_disagg_seeded_identity_crosses_the_handoff(disagg, pm):
    """Seeded sampling is handoff-invariant: the migrated run reproduces
    a direct run on the decode engine under the same key, twice."""
    rs, _, D = disagg
    (p,) = _prompts([2 * BS + 2], seed=13)
    a = rs.generate(p, 8, temperature=0.7, rng=jax.random.PRNGKey(17),
                    timeout_s=120.0).tokens
    b = rs.generate(p, 8, temperature=0.7, rng=jax.random.PRNGKey(17),
                    timeout_s=120.0).tokens
    direct = D.generate(p, 8, temperature=0.7, rng=jax.random.PRNGKey(17),
                        timeout_s=120.0).tokens
    assert np.array_equal(a, b) and np.array_equal(a, direct)


def test_disagg_identity_through_mid_decode_preemption(disagg, pm,
                                                       monkeypatch):
    """The decode pool runs OUT of blocks mid-flight (overcommit admits
    more growth than it holds): the youngest migrated stream preempts,
    recomputes, and every answer still matches the sequential path."""
    rs, _, D = disagg
    prompts = _prompts([18, 19, 21], seed=17)
    steps = 24
    # the overcommit is the pool's arithmetic, not the threads' timing: each
    # stream writes 6 blocks before it ends, so any two that start decoding
    # together outgrow the pool's 10 while neither can finish first
    per_stream = [D.pool.blocks_for(D.pool.total_positions(len(p), steps))
                  for p in prompts]
    assert per_stream == [6, 6, 6] and 2 * 6 > D.pool.n_blocks
    refs = [np.asarray(pm.generate(p[None, :], steps))[0] for p in prompts]
    base = D.snapshot()["serve.preemptions"]
    # ordered admission: a handoff runs on the submitting thread (prefill on
    # P, migrate, queue on D), so without the gate the first stream may be
    # done before the second arrives. D keeps admitting and landing imports
    # but decodes nothing until two streams are resident.
    gate = threading.Event()
    tick = D._decode_tick
    monkeypatch.setattr(D, "_decode_tick", lambda: gate.is_set() and tick())
    try:
        futs = [rs.submit_generate(p, steps, timeout_s=300.0)
                for p in prompts]
        deadline = time.monotonic() + 120.0
        while (D.snapshot()["serve.resident_streams"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert D.snapshot()["serve.resident_streams"] >= 2
    finally:
        gate.set()
    out = [f.result(timeout=300) for f in futs]
    assert D.snapshot()["serve.preemptions"] > base, \
        "overcommit never ran out — the drill lost its teeth"
    for j, (r, ref) in enumerate(zip(out, refs)):
        assert np.array_equal(r.tokens, ref), j


def test_disagg_identity_through_prefill_replica_restart(disagg, pm):
    """An in-place prefill-replica restart (the supervisor's recovery
    path) drops its pool cold; the very next request hands off again with
    a FRESH migration and tokens never change. The process-level variant
    (DDW_FAULT crash + supervisor respawn) rides load_gen --disagg."""
    rs, P, D = disagg
    before = rs.snapshot()["serve.handoffs"]
    migrated = D.snapshot()["serve.kv_blocks_migrated"]
    P.stop()
    P.restart()                       # warm rejoin, device state re-init
    rs.prefix_index.drop_replica(0)   # a fresh pool holds nothing
    (p,) = _prompts([3 * BS + 2], seed=19)
    ref = np.asarray(pm.generate(p[None, :], 6))[0]
    assert np.array_equal(rs.generate(p, 6, timeout_s=120.0).tokens, ref)
    assert rs.snapshot()["serve.handoffs"] > before
    assert D.snapshot()["serve.kv_blocks_migrated"] > migrated
    assert P.snapshot()["serve.decode_ticks"] == 0.0
