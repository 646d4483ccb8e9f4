"""Serving under load: latency/throughput curves for the packaged artifacts.

Completes the reference's ``spark_udf`` scoring role
(``Part 2 - Distributed Tuning & Inference/03_pyfunc_distributed_inference.py:
466-472``) with numbers: the image package's batch-size curve (what a scorer
worker sees per ``predict_logits`` call, H2D/D2H included), the LM package's
per-token generation latency with speculative decoding off/on, and the
ONLINE arm — an offered-load sweep through the continuous-batching engine
(``ddw_tpu.serve``): closed-loop clients at each concurrency level, reporting
aggregate tokens/sec, queue time, TTFT, and p99 latency per load point
against the sequential single-request baseline — plus the PAGED-CAPACITY
arm: resident streams and tok/s for the paged block pool vs the slot
baseline at equal KV memory on a shared-prefix burst (the smoke pins
paged residency > n_slots at >= 2x slots' peak with no throughput loss) —
plus the BATCH-LANE arm: interactive-only vs batch-only vs mixed rows on
one paged engine at equal KV memory (the smoke pins mixed interactive
TTFT p99 within a generous bound of interactive-only while batch items
complete during the run — the dual-lane headline) — plus the ROUTING-A/B
arm: cache-aware routing vs the least-outstanding baseline on the same
shared-prefix workload over a 2-replica fleet (the smoke pins strictly
fewer prefill tokens computed with TTFT p99 no worse — the fleet
prefix-cache headline) — plus the DISAGG-A/B arm: colocated vs
1-prefill+1-decode replicas at equal devices on a prefill-heavy burst
(the smoke pins bit-identical completions greedy AND seeded, KV blocks
actually migrating, the prefix-warm payload skip, and request p99 inside
an equal-devices bound — the KV-migration headline) — plus the SPEC-A/B
arm: speculative decoding on
vs off at equal engine config on the same workload with a self-draft (the
smoke pins bit-identical completions, acceptance exactly 1.0, >1 tokens
per target dispatch, and strictly fewer decode ticks) — plus the
observability A/B arms: TRACE-A/B and TELEMETRY-A/B, each on-vs-off at
equal engine config on the same workload with interleaved sweeps and
best-of per arm (the smoke pins both overheads within 3% — the
"observability is cheap enough to leave on" contract, numbers in
docs/observability.md).

Usage (chip): ``DDW_REQUIRE_TPU=1 python tools/serving_curve.py``
CI smoke:     ``DDW_BENCH_SMOKE=1`` shrinks shapes/batches/steps.

CPU framing for the fleet-shaped arms (and tools/load_gen.py's fleet
smoke and ``--autoscale`` arm): every replica here shares ONE core, so
adding replicas cannot add service rate — the honest CPU pins are
STRUCTURAL (queue-wait halving on a burst at 2x slot capacity, the
autoscaler converging actual to desired with surge admission and
drain-first retirement, bit-identical outputs across membership changes),
never raw throughput. On a real fleet — replica per chip/host, spawned
over the ``host=`` transport (docs/serving.md "Autoscaling") — the same
loops add genuine capacity, and these curves are re-measured there.

Prints ONE JSON line: ``{"device": ..., "image_curve": [rows], "lm": {...},
"engine": {...}}`` — each image row is {batch, median_ms, p90_ms,
images_per_sec}; the LM block carries per-token ms for plain and speculative
generation plus the speculative acceptance stats; the engine block carries
{"sequential_tokens_per_sec", "sweep": [{concurrency, tokens_per_sec,
queue_ms_p50, ttft_ms_p50, total_ms_p99, completed}]}. Speculative speedup
depends on draft/target agreement — random-weight packages measure the
compute path, not the acceptance rate a trained pair would get (stats are
reported so that caveat is visible).
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ddw_tpu.utils.config import env_flag

SMOKE = env_flag("DDW_BENCH_SMOKE")
REPEATS = 3 if SMOKE else 20


def _timed(call, *args, **kw):
    """Median/p90 wall ms of a serving call (outputs are host arrays — the
    fetch IS the completion barrier, exactly what a scorer worker pays).
    p90 is interpolated (np.percentile) — with few repeats, indexing
    int(0.9*len) lands on the max and overstates tail fidelity."""
    call(*args, **kw)  # warmup/compile
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        call(*args, **kw)
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times)), float(np.percentile(times, 90))


def throwaway_image_package(tmp: str, img: tuple):
    """Frozen-random bf16 MobileNetV2 packaged into ``tmp`` and loaded back —
    the serving fixture ``image_curve`` measures. Returns the loaded
    :class:`PackagedModel`."""
    import warnings

    from ddw_tpu.models.registry import build_model
    from ddw_tpu.serving.package import PackagedModel, save_packaged_model
    from ddw_tpu.utils.config import ModelCfg

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # frozen-random warning: speed only
        mcfg = ModelCfg(name="mobilenet_v2", num_classes=5, dropout=0.0,
                        freeze_base=True, allow_frozen_random=True,
                        dtype="bfloat16")
        model = build_model(mcfg)
        variables = model.init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, *img)), train=False)
        save_packaged_model(tmp, mcfg, [f"c{i}" for i in range(5)],
                            variables["params"],
                            variables.get("batch_stats"),
                            img_height=img[0], img_width=img[1])
        return PackagedModel(tmp)


def image_curve(batches, img):
    rng = np.random.RandomState(0)
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        pm = throwaway_image_package(tmp, img)
        for b in batches:
            imgs = rng.rand(b, *img).astype(np.float32) * 2 - 1
            med, p90 = _timed(pm.predict_logits, imgs)
            rows.append({"batch": b, "median_ms": round(med, 3),
                         "p90_ms": round(p90, 3),
                         "images_per_sec": round(b / med * 1e3, 1)})
            print(f"[curve] image b={b}: {med:.2f} ms "
                  f"({b / med * 1e3:.0f} img/s)", file=sys.stderr, flush=True)
    return rows


def _make_lm_pkg(tmp, name, h, d, heads, vocab, max_len, dtype="bfloat16",
                 seed=0):
    from ddw_tpu.models.lm import TransformerLM
    from ddw_tpu.serving.lm_package import load_lm_package, save_lm_package
    from ddw_tpu.train.lm_step import init_lm_state
    from ddw_tpu.utils.config import LMCfg

    import optax

    cfg = LMCfg(vocab_size=vocab, max_len=max_len, hidden=h, depth=d,
                num_heads=heads, mlp_dim=4 * h, dropout=0.0, dtype=dtype)
    model = TransformerLM(vocab_size=vocab, max_len=max_len, hidden=h,
                          depth=d, num_heads=heads, mlp_dim=4 * h,
                          dropout=0.0, dtype=dtype)
    # seed varies the WEIGHTS: two packages from different seeds have
    # different content digests (the deploy drills hot-swap between them)
    state = init_lm_state(model, optax.sgd(0.0), jax.random.PRNGKey(seed))
    out = os.path.join(tmp, name)
    save_lm_package(out, cfg, state.params)
    return load_lm_package(out)


def lm_latencies(hidden, depth, heads, vocab, max_len, prompt_len, steps,
                 spec_k):
    def make_pkg(tmp, name, h, d):
        return _make_lm_pkg(tmp, name, h, d, heads, vocab, max_len)

    rng = np.random.RandomState(0)
    prompt = rng.randint(0, vocab, size=(1, prompt_len)).astype(np.int32)
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        target = make_pkg(tmp, "target", hidden, depth)
        draft = make_pkg(tmp, "draft", max(hidden // 4, 16), 2)

        med, p90 = _timed(target.generate, prompt, steps)
        out["generate"] = {"steps": steps, "median_ms_per_token":
                           round(med / steps, 3), "p90_ms_total": round(p90, 2)}
        print(f"[curve] lm generate: {med / steps:.2f} ms/token",
              file=sys.stderr, flush=True)

        stats_box = {}

        def spec_call():
            _, stats = target.generate_speculative(draft, prompt, steps,
                                                   k=spec_k)
            stats_box.update(stats)

        med, p90 = _timed(spec_call)
        out["generate_speculative"] = {
            "steps": steps, "k": spec_k,
            "median_ms_per_token": round(med / steps, 3),
            "p90_ms_total": round(p90, 2),
            "stats": {k: (round(float(v), 4) if isinstance(v, float)
                          else int(v) if isinstance(v, (int, np.integer))
                          else v) for k, v in stats_box.items()},
        }
        print(f"[curve] lm speculative(k={spec_k}): {med / steps:.2f} "
              f"ms/token", file=sys.stderr, flush=True)
    return out


def engine_load_sweep(levels, hidden, depth, heads, vocab, max_len,
                      prompt_len, steps, n_slots, steps_per_tick,
                      requests_per_level, dtype="bfloat16"):
    """Offered-load sweep through the online engine: at each concurrency
    level, that many closed-loop clients fire generate requests back to
    back until ``requests_per_level`` complete; aggregate tokens/sec plus
    the queue/TTFT/p99 SLO numbers come from the engine's own metrics. The
    sequential baseline times the SAME requests one at a time through the
    package path — the number continuous batching must beat."""
    import threading

    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests_per_level)]
    out = {"steps": steps, "n_slots": n_slots,
           "steps_per_tick": steps_per_tick, "sweep": []}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "engine", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        pm.generate(prompts[0][None, :], steps)  # warmup/compile
        t0 = time.perf_counter()
        for p in prompts:
            pm.generate(p[None, :], steps)
        seq_s = time.perf_counter() - t0
        out["sequential_tokens_per_sec"] = round(
            requests_per_level * steps / seq_s, 1)
        print(f"[curve] engine baseline: sequential "
              f"{out['sequential_tokens_per_sec']:.0f} tok/s",
              file=sys.stderr, flush=True)
        for conc in levels:
            eng = ServingEngine(lm=pm, cfg=EngineCfg(
                n_slots=n_slots, steps_per_tick=steps_per_tick,
                queue_depth=max(2 * conc, 8), default_timeout_s=600.0))
            with eng:
                eng.warmup([prompt_len])         # compile outside the clock
                eng.generate(prompts[0], steps)
                eng.metrics = type(eng.metrics)()  # fresh window
                it = iter(prompts)
                lock = threading.Lock()

                def client():
                    while True:
                        with lock:
                            p = next(it, None)
                        if p is None:
                            return
                        eng.generate(p, steps)

                t0 = time.perf_counter()
                threads = [threading.Thread(target=client)
                           for _ in range(conc)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                wall = time.perf_counter() - t0
                snap = eng.snapshot()
            row = {
                "concurrency": conc,
                "tokens_per_sec": round(
                    requests_per_level * steps / wall, 1),
                # busy-window aggregate from the engine's own metrics
                # (first admission -> last completion): the steady-state
                # number, insensitive to closed-loop arrival raggedness
                "tokens_per_sec_busy": round(
                    snap.get("serve.tokens_per_sec", 0.0), 1),
                "queue_ms_p50": round(snap["serve.queue_ms_p50"], 2),
                "ttft_ms_p50": round(snap["serve.ttft_ms_p50"], 2),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "total_ms_p99": round(snap["serve.total_ms_p99"], 2),
                "completed": int(snap["serve.completed"]),
            }
            out["sweep"].append(row)
            print(f"[curve] engine c={conc}: {row['tokens_per_sec']:.0f} "
                  f"tok/s, ttft p50 {row['ttft_ms_p50']:.1f} ms, p99 "
                  f"{row['total_ms_p99']:.1f} ms", file=sys.stderr,
                  flush=True)
    return out


def paged_capacity(hidden, depth, heads, vocab, max_len, prompt_len, steps,
                   n_slots, steps_per_tick, dtype="float32",
                   shared_prefix=16):
    """The paged-KV capacity arm: resident streams + tok/s, paged pool vs
    the contiguous slot baseline at EQUAL KV-cache memory (the paged
    engine's default derives its block count from n_slots * cache
    capacity). The workload is a burst of 2 * n_slots requests whose
    prompts share a ``shared_prefix``-token head (the fleet-wide
    system-prompt shape) behind one completed warm request, so the paged
    run also exercises prefix reuse. The slot pool structurally caps
    residency at n_slots (the burst runs as two waves); the paged pool
    admits the whole burst because actual usage — not worst-case length —
    bounds capacity. DDW_BENCH_SMOKE pins paged residency strictly above
    n_slots, at >= 2x the slot baseline, with throughput no worse."""
    import threading

    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(0)
    burst = 2 * n_slots
    prefix = rng.randint(0, vocab, size=(shared_prefix,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.randint(
        0, vocab, size=(prompt_len - shared_prefix,)).astype(np.int32)])
        for _ in range(burst)]
    out = {"n_slots": n_slots, "burst": burst, "steps": steps}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "paged", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        for name, paged in (("slot", False), ("paged", True)):
            cfg = EngineCfg(n_slots=n_slots, steps_per_tick=steps_per_tick,
                            paged=paged, queue_depth=4 * burst,
                            default_timeout_s=600.0)
            with ServingEngine(lm=pm, cfg=cfg) as eng:
                eng.warmup([prompt_len])
                eng.generate(prompts[0], steps)   # warm + seed prefix cache
                eng.metrics = type(eng.metrics)()  # fresh window
                peak = [0]
                stop = threading.Event()

                def sampler():
                    while not stop.is_set():
                        peak[0] = max(peak[0],
                                      eng.health()["busy_slots"])
                        time.sleep(0.002)

                # suffix buckets too: prefix-hit requests prefill only
                # their uncovered tail, which lands on smaller buckets
                eng.warmup([max(prompt_len - shared_prefix, 1), 1])
                th = threading.Thread(target=sampler)
                th.start()
                t0 = time.perf_counter()
                futs = [eng.submit_generate(p, steps) for p in prompts]
                for f in futs:
                    f.result(timeout=600)
                wall = time.perf_counter() - t0
                stop.set()
                th.join()
                snap = eng.snapshot()
            row = {
                "resident_peak": peak[0],
                "tokens_per_sec": round(burst * steps / wall, 1),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "total_ms_p99": round(snap["serve.total_ms_p99"], 2),
                "prefix_hit_tokens": int(
                    snap.get("serve.prefix_hit_tokens", 0)),
                "cow_copies": int(snap.get("serve.cow_copies", 0)),
            }
            out[name] = row
            print(f"[curve] capacity {name}: peak {row['resident_peak']} "
                  f"resident, {row['tokens_per_sec']:.0f} tok/s, "
                  f"prefix hits {row['prefix_hit_tokens']} tok",
                  file=sys.stderr, flush=True)
    if SMOKE:
        # the acceptance pin: at equal KV memory the paged pool admits
        # strictly more concurrent streams than n_slots (>= 2x the slot
        # baseline's peak) without giving up throughput
        assert out["paged"]["resident_peak"] > n_slots, out
        assert (out["paged"]["resident_peak"]
                >= 2 * out["slot"]["resident_peak"]), out
        assert (out["paged"]["tokens_per_sec"]
                >= out["slot"]["tokens_per_sec"]), out
        assert out["paged"]["prefix_hit_tokens"] > 0, out
    return out


def batch_lane_curve(hidden, depth, heads, vocab, max_len, prompt_len,
                     steps, n_slots, steps_per_tick, dtype="float32",
                     requests=24, clients=4, batch_items=64):
    """Dual-lane rows at EQUAL KV memory: one paged engine (one pool, one
    reserve watermark) measured three ways — interactive-only, batch-only,
    and mixed (closed-loop interactive over a saturating batch job). The
    headline pin: with the batch lane saturated, interactive TTFT p99
    stays within a generous bound of the interactive-only baseline
    (max(3x, +250 ms) — 1-core CI noise dwarfs the true cost, since batch
    rows ride decode dispatches that already ran at ``max_resident``
    width) while batch items complete during the interactive run (> 0).
    TTFT tails come from the engine's own records, which are
    interactive-lane-only by construction."""
    import threading

    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(1)

    def mk(n):
        return [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
                for _ in range(n)]

    iprompts, bprompts = mk(requests), mk(batch_items)
    out = {"n_slots": n_slots, "steps": steps, "requests": requests,
           "clients": clients, "batch_items": batch_items}

    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "lanes", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        cfg = EngineCfg(n_slots=n_slots, steps_per_tick=steps_per_tick,
                        queue_depth=4 * max(requests, clients),
                        default_timeout_s=600.0)
        with ServingEngine(lm=pm, cfg=cfg) as eng:
            eng.warmup([prompt_len])
            eng.generate(iprompts[0], steps)          # warm the programs

            def interactive_run():
                it = iter(iprompts)
                lock = threading.Lock()

                def worker():
                    while True:
                        with lock:
                            p = next(it, None)
                        if p is None:
                            return
                        eng.submit_generate(p, steps).result(timeout=600)

                threads = [threading.Thread(target=worker)
                           for _ in range(clients)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                return time.perf_counter() - t0

            interactive_run()     # warm the grouped-prefill programs too —
            #                       the baseline must not eat compile time
            eng.metrics = type(eng.metrics)()          # fresh window
            wall = interactive_run()
            snap = eng.snapshot()
            out["interactive_only"] = {
                "tokens_per_sec": round(requests * steps / wall, 1),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "total_ms_p99": round(snap["serve.total_ms_p99"], 2)}

            eng.metrics = type(eng.metrics)()
            t0 = time.perf_counter()
            job = eng.submit_batch(bprompts, kind="generate",
                                   num_steps=steps)
            job.wait(timeout_s=600)
            wall = time.perf_counter() - t0
            out["batch_only"] = {
                "items_per_sec": round(batch_items / wall, 2),
                "tokens_per_sec": round(batch_items * steps / wall, 1)}

            eng.metrics = type(eng.metrics)()
            job = eng.submit_batch(bprompts, kind="generate",
                                   num_steps=steps)
            wall = interactive_run()
            st = job.progress()          # batch progress DURING the run
            job.cancel()
            snap = eng.snapshot()
            out["mixed"] = {
                "interactive_tokens_per_sec": round(
                    requests * steps / wall, 1),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "total_ms_p99": round(snap["serve.total_ms_p99"], 2),
                "batch_completed_during_run": st["completed"],
                "batch_items_per_sec": st["items_per_sec"],
                "batch_preemptions": int(
                    snap.get("serve.batch_preemptions", 0))}
    for name in ("interactive_only", "batch_only", "mixed"):
        print(f"[curve] lanes {name}: {out[name]}",
              file=sys.stderr, flush=True)
    if SMOKE:
        base = out["interactive_only"]["ttft_ms_p99"]
        bound = max(3.0 * base, base + 250.0)
        assert out["mixed"]["ttft_ms_p99"] <= bound, out
        assert out["mixed"]["batch_completed_during_run"] > 0, out
        assert out["batch_only"]["items_per_sec"] > 0, out
    return out


def routing_ab(hidden, depth, heads, vocab, max_len, n_slots,
               steps_per_tick, dtype="float32", families=6, shared_len=64,
               tail_len=8, rounds=3, steps=4):
    """The fleet-routing A/B arm: cache-aware routing vs the
    least-outstanding baseline on the SAME shared-prefix workload over a
    2-replica fleet, from identical starting states.

    Setup per arm: a fresh 2-engine :class:`ReplicaSet` (cache-aware =
    default; baseline = ``route_by_prefix=False``), then ``families``
    distinct prefix heads are seeded DIRECTLY onto replica 1 — the
    worst-case placement for an index-blind router, whose projected-wait
    tie-break lands every idle-fleet request on slot 0. The measured
    window replays ``rounds`` requests per family (fresh random tails)
    through the router one at a time, so queues stay drained and the A/B
    isolates the routing decision itself: by design the prefix credit
    only ever breaks WAIT ties (a replica's service estimate always
    exceeds its own prefill-savings credit, so affinity never beats a
    genuinely shorter queue — docs/serving.md). The baseline prefills
    each family cold on replica 0 once before its local cache kicks in;
    the cache-aware router sends every request to the holder.

    Prefill tokens computed = prompt tokens - fleet prefix-cache hit
    tokens (offered tokens are identical across arms by construction).
    DDW_BENCH_SMOKE pins the acceptance number: cache-aware computes
    STRICTLY fewer prefill tokens than least-outstanding, with TTFT p99
    no worse (a small bound absorbs 1-core scheduler noise — the
    structural gap, six cold 72-token prefills in the baseline's tail, is
    far larger)."""
    from ddw_tpu.gateway import ReplicaSet
    from ddw_tpu.serve import EngineCfg, ServingEngine
    from ddw_tpu.serve.metrics import merge_metrics

    rng = np.random.RandomState(3)
    heads_tok = [rng.randint(0, vocab, size=(shared_len,)).astype(np.int32)
                 for _ in range(families)]
    # rounds x families prompts, families interleaved — identical token
    # streams for both arms, fresh tails so only the PREFIX can hit
    prompts = [np.concatenate([heads_tok[f], rng.randint(
        0, vocab, size=(tail_len,)).astype(np.int32)])
        for _ in range(rounds) for f in range(families)]
    offered_tokens = sum(len(p) for p in prompts)
    out = {"families": families, "shared_len": shared_len,
           "rounds": rounds, "offered_prefill_tokens": offered_tokens}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "routing", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        for name, by_prefix in (("least_outstanding", False),
                                ("cache_aware", True)):
            engines = [ServingEngine(lm=pm, cfg=EngineCfg(
                n_slots=n_slots, steps_per_tick=steps_per_tick,
                queue_depth=4 * n_slots, default_timeout_s=600.0))
                for _ in range(2)]
            rs = ReplicaSet(engines, route_by_prefix=by_prefix)
            rs.prefix_index.poll_interval_s = 0.0   # fresh on every route
            with rs:
                rs.warmup([shared_len + tail_len, tail_len, 1])
                for h in heads_tok:   # seed replica 1, router unseen
                    engines[1].generate(
                        np.concatenate([h, h[:tail_len]]), steps)
                for eng in engines:   # measured window starts clean
                    eng.metrics = type(eng.metrics)()
                t0 = time.perf_counter()
                for p in prompts:
                    rs.generate(p, steps)
                wall = time.perf_counter() - t0
                snap = merge_metrics(
                    [e.metrics for e in engines]).snapshot()
            hit = int(snap.get("serve.prefix_hit_tokens", 0))
            row = {
                "prefill_tokens_computed": offered_tokens - hit,
                "prefix_hit_tokens": hit,
                "routed_cache_hit": int(
                    snap.get("serve.routed_cache_hit", 0)),
                "routed_wait_override": int(
                    snap.get("serve.routed_wait_override", 0)),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "tokens_per_sec": round(
                    len(prompts) * steps / wall, 1),
                "completed": int(snap["serve.completed"]),
            }
            out[name] = row
            print(f"[curve] routing {name}: "
                  f"{row['prefill_tokens_computed']} prefill tok computed "
                  f"({row['prefix_hit_tokens']} hit), ttft p99 "
                  f"{row['ttft_ms_p99']:.1f} ms", file=sys.stderr,
                  flush=True)
    if SMOKE:
        ca, lo = out["cache_aware"], out["least_outstanding"]
        assert ca["completed"] == lo["completed"] == len(prompts), out
        # THE acceptance pin: strictly fewer prefill tokens computed...
        assert (ca["prefill_tokens_computed"]
                < lo["prefill_tokens_computed"]), out
        # ...with TTFT p99 no worse (generous-noise bound; the real gap
        # is the baseline's cold-prefill tail, several times larger)
        assert (ca["ttft_ms_p99"]
                <= 1.1 * lo["ttft_ms_p99"] + 5.0), out
        assert ca["routed_cache_hit"] > 0, out
    return out


def disagg_ab(hidden, depth, heads, vocab, max_len, n_slots,
              steps_per_tick, dtype="float32", families=4, shared_len=48,
              tail_len=8, rounds=3, steps=4, clients=4):
    """The prefill/decode disaggregation A/B arm: colocated (two
    ``role="both"`` replicas) vs disaggregated (one ``role="prefill"`` +
    one ``role="decode"``) at EQUAL devices on the SAME prefill-heavy
    burst — long shared-prefix prompts, few decode steps, ``clients``
    concurrent submitters.

    Per arm: a fresh 2-engine :class:`ReplicaSet`, a seeding round (one
    request per family — compiles, performs the FIRST migrations, and
    warms both sides' prefix caches), then the measured burst over
    ``rounds`` fresh-tailed requests per family. The honest claim on a
    single CPU host is mechanics, not speed (both roles share one core,
    so the structural TTFT win — decode tails no longer queueing behind
    compute-bound prefills — needs genuinely separate hosts; the
    synchronous handoff only ADDS serialized work here). What the smoke
    pins is therefore the correctness + migration surface: completions
    bit-identical across arms (greedy AND seeded sampling), handoffs and
    ``kv_blocks_migrated`` > 0 in the disagg arm and zero in colocated,
    the prefix-warm skip (the measured window re-migrates NOTHING — the
    transfer directory names every warm block), and client-observed
    request p99 inside a generous equal-devices noise bound."""
    import concurrent.futures as cf

    from ddw_tpu.gateway import ReplicaSet
    from ddw_tpu.serve import EngineCfg, ServingEngine
    from ddw_tpu.serve.metrics import merge_metrics

    rng = np.random.RandomState(13)
    heads_tok = [rng.randint(0, vocab, size=(shared_len,)).astype(np.int32)
                 for _ in range(families)]
    seeders = [np.concatenate([h, rng.randint(
        0, vocab, size=(tail_len,)).astype(np.int32)]) for h in heads_tok]
    prompts = [np.concatenate([heads_tok[f], rng.randint(
        0, vocab, size=(tail_len,)).astype(np.int32)])
        for _ in range(rounds) for f in range(families)]
    out = {"families": families, "shared_len": shared_len,
           "rounds": rounds, "steps": steps, "clients": clients}
    completions = {}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "disagg", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        for name, roles in (("colocated", ("both", "both")),
                            ("disagg", ("prefill", "decode"))):
            engines = [ServingEngine(lm=pm, cfg=EngineCfg(
                n_slots=n_slots, steps_per_tick=steps_per_tick,
                queue_depth=4 * n_slots, default_timeout_s=600.0,
                role=role)) for role in roles]
            rs = ReplicaSet(engines)
            rs.prefix_index.poll_interval_s = 0.0   # fresh on every route
            with rs:
                rs.warmup([shared_len + tail_len, tail_len, 1])
                for p in seeders:   # compile + first migrations + warm
                    rs.generate(p, steps)
                seed_snap = merge_metrics(
                    [e.metrics for e in engines]).snapshot()
                for eng in engines:   # measured window starts clean
                    eng.metrics = type(eng.metrics)()
                lat: list = []
                t0 = time.perf_counter()
                with cf.ThreadPoolExecutor(clients) as pool:
                    def one(p):
                        t = time.perf_counter()
                        r = rs.generate(p, steps)
                        return (time.perf_counter() - t) * 1e3, r.tokens
                    got = list(pool.map(one, prompts))
                wall = time.perf_counter() - t0
                lat = [g[0] for g in got]
                completions[name] = [g[1] for g in got]
                # seeded sampling crosses the handoff bit-identically too:
                # fixed PRNG key over bit-identical logits
                completions[name + "_seeded"] = [
                    rs.generate(p, steps, temperature=0.7,
                                rng=jax.random.PRNGKey(17)).tokens
                    for p in prompts[:families]]
                snap = merge_metrics(
                    [e.metrics for e in engines]).snapshot()
            fleet = rs.fleet_metrics.snapshot()
            row = {
                "request_ms_p99": round(float(np.percentile(lat, 99)), 2),
                "ttft_ms_p99": round(snap["serve.ttft_ms_p99"], 2),
                "tokens_per_sec": round(len(prompts) * steps / wall, 1),
                "completed": int(snap["serve.completed"]),
                "handoffs": int(fleet.get("serve.handoffs", 0)),
                "handoff_ms": int(fleet.get("serve.handoff_ms", 0)),
                "kv_blocks_migrated_seed": int(
                    seed_snap.get("serve.kv_blocks_migrated", 0)),
                "kv_bytes_migrated_seed": int(
                    seed_snap.get("serve.kv_bytes_migrated", 0)),
                "kv_blocks_migrated_measured": int(
                    snap.get("serve.kv_blocks_migrated", 0)),
            }
            out[name] = row
            print(f"[curve] disagg_ab {name}: req p99 "
                  f"{row['request_ms_p99']:.1f} ms, "
                  f"{row['handoffs']} handoffs, "
                  f"{row['kv_blocks_migrated_seed']} blocks migrated "
                  f"(measured-window re-migrations: "
                  f"{row['kv_blocks_migrated_measured']})",
                  file=sys.stderr, flush=True)
    if SMOKE:
        co, dg = out["colocated"], out["disagg"]
        # THE pin: disaggregation changes WHERE prefill runs, never what
        # anyone computes — greedy and seeded, token for token
        for a, b in zip(completions["colocated"], completions["disagg"]):
            assert np.array_equal(a, b), out
        for a, b in zip(completions["colocated_seeded"],
                        completions["disagg_seeded"]):
            assert np.array_equal(a, b), out
        # every client request completed in both arms (engine-side
        # "completed" counts the disagg arm's 1-step prefill probes too,
        # so client completions are counted here, not from the snapshot)
        assert len(completions["colocated"]) == len(prompts), out
        assert len(completions["disagg"]) == len(prompts), out
        # migration actually happened, and only in the disagg arm
        assert dg["handoffs"] > 0 and dg["kv_blocks_migrated_seed"] > 0, out
        assert dg["kv_bytes_migrated_seed"] > 0, out
        assert co["handoffs"] == 0, out
        assert co["kv_blocks_migrated_seed"] == 0, out
        # the prefix-warm skip: every measured-window handoff found its
        # blocks already warm on the decode side via the transfer
        # directory — nothing re-crossed the wire
        assert dg["kv_blocks_migrated_measured"] == 0, out
        # equal-devices latency bound (generous: one CPU core serializes
        # the roles, so this bounds the handoff overhead, it can't show
        # the separate-hosts win)
        assert dg["request_ms_p99"] <= max(
            3.0 * co["request_ms_p99"],
            co["request_ms_p99"] + 500.0), out
    return out


def spec_ab(hidden, depth, heads, vocab, max_len, prompt_len, steps,
            n_slots, steps_per_tick, spec_k, dtype="float32", requests=8):
    """The engine speculative-decode A/B arm: spec-on vs spec-off at EQUAL
    engine config on the SAME workload through the paged engine. The draft
    is the target itself (self-draft) — greedy proposals then always match
    the verifier's own picks, so acceptance is exactly 1.0 and every tick
    advances k+1 tokens per stream: the arm isolates the dispatch-count
    mechanics (ticks saved) from draft quality, which random weights cannot
    represent (a trained draft/target pair sits between the two arms).
    DDW_BENCH_SMOKE pins bit-identical completions across arms, >1
    accepted tokens per target dispatch, and strictly fewer decode ticks;
    tok/s is reported for both arms without a pin — on CPU the self-draft
    pays target-sized drafting compute, so the wall-clock win needs a
    genuinely small draft."""
    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests)]
    out = {"k": spec_k, "requests": requests, "steps": steps}
    completions = {}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "spec_ab", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        for name, k in (("spec_off", 0), ("spec_on", spec_k)):
            cfg = EngineCfg(n_slots=n_slots, steps_per_tick=steps_per_tick,
                            spec_k=k, queue_depth=4 * requests,
                            default_timeout_s=600.0)
            with ServingEngine(lm=pm, cfg=cfg,
                               draft=pm if k else None) as eng:
                eng.warmup([prompt_len])
                eng.generate(prompts[0], steps)     # compile + warm cache
                eng.metrics = type(eng.metrics)()   # fresh window
                t0 = time.perf_counter()
                futs = [eng.submit_generate(p, steps) for p in prompts]
                completions[name] = [f.result(timeout=600).tokens
                                     for f in futs]
                wall = time.perf_counter() - t0
                snap = eng.snapshot()
            row = {
                "tokens_per_sec": round(requests * steps / wall, 1),
                "decode_ticks": int(snap["serve.decode_ticks"]),
                "spec_acceptance_rate": round(
                    snap.get("serve.spec_acceptance_rate", 0.0), 4),
                "spec_tokens_per_tick": round(
                    snap.get("serve.spec_tokens_per_tick", 0.0), 3),
            }
            out[name] = row
            print(f"[curve] spec_ab {name}: {row['decode_ticks']} decode "
                  f"ticks, {row['tokens_per_sec']:.0f} tok/s"
                  + (f", {row['spec_tokens_per_tick']:.2f} tok/tick at "
                     f"acceptance {row['spec_acceptance_rate']:.2f}"
                     if k else ""), file=sys.stderr, flush=True)
    out["ticks_saved"] = (out["spec_off"]["decode_ticks"]
                          - out["spec_on"]["decode_ticks"])
    if SMOKE:
        # the acceptance pins: content is UNTOUCHED by speculation while
        # each target dispatch yields more than one token
        for a, b in zip(completions["spec_off"], completions["spec_on"]):
            assert np.array_equal(a, b), out
        assert out["spec_on"]["spec_tokens_per_tick"] > 1.0, out
        assert out["spec_on"]["spec_acceptance_rate"] == 1.0, out
        assert out["ticks_saved"] > 0, out
    return out


def tp_ab(hidden, depth, heads, vocab, max_len, prompt_len, steps,
          n_slots, steps_per_tick, spec_k, dtype="float32", requests=8):
    """The tensor-parallel A/B arm: tp=2 (a 2-wide model-axis mesh over
    fake CPU devices) vs tp=1 at EQUAL engine config on the SAME workload,
    plus a spec×TP composition row (tp=2 AND self-draft speculation). The
    honest claim on a CPU host is mechanics, not speed — collectives over
    fake devices cost, they don't amortize — so tok/s is reported without
    a pin and ``tp_dispatch_cost_us`` surfaces what each sharded dispatch
    paid. DDW_BENCH_SMOKE pins completions bit-identical across ALL THREE
    arms, equal prefill/decode dispatch counts tp2-vs-tp1, tp counters
    flowing only under a mesh, and self-draft acceptance still exactly
    1.0 when speculation runs sharded."""
    from ddw_tpu.serve import EngineCfg, ServingEngine

    if jax.device_count() < 2:
        # standalone invocation without forced host devices: the arm needs
        # a 2-device slice; the smoke/test harness always provides one
        print("[curve] tp_ab: skipped (needs >= 2 devices)",
              file=sys.stderr, flush=True)
        return {"skipped": "needs >= 2 devices"}
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests)]
    out = {"requests": requests, "steps": steps, "k": spec_k}
    completions = {}
    with tempfile.TemporaryDirectory() as tmp:
        pm = _make_lm_pkg(tmp, "tp_ab", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        arms = (("tp1", 1, 0), ("tp2", 2, 0), ("tp2_spec", 2, spec_k))
        for name, tp, k in arms:
            cfg = EngineCfg(n_slots=n_slots, tp=tp, spec_k=k,
                            steps_per_tick=1 if k else steps_per_tick,
                            queue_depth=4 * requests,
                            default_timeout_s=600.0)
            with ServingEngine(lm=pm, cfg=cfg,
                               draft=pm if k else None) as eng:
                eng.warmup([prompt_len])
                eng.generate(prompts[0], steps)     # compile + warm cache
                eng.metrics = type(eng.metrics)()   # fresh window
                t0 = time.perf_counter()
                futs = [eng.submit_generate(p, steps) for p in prompts]
                completions[name] = [f.result(timeout=600).tokens
                                     for f in futs]
                wall = time.perf_counter() - t0
                snap = eng.snapshot()
            row = {
                "tokens_per_sec": round(requests * steps / wall, 1),
                "decode_ticks": int(snap["serve.decode_ticks"]),
                "prefills": int(snap["serve.prefills"]),
                "tp_dispatches": int(snap["serve.tp_dispatches"]),
                "tp_dispatch_cost_us": round(
                    snap.get("serve.tp_dispatch_cost_us", 0.0), 1),
                "spec_acceptance_rate": round(
                    snap.get("serve.spec_acceptance_rate", 0.0), 4),
            }
            out[name] = row
            print(f"[curve] tp_ab {name}: {row['tokens_per_sec']:.0f} "
                  f"tok/s, {row['tp_dispatches']} sharded dispatches at "
                  f"{row['tp_dispatch_cost_us']:.0f} us each",
                  file=sys.stderr, flush=True)
    if SMOKE:
        # THE pin: one replica spanning a mesh slice is a pure layout
        # change — same tokens, same dispatch schedule, spec acceptance
        # untouched by sharding
        for name in ("tp2", "tp2_spec"):
            for a, b in zip(completions["tp1"], completions[name]):
                assert np.array_equal(a, b), (name, out)
        assert out["tp2"]["decode_ticks"] == out["tp1"]["decode_ticks"], out
        assert out["tp2"]["prefills"] == out["tp1"]["prefills"], out
        assert out["tp1"]["tp_dispatches"] == 0, out
        assert out["tp2"]["tp_dispatches"] > 0, out
        assert out["tp2"]["tp_dispatch_cost_us"] > 0, out
        assert out["tp2_spec"]["spec_acceptance_rate"] == 1.0, out
    return out


def trace_ab(hidden, depth, heads, vocab, max_len, prompt_len, steps,
             n_slots, steps_per_tick, dtype="float32", requests=32,
             repeats=3):
    """The tracing-overhead A/B arm: trace-on vs trace-off at EQUAL engine
    config on the SAME workload. The tracer's whole hot-path cost is one
    plain-bool branch per call site plus (when on) one dict append per
    event, so the honest claim is "within noise". Both engines stay live
    for the whole measurement and sweeps INTERLEAVE (off, on, off, on,
    ...) with best-of per arm — interleaving cancels the slow machine
    drift that dominates a run-arm-A-then-arm-B comparison on shared CI
    cores, and best-of de-noises the rest.
    DDW_BENCH_SMOKE pins trace-on tok/s within 3% of trace-off
    (docs/observability.md carries the measured numbers)."""
    import contextlib

    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests)]
    out = {"requests": requests, "steps": steps, "repeats": repeats}
    walls = {"trace_off": [], "trace_on": []}
    events = {"trace_off": 0, "trace_on": 0}
    with tempfile.TemporaryDirectory() as tmp, contextlib.ExitStack() as st:
        pm = _make_lm_pkg(tmp, "trace_ab", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        engines = {}
        for name, tr in (("trace_off", False), ("trace_on", True)):
            cfg = EngineCfg(n_slots=n_slots, steps_per_tick=steps_per_tick,
                            trace=tr, queue_depth=4 * requests,
                            default_timeout_s=600.0)
            eng = st.enter_context(ServingEngine(lm=pm, cfg=cfg))
            eng.warmup([prompt_len])
            eng.generate(prompts[0], steps)         # compile + warm cache
            engines[name] = eng

        def sweep(eng):
            t0 = time.perf_counter()
            futs = [eng.submit_generate(p, steps) for p in prompts]
            for f in futs:
                f.result(timeout=600)
            return time.perf_counter() - t0

        for _ in range(2):                          # warm residency, untimed
            for name, eng in engines.items():
                sweep(eng)
        for _ in range(repeats):
            for name, eng in engines.items():
                walls[name].append(sweep(eng))
        for name, eng in engines.items():
            events[name] = eng.tracer.summary()["events"]
    for name in walls:
        best = min(walls[name])
        out[name] = {
            "tokens_per_sec": round(requests * steps / best, 1),
            "walls_s": [round(w, 4) for w in walls[name]],
            "trace_events": events[name]}
    off, on = out["trace_off"], out["trace_on"]
    out["overhead_pct"] = round(
        100.0 * (1.0 - on["tokens_per_sec"] / off["tokens_per_sec"]), 2)
    print(f"[curve] trace_ab: off {off['tokens_per_sec']:.0f} tok/s, on "
          f"{on['tokens_per_sec']:.0f} tok/s ({out['overhead_pct']:+.1f}% "
          f"overhead, {on['trace_events']} events recorded)",
          file=sys.stderr, flush=True)
    if SMOKE:
        # the observability contract: tracing is cheap enough to leave on
        assert out["overhead_pct"] <= 3.0, out
        assert on["trace_events"] > 0, out
        assert off["trace_events"] == 0, out    # trace=False records nothing
    return out


def telemetry_ab(hidden, depth, heads, vocab, max_len, prompt_len, steps,
                 n_slots, steps_per_tick, dtype="float32", requests=32,
                 repeats=3, interval_s=0.05):
    """The telemetry-overhead A/B arm: telemetry-on vs telemetry-off at
    EQUAL engine config on the SAME workload — the trace_ab methodology
    verbatim (interleaved sweeps, best-of per arm, both engines live the
    whole run). Telemetry's hot-path cost is one plain-bool branch per
    finished request plus (when on) three ring appends; the sampler runs
    on its own thread off the request path, so the honest claim is the
    same "within noise". DDW_BENCH_SMOKE pins telemetry-on tok/s within
    3% of telemetry-off and that the off engine recorded ZERO samples
    (docs/observability.md carries the measured numbers)."""
    import contextlib

    from ddw_tpu.serve import EngineCfg, ServingEngine

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests)]
    out = {"requests": requests, "steps": steps, "repeats": repeats}
    walls = {"telemetry_off": [], "telemetry_on": []}
    samples = {"telemetry_off": 0, "telemetry_on": 0}
    with tempfile.TemporaryDirectory() as tmp, contextlib.ExitStack() as st:
        pm = _make_lm_pkg(tmp, "telemetry_ab", hidden, depth, heads, vocab,
                          max_len, dtype=dtype)
        engines = {}
        for name, tl in (("telemetry_off", False), ("telemetry_on", True)):
            cfg = EngineCfg(n_slots=n_slots, steps_per_tick=steps_per_tick,
                            telemetry=tl, telemetry_interval_s=interval_s,
                            queue_depth=4 * requests,
                            default_timeout_s=600.0)
            eng = st.enter_context(ServingEngine(lm=pm, cfg=cfg))
            eng.warmup([prompt_len])
            eng.generate(prompts[0], steps)         # compile + warm cache
            engines[name] = eng

        def sweep(eng):
            t0 = time.perf_counter()
            futs = [eng.submit_generate(p, steps) for p in prompts]
            for f in futs:
                f.result(timeout=600)
            return time.perf_counter() - t0

        for _ in range(2):                          # warm residency, untimed
            for name, eng in engines.items():
                sweep(eng)
        for _ in range(repeats):
            for name, eng in engines.items():
                walls[name].append(sweep(eng))
        for name, eng in engines.items():
            samples[name] = (eng.telem.summary()["samples"]
                             + eng.telem.samples_dropped
                             if eng.telem is not None else 0)
    for name in walls:
        best = min(walls[name])
        out[name] = {
            "tokens_per_sec": round(requests * steps / best, 1),
            "walls_s": [round(w, 4) for w in walls[name]],
            "telemetry_samples": samples[name]}
    off, on = out["telemetry_off"], out["telemetry_on"]
    out["overhead_pct"] = round(
        100.0 * (1.0 - on["tokens_per_sec"] / off["tokens_per_sec"]), 2)
    print(f"[curve] telemetry_ab: off {off['tokens_per_sec']:.0f} tok/s, "
          f"on {on['tokens_per_sec']:.0f} tok/s ({out['overhead_pct']:+.1f}%"
          f" overhead, {on['telemetry_samples']} samples recorded)",
          file=sys.stderr, flush=True)
    if SMOKE:
        # the observability contract: sampling is cheap enough to leave on
        assert out["overhead_pct"] <= 3.0, out
        assert on["telemetry_samples"] > 0, out
        assert off["telemetry_samples"] == 0, out  # telemetry=False: nothing
    return out


def main():
    from ddw_tpu.utils.config import require_tpu_or_exit

    kind = require_tpu_or_exit("measure")
    print(f"device: {kind}", file=sys.stderr, flush=True)

    if SMOKE:
        batches, img = [1, 4], (64, 64, 3)
        lm_kw = dict(hidden=64, depth=2, heads=4, vocab=256, max_len=128,
                     prompt_len=16, steps=8, spec_k=4)
        # wide enough that decode is weight-stream-bound — the regime the
        # batching win exists in (tests pin engine > sequential here)
        # f32 on the CPU smoke (bf16 matmuls emulate slowly on host and
        # drown the batching signal), wide enough (hidden 384) that decode
        # is weight-stream-bound — measured ~1.9x engine win at c=8, so the
        # strictly-above assertion has CI-noise margin
        eng_kw = dict(levels=[1, 4, 8], hidden=384, depth=3, heads=4,
                      vocab=256, max_len=128, prompt_len=16, steps=24,
                      n_slots=8, steps_per_tick=8, requests_per_level=32,
                      dtype="float32")
        cap_kw = dict(hidden=384, depth=3, heads=4, vocab=256, max_len=128,
                      prompt_len=24, steps=24, n_slots=8, steps_per_tick=8,
                      dtype="float32", shared_prefix=16)
        lane_kw = dict(hidden=64, depth=2, heads=4, vocab=256, max_len=128,
                       prompt_len=16, steps=24, n_slots=4,
                       steps_per_tick=8, dtype="float32", requests=24,
                       clients=4, batch_items=48)
        ab_kw = dict(hidden=384, depth=3, heads=4, vocab=256, max_len=128,
                     n_slots=4, steps_per_tick=4, dtype="float32",
                     families=6, shared_len=64, tail_len=8, rounds=3,
                     steps=4)
        # small model: the arm pins migration mechanics (identity +
        # counters + warm skip), not throughput — one CPU core serializes
        # both roles, so there is no separate-hosts win to measure
        disagg_kw = dict(hidden=64, depth=2, heads=4, vocab=256,
                         max_len=128, n_slots=4, steps_per_tick=4,
                         dtype="float32", families=4, shared_len=48,
                         tail_len=8, rounds=3, steps=4, clients=4)
        # steps_per_tick=1 so one decode tick == one target dispatch in
        # BOTH arms: ticks saved then reads directly as dispatches saved
        spec_kw = dict(hidden=64, depth=2, heads=4, vocab=256, max_len=128,
                       prompt_len=16, steps=24, n_slots=4,
                       steps_per_tick=1, spec_k=4, dtype="float32",
                       requests=8)
        # small model: the arm pins mechanics (identity + dispatch
        # counts), not throughput — fake-device collectives only cost
        tp_kw = dict(hidden=64, depth=2, heads=4, vocab=256, max_len=128,
                     prompt_len=16, steps=16, n_slots=4, steps_per_tick=4,
                     spec_k=4, dtype="float32", requests=6)
        # hidden 384 (weight-stream-bound decode) for the same reason as
        # eng_kw: long enough walls that the 3% overhead pin has margin
        # over 1-core timing noise, with best-of-3 de-noising on top
        trace_kw = dict(hidden=384, depth=3, heads=4, vocab=256,
                        max_len=128, prompt_len=16, steps=24, n_slots=8,
                        steps_per_tick=8, dtype="float32", requests=32,
                        repeats=5)
        telem_kw = dict(trace_kw)   # same regime, same noise-margin logic
    else:
        batches, img = [1, 2, 4, 8, 16, 32, 64, 128, 256], (224, 224, 3)
        lm_kw = dict(hidden=512, depth=6, heads=8, vocab=8192, max_len=2048,
                     prompt_len=64, steps=128, spec_k=4)
        eng_kw = dict(levels=[1, 2, 4, 8, 16, 32], hidden=512, depth=6,
                      heads=8, vocab=8192, max_len=2048, prompt_len=64,
                      steps=128, n_slots=16, steps_per_tick=8,
                      requests_per_level=64)
        cap_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                      max_len=2048, prompt_len=96, steps=128, n_slots=16,
                      steps_per_tick=8, shared_prefix=64)
        lane_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                       max_len=2048, prompt_len=64, steps=128, n_slots=16,
                       steps_per_tick=8, requests=64, clients=8,
                       batch_items=256)
        ab_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                     max_len=2048, n_slots=16, steps_per_tick=8,
                     families=8, shared_len=512, tail_len=32, rounds=4,
                     steps=16)
        disagg_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                         max_len=2048, n_slots=16, steps_per_tick=8,
                         families=8, shared_len=512, tail_len=32,
                         rounds=4, steps=16, clients=8)
        spec_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                       max_len=2048, prompt_len=64, steps=128, n_slots=16,
                       steps_per_tick=1, spec_k=4, requests=32)
        tp_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                     max_len=2048, prompt_len=64, steps=128, n_slots=16,
                     steps_per_tick=8, spec_k=4, requests=32)
        trace_kw = dict(hidden=512, depth=6, heads=8, vocab=8192,
                        max_len=2048, prompt_len=64, steps=128, n_slots=16,
                        steps_per_tick=8, requests=64, repeats=3)
        telem_kw = dict(trace_kw)

    result = {
        "device": {"kind": kind, "n": jax.device_count()},
        "image_curve": image_curve(batches, img),
        "lm": lm_latencies(**lm_kw),
        "engine": engine_load_sweep(**eng_kw),
        "paged_capacity": paged_capacity(**cap_kw),
        "batch_lanes": batch_lane_curve(**lane_kw),
        "routing_ab": routing_ab(**ab_kw),
        "disagg_ab": disagg_ab(**disagg_kw),
        "spec_ab": spec_ab(**spec_kw),
        "tp_ab": tp_ab(**tp_kw),
        "trace_ab": trace_ab(**trace_kw),
        "telemetry_ab": telemetry_ab(**telem_kw),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
