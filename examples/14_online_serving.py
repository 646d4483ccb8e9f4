"""Contract 14 — online serving: continuous batching under concurrent load.

The reference stack stops at offline scoring (`mlflow.pyfunc.spark_udf`
over static tables); this example runs the missing online half
(``ddw_tpu.serve``, docs/serving.md) end-to-end on CPU:

1. package a small TransformerLM, start a :class:`ServingEngine` with a
   4-slot KV-cache pool, warm the program lattice, and fire a burst of
   concurrent generate requests with varied prompt lengths — every output
   is verified token-identical to the sequential single-request
   ``LMPackagedModel.generate`` path (the continuous-batching determinism
   contract);
2. overload a tiny queue and catch the structured ``Overloaded``
   backpressure reply (capacity/depth/retry hint — a refusal, not a hang);
3. print the engine's SLO snapshot: queue/TTFT/latency percentiles and
   aggregate tokens/sec.

Engine architecture, slot lifecycle, and the knob table: docs/serving.md.

    PYTHONPATH=. python examples/14_online_serving.py --quick
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("overrides", nargs="*", help="lm.key=value")
    args = ap.parse_args()
    from ddw_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    overrides = args.overrides

    import jax
    import numpy as np

    from ddw_tpu.models.lm import build_lm
    from ddw_tpu.serve import EngineCfg, Overloaded, ServingEngine
    from ddw_tpu.serving.lm_package import (load_lm_package,
                                            save_lm_package)
    from ddw_tpu.utils.config import LMCfg, apply_overrides

    cfgs = {"lm": LMCfg(vocab_size=128, max_len=96, hidden=64, depth=2,
                        num_heads=4, mlp_dim=128, dropout=0.0,
                        dtype="float32")}
    apply_overrides(cfgs, overrides)
    cfg = cfgs["lm"]
    model = build_lm(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int32))["params"]

    workdir = args.workdir or tempfile.mkdtemp(prefix="ddw_online_serving_")
    pm = load_lm_package(
        save_lm_package(os.path.join(workdir, "lm_pkg"), cfg, params))

    rng = np.random.RandomState(0)
    lens = [int(rng.randint(3, 24)) for _ in range(args.requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in lens]

    print(f"[1] continuous batching: {args.requests} concurrent requests, "
          f"{args.slots} slots, prompt lengths {min(lens)}..{max(lens)}")
    refs = [pm.generate(p[None, :], args.steps)[0] for p in prompts]
    ecfg = EngineCfg(n_slots=args.slots, steps_per_tick=4)
    with ServingEngine(lm=pm, cfg=ecfg) as eng:
        eng.warmup(sorted(set(lens)))
        futs = [eng.submit_generate(p, args.steps) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.snapshot()
    matches = sum(bool(np.array_equal(o.tokens, r))
                  for o, r in zip(outs, refs))
    print(f"    engine_matches_sequential={matches}/{args.requests} "
          f"(prefills={int(snap['serve.prefills'])}, "
          f"decode_ticks={int(snap['serve.decode_ticks'])})")
    assert matches == args.requests

    print("[2] backpressure: queue_depth=2, third submission refused")
    eng = ServingEngine(lm=pm, cfg=EngineCfg(n_slots=1, queue_depth=2))
    eng.submit_generate(prompts[0], 4)
    eng.submit_generate(prompts[1], 4)
    try:
        eng.submit_generate(prompts[2], 4)
        raise SystemExit("expected Overloaded")
    except Overloaded as e:
        print(f"    overloaded={e.to_dict()}")
    finally:
        eng.stop()

    print("[3] SLO snapshot (the numbers a serving SLO is written against)")
    for key in ("serve.completed", "serve.queue_ms_p50", "serve.ttft_ms_p50",
                "serve.ttft_ms_p99", "serve.total_ms_p99",
                "serve.tokens_per_sec"):
        print(f"    {key} = {snap[key]:.1f}")

    print("online serving: token-identical continuous batching with "
          "structured backpressure and tracked SLO metrics")


if __name__ == "__main__":
    main()
