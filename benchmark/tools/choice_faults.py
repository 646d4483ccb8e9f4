#!/usr/bin/env python3
"""The controls of ``keyevl2_train_s8192``'s choice limits: what the four
numbers that hold the program's choices of keys and experts to the reference's
own scores (``reference/keye_vl2.py::choice_margins``) read when the choice is
sound and when a fault is planted in it, at the cell's own size.

Sound: the program's choices at the seeded weights (the family's
``read_choices``, as a run of the cell reads them; the reference's own read
zero by construction, tests/test_keye_vl2.py). Faults, made by the reference on its own scores
(``own_choices``): every matrix product's operands rounded to bfloat16 — index
scores and router logits in the precision below the one the configuration
states for them — and the choice off by N ranks, the wrong choice that lies
nearest the right one. The limits in ``cells/<workload>.json`` lie between the
sound readings' largest and the faults' smallest (PERF.md section 2).

    python3 benchmark/tools/choice_faults.py --workload keyevl2_train_s8192 \\
        --seeds 11,12 --out chiprun_out/choice_faults.jsonl
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# name -> (precision of the reference's products, ranks the choice of keys is
# off by as a share of topk, ranks the choice of experts is off by)
FAULTS = {"scores_bf16": ("bf16", 0.0, 0), "off_by_1_32nd": ("f32", 1 / 32, 1),
          "off_by_1_8th": ("f32", 1 / 8, 2)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--tiny", action="store_true",
                    help="the family's tiny sizes, on whatever device JAX has")
    args = ap.parse_args()

    import jax

    from benchmark import run as bench_run
    from benchmark.harness.manifest import Cell, load_manifest
    from benchmark.harness.step_probe import program_tree
    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import keye_vl2
    from ddw_tpu.models.lm import build_lm

    cell = Cell(load_manifest(), args.workload)
    family = cell.family
    tiny = family.TINY if args.tiny else {}
    config = dict(cell.config, **tiny.get("config", {}))
    traffic = dict(cell.traffic, **tiny.get("traffic", {}))
    if not args.tiny:
        bench_run.find_devices(cell.chips)
        bench_run.configure_cache()
    seq, rows = traffic["seq_len"], traffic["batch_per_chip"]
    spec, mapping = family.reference_spec(config), family.leaf_map(config)
    model = build_lm(family._lm_cfg(config, traffic))
    topk = config["sa_config"]["topk"]

    weights_of = jax.jit(lambda key: seeded_weights(key, spec))
    margins = jax.jit(lambda w, x: keye_vl2.choice_margins(w, x, seq, config))
    program = jax.jit(family.read_choices, static_argnums=0)
    faulty = {name: jax.jit(
        lambda w, x, p=p, shift=(int(share * topk), e):
        keye_vl2.own_choices(w, x, config, p, shift))
        for name, (p, share, e) in FAULTS.items()}

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        weights = weights_of(seed_key(seed))
        tokens = family.make_corpus(seed % (2 ** 31 - 1), rows, seq,
                                    config["vocab_size"])[:, :-1]
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
        params = jax.jit(lambda w: program_tree(shapes, mapping, w))(weights)
        handed = {"program_sound": program(model, params, tokens)}
        del params
        for name, make in faulty.items():
            handed[name] = make(weights, tokens)
        row = {"workload": cell.name, "seed": seed, "readings": {
            name: {k: float(v) for k, v in margins(weights, x).items()}
            for name, x in handed.items()}, "took_s": time.time() - t0}
        print("choice_faults " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
