#!/usr/bin/env python3
"""Split a kept profile's device time by the program's named scopes.

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace DIR
    python3 benchmark/tools/scope_shares.py DIR [--steps 8] [--top 12]

The train steps put ``jax.named_scope`` names on their operations
(``fwd_bwd``, ``loss``, ``grad_sync``, ``optimizer`` in ``train/step.py`` and
``train/lm_step.py``, ``attention`` at ``ops/flash_attention.py``'s dispatch).
The compiler carries them as each operation's ``op_name``, and the profile
keeps that in the ``tf_op`` stat of the event's METADATA (not of the event),
which ``jax.profiler.ProfileData`` does not hand out; so this reads the
``.xplane.pb`` with the protobuf classes TensorFlow ships.

Every operation of the ``XLA Ops`` line of each chip goes to the first of
these that its ``op_name`` holds: ``attention`` (forward, recomputation and
backward together), ``loss``, ``optimizer``, ``grad_sync``, then what is left
of ``fwd_bwd`` as ``forward`` (``jvp(`` without ``transpose(``) or
``backward``; anything else is ``other`` (the validation step, copies the
compiler added without a name). A share is of the summed operation time, a
mean over the chips.

Scopes are metadata, and JAX leaves metadata out of its compile-cache key: an
executable cached by a program without the scopes is loaded for a program
with them, and its profile has none. Take the profile with
``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=true`` set if in doubt.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ORDER = ("attention", "loss", "optimizer", "grad_sync")


def scope_of(op_name: str) -> str:
    for scope in ORDER:
        if (f"/{scope}/" in op_name or f"({scope})" in op_name
                or op_name.startswith(scope + "/")):
            return scope
    if "fwd_bwd" in op_name:
        return "backward" if "transpose(" in op_name else "forward"
    return "other"


def device_ops(path: str):
    """``{plane: [(hlo name, op_name, duration_ps), ...]}`` of the TPU planes'
    ``XLA Ops`` lines."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark.harness import trace_reduce

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        stat_ids = {k for k, v in plane.stat_metadata.items()
                    if v.name == "tf_op"}
        named = {}
        for key, meta in plane.event_metadata.items():
            op_name = next((s.str_value for s in meta.stats
                            if s.metadata_id in stat_ids), "")
            named[key] = (trace_reduce.op_name(meta.name), op_name)
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                out[plane.name] = [named[e.metadata_id] + (e.duration_ps,)
                                   for e in line.events]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a directory with a .xplane.pb, or the file")
    ap.add_argument("--steps", type=int, default=0,
                    help="optimizer steps in the profile: adds ms a step")
    ap.add_argument("--top", type=int, default=12,
                    help="operations listed under 'other' and 'attention'")
    args = ap.parse_args()

    from benchmark.harness import trace_reduce

    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    planes = device_ops(path)
    if not planes:
        raise SystemExit(f"scope_shares: no TPU plane in {path}")
    chips = len(planes)
    by_scope: dict = {}
    by_op: dict = {}
    for events in planes.values():
        for hlo, op_name, ps in events:
            scope = scope_of(op_name)
            by_scope[scope] = by_scope.get(scope, 0) + ps / chips
            key = (scope, trace_reduce.op_family(hlo), op_name)
            by_op[key] = by_op.get(key, 0) + ps / chips
    total = sum(by_scope.values())
    rows = sorted(by_scope.items(), key=lambda r: -r[1])
    print(f"scope_shares: {path}: {chips} chip(s), "
          f"{total / 1e9:.3f} ms of operations a chip")
    for scope, ps in rows:
        per_step = f"  {ps / 1e9 / args.steps:9.3f} ms/step" if args.steps else ""
        print(f"  {scope:10s} {100 * ps / total:6.2f} %  {ps / 1e9:10.3f} ms"
              + per_step)
    for scope in ("other", "attention"):
        top = sorted(((k, v) for k, v in by_op.items() if k[0] == scope),
                     key=lambda r: -r[1])[:args.top]
        for (_, family, op_name), ps in top:
            print(f"    {scope}: {100 * ps / total:5.2f} %  {family}  "
                  f"{op_name[-110:]}")
    print("scope_shares " + json.dumps(
        {"chips": chips, "ops_ms": total / 1e9,
         "share_pct": {s: 100 * ps / total for s, ps in rows}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
