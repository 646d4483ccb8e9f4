#!/usr/bin/env python3
"""Print what a profiler trace holds — planes, their lines, the commonest
event names of each line — to look at one by hand before trusting the
reduction (``harness/trace_reduce.py``).

    python3 benchmark/tools/trace_layout.py <dir-or-xplane.pb> [--json out]
"""

import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from jax.profiler import ProfileData

    from benchmark.harness import trace_reduce

    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(
                trace_reduce.op_family(trace_reduce.op_name(e.name))
                for e in events)
            total = sum(e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{total / 1e6:.3f} ms; commonest {names.most_common(6)}")
    if "--json" in sys.argv:
        record = trace_reduce.load_xplane(path)
        with open(sys.argv[sys.argv.index("--json") + 1], "w") as f:
            json.dump({k: record[k] for k in ("devices", "modules")}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
