"""Layer: entry and set-up. Sum of the first epoch's ``dispatch`` and
``val_dispatch`` spans: the calls that compile or load the executables (the
train step's twice today, PERF.md section 7b)."""


def read(ctx):
    epochs = [s for s in ctx["spans"] if s["name"] == "epoch"]
    if not epochs:
        return None
    first = min(epochs, key=lambda s: s["ts"])
    end = first["ts"] + first["dur"]
    loads = [s["dur"] for s in ctx["spans"]
             if s["name"] in ("dispatch", "val_dispatch")
             and first["ts"] <= s["ts"] < end]
    return sum(loads) / 1e6 if loads else None
