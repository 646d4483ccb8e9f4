"""Layer: collectives. Summed length of the collective operations per
optimizer step, worst chip. Nothing to read on one chip."""


def read(ctx):
    red, traced = ctx["reduced"], ctx["traced"]
    if (not red or ctx["chips"] < 2 or not traced or not traced.get("steps")
            or not red["collective_ns"]):
        return None
    return red["collective_ns"] / traced["steps"] / 1e6
