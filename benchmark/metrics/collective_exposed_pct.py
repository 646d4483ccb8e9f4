"""Layer: collectives. Share of the traced window in which a collective runs
and no other operation does on that chip, worst chip."""


def read(ctx):
    red = ctx["reduced"]
    if not red or ctx["chips"] < 2 or not red["collective_ns"]:
        return None
    return 100.0 * red["collective_exposed_ns"] / red["window_ns"]
