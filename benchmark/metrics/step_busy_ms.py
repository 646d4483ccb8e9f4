"""Layer: train step, device. Union of the device operations' intervals in
the traced window (mean over the chips) over the optimizer steps dispatched in
it. The window is one epoch, so the epoch's one validation batch is inside."""


def read(ctx):
    red, traced = ctx["reduced"], ctx["traced"]
    if not red or not traced or not traced.get("steps"):
        return None
    return red["busy_mean_ns"] / traced["steps"] / 1e6
