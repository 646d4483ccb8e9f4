"""Layer: device. 1 - busy union over the traced window, on the chip that
was busy least."""


def read(ctx):
    red = ctx["reduced"]
    if not red:
        return None
    return 100.0 * (1.0 - red["busy_min_ns"] / red["window_ns"])
