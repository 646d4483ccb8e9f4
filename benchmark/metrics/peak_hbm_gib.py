"""Layer: train step, device. What the fullest chip held at its peak: the
runtime's ``peak_bytes_in_use`` (state, batches, executables) plus its
``peak_bytes_reserved`` (the scratch a running step reserves, which the first
counter does not see). Read after the window, before the reference runs; the
same number as the result line's ``memory_peak_bytes``."""


def read(ctx):
    return ctx["peak_bytes"] / 2 ** 30 if ctx["peak_bytes"] else None
