"""Layer: entry / set-up. Process start to the tables being written (imports
and the device look included)."""


def read(ctx):
    return ctx["tables_s"]
