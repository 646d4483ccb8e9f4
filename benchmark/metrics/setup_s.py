"""End to end. Process start to the end of the first epoch's record: imports,
table writing, model init, optimizer state, compile or executable load, and
the first epoch's steps."""


def read(ctx):
    return ctx["setup_s"]
