"""Layer: entry / set-up. Building the trainer to the first epoch's record:
model init, optimizer state, compile or executable load, first steps."""


def read(ctx):
    return ctx["first_epoch_s"]
