"""Layer: entry and set-up. The program's ``fit_setup`` span: entry of ``fit``
to the first epoch's first ``train_chain`` (model and optimizer state, the
step's construction, restore, loaders)."""


def read(ctx):
    setups = [s["dur"] for s in ctx["spans"] if s["name"] == "fit_setup"]
    return setups[0] / 1e6 if setups else None
