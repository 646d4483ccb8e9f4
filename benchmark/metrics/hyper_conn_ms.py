"""Layer: train step, device. Device time a step of the operations whose
innermost layer scope is ``hyper_conn``: the hyper-connections of
``models/lm.py`` (``HyperConnection``, ``hyper_read``, ``hyper_write``, the
streams' sum at the exit): the flattened norm, the product with ``phi``, the
sigmoids, the Sinkhorn rounds, a sublayer's read of the streams, its write to
all of them and their mix, forward, made again and backward, in the trunk's
blocks and the MTP module's. Read by ``scope_time.join`` from the device trace
joined with the program's ``step_scopes`` table, with the layer set of
``scope_time.json`` PLUS ``hyper_conn`` and ``mtp``: that file names neither
scope yet (it is not this PR's to edit), so the ``scope_*_ms`` readers see
these operations under no layer scope and this cell's ``scope_other_ms`` also
holds ``hyper_conn``'s time, until a ``benchmark`` PR enters the two scopes
there (PERF.md sections 3 and 7). Nothing to read where the program recorded
no table or nothing ran in the scope (a parent without the scope)."""

from benchmark.metrics import scope_time

NEW_SCOPES = ("hyper_conn", "mtp")


def times_with(ctx: dict, layers, key: str) -> dict:
    """``scope_time.join`` of a benchmark run under another layer set, made
    once and kept on ``ctx`` under ``key``."""
    if key not in ctx:
        record, traced = ctx.get("record"), ctx.get("traced") or {}
        table = scope_time.find_table(ctx.get("spans"))
        ctx[key] = (scope_time.join(record, table, set(layers),
                                    traced.get("steps"))
                    if record and record.get("devices") and table else {})
    return ctx[key]


def read(ctx):
    layers = set(scope_time.scope_map()["layers"]) | set(NEW_SCOPES)
    times = times_with(ctx, layers, "_scope_time_hyper")
    return scope_time.scope_ms(times, ["hyper_conn"]) if times else None
