"""Layer: train step, device. Device time a step of every operation with the
``mtp`` scope ANYWHERE in its path: the multi-token-prediction module of
``models/lm.py`` whole (the second embedding look-up, its two norms,
``mtp_proj``, the module's block with its attention, hyper-connections and
experts, the second product with the shared head) and its cross-entropy
(``train/lm_step.py``), forward, made again and backward. ``scope_time.join``
with ``mtp`` as the ONLY layer scope, so that the scopes inside the module do
not take its operations away as they do in the ``scope_*_ms`` split (where
the module's attention counts as ``attention``, its head as ``head``, and so
on: this metric overlaps those and is no part of their sum). Nothing to read
where the program recorded no table or nothing ran in the scope (a parent
without the module, or a configuration with ``num_nextn_predict_layers``
0)."""

from benchmark.metrics import scope_time
from benchmark.metrics.hyper_conn_ms import times_with


def read(ctx):
    times = times_with(ctx, {"mtp"}, "_scope_time_mtp")
    return scope_time.scope_ms(times, ["mtp"]) if times else None
