"""MoE routing characterization: capacity factor vs token drop rate, and the
aux-loss effect on balance entropy (VERDICT r2 item 7).

Trains the small MoE LM in four arms on the virtual 8-device EP mesh —
router in {top1 (Switch), top2 (GShard)} x load-balance aux loss {on (0.01,
Fedus et al. 2101.03961), off} — then sweeps each trained router over
capacity factors, measuring dropped dispatch-slot rate (slots past the
static capacity ``C = ceil(cf * k * T / E)``, k = choices per token, out of
``k*T`` slots) and normalized assignment entropy (1.0 = balanced, 0.0 =
collapsed). Routing semantics and the capacity formula come from
``ddw_tpu.models.moe.router_fn`` / ``expert_capacity`` — the exact code the
model runs. The tables it prints are CPU runs at toy width: the chip has not
measured them, and no document quotes them (``docs/DISTRIBUTED.md`` says how
to regenerate them).

Run:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=. python tools/moe_capacity_sweep.py
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ddw_tpu.models.lm import TransformerLM
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step

VOCAB = 64
EXPERTS = 8
SEQ = 32
BATCH = 16
STEPS = 120
CFS = (0.5, 0.75, 1.0, 1.25, 1.5, 2.0)


def build(expert_axis, router="top1"):
    return TransformerLM(vocab_size=VOCAB, max_len=SEQ, hidden=32, depth=2,
                         num_heads=2, mlp_dim=64, dropout=0.0,
                         dtype=jnp.float32, num_experts=EXPERTS,
                         expert_axis=expert_axis, capacity_factor=1.25,
                         moe_router=router)


def train(aux_weight: float, mesh, router="top1"):
    model = build(DATA_AXIS, router)
    tx = optax.adam(3e-3)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None,
                              aux_loss_weight=aux_weight)
    rng = np.random.RandomState(0)
    for i in range(STEPS):
        toks = rng.randint(0, VOCAB, size=(BATCH, SEQ + 1)).astype(np.int32)
        state, m = step(state, toks[:, :-1], toks[:, 1:], jax.random.PRNGKey(i))
    return state, float(m["loss"]), float(m["aux_loss"])


def router_stats(state, cf: float, router="top1") -> tuple[float, float]:
    """Mean (drop_rate, balance_entropy) over the model's MoE blocks for a
    fresh token batch at capacity factor ``cf`` (dense apply — the routing
    decision is mesh-independent)."""
    model = build(None, router)
    rng = np.random.RandomState(1)
    toks = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
    # the blocks sow their raw gate logits; re-run routing over them at the
    # probe cf (intermediates' own drop/entropy reflect the *trained* cf)
    _, mods = model.apply({"params": state.params}, jnp.asarray(toks),
                          train=False, mutable=["intermediates"])
    from ddw_tpu.models.moe import collect_sown, expert_capacity, router_fn

    gate_logits = collect_sown(mods, "gate_logits")
    route, k = router_fn(router)
    drops, ents = [], []
    for gl in gate_logits:
        t = gl.shape[0]
        cap = expert_capacity(cf, k, t, EXPERTS)
        _, _, _, stats = route(gl, cap)
        drops.append(float(stats["drop_rate"]))
        ents.append(float(stats["balance_entropy"]))
    return float(np.mean(drops)), float(np.mean(ents))


def main():
    mesh = make_mesh(MeshSpec(((DATA_AXIS, len(jax.devices())),)))
    print(f"mesh: {dict(mesh.shape)}  experts={EXPERTS}  "
          f"tokens/shard={BATCH * SEQ // mesh.shape[DATA_AXIS]}")
    rows = []
    for router in ("top1", "top2"):
        for aux_w in (0.01, 0.0):
            state, loss, aux = train(aux_w, mesh, router)
            for cf in CFS:
                drop, ent = router_stats(state, cf, router)
                rows.append((router, aux_w, cf, drop, ent, loss, aux))
    print(f"\n{'router':>6} {'aux_w':>6} {'cf':>5} {'drop%':>7} "
          f"{'entropy':>8} {'final_loss':>11} {'final_aux':>10}")
    for router, aux_w, cf, drop, ent, loss, aux in rows:
        print(f"{router:>6} {aux_w:>6} {cf:>5} {100 * drop:>6.1f}% "
              f"{ent:>8.3f} {loss:>11.3f} {aux:>10.3f}")


if __name__ == "__main__":
    main()
