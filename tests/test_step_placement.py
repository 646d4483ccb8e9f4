"""A step compiles once for each placement of its arguments, so a fit places
its state as the step returns it before the first call: every factory's
``place_state`` gives exactly that placement, placing moves bytes and changes
no number, and ``set_lr`` writes the rate where the leaf it replaces is."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ddw_tpu.models.lm import TransformerLM
from ddw_tpu.models.registry import build_model
from ddw_tpu.parallel.pipeline import init_pp_state, make_pp_lm_train_step
from ddw_tpu.parallel.sharding import LM_TP_RULES, make_sharded_train_step
from ddw_tpu.parallel.zero import (make_fsdp_train_chain, make_fsdp_train_step,
                                   make_zero_train_chain, make_zero_train_step)
from ddw_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, MeshSpec, make_mesh
from ddw_tpu.train.lm_step import (init_lm_state, make_lm_train_chain,
                                   make_lm_train_step)
from ddw_tpu.train.step import (TrainState, get_lr, init_state,
                                make_optimizer, make_train_chain,
                                make_train_step, set_lr, with_param_ema)
from ddw_tpu.utils.config import ModelCfg, TrainCfg

IMG = (16, 16, 3)
N = 4           # devices on the data axis


def _mesh(*axes):
    axes = axes or ((DATA_AXIS, N),)
    n = int(np.prod([size for _, size in axes]))
    return make_mesh(MeshSpec(axes), devices=jax.devices()[:n])


def _vision(make, mesh):
    """``(state, step, args)``: a tiny CNN, its eager state, the step or
    chain ``make`` builds for it and one call's other arguments."""
    mcfg = ModelCfg(name="small_cnn", num_classes=5, dropout=0.0,
                    dtype="float32")
    model = build_model(mcfg)
    state, tx = init_state(model, mcfg, TrainCfg(batch_size=2, optimizer="adam"),
                           IMG, jax.random.PRNGKey(0))
    step = make(model, tx, mesh, donate=False)
    rng = np.random.RandomState(0)
    lead = (2,) if "chain" in make.__name__ else ()     # [K, B, ...]
    images = rng.randn(*lead, 2 * N, *IMG).astype(np.float32)
    labels = rng.randint(0, 5, size=(*lead, 2 * N)).astype(np.int32)
    sharding = NamedSharding(mesh, P(*(None,) * len(lead), DATA_AXIS))
    return state, step, (jax.device_put(images, sharding),
                         jax.device_put(labels, sharding),
                         jax.random.PRNGKey(1))


def _lm(make, mesh):
    model = TransformerLM(vocab_size=32, max_len=32, hidden=16, depth=2,
                          num_heads=2, mlp_dim=32, dropout=0.0,
                          dtype=jnp.float32)
    tx = optax.adam(1e-2)
    if make is make_pp_lm_train_step:
        state = init_pp_state(model, tx, mesh, jax.random.PRNGKey(0))
        step = make(model, tx, mesh, num_microbatches=2, donate=False)
    elif make is make_sharded_train_step:
        state = init_lm_state(model, tx, jax.random.PRNGKey(0))
        step = make(model, tx, mesh, LM_TP_RULES)     # it donates its state
    else:
        state = init_lm_state(model, tx, jax.random.PRNGKey(0))
        step = make(model, tx, mesh, seq_axis=None, donate=False)
    chained = "chain" in make.__name__
    tokens = np.random.RandomState(0).randint(
        0, 32, size=(*((2,) if chained else ()), 2 * N, 17)).astype(np.int32)
    sharding = (step.super_batch_sharding if chained
                else getattr(step, "batch_sharding", None))
    batch = [tokens[..., :-1], tokens[..., 1:]]
    if sharding is not None:
        batch = [jax.device_put(x, sharding) for x in batch]
    rng = () if make is make_pp_lm_train_step else (jax.random.PRNGKey(1),)
    return state, step, (*batch, *rng)


FACTORIES = {
    "vision": (_vision, make_train_step, ()),
    "vision-chain": (_vision, make_train_chain, ()),
    "lm": (_lm, make_lm_train_step, ()),
    "lm-chain": (_lm, make_lm_train_chain, ()),
    "zero": (_vision, make_zero_train_step, ()),
    "zero-chain": (_vision, make_zero_train_chain, ()),
    "fsdp": (_vision, make_fsdp_train_step, ()),
    "fsdp-chain": (_vision, make_fsdp_train_chain, ()),
    "pipeline": (_lm, make_pp_lm_train_step, (("pipe", 2),)),
    "tensor-parallel": (_lm, make_sharded_train_step,
                        ((DATA_AXIS, 2), (MODEL_AXIS, 2))),
}


@pytest.mark.parametrize("name", FACTORIES)
def test_place_state_is_the_placement_the_step_returns(name):
    """Leaf for leaf: committed, and sharded as the step's output is — so the
    second call has the first call's signature and the step one executable."""
    build, make, axes = FACTORIES[name]
    state, step, args = build(make, _mesh(*axes))
    placed = step.place_state(state)
    out, _ = step(placed, *args)
    for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(out)):
        assert a.committed and b.committed
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (
            a.shape, a.sharding, b.sharding)
    step(out, *args)
    # the tensor-parallel step (no trainer builds it) lets GSPMD name its
    # outputs: P('model') for a leaf placed as P('model', None), the same
    # placement under another key, so it holds two (ROADMAP S2e)
    assert step._cache_size() == (2 if name == "tensor-parallel" else 1)


@pytest.mark.parametrize("name", ["vision", "lm"])
def test_placing_changes_no_number(name):
    """The first step from the placed state and from the eager one: the same
    loss and the same new state, bit for bit; only the second is a signature
    the step never sees again."""
    build, make, axes = FACTORIES[name]
    state, step, args = build(make, _mesh(*axes))
    eager, eager_metrics = step(state, *args)
    placed, placed_metrics = step(step.place_state(state), *args)
    assert step._cache_size() == 2
    assert np.asarray(eager_metrics["loss"]) == np.asarray(
        placed_metrics["loss"])
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("wrap", ["plain", "frozen", "ema"])
def test_set_lr_keeps_the_rate_where_it_was(wrap):
    """On a placed state the new rate is committed to the old one's
    sharding, through the masked and the EMA wrappers alike; on an eager
    state it stays uncommitted, as the rest of that state is."""
    cfg = TrainCfg(batch_size=2, optimizer="adam", learning_rate=1e-3)
    params = {"backbone": {"w": jnp.ones((4, 4))}, "head": {"w": jnp.ones(4)}}
    tx = make_optimizer(cfg, ("backbone",) if wrap == "frozen" else ())
    if wrap == "ema":
        tx = with_param_ema(tx, 0.9)
    state = TrainState(params, {}, tx.init(params), jnp.zeros((), jnp.int32))

    def rate_leaf(s):
        os_ = s.opt_state.inner if wrap == "ema" else s.opt_state
        if wrap == "frozen":
            os_ = os_.inner_states["train"].inner_state
        return os_.hyperparams["learning_rate"]

    eager = set_lr(state, 5e-4)
    assert not rate_leaf(eager).committed
    repl = NamedSharding(_mesh(), P())
    placed = jax.tree.map(lambda x: jax.device_put(x, repl), state)
    cut = set_lr(placed, 5e-4)
    leaf = rate_leaf(cut)
    assert leaf.committed and leaf.sharding == repl
    assert leaf.dtype == jnp.float32 and leaf.shape == ()
    assert get_lr(cut) == pytest.approx(5e-4)
    # nothing else was touched
    assert jax.tree.structure(cut) == jax.tree.structure(placed)
    assert sum(a is not b for a, b in zip(jax.tree.leaves(cut),
                                          jax.tree.leaves(placed))) == 1
