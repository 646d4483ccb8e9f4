"""Chosen-key attention on the Pallas kernels (``ddw_tpu/ops/indexed_kernels
.py``), under the interpreter at sizes that cost seconds, against the XLA
tiles they replace on the same choice: the output, the KL terms and the
gradient to all six inputs; a block of the causal half in which no query
chose a key; what a block rematerialised whole runs twice; and which shapes
the kernels take. Since PR 46 the backward pass is one kernel
(``indexed_dkv``) whose dQ sums over the key blocks through a buffer in
HBM."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddw_tpu.models.lm import build_lm
from ddw_tpu.ops import indexed_kernels as ik
from ddw_tpu.ops.indexed_attention import _tier, indexed_attention
from ddw_tpu.train.lm_step import layer_terms, lm_loss
from ddw_tpu.utils.config import LayerSpec, LMCfg

H, KV = 8, 2


def _kernels(jaxpr, name):
    """The ``pallas_call`` equations named ``name``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernels(sub, name)


def _count(jaxpr, name) -> int:
    return len(list(_kernels(jaxpr, name)))


def _backward_kernels(fn, *args):
    return list(_kernels(jax.make_jaxpr(fn)(*args).jaxpr, "indexed_dkv"))


def _inputs(s, d, b=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (b, s, H, d))
    k, v = (jax.random.normal(kk, (b, s, KV, d)) for kk in keys[1:3])
    qi = jax.random.normal(keys[3], (b, s, 4, 16))
    ki = jax.random.normal(keys[4], (b, s, 16))
    wi = jax.random.normal(keys[5], (b, s, 4))
    return q, k, v, qi, ki, wi


def _weights(shape):
    return jnp.cos(jnp.arange(np.prod(shape), dtype=jnp.float32)
                   ).reshape(shape)


# S = 384 goes in 3 x 3 blocks of 128, S = 256 in one block and S = 512 in
# two q blocks of one key block: with one key block dQ has no sum to keep, and
# that one visit goes through the buffer in HBM like any other
@pytest.mark.parametrize("s,d", [(256, 64), (384, 64), (384, 128),
                                 (512, 128)])
def test_the_kernels_agree_with_the_xla_tiles(s, d):
    args = _inputs(s, d)

    def run(impl):
        def loss(*a):
            out, kl, chosen, choice = indexed_attention(
                *a, topk=s // 4, tile=128, impl=impl)
            return (jnp.sum(out * _weights(out.shape)) + jnp.sum(kl),
                    (out, kl, chosen, choice))
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                          has_aux=True))(*args)

    (_, (out, kl, chosen, choice)), grads = run("pallas")
    (_, (out_x, kl_x, chosen_x, choice_x)), grads_x = run("xla")
    assert len(_backward_kernels(
        jax.grad(lambda *a: jnp.sum(indexed_attention(
            *a, topk=s // 4, tile=128, impl="pallas")[0])), *args)) == 1
    np.testing.assert_array_equal(choice, choice_x)
    np.testing.assert_array_equal(chosen, chosen_x)
    np.testing.assert_allclose(out, out_x, atol=2e-5)
    np.testing.assert_allclose(kl, kl_x, atol=2e-5)
    for name, got, want in zip("q k v qi ki wi".split(), grads, grads_x):
        np.testing.assert_allclose(got, want, atol=5e-5, err_msg=name)
    # the indexer learns from the KL term and the attention does not
    assert all(float(jnp.max(jnp.abs(g))) > 1e-3 for g in grads)


def _dense(q, k, v, mask):
    """Attention under a mask and the heads' summed probabilities, plainly."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, s, kv, h // kv, d),
                        k) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(mask[:, None, None] != 0, scores, -1e30),
                           axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, h, d)
    return out, jnp.sum(probs, axis=(1, 2)) / h


def _holed_mask(s, block):
    """Causal, and the queries of the last block choose no key of block 1."""
    mask = jnp.tril(jnp.ones((s, s), jnp.int8))
    return mask.at[s - block:, block:2 * block].set(0)[None]


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128)])
def test_a_block_nobody_chose_from_changes_nothing(block_q, block_k):
    """A block of the causal half in which no query chose a key is visited
    (the kernels skip by position alone) and is an exact no-op: the running
    maximum's guard keeps a row that has chosen nothing yet at zero. In the
    backward kernel the hole's visit adds zeros to dQ's sum (two to four key
    blocks)."""
    s, d = 512, 64
    q, k, v = _inputs(s, d, seed=1)[:3]
    mask = _holed_mask(s, 128)

    def run(attend):
        def loss(q, k, v):
            out, target = attend(q, k, v)
            return jnp.sum(out * _weights(out.shape)), (
                out, jnp.where(mask != 0, target, 0.0))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    got = run(lambda *a: ik.attend_chosen(*a, mask, block_q=block_q,
                                          block_k=block_k))
    want = run(lambda *a: _dense(*a, mask))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    # and the keys of the hole get no gradient from the queries that left it
    hole = run(lambda *a: ik.attend_chosen(
        *a, mask.at[:, :s - 128].set(0).at[:, :, 0].set(1), block_q=block_q,
        block_k=block_k))[1]
    assert not jnp.any(hole[1][:, 128:256]) and not jnp.any(hole[2][:, 128:256])


# s, block_q, block_k: one key block; two; four with two q blocks each (a q
# block's last key block is not the grid's last); eight q blocks of four
_BACKWARD_CASES = {
    "one_key_block": (256, 128, 256),
    "two_key_blocks": (256, 128, 128),
    "wide_q_blocks": (512, 256, 128),
    "wide_k_blocks": (512, 64, 128),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(_BACKWARD_CASES))
def test_one_backward_kernel_makes_all_three_gradients(case, dtype):
    """``attend_chosen``'s gradients to q, k and v against plain attention
    under the same mask (a choice with a hole where the blocks allow one):
    float32 to rounding; bfloat16 operands against the float32 gradients of
    the same rounded operands, to a bfloat16 step of the largest entry (dQ is
    summed in float32 and rounded once)."""
    s, block_q, block_k = _BACKWARD_CASES[case]
    d = 64 if dtype == jnp.float32 else 128
    q, k, v = (x.astype(dtype) for x in _inputs(s, d, b=2, seed=3)[:3])
    mask = jnp.tile(_holed_mask(s, 128) if s > 256
                    else jnp.tril(jnp.ones((s, s), jnp.int8))[None], (2, 1, 1))
    weights = _weights((2, s, H, d))

    def grads(attend, *operands):
        return jax.grad(lambda *a: jnp.sum(
            attend(*a)[0].astype(jnp.float32) * weights),
            argnums=(0, 1, 2))(*operands)

    kernel = lambda *a: ik.attend_chosen(                      # noqa: E731
        *a, mask, block_q=block_q, block_k=block_k)
    call, = _backward_kernels(lambda *a: grads(kernel, *a), q, k, v)
    assert call.params["grid_mapping"].grid == (2, KV, s // block_k,
                                                s // block_q)
    got = grads(kernel, q, k, v)
    want = grads(lambda *a: _dense(*a, mask),
                 *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5,
                                       err_msg=name)
        else:
            step = float(jnp.max(jnp.abs(b))) * 2.0 ** -7
            np.testing.assert_allclose(a.astype(jnp.float32), b, atol=step,
                                       err_msg=name)


def test_the_backward_at_its_own_key_block():
    """At the code's own blocks a sequence that 1,024 divides goes forward in
    key blocks of 512 and backward in blocks of 1,024 (two of them here): the
    gradients against plain attention under a choice with a hole."""
    s, d = 2048, 64
    assert ik.pick_blocks(s) == (256, 512, 1024)
    q, k, v = _inputs(s, d, seed=5)[:3]
    mask = _holed_mask(s, 512)
    weights = _weights((1, s, H, d))

    def grads(attend):
        return jax.grad(lambda *a: jnp.sum(attend(*a)[0] * weights),
                        argnums=(0, 1, 2))

    kernel = grads(lambda *a: ik.attend_chosen(*a, mask))
    call, = _backward_kernels(kernel, q, k, v)
    assert call.params["grid_mapping"].grid == (1, KV, 2, 8)
    for name, a, b in zip("qkv", kernel(q, k, v),
                          grads(lambda *a: _dense(*a, mask))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5, err_msg=name)


def test_the_mask_waits_for_the_cotangent():
    """The backward pass holds the mask and the output's cotangent behind
    one ``optimization_barrier``: a mask made again under ``remat`` is then
    made when the cotangent is there and not a layer's expert blocks earlier
    (PERF.md section 6, PR 46: 128 MiB of the cell's peak)."""
    s, d = 256, 64
    q, k, v = _inputs(s, d)[:3]
    mask = jnp.tril(jnp.ones((1, s, s), jnp.int8))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ik.attend_chosen(*a, mask)[0]), argnums=(0, 1, 2))
    )(q, k, v).jaxpr
    barrier, = (eqn for eqn in jaxpr.eqns
                if eqn.primitive.name == "optimization_barrier")
    assert {(v.aval.shape, str(v.aval.dtype)) for v in barrier.invars} == {
        ((1, s, s), "int8"), ((1, s, H, d), "float32")}


def _lm(remat, seq=512):
    spec = LayerSpec(norm="rmsnorm", bias=False, head_dim=64, qk_norm=True,
                     attention="indexed", index_heads=2, index_head_dim=8,
                     index_topk=seq // 4, index_tile=128, mlp="swiglu")
    return build_lm(LMCfg(vocab_size=64, max_len=seq, hidden=64, depth=2,
                          num_heads=2, num_kv_heads=1, mlp_dim=64,
                          dropout=0.0, dtype="float32", pos_encoding="rope",
                          remat=remat, layer=spec))


@pytest.mark.parametrize("remat", ["none", "full"])
def test_no_forward_kernel_runs_twice(remat):
    """A block rematerialised whole keeps the choice, the attention's output
    and its log-sum-exp (``models/lm.py`` saves the names), so the backward
    pass holds the forward kernel once a layer; the target, which nothing
    keeps, is made again. The backward is one kernel a layer, ``indexed_dkv``,
    and no ``indexed_dq`` runs (the benchmark counts both names)."""
    model, seq = _lm(remat), 512
    tokens = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]

    def loss(p):
        logits, mods = model.apply({"params": p}, tokens, train=True,
                                   mutable=["intermediates"])
        return lm_loss(logits, tokens) + layer_terms(mods)["indexer_kl"]

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    counts = {name: _count(jaxpr, name) for name in
              ("indexed_fwd", "indexed_dq", "indexed_dkv", "indexed_target")}
    assert counts == {"indexed_fwd": 2, "indexed_dq": 0, "indexed_dkv": 2,
                      "indexed_target": 4 if remat == "full" else 2}


@pytest.mark.parametrize("s,h,kv,d,tier", [
    (512, 32, 4, 128, "pallas"), (8192, 32, 4, 128, "pallas"),
    (640, 8, 2, 64, "pallas"),
    (256, 8, 2, 64, "xla"),         # short
    (576, 8, 2, 128, "xla"),        # no block divides it
    (512, 8, 2, 32, "xla"),         # a head dim the kernels were not built for
    (512, 6, 4, 128, "xla"),        # no whole query groups
])
def test_the_shapes_choose_the_tier(s, h, kv, d, tier):
    q = jax.ShapeDtypeStruct((2, s, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, s, kv, d), jnp.bfloat16)
    assert _tier(q, k, "auto") == tier
    assert _tier(q, k, "xla") == "xla" and _tier(q, k, "pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown impl"):
        _tier(q, k, "flash")


def test_a_shape_the_kernels_do_not_take_runs_the_xla_tiles():
    args = _inputs(64, 16)
    jaxpr = jax.make_jaxpr(
        lambda *a: indexed_attention(*a, topk=16, tile=16))(*args).jaxpr
    assert not any(_count(jaxpr, name) for name in
                   ("indexed_fwd", "indexed_target"))
    with pytest.raises(ValueError, match="no multiple"):
        ik.attend_chosen(*args[:3], jnp.ones((1, 64, 64), jnp.int8))
