"""Chosen-key attention timed on the chip, one JSON line an arm.

At the shape of the ``keyevl2_train_s8192`` cell (``[2, 8192, 32 / 4, 128]``
bfloat16, 16 index heads of 64, top 2,048; ``--seq`` for another length), on
a choice the real choose pass made from random index inputs:

- ``kernels``: each of the three kernels of ``ops/indexed_kernels.py`` alone
  (``indexed_fwd``, ``indexed_dkv`` -- the whole backward pass -- and
  ``indexed_target``) over (block_q, block_k), the code's own blocks first
  (``chosen`` for the forward and the target, ``chosen_bwd`` the backward's),
  with the share of the causal half's block pairs in which some query chose
  a key (the kernels visit them all: what skipping the others could earn);
- ``tiers``: ``indexed_attention`` forward + backward to all six inputs on
  the kernels and on the XLA tiles, the KL terms alone (from a given target),
  and how far the two tiers' outputs and gradients lie apart.

An arm that fails to compile or to fit (blocks over the 16 MiB of VMEM a
kernel gets unasked) prints ``"ms": null`` and the error.

Run on the TPU:  python tools/indexed_sweep.py [--only kernels|tiers]
    [--grid chosen|all] [--seq S] [--batch B]
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import json

import jax
import jax.numpy as jnp
from jax import lax

from ddw_tpu.ops import indexed_attention as ia
from ddw_tpu.ops import indexed_kernels as ik
from tools.fa2_sweep import time_ms

BLOCK_GRID = ((512, 512), (256, 512), (512, 256), (256, 256), (128, 512),
              (256, 1024), (128, 1024))


def inputs(b, s, h, kv, d, j, di, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = lambda key, shape: jax.random.normal(key, shape, jnp.bfloat16)
    q, g = bf(keys[0], (b, s, h, d)), bf(keys[6], (b, s, h, d))
    k, v = bf(keys[1], (b, s, kv, d)), bf(keys[2], (b, s, kv, d))
    qi = jax.random.normal(keys[3], (b, s, j, di))
    ki = jax.random.normal(keys[4], (b, s, di))
    wi = jax.random.normal(keys[5], (b, s, j))
    return q, k, v, g, qi, ki, wi


def emit(row: dict, device: str):
    print(json.dumps(dict(row, device=device)), flush=True)


def timed(row, device, fn, *args):
    try:
        row["ms"] = round(time_ms(fn, *args, min_s=0.2), 4)
    except Exception as e:
        row.update(ms=None, error=f"{type(e).__name__}: {e}"[:300])
    emit(row, device)


def run_kernels(q, k, v, g, mask, device, grid):
    s, d = q.shape[1], q.shape[-1]
    scale = float(d) ** -0.5
    *chosen, bk_bwd = ik.pick_blocks(s)
    chosen, chosen_bwd = tuple(chosen), (chosen[0], bk_bwd)
    # the backward's and the target's inputs, which no block size changes
    out, lse = ik._forward(q, k, v, mask, scale, *chosen,
                           ik.interpret_by_default())
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1).transpose(0, 2, 1).reshape(lse.shape)
    for bq, bk in dict.fromkeys((chosen, chosen_bwd) + tuple(grid)):
        if s % bq or s % bk:
            continue
        nq, nk = s // bq, s // bk
        occupied = int(jnp.sum(jnp.max(
            mask.reshape(-1, nq, bq, nk, bk), axis=(2, 4))))
        causal = sum(kb * bk < (qb + 1) * bq
                     for qb in range(nq) for kb in range(nk)) * q.shape[0]
        static = (scale, bq, bk, ik.interpret_by_default())
        row = {"preset": "kernels", "blocks": [bq, bk], "shape": list(q.shape),
               "chosen": (bq, bk) == chosen,
               "chosen_bwd": (bq, bk) == chosen_bwd,
               "occupied_share": round(occupied / causal, 4)}
        bwd_args = (q, k, v, mask, g, lse, dvec)
        arms = {
            "indexed_fwd": (lambda: ik._forward(q, k, v, mask, *static)),
            "indexed_dkv": (lambda: ik._backward(*bwd_args, *static)),
            "indexed_target": (lambda: ik._target(q, k, mask, lse, *static)),
        }
        for name, fn in arms.items():
            timed(dict(row, arm=name), device, fn)


def run_tiers(q, k, v, g, qi, ki, wi, topk, tile, device):
    def fwd_bwd(impl):
        def loss(*a):
            out, kl, _, _ = ia.indexed_attention(*a, topk=topk, tile=tile,
                                                 impl=impl)
            return (jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))
                    + jnp.sum(kl)), (out, kl)
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                          has_aux=True))

    results = {}

    def tier(impl):
        fn = fwd_bwd(impl)
        timed({"preset": "tiers", "arm": impl, "shape": list(q.shape)},
              device, fn, q, k, v, qi, ki, wi)
        try:
            results[impl] = fn(q, k, v, qi, ki, wi)
        except Exception:
            pass

    tier("pallas")
    # the KL terms alone: forward and the gradient to the indexer's inputs
    s = q.shape[1]
    masks, scored = lax.map(
        lambda a: ia._choose_row(*a, topk=topk, tile=tile), (qi, ki, wi))
    target = jnp.where(ia._whole(masks, s), 1.0 / topk, 0.0)

    def kl_loss(qi, ki, wi):
        return jnp.sum(lax.map(lambda a: ia._row_kl(*a, tile=tile),
                               (target, qi, ki, wi, masks, scored)))
    timed({"preset": "tiers", "arm": "kl_terms", "shape": list(q.shape)},
          device, jax.jit(jax.value_and_grad(kl_loss, argnums=(0, 1, 2))),
          qi, ki, wi)
    timed({"preset": "tiers", "arm": "choose", "shape": list(q.shape)},
          device, jax.jit(lambda *a: lax.map(
              lambda r: ia._choose_row(*r, topk=topk, tile=tile)[0], a)),
          qi, ki, wi)
    tier("xla")

    if len(results) == 2:
        (_, (out, kl)), grads = results["pallas"]
        (_, (out_x, kl_x)), grads_x = results["xla"]
        far = lambda a, b: round(float(                        # noqa: E731
            jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
            / jnp.linalg.norm(b.astype(jnp.float32).ravel())), 6)
        emit({"preset": "tiers", "arm": "distance", "out": far(out, out_x),
              "kl": far(kl, kl_x),
              **{"d" + n: far(a, b) for n, a, b in
                 zip("q k v qi ki wi".split(), grads, grads_x)}}, device)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", choices=("kernels", "tiers"), default=None)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--grid", choices=("chosen", "all"), default="all")
    args = ap.parse_args()
    device = f"{jax.devices()[0].platform}:{jax.devices()[0].device_kind}"
    topk, tile = args.seq // 4, min(512, args.seq)
    q, k, v, g, qi, ki, wi = inputs(args.batch, args.seq, 32, 4, 128, 16, 64)
    if args.only != "tiers":
        masks, _ = lax.map(lambda a: ia._choose_row(*a, topk=topk, tile=tile),
                           (qi, ki, wi))
        mask = ia._whole(masks, args.seq).astype(jnp.int8)
        run_kernels(q, k, v, g, mask, device,
                    BLOCK_GRID if args.grid == "all" else ())
    if args.only != "kernels":
        run_tiers(q, k, v, g, qi, ki, wi, topk, tile, device)


if __name__ == "__main__":
    main()
