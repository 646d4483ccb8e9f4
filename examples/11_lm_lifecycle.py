"""Contract 11 (beyond parity) — the full LM lifecycle in one pass.

The image side walks prep → train → package → distributed scoring (examples
01–06, the reference's workshop arc); this is the same arc for the language
model family: train with the managed LMTrainer (DP×SP mesh, LR schedules,
checkpoints, tracker), package the result as a self-contained artifact
(optionally int8), then drive the artifact the way a scorer worker would —
per-sequence NLL scoring, greedy generation, and draft-verified speculative
decoding against a smaller packaged draft.

    PYTHONPATH=. python examples/11_lm_lifecycle.py --quick [--int8]
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import dataclasses

import jax
import numpy as np

from ddw_tpu.runtime.mesh import DATA_AXIS
from ddw_tpu.serving import load_lm_package, save_lm_package
from ddw_tpu.tracking.tracker import Tracker
from ddw_tpu.train.lm_trainer import LMTrainer
from ddw_tpu.utils.compile_cache import enable_compile_cache
from ddw_tpu.utils.config import LMCfg, TrainCfg, apply_overrides


def synthetic_text(rng, n, seq, vocab):
    """Arithmetic sequences mod vocab — memorizable structure."""
    starts = rng.randint(0, vocab, size=(n, 1))
    steps = rng.randint(1, 5, size=(n, 1))
    return ((starts + steps * np.arange(seq + 1)[None]) % vocab
            ).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="package int8 weight-only artifacts")
    ap.add_argument("--workdir", default="/tmp/ddw_tpu_workshop")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args()
    enable_compile_cache()

    cfgs = {"lm": LMCfg(), "train": TrainCfg(warmup_epochs=0)}
    if args.quick:
        cfgs["lm"] = LMCfg(vocab_size=64, max_len=128, hidden=64, depth=2,
                           num_heads=4, mlp_dim=128, dropout=0.0,
                           dtype="float32")
        cfgs["train"] = TrainCfg(batch_size=8, epochs=3, warmup_epochs=0,
                                 learning_rate=3e-3)
    apply_overrides(cfgs, args.overrides)
    lm_cfg, train_cfg = cfgs["lm"], cfgs["train"]

    n = len(jax.devices())
    rng = np.random.RandomState(train_cfg.seed)
    seq = min(lm_cfg.max_len - 8, 32)
    corpus = synthetic_text(rng, max(96, 3 * train_cfg.batch_size * n), seq,
                            lm_cfg.vocab_size)

    # -- prep: materialize the corpus as token tables -------------------------
    # The image arc's store discipline for the LM family: a seeded split
    # written once (prep.write_token_table), streamed back through the
    # sharded loader by the trainer (fit_tables).
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore

    store = TableStore(os.path.join(args.workdir, "lm_store"))
    split = np.random.RandomState(train_cfg.seed).permutation(len(corpus))
    n_val = max(train_cfg.batch_size * n, len(corpus) // 10)
    train_tbl = write_token_table(store, "lm_train", corpus[split[n_val:]])
    val_tbl = write_token_table(store, "lm_val", corpus[split[:n_val]])
    print(f"[prep] token tables: train={train_tbl.num_records} "
          f"val={val_tbl.num_records} seq+1={train_tbl.meta['seq_plus_one']}")

    # -- train (managed, table-fed) -------------------------------------------
    tracker = Tracker(os.path.join(args.workdir, "runs"), "workshop")
    run = tracker.start_run("lm_lifecycle")
    res = LMTrainer(lm_cfg, train_cfg, run=run).fit_tables(train_tbl, val_tbl)
    run.end()
    print(f"[train] epochs={res.epochs_run} val_loss={res.val_loss:.4f} "
          f"val_accuracy={res.val_accuracy:.3f}")

    # -- package --------------------------------------------------------------
    quant = "int8" if args.int8 else None
    pkg_dir = os.path.join(args.workdir, "lm_package")
    save_lm_package(pkg_dir, lm_cfg, res.state.params, quantize=quant)
    pm = load_lm_package(pkg_dir)
    size = os.path.getsize(os.path.join(pkg_dir, "params.msgpack"))
    print(f"[package] {pkg_dir} ({size / 1e6:.2f} MB"
          f"{', int8 weight-only' if quant else ''}) "
          f"digest={pm.content_digest}")

    # -- score ----------------------------------------------------------------
    probe = synthetic_text(np.random.RandomState(99), 16, seq,
                           lm_cfg.vocab_size)
    noise = np.random.RandomState(7).randint(
        0, lm_cfg.vocab_size, size=probe.shape).astype(np.int32)
    nll_structured = float(pm.score(probe).mean())
    nll_noise = float(pm.score(noise).mean())
    print(f"[score] structured nll={nll_structured:.3f} "
          f"(ppl {np.exp(nll_structured):.1f})  noise nll={nll_noise:.3f} "
          f"(ppl {np.exp(nll_noise):.1f})  "
          f"model_prefers_structure={nll_structured < nll_noise}")

    # -- distributed batch scoring over the val table -------------------------
    # The spark_udf leg for the LM family: shared-nothing shard split,
    # per-sequence NLL, one scores table (ddw_tpu.serving.LMBatchScorer).
    from ddw_tpu.serving import LMBatchScorer

    rows = LMBatchScorer(pm, batch_per_device=8).score_table(
        val_tbl, out_store=store)
    table_nll = float(np.mean([v for _, v in rows]))
    print(f"[batch-score] {len(rows)} val sequences -> "
          f"{store.table('lm_scores').num_records}-row scores table "
          f"(mean nll {table_nll:.3f})")

    # -- generate + speculative ----------------------------------------------
    prompt = probe[:1, :12]
    cont = pm.generate(prompt, num_steps=12)
    match = float((cont[0] == probe[0, 12:24]).mean())
    print(f"[generate] 12-token greedy continuation matches the arithmetic "
          f"stream {match:.0%}")

    # the draft trains on the same token tables: agreement (and therefore
    # acceptance) grows with how much signal both models have absorbed, and
    # the target's val split stays held out from BOTH models
    draft_cfg = dataclasses.replace(lm_cfg, hidden=32, depth=1, mlp_dim=64)
    draft_res = LMTrainer(draft_cfg, train_cfg).fit_tables(train_tbl, val_tbl)
    draft_dir = os.path.join(args.workdir, "lm_draft_package")
    save_lm_package(draft_dir, draft_cfg, draft_res.state.params,
                    quantize=quant)
    spec, stats = pm.generate_speculative(load_lm_package(draft_dir),
                                          prompt, num_steps=12, k=4)
    assert (spec == cont).all(), "speculative decode diverged from greedy"
    print(f"[speculative] identical tokens in {stats['target_calls']} target "
          f"calls (acceptance {stats['acceptance_rate']:.0%}, "
          f"{stats['tokens_per_target_call']:.1f} tok/call; plain greedy "
          f"= 1.0)")


if __name__ == "__main__":
    main()
