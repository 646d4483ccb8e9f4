"""Turnkey real-artifact acceptance: ImageNet MobileNetV2 + tf_flowers,
contracts 1-5 end-to-end, golden checksums per stage.

The reference's headline result rests on TWO artifacts this zero-egress
environment cannot hold: ImageNet-pretrained MobileNetV2 weights
(``Part 1 - Distributed Training/02_model_training_single_node.py:164-169``)
and the real tf_flowers corpus (``01_data_prep.py:5``). The in-repo chain is
proven on produced artifacts (example 08 / tests/test_pretrained_transfer.py);
THIS script is the one command a connected machine runs to close the accuracy
half of the contract on the real ones:

    python examples/12_real_acceptance.py --work /tmp/acceptance

Stages (each records a sha256/fingerprint into <work>/acceptance_report.json
and verifies it against --golden when that file has an entry — so a re-run,
or a run on another machine, proves byte-for-byte the same pipeline):

  fetch-weights   download torchvision's mobilenet_v2 state_dict (the 8-hex
                  chunk in the published filename IS its sha256 prefix —
                  verified after download, no trust-on-first-use needed)
  fetch-flowers   download + extract flower_photos.tgz
  convert         state_dict -> backbone .npz via the real import path
                  (ddw_tpu.models.convert); fingerprint of the array tree
  prep            contract 1: scan -> bronze -> seeded split -> silver
  train-single    contract 2: frozen-base transfer on one device; asserts
                  val top-1 >= --bar (reference publishes no number —
                  BASELINE.json: "published": {} — so the bar is this
                  framework's own stake in the ground, default 0.85)
  train-dist      contract 3: the same fit over every local device
  hpo             contract 4: TPE over the reference's space (optimizer
                  choice x loguniform LR x uniform dropout), parallel trials
  hpo-dist        contract 5: sequential whole-mesh trials, nested runs
  package-score   the inference contract: package the winner, batch-score
                  the val table, agreement must match the fit's accuracy

A failed run resumes: ``--resume`` skips every stage already recorded in
<work>/acceptance_report.json whose artifacts still exist (a dropped
connection during fetch, or a crash in package-score, must not re-pay
training or HPO; hpo-dist records its tuned params in the report so
package-score can resume past it).

On the bar: the reference never publishes a top-1 number for its headline
run (BASELINE.json's ``"published": {}`` records the absence), so 0.85 is this
framework's own stake — chosen below the 0.88-0.92 that frozen
ImageNet-MobileNetV2 transfer on tf_flowers typically reaches, so it fails
on real regressions (wrong preprocessing, broken weight import) without
flaking on seed/split variance. ``--bar`` overrides it; fixtures cap it at
chance+0.10 because stand-in artifacts only validate the mechanism.

Offline dry-run (what tests/test_real_acceptance.py exercises — every stage
except the two downloads, on generated stand-ins):

    python examples/12_real_acceptance.py --quick \\
        --fixture-weights <state_dict.pt> --fixture-flowers <jpeg_tree>
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import hashlib
import json
import tarfile
import time
import urllib.request

import numpy as np

WEIGHTS_URL = "https://download.pytorch.org/models/mobilenet_v2-b0353104.pth"
FLOWERS_URL = ("https://storage.googleapis.com/download.tensorflow.org/"
               "example_images/flower_photos.tgz")


def require(cond, msg: str) -> None:
    """Contract checks must not vanish under ``python -O`` the way bare
    asserts do — the bar IS the point of this script."""
    if not cond:
        raise SystemExit(f"[acceptance] FAILED: {msg}")


def trials_sha(trials) -> str:
    """Fingerprint of the whole search: every completed trial's params and
    loss (seeded TPE on fixed data is deterministic end-to-end)."""
    rows = [{**t["params"], "loss": round(float(t["loss"]), 6)}
            for t in trials.completed()]
    return hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def tree_sha(arrays: dict) -> str:
    """Deterministic content hash of a {name: ndarray} tree (np.savez zip
    timestamps make file-level sha256 unstable; the arrays are the truth)."""
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


class Stages:
    """Run stages in order; record fingerprints; verify against goldens."""

    def __init__(self, work: str, golden_path: str, record: bool,
                 resume: bool = False):
        self.work = work
        self.report_path = os.path.join(work, "acceptance_report.json")
        self.golden_path = golden_path
        self.record = record
        self.report: dict = {}
        self.golden: dict = {}
        self.previous: dict = {}
        if golden_path and os.path.exists(golden_path):
            with open(golden_path) as f:
                self.golden = json.load(f)
        if resume and os.path.exists(self.report_path):
            with open(self.report_path) as f:
                self.previous = json.load(f)
            print(f"[resume] {len(self.previous)} stage(s) recorded in "
                  f"{self.report_path}")

    def skip(self, stage: str, *artifacts: str):
        """On ``--resume``: the stage's previously recorded entry, if it
        completed, every artifact it produced still exists, AND its
        fingerprint agrees with the golden (a carried-forward entry must
        not dodge the verification a re-run would face). None = run it."""
        entry = self.previous.get(stage)
        if entry is None or any(not os.path.exists(a) for a in artifacts):
            return None
        want = self.golden.get(stage, {}).get("fingerprint")
        if want is not None and want != entry.get("fingerprint"):
            print(f"[{stage}] recorded fingerprint != golden — re-running, "
                  f"not resuming")
            return None
        entry = {**entry, "golden": "match" if want else entry.get("golden")}
        self.report[stage] = entry
        with open(self.report_path, "w") as f:
            json.dump(self.report, f, indent=1)
        print(f"[{stage}] resumed ({entry.get('fingerprint', '')[:16]}...)")
        return entry

    def done(self, stage: str, fingerprint: str, **info) -> None:
        entry = {"fingerprint": fingerprint, **info}
        want = self.golden.get(stage, {}).get("fingerprint")
        if want is not None and want != fingerprint:
            raise SystemExit(
                f"[{stage}] fingerprint {fingerprint[:16]}... != golden "
                f"{want[:16]}... — the pipeline is not reproducing the "
                f"recorded run (different inputs, or a behavior change)")
        entry["golden"] = ("match" if want else
                           "unrecorded" if not self.record else "recorded")
        self.report[stage] = entry
        with open(self.report_path, "w") as f:
            json.dump(self.report, f, indent=1)
        print(f"[{stage}] {fingerprint[:16]}... {entry['golden']} "
              + " ".join(f"{k}={v}" for k, v in info.items()))

    def finish(self) -> None:
        if self.record and self.golden_path:
            with open(self.golden_path, "w") as f:
                json.dump(self.report, f, indent=1)
            print(f"[golden] recorded {len(self.report)} stages -> "
                  f"{self.golden_path}")


def fetch(url: str, dest: str) -> str:
    if not os.path.exists(dest):
        print(f"[fetch] {url}")
        tmp = dest + ".part"
        urllib.request.urlretrieve(url, tmp)
        os.replace(tmp, dest)
    return dest


def main():
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--work", default="acceptance_run")
    ap.add_argument("--bar", type=float, default=0.85,
                    help="val top-1 the frozen-transfer contracts must reach "
                         "on real artifacts (fixtures use chance+0.10)")
    ap.add_argument("--quick", action="store_true",
                    help="small width/resolution/epochs (fixture dry-runs)")
    ap.add_argument("--fixture-weights", default="",
                    help="offline stand-in for the torchvision download: a "
                         "torch-format mobilenet_v2 state_dict file")
    ap.add_argument("--fixture-flowers", default="",
                    help="offline stand-in for tf_flowers: a <dir>/<class>/"
                         "*.jpg tree")
    ap.add_argument("--golden", default=os.path.join(
        os.path.dirname(__file__), "real_acceptance_golden.json"))
    ap.add_argument("--record", action="store_true",
                    help="write this run's fingerprints as the new goldens")
    ap.add_argument("--resume", action="store_true",
                    help="skip stages already recorded in the work dir's "
                         "acceptance_report.json whose artifacts still exist "
                         "(a crash mid-run must not re-pay downloads, "
                         "training, or HPO)")
    args = ap.parse_args()
    from ddw_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    os.makedirs(args.work, exist_ok=True)
    st = Stages(args.work, args.golden, args.record, resume=args.resume)
    fixtures = bool(args.fixture_weights or args.fixture_flowers)
    if fixtures and not (args.fixture_weights and args.fixture_flowers):
        raise SystemExit("--fixture-weights and --fixture-flowers go together")
    if args.quick and not fixtures:
        # --quick shrinks the model to width 0.35, which cannot load the
        # real width-1.0 torchvision artifact — it would download ~250 MB
        # and then crash on the first pretrained-load shape mismatch.
        raise SystemExit("--quick is the fixture dry-run mode; pass "
                         "--fixture-weights/--fixture-flowers with it (the "
                         "real-artifact run needs the full-width model)")

    width = 0.35 if args.quick else 1.0
    img = 48 if args.quick else 224
    epochs = 2 if args.quick else 3
    t0 = time.time()

    # -- environment --------------------------------------------------------
    # Recorded (report AND golden) so a non-reproducing run on another
    # machine shows WHAT differed; the constant fingerprint means version
    # drift is visible, not fatal — the artifact sha stages are the pins.
    import jax
    import torch

    run_cfg = {"quick": args.quick, "bar": args.bar, "fixtures": fixtures,
               "width": width, "img": img, "epochs": epochs}
    prev_cfg = st.previous.get("environment", {}).get("config")
    if prev_cfg is not None and prev_cfg != run_cfg:
        # Mixing entries from two configurations would fingerprint a
        # pipeline no single invocation can reproduce.
        raise SystemExit(f"[resume] config mismatch: the recorded run used "
                         f"{prev_cfg}, this one is {run_cfg} — rerun with "
                         f"the same flags, or drop --resume")
    st.done("environment", "-", python=sys.version.split()[0],
            torch=torch.__version__, jax=jax.__version__,
            numpy=np.__version__, config=run_cfg,
            weights_url=WEIGHTS_URL, flowers_url=FLOWERS_URL)

    # -- fetch-weights ------------------------------------------------------
    if fixtures:
        wpath = args.fixture_weights
        if not st.skip("fetch-weights", wpath):
            st.done("fetch-weights", sha256_file(wpath), source="fixture")
    else:
        wpath = os.path.join(args.work, "mnv2_imagenet.pth")
        if not st.skip("fetch-weights", wpath):
            fetch(WEIGHTS_URL, wpath)
            digest = sha256_file(wpath)
            # torchvision convention: the filename's 8-hex chunk is the
            # sha256 prefix of the artifact — an integrity check with no
            # golden needed.
            expect = os.path.basename(WEIGHTS_URL).rsplit("-", 1)[1].split(".")[0]
            if not digest.startswith(expect):
                os.remove(wpath)  # a --resume retry must re-download
                raise SystemExit(f"weights sha256 {digest[:8]} != published "
                                 f"prefix {expect} — corrupt download")
            st.done("fetch-weights", digest, source=WEIGHTS_URL)

    # -- fetch-flowers ------------------------------------------------------
    if fixtures:
        flowers_dir = args.fixture_flowers
        if not st.skip("fetch-flowers", flowers_dir):
            st.done("fetch-flowers", "fixture", source="fixture")
    else:
        flowers_dir = os.path.join(args.work, "flower_photos")
        if not st.skip("fetch-flowers", flowers_dir):
            tgz = fetch(FLOWERS_URL,
                        os.path.join(args.work, "flower_photos.tgz"))
            digest = sha256_file(tgz)
            # Golden check BEFORE extracting: a recorded golden must reject
            # a tampered archive without a single member touching disk;
            # filter='data' additionally refuses path-escaping members on
            # first (unrecorded) runs.
            want = st.golden.get("fetch-flowers", {}).get("fingerprint")
            if want is not None and want != digest:
                raise SystemExit(f"flowers archive sha256 {digest[:16]}... "
                                 f"!= golden {want[:16]}... — refusing to "
                                 f"extract")
            if not os.path.isdir(flowers_dir):
                # Extract atomically (tmp dir + rename) and record done()
                # only AFTER: a crash mid-extract must not leave a partial
                # tree that --resume would accept as complete.
                tmp_extract = os.path.join(args.work, ".flowers_extract")
                import shutil

                shutil.rmtree(tmp_extract, ignore_errors=True)
                with tarfile.open(tgz) as tf:
                    tf.extractall(tmp_extract, filter="data")
                os.replace(os.path.join(tmp_extract, "flower_photos"),
                           flowers_dir)
                shutil.rmtree(tmp_extract, ignore_errors=True)
            st.done("fetch-flowers", digest, source=FLOWERS_URL)

    # -- convert ------------------------------------------------------------
    backbone_npz = os.path.join(args.work, "imagenet_backbone.npz")
    if not st.skip("convert", backbone_npz):
        from ddw_tpu.models.convert import (convert_torch_mobilenet_v2,
                                            save_pretrained)

        sd = torch.load(wpath, map_location="cpu", weights_only=True)
        tree = convert_torch_mobilenet_v2(sd)
        flat = {f"{g}/{k}": np.asarray(v) for g, sub in tree.items()
                for k, v in _flatten(sub)}
        save_pretrained(backbone_npz, tree)
        st.done("convert", tree_sha(flat), leaves=len(flat))

    # -- prep (contract 1) --------------------------------------------------
    from ddw_tpu.data.prep import prepare_flowers
    from ddw_tpu.data.store import TableStore

    store = TableStore(os.path.join(args.work, "store"))
    if not store.exists("silver_train"):
        prepare_flowers(flowers_dir, store, sample_fraction=1.0,
                        split_seed=42)
    train_tbl, val_tbl = store.table("silver_train"), store.table("silver_val")
    labels = train_tbl.meta["label_to_idx"]
    st.done("prep", hashlib.sha256(json.dumps(
        [sorted(labels.items()), train_tbl.num_records,
         val_tbl.num_records]).encode()).hexdigest(),
        train=train_tbl.num_records, val=val_tbl.num_records,
        classes=len(labels))

    # -- the shared frozen-transfer fit -------------------------------------
    from ddw_tpu.train.trainer import Trainer
    from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg

    data_cfg = DataCfg(img_height=img, img_width=img, loader_workers=4)
    # Fixture runs validate the MECHANISM (every stage executes, fingerprints
    # reproduce); the accuracy half of the contract needs the real artifacts,
    # so the fixture bar never exceeds chance+0.10 and --bar can lower it.
    bar = min(args.bar, 1.0 / len(labels) + 0.10) if fixtures else args.bar

    def head_fit(num_devices: int, lr=5e-3, dropout=0.1, optimizer="adam",
                 n_epochs=None):
        mcfg = ModelCfg(name="mobilenet_v2", num_classes=len(labels),
                        dropout=dropout, width_mult=width, freeze_base=True,
                        dtype="float32", pretrained_path=backbone_npz)
        tcfg = TrainCfg(batch_size=8 if args.quick else 32,
                        epochs=n_epochs or epochs,
                        warmup_epochs=0, learning_rate=lr,
                        optimizer=optimizer, num_devices=num_devices,
                        checkpoint_dir="", seed=0)
        return Trainer(data_cfg, mcfg, tcfg).fit(train_tbl, val_tbl), mcfg

    # -- train-single (contract 2) ------------------------------------------
    if not st.skip("train-single"):
        res1, _ = head_fit(num_devices=1)
        require(res1.val_accuracy >= bar,
                f"single-node frozen transfer top-1 {res1.val_accuracy:.3f} "
                f"< bar {bar:.2f}")
        st.done("train-single", f"{res1.val_accuracy:.4f}",
                val_accuracy=round(res1.val_accuracy, 4), bar=round(bar, 3))

    # -- train-dist (contract 3) --------------------------------------------
    if not st.skip("train-dist"):
        res2, _ = head_fit(num_devices=len(jax.devices()))
        require(res2.val_accuracy >= bar,
                f"distributed frozen transfer top-1 {res2.val_accuracy:.3f} "
                f"< bar {bar:.2f}")
        st.done("train-dist", f"{res2.val_accuracy:.4f}",
                val_accuracy=round(res2.val_accuracy, 4),
                devices=len(jax.devices()))

    # -- hpo (contract 4) ---------------------------------------------------
    from ddw_tpu.tune import STATUS_OK, Trials, choice, fmin, loguniform, uniform

    space = {"optimizer": choice("optimizer", ["adam", "adadelta"]),
             "lr": loguniform("lr", np.log(1e-4), np.log(1e-1)),
             "dropout": uniform("dropout", 0.1, 0.9)}

    def objective(params, trial=None):
        r, _ = head_fit(num_devices=1, lr=params["lr"],
                        dropout=params["dropout"],
                        optimizer=params["optimizer"], n_epochs=1)
        return {"loss": -r.val_accuracy, "status": STATUS_OK}

    if not st.skip("hpo"):
        trials = Trials()
        fmin(objective, space, max_evals=2 if args.quick else 8,
             trials=trials, parallelism=1, seed=0)
        st.done("hpo", trials_sha(trials),
                evals=len(trials), best_acc=round(-trials.best["loss"], 4))

    # -- hpo-dist (contract 5) ----------------------------------------------
    def objective_dist(params, trial=None):
        r, _ = head_fit(num_devices=len(jax.devices()), lr=params["lr"],
                        dropout=params["dropout"], n_epochs=1)
        return {"loss": -r.val_accuracy, "status": STATUS_OK}

    # The tuned params ride the report entry so a --resume past this stage
    # (e.g. after a package-score crash) still knows the winner. A report
    # from an older script version lacks them — fall back to re-running.
    prev = st.skip("hpo-dist")
    if prev and "tuned_lr" in prev:
        tuned = {"lr": prev["tuned_lr"], "dropout": prev["tuned_dropout"]}
    else:
        dtrials = Trials()
        fmin(objective_dist,
             {"lr": loguniform("lr", np.log(1e-4), np.log(1e-1)),
              "dropout": uniform("dropout", 0.1, 0.9)},
             max_evals=2 if args.quick else 4, trials=dtrials, parallelism=1,
             seed=0)
        tuned = dtrials.best["params"]
        st.done("hpo-dist", trials_sha(dtrials),
                best_acc=round(-dtrials.best["loss"], 4),
                tuned_lr=float(tuned["lr"]),
                tuned_dropout=float(tuned["dropout"]))

    # -- package-score ------------------------------------------------------
    pkg = os.path.join(args.work, "accepted_pkg")
    if not st.skip("package-score", pkg):
        from ddw_tpu.serving.batch import BatchScorer
        from ddw_tpu.serving.package import save_packaged_model

        # The winner: the tuned hyperparameters from contract 5, retrained at
        # full epochs over the whole mesh (the reference's best-run ->
        # registry -> production arc,
        # 01_hyperopt_single_machine_model.py:253-293).
        res_best, mcfg_best = head_fit(num_devices=len(jax.devices()),
                                       lr=tuned["lr"],
                                       dropout=tuned["dropout"])
        classes = [c for c, _ in sorted(labels.items(),
                                        key=lambda kv: kv[1])]
        save_packaged_model(pkg, mcfg_best, classes, res_best.state.params,
                            res_best.state.batch_stats,
                            img_height=img, img_width=img)
        rows = BatchScorer(pkg, batch_per_device=32).score_table(val_tbl)
        truth = {r.path: r.label for r in val_tbl.iter_records()}
        agree = sum(truth[p] == pred for p, pred in rows) / len(rows)
        # score_table covers every record; the fit's eval drops remainder
        # batches — tiny fixture tables make that gap large, real flowers
        # keep it small.
        tol = 0.25 if fixtures else 0.05
        require(abs(agree - res_best.val_accuracy) < tol,
                f"packaged-score agreement {agree:.3f} vs fit accuracy "
                f"{res_best.val_accuracy:.3f} — train/serve skew")
        st.done("package-score", f"{agree:.4f}", rows=len(rows),
                agreement=round(agree, 4),
                tuned_lr=round(float(tuned["lr"]), 6),
                tuned_dropout=round(float(tuned["dropout"]), 3))

    st.finish()
    print(f"[acceptance] ALL STAGES PASSED in {time.time() - t0:.0f}s "
          f"(report: {st.report_path})")


def _flatten(tree, prefix=""):
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _flatten(v, key)
        else:
            yield key, v


if __name__ == "__main__":
    main()
