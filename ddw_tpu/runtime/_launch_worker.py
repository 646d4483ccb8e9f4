"""Worker entrypoint for :class:`ddw_tpu.runtime.launcher.Launcher` multi-process mode.

Each spawned process: initialize the distributed runtime (the ``hvd.init()`` /
mpirun-rendezvous analog), unpickle and run the train fn, and — rank 0 only — write
the return value back for the driver (the HorovodRunner return contract,
reference ``03_model_training_distributed.py:375``).

Robustness contract (docs/fault_tolerance.md):

- SIGTERM is routed to the graceful-preemption flag before any work starts;
  a step loop that honors it checkpoints and raises ``Preempted``, which this
  process converts to ``EXIT_PREEMPTED`` so the supervisor restarts without
  burning the crash budget.
- A coordinator port-bind failure (the ``_free_port`` probe-to-bind race)
  exits ``EXIT_COORD_BIND`` so the launcher respawns the gang on a fresh port
  instead of hanging every other rank until the gang deadline.
- ``result.pkl`` is written atomically (tmp + ``os.replace``): a rank 0
  killed mid-write must leave either no result (detected as
  ``result-missing``) or a complete one — never a torn pickle that masks the
  root cause or unpickles as garbage on the success path.
- Elastic mode (``DDW_RENDEZVOUS_DIR`` set by an elastic
  :class:`~ddw_tpu.runtime.launcher.Launcher`): the gang's topology is the
  explicit :class:`~ddw_tpu.runtime.elastic.GangRendezvous`, NOT
  ``jax.distributed`` (whose coordination service admits each process id
  exactly once — a respawned rank could never rejoin it), so the
  distributed init is skipped and cross-rank sync rides the rendezvous
  control plane. When a peer dies, this process's train fn raises
  :class:`~ddw_tpu.runtime.elastic.ElasticRestart` at its next chain
  boundary (or parked barrier); the fn is then re-run *in this same
  process* at the bumped generation — restoring from the latest durable
  checkpoint — which is the whole point: survivors keep their pid, imports
  and compiled programs. Exceptions that land while a recovery is pending
  are treated as collateral of the dead peer, not application bugs.
"""

from __future__ import annotations

import os
import pickle
import sys
import traceback

from ddw_tpu.runtime.faults import (
    EXIT_COORD_BIND,
    EXIT_PREEMPTED,
    Preempted,
    install_preemption_handler,
    maybe_fault,
)

_BIND_FAILURE_MARKERS = ("address already in use", "failed to bind",
                         "errno 98", "eaddrinuse", "bind address")


def _looks_like_bind_failure(text: str) -> bool:
    text = text.lower()
    return any(m in text for m in _BIND_FAILURE_MARKERS)


def _write_result(result_path: str, status) -> None:
    """Atomic result write: serialize fully, then publish via os.replace —
    the driver either sees the complete pickle or none at all."""
    try:
        blob = pickle.dumps(status)
    except Exception as e:  # unpicklable return value: report, don't mask
        status = ("error", f"rank-0 return value is not picklable: {e!r}")
        blob = pickle.dumps(status)
    tmp = f"{result_path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, result_path)


def main() -> int:
    payload_path, result_path = sys.argv[1], sys.argv[2]
    install_preemption_handler()
    maybe_fault("coord_bind")
    from ddw_tpu.runtime.elastic import context as elastic_context
    from ddw_tpu.runtime.mesh import initialize_distributed, is_coordinator
    from ddw_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rdzv = elastic_context()
    if rdzv is not None:
        # Elastic gang: membership/barrier/reduce live in the explicit
        # rendezvous object; jax.distributed stays out (its coordination
        # service cannot re-admit a respawned process id). Each process
        # keeps its own local CPU/TPU devices for jitted compute. Under
        # DDW_ELASTIC_JAX_DIST=1 the gang ALSO forms a real jax.distributed
        # world, torn down and re-formed per generation on the generation's
        # fresh coordinator port (global-mesh trainers survive rank loss).
        from ddw_tpu.runtime.elastic import maybe_reinit_distributed
        rdzv.announce()
        maybe_reinit_distributed()
    else:
        try:
            initialize_distributed()  # reads DDW_COORDINATOR / DDW_NUM_PROCESSES / DDW_PROCESS_ID
        except Exception:
            tb = traceback.format_exc()
            if (os.environ.get("DDW_PROCESS_ID", "0") == "0"
                    and _looks_like_bind_failure(tb)):
                # Coordinator lost the spawn-time port race — a distinguished
                # exit code tells the launcher "respawn on a fresh port", which
                # a generic crash must not trigger.
                sys.stderr.write(tb)
                return EXIT_COORD_BIND
            raise
        # jax.distributed's preemption notifier replaces the SIGTERM
        # disposition during initialize; re-route it to the graceful-
        # preemption flag — the launcher's gang-wide broadcast must reach
        # the step loop, not XLA's notifier.
        install_preemption_handler()
    with open(payload_path, "rb") as f:
        fn_spec, args, kwargs = pickle.load(f)
    kind, blob, qualname = fn_spec
    if kind == "pickled":
        fn = pickle.loads(blob)
    else:  # "by_file": re-import the driver script under a non-__main__ name
        import importlib.util

        spec = importlib.util.spec_from_file_location("ddw_launched_main", blob)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["ddw_launched_main"] = mod
        spec.loader.exec_module(mod)
        fn = mod
        for part in qualname.split("."):
            fn = getattr(fn, part)
    from ddw_tpu.runtime.elastic import ElasticRestart

    while True:
        try:
            value = fn(*args, **kwargs)
            status = ("ok", value)
        except ElasticRestart as e:
            # A peer died and the launcher re-formed the gang: adopt the new
            # generation and re-run the fn IN THIS PROCESS — it restores
            # from the latest durable checkpoint exactly as a whole-world
            # restart would, but the pid/imports/compiled programs survive.
            # A shrink record remaps this rank's identity inside advance();
            # a jax.distributed gang then re-forms on the generation's
            # fresh coordinator port.
            from ddw_tpu.runtime.elastic import maybe_reinit_distributed
            rdzv.advance(e.generation)
            rdzv.announce()
            maybe_reinit_distributed()
            continue
        except Preempted as e:
            # Graceful preemption: the step loop already checkpointed. A
            # clean, distinguished exit lets the supervisor restart outside
            # the crash budget.
            status = ("preempted", {"step": e.step})
        except Exception:
            from ddw_tpu.runtime.faults import preemption_requested

            if preemption_requested():
                # SIGTERM already arrived (the launcher forwards it
                # gang-wide on the first EXIT_PREEMPTED): this exception is
                # almost certainly the collateral collective error of a
                # preempting peer, not an application bug — exit as
                # preempted so the restart stays outside the crash budget.
                status = ("preempted", {"step": None})
            elif rdzv is not None:
                # Collateral of a dead peer (a sync aborted under it while
                # recovery was being posted): park via the elastic path
                # instead of dying — consuming the pending record bounds
                # this to one re-run per generation. The same vote/commit-
                # aware check as a parked barrier, so a survivor never
                # adopts a shrink record it vetoed or one the driver has
                # not committed.
                err = traceback.format_exc()
                try:
                    rdzv._check_recovery(None)
                except ElasticRestart as e2:
                    from ddw_tpu.runtime.elastic import (
                        maybe_reinit_distributed)
                    rdzv.advance(e2.generation)
                    rdzv.announce()
                    maybe_reinit_distributed()
                    continue
                status = ("error", err)
            else:
                status = ("error", traceback.format_exc())
        break
    if (os.environ.get("DDW_PROCESS_ID", "0") == "0"
            if rdzv is not None else is_coordinator()):
        _write_result(result_path, status)
    if status[0] == "ok":
        return 0
    # Error/preemption exits skip interpreter finalization (os._exit): the
    # jax.distributed shutdown hooks block on gang peers, and on these paths
    # a peer is typically wedged inside a collective — a clean sys.exit would
    # hang this rank until the gang deadline instead of failing fast. The
    # result file is already durable (fsync + rename above).
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(EXIT_PREEMPTED if status[0] == "preempted" else 1)


if __name__ == "__main__":
    sys.exit(main())
