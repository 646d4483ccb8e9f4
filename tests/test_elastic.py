"""Elastic gang recovery + async-checkpoint fault drills.

Tier-1 keeps the pure rendezvous/topology units (threaded fake gangs — no
subprocess) plus one fast real-process representative per drill class: the
single-rank kill-and-respawn drill and the torn-async-checkpoint drill.
The whole-world-fallback and sharded-async gang variants ride tier-2
(`slow`), per the ROADMAP's budget practice."""

import functools
import os
import threading
import time

import numpy as np
import pytest

from ddw_tpu.runtime.elastic import ElasticRestart, GangRendezvous
from ddw_tpu.runtime.launcher import GangError, Launcher
from ddw_tpu.runtime.supervisor import GangFailure, GangSupervisor

TOTAL_STEPS = 6


# -- pure topology units (threaded fake gang, no subprocess) -----------------

def _threads(n, fn):
    errs = []

    def run(r):
        try:
            fn(r)
        except BaseException as e:   # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=run, args=(r,)) for r in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    return errs


def test_rendezvous_barrier_and_reduce(tmp_path):
    """All ranks meet at the barrier; the host all-reduce folds in rank
    order (deterministic, bit-identical everywhere)."""
    root = str(tmp_path)
    out = {}

    def worker(r):
        rdzv = GangRendezvous(root, world_size=3, rank=r)
        rdzv.announce()
        rdzv.barrier("start")
        total = rdzv.all_reduce(0, np.full((2,), float(r + 1)))
        mean = rdzv.all_reduce(1, float(r), op="mean")
        out[r] = (total, mean)

    assert _threads(3, worker) == []
    for r in range(3):
        np.testing.assert_array_equal(out[r][0], np.full((2,), 6.0))
        assert out[r][1] == pytest.approx(1.0)
    # membership carries the pid evidence the drills assert on
    rdzv = GangRendezvous(root, 3, 0)
    assert rdzv.member(0, 1)["pid"] == os.getpid()


def test_barrier_aborts_with_elastic_restart_on_recovery(tmp_path):
    """Survivors parked at a barrier (a dead peer never arrives) leave via
    ElasticRestart the moment the driver posts the recovery record — they
    never wait out the timeout."""
    root = str(tmp_path)
    rdzv0 = GangRendezvous(root, world_size=2, rank=0)
    caught = []

    def survivor(_):
        try:
            rdzv0.barrier(3, timeout_s=20.0)
        except ElasticRestart as e:
            caught.append(e)

    t = threading.Thread(target=survivor, args=(0,))
    t.start()
    time.sleep(0.1)      # park first
    GangRendezvous(root, 2, -1).post_recovery(1, dead_rank=1, exit_code=-9)
    t.join(timeout=5)
    assert not t.is_alive()
    assert caught and caught[0].generation == 1
    assert caught[0].record["dead_rank"] == 1
    assert caught[0].step == 3
    # adopting the new generation consumes the record
    rdzv0.advance(caught[0].generation)
    assert rdzv0.recovery_pending() is None
    assert os.environ.pop("DDW_ELASTIC_GEN") == "1"


def test_reduce_aborts_and_regenerations_do_not_mix(tmp_path):
    """A reduce parked under a dead peer aborts; contributions of the old
    generation are invisible to the re-formed gang."""
    root = str(tmp_path)
    r0 = GangRendezvous(root, world_size=2, rank=0)
    with pytest.raises(ElasticRestart):
        # contribute, then see the recovery record posted mid-park
        threading.Timer(
            0.1, lambda: GangRendezvous(root, 2, -1).post_recovery(
                1, dead_rank=1)).start()
        r0.all_reduce(5, 1.0, timeout_s=20.0)
    # gen 1: both ranks contribute fresh values at the SAME tag
    out = {}

    def worker(r):
        rdzv = GangRendezvous(root, 2, r, generation=1)
        out[r] = float(rdzv.all_reduce(5, float(10 + r)))

    assert _threads(2, worker) == []
    assert out[0] == out[1] == 21.0   # not polluted by gen-0's value 1.0


def test_maybe_elastic_restart_hook(tmp_path, monkeypatch):
    """The trainers' chain-boundary hook: free no-op outside elastic mode,
    raises once a newer recovery record exists."""
    from ddw_tpu.runtime import elastic

    elastic.reset_context()
    elastic.maybe_elastic_restart(step=0)          # no env: no-op
    monkeypatch.setenv("DDW_RENDEZVOUS_DIR", str(tmp_path))
    monkeypatch.setenv("DDW_NUM_PROCESSES", "2")
    monkeypatch.setenv("DDW_PROCESS_ID", "0")
    elastic.reset_context()
    elastic.maybe_elastic_restart(step=1)          # no record yet: no-op
    GangRendezvous(str(tmp_path), 2, -1).post_recovery(1, dead_rank=1)
    with pytest.raises(ElasticRestart) as exc:
        elastic.maybe_elastic_restart(step=7)
    assert exc.value.generation == 1 and exc.value.step == 7
    elastic.reset_context()


def test_fault_spec_egen_and_new_kinds():
    from ddw_tpu.runtime.faults import parse_fault

    spec = parse_fault("kill:rank=1:step=3")
    assert spec.kind == "kill" and spec.site == "step"
    # default egen=0: the respawned rank (egen 1) runs clean
    assert spec.matches("step", step=3, rank=1, gen=0, egen=0, attempt=0)
    assert not spec.matches("step", step=3, rank=1, gen=0, egen=1, attempt=0)
    # egen=* chases every respawn — the re-rendezvous-keeps-failing drill
    chase = parse_fault("kill:rank=1:step=3:egen=*")
    assert chase.matches("step", step=3, rank=1, gen=0, egen=2, attempt=0)
    assert not chase.matches("step", step=3, rank=1, gen=1, egen=0,
                             attempt=0)  # gen still defaults to 0
    torn = parse_fault("ckpt_async_torn:step=4")
    assert torn.site == "ckpt_async"
    assert torn.matches("ckpt_async", step=4, rank=0, gen=0, egen=0,
                        attempt=0)
    assert not torn.matches("step", step=4, rank=0, gen=0, egen=0, attempt=0)


# -- real-process drills ------------------------------------------------------

def _elastic_worker(ckpt_dir: str, total_steps: int) -> dict:
    """The elastic supervised-worker contract: explicit-topology gang (the
    launcher's elastic mode skips jax.distributed — a respawned rank could
    never rejoin its coordination service), checkpoint via the rank-0
    writer, per-step fault hook + chain-boundary park hook, host all-reduce
    as the per-step gang data barrier."""
    import os

    import numpy as np

    from ddw_tpu.checkpoint.ckpt import CheckpointManager
    from ddw_tpu.runtime import elastic
    from ddw_tpu.runtime.faults import maybe_fault

    mgr = CheckpointManager(ckpt_dir)
    state = {"w": np.zeros((4,), np.float32), "step": np.asarray(0, np.int32)}
    start = 0
    if mgr.latest_step() is not None:
        state, start = mgr.restore(state)
        start = int(start)
    elastic.elastic_barrier("start")   # the (re-formed) gang resumes in step
    for step in range(start, total_steps):
        maybe_fault("step", step=step, ckpt_dir=ckpt_dir)
        elastic.maybe_elastic_restart(step=step)
        total = elastic.host_all_reduce(step, np.ones(()))  # gang barrier
        state = {"w": state["w"] + float(total),
                 "step": np.asarray(step + 1, np.int32)}
        mgr.save(state, step + 1)      # env-guarded rank-0 writer
    mgr.close()
    ctx = elastic.context()
    return {"final_step": int(state["step"]), "resume_step": start,
            "w": float(state["w"][0]), "pid": os.getpid(),
            "egen": ctx.generation if ctx is not None else 0}


def _gang(tmp_path, elastic_restarts=1, timeout_s=120, **kw):
    return Launcher(np=2, devices_per_proc=1, timeout_s=timeout_s,
                    elastic_restarts=elastic_restarts,
                    rendezvous_dir=str(tmp_path / "rdzv"), **kw)


@pytest.mark.faults
def test_elastic_single_rank_respawn(tmp_path, monkeypatch,
                                     worker_pythonpath):
    """The tentpole acceptance drill: kill exactly one rank mid-epoch —
    the gang resumes with ONLY that rank respawned (the survivor's pid is
    identical across generations), resume semantics match the
    whole-world restart contract (restore from the latest durable
    checkpoint), and the forensics land in the supervisor's attempt
    record tagged elastic."""
    baseline = Launcher(np=-1).run(functools.partial(
        _elastic_worker, str(tmp_path / "base"), TOTAL_STEPS))
    assert baseline["final_step"] == TOTAL_STEPS

    monkeypatch.setenv("DDW_FAULT", "kill:rank=1:step=3")
    launcher = _gang(tmp_path)
    sup = GangSupervisor(launcher, max_restarts=0, backoff_base_s=0.05,
                         jitter=0.0)
    out = sup.run(functools.partial(_elastic_worker, str(tmp_path / "ck"),
                                    TOTAL_STEPS))
    # resumed exactly at the last durable step, completed, and each step
    # contributed world_size — identical to an uninterrupted run's math
    assert out["final_step"] == TOTAL_STEPS
    assert out["resume_step"] == 3
    assert out["w"] == TOTAL_STEPS * 2
    assert out["egen"] == 1

    # only rank 1 was respawned: one elastic event, signal death, and the
    # membership ledger shows rank 0's pid stable across generations
    assert len(launcher.elastic_events) == 1
    ev = launcher.elastic_events[0]
    assert ev.dead_rank == 1 and ev.generation == 1
    assert ev.exit_signal == 9                      # SIGKILL forensics
    rdzv = GangRendezvous(launcher.last_rendezvous_dir, 2, -1)
    assert rdzv.member(0, 0)["pid"] == rdzv.member(1, 0)["pid"]
    assert rdzv.member(0, 1)["pid"] != rdzv.member(1, 1)["pid"]
    assert rdzv.member(1, 1)["pid"] == ev.respawn_pid
    assert out["pid"] == rdzv.member(1, 0)["pid"]   # rank-0 result, same pid

    # supervisor forensics: the recovery is an attempt tagged elastic, and
    # it consumed NO whole-world budget (max_restarts=0 and we completed)
    assert [a.recovery for a in sup.attempts] == ["elastic"]
    assert sup.attempts[0].dead_rank == 1
    assert sup.attempts[0].exit_signal == 9
    assert sup.attempts[0].kind == "rank-death"


@pytest.mark.faults
@pytest.mark.slow   # three gang launches of real processes — tier-2 drill
def test_elastic_budget_exhausted_falls_back_to_whole_world(
        tmp_path, monkeypatch, worker_pythonpath):
    """Re-rendezvous failure: egen=* re-kills the respawned rank, the
    elastic budget (1) exhausts, the launcher kills the gang (classic
    GangError) and the supervisor's whole-world restart completes the run
    — the fallback the elastic path must never replace."""
    monkeypatch.setenv("DDW_FAULT", "kill:rank=1:step=3:egen=*")
    launcher = _gang(tmp_path, elastic_restarts=1)
    sup = GangSupervisor(launcher, max_restarts=1, backoff_base_s=0.05,
                         jitter=0.0)
    out = sup.run(functools.partial(_elastic_worker, str(tmp_path / "ck"),
                                    TOTAL_STEPS))
    assert out["final_step"] == TOTAL_STEPS
    assert out["resume_step"] == 3          # whole-world restore point
    assert out["w"] == TOTAL_STEPS * 2
    # attempt record tells the full story: one elastic recovery, then the
    # whole-world crash attempt that actually healed the run
    kinds = [(a.kind, a.recovery) for a in sup.attempts]
    assert ("rank-death", "elastic") in kinds
    assert ("crash", "whole-world") in kinds


@pytest.mark.faults
@pytest.mark.slow
def test_elastic_exhausts_into_gangfailure(tmp_path, monkeypatch,
                                           worker_pythonpath):
    """Elastic budget out AND whole-world budget out -> GangFailure with
    both the elastic events and the gang attempts in the record."""
    monkeypatch.setenv("DDW_FAULT", "kill:rank=1:step=3:egen=*:gen=*")
    sup = GangSupervisor(_gang(tmp_path, elastic_restarts=1),
                         max_restarts=0, backoff_base_s=0.05, jitter=0.0)
    with pytest.raises(GangFailure) as exc:
        sup.run(functools.partial(_elastic_worker, str(tmp_path / "ck"),
                                  TOTAL_STEPS))
    recs = [a.recovery for a in exc.value.attempts]
    assert "elastic" in recs and "whole-world" in recs


# -- torn ASYNC checkpoint: quarantined across generations -------------------

def _async_ckpt_worker(ckpt_dir: str, total_steps: int,
                       sharded: bool = False) -> dict:
    """Supervised worker writing checkpoints through the ASYNC writer
    (bounded in-flight depth 2). DDW_FAULT=ckpt_async_torn fires on the
    background writer thread mid-write."""
    import numpy as np

    if sharded:
        import jax

        from ddw_tpu.checkpoint.sharded import ShardedCheckpointManager

        class _Mgr:
            def __init__(self, d):
                self._m = ShardedCheckpointManager(d, async_write=True,
                                                   max_inflight=2)

            def latest_step(self):
                return self._m.latest_step()

            def restore(self, target):
                # host leaves: any sharding sentinel without device_set
                sh = jax.tree.map(lambda _: object(), target)
                return self._m.restore(target, sh)

            def save(self, state, step):
                self._m.save(state, step)

            def close(self):
                self._m.close()

        mgr = _Mgr(ckpt_dir)
    else:
        from ddw_tpu.checkpoint.ckpt import CheckpointManager

        mgr = CheckpointManager(ckpt_dir, async_write=True, max_inflight=2)
    state = {"w": np.zeros((4,), np.float32), "step": np.asarray(0, np.int32)}
    start = 0
    if mgr.latest_step() is not None:
        state, start = mgr.restore(state)
        start = int(start)
    for step in range(start, total_steps):
        state = {"w": state["w"] + 1.0,
                 "step": np.asarray(step + 1, np.int32)}
        mgr.save(state, step + 1)
    mgr.close()
    return {"final_step": int(state["step"]), "resume_step": start}


@pytest.mark.faults
def test_torn_async_write_quarantined_across_generations(
        tmp_path, monkeypatch, worker_pythonpath):
    """Satellite pin: the writer process dies mid-async-write of step 3
    leaving a torn dir; the restarted generation quarantines it and
    resumes from step 2 — the async path's crash consistency is exactly
    the synchronous path's."""
    ckpt_dir = str(tmp_path / "ck")
    monkeypatch.setenv("DDW_FAULT", "ckpt_async_torn:rank=0:step=3")
    sup = GangSupervisor(Launcher(np=1, devices_per_proc=1, timeout_s=120),
                         max_restarts=1, backoff_base_s=0.05, jitter=0.0)
    out = sup.run(functools.partial(_async_ckpt_worker, ckpt_dir,
                                    TOTAL_STEPS))
    assert out["final_step"] == TOTAL_STEPS
    # writes retire in order on the writer thread: steps 1 and 2 were
    # durable before the torn step-3 write began -> clean fallback restore
    assert out["resume_step"] == 2
    names = os.listdir(ckpt_dir)
    assert any(n.startswith("step_0000000003.torn") for n in names)
    assert "step_0000000003" not in [n for n in names if "." not in n]


@pytest.mark.faults
@pytest.mark.slow
def test_torn_async_sharded_write_quarantined(tmp_path, monkeypatch,
                                              worker_pythonpath):
    """The sharded-format twin of the torn-async drill: proc_bytes
    completeness + quarantine hold when the commit protocol runs on the
    background writer."""
    ckpt_dir = str(tmp_path / "ck")
    monkeypatch.setenv("DDW_FAULT", "ckpt_async_torn:rank=0:step=3")
    sup = GangSupervisor(Launcher(np=1, devices_per_proc=1, timeout_s=120),
                         max_restarts=1, backoff_base_s=0.05, jitter=0.0)
    out = sup.run(functools.partial(_async_ckpt_worker, ckpt_dir,
                                    TOTAL_STEPS, True))
    assert out["final_step"] == TOTAL_STEPS
    assert out["resume_step"] == 2
    assert any(n.startswith("step_0000000003.torn")
               for n in os.listdir(ckpt_dir))


# -- shrink recovery: N-1 elastic reshard ------------------------------------
#
# The two-phase shrink protocol, unit-tested on threaded fake gangs, then
# drilled with real processes: a PERMANENTLY lost rank (host_lost) makes the
# survivors vote a contiguous re-rank into a committed shrink record and the
# run completes at world-1, bit-identical to an uninterrupted run at the
# shrunken size restored from the same checkpoint.

def _pop_topology_env():
    """advance() on a shrink/grow record mirrors the remapped identity into
    the process env; threaded fakes share this process's env, so tests that
    adopt records clean up after themselves."""
    for k in ("DDW_ELASTIC_GEN", "DDW_PROCESS_ID", "DDW_NUM_PROCESSES"):
        os.environ.pop(k, None)


def test_shrink_two_phase_vote_then_commit(tmp_path):
    """Survivors vote on a shrink proposal but adopt NOTHING until the
    driver's commit marker lands — a proposal abandoned mid-vote strands
    no one halfway into a world that never forms."""
    root = str(tmp_path)
    r0 = GangRendezvous(root, world_size=3, rank=0)
    r1 = GangRendezvous(root, world_size=3, rank=1)
    driver = GangRendezvous(root, 3, -1)
    try:
        driver.post_shrink(1, dead_rank=2, assignment={0: 0, 1: 1},
                           world_size=2, exit_code=85)
        r0._check_recovery(0)                   # votes ack, keeps parking
        assert driver.read_votes(1) == {0: "ack"}
        assert r0.generation == 0               # not adopted: no commit yet
        r1._check_recovery(0)
        votes = driver.wait_votes(1, [0, 1], timeout_s=5.0)
        assert votes == {0: "ack", 1: "ack"}
        driver.commit_recovery(1)
        with pytest.raises(ElasticRestart) as exc:
            r0._check_recovery(7)
        assert exc.value.generation == 1 and exc.value.step == 7
        r0.advance(1)
        assert (r0.rank, r0.world_size) == (0, 2)
        with pytest.raises(ElasticRestart):
            r1._check_recovery(None)
        r1.advance(1)
        assert (r1.rank, r1.world_size) == (1, 2)
        # the env mirror follows the LAST adopter (one process per rank in
        # real gangs; threads share the env here)
        assert os.environ["DDW_NUM_PROCESSES"] == "2"
    finally:
        _pop_topology_env()


def test_shrink_remap_and_evicted_zombie(tmp_path):
    """A non-identity assignment renumbers survivors contiguously; the
    evicted rank itself (a zombie the driver gave up on) cannot adopt the
    record — ElasticRestart out of the park, RuntimeError on advance."""
    root = str(tmp_path)
    driver = GangRendezvous(root, 3, -1)
    try:
        driver.post_shrink(1, dead_rank=0, assignment={1: 0, 2: 1},
                           world_size=2, exit_code=85)
        driver.commit_recovery(1)
        r2 = GangRendezvous(root, world_size=3, rank=2)
        with pytest.raises(ElasticRestart):
            r2._check_recovery(4)
        r2.advance(1)
        assert (r2.rank, r2.world_size) == (1, 2)
        zombie = GangRendezvous(root, world_size=3, rank=0)
        with pytest.raises(ElasticRestart) as exc:
            zombie._check_recovery(4)
        with pytest.raises(RuntimeError, match="evicted"):
            zombie.advance(exc.value.generation)
    finally:
        _pop_topology_env()


def test_shrink_veto_pins_until_retry_supersedes(tmp_path, monkeypatch):
    """shrink_veto vetoes exactly the first proposal this process votes on
    (vote-ordinal matching): the vetoer stays pinned — even a commit marker
    cannot move it — until the driver's retry at a bumped generation, which
    it acks and adopts."""
    monkeypatch.setenv("DDW_FAULT", "shrink_veto")
    root = str(tmp_path)
    r0 = GangRendezvous(root, world_size=2, rank=0)
    driver = GangRendezvous(root, 2, -1)
    try:
        driver.post_shrink(1, dead_rank=1, assignment={0: 0}, world_size=1)
        r0._check_recovery(3)                   # casts the veto, stays parked
        assert driver.read_votes(1) == {0: "veto"}
        driver.commit_recovery(1)
        r0._check_recovery(3)                   # still pinned despite commit
        assert r0.generation == 0
        driver.post_shrink(2, dead_rank=1, assignment={0: 0}, world_size=1)
        r0._check_recovery(3)                   # second vote ordinal: ack
        assert driver.read_votes(2) == {0: "ack"}
        driver.commit_recovery(2)
        with pytest.raises(ElasticRestart) as exc:
            r0._check_recovery(3)
        assert exc.value.generation == 2
        r0.advance(2)
        assert (r0.rank, r0.world_size) == (0, 1)
    finally:
        _pop_topology_env()


def test_shrink_vote_timeout_returns_none(tmp_path):
    """A survivor that cannot vote cannot adopt either: the driver's wait
    times out to None and the launcher falls back to whole-world."""
    driver = GangRendezvous(str(tmp_path), 2, -1)
    driver.post_shrink(1, dead_rank=1, assignment={0: 0}, world_size=1)
    assert driver.wait_votes(1, [0], timeout_s=0.3) is None
    assert not driver.recovery_committed(1)


def test_reduce_membership_follows_shrunken_world(tmp_path):
    """The generation-aware-membership satellite pin: barrier/reduce scans
    use the ADOPTED world size, so a survivor gang at world-1 never waits
    on the evicted rank's part file (the construction-time
    range(self.world_size) would)."""
    root = str(tmp_path)
    driver = GangRendezvous(root, 3, -1)
    driver.post_shrink(1, dead_rank=0, assignment={1: 0, 2: 1},
                       world_size=2, exit_code=85)
    driver.commit_recovery(1)
    out = {}

    def survivor(i):
        rdzv = GangRendezvous(root, world_size=3, rank=i + 1)
        with pytest.raises(ElasticRestart):
            rdzv.all_reduce(5, 99.0, timeout_s=20.0)
        rdzv.advance(1)
        assert (rdzv.rank, rdzv.world_size) == (i, 2)
        rdzv.announce()
        rdzv.barrier("start", timeout_s=20.0)
        out[rdzv.rank] = float(rdzv.all_reduce(5, float(rdzv.rank + 1),
                                               timeout_s=20.0))

    try:
        assert _threads(2, survivor) == []
    finally:
        _pop_topology_env()
    # gen-1 reduce folds exactly the two survivors; gen-0's aborted
    # contribution (99.0) is invisible to the re-formed gang
    assert out == {0: 3.0, 1: 3.0}


def test_fault_spec_host_lost_and_shrink_veto():
    from ddw_tpu.runtime.faults import EXIT_HOST_LOST, parse_fault

    assert EXIT_HOST_LOST == 85
    spec = parse_fault("host_lost:rank=2:step=3")
    assert spec.kind == "host_lost" and spec.site == "step"
    # egen defaults to ANY: a lost host stays lost — a respawn of that rank
    # (which the launcher must not attempt) would die again immediately
    assert spec.matches("step", step=3, rank=2, gen=0, egen=0, attempt=0)
    assert spec.matches("step", step=3, rank=2, gen=0, egen=4, attempt=0)
    assert not spec.matches("step", step=3, rank=1, gen=0, egen=0, attempt=0)
    veto = parse_fault("shrink_veto:rank=0")
    assert veto.site == "shrink_vote"
    # step defaults to vote ordinal 0: veto the FIRST proposal, ack the retry
    assert veto.matches("shrink_vote", step=0, rank=0, gen=0, egen=0,
                        attempt=0)
    assert not veto.matches("shrink_vote", step=1, rank=0, gen=0, egen=0,
                            attempt=0)
    always = parse_fault("shrink_veto:rank=0:step=*")
    assert always.matches("shrink_vote", step=5, rank=0, gen=0, egen=0,
                          attempt=0)


def test_fault_multi_spec_chain(monkeypatch):
    """';'-chained specs arm independent hook sites in one env var — the
    shrink drills need host_lost (step site) and shrink_veto (vote site)
    simultaneously."""
    from ddw_tpu.runtime.faults import active_faults

    monkeypatch.setenv("DDW_FAULT",
                       "host_lost:rank=2:step=3;shrink_veto:rank=0")
    specs = active_faults()
    assert [s.kind for s in specs] == ["host_lost", "shrink_veto"]
    assert specs[0].site == "step" and specs[1].site == "shrink_vote"


# -- real-process shrink drills ----------------------------------------------

N_SAMPLES = 8


def _shrink_worker(ckpt_dir: str, total_steps: int) -> dict:
    """Shrink-drill worker: each step's gang contribution is a coverage
    vector over N_SAMPLES virtual samples partitioned by
    ShardedLoader.shard_plan at the CURRENT (rank, world) — the reduce
    proves every sample is covered exactly once per step at every world
    size, and the parameter update (w += 1..N) is world-independent, so the
    final params must be bit-identical to any uninterrupted run's."""
    import os

    import numpy as np

    from ddw_tpu.checkpoint.ckpt import CheckpointManager
    from ddw_tpu.data.loader import ShardedLoader
    from ddw_tpu.runtime import elastic
    from ddw_tpu.runtime.faults import maybe_fault

    mgr = CheckpointManager(ckpt_dir, keep=total_steps + 2)
    state = {"w": np.zeros((N_SAMPLES,), np.float32),
             "step": np.asarray(0, np.int32)}
    start = 0
    if mgr.latest_step() is not None:
        state, start = mgr.restore(state)
        start = int(start)
    elastic.elastic_barrier("start")
    coverage_ok = True
    for step in range(start, total_steps):
        maybe_fault("step", step=step, ckpt_dir=ckpt_dir)
        elastic.maybe_elastic_restart(step=step)
        rank, world = elastic.process_topology()
        contrib = np.zeros((N_SAMPLES + 1,), np.float64)
        contrib[0] = 1.0                    # world-size head count
        for i in ShardedLoader.shard_plan(N_SAMPLES, world)[rank]:
            contrib[i + 1] = float(i + 1)   # this rank's sample slice
        tot = elastic.host_all_reduce(step, contrib)
        # exactly-once coverage at the CURRENT world: the head counts the
        # contributors, the tail must be each sample's value exactly once
        coverage_ok = (coverage_ok and tot[0] == world
                       and bool(np.array_equal(
                           tot[1:], np.arange(1., N_SAMPLES + 1.))))
        state = {"w": state["w"] + tot[1:].astype(np.float32),
                 "step": np.asarray(step + 1, np.int32)}
        mgr.save(state, step + 1)
    mgr.close()
    ctx = elastic.context()
    rank, world = elastic.process_topology()
    return {"final_step": int(state["step"]), "resume_step": start,
            "w": [float(x) for x in state["w"]], "pid": os.getpid(),
            "egen": ctx.generation if ctx is not None else 0,
            "world": world, "coverage_ok": bool(coverage_ok)}


def _shrink_gang(tmp_path, np_=3, **kw):
    kw.setdefault("elastic_restarts", 1)
    kw.setdefault("min_world_size", 2)
    return Launcher(np=np_, devices_per_proc=1, timeout_s=120,
                    rendezvous_dir=str(tmp_path / "rdzv"), **kw)


@pytest.mark.faults
def test_shrink_recovery_on_host_lost(tmp_path, monkeypatch,
                                      worker_pythonpath):
    """The tentpole acceptance drill: rank 2 of 3 dies PERMANENTLY
    (host_lost) mid-epoch — the survivors vote, shrink to world 2, keep
    their pids, cover every sample exactly once at the new size, and finish
    with params bit-identical to an uninterrupted 2-rank run restored from
    the same checkpoint. Forensics land as recovery="shrink" with the
    old/new world, and the tracker carries shrink_recoveries + the
    gang.world_size timeline."""
    import shutil

    from ddw_tpu.tracking.tracker import Tracker

    ckpt = str(tmp_path / "ck")
    monkeypatch.setenv("DDW_FAULT", "host_lost:rank=2:step=3")
    launcher = _shrink_gang(tmp_path)
    run = Tracker(str(tmp_path / "mlruns"), "gang").start_run("shrink")
    sup = GangSupervisor(launcher, max_restarts=0, backoff_base_s=0.05,
                         jitter=0.0, tracker_run=run)
    out = sup.run(functools.partial(_shrink_worker, ckpt, TOTAL_STEPS))
    run.end()

    # resumed at the last durable step, completed at world 2, and EVERY
    # sample was covered exactly once per step at both world sizes
    assert out["final_step"] == TOTAL_STEPS
    assert out["resume_step"] == 3
    assert out["world"] == 2 and out["egen"] == 1
    assert out["coverage_ok"] is True
    assert out["w"] == [TOTAL_STEPS * float(i) for i in
                        range(1, N_SAMPLES + 1)]

    # one shrink event: rank 2 evicted with its exit code, no respawn pid
    assert [e.kind for e in launcher.elastic_events] == ["shrink"]
    ev = launcher.elastic_events[0]
    assert ev.dead_rank == 2 and ev.exit_code == 85
    assert ev.respawn_pid is None
    assert (ev.old_world, ev.new_world) == (3, 2)

    # survivors kept their pids across the shrink (the membership ledger
    # at gen 1 shows the same processes under their — here identical —
    # contiguous ranks), and the evicted rank never announced again
    rdzv = GangRendezvous(launcher.last_rendezvous_dir, 2, -1)
    for r in (0, 1):
        assert rdzv.member(1, r)["pid"] == rdzv.member(0, r)["pid"]
    assert rdzv.member(1, 2) is None
    assert out["pid"] == rdzv.member(1, 0)["pid"]

    # supervisor forensics + telemetry: recovery="shrink" with the worlds,
    # and the world-size gauge walks 3 -> 2
    assert [a.recovery for a in sup.attempts] == ["shrink"]
    a = sup.attempts[0]
    assert a.dead_rank == 2 and a.kind == "rank-death"
    assert (a.old_world_size, a.new_world_size) == (3, 2)
    assert run.final_metrics()["supervisor.shrink_recoveries"] == 1.0
    assert [v for _, v in run.metric_history("gang.world_size")] == [3.0, 2.0]

    # bit-identity: an uninterrupted 2-rank gang restored from a COPY of
    # the same step-3 checkpoint must produce the identical params
    ref_ckpt = str(tmp_path / "ref_ck")
    os.makedirs(ref_ckpt)
    shutil.copytree(os.path.join(ckpt, "step_0000000003"),
                    os.path.join(ref_ckpt, "step_0000000003"))
    monkeypatch.delenv("DDW_FAULT")
    ref = GangSupervisor(
        Launcher(np=2, devices_per_proc=1, timeout_s=120, elastic_restarts=1,
                 rendezvous_dir=str(tmp_path / "rdzv_ref")),
        max_restarts=0, backoff_base_s=0.05, jitter=0.0,
    ).run(functools.partial(_shrink_worker, ref_ckpt, TOTAL_STEPS))
    assert ref["resume_step"] == 3 and ref["coverage_ok"] is True
    assert ref["w"] == out["w"]


@pytest.mark.faults
@pytest.mark.slow   # two extra real-process gang drills — tier-2 budget
def test_shrink_veto_retry_then_adopt(tmp_path, monkeypatch,
                                      worker_pythonpath):
    """A survivor vetoes the first shrink proposal (one-shot shrink_veto
    arm); the driver retries at a bumped generation, the retry is acked
    unanimously and the run completes at world 2 — the adopted record is
    generation 2, not 1."""
    monkeypatch.setenv("DDW_FAULT",
                       "host_lost:rank=2:step=3;shrink_veto:rank=0")
    launcher = _shrink_gang(tmp_path)
    sup = GangSupervisor(launcher, max_restarts=0, backoff_base_s=0.05,
                         jitter=0.0)
    out = sup.run(functools.partial(_shrink_worker, str(tmp_path / "ck"),
                                    TOTAL_STEPS))
    assert out["final_step"] == TOTAL_STEPS
    assert out["world"] == 2 and out["coverage_ok"] is True
    assert [e.kind for e in launcher.elastic_events] == ["shrink"]
    assert launcher.elastic_events[0].generation == 2   # gen 1 was vetoed
    assert out["egen"] == 2


@pytest.mark.faults
@pytest.mark.slow
def test_shrink_always_vetoed_falls_back_to_whole_world(
        tmp_path, monkeypatch, worker_pythonpath):
    """A survivor that vetoes EVERY proposal (step=*) exhausts the shrink
    retries: no shrink is committed, the gang is killed, and the
    supervisor's whole-world restart completes the run — the fallback the
    shrink path must never replace."""
    monkeypatch.setenv("DDW_FAULT",
                       "host_lost:rank=2:step=3;shrink_veto:rank=0:step=*")
    launcher = _shrink_gang(tmp_path)
    sup = GangSupervisor(launcher, max_restarts=1, backoff_base_s=0.05,
                         jitter=0.0)
    out = sup.run(functools.partial(_shrink_worker, str(tmp_path / "ck"),
                                    TOTAL_STEPS))
    assert out["final_step"] == TOTAL_STEPS
    assert out["resume_step"] == 3          # whole-world restore point
    assert out["world"] == 3                # full world, never shrunk
    assert not any(e.kind == "shrink" for e in launcher.elastic_events)
    assert ("crash", "whole-world") in [(a.kind, a.recovery)
                                        for a in sup.attempts]


@pytest.mark.faults
@pytest.mark.slow
def test_shrink_below_min_world_falls_back_to_whole_world(
        tmp_path, monkeypatch, worker_pythonpath):
    """min_world_size is the floor: a permanent loss that would shrink
    below it goes straight to the whole-world ladder rung."""
    monkeypatch.setenv("DDW_FAULT", "host_lost:rank=1:step=3")
    launcher = _shrink_gang(tmp_path, np_=2)    # 2 - 1 < min_world_size=2
    sup = GangSupervisor(launcher, max_restarts=1, backoff_base_s=0.05,
                         jitter=0.0)
    out = sup.run(functools.partial(_shrink_worker, str(tmp_path / "ck"),
                                    TOTAL_STEPS))
    assert out["final_step"] == TOTAL_STEPS
    assert out["world"] == 2
    assert launcher.elastic_events == []
    assert ("crash", "whole-world") in [(a.kind, a.recovery)
                                        for a in sup.attempts]


@pytest.mark.faults
@pytest.mark.slow
def test_gang_drill_cli_smoke(tmp_path):
    """tools/gang_drill.py is the operator-facing drill: run its smoke mode
    as a subprocess and hold it to its own CI-gate contract — exit 0 with a
    one-line JSON verdict covering shrink, regrow and bit-identity."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, DDW_DRILL_SMOKE="1", JAX_PLATFORMS="cpu")
    env.pop("DDW_FAULT", None)          # the drill arms its own fault
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "gang_drill.py"),
         "--out", str(tmp_path / "drill")],
        capture_output=True, text=True, env=env, cwd=repo, timeout=900)
    assert out.returncode == 0, f"drill failed:\n{out.stdout}\n{out.stderr}"
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["verdict"] == "ok" and d["bit_identical"] is True
    kinds = [e["kind"] for e in d["events"]]
    assert "shrink" in kinds and "grow" in kinds
    assert d["drill"]["coverage_ok"] and d["reference"]["coverage_ok"]
