"""One run of a training cell: set-up, window, readings, comparison.

Shared by every family whose system under test is a trainer's ``fit``; the
family module says how to write the tables, build the trainer and what the
reference is (``families/lm_train.py`` is the pattern).
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import tempfile
import time

from benchmark.harness import check
from benchmark.harness.step_probe import probing
from benchmark.harness.train_window import (WARM_EPOCHS, CompileCounter,
                                            EpochClock)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: dict | None, tiny: dict | None = None,
        controls: tuple = (), keep_trace: str = "") -> dict:
    """Returns what ``run.py`` prints. ``peaks`` is None only in a rehearsal
    (``tiny`` sizes on the CPU), which reports no device metric. ``controls``
    (``tools/sweep.py`` only): precisions in which the reference is run again
    in the program's place, each compared with the float32 reference."""
    from ddw_tpu.runtime.faults import (Preempted, request_preemption,
                                        reset_preemption)

    family = cell.family
    config = dict(cell.config, **(tiny or {}).get("config", {}))
    traffic = dict(cell.traffic, **(tiny or {}).get("traffic", {}))
    chips = len(devices)
    seed31 = int(seed) % (2 ** 31 - 1)
    work = tempfile.mkdtemp(prefix="ddw_bench_")
    compiles = CompileCounter()
    try:
        job = family.prepare(config, traffic, seed31, work, devices)
        t_tables = time.time()
        tracer = None
        if trace:
            from ddw_tpu.obs.trace import Tracer

            tracer = Tracer(capacity=65536, process="bench")
        probes: list = []
        warm = int(traffic.get("warm_epochs", WARM_EPOCHS))
        clock = EpochClock(seconds, compiles, request_preemption,
                           trace_dir=(work + "/trace") if trace else "",
                           probe_calls=lambda: probes[0].calls if probes else 0,
                           warm_epochs=warm,
                           window_epochs=int(traffic.get("window_epochs", 0)))
        t_trainer = time.time()
        spec = family.reference_spec(config)
        mapping = family.leaf_map(config)
        with probing(family.STEP_FACTORY, seed, spec, mapping) as probes:
            try:
                job.fit(clock, tracer)
                raise RuntimeError("fit ended before the window closed")
            except Preempted:
                pass
            finally:
                clock.abandon_trace()
                reset_preemption()
        probe = probes[0]
        t_first, t_open = clock.ends[0], clock.ends[warm - 1]
        steps_per_epoch = job.steps_per_epoch
        window = clock.window(steps_per_epoch, job.items_per_step)
        program = probe.collect()
        batches = probe.batches
        stamps = probe.stamps
        peak_bytes = max(_peak_bytes(d.memory_stats() or {}) for d in devices)
        spans = tracer.drain() if tracer is not None else []
        traced = clock.traced
        record = None
        if trace:
            from benchmark.harness import trace_reduce

            xplane = trace_reduce.find_xplane(work + "/trace")
            record = trace_reduce.load_xplane(xplane)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, keep_trace)
        # free the program's state before the reference takes the device
        # (the clock froze the garbage collector's view when the window opened)
        del probe, probes, job
        gc.unfreeze()
        gc.collect()

        # setup_s: process start to the window's opening. perf_counter and
        # time.time tick alike; the epochs' ends were stamped on perf_counter,
        # so carry them over by the offset taken now.
        offset = time.time() - time.perf_counter()
        setup_s = (t_open + offset) - t_start
        rows = clock.rows
        nonfinite = sum(1 for r in rows[warm:]
                        if not all(math.isfinite(r[k])
                                   for k in ("loss", "val_loss")))
        print("setup: process start -> tables "
              f"{t_tables - t_start:.1f} s; trainer -> step factory (model "
              f"init, optimizer state) {stamps[0] - t_trainer:.1f} s; -> first "
              f"step called {stamps[1] - stamps[0]:.1f} s; " + "; ".join(
                  f"call {i} took {b - a:.1f} s" for i, (a, b) in
                  enumerate(zip(stamps[1:], stamps[2:]), start=1))
              + f"; -> first epoch's record {t_first + offset - stamps[-1]:.1f}"
              f" s; memory_stats {devices[0].memory_stats()}", flush=True)
        t_ref = time.time()
        reference = check.reference_steps(
            family, config, seed, batches, family.hyper(traffic),
            traffic["reference_micro_rows"], devices=devices)
        numbers = check.compare_steps(program, reference)
        numbers["first_step_loss_ratio"] = (
            program["losses"][0] / family.loss_at_random(config))
        numbers["compiles_in_window"] = len(compiles.in_window)
        numbers["nonfinite_epochs"] = nonfinite
        print(f"check program losses {program['losses']} reference "
              f"{reference['losses']}; worst leaves {numbers['_where']}; "
              f"reference took {time.time() - t_ref:.1f} s", flush=True)
        correct = check.judge(numbers, cell.limits)
        control_numbers = {}
        for precision in controls:
            lowered = check.reference_steps(
                family, config, seed, batches, family.hyper(traffic),
                traffic["reference_micro_rows"], precision, devices)
            control_numbers[precision] = check.compare_steps(lowered,
                                                             reference)

        ctx = {
            "cell": cell.name, "chips": chips, "config": config,
            "traffic": traffic, "window": window, "setup_s": setup_s,
            "tables_s": t_tables - t_start,
            "first_epoch_s": (t_first + offset) - t_trainer,
            "peak_bytes": peak_bytes, "spans": spans, "traced": traced,
            "record": record, "rows": rows, "warm_epochs": warm,
            "flops_per_item": family.required_flops_per_item(config),
            "peaks": peaks, "steps_per_epoch": steps_per_epoch,
            "reduced": None,        # run.py fills it from ``record``
        }
        return {"correct": bool(correct), "attempted": window["steps"],
                "failed": nonfinite * steps_per_epoch, "ctx": ctx,
                "controls": control_numbers,
                "numbers": {k: v for k, v in numbers.items()
                            if not k.startswith("_")}}
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _peak_bytes(stats: dict) -> int:
    """What a chip held at its peak, from the runtime's two counters:
    ``peak_bytes_in_use`` counts buffers (state, batches, executables) and
    ``peak_bytes_reserved`` the scratch that running programs reserve, which
    the first does not see (GPT-2 medium: 5.29 GB + 5.86 GB where the compiler
    plans 4.88 GB of arguments and 6.00 GB of temporaries). A step runs with
    both held, so the peak is their sum."""
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))
