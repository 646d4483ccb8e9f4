"""A run of each cell at tiny sizes on the CPU, the look for a chip skipped:
the program against the plain reference in float32, and what ``correct`` does
when the timed path is broken underneath or a lower precision stands in."""

import importlib

import pytest

from benchmark import run as bench_run
from benchmark.harness import check
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.tiny import TINY

MANIFEST = load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def tiny_for(name):
    return TINY[Cell(MANIFEST, name).config["family"]]


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_reference_in_float32(name):
    out = bench_run.rehearse(name, 2 ** 31 + 5, 0.5, False, tiny_for(name))
    assert out["rehearsal"] is True and "metrics" not in out
    assert out["correct"] is True, out["checks"]
    # float32 on both sides: far inside any limit a bfloat16 cell carries
    assert out["checks"]["loss_gap"] < 1e-5
    assert out["checks"]["grad_norm_gap"] < 1e-4
    assert out["checks"]["compiles_in_window"] == 0
    assert out["attempted"] == out["window"]["steps"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        name, monkeypatch):
    family = Cell(MANIFEST, name).family
    module = importlib.import_module(family.STEP_FACTORY[0])
    real = getattr(module, family.STEP_FACTORY[1])

    def broken_factory(*a, **kw):
        kw["donate"] = False
        step = real(*a, **kw)

        def broken(state, *args):
            new_state, metrics = step(state, *args)
            return state.replace(step=new_state.step,
                                 opt_state=new_state.opt_state), metrics

        broken.batch_sharding = getattr(step, "batch_sharding", None)
        return broken

    monkeypatch.setattr(module, family.STEP_FACTORY[1], broken_factory)
    out = bench_run.rehearse(name, 7, 0.3, False, tiny_for(name))
    assert out["correct"] is False
    assert out["checks"]["delta_norm_gap"] > Cell(MANIFEST, name).limits[
        "delta_norm_gap"]


def test_the_command_refuses_without_a_chip(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    printed = capsys.readouterr().out
    assert "metrics" not in printed and "correct" not in printed


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_fails_the_cells_limits(name, capsys):
    """The reference in fp8 put in the program's place, against the float32
    reference, at a size a test can hold: it has to fail one of the cell's
    limits (on the chip it was read at the cell's own size, PERF.md)."""
    cell = Cell(MANIFEST, name)
    tiny = tiny_for(name)
    config = dict(cell.config, **tiny["config"])
    traffic = dict(cell.traffic, **tiny["traffic"])
    batches = cell.family.tiny_batches(config, traffic, seed=3, steps=3)
    hyper = cell.family.hyper(traffic)
    ref = check.reference_steps(cell.family, config, 3, batches, hyper,
                                traffic["reference_micro_rows"])
    low = check.reference_steps(cell.family, config, 3, batches, hyper,
                                traffic["reference_micro_rows"], "fp8")
    numbers = check.compare_steps(low, ref)
    numbers.update(first_step_loss_ratio=1.0, compiles_in_window=0,
                   nonfinite_epochs=0)
    assert check.judge(numbers, cell.limits) is False, numbers
