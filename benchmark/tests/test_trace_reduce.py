"""The reduction from a trace to busy, idle, collective and per-operation
times, on a small recorded trace with answers computed by hand."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.harness.manifest import load_manifest

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return tr.reduce(json.load(f))


def test_interval_arithmetic():
    assert tr.union([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert tr.length([[1, 4], [5, 7]]) == 5
    assert tr.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 8]], [[1, 7]]) == [[0, 1], [7, 8]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def test_window_and_busy_union(reduced):
    # first operation starts at 1000 (chip 0), last ends at 11000 (chip 0)
    assert reduced["window_ns"] == 10000
    # chip 0: [1000,4500] + [5000,9000] + [10000,11000] = 8500
    # chip 1: [1500,4000] + [6000,9000] = 5500
    assert reduced["busy_mean_ns"] == (8500 + 5500) / 2
    assert reduced["busy_min_ns"] == 5500


def test_idle_share_is_the_worst_chips(reduced):
    read = _reader("device_idle_pct")
    assert read({"reduced": reduced}) == pytest.approx(45.0)


def test_collectives_and_their_exposed_part(reduced):
    # chip 0: all-reduce.1 2000 + all-reduce.2 1000; all-reduce.1 runs
    # [2500,4500] of which fusion.1 covers up to 3000 -> 1500 exposed, and
    # all-reduce.2 runs alone -> 1000 exposed
    assert reduced["collective_ns"] == 3000
    assert reduced["collective_exposed_ns"] == 2500
    ctx = {"reduced": reduced, "chips": 2, "traced": {"steps": 2}}
    assert _reader("collective_exposed_pct")(ctx) == pytest.approx(25.0)
    assert _reader("collective_ms")(ctx) == pytest.approx(3000 / 2 / 1e6)
    assert _reader("collective_ms")(dict(ctx, chips=1)) is None


def test_step_busy_is_per_dispatched_step(reduced):
    ctx = {"reduced": reduced, "traced": {"steps": 2}}
    assert _reader("step_busy_ms")(ctx) == pytest.approx(7000 / 2 / 1e6)
    assert _reader("step_busy_ms")({"reduced": None, "traced": None}) is None


def test_top_operations_and_gaps(reduced):
    ops = dict(reduced["top_ops"])
    assert ops["fusion.2"] == 3000 and ops["fusion.1"] == 2000
    assert ops["all-reduce.1"] == 1250 and ops["copy"] == 1000
    assert reduced["top_ops"][0][0] == "fusion.2"
    assert reduced["top_families"][:3] == [["fusion", 5000.0],
                                           ["all-reduce", 1750.0],
                                           ["copy", 1000.0]]
    longest = [(e - s, dev) for s, e, dev, _ in reduced["gaps"][:3]]
    assert longest[0] == (2000, "/device:TPU:1")
    assert longest[1] == (2000, "/device:TPU:1")
    assert longest[2] == (1000, "/device:TPU:0")
    # chip 1 starts late and ends early: both edges count as gaps
    placed = {(s, e, dev): at for s, e, dev, at in reduced["gaps"]}
    # chip 1's train step runs [1500,4000] and [6000,9000]
    assert placed[(4000, 6000, "/device:TPU:1")] == {
        "module": "jit__step", "step": 2, "of": 2, "where": "before"}
    assert placed[(9000, 11000, "/device:TPU:1")]["where"] == "after_last"
    assert placed[(1000, 1500, "/device:TPU:1")] == {
        "module": "jit__step", "step": 1, "of": 2, "where": "before"}
    # chip 0: the step runs [1000,4500] and [5000,9000]; jit__eval is not it
    assert placed[(4500, 5000, "/device:TPU:0")]["step"] == 2
    assert placed[(9000, 10000, "/device:TPU:0")]["where"] == "after_last"


def test_a_gap_inside_an_execution():
    at = tr.place_gap([120, 130, "d"], [["jit__step", 0, 100],
                                        ["jit__step", 110, 100],
                                        ["jit_eval", 300, 10]])
    assert at == {"module": "jit__step", "step": 2, "of": 2, "where": "inside"}
    assert tr.place_gap([1, 2, "d"], []) == {}


def test_names():
    assert tr.is_collective("all-reduce-start.3")
    assert tr.is_collective("reduce-scatter.1")
    assert not tr.is_collective("fusion.12")
    assert tr.op_family("fusion.123") == "fusion"
    assert tr.op_family("copy") == "copy"
    assert tr.module_name("jit__step(4061511910469569733)") == "jit__step"
    assert tr.op_name("%fusion.12 = f32[8,1024]{1,0} fusion(f32[8] %p)") == \
        "fusion.12"


def test_every_listed_metric_has_a_reader_file():
    manifest = load_manifest()
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert callable(_reader(m["name"])), m["name"]


def _reader(name):
    import importlib

    return importlib.import_module("benchmark.metrics." + name).read
