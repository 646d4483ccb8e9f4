"""``harness/span_clock.py`` on the recorded trace of ``test_trace_reduce.py``
and a small hand-made span list: the clocks are joined by the fetch anchor
whatever the program's clock counts from, the four idle shares add up to the
idle share, and the span-only readers read a tiny traced run's spans."""

import json
import os

import pytest

from benchmark.harness import span_clock, trace_reduce
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.tiny import TINY

HERE = os.path.dirname(__file__)
MANIFEST = load_manifest()
NEW = ["data_wait_ms", "loader_batch_ms", "loader_h2d_ms", "dispatch_ms",
       "idle_in_data_wait_pct", "idle_in_dispatch_pct",
       "idle_at_boundary_pct", "idle_unattributed_pct", "clock_residual_ms",
       "trainer_init_s", "step_load_s"]


def _reader(name):
    return Cell(MANIFEST, MANIFEST["workloads"][0]["name"]).reader(name)


# name, start and end on the profile's clock (ns), parent's name
TREE = [("epoch", 100, 12000, None),
        ("train_chain", 200, 1050, "epoch"),
        ("data_wait", 200, 400, "train_chain"),
        ("dispatch", 400, 1050, "train_chain"),
        ("train_chain#2", 1050, 6500, "epoch"),
        ("data_wait#2", 1100, 5500, "train_chain#2"),
        ("dispatch#2", 5500, 6500, "train_chain#2"),
        ("validation", 6500, 9500, "epoch"),
        ("val_data_wait", 6500, 7000, "validation"),
        ("val_dispatch", 7000, 9500, "validation"),
        ("epoch_fetch", 9500, 11000, "epoch"),
        ("epoch_report", 11000, 11800, "epoch")]


def _span(key, start_ns, end_ns, parent, shift_us, tid="train"):
    """A tracer event whose interval, on the profile's clock, is
    [start_ns, end_ns]; the host's clock is ``shift_us`` ahead of it."""
    return {"name": key.split("#")[0], "ph": "X", "tid": tid, "span": key,
            "parent": parent, "ts": shift_us + start_ns / 1e3,
            "dur": (end_ns - start_ns) / 1e3}


def make_ctx(shift_us):
    """The recorded trace (two chips; chip 1 is busy least: [1500,4000] and
    [6000,9000] of the window [1000,11000]) under a program whose train thread
    ran ``TREE``: the first dispatch returns at 1050 as the step's first
    execution starts at 1000 (chip 0), and the epoch's fetch ends with the
    last operation at 11000."""
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        record = json.load(f)
    spans = [_span(*row, shift_us) for row in TREE]
    spans.append(_span("loader_batch", 0, 20000, None, shift_us, tid="loader"))
    return {"record": record, "reduced": trace_reduce.reduce(record),
            "spans": spans,
            "traced": {"wall_start": (shift_us + 0.15) / 1e6,
                       "wall_end": (shift_us + 11.9) / 1e6, "steps": 2}}


def test_innermost_names_each_moment_by_its_deepest_span():
    pieces = span_clock.innermost([[0, 10, "a", 0], [2, 5, "b", 1],
                                   [3, 4, "c", 2], [12, 14, "d", 0],
                                   [5, 7, "e", 1]])
    assert pieces == [[0, 2, "a"], [2, 3, "b"], [3, 4, "c"], [4, 5, "b"],
                      [5, 7, "e"], [7, 10, "a"], [12, 14, "d"]]
    assert span_clock.overlaps([[1, 4], [9, 13]], pieces) == [
        {"a": 1, "b": 1, "c": 1}, {"a": 1, "d": 1}]
    # siblings that share a stamp and overlap by a rounding stay siblings
    # ("b" is not taken for a child of "a"), as the parent ids say
    assert span_clock.innermost([[0, 5000.3, "a", 1], [5000, 90000, "b", 1],
                                 [5000, 60000, "c", 2], [0, 90000, "e", 0]]
                                ) == [[0, 5000, "a"], [5000, 60000, "c"],
                                      [60000, 90000, "b"]]
    ids = [{"span": "r", "parent": None}, {"span": "k", "parent": "r"},
           {"span": "g", "parent": "k"}, {"span": "o", "parent": "gone"}]
    assert span_clock.depths(ids) == {"r": 0, "k": 1, "g": 2, "o": 0}


@pytest.mark.parametrize("shift_us", [0.0, 1.79e15, 123456.0])
def test_the_fetch_anchor_finds_the_shift_and_the_shares_add_up(shift_us):
    ctx = make_ctx(shift_us)
    found = span_clock.idle_by_span(ctx)
    clock = found["clock"]
    # the host's clock read back through the anchor lands on the profile's:
    # doubles near 1.8e15 us carry a quarter of a microsecond
    on_profile = (shift_us + 9.5 - clock["base_us"]) * 1e3 - clock["offset_ns"]
    assert on_profile == pytest.approx(9500, abs=600)
    # dispatch anchor: the first dispatch returns at 1050, the step's first
    # execution starts at 1000; the fetch anchor is exact here
    assert clock["residual_ns"] == pytest.approx(50, abs=600)
    assert clock["dispatch_start_offset_ns"] == pytest.approx(
        clock["offset_ns"] - 600, abs=600)
    assert found["chip"] == "/device:TPU:1"
    assert found["window_ns"] == 10000 and found["idle_ns"] == 4500
    # chip 1 idles [1000,1500] [4000,6000] [9000,11000]:
    #   [1000,1050] dispatch, [1050,1100] train_chain itself,
    #   [1100,1500] data_wait
    #   [4000,5500] data_wait, [5500,6000] dispatch
    #   [9000,9500] val_dispatch, [9500,11000] epoch_fetch
    kinds = found["by_kind"]
    assert kinds["data_wait"] == pytest.approx(1900, abs=600)
    assert kinds["dispatch"] == pytest.approx(1100, abs=600)
    assert kinds["boundary"] == pytest.approx(1500, abs=600)
    assert kinds["unattributed"] == pytest.approx(0, abs=600)
    assert sum(kinds.values()) == pytest.approx(4500, abs=1e-6)
    shares = [_reader(f"idle_{k}_pct")(ctx) for k in (
        "in_data_wait", "in_dispatch", "at_boundary", "unattributed")]
    assert sum(shares) == pytest.approx(_reader("device_idle_pct")(ctx))
    assert shares[0] == pytest.approx(19.0, abs=0.01 if shift_us < 1e9 else 6)
    # the longest gap and the spans that own it
    start, end, owners = found["gaps"][0]
    assert [start, end] in ([4000, 6000], [9000, 11000])
    assert set(owners) in ({"data_wait", "dispatch"},
                           {"val_dispatch", "epoch_fetch"})
    assert _reader("clock_residual_ms")(ctx) == pytest.approx(
        clock["residual_ns"] / 1e6)


def test_clock_check_reports_the_session_the_anchors_and_the_owners():
    from benchmark.tools import clock_check

    shift_us = 2e9
    ctx = dict(make_ctx(shift_us), cell="hand-made", chips=2,
               first_epoch_s=1.0, setup_s=2.0,
               window={"epoch_s": [1.0, 1.1, 1.2]})
    # the session started 300 ns before the profile clock's zero
    session = {"profile_start_time": int(shift_us * 1e3) - 300}
    line = clock_check.report(ctx, lambda name: _reader(name)(ctx), session)
    assert line["session"]["zero_by_fetch_ms"] == pytest.approx(3e-4, abs=1e-6)
    assert line["session"]["zero_by_dispatch_ms"] == pytest.approx(
        3.5e-4, abs=1e-6)
    assert line["session"]["zero_by_dispatch_start_ms"] == pytest.approx(
        -3e-4, abs=1e-6)
    assert line["anchors"]["residual_ms"] == pytest.approx(5e-5, abs=1e-6)
    assert line["chips_first_step_us"] == {"/device:TPU:0": 0.0,
                                           "/device:TPU:1": 0.5}
    assert line["idle"]["sum_pct"] == pytest.approx(
        line["idle"]["device_idle_pct"])
    assert list(line["idle"]["longest_gaps"][0]["owners_ms"]) in (
        ["data_wait", "dispatch"], ["epoch_fetch", "val_dispatch"])
    # chains [200,1050] and [1050,6500] less their data_wait and dispatch
    assert line["host"]["chain_self_ms"] == pytest.approx(25e-6, rel=1e-3)
    assert line["traced_epoch_spans_ms"]["dispatch"] == pytest.approx(
        [2, 1650e-6, 1000e-6], rel=1e-3)
    assert line["traced_epoch_index"] == 1


def test_time_outside_every_span_is_unattributed():
    ctx = make_ctx(0.0)
    ctx["spans"] = [s for s in ctx["spans"]
                    if s["name"] not in ("epoch", "validation",
                                         "val_data_wait", "val_dispatch")]
    kinds = span_clock.idle_by_span(ctx)["by_kind"]
    # [9000,9500] of chip 1's idle time is now under no span
    assert kinds["unattributed"] == pytest.approx(500, abs=1)
    assert kinds["boundary"] == pytest.approx(1500)


def test_span_readers_take_the_traced_epochs_median():
    ctx = make_ctx(5e6)
    assert _reader("data_wait_ms")(ctx) == pytest.approx(
        (200 + 4400) / 2 / 1e6, rel=1e-3)
    assert _reader("dispatch_ms")(ctx) == pytest.approx(
        (650 + 1000) / 2 / 1e6, rel=1e-3)
    assert _reader("loader_h2d_ms")(ctx) is None        # no such span
    # loader_batch starts before the traced interval opens
    assert _reader("loader_batch_ms")(ctx) is None


def test_a_program_without_the_spans_reads_nothing_and_does_not_raise():
    """The parent of the PR that brought the spans has ``train_chain`` only;
    the driver lays these readers over it."""
    ctx = make_ctx(0.0)
    ctx["spans"] = [s for s in ctx["spans"] if s["name"] == "train_chain"]
    for name in NEW:
        assert _reader(name)(ctx) is None, name
    untraced = {"spans": [], "traced": None, "record": None, "reduced": None}
    for name in NEW:
        assert _reader(name)(untraced) is None, name


def test_every_new_metric_is_listed_for_every_cell():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == cells


@pytest.mark.parametrize("name", [w["name"] for w in MANIFEST["workloads"]])
def test_span_readers_on_a_tiny_traced_run(name):
    """What ``rehearsal/tiny_cells.py --trace`` runs, with the readers' view
    kept: the span-only metrics read the program's own spans (the CPU's
    profile has no device plane, so the shares and the residual read
    nothing)."""
    import time

    import jax

    cell = Cell(MANIFEST, name)
    result = cell.family.run(cell, 2 ** 31 + 3, 0.5, True, time.time(),
                             jax.devices()[:cell.chips], None,
                             tiny=TINY[cell.config["family"]])
    ctx = result["ctx"]
    assert result["correct"] is True
    names = {s["name"] for s in ctx["spans"]}
    assert {"fit_setup", "epoch", "train_chain", "data_wait", "dispatch",
            "validation", "epoch_fetch", "loader_batch",
            "loader_h2d"} <= names
    for metric in ("data_wait_ms", "loader_batch_ms", "loader_h2d_ms",
                   "dispatch_ms", "trainer_init_s", "step_load_s"):
        value = cell.reader(metric)(ctx)
        assert value is not None and value > 0, metric
    chain = cell.reader("host_chain_ms")(ctx)
    assert (cell.reader("data_wait_ms")(ctx) + cell.reader("dispatch_ms")(ctx)
            <= chain * 2)       # medians of parts against the median whole
    # set-up: the two new spans lie inside first_epoch_s's interval
    assert (cell.reader("trainer_init_s")(ctx) + cell.reader("step_load_s")(ctx)
            <= ctx["first_epoch_s"])
    for metric in ("idle_in_dispatch_pct", "clock_residual_ms"):
        assert cell.reader(metric)(ctx) is None
