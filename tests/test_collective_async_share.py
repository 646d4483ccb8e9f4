"""``collective_async_share`` on small records: 0.0 where every reduce is a
synchronous operation (the recorded two-chip trace's ``all-reduce.N``, and the
compiler's single-operand ``psum.N``), 1.0 where every reduce is an
asynchronous fusion pair, the share of operations between, and nothing on one
chip, without a reduce or without a trace."""

import copy
import json
import os

import pytest

from benchmark.metrics.collective_async_share import read

# the small records the benchmark's own tests read
DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                    "benchmark", "tests", "data")


def _record(name="recorded_trace.json"):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _renamed(record, rename):
    """``record`` with every operation's name passed through ``rename``, which
    may give several names (a pair's halves) for one."""
    record = copy.deepcopy(record)
    for device, events in record["devices"].items():
        record["devices"][device] = [
            [new, start, dur] for name, start, dur in events
            for new in rename(name)]
    return record


def _pair(name):
    if not name.startswith("all-reduce"):
        return [name]
    n = name.rpartition(".")[2]
    return [f"async-collective-start.{n}", f"async-collective-done.{n}"]


@pytest.mark.parametrize("rename", [
    lambda name: [name],                                     # all-reduce.N
    lambda name: [name.replace("all-reduce", "psum")],       # psum.N
], ids=["all-reduce", "psum"])
def test_synchronous_reduces_read_zero(rename):
    assert read({"record": _renamed(_record(), rename), "chips": 2}) == 0.0


def test_asynchronous_pairs_read_one_and_count_once():
    fused = _renamed(_record(), _pair)
    assert read({"record": fused, "chips": 2}) == 1.0
    # the first pair has no numeric suffix in a compiled step
    bare = _renamed(fused, lambda name: [name.replace(".1", "")])
    assert read({"record": bare, "chips": 2}) == 1.0


def test_a_mix_counts_operations_over_every_chip():
    # chip 0 runs all-reduce.1 and all-reduce.2, chip 1 all-reduce.1: fusing
    # all-reduce.1 alone fuses two of the three
    mixed = _renamed(_record(), lambda name: (
        _pair(name) if name == "all-reduce.1" else [name]))
    assert read({"record": mixed, "chips": 2}) == pytest.approx(2 / 3)


@pytest.mark.parametrize("ctx", [
    {"record": _record(), "chips": 1},                          # one chip
    {"record": _record("recorded_flash_trace.json"), "chips": 4},   # no reduce
    {"record": None, "chips": 4, "reduced": None, "traced": None},
    {"chips": 4, "reduced": None, "traced": {"steps": 2}},    # untraced
    {"record": {"devices": {}}, "chips": 4},
], ids=["one_chip", "no_reduce", "no_record", "untraced", "no_devices"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
