"""``collective_wait_ms`` on small records: the length of the synchronous
reduces (``all-reduce.N``, and the single-operand ``psum.N`` that
``collective_ms`` never counted), the length of the ``done`` halves alone
where the reduces are asynchronous fusion pairs, the sum in a mix, a step and
the worst chip; and nothing on one chip, without a reduce or without a trace."""

import copy
import json
import os

import pytest

from benchmark.metrics.collective_wait_ms import read

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


# the recorded two-chip trace: chip 0 runs all-reduce.1 (2,000 ns) and
# all-reduce.2 (1,000 ns), chip 1 all-reduce.1 (500 ns)
TRACED = {"steps": 2}


def _halves(name):
    """A fused reduce: the ``start`` half takes the operation's place and
    length (it must not count), the ``done`` half is a tenth of it."""
    n = name.rpartition(".")[2]
    return [f"async-collective-start.{n}", f"async-collective-done.{n}"]


def _fused(record, which=lambda name: name.startswith("all-reduce")):
    record = _renamed(record, lambda name: (_halves(name) if which(name)
                                            else [name]))
    for events in record["devices"].values():
        for event in events:
            if event[0].startswith("async-collective-done"):
                event[2] //= 10
    return record


@pytest.mark.parametrize("record, ms", [
    (_record(), 3000 / 2 / 1e6),
    (_renamed(_record(), lambda n: [n.replace("all-reduce", "psum")]),
     3000 / 2 / 1e6),
    (_fused(_record()), 300 / 2 / 1e6),
    (_fused(_record(), lambda name: name == "all-reduce.1"),
     (200 + 1000) / 2 / 1e6),
], ids=["all-reduce", "psum", "fused", "mix"])
def test_waits_a_step_on_the_worst_chip(record, ms):
    assert read({"record": record, "chips": 2,
                 "traced": TRACED}) == pytest.approx(ms)


@pytest.mark.parametrize("ctx", [
    {"record": _record(), "chips": 1, "traced": TRACED},
    {"record": _record("recorded_flash_trace.json"), "chips": 4,
     "traced": TRACED},
    {"record": None, "chips": 4, "reduced": None, "traced": None},
    {"record": _record(), "chips": 2, "traced": {"steps": 0}},
    {"record": {"devices": {}}, "chips": 4, "traced": TRACED},
], ids=["one_chip", "no_reduce", "untraced", "no_steps", "no_devices"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
