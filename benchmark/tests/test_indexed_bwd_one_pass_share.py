"""``indexed_bwd_one_pass_share`` on small recorded traces: 0.0 where every
chosen-key backward ran as the dQ and the dK/dV kernel (the recorded indexed
trace, from before PR 46), 1.0 where ``indexed_dkv`` is the whole backward,
the share between where some calls keep the pair, and nothing where no
``indexed_dkv`` ran or nothing was traced."""

import copy
import json
import os

import pytest

from benchmark.metrics.indexed_bwd_one_pass_share import read

HERE = os.path.dirname(__file__)


def _record(name="recorded_indexed_trace.json"):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _without(record, drop):
    """``record`` without the operations ``drop(event)`` is true of."""
    record = copy.deepcopy(record)
    for device, events in record["devices"].items():
        record["devices"][device] = [e for e in events if not drop(e)]
    return record


def test_two_kernels_a_backward_read_zero():
    assert read({"record": _record()}) == 0.0


def test_one_kernel_a_backward_reads_one():
    fused = _without(_record(), lambda e: e[0].startswith("indexed_dq"))
    assert read({"record": fused}) == 1.0


def test_a_step_that_keeps_the_pair_counts_by_calls():
    # the second step runs one pass, the first still the pair: half the calls
    mixed = _without(_record(), lambda e: e[0].startswith("indexed_dq")
                     and e[1] > 20000)
    assert read({"record": mixed}) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["recorded_trace.json",
                                  "recorded_flash_trace.json",
                                  "recorded_short_flash_trace.json"])
def test_nothing_where_no_chosen_key_backward_ran(name):
    assert read({"record": _record(name)}) is None


def test_nothing_where_only_the_forward_kernels_ran():
    forward = _without(_record(), lambda e: e[0].startswith(
        ("indexed_dq", "indexed_dkv")))
    assert read({"record": forward}) is None


@pytest.mark.parametrize("ctx", [
    {"record": None, "reduced": None, "traced": None},
    {"reduced": None, "traced": {"steps": 2}},      # an untraced run
    {"record": {"devices": {}}, "traced": {"steps": 2}},
])
def test_nothing_without_a_trace(ctx):
    assert read(ctx) is None
