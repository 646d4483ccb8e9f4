"""``attn_bwd_one_pass_share`` on small recorded traces: 0.0 where every
streaming backward ran as the dQ and the dK/dV kernel (the recorded flash
trace, from before PR 45), 1.0 where ``flash_dkv`` is the whole backward, the
share between where some calls keep the pair, and nothing where no
``flash_dkv`` ran or nothing was traced."""

import copy
import json
import os

import pytest

from benchmark.metrics.attn_bwd_one_pass_share import read

HERE = os.path.dirname(__file__)


def _record(name="recorded_flash_trace.json"):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _without(record, drop):
    """``record`` without the operations ``drop(name)`` is true of."""
    record = copy.deepcopy(record)
    for device, events in record["devices"].items():
        record["devices"][device] = [e for e in events if not drop(e[0])]
    return record


def test_two_kernels_a_backward_read_zero():
    assert read({"record": _record()}) == 0.0


def test_one_kernel_a_backward_reads_one():
    fused = _without(_record(), lambda name: name.startswith("flash_dq"))
    assert read({"record": fused}) == 1.0


def test_a_layer_that_keeps_the_pair_counts_by_calls():
    # layer 2 runs one pass, layer 1 still the pair: half the calls
    mixed = _without(_record(), lambda name: name == "flash_dq.2")
    assert read({"record": mixed}) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["recorded_trace.json",
                                  "recorded_short_flash_trace.json",
                                  "recorded_indexed_trace.json"])
def test_nothing_where_no_streaming_backward_ran(name):
    assert read({"record": _record(name)}) is None


@pytest.mark.parametrize("ctx", [
    {"record": None, "reduced": None, "traced": None},
    {"reduced": None, "traced": {"steps": 2}},      # an untraced run
    {"record": {"devices": {}}, "traced": {"steps": 2}},
])
def test_nothing_without_a_trace(ctx):
    assert read(ctx) is None
