"""``indexed_attention_kernel_ms`` on a small recorded trace: the four
chosen-key attention kernels' time a step where they ran — the short target
pass included, which the ten longest families leave out — and nothing where
they did not, the flash kernels' traces among the latter."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.metrics import attention_kernel_ms, attention_short_kernel_ms
from benchmark.metrics.indexed_attention_kernel_ms import read

HERE = os.path.dirname(__file__)


def _ctx(name, steps=2):
    with open(os.path.join(HERE, "data", name)) as f:
        record = json.load(f)
    return {"record": record, "reduced": tr.reduce(record),
            "traced": {"steps": steps}}


def test_sums_the_four_kernels_per_step():
    ctx = _ctx("recorded_indexed_trace.json")
    assert "indexed_target" not in dict(ctx["reduced"]["top_families"])
    # a step: forward 1200 / 1180, target 60 + 70, dQ 1500 / 1520, dK/dV 1700
    assert read(ctx) == pytest.approx((1190 + 130 + 1510 + 1700) / 1e6)
    # and the flash kernels' readers find none of theirs there
    assert attention_kernel_ms.read(ctx) is None
    assert attention_short_kernel_ms.read(ctx) is None


@pytest.mark.parametrize("name", ["recorded_trace.json",
                                  "recorded_flash_trace.json",
                                  "recorded_short_flash_trace.json"])
def test_nothing_where_no_indexed_kernel_ran(name):
    assert read(_ctx(name)) is None
    assert read(_ctx(name, steps=0)) is None


@pytest.mark.parametrize("ctx", [
    {"record": None, "reduced": None, "traced": None},
    {"reduced": None, "traced": {"steps": 2}},      # an untraced run
    {"record": {"devices": {}}, "traced": {"steps": 2}},
])
def test_nothing_without_a_trace(ctx):
    assert read(ctx) is None
