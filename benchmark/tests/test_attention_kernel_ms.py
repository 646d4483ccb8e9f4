"""``attention_kernel_ms`` on a small recorded trace: the flash kernels' time a
step where they ran, nothing where they did not."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.metrics.attention_kernel_ms import read

HERE = os.path.dirname(__file__)


def _reduced(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return tr.reduce(json.load(f))


def test_sums_the_three_kernels_per_step():
    red = _reduced("recorded_flash_trace.json")
    fam = dict(red["top_families"])
    assert fam["flash_fwd"] == 2 * 900 and fam["flash_dq"] == 2 * 1190
    assert fam["flash_dkv"] == 2 * 1410
    # a step: forward 460 + 440, dQ 600 + 590, dK/dV 700 + 710 = 3500 ns
    ctx = {"reduced": red, "traced": {"steps": 2}}
    assert read(ctx) == pytest.approx(3500 / 1e6)


def test_nothing_where_no_kernel_ran():
    # the benchmark's first recorded trace: fusions, copies, all-reduces
    ctx = {"reduced": _reduced("recorded_trace.json"), "traced": {"steps": 2}}
    assert read(ctx) is None
    assert read({"reduced": None, "traced": None}) is None
    assert read(dict(ctx, traced={"steps": 0})) is None
