"""``attention_short_kernel_ms`` on a small recorded trace: the one-block flash
kernels' time a step where they ran, nothing where they did not — the
streaming kernels' trace among the latter, as theirs reads nothing here."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.metrics import attention_kernel_ms
from benchmark.metrics.attention_short_kernel_ms import read

HERE = os.path.dirname(__file__)


def _reduced(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return tr.reduce(json.load(f))


def test_sums_the_two_kernels_per_step():
    red = _reduced("recorded_short_flash_trace.json")
    fam = dict(red["top_families"])
    assert fam["flash_short_fwd"] == 2 * 1020
    assert fam["flash_short_bwd"] == 2 * 2280
    # a step: forward 520 + 500, backward 1150 + 1130 = 3300 ns
    ctx = {"reduced": red, "traced": {"steps": 2}}
    assert read(ctx) == pytest.approx(3300 / 1e6)
    # and the streaming kernels' reader finds none of its three there
    assert attention_kernel_ms.read(ctx) is None


@pytest.mark.parametrize("name", ["recorded_trace.json",
                                  "recorded_flash_trace.json"])
def test_nothing_where_no_short_kernel_ran(name):
    ctx = {"reduced": _reduced(name), "traced": {"steps": 2}}
    assert read(ctx) is None
    assert read({"reduced": None, "traced": None}) is None
    assert read(dict(ctx, traced={"steps": 0})) is None
