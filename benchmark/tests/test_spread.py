"""``tools/spread.py`` on recorded runs: the driver's spread leaves out the
run farthest from the median only where that narrows the range."""

import json
import statistics

import pytest

from benchmark.tools import spread

# nemotron3nano_train_s8192, six untraced runs on six seeds (chip runs, PR 36)
PR36 = [25.46401032993203, 25.677648513117603, 25.556597478519965,
        25.520106710168466, 25.62968355108408, 25.6066958245324]


def test_the_farthest_run_is_left_out_where_that_narrows_it():
    median = statistics.median(PR36)
    # 25.464 lies 0.118 under the median, 25.678 0.096 over: the first goes
    assert spread.range_drop1(PR36) == pytest.approx(
        (25.677648513117603 - 25.520106710168466) / median)
    assert spread.range_drop1(PR36) == pytest.approx(0.006158, abs=1e-6)
    assert spread.summary(PR36)["range"] == pytest.approx(0.008351, abs=1e-6)
    # the contract's quartile distance on the same runs (PERF.md: 0.53 %),
    # and without the farthest run, the five left: quartiles at 25.5384 and
    # 25.6537
    assert spread.iqr(PR36) == pytest.approx(0.005300, abs=1e-6)
    assert spread.iqr_drop1(PR36) == pytest.approx(
        (25.65366603210084 - 25.538352094344216) / median)
    assert spread.without_farthest(PR36) == sorted(PR36)[1:]


@pytest.mark.parametrize("values,want", [
    ([10.0, 10.0, 10.0, 10.0], 0.0),                # nothing to narrow
    ([9.0, 10.0, 10.0, 10.0, 11.0], 0.1),           # a tie: one end goes
    ([10.0, 10.1], 0.1 / 10.05),                    # two runs: the range
    ([10.0, 10.1, 10.2, 13.0], (10.2 - 10.0) / 10.15),  # one far-off run
    ([7.0, 10.0, 10.1, 10.2, 13.0], (13.0 - 10.0) / 10.1),  # two: one stays
])
def test_range_drop1(values, want):
    assert spread.range_drop1(values) == pytest.approx(want)


def test_it_reads_last_lines_from_logs_and_counts_what_is_not_correct(
        tmp_path, capsys):
    log = tmp_path / "runs.log"
    lines = ["benchmark: window 4 epochs", "{not json"]
    for i, v in enumerate(PR36):
        lines.append(json.dumps({
            "correct": i != 2, "attempted": 32, "failed": 0,
            "metrics": {"train_mfu": {"value": v, "unit": "%"},
                        "setup_s": {"value": 50.0 + i, "unit": "s"}},
            "device": {}}))
    log.write_text("\n".join(lines) + "\n")
    assert spread.main([str(log)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["runs"] == 6 and out["not_correct"] == 1
    assert out["train_mfu"]["range_drop1"] == pytest.approx(0.006158, abs=1e-6)
    assert out["train_mfu"]["values"] == PR36
    assert out["setup_s"]["median"] == 52.5
    assert spread.main([str(log), "--metric", "setup_s"]) == 0
    assert "train_mfu" not in json.loads(capsys.readouterr().out)
