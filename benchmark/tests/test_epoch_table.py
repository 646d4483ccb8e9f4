"""``tools/epoch_table.py`` on made-up logs whose answer is known: seeds that
differ by a constant are all between-seed variance and no window length helps;
seeds that differ epoch by epoch are within-run and a longer window does."""

import json

import pytest

from benchmark.tools import epoch_table


def write_log(path, epoch_s, counters, mfu, warm=2):
    rows = [{"moe_assignments_per_token": c, "moe_rows_run_share": r}
            for c, r in counters]
    assert len(rows) == warm + len(epoch_s)
    path.write_text(
        "setup: ...\ncounters by epoch " + str(rows) + "\n"
        f"benchmark: window {len(epoch_s)} epochs, {8 * len(epoch_s)} steps "
        f"in {sum(epoch_s):.3f} s; 1.0 items/s/chip; epochs {epoch_s}\n"
        + json.dumps({"correct": True, "metrics": {
            "train_mfu": {"value": mfu, "unit": "%"}}}) + "\n")
    return str(path)


def logs(tmp_path, lengths):
    """One log a seed from its epochs' lengths; train_mfu is 100 / the mean
    epoch's seconds, the counter climbs by 0.1 an epoch from the seed's own
    start and the dispatch leaves its floor where it passes 0.75."""
    out = []
    for i, epoch_s in enumerate(lengths):
        a = [0.3 + 0.05 * i + 0.1 * e for e in range(len(epoch_s) + 2)]
        counters = [(x, 0.125 if x < 0.75 else 0.25) for x in a]
        out.append(write_log(tmp_path / f"seed{i}.log", epoch_s, counters,
                             100.0 * len(epoch_s) / sum(epoch_s)))
    return out


def run(capsys, paths, *args):
    assert epoch_table.main(list(paths) + list(args)) == 0
    return json.loads(capsys.readouterr().out)


def test_a_constant_offset_is_between_seeds_and_no_length_helps(tmp_path,
                                                                capsys):
    lengths = [[5.0 + 0.01 * i] * 9 for i in range(8)]
    out = run(capsys, logs(tmp_path, lengths), "--opens", "2,5",
              "--lengths", "3,6")
    assert out["variance"]["between_share"] == pytest.approx(1.0)
    assert out["variance"]["seeds"] == 8 and out["variance"]["epochs"] == 9
    spreads = {(w["opens"], w["length"]): w["range_drop1"]
               for w in out["windows"]}
    assert set(spreads) == {(2, 3), (2, 6), (5, 3), (5, 6)}
    assert max(spreads.values()) == pytest.approx(min(spreads.values()))
    # eight seeds: every six of them, too
    first = out["windows"][0]
    assert 0 < first["sixes_mean"] <= first["sixes_worst"]


def test_epoch_to_epoch_swings_are_within_a_run_and_a_longer_window_helps(
        tmp_path, capsys):
    # every seed has the same lengths, in another order
    base = [5.0, 5.1, 4.9, 5.05, 4.95, 5.2, 4.8, 5.0, 5.0]
    lengths = [base[i:] + base[:i] for i in range(6)]
    out = run(capsys, logs(tmp_path, lengths), "--opens", "2",
              "--lengths", "3,9")
    assert out["variance"]["between_share"] == pytest.approx(0.0, abs=1e-9)
    three, nine = out["windows"]
    assert nine["range_drop1"] == pytest.approx(0.0, abs=1e-12)
    assert three["range_drop1"] > 0.01
    assert "sixes_worst" not in three       # six seeds: one set


def test_the_counters_range_by_epoch_and_who_left_the_floor(tmp_path, capsys):
    lengths = [[5.0] * 6 for _ in range(3)]
    out = run(capsys, logs(tmp_path, lengths), "--at", "2,4,20")
    by = out["counter_by_seed"]
    assert set(by) == {"2", "4"}            # no run reaches epoch 20
    assert by["2"]["least"] == pytest.approx(0.5)
    assert by["2"]["range"] == pytest.approx(0.1)
    # 0.3 + 0.05 i + 0.1 e passes 0.75 at epoch 5, 4 and 4
    left = out["left_the_floor"]
    assert [left[f"seed{i}.log"]["first_epoch_over"] for i in range(3)] == [
        5, 4, 4]
    assert left["seed0.log"]["floor"] == 0.125


def test_a_window_a_run_does_not_reach_is_left_out(tmp_path, capsys):
    paths = logs(tmp_path, [[5.0] * 4, [5.0] * 9])
    out = run(capsys, paths, "--opens", "2,5", "--lengths", "3")
    assert [(w["opens"], w["runs"]) for w in out["windows"]] == [(2, 2)]
