"""``metrics/scope_time.py`` on a small checked-in pair of a recorded trace and
the ``step_scopes`` table of its step (``data/scope_time_pair.json``, whose
comment says what a step holds): a ``while`` is counted once, operations
outside the step's module are left out, the sum closes, and every reader of
the manifest's scope metrics reads through it."""

import copy
import json
import os

import pytest

from benchmark.harness import trace_reduce
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.metrics import scope_time as st

HERE = os.path.dirname(__file__)
NS = 1e-6       # the pair's times are ns, the metrics ms
CELL = "nemotron3nano_train_s8192"      # lists every scope the pair holds


@pytest.fixture()
def pair():
    with open(os.path.join(HERE, "data", "scope_time_pair.json")) as f:
        return json.load(f)


def _ctx(pair, cell=CELL, steps=2):
    return {"cell": cell, "record": pair["record"], "traced": {"steps": steps},
            "spans": [{"name": "fit_setup", "args": {}},
                      {"name": "step_scopes", "args": pair["table"]}]}


def test_a_while_is_counted_once_and_the_sum_closes(pair):
    times = st.join(pair["record"], pair["table"],
                    set(st.scope_map()["layers"]))
    # the loop's 500 ns: 200 of its own, 2 x 100 of its body's fusion, and
    # 2 x 50 of a custom call without a name stack, which is the loop's
    assert times["scopes"]["ssm_scan"]["fwd"] == pytest.approx(500 * NS)
    assert times["steps"] == 2      # the module's executions
    by_scope = {s: sum(by.values()) for s, by in times["scopes"].items()}
    assert sum(by_scope.values()) == pytest.approx(950 * NS)
    assert times["total_ms"] == pytest.approx(950 * NS)
    # and on the union of the same operations' intervals: nothing twice
    assert times["busy_ms"] == pytest.approx(950 * NS)
    assert by_scope == pytest.approx({
        "attention": 100 * NS, "ssm_scan": 500 * NS, "unnamed": 50 * NS,
        "mlp": 200 * NS, "other": 100 * NS})
    assert times["scopes"]["mlp"] == {"fwd": 0.0, "bwd": 0.0,
                                      "remat": pytest.approx(200 * NS)}


def test_operations_outside_the_steps_module_are_left_out(pair):
    # the validation batch runs a fusion.1 too, 150 ns, between the steps
    names = [n for n, _, _ in pair["record"]["devices"]["/device:TPU:0"]]
    assert names.count("fusion.1") == 3
    assert st.read_metric(_ctx(pair), "scope_attention_ms") == \
        pytest.approx(100 * NS)
    # without the module's executions nothing is anybody's
    record = dict(pair["record"], modules={})
    assert st.join(record, pair["table"], {"attention"}) == {}
    assert st.read_metric(dict(_ctx(pair), record=record),
                          "scope_attention_ms") is None


def test_the_metrics_of_a_cell_add_up_to_the_step(pair):
    ctx = _ctx(pair)
    listed = st.listed(CELL)
    assert "scope_ssm_scan_ms" in listed and "scope_mlp_ms" not in listed
    values = {m: st.read_metric(ctx, m)
              for m in listed + ["scope_other_ms", "scope_unnamed_ms"]}
    assert values["scope_unnamed_ms"] == pytest.approx(50 * NS)
    # mlp has no line in this cell: its 200 ns are other's, beside the norm
    assert values["scope_other_ms"] == pytest.approx(300 * NS)
    assert sum(v for v in values.values() if v is not None) == \
        pytest.approx(950 * NS)
    assert st.read_metric(ctx, "step_recompute_ms") == pytest.approx(200 * NS)
    # where the dense MLP has a line, other loses it
    dense = _ctx(pair, "gpt2m_train_s1024")
    assert st.read_metric(dense, "scope_mlp_ms") == pytest.approx(200 * NS)
    assert "scope_ssm_scan_ms" not in st.listed("gpt2m_train_s1024")
    assert st.read_metric(dense, "scope_other_ms") == pytest.approx(
        (950 - 50 - 200 - 100) * NS)


def test_a_mean_over_the_chips_and_per_optimizer_step(pair):
    record = copy.deepcopy(pair["record"])
    for part in ("devices", "modules"):
        record[part]["/device:TPU:1"] = record[part]["/device:TPU:0"]
    ctx = dict(_ctx(pair), record=record)
    assert st.read_metric(ctx, "scope_ssm_scan_ms") == pytest.approx(500 * NS)
    # four optimizer steps in the two executions (a chained step)
    # (a ctx keeps its join: a fresh one for another count)
    chained = dict(_ctx(pair, steps=4), record=record)
    assert st.read_metric(chained, "scope_ssm_scan_ms") == \
        pytest.approx(250 * NS)


def test_nothing_to_read_without_the_table_or_the_scope(pair):
    ctx = _ctx(pair)
    parent = dict(ctx, spans=[s for s in ctx["spans"]
                              if s["name"] != "step_scopes"])
    for metric in ("scope_attention_ms", "scope_other_ms", "scope_unnamed_ms",
                   "step_recompute_ms"):
        assert st.read_metric(parent, metric) is None
        assert st.read_metric(dict(ctx, record=None), metric) is None
    assert st.read_metric(ctx, "scope_router_ms") is None     # None, not 0
    with pytest.raises(KeyError):
        st.read_metric(ctx, "scope_nonsense_ms")


def _scope_entries():
    return [m for m in load_manifest()["per_layer"]
            if m["name"].startswith("scope_")
            or m["name"] == "step_recompute_ms"]


@pytest.mark.parametrize("entry", _scope_entries(), ids=lambda m: m["name"])
def test_every_listed_scope_metric_has_its_reader(entry, pair):
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"]) == ("train step, device", "device_trace",
                                 "train_mfu", "ms", "lower")
    assert entry["workloads"]
    cell = Cell(load_manifest(), entry["workloads"][0])
    assert entry in cell.per_layer
    read = cell.reader(entry["name"])
    assert read(_ctx(pair, cell.name)) == st.read_metric(
        _ctx(pair, cell.name), entry["name"])
    assert read({"cell": cell.name, "spans": [], "record": None,
                 "traced": None}) is None


def test_the_scope_map_names_each_scope_once():
    data = st.scope_map()
    scopes = [s for group in data["metrics"].values() for s in group]
    assert len(scopes) == len(set(scopes))
    assert not set(scopes) & set(data["unmetered"])
    named = set(data["metrics"])
    assert named | {"scope_other_ms", "scope_unnamed_ms",
                    "step_recompute_ms"} == {m["name"]
                                             for m in _scope_entries()}


def test_the_read_out_of_a_trace_dir(pair, tmp_path, monkeypatch, capsys):
    from ddw_tpu.obs.trace import Tracer, chrome_trace

    tracer = Tracer(capacity=16, process="train")
    tracer.record_span("step_scopes", "train", 0.0, 1.5, tid="train",
                       args=pair["table"])
    (tmp_path / "train_spans.trace.json").write_text(
        json.dumps(chrome_trace(tracer.drain())))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "load_xplane",
                        lambda path: pair["record"])
    assert st.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("jit__step: 2 executions, 0.001 ms")
    rows = {line.split()[0]: line.split()[1:] for line in out[2:]}
    assert list(rows) == ["ssm_scan", "mlp", "attention", "other", "unnamed"]
    assert rows["mlp"][:3] == ["0.000", "0.000", "0.000"]   # 200 ns in ms
    assert float(rows["ssm_scan"][-1]) == pytest.approx(52.63, abs=0.01)
    (tmp_path / "train_spans.trace.json").write_text(
        json.dumps(chrome_trace([])))
    with pytest.raises(SystemExit, match="no step_scopes span"):
        st.main([str(tmp_path)])
