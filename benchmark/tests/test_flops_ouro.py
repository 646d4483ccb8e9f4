"""Family ``lm_looped_train``'s required-FLOP function, its parameter counts
and its attention kernels' operations function against a count by hand at the
published sizes, the cell's files found by name, the reference's leaves counted
against the family's, and the two new counters' readers on made-up rows: each
reads its own counter, and nothing (None, no error) from a program that has
none."""

import math

import pytest

from benchmark.families import lm_looped_train as family
from benchmark.harness.manifest import ROOT, Cell, load_json, load_manifest
from benchmark.tests import tiny

CONFIG = load_json(ROOT + "/benchmark/configs/ouro-2.6b.json")
CELL = "ouro_train_s8192"
# the benchmark's own table of tiny sizes (tests/tiny.py and tests/conftest.py
# are not this PR's to edit; rehearsal/tiny_ouro.py enters the same)
tiny.TINY.setdefault("lm_looped_train", family.TINY)


def test_a_layers_matmul_parameters_and_the_uses_a_token_makes():
    # W_q, W_k, W_v, W_o 2048 x 2048 each; gate, up and down 2048 x 5632
    assert family.layer_params(CONFIG) == (4 * 2048 ** 2
                                           + 3 * 2048 * 5632) == 51_380_224
    assert family.applications(CONFIG) == 6 * 4 == 24
    # four passes: 24 layer uses, the head four times, the gate four times
    assert family.matmul_params(CONFIG) == (
        24 * 51_380_224 + 4 * 2048 * 49_152 + 4 * 2048) == 1_635_786_752


def test_the_held_parameters_are_the_issues_count():
    """What the chip holds, every weight ONCE whatever its uses: the
    reference's leaves, which are the program's one for one."""
    spec = family.reference_spec(CONFIG)
    count = lambda keep: sum(math.prod(shape)             # noqa: E731
                             for name, (shape, _) in spec.items()
                             if keep(name))
    # a layer's matrices and its four norms' gains
    assert count(lambda n: n.startswith("l3.")) == 51_380_224 + 4 * 2048 == (
        51_388_416)
    assert count(lambda n: n in ("wte", "head.w")) == 201_326_592
    assert count(lambda n: n == "lnf.g") == 2_048
    assert count(lambda n: n.startswith("gate.")) == 2_049
    assert count(lambda n: True) == family.held_params(CONFIG) == (
        6 * 51_388_416 + 201_326_592 + 2_048 + 2_049) == 509_661_185
    assert set(family.leaf_map(CONFIG).values()) == set(spec)
    # six blocks, not twenty-four: the passes share them
    assert sum(1 for n in spec if n.endswith(".wq")) == 6


def test_required_flops_a_token_at_8192():
    dense = 6 * 1_635_786_752
    # 24 block applications' two S-long products, counted full: 12 S hidden
    attention = 12 * 24 * 2048 * 8192
    assert (dense, attention) == (9_814_720_512, 4_831_838_208)
    want = family.required_flops_per_item(CONFIG, 8192)
    assert want == dense + attention == 14_646_558_720
    # a step of 8,192 tokens: 119.98 TFLOP; the blocks' projections and MLPs
    # 50.5 % of it, attention's S-long products 33.0 %, the four heads 16.5 %
    assert want * 8_192 / 1e12 == pytest.approx(119.98, abs=0.01)
    assert 6 * 24 * 51_380_224 / want == pytest.approx(0.505, abs=0.001)
    assert attention / want == pytest.approx(0.330, abs=0.001)
    assert 6 * 4 * 2048 * 49_152 / want == pytest.approx(0.165, abs=0.001)
    # without a row length: the declared context
    assert family.required_flops_per_item(CONFIG) == (
        dense + 12 * 24 * 2048 * 65_536)


def test_the_attention_kernels_own_operations_a_step():
    traffic = load_json(ROOT + "/benchmark/traffic/lm_continue_s8192_b1.json")
    pairs = 1 * 16 * 8192 * 8193 // 2           # causal, the diagonal in
    assert pairs == 536_936_448
    # the seven products of forward and backward, each 2 x 128 a pair
    want = family.attention_kernel_flops_per_step(CONFIG, traffic)
    assert want == 24 * pairs * 2 * 7 * 128 == 23_092_562_755_584
    # at the chip's 197 TFLOP/s that is 117.2 ms a step: the kernels' time
    # cannot read under it
    assert want / 197e12 * 1e3 == pytest.approx(117.22, abs=0.01)


def test_the_cell_is_found_by_name_with_its_readers():
    cell = Cell(load_manifest(), CELL)
    assert cell.family is family and cell.chips == 1
    assert (cell.traffic["seq_len"], cell.traffic["batch_per_chip"]) == (8192,
                                                                         1)
    assert cell.traffic["moment_dtype"] == "float32"
    assert cell.traffic["learning_rate"] == 1e-5
    assert cell.config["num_hidden_layers"] == 6
    assert cell.config["total_ut_steps"] == 4
    assert cell.config["vocab_size"] == 49_152
    assert cell.config["reduced"] == ["num_hidden_layers", "layer_types"]
    names = {m["name"] for m in cell.per_layer}
    assert {"exit_expected_passes", "exit_entropy", "attention_kernel_ms",
            "attn_bwd_one_pass_share", "mla_attention_roofline_pct",
            "scope_attention_ms", "scope_mlp_ms", "scope_head_ms",
            "scope_optimizer_ms", "step_recompute_ms", "peak_hbm_gib",
            "device_idle_pct"} <= names
    assert not {"collective_ms", "keys_per_query", "moe_assignments_per_token",
                "mtp_module_ms", "hyper_conn_ms", "scope_experts_ms"} & names
    assert set(cell.limits) >= {"total_loss_gap", "exit_share_gap",
                                "grad_direction_gap", "delta_norm_gap"}
    # between this cell's own readings on the chip, with room on both sides
    # (the sound runs' largest, the least planted fault's), and no band that
    # nothing can leave
    assert 2 * 9.3e-4 < cell.limits["delta_norm_gap"] <= 0.00502 / 2
    assert not [k for k in cell.limits if k.startswith("exit_expected")]
    # the new counters are this cell's alone
    for name in ("exit_expected_passes", "exit_entropy"):
        assert next(m for m in load_manifest()["per_layer"]
                    if m["name"] == name)["workloads"] == [CELL]


@pytest.mark.parametrize("metric", ["exit_expected_passes", "exit_entropy"])
def test_a_new_reader_reads_its_counter_and_nothing_without_it(metric):
    read = Cell(load_manifest(), CELL).reader(metric)
    rows = [{"loss": 1.0}] * 2 + [
        {"exit_expected_passes": 1.8, "exit_entropy": 1.10},
        {"exit_expected_passes": 2.0, "exit_entropy": 1.20}]
    want = {"exit_expected_passes": 1.9, "exit_entropy": 1.15}[metric]
    assert read({"rows": rows, "warm_epochs": 2}) == pytest.approx(want)
    # a parent without the counter: nothing, and no error
    assert read({"rows": [{"loss": 1.0}] * 4, "warm_epochs": 2}) is None
