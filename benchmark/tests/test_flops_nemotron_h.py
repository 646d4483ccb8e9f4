"""Family ``lm_hybrid_ssm_moe_train``'s required-FLOP function against hand
arithmetic at the cell's sizes, and its readers of the layers' counters."""

import pytest

from benchmark.families import lm_hybrid_ssm_moe_train as family
from benchmark.harness.manifest import ROOT, Cell, load_json, load_manifest

CONFIG = load_json(ROOT + "/benchmark/configs/nemotron-3-nano-30b-a3b.json")
CELL = "nemotron3nano_train_s8192"


def test_matmul_parameters_a_token_meets():
    per = family.layer_params(CONFIG)
    # in_proj 2688 x (4096 + 6144 + 64) and out_proj 4096 x 2688
    assert per["M"] == 2688 * 10_304 + 4096 * 2688 == 38_707_200
    assert per["*"] == 2 * 2688 * 4096 + 2 * 2688 * 256 == 23_396_352
    # the shared expert, the router at 128, and 6 choices x 8 of 128 experts
    # held = 0.375 routed experts a token, expected
    assert per["E"] == (2 * 2688 * 3712 + 2688 * 128
                        + 0.375 * 2 * 2688 * 1856) == 24_041_472
    assert family.matmul_params(CONFIG) == (
        4 * 38_707_200 + 23_396_352 + 4 * 24_041_472 + 2688 * 16_384
    ) == 318_431_232


def test_required_flops_a_token_at_8192():
    # the scan's own products, forward, a token and Mamba layer, Q = 128:
    # scores 2 Q N G, masked product 2 Q P H, state built and read 4 N P H
    scan = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 128 * 64 * 64
    assert scan == family.scan_flops_per_token(CONFIG) == 3_407_872
    dense = 6 * 318_431_232
    attention = 12 * 4096 * 8192          # counted full, one layer
    assert (dense, attention, 4 * 3 * scan) == (
        1_910_587_392, 402_653_184, 40_894_464)
    want = family.required_flops_per_item(CONFIG, 8192)
    assert want == dense + attention + 4 * 3 * scan == 2_354_135_040
    # forward 784.7 MFLOP a token; a step of 16,384 tokens: 38.6 TFLOP; the
    # Mamba layers are 41 % of it
    assert want / 3 / 1e6 == pytest.approx(784.7, abs=0.05)
    assert want * 16_384 / 1e12 == pytest.approx(38.6, abs=0.05)
    assert 4 * (6 * 38_707_200 + 3 * scan) / want == pytest.approx(0.41,
                                                                   abs=0.005)


def test_the_cell_is_found_by_name_with_its_counters():
    cell = Cell(load_manifest(), CELL)
    assert cell.family is family and cell.chips == 1
    assert cell.traffic["seq_len"] * cell.traffic["batch_per_chip"] == 16_384
    assert cell.traffic["learning_rate"] == 1e-5
    names = [m["name"] for m in cell.per_layer]
    assert {"ssm_chunk_carry", "moe_load_max_over_mean",
            "moe_assignments_per_token", "attention_kernel_ms"} <= set(names)
    assert "collective_ms" not in names and "keys_per_query" not in names
    rows = [{"loss": 1.0}] * 2 + [{"ssm_chunk_carry": 0.5}, {
        "ssm_chunk_carry": 0.6}]
    assert cell.reader("ssm_chunk_carry")({"rows": rows}) == pytest.approx(
        0.55)
    # a program without the counter (the parent): nothing, and no error
    assert cell.reader("ssm_chunk_carry")({"rows": [{"loss": 1.0}] * 4}) is None
    assert set(cell.limits) >= {"ssm_chunk_carry_least", "grad_direction_gap",
                                "expert_choice_margin", "moe_dropped"}
