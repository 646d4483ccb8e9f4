"""Family ``lm_sparse_moe_train``'s required-FLOP function against hand
arithmetic at the cell's sizes, and its readers of the layers' counters."""

import pytest

from benchmark.families import lm_sparse_moe_train as family
from benchmark.harness.manifest import ROOT, Cell, load_json, load_manifest

CONFIG = load_json(ROOT + "/benchmark/configs/keye-vl-2.0-30b-a3b.json")


def test_keys_a_query_chooses():
    # queries 0..2047 see t + 1 keys, the other 6,144 choose 2,048
    assert family.keys_per_query(8192, 2048) == (
        2048 * 2049 / 2 + 6144 * 2048) / 8192 == 1792.125
    assert family.keys_per_query(1024, 2048) == 512.5     # all causal
    assert family.keys_per_query(32, 8) == 7.125


def test_matmul_parameters_a_token_meets():
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert (attention, indexer) == (18_874_368, 2_260_992)
    # 8 choices x 16 of 128 experts held = one expert a token, expected
    assert family.matmul_params(CONFIG) == 4 * (
        attention + indexer + 2048 * 128 + 1.0 * 3 * 2048 * 768
    ) + 2048 * 18_992 == 143_360_000


def test_required_flops_a_token_at_8192():
    dense = 6 * 143_360_000
    attention = 4 * 12 * 4096 * 1792.125
    index = 4 * (2 * 1024 * 4096.5 + 4 * 1024 * 1792.125)
    assert (dense, attention, index) == (860_160_000, 352_346_112, 62_920_704)
    want = family.required_flops_per_item(CONFIG, 8192)
    assert want == dense + attention + index == 1_275_426_816
    # a step of 16,384 tokens: 20.9 TFLOP
    assert want * 16_384 / 1e12 == pytest.approx(20.9, abs=0.05)


def test_the_cell_is_found_by_name_with_its_counters():
    cell = Cell(load_manifest(), "keyevl2_train_s8192")
    assert cell.family is family and cell.chips == 1
    assert cell.traffic["seq_len"] * cell.traffic["batch_per_chip"] == 16_384
    names = [m["name"] for m in cell.per_layer]
    assert "keys_per_query" in names and "moe_load_max_over_mean" in names
    assert "collective_ms" not in names and "attention_kernel_ms" not in names
    rows = [{"loss": 1.0}] * 2 + [{"keys_per_query": 1792.0,
                                  "moe_load_max_over_mean": 1.2},
                                 {"keys_per_query": 1793.0,
                                  "moe_load_max_over_mean": 1.4}]
    assert cell.reader("keys_per_query")({"rows": rows}) == 1792.5
    assert cell.reader("moe_load_max_over_mean")({"rows": rows}) == (
        pytest.approx(1.3))
    # a program without the counters (the parent): nothing, and no error
    assert cell.reader("keys_per_query")({"rows": [{"loss": 1.0}] * 4}) is None
