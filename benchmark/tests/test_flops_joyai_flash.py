"""Family ``lm_latent_moe_train``'s required-FLOP function, its ``part_params``
and its attention kernels' operations function against a count by hand at the
published sizes, the cell's files found by name, the reference's leaves counted
against the family's, and the new counter's reader on made-up rows: it reads
its own counter, and nothing (None, no error) from a program that has none."""

import math

import pytest

from benchmark.families import lm_latent_moe_train as family
from benchmark.harness.manifest import ROOT, Cell, load_json, load_manifest
from benchmark.tests import tiny

CONFIG = load_json(ROOT + "/benchmark/configs/joyai-llm-flash.json")
CELL = "joyai_flash_train_s8192"
# the benchmark's own table of tiny sizes (tests/tiny.py and tests/conftest.py
# are not this PR's to edit; rehearsal/tiny_joyai_flash.py enters the same)
tiny.TINY.setdefault("lm_latent_moe_train", family.TINY)


def test_matmul_parameters_a_token_meets():
    per = family.part_params(CONFIG)
    # W_dq 2048 x 1536, W_uq 1536 x 6144, W_dkv 2048 x 576, W_uk and W_uv
    # 512 x 4096 each, W_o 4096 x 2048
    assert per["mla"] == (2048 * 1536 + 1536 * 6144 + 2048 * 576
                          + 512 * 8192 + 4096 * 2048) == 26_345_472
    assert per["dense_mlp"] == 3 * 2048 * 7168 == 44_040_192
    # an expert 3 x 2048 x 768; the shared one, the router at 256, and
    # 8 choices x 16 of 256 experts held = 0.5 routed experts a token, expected
    assert 3 * 2048 * 768 == 4_718_592
    assert per["expert_layer"] == (4_718_592 + 2048 * 256
                                   + 0.5 * 4_718_592) == 7_602_176
    assert per["eh_proj"] == 4096 * 2048 == 8_388_608
    assert per["head"] == 2048 * 16_160 == 33_095_680
    # 6 blocks of MLA, 1 dense MLP, 5 expert layers (4 of the trunk and the
    # MTP module's), eh_proj, and the head twice
    assert family.matmul_params(CONFIG) == (
        6 * 26_345_472 + 44_040_192 + 5 * 7_602_176 + 8_388_608
        + 2 * 33_095_680) == 314_703_872


def test_the_held_parameters_are_the_issues_count():
    """What the chip holds (every held expert whole, the norms' gains too):
    the reference's leaves, which are the program's one for one."""
    spec = family.reference_spec(CONFIG)
    count = lambda keep: sum(math.prod(shape)             # noqa: E731
                             for name, (shape, _) in spec.items()
                             if keep(name))
    attention = ("wdq", "qn.g", "wuq", "wdkv", "kvn.g", "wuk", "wuv", "wo")
    # 26,345,472 in matrices and the two latents' norms' 1,536 + 512 gains
    assert count(lambda n: n.startswith("l3.") and n[3:] in attention) == (
        26_345_472 + 2_048)
    assert count(lambda n: n.startswith("l0.")) == 70_391_808
    assert count(lambda n: n.startswith("l1.")) == 107_091_968
    assert count(lambda n: n.startswith("m.")) == 107_091_968 + 8_392_704
    assert count(lambda n: n in ("wte", "head.w", "lnf.g")) == 66_193_408
    assert count(lambda n: True) == 680_437_760
    assert set(family.leaf_map(CONFIG).values()) == set(spec)


def test_required_flops_a_token_at_8192():
    dense = 6 * 314_703_872
    # six blocks' two S-long products, counted full: 6 S heads (192 + 128)
    attention = 6 * 6 * 8192 * 32 * 320
    assert (dense, attention) == (1_888_223_232, 3_019_898_880)
    want = family.required_flops_per_item(CONFIG, 8192)
    assert want == dense + attention == 4_908_122_112
    # a step of 16,384 tokens: 80.4 TFLOP; the S-long products 61.5 % of it,
    # MLA's projections 19.3 %, both heads 8.1 %, layer 0's MLP 5.4 %, router
    # + shared + held experts 4.6 %, eh_proj 1.0 %
    assert want * 16_384 / 1e12 == pytest.approx(80.41, abs=0.01)
    share = lambda params: 6 * params / want                  # noqa: E731
    assert attention / want == pytest.approx(0.615, abs=0.001)
    assert share(6 * 26_345_472) == pytest.approx(0.193, abs=0.001)
    assert share(2 * 33_095_680) == pytest.approx(0.081, abs=0.001)
    assert share(44_040_192) == pytest.approx(0.054, abs=0.001)
    assert share(5 * 7_602_176) == pytest.approx(0.046, abs=0.001)
    assert share(8_388_608) == pytest.approx(0.010, abs=0.001)


def test_the_attention_kernels_own_operations_a_step():
    traffic = load_json(ROOT + "/benchmark/traffic/lm_continue_s8192_b2.json")
    pairs = 2 * 32 * 8192 * 8193 // 2           # causal, the diagonal in
    assert pairs == 2_147_745_792
    # scores, their second making, dQ and dK at 192; PV, dP and dV at 128
    a_pair = 2 * (4 * 192 + 3 * 128)
    assert a_pair == 2304
    want = family.attention_kernel_flops_per_step(CONFIG, traffic)
    assert want == 6 * pairs * a_pair == 29_690_437_828_608
    # at the chip's 197 TFLOP/s that is 150.7 ms a step: the kernels' time
    # cannot read under it
    assert want / 197e12 * 1e3 == pytest.approx(150.71, abs=0.01)


def test_the_cell_is_found_by_name_with_its_readers():
    cell = Cell(load_manifest(), CELL)
    assert cell.family is family and cell.chips == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["moment_dtype"] == "bfloat16"
    assert cell.config["num_hidden_layers"] == 5
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_block_assignments_max", "mtp_module_ms",
            "mla_attention_roofline_pct", "attention_kernel_ms",
            "moe_assignments_per_token", "moe_rows_run_share",
            "scope_router_ms", "scope_shared_expert_ms"} <= names
    assert not {"collective_ms", "keys_per_query", "ssm_chunk_carry",
                "hyper_conn_ms", "hc_fused_share"} & names
    assert set(cell.limits) >= {"mtp_first_loss_ratio", "grad_direction_gap",
                                "expert_choice_margin", "moe_dropped"}
    # the three routed cells that were there report the new counter too
    listed = next(m for m in load_manifest()["per_layer"]
                  if m["name"] == "moe_block_assignments_max")["workloads"]
    assert listed == ["keyevl2_train_s8192", "nemotron3nano_train_s8192",
                      "xing4_train_s4096", CELL]


def test_the_new_reader_reads_its_counter_and_nothing_without_it():
    cell = Cell(load_manifest(), CELL)
    read = cell.reader("moe_block_assignments_max")
    rows = [{"loss": 1.0}] * 2 + [
        {"moe_block_assignments_max": 0.6, "moe_assignments_per_token": 0.5},
        {"moe_block_assignments_max": 0.7, "moe_assignments_per_token": 0.6}]
    ctx = {"rows": rows, "warm_epochs": 2}
    assert read(ctx) == pytest.approx(0.65)
    assert read(ctx) >= cell.reader("moe_assignments_per_token")(ctx)
    # a parent without the counter: nothing, and no error
    assert read({"rows": [{"loss": 1.0}] * 4, "warm_epochs": 2}) is None
