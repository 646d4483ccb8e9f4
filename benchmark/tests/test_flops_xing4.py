"""Family ``lm_latent_hc_moe_train``'s required-FLOP function and its
attention kernels' operations function against a count by hand at the cell's
sizes, the cell's files found by name, and its four readers on a small
made-up trace: each reads its own scope or counter, and nothing (None, no
error) from a program that has none."""

import pytest

from benchmark.families import lm_latent_hc_moe_train as family
from benchmark.harness.manifest import ROOT, Cell, load_json, load_manifest
from benchmark.tests import tiny

CONFIG = load_json(ROOT + "/benchmark/configs/xing4.0-29b-a4b.json")
CELL = "xing4_train_s4096"
# the benchmark's own table of tiny sizes (tests/tiny.py is not this PR's to
# edit; rehearsal/tiny_xing4.py enters the same)
tiny.TINY.setdefault("lm_latent_hc_moe_train", family.TINY)


def test_matmul_parameters_a_token_meets():
    per = family.part_params(CONFIG)
    # W_dq 3584 x 768, W_uq 768 x 6144, W_dkv 3584 x 576, W_uk and W_uv
    # 512 x 4096 each, W_o 4096 x 3584
    assert per["mla"] == (3584 * 768 + 768 * 6144 + 3584 * 576
                          + 512 * 8192 + 4096 * 3584) == 28_409_856
    assert per["dense_mlp"] == 3 * 3584 * 9216 == 99_090_432
    # the shared expert, the router at 64, and 4 choices x 8 of 64 experts
    # held = 0.5 routed experts a token, expected
    assert per["expert_layer"] == (3 * 3584 * 1024 + 3584 * 64
                                   + 0.5 * 3 * 3584 * 1024) == 16_744_448
    assert per["phi"] == 14_336 * 24 == 344_064
    assert per["eh_proj"] == 7168 * 3584 == 25_690_112
    assert per["head"] == 3584 * 16_384 == 58_720_256
    # 6 blocks of MLA and two phi each, 1 dense MLP, 5 expert layers (4 of the
    # trunk and the MTP module's), eh_proj, and the head twice
    assert family.matmul_params(CONFIG) == (
        6 * (28_409_856 + 2 * 344_064) + 99_090_432 + 5 * 16_744_448
        + 25_690_112 + 2 * 58_720_256) == 500_531_200


def test_required_flops_a_token_at_4096():
    dense = 6 * 500_531_200
    # six blocks' two S-long products, counted full: 6 S heads (192 + 128)
    attention = 6 * 6 * 4096 * 32 * 320
    assert (dense, attention) == (3_003_187_200, 1_509_949_440)
    want = family.required_flops_per_item(CONFIG, 4096)
    assert want == dense + attention == 4_513_136_640
    assert family.required_flops_per_item(CONFIG) == want   # YaRN's original
    # a step of 4,096 tokens: 18.5 TFLOP; MLA's projections and S-long
    # products are 56 % of it, the MTP module's block and head a sixth
    assert want * 4096 / 1e12 == pytest.approx(18.49, abs=0.01)
    assert (6 * 6 * 28_409_856 + attention) / want == pytest.approx(
        0.561, abs=0.001)


def test_the_attention_kernels_own_operations_a_step():
    traffic = load_json(ROOT + "/benchmark/traffic/lm_continue_s4096_b1.json")
    pairs = 1 * 32 * 4096 * 4097 // 2           # causal, the diagonal in
    assert pairs == 268_500_992
    # scores, their second making, dQ and dK at 192; PV, dP and dV at 128
    a_pair = 2 * (4 * 192 + 3 * 128)
    assert a_pair == 2304
    want = family.attention_kernel_flops_per_step(CONFIG, traffic)
    assert want == 6 * pairs * a_pair == 3_711_757_713_408
    # at the chip's 197 TFLOP/s that is 18.8 ms a step: the kernels' time
    # cannot read under it
    assert want / 197e12 * 1e3 == pytest.approx(18.84, abs=0.01)


def test_the_cell_is_found_by_name_with_its_readers():
    cell = Cell(load_manifest(), CELL)
    assert cell.family is family and cell.chips == 1
    assert cell.traffic["seq_len"] * cell.traffic["batch_per_chip"] == 4096
    assert cell.traffic["moment_dtype"] == "bfloat16"
    assert cell.config["num_hidden_layers"] == 5
    names = {m["name"] for m in cell.per_layer}
    assert {"hyper_conn_ms", "mtp_module_ms", "mla_attention_roofline_pct",
            "hc_res_offdiag_share", "attention_kernel_ms",
            "moe_assignments_per_token"} <= names
    assert not {"collective_ms", "keys_per_query", "ssm_chunk_carry"} & names
    assert set(cell.limits) >= {"hc_res_offdiag_share_least",
                                "hc_sinkhorn_error", "mtp_first_loss_ratio",
                                "grad_direction_gap"}
    assert set(family.leaf_map(cell.config).values()) == set(
        family.reference_spec(cell.config))


def made_up_ctx():
    """One chip, one execution of a step of 1,000 ns: a product under
    ``attn_proj`` inside the MTP module (100), a hyper-connection's fusion in
    the trunk (200) and one inside the module (50), the module's loss (30),
    the trunk's head (70), three attention kernels (300)."""
    scopes = ["fwd/fwd_bwd/TransformerLM/mtp/mtp_block/attn/attn_proj/q_up",
              "fwd/fwd_bwd/TransformerLM/backbone_block0/hc_attn/hyper_conn",
              "remat/fwd_bwd/TransformerLM/mtp/mtp_block/hc_mlp/hyper_conn",
              "fwd/fwd_bwd/mtp/loss", "fwd/fwd_bwd/TransformerLM/head",
              "fwd/fwd_bwd/TransformerLM/backbone_block0/attn/attention"]
    events = [["fusion.1", 1000, 100], ["fusion.2", 1100, 200],
              ["fusion.3", 1300, 50], ["fusion.4", 1350, 30],
              ["fusion.5", 1380, 70], ["flash_fwd.1", 1450, 100],
              ["flash_dq.1", 1550, 100], ["flash_dkv.1", 1650, 100]]
    table = {"module": "jit__step", "scopes": scopes, "inside": {},
             "ops": {"fusion.1": 0, "fusion.2": 1, "fusion.3": 2,
                     "fusion.4": 3, "fusion.5": 4, "flash_fwd.1": 5,
                     "flash_dq.1": 5, "flash_dkv.1": 5}, "unnamed_ops": 0}
    record = {"devices": {"/device:TPU:0": events},
              "modules": {"/device:TPU:0": [["jit__step", 1000, 1000]]}}
    return {"cell": CELL, "record": record, "traced": {"steps": 1},
            "spans": [{"name": "step_scopes", "args": table}],
            "reduced": {"top_families": [["flash_fwd", 100], ["flash_dq", 100],
                                         ["flash_dkv", 100]]},
            "config": CONFIG, "chips": 1,
            "traffic": load_json(
                ROOT + "/benchmark/traffic/lm_continue_s4096_b1.json"),
            "peaks": {"bf16_flops_per_s": 197e12}, "warm_epochs": 2,
            "rows": [{"loss": 1.0}] * 2 + [{"hc_res_offdiag_share": 0.4},
                                           {"hc_res_offdiag_share": 0.5}]}


def test_the_new_readers_read_their_own_scope_and_counter():
    cell, ctx = Cell(load_manifest(), CELL), made_up_ctx()
    ns = 1e-6
    # innermost hyper_conn, in the trunk and in the module
    assert cell.reader("hyper_conn_ms")(ctx) == pytest.approx(250 * ns)
    # mtp anywhere in the path: the product, the module's hyper-connection,
    # its loss
    assert cell.reader("mtp_module_ms")(ctx) == pytest.approx(180 * ns)
    # the accepted split still sees the module's product as attn_proj's, and
    # the hyper-connections under no layer scope of scope_time.json
    assert cell.reader("scope_attn_proj_ms")(ctx) == pytest.approx(100 * ns)
    assert cell.reader("hc_res_offdiag_share")(ctx) == pytest.approx(0.45)
    share = cell.reader("mla_attention_roofline_pct")(ctx)
    assert share == pytest.approx(
        100 * 3_711_757_713_408 / (300e-9 * 197e12))


def test_a_program_without_them_gives_nothing_and_no_error():
    cell, ctx = Cell(load_manifest(), CELL), made_up_ctx()
    bare = dict(ctx, spans=[], reduced={"top_families": [["fusion", 900]]},
                rows=[{"loss": 1.0}] * 4)
    for name in ("hyper_conn_ms", "mtp_module_ms",
                 "mla_attention_roofline_pct", "hc_res_offdiag_share"):
        assert cell.reader(name)(dict(bare)) is None
    # a table that holds neither scope: nothing ran there
    plain = made_up_ctx()
    plain["spans"][0]["args"]["scopes"] = [
        "fwd/fwd_bwd/TransformerLM/head"] * 6
    assert cell.reader("hyper_conn_ms")(plain) is None
    assert cell.reader("mtp_module_ms")(plain) is None
