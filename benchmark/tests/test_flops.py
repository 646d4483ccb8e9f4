"""The required-FLOP functions against hand arithmetic, and the peaks table."""

import pytest

from benchmark.harness.manifest import load_json, ROOT
from benchmark.harness.peaks import peaks_for


def test_gpt2_medium_flops_per_token():
    from benchmark.families import lm_train

    cfg = load_json(ROOT + "/benchmark/configs/gpt2-medium.json")
    # 24 blocks x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 50257 head
    assert lm_train.matmul_params(cfg) == 24 * 12_582_912 + 51_463_168
    # 6 per matmul parameter + 12 x L x hidden x S (full attention)
    want = 6 * 353_453_056 + 12 * 24 * 1024 * 1024
    assert lm_train.required_flops_per_item(cfg) == want == 2_422_708_224
    assert want / 1e9 == pytest.approx(2.42, abs=0.005)


def test_vit_b16_flops_per_image():
    from benchmark.families import vision_train

    cfg = load_json(ROOT + "/benchmark/configs/vit-b16.json")
    # per patch token: 12 blocks x (4 x 768^2 + 2 x 768 x 3072) + 768 x 768 patch
    assert vision_train.matmul_params_per_token(cfg) == 84_934_656 + 589_824
    # 196 tokens x 6 x that + 6 x 768 x 1000 head + 12 x 12 x 768 x 196^2
    want = 196 * 6 * 85_524_480 + 4_608_000 + 110_592 * 38_416
    assert vision_train.required_flops_per_item(cfg) == want == 104_829_898_752
    assert want / 1e9 == pytest.approx(105, abs=0.5)


def test_mfu_reader_is_items_times_flops_over_peak():
    from benchmark.metrics import train_mfu

    ctx = {"peaks": {"bf16_flops_per_s": 200e12}, "chips": 4,
           "window": {"items_per_s": 80_000.0}, "flops_per_item": 2.5e9}
    assert train_mfu.read(ctx) == pytest.approx(100 * 80e3 * 2.5e9 / 800e12)


def test_unknown_device_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in the peaks table"):
        peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks_for("_source")
