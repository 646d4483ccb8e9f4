"""Pin the analytic roofline math: the floors and ceilings that
``tools/roofline.py`` prints (no device) regenerate from the code.

The measured tool (conv_profile) shares the ConvSpec FLOP/byte models, so
these tests guard both the printed tables and the on-chip tool's `vs_bound`
column from silent drift.
"""

import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "tools"))

from conv_profile import ConvSpec, mobilenet_v2_convs, resnet50_convs  # noqa: E402
from roofline import model_floor, transformer_floor, PARAMS  # noqa: E402


def test_conv_spec_arithmetic():
    """Hand-checked 1x1 conv: flops and minimal bytes."""
    s = ConvSpec("x", in_hw=8, cin=16, cout=32, k=1, stride=1)
    b = 2
    # fwd MACs*2 = 2*B*HW^2*K^2*Cin*Cout
    assert s.fwd_flops(b) == 2 * b * 64 * 16 * 32
    assert s.flops(b) == 3 * s.fwd_flops(b)
    act_in, act_out = b * 64 * 16 * 2, b * 64 * 32 * 2
    w = 16 * 32 * 2
    assert s.bytes_fwd(b) == act_in + act_out + w
    # bwd: in 3x (2 reads + din), out 2x (write + dout read), w 3x
    assert s.bytes_moved(b) == 3 * act_in + 2 * act_out + 3 * w


def test_depthwise_is_deeply_memory_bound():
    s = ConvSpec("dw", in_hw=56, cin=144, cout=144, k=3, stride=1,
                 groups=144)
    ai = s.flops(256) / s.bytes_moved(256)
    assert ai < 10  # ~1 flop/byte territory; v5e needs 241 to be MXU-bound


def test_published_model_floors():
    """The values ``tools/roofline.py`` prints for the two conv models at
    224², batch 256 (rounded) regenerate from the code."""
    mn = model_floor("mn", mobilenet_v2_convs(224), 256, "fwdbwd",
                     PARAMS["mobilenet_v2"])
    rn = model_floor("rn", resnet50_convs(224), 256, "fwdbwd",
                     PARAMS["resnet50"])
    assert abs(mn["floor_ms"] - 21.2) < 0.5, mn["floor_ms"]
    assert 0.09 < mn["mfu_ceiling"] < 0.13
    assert mn["mem_bound_frac"] > 0.95  # "99% memory-bound"
    assert abs(rn["floor_ms"] - 45.8) < 1.0, rn["floor_ms"]
    assert 0.65 < rn["mfu_ceiling"] < 0.75


def test_published_transformer_floors():
    vit = transformer_floor("vit", batch=256, seq=196, hidden=192, depth=6,
                            mlp_dim=768, vocab=5)
    lm = transformer_floor("lm", batch=8, seq=2048, hidden=512, depth=6,
                           mlp_dim=2048, vocab=8192)
    assert vit["bound"] == "mxu" and lm["bound"] == "mxu"
    assert vit["mfu_ceiling"] > 0.9 and lm["mfu_ceiling"] > 0.9
    # cross-checks against XLA's cost analysis of the compiled steps at
    # these shapes (986 GFLOP, 3.98 TFLOP): analytic totals within ~15%
    assert abs(vit["flops"] - 986e9) / 986e9 < 0.15
    assert abs(lm["flops"] - 3.98e12) / 3.98e12 < 0.15


def test_conv_layer_counts():
    """Model tables enumerate the architectures they claim."""
    mn = mobilenet_v2_convs(224)
    rn = resnet50_convs(224)
    # MobileNetV2: stem + 17 blocks (16 with expand) + top conv
    assert sum(1 for s in mn if s.groups > 1) == 17   # one dw per block
    assert mn[0].name == "stem" and mn[-1].cout == 1280
    # ResNet50: stem + 16 bottlenecks x3 + 4 projections = 53 convs
    assert len(rn) == 53
    assert sum(1 for s in rn if s.k == 3) == 16
