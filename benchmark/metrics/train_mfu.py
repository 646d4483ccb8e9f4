"""End to end. 100 x items/s in the window x required FLOPs per item over
(chips x the bf16 peak of the device found). Items are tokens or images; the
FLOPs are the family's ``required_flops_per_item`` (never XLA's count)."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return (100.0 * ctx["window"]["items_per_s"] * ctx["flops_per_item"]
            / peak)
