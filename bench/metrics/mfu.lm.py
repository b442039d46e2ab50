"""The whole step's share of the chips' bf16 peak: the operations that the
forward and backward passes of the window's rounds require (bench.counts,
no recomputation), over the traced window, the chips and the peak."""


def read(ctx):
    t = ctx["trace"]
    if not t["rounds"]:
        return None
    ops = ctx["round_ops"] * t["rounds"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ops / (t["window_s"] * peak)
