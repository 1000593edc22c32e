"""Share of the chip's bf16 peak that the rounds' own work makes of the
window: the FLOPs a federation-round needs (every client's local steps,
the cohort's loss refresh, the held-out forward on an evaluation round;
``bench/flops.py``) times the window's federation-rounds, over the window's
seconds times the peak, in percent."""


def read(ctx):
    total = ctx.round_flops_total()
    if not total:
        return None
    return 100.0 * total / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
