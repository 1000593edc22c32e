"""Share of the chip's bf16 peak that all the work a federation needs
makes of the window: the rounds (as ``round_mfu``) plus each federation's
initialisation (every client profiled, the initial losses, the two eq.-14
kernels; not the ``eigh``), over the window's seconds times the peak, in
percent.  It bounds every kernel's gain: a kernel taken off the path falls
silent, this does not."""


def read(ctx):
    total = ctx.round_flops_total() + ctx.stats["attempted"] * ctx.system.init_flops()
    if not total:
        return None
    return 100.0 * total / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
