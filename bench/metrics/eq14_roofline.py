"""Roofline share of the two Pallas eq.-14 kernels: for each launch the
least time the chip could take (the larger of its FLOPs over the bf16 peak
and its bytes over HBM bandwidth, ``bench/flops.py``), times the launches
in the window, over the device time of the launches' modules, in percent.
The kernels contract float32 at ``Precision.HIGHEST``, several bf16 passes
of the MXU, so the share against the bf16 peak stays far below 100."""

MODULES = {"pairwise_dists_stats": "pairwise_dists_stats_kernel",
           "normalized_gram": "normalized_gram_kernel"}


def read(ctx):
    from bench import flops

    measured = ctx.trace.module_seconds(list(MODULES.values()))
    busy = sum(measured.values())
    if busy <= 0:
        return None
    kernels = ctx.system.eq14_kernels()
    least = sum(flops.roofline_seconds(kernels[k]["flops"], kernels[k]["bytes"], ctx.peak)
                for k in MODULES)
    return 100.0 * least * ctx.stats["attempted"] / busy
