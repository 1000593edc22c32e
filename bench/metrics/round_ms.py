"""Device milliseconds per federation-round: the device time inside the
benchmark's spans around each scan chunk and its accuracy reading, over the
federation-rounds of the window (a lockstep batch's rounds count once per
federation in it)."""


def read(ctx):
    busy = ctx.trace.busy_in_spans("bench.chunk") + ctx.trace.busy_in_spans("bench.eval")
    rounds = ctx.stats["fed_rounds"]
    return 1e3 * busy / rounds if rounds and busy > 0 else None
