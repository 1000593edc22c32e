"""Device milliseconds per federation-round of the operations the program
scopes ``fl.select`` (the strategy's draw: for fl-dp3s the k-DPP sample
from the spectral cache), over the window's federation-rounds."""


def read(ctx):
    from bench import scopes

    found = scopes.of(ctx)
    rounds = ctx.stats["fed_rounds"]
    if found is None or not rounds or not found.scoped():
        return None
    lo, hi = ctx.span
    return 1e3 * found.seconds("fl.select", lo, hi) / rounds
