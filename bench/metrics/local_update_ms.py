"""Device milliseconds per federation-round of the operations the program
scopes ``fl.local_update`` (every selected client's local steps, eq. 3-5),
over the window's federation-rounds."""


def read(ctx):
    from bench import scopes

    found = scopes.of(ctx)
    rounds = ctx.stats["fed_rounds"]
    if found is None or not rounds or not found.scoped():
        return None
    lo, hi = ctx.span
    return 1e3 * found.seconds("fl.local_update", lo, hi) / rounds
