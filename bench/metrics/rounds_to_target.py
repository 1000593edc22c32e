"""Mean rounds to the target accuracy over the window's federations that
reached it (read at chunk ends, so a multiple of ``eval_every``)."""


def read(ctx):
    hits = ctx.stats["rounds_to_target"]
    return sum(hits) / len(hits) if hits else None
