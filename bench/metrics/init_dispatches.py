"""Jitted calls the host dispatched inside the program's ``fl.init`` spans,
per federation initialised in the window."""


def read(ctx):
    from bench import scopes

    feds = ctx.stats["attempted"]
    if not feds or not ctx.trace.span_intervals("fl.init"):
        return None
    return scopes.dispatches_in_spans(ctx.trace, "fl.init") / feds
