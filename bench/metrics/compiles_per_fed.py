"""Compiles per federation: the program's ``obs.compile`` markers (one per
backend compile or compile-cache load) inside the window, over the
federations it attempted.  Read only where the program marks its
initialisation (``fl.init`` spans), so a program without the markers
reads nothing rather than zero."""


def read(ctx):
    from bench import scopes

    found = scopes.of(ctx)
    feds = ctx.stats["attempted"]
    if found is None or not feds or not ctx.trace.span_intervals("fl.init"):
        return None
    lo, hi = ctx.span
    return found.markers_in(lo, hi) / feds
