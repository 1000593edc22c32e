"""Device-idle milliseconds per federation inside the program's
``fl.init`` spans (``init_server_state``), over the federations
initialised in the window: how long the host's initialisation holds the
chip back."""


def read(ctx):
    from bench import scopes

    feds = ctx.stats["attempted"]
    if not feds or not ctx.trace.span_intervals("fl.init"):
        return None
    return 1e3 * scopes.span_idle_seconds(ctx.trace, "fl.init") / feds
