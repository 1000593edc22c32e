"""Device milliseconds per federation of the selection layer's set-up:
the device time inside the benchmark's span around ``init_server_state``
(profile pass, initial losses, eq.-14 kernel, ``eigh`` and the ESP table,
label histograms), over the federations initialised in the window."""


def read(ctx):
    busy = ctx.trace.busy_in_spans("bench.init")
    feds = ctx.stats["attempted"]
    return 1e3 * busy / feds if feds and busy > 0 else None
