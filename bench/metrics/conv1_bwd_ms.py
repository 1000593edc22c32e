"""Device milliseconds per federation-round of the first convolution
stage's backward as one Pallas pass: the operations that carry the
program's ``conv1_bwd`` scope on their op-name path, over the window's
federation-rounds.

``conv1_bwd`` is no ``fl.*`` stage: ``bench/scopes.py`` gives these
operations to the stage around them (``fl.local_update``), whose metric
keeps counting them; this reader reads the same trace with the kernel's
scope in place of the stage's.  A program that runs XLA's backward there
has no such operations: on the CPU, where the program keeps XLA's backward
by design, that reads 0; on the chip it means a program without the
kernel, and reads nothing."""

import re

SCOPE = "conv1_bwd"
CPU_HOST = ["/host:CPU"]
# the scope as one op-name component, bare or inside a transform's name
# (``.../jvp(conv1_bwd)/...`` in a gradient)
_COMPONENT = re.compile(r"(?:^|[/(])" + SCOPE + r"(?:[)/]|$)")


def _kernel_scope(op_name):
    return SCOPE if _COMPONENT.search(op_name or "") else None


def read(ctx):
    from unittest import mock

    from bench import scopes

    found = scopes.of(ctx)
    rounds = ctx.stats["fed_rounds"]
    if found is None or not rounds or not found.scoped():
        return None
    # the same operations, devices and clock as every scope reader's, each
    # named by the kernel's scope instead of its innermost fl.* one
    with mock.patch.object(scopes, "scope_of", _kernel_scope):
        kernel = scopes.load(scopes.TRACE_DIR)
    lo, hi = ctx.span
    ms = 1e3 * kernel.seconds(SCOPE, lo, hi) / rounds
    if not ms and found.devices != CPU_HOST:
        return None
    return ms
