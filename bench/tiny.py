"""A cell cut to a size the CPU tests can run in seconds: the sizes its
configuration's adapter gives in ``TINY_SIZES`` in place of the
configuration's own, the protocol (strategy, lockstep, chunking, stopping
rule, check) unchanged.  Only the tests use it; no chip run does."""

from __future__ import annotations

import dataclasses

from bench import harness


def tiny(cell, **extra):
    """The cell with its configuration cut to its adapter's ``TINY_SIZES``
    and at most two federations in lockstep."""
    sizes = harness.load_module("models", cell.cfg["model"], cell.root).TINY_SIZES
    traffic = dict(cell.traffic, lockstep=min(2, int(cell.traffic["lockstep"])))
    return dataclasses.replace(cell, cfg={**cell.cfg, **sizes, **extra},
                               workload={**cell.workload, "traffic": traffic})
