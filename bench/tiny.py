"""A cell cut to a size the CPU tests can run in seconds: every width and
count shrunk, the protocol (strategy, lockstep, chunking, stopping rule,
check) unchanged.  Only the tests use it; no chip run does."""

from __future__ import annotations

import dataclasses

TINY_SIZES = {"num_clients": 12, "clients_per_round": 3, "samples_per_client": 20,
              "num_classes": 4, "channels": [4, 8], "fc1_dim": 16, "test_samples": 64,
              "max_rounds": 20, "target_accuracy": 0.4}


def tiny(cell, **extra):
    """The cell with its configuration cut to ``TINY_SIZES`` and at most two
    federations in lockstep."""
    traffic = dict(cell.traffic, lockstep=min(2, int(cell.traffic["lockstep"])))
    return dataclasses.replace(cell, cfg={**cell.cfg, **TINY_SIZES, **extra},
                               workload={**cell.workload, "traffic": traffic})
