"""The traffic generator: federations run back to back over one deployment.

A cell's traffic (its workload file's ``traffic`` object) says which
selection strategy every federation uses, how many federations run in
lockstep as one ``run_many`` batch (``lockstep``; 1 runs them one at a time
through ``run_scanned``), and how many federations of a window the
correctness check replays (``check_federations``).  The configuration gives
the rounds per chunk (``eval_every``), the round limit (``max_rounds``) and
the target accuracy.

Each federation is one ``init_server_state`` (profiles, eq.-14 kernel,
spectral cache) and then chunks of ``eval_every`` rounds; after each chunk
the program's accuracy on the held-out set is read, and a federation stops
counting at the target (a batch runs on until all of its federations have
reached it, or to the limit).  Every key derives from the run's seed, the
batch and the slot, so the same seed gives the same federations.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import check

__all__ = ["FedRecord", "Runner", "derive_key"]


def derive_key(seed: int, *path: int) -> int:
    """A 32-bit integer key drawn from ``seed`` (any size) and ``path``."""
    return int(np.random.SeedSequence([seed % 2**63, seed // 2**63, *path]).generate_state(1)[0])


def _span(name):
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class FedRecord:
    batch: int
    slot: int
    reached_at: Optional[int]  # rounds to the target, None if it was missed
    rounds_run: int


class Runner:
    """Runs a cell's federations on one deployment (the client data)."""

    PURPOSE_PARAMS, PURPOSE_SELECTION = 1, 2

    def __init__(self, system, cfg: dict, traffic: dict, data, seed: int):
        self.system, self.cfg, self.traffic, self.seed = system, cfg, traffic, seed
        self.client_xs, self.client_ys, self.test_xs, self.test_ys = data
        self.lockstep = int(traffic["lockstep"])
        self.chunk = int(cfg["eval_every"])
        self.max_rounds = int(cfg["max_rounds"])
        self.target = float(cfg["target_accuracy"])
        self.records: List[FedRecord] = []
        self.snapshots: Dict[tuple, dict] = {}
        self.invalid_rounds = 0

    def keys(self, batch: int, slot: int):
        path = (batch + 1, slot)  # batch -1 is the warm-up
        return (derive_key(self.seed, self.PURPOSE_PARAMS, *path),
                derive_key(self.seed, self.PURPOSE_SELECTION, *path))

    def run_batch(self, batch: int, max_chunks: Optional[int] = None,
                  snapshot: bool = True) -> List[FedRecord]:
        """One batch of ``lockstep`` federations, to the target or the limit
        (or ``max_chunks`` chunks: the warm-up)."""
        s_count, sysm = self.lockstep, self.system
        with _span("bench.init"):
            params0, states = [], []
            for slot in range(s_count):
                pk, sk = self.keys(batch, slot)
                p0 = sysm.init_params(jax.random.key(pk))
                states.append(sysm.init_state(p0, jax.random.key(sk),
                                              self.client_xs, self.client_ys))
                params0.append(p0)
            state = states[0] if s_count == 1 else sysm.stack(states)
            jax.block_until_ready(state)
        reached: List[Optional[int]] = [None] * s_count
        judged = {}  # slot -> (the batch's params, its accuracy, rounds) where it was judged
        rounds = 0
        while rounds < self.max_rounds:
            with _span("bench.chunk"):
                state, outs = sysm.run_chunk(state, self.chunk, s_count)
                # the accuracy reading is queued behind the chunk before the
                # host waits on the chunk's outputs, so the part of its
                # dispatch that does not itself wait for the chunk runs
                # while the chip computes
                acc = sysm.accuracy(state.params, self.test_xs, self.test_ys, s_count)
                selected = np.asarray(outs["selected"])
                loss = np.asarray(outs["loss"])
            rounds += self.chunk
            with _span("bench.eval"):
                acc = np.atleast_1d(np.asarray(acc))
            self.invalid_rounds += check.cohort_invalid(
                selected, self.cfg["clients_per_round"], self.cfg["num_clients"])
            if snapshot and rounds == self.chunk:
                self._snapshot(batch, params0, states, state, selected, loss)
            for slot in range(s_count):
                if reached[slot] is None and acc[slot] >= self.target:
                    reached[slot] = rounds
                    judged[slot] = (state.params, float(acc[slot]), rounds)
            if all(r is not None for r in reached):
                break
            if max_chunks is not None and rounds >= max_chunks * self.chunk:
                break
        if snapshot:
            for slot in range(s_count):
                params, a, r = judged.get(slot, (state.params, float(acc[slot]), rounds))
                self.snapshots[(batch, slot)].update(
                    judged_params=params, judged_acc=a, judged_round=r)
        return [FedRecord(batch, slot, reached[slot], rounds) for slot in range(s_count)]

    def _snapshot(self, batch, params0, states, state, selected, loss):
        """What the check needs of each federation's first chunk: device
        references to small leaves only, never the client data, and no
        device work (a lockstep batch's params are sliced after the
        window, see :meth:`snapshot`).  The run adds the params and the
        accuracy reading of the chunk where the stopping rule judged the
        federation: where it reached the target, or its last."""
        for slot in range(self.lockstep):
            one = self.lockstep == 1
            self.snapshots[(batch, slot)] = {
                "params0": params0[slot],
                "kernel": states[slot].kernel,
                "params": state.params,
                "selected": selected if one else selected[slot],
                "loss": loss if one else loss[slot],
            }

    def snapshot(self, batch: int, slot: int) -> dict:
        """A federation's snapshot with its own params picked out."""
        snap = dict(self.snapshots[(batch, slot)])
        if self.lockstep > 1:
            for k in ("params", "judged_params"):
                snap[k] = jax.tree_util.tree_map(lambda x: x[slot], snap[k])
        return snap

    def warm_up(self):
        """Compile and load every program of the cell: one batch's
        initialisation and two chunks with their accuracy readings (the
        second chunk's input is a chunk's output, which a lockstep batch's
        program sees as a new signature)."""
        self.run_batch(-1, max_chunks=2, snapshot=False)
        self.invalid_rounds = 0

    def window(self, seconds: float) -> dict:
        """Batches back to back; none starts after ``seconds``, and the
        measured time runs to the end of the last one."""
        start = time.perf_counter()
        batch = 0
        with _span("bench.window"):
            while time.perf_counter() - start < seconds:
                self.records.extend(self.run_batch(batch))
                batch += 1
        elapsed = time.perf_counter() - start
        reached = [r for r in self.records if r.reached_at is not None]
        fed_rounds = sum(r.rounds_run for r in self.records)
        return {
            "window_s": elapsed,
            "batches": batch,
            "attempted": len(self.records),
            "failed": len(self.records) - len(reached),
            "reached": len(reached),
            "fed_rounds": fed_rounds,
            "rounds_to_target": [r.reached_at for r in reached],
        }
