"""The benchmark's data-driven parts: finding a cell's files by name,
checking a window's federations against the plain reference, and reading
the per-layer metrics.

Everything that belongs to one cell, configuration or metric sits in a
file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration's sizes, its source,
  its target accuracy, and the names of its program adapter
  (``bench/models/<model>.py``) and plain reference
  (``bench/references/<reference>.py``);
* ``bench/workloads/<cell>.json``: the cell's traffic and the limits of
  its correctness numbers;
* ``bench/metrics/<metric>.py``: one per-layer metric's reader, a function
  ``read(ctx)`` that returns the value or ``None`` when its cell has
  nothing for it to read.

Nothing here, in ``run.py``, ``federations.py``, ``calibrate.py``,
``tiny.py`` or a metric's reader names a model or reads a key of one model
family.  What a model needs, its adapter and its reference give.

A program adapter, ``bench/models/<model>.py``, exports:

* ``make_deployment(cfg, key) -> (client_xs, client_ys, test_xs, test_ys)``:
  the clients' data, stacked ``(C, n, ...)``, their labels ``(C, n)`` (the
  label histograms and eq. 15 read them), and the held-out set, made on the
  device from ``key``;
* ``TINY_SIZES``: the keys of the configuration to override so that the
  CPU tests run the cell in seconds (``bench/tiny.py``);
* ``System(cfg, strategy)``, the program built through its normal path,
  with ``init_params(key)``, ``init_state(params, key, client_xs,
  client_ys)`` (``init_server_state``), ``stack(states)``,
  ``run_chunk(state, rounds, lockstep) -> (state, outputs)`` (``run_scanned``
  or ``run_many``; the outputs hold ``selected`` and ``loss`` per round),
  ``accuracy(params, test_xs, test_ys, lockstep)`` (one reading per
  federation), and the counts from shapes: ``round_flops(eval_round)``,
  ``init_flops()`` and ``eq14_kernels()`` (``bench/flops.py``);
* optionally ``accuracy(params, test_xs, test_ys)``: one federation's
  reading, which ``calibrate.py limits`` also takes under ``jax.vmap``.

A plain reference, ``bench/references/<reference>.py``, imports nothing of
the program and exports, each reading its sizes and settings from ``cfg``:

* ``init_params(key, cfg)``: the initial weights by the program's recipe;
* ``profiles(params, client_xs, cfg, dtype)``: the (C, F) eq.-(11)
  profiles;
* ``replay(params0, client_xs, client_ys, cohorts, cfg, dtype, keep) ->
  (params, loss)``: the rounds with the given (R, k) cohorts, each client
  on the first ``keep`` share of its samples, and each round's mean local
  loss;
* ``accuracy(params, xs, ys, cfg, dtype)``: the held-out accuracy.

``dtype`` is ``float32`` or, for the control, ``bfloat16``; ``keep`` < 1
plants a fault.  The eq.-(14) kernel is model-free (``bench/eq14.py``).

A per-layer metric with no ``workloads`` list in ``BENCHMARK.json`` is
read in every cell, those that later configurations add too, so its reader
may use only what every adapter gives.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, flops
from bench.eq14 import eq14_kernel
from bench.federations import Runner

__all__ = ["ROOT", "Cell", "load_cell", "load_module", "MetricContext", "read_metrics",
           "pick", "check_window", "reference_run"]

ROOT = Path(__file__).resolve().parent.parent


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` of the checkout at ``root`` as a module."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    root: Path  # the checkout the cell's files were read from
    entry: dict  # the cell's entry in BENCHMARK.json
    cfg: dict  # bench/configs/<config>.json
    workload: dict  # bench/workloads/<cell>.json
    per_layer: List[dict]  # BENCHMARK.json's per-layer metrics read in this cell
    end_to_end: List[dict]

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[entry["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    workload = json.loads((root / "bench" / "workloads" / f"{name}.json").read_text())

    def in_cell(m):
        return name in m.get("workloads", [name])

    return Cell(name, root, entry, cfg, workload,
                [m for m in spec["per_layer"] if in_cell(m)],
                [m for m in spec["end_to_end"] if in_cell(m)])


# ------------------------------------------------------------------ check


def pick(runner: Runner, cell: Cell, seed: int) -> list:
    """The (batch, slot) of the federations the check replays, drawn from
    the seed."""
    rng = np.random.default_rng([seed % 2**63, seed // 2**63, 7])
    keys = sorted(runner.snapshots)
    n = min(len(keys), int(cell.traffic["check_federations"]))
    return [keys[i] for i in sorted(rng.choice(len(keys), size=n, replace=False))]


def check_window(runner: Runner, cell: Cell, seed: int, reference=None):
    """Replay a sample of the window's federations, drawn from the seed,
    with the plain reference.  -> (correct, checks, per-federation numbers)."""
    cfg = cell.cfg
    ref = reference or load_module("references", cfg["reference"], cell.root)
    picked = pick(runner, cell, seed)
    numbers: Dict[str, float] = {"cohort_invalid": float(runner.invalid_rounds)}
    each = []
    for batch, slot in picked:
        snap = runner.snapshot(batch, slot)
        got = reference_run(ref, cfg, runner, batch, slot, snap)
        nums = check.compare(snap, got)
        each.append({"federation": [batch, slot], **nums})
        for k, v in nums.items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    if not picked:
        numbers["federations_checked"] = 0.0
    correct, checks = check.verdict(numbers, cell.workload["limits"])
    return correct and bool(picked), checks, each


def reference_run(ref, cfg, runner: Runner, batch: int, slot: int, snap: dict,
                  dtype=jnp.float32, keep: float = 1.0) -> dict:
    """The plain reference over one federation's first chunk: its own
    initial weights from the federation's key, profiles, eq.-(14) kernel,
    the rounds replayed with the program's cohorts, and the held-out
    accuracy of the params on which the program's stopping rule read its
    own.  ``dtype`` and ``keep`` give the control and a planted fault."""
    pk, _ = runner.keys(batch, slot)
    p0 = ref.init_params(jax.random.key(pk), cfg)
    prof = ref.profiles(p0, runner.client_xs, cfg, dtype=dtype)
    kernel = eq14_kernel(prof, dtype=dtype)
    params, loss = ref.replay(p0, runner.client_xs, runner.client_ys,
                              jnp.asarray(snap["selected"], jnp.int32), cfg, dtype=dtype,
                              keep=keep)
    acc = ref.accuracy(snap["judged_params"], runner.test_xs, runner.test_ys, cfg, dtype=dtype)
    return {"params0": p0, "params": params, "loss": np.asarray(loss),
            "judged_acc": float(acc), "kernel": kernel}


# --------------------------------------------------------------- metrics


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read: the reduced trace of the window,
    the window's counts, and the cell's configuration and peaks."""

    trace: object  # bench.trace.Trace
    stats: dict  # Runner.window()'s counts
    records: list  # the window's FedRecords
    cfg: dict
    traffic: dict
    system: object
    peak: dict

    @property
    def span(self):
        return self.trace.window()

    @property
    def window_s(self) -> float:
        lo, hi = self.span
        return (hi - lo) / 1e9

    def round_flops_total(self) -> float:
        """FLOPs of the window's federation-rounds, by the adapter's count
        of one round."""
        every = int(self.cfg["eval_every"])
        return float(sum(flops.rounds_flops(self.system.round_flops, every, r.rounds_run)
                         for r in self.records))


def read_metrics(cell: Cell, ctx: MetricContext) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader found something."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"], cell.root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def fmt_limit(limit: Optional[float]) -> str:
    return "not compared" if limit is None else repr(limit)
