"""The comparison that decides ``correct`` fails a run whose program is
broken underneath it, on the CPU at a tiny size, with each cell's own
limits: a round that returns its weights unchanged, half of each client's
batch left out (the mean taken over the rest), a cohort trained other
than the one reported, and the held-out accuracy read against labels
shifted by one sample (an answer altered where it is produced).  The
harness's look for a chip is skipped; everything else is the run's own
path (warm-up, window, check).  The control, the plain reference in
bfloat16 put in the program's place, fails the same limits.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness
from bench.federations import Runner, derive_key
from bench.tiny import tiny
from repro.fl import engine

ROOT = Path(__file__).resolve().parent.parent
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**32 + 11


def _runner(cell, seed=SEED):
    cfg = cell.cfg
    model = harness.load_module("models", cfg["model"], cell.root)
    system = model.System(cfg, cell.traffic["strategy"])
    data = model.make_deployment(cfg, jax.random.key(derive_key(seed, 0)))
    return Runner(system, cfg, cell.traffic, data, seed)


def _unchanged(monkeypatch, runner):
    for fn in ("run_scanned", "run_many"):
        orig = getattr(engine, fn)

        def broken(round_fn, state, *a, _orig=orig, **k):
            new, outs = _orig(round_fn, state, *a, **k)
            return dataclasses.replace(new, params=state.params), outs

        monkeypatch.setattr(engine, fn, broken)


def _half_batch(monkeypatch, runner):
    orig = engine.make_client_batches

    def broken(cfg, key, xs, ys, sel):
        xb, yb = orig(cfg, key, xs, ys, sel)
        n = xb.shape[2] // 2
        return xb[:, :, :n], yb[:, :, :n]

    monkeypatch.setattr(engine, "make_client_batches", broken)


def _cohort_altered(monkeypatch, runner):
    orig = engine.make_client_batches

    def broken(cfg, key, xs, ys, sel):
        return orig(cfg, key, xs, ys, (sel + 1) % xs.shape[0])

    monkeypatch.setattr(engine, "make_client_batches", broken)


def _answer_altered(monkeypatch, runner):
    read = runner.system.accuracy

    def broken(params, xs, ys, lockstep):
        return read(params, xs, jnp.roll(ys, 1), lockstep)

    monkeypatch.setattr(runner.system, "accuracy", broken)


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "cohort_altered": _cohort_altered, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_program_is_not_correct(name, fault, monkeypatch):
    cell = tiny(harness.load_cell(name), max_rounds=5)
    runner = _runner(cell)
    FAULTS[fault](monkeypatch, runner)
    runner.warm_up()
    runner.window(1e-3)
    correct, checks, _ = harness.check_window(runner, cell, SEED)
    assert not correct, checks


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny(harness.load_cell(name), max_rounds=5)
    runner = _runner(cell)
    runner.run_batch(0)
    ref = harness.load_module("references", cell.cfg["reference"], cell.root)
    numbers = {}
    for batch, slot in harness.pick(runner, cell, SEED):
        snap = runner.snapshot(batch, slot)
        want = harness.reference_run(ref, cell.cfg, runner, batch, slot, snap)
        ctrl = harness.reference_run(ref, cell.cfg, runner, batch, slot, snap,
                                     dtype=jnp.bfloat16)
        for k, v in check.compare(ctrl, want).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    assert numbers
    correct, checks = check.verdict(numbers, cell.workload["limits"])
    assert not correct, checks
