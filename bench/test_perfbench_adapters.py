"""The harness reaches a model only through its adapter and its reference:
paper-cnn's readings through them are the readings the harness took when
it called the CNN's data, FLOP counts and reference directly, and no
model-free file of the harness names a model or reads a model's key."""

from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, data, harness
from bench.federations import FedRecord, Runner, derive_key
from bench.tiny import tiny

ROOT = Path(__file__).resolve().parent.parent
CELL = "paper-cnn.table1"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**33 + 29

# Read at the commit before the harness went through the adapter, from
# ``sum(flops.rounds_flops(cfg, r.rounds_run) for r in RECORDS)`` on the
# tiny cell, and from ``check.compare`` of the tiny cell's first batch
# (seed SEED, max_rounds 5) against the reference called without ``cfg``:
# the program, the reference on half of each batch, and the reference in
# bfloat16, each against the reference.
RECORDS = [FedRecord(0, 0, 10, 10), FedRecord(0, 1, None, 20), FedRecord(1, 0, 5, 15),
           FedRecord(1, 1, 15, 3)]
PARENT_ROUND_FLOPS = 9113812992
PARENT_NUMBERS = {
    (0, 0): {
        "program": {"kernel_gap": 1.6118326002219356e-06, "first_loss_gap": 0.0,
                    "loss_gap": 6.995453934306252e-08, "update_gap": 0.0, "update_diff": 0.0,
                    "acc_gap": 0.0},
        "half": {"kernel_gap": 0.0, "first_loss_gap": 0.0980886345844371,
                 "loss_gap": 0.2673658296419489, "update_gap": 0.06171276483918311,
                 "update_diff": 0.41560096562647897, "acc_gap": 0.0},
        "bf16": {"kernel_gap": 0.00715256748565318, "first_loss_gap": 0.00425525041358699,
                 "loss_gap": 0.019117134888372005, "update_gap": 0.05918885252635433,
                 "update_diff": 0.1894969534115673, "acc_gap": 0.0},
    },
    (0, 1): {
        "program": {"kernel_gap": 1.1021284317613068e-06, "first_loss_gap": 0.0,
                    "loss_gap": 1.535040759169758e-07, "update_gap": 0.0, "update_diff": 0.0,
                    "acc_gap": 0.0},
        "half": {"kernel_gap": 0.0, "first_loss_gap": 0.0739297980131007,
                 "loss_gap": 0.2630959706703537, "update_gap": 0.14124940119710755,
                 "update_diff": 0.5466562760247181, "acc_gap": 0.0},
        "bf16": {"kernel_gap": 0.0042482379873525706, "first_loss_gap": 0.0010032474221235241,
                 "loss_gap": 0.01700045406904174, "update_gap": 0.04983821220475546,
                 "update_diff": 0.1500339854123644, "acc_gap": 0.0},
    },
}

MODEL_FREE = ["bench/run.py", "bench/harness.py", "bench/federations.py", "bench/calibrate.py",
              "bench/tiny.py", *sorted(str(p.relative_to(ROOT))
                                       for p in (ROOT / "bench" / "metrics").glob("*.py"))]
# the CNN's program module, its data (bench/data.py), its name and its keys
MODEL_WORDS = re.compile(r"repro\.models|bench\.data|\b(cnn|image_hw|channels|fc1_dim|xi)\b",
                         re.I)


def _model(cell):
    return harness.load_module("models", cell.cfg["model"], cell.root)


def test_cnn_deployment_is_bench_data_bit_for_bit():
    cell = tiny(harness.load_cell(CELL))
    key = jax.random.key(derive_key(SEED, 0))
    ours = _model(cell).make_deployment(cell.cfg, key)
    theirs = data.make_deployment(cell.cfg, key)
    assert len(ours) == len(theirs) == 4
    for a, b in zip(ours, theirs):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


def test_round_flops_total_is_the_parents_count():
    cell = tiny(harness.load_cell(CELL))
    system = _model(cell).System(cell.cfg, cell.traffic["strategy"])
    ctx = harness.MetricContext(None, {}, RECORDS, cell.cfg, cell.traffic, system, PEAK)
    assert ctx.round_flops_total() == PARENT_ROUND_FLOPS


def test_check_numbers_with_cfg_are_the_parents():
    cell = tiny(harness.load_cell(CELL), max_rounds=5)
    cfg = cell.cfg
    model = _model(cell)
    runner = Runner(model.System(cfg, cell.traffic["strategy"]), cfg, cell.traffic,
                    model.make_deployment(cfg, jax.random.key(derive_key(SEED, 0))), SEED)
    runner.run_batch(0)
    ref = harness.load_module("references", cfg["reference"], cell.root)
    assert sorted(runner.snapshots) == sorted(PARENT_NUMBERS)
    for (batch, slot), parent in PARENT_NUMBERS.items():
        snap = runner.snapshot(batch, slot)
        want = harness.reference_run(ref, cfg, runner, batch, slot, snap)
        got = {
            "program": check.compare(snap, want),
            "half": check.compare(
                harness.reference_run(ref, cfg, runner, batch, slot, snap, keep=0.5), want),
            "bf16": check.compare(
                harness.reference_run(ref, cfg, runner, batch, slot, snap, dtype=jnp.bfloat16),
                want),
        }
        for kind, numbers in parent.items():
            assert got[kind] == pytest.approx(numbers, rel=1e-12, abs=0), (batch, slot, kind)


@pytest.mark.parametrize("path", MODEL_FREE)
def test_model_free_files_name_no_model(path):
    found = [(i, line) for i, line in enumerate((ROOT / path).read_text().splitlines(), 1)
             if MODEL_WORDS.search(line)]
    assert not found, found
