"""Every cell's pieces end to end on the CPU at a tiny size (Pallas in
interpret mode), the command's refusal without a TPU, and a cell, a
configuration and a metric added as new files only."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import check, harness
from bench.data import make_deployment
from bench.federations import Runner, derive_key
from bench.tiny import tiny

ROOT = Path(__file__).resolve().parent.parent
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run_window(cell, seed, seconds=0.5, trace_dir=None):
    cfg = cell.cfg
    system = harness.load_module("models", cfg["model"], cell.root).System(
        cfg, cell.traffic["strategy"])
    data = make_deployment(cfg, jax.random.key(derive_key(seed, 0)))
    runner = Runner(system, cfg, cell.traffic, data, seed)
    runner.warm_up()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        stats = runner.window(seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return system, runner, stats


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_at_tiny_size(name, tmp_path):
    cell = tiny(harness.load_cell(name))
    seed = 2**33 + 17  # wider than 32 bits, as the driver's seeds are
    system, runner, stats = _run_window(cell, seed, trace_dir=tmp_path)
    assert stats["attempted"] >= 1 and stats["fed_rounds"] >= 5
    assert stats["attempted"] == stats["reached"] + stats["failed"]
    correct, checks, each = harness.check_window(runner, cell, seed)
    assert correct, checks
    assert each and set(checks) == set(check.NUMBERS)
    from bench import trace

    ctx = harness.MetricContext(trace.load(str(tmp_path)), stats, runner.records, cell.cfg,
                                cell.traffic, system, PEAK)
    metrics = harness.read_metrics(cell, ctx)
    want = {m["name"] for m in cell.per_layer}
    if not stats["reached"]:
        want.discard("rounds_to_target")
    assert set(metrics) == want
    for m in ("round_mfu", "fed_mfu", "eq14_roofline", "device_idle_share"):
        assert 0 < metrics[m]["value"] <= 100, (m, metrics[m])


def test_same_seed_same_federations():
    cell = tiny(harness.load_cell(CELLS[0]))
    a = _run_window(cell, 5, seconds=0.0)[1]
    b = _run_window(cell, 5, seconds=0.0)[1]
    for r in (a, b):
        r.run_batch(0, max_chunks=1)
    assert (a.snapshots[(0, 0)]["selected"] == b.snapshots[(0, 0)]["selected"]).all()
    assert a.keys(0, 0) == b.keys(0, 0) != a.keys(0, 1)


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_host_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


def _digest(root: Path):
    files = [ROOT / "BENCHMARK.json"] + sorted(
        p for p in (ROOT / "bench").rglob("*") if p.is_file() and "__pycache__" not in p.parts)
    return {str(p.relative_to(ROOT)): hashlib.sha256((root / p.relative_to(ROOT)).read_bytes())
            .hexdigest() for p in files}


def test_a_new_cell_config_and_metric_need_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path)
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "paper-cnn.json").read_text())
    cfg.update(name="half-cnn", channels=[8, 16], fc1_dim=64)
    (bench / "configs" / "half-cnn.json").write_text(json.dumps(cfg))
    workload = json.loads((bench / "workloads" / f"{CELLS[0]}.json").read_text())
    workload["traffic"].update(strategy="fedavg", lockstep=1)
    (bench / "workloads" / "half-cnn.uniform.json").write_text(json.dumps(workload))
    (bench / "metrics" / "chunks_per_federation.py").write_text(
        "def read(ctx):\n"
        "    feds = ctx.stats['attempted']\n"
        "    return ctx.stats['fed_rounds'] / ctx.cfg['eval_every'] / feds if feds else None\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "half-cnn", "source": "https://arxiv.org/abs/2303.17358",
                            "file": "bench/configs/half-cnn.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "half-cnn.uniform", "config": "half-cnn",
                              "traffic": "uniform", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "chunks_per_federation", "unit": "chunks",
                              "better": "lower", "source": "program_counter",
                              "layer": "federation engine", "moves": "rounds_per_s",
                              "workloads": ["half-cnn.uniform"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}

    cell = harness.load_cell("half-cnn.uniform", root=tmp_path)
    assert cell.cfg["fc1_dim"] == 64 and cell.traffic["strategy"] == "fedavg"
    assert "chunks_per_federation" in {m["name"] for m in cell.per_layer}
    cell = tiny(cell, channels=[4, 8])
    system, runner, stats = _run_window(cell, 9, seconds=0.2, trace_dir=tmp_path / "t")
    from bench import trace

    ctx = harness.MetricContext(trace.load(str(tmp_path / "t")), stats, runner.records,
                                cell.cfg, cell.traffic, system, PEAK)
    metrics = harness.read_metrics(cell, ctx)
    assert metrics["chunks_per_federation"]["unit"] == "chunks"
    assert metrics["chunks_per_federation"]["value"] >= 1
