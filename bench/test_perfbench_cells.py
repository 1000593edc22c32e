"""Every cell's pieces end to end on the CPU at a tiny size (Pallas in
interpret mode), the command's refusal without a TPU, and a cell, a
configuration and a metric added as new files only: a sibling of the CNN,
and a token federation whose model is no CNN (``bench/fixtures/bigram``)."""

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
from bench.federations import Runner, derive_key
from bench.tiny import tiny

ROOT = Path(__file__).resolve().parent.parent
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run_window(cell, seed, seconds=0.5, trace_dir=None):
    cfg = cell.cfg
    model = harness.load_module("models", cfg["model"], cell.root)
    system = model.System(cfg, cell.traffic["strategy"])
    data = model.make_deployment(cfg, jax.random.key(derive_key(seed, 0)))
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


FIXTURE = ROOT / "bench" / "fixtures" / "bigram"


def _bigram_round_flops(cfg, eval_round):
    """The bigram's round counted by hand: three products of the head per
    position and local step, one per position for the loss refresh and the
    held-out forward."""
    fwd = 2 * (cfg["seq_len"] - 1) * cfg["embed_dim"] * cfg["vocab_size"]
    kn = cfg["clients_per_round"] * cfg["samples_per_client"]
    return (kn * cfg["local_epochs"] * 3 * fwd + kn * fwd
            + (cfg["test_samples"] * fwd if eval_round else 0))


def test_a_token_federation_needs_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path)
    added = [p.relative_to(FIXTURE) for p in sorted(FIXTURE.rglob("*"))
             if p.suffix in (".py", ".json")]
    for rel in added:
        dest = tmp_path / "bench" / rel
        assert not dest.exists(), rel
        shutil.copy(FIXTURE / rel, dest)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "bigram", "source": "https://arxiv.org/abs/2303.17358",
                            "file": "bench/configs/bigram.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "bigram.topics", "config": "bigram",
                              "traffic": "topics", "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}

    cell = tiny(harness.load_cell("bigram.topics", root=tmp_path))
    cfg = cell.cfg
    assert cfg["num_clients"] == 12 and cfg["vocab_size"] == 32 and cfg["embed_dim"] == 16
    assert "channels" not in cfg and cell.traffic["lockstep"] == 2
    seed = 2**33 + 41
    system, runner, stats = _run_window(cell, seed, seconds=0.3, trace_dir=tmp_path / "t")
    assert stats["attempted"] >= 2 and stats["fed_rounds"] >= 10
    from bench import trace

    ctx = harness.MetricContext(trace.load(str(tmp_path / "t")), stats, runner.records, cfg,
                                cell.traffic, system, PEAK)
    metrics = harness.read_metrics(cell, ctx)
    every = cfg["eval_every"]
    want = sum((r.rounds_run - r.rounds_run // every) * _bigram_round_flops(cfg, False)
               + r.rounds_run // every * _bigram_round_flops(cfg, True) for r in runner.records)
    assert metrics["round_mfu"]["value"] == pytest.approx(
        100 * want / (ctx.window_s * PEAK["bf16_flops_per_s"]), rel=1e-12)
    listed = {m["name"] for m in spec["per_layer"] if "workloads" in m}
    assert "eq14_roofline" in listed and not listed & set(metrics)
    assert {"round_ms", "fed_mfu", "selection_ms", "device_idle_share"} <= set(metrics)

    correct, checks, each = harness.check_window(runner, cell, seed)
    assert correct, checks
    assert len(each) == 2

    # the reference on half of each client's batch, put in the program's place
    ref = harness.load_module("references", cfg["reference"], tmp_path)
    numbers = {}
    for batch, slot in harness.pick(runner, cell, seed):
        snap = runner.snapshot(batch, slot)
        good = harness.reference_run(ref, cfg, runner, batch, slot, snap)
        half = harness.reference_run(ref, cfg, runner, batch, slot, snap, keep=0.5)
        for k, v in check.compare(half, good).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    correct, checks = check.verdict(numbers, cell.workload["limits"])
    assert not correct
    assert checks["update_gap"]["value"] > checks["update_gap"]["limit"], checks
