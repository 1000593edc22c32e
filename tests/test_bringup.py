"""Bring-up plumbing, checked on the CPU: the chip smoke run refuses a host
without a TPU, the compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or a
fixed in-checkout path, and the CPU harnesses (dry-run, bench children)
pin ``JAX_PLATFORMS=cpu`` so they never reach for a chip their parent may
hold."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**overrides):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(overrides)
    return env


def test_chip_smoke_refuses_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_child_env(JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    second = compile_cache.use_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


@pytest.mark.parametrize("platforms", [None, "tpu"])
def test_dryrun_pins_cpu_before_importing_jax(platforms):
    code = (
        "import os, repro.launch.dryrun, jax; "
        "print(os.environ['JAX_PLATFORMS'], jax.config.jax_platforms)"
    )
    env = _child_env() if platforms is None else _child_env(JAX_PLATFORMS=platforms)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["cpu", "cpu"]


@pytest.mark.parametrize("module,call", [
    ("shard_bench", lambda m: m._spawn_payload(4, {"devices": 4})),
    ("async_bench", lambda m: m._spawn({"devices": 4})),
    ("fault_bench", lambda m: m._spawn({"devices": 4})),
    ("cohort_sweep", lambda m: m._spawn({}, 4)),
])
def test_bench_children_pin_cpu(monkeypatch, module, call):
    import importlib

    from benchmarks import common

    seen = {}

    def fake_run(cmd, env, **kw):
        seen.update(cmd=cmd, env=env)
        return types.SimpleNamespace(returncode=0, stdout=json.dumps({"ok": 1}),
                                     stderr="")

    monkeypatch.setattr(common.subprocess, "run", fake_run)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert call(importlib.import_module(f"benchmarks.{module}")) == {"ok": 1}
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    assert seen["cmd"][1:3] == ["-m", f"benchmarks.{module}"]


def test_planted_swap_crosses_shards():
    """The four-chip phase's planted fault swaps the data of the first
    round's lowest-id client with the least-selected client's on another
    shard, and nothing else."""
    import importlib.util

    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    @dataclasses.dataclass
    class Clients:
        client_xs: np.ndarray
        client_ys: np.ndarray

    state = Clients(np.arange(8)[:, None] * np.ones((8, 3)), np.arange(8))
    sel = np.array([[2, 1], [1, 3], [1, 0], [4, 2]])
    swapped, (i, j) = smoke._planted_swap(state, sel, c_loc=4)
    # client 1 lives on shard 0 (clients 0-3); on shard 1, 5 is the
    # least-selected client with the lowest id
    assert (i, j) == (1, 5)
    want = np.array([0, 5, 2, 3, 4, 1, 6, 7])
    np.testing.assert_array_equal(swapped.client_ys, want)
    np.testing.assert_array_equal(swapped.client_xs[:, 0], want)
