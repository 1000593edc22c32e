"""Shared FL experiment runner for the paper-figure benchmarks.

Runs the Algorithm-1 protocol over a (dataset × ξ × method × seed) grid at
the CPU-budget scale (``paper_cnn.bench_scale``) and caches every history in
``results/fl_grid.json`` so benchmark modules (fig1/fig2/table1 all read the
same runs) and re-invocations never recompute.

Scale via env: REPRO_BENCH_SCALE = tiny | bench (default) | paper.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs import paper_cnn
from repro.core import make_strategy
from repro.data import make_image_dataset, skewness_partition
from repro.fl import FLConfig, FLTrainer, engine
from repro.models import cnn

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")
GRID_PATH = os.path.join(RESULTS, "fl_grid.json")

# two synthetic datasets stand in for MNIST / Fashion-MNIST (data gate —
# DESIGN.md §4): same shape/scale, different generative seeds & noise.
DATASETS = {"synth-mnist": dict(seed=11, noise=0.5), "synth-fashion": dict(seed=23, noise=0.8)}


def scale() -> paper_cnn.PaperExperiment:
    s = os.environ.get("REPRO_BENCH_SCALE", "bench")
    if s == "paper":
        return paper_cnn.paper_scale()
    if s == "tiny":
        return paper_cnn.PaperExperiment(
            num_clients=16, clients_per_round=4, samples_per_client=60,
            local_epochs=1, lr=0.08, rounds=10, eval_every=2, seeds=1,
            cnn_channels=(8, 16), fc1_dim=64,
        )
    return paper_cnn.bench_scale()


def spawn_cpu_child(module: str, payload, devices: int,
                    timeout: int = 1200) -> Dict:
    """Run ``python -m <module> --child <payload>`` on ``devices`` emulated
    CPU devices and return the JSON object its last stdout line prints.

    The child is a CPU harness, never a chip measurement: it pins
    ``JAX_PLATFORMS=cpu`` beside the forced host device count, so it never
    reaches for an accelerator that its parent process may hold."""
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} " + flags
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", module, "--child", json.dumps(payload)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{module} child (devices={devices}) failed:\n"
            f"{proc.stdout}\n{proc.stderr}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


def _load_grid() -> Dict:
    if os.path.exists(GRID_PATH):
        with open(GRID_PATH) as f:
            return json.load(f)
    return {}


def _save_grid(grid: Dict) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    tmp = GRID_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(grid, f)
    os.replace(tmp, GRID_PATH)


def build_trainer(
    exp: paper_cnn.PaperExperiment,
    dataset: str,
    xi,
    method: str,
    seed: int,
    init_scheme: str = "kaiming_uniform",
    profile_kind: str = "fc1",
) -> FLTrainer:
    dkw = DATASETS[dataset]
    ds = make_image_dataset(
        n=exp.num_clients * exp.samples_per_client, seed=dkw["seed"], noise=dkw["noise"]
    )
    shards = skewness_partition(
        ds.ys, exp.num_clients, xi, ds.num_classes,
        samples_per_client=exp.samples_per_client, seed=seed,
    )
    cxs = np.stack([ds.xs[s] for s in shards])
    cys = np.stack([ds.ys[s] for s in shards])
    params = cnn.init_cnn(
        jax.random.key(seed),
        channels=exp.cnn_channels,
        fc1_dim=exp.fc1_dim,
        scheme=init_scheme,
    )
    cfg = paper_cnn.fl_config(exp, seed=seed)
    trainer = FLTrainer(
        cfg, params, cnn.cnn_loss, cnn.apply_with_features, cxs, cys,
        make_strategy(method), accuracy_fn=cnn.accuracy,
    )
    if profile_kind != "fc1":
        _swap_profiles(trainer, profile_kind)
    return trainer


def _swap_profiles(trainer: FLTrainer, kind: str) -> None:
    """Fig.-3 ablation: rebuild the DPP kernel from gradient-based profiles."""
    from repro.core import kernel_from_profiles, profiles as profiles_lib
    import jax.numpy as jnp

    rows = []
    for c in range(trainer.cfg.num_clients):
        if kind == "gradient":
            r = profiles_lib.gradient_profile(
                trainer.loss_fn, trainer.params, trainer.client_xs[c], trainer.client_ys[c]
            )
        elif kind == "repr_gradient":
            r = profiles_lib.representative_gradient_profile(
                trainer.loss_fn, trainer.params, trainer.client_xs[c], trainer.client_ys[c]
            )
        else:
            raise ValueError(kind)
        rows.append(r)
    f = jnp.stack(rows)
    trainer.round_state.profiles = f
    trainer.round_state.kernel = kernel_from_profiles(f)


def _case_key(dataset, xi, method, seed, exp, init_scheme="kaiming_uniform",
              profile_kind="fc1") -> str:
    return (
        f"{dataset}|xi={xi}|{method}|seed={seed}|init={init_scheme}|prof={profile_kind}|"
        f"C={exp.num_clients}x{exp.samples_per_client}|T={exp.rounds}"
    )


def prefill_grid(
    datasets: Sequence[str], xis: Sequence, methods: Sequence[str], exp=None
) -> int:
    """Fill the fl_grid cache for a (dataset × ξ × method × seed) sweep
    through the scanned federation engine.

    All methods share ONE multi-strategy ``round_fn`` (``lax.switch`` on
    ``ServerState.strategy_index``), so the entire grid executes through a
    single compiled scan program — the per-case data/params/kernel ride in
    the state.  Returns the number of newly computed cases.
    """
    exp = exp or scale()
    grid = _load_grid()
    missing = [
        (ds, xi, m, s)
        for ds in datasets
        for xi in xis
        for m in methods
        for s in range(exp.seeds)
        if _case_key(ds, xi, m, s, exp) not in grid
    ]
    if not missing:
        return 0
    methods = tuple(methods)
    strategies = tuple(make_strategy(m) for m in methods)
    cfg = paper_cnn.fl_config(exp, seed=0)
    round_fn = engine.make_round_fn(
        cfg, cnn.cnn_loss, strategies, accuracy_fn=cnn.accuracy
    )
    for ds, xi, m, s in missing:
        t0 = time.time()
        trainer = build_trainer(exp, ds, xi, m, s)
        state = dataclasses.replace(
            trainer.server_state(),
            strategy_index=np.int32(methods.index(m)),
        )
        state_f, outs = engine.run_scanned(round_fn, state, exp.rounds)
        final_acc = None
        if exp.rounds % exp.eval_every != 0:
            xs = trainer.client_xs.reshape((-1,) + trainer.client_xs.shape[2:])
            final_acc = float(cnn.accuracy(state_f.params, xs, trainer.client_ys.reshape(-1)))
        hist = engine.history_from_outputs(
            jax.tree_util.tree_map(np.asarray, outs), exp.eval_every, final_acc=final_acc
        )
        hist["wall_s"] = time.time() - t0
        grid = _load_grid()
        grid[_case_key(ds, xi, m, s, exp)] = hist
        _save_grid(grid)
    return len(missing)


def run_case(
    dataset: str, xi, method: str, seed: int, exp=None,
    init_scheme: str = "kaiming_uniform", profile_kind: str = "fc1",
    force: bool = False,
) -> Dict[str, List]:
    exp = exp or scale()
    key = _case_key(dataset, xi, method, seed, exp, init_scheme, profile_kind)
    grid = _load_grid()
    if key in grid and not force:
        return grid[key]
    t0 = time.time()
    trainer = build_trainer(exp, dataset, xi, method, seed, init_scheme, profile_kind)
    hist = trainer.run()
    hist["wall_s"] = time.time() - t0
    grid = _load_grid()  # re-read: other processes may have written
    grid[key] = hist
    _save_grid(grid)
    return hist


def rounds_to_accuracy(hist: Dict[str, List], target: float) -> Optional[int]:
    for r, a in zip(hist["round"], hist["acc"]):
        if a >= target:
            return r
    return None


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
