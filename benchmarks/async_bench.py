"""Bounded-staleness vs synchronous barrier: simulated wall-clock-to-target.

Prices the scanned federation engine's two aggregation modes (DESIGN.md §§8-9)
under the system-heterogeneity scenarios of ``repro.fl.scenarios``: for each
latency model the SAME federation (same clients, same selection key chain —
cohorts are bit-identical by construction, latency-only scenarios never touch
the selection stream) runs once through the synchronous sharded round (round
cost = max latency over the cohort, the psum barrier) and once through
bounded-staleness aggregation (round cost = the scenario deadline for
stragglers, their contributions landing stale and decay-weighted).  Both
runs' per-round ``sim_time`` metrics come straight out of the compiled scan.

The headline metric is **simulated wall clock to equal final loss**: the
target is the loss floor both arms reach, and the speedup is the ratio of
cumulative simulated time to first hit it.  Under the heavy-tail scenario
(Pareto α=1.1 stragglers) the synchronous barrier pays the max of the
cohort's draws every round while the stale round is cut off at the deadline,
so the win is structural — gated at ≥1.5x (full mode only; the metric is
*simulated*, so unlike the shard-scaling gates it does not depend on host
core count).  The child also asserts the staleness-parity contract:
``staleness_bound=0`` picks bit-identical cohorts and fp32-close params vs
the synchronous engine.

Runs in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count``
(the staleness engine needs a client mesh; the flag must precede jax init).
Writes ``BENCH_async.json`` (repo root); ``--smoke`` runs tiny shapes with no
gate and writes ``BENCH_async_smoke.json`` (CI harness + check_regression
input):

    PYTHONPATH=src python -m benchmarks.async_bench [--smoke]

This is a CPU harness, not a chip measurement: its children run on emulated
host devices with ``JAX_PLATFORMS=cpu`` pinned (``common.spawn_cpu_child``),
and every time it records is a CPU time.
"""

from __future__ import annotations

import argparse
import json
import os
import time

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_async.json")
SMOKE_OUT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_async_smoke.json"
)

# one federation, three latency regimes; rounds_stale > rounds_sync because
# stale gradients buy cheap rounds at a small per-round convergence cost —
# time-to-target is the honest comparison, not rounds-to-target
FULL = dict(clients=16, n_c=32, feat=32, hidden=64, steps=4, k=8, devices=8,
            rounds_sync=48, rounds_stale=72, bound=4,
            decay="polynomial", alpha=0.5, lr=0.05)
SMOKE = dict(clients=8, n_c=8, feat=8, hidden=16, steps=2, k=4, devices=4,
             rounds_sync=8, rounds_stale=12, bound=2,
             decay="polynomial", alpha=0.5, lr=0.05)
BENCH_SCENARIOS = ("uniform", "lognormal", "heavy_tail")
ASYNC_TARGET = 1.5  # x, heavy_tail time-to-target, full mode only


# ----------------------------------------------------------------- child


def _time_to_target(losses, sim_times, target):
    """Cumulative simulated time at the first round whose running-best loss
    reaches ``target`` (the loss signal is the cohort mean — monotonise with
    a running min before thresholding)."""
    import numpy as np

    best = np.minimum.accumulate(np.asarray(losses, np.float64))
    cum = np.cumsum(np.asarray(sim_times, np.float64))
    hit = np.nonzero(best <= target)[0]
    return float(cum[hit[0]]) if hit.size else None


def _child(w: dict) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from benchmarks.shard_bench import _mlp_workload, _parity
    from repro.core import selection as selection_lib
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == w["devices"], (jax.device_count(), w)
    loss_fn, xs, ys, params, ncls = _mlp_workload(w)
    mesh = make_client_mesh(w["devices"])
    strat = selection_lib.UniformSelection()
    base = dict(
        num_clients=w["clients"], clients_per_round=w["k"],
        local_epochs=w["steps"], lr=w["lr"], rounds=w["rounds_sync"],
        eval_every=10 * w["rounds_stale"], num_classes=ncls, seed=0,
    )

    def run(cfg, rounds):
        state = engine.init_server_state(
            cfg, params, loss_fn, None, xs, ys, strategy=strat,
            profiles=xs.mean(axis=1), mesh=mesh,
        )
        rf = engine.make_round_fn(cfg, loss_fn, (strat,), mesh=mesh)
        st, outs = engine.run_scanned(rf, state, rounds, mesh=mesh)
        return st, jax.tree_util.tree_map(np.asarray, outs)

    by_scenario = {}
    parity = None
    for scen in BENCH_SCENARIOS:
        cfg_sync = engine.FLConfig(**dict(base, scenario=scen))
        st_sync, out_sync = run(cfg_sync, w["rounds_sync"])
        cfg_stale = engine.FLConfig(**dict(
            base, scenario=scen, staleness_bound=w["bound"],
            staleness_decay=w["decay"], staleness_alpha=w["alpha"],
        ))
        st_stale, out_stale = run(cfg_stale, w["rounds_stale"])

        if scen == "heavy_tail":
            # the s=0 parity contract: bit-identical cohorts, fp32 params
            cfg_s0 = engine.FLConfig(**dict(
                base, scenario=scen, staleness_bound=0,
                staleness_decay=w["decay"], staleness_alpha=w["alpha"],
            ))
            st_s0, out_s0 = run(cfg_s0, w["rounds_sync"])
            parity = _parity((st_sync, out_sync), (st_s0, out_s0))

        # equal-final-loss target: the loss floor BOTH arms reach
        floor_sync = float(np.min(out_sync["loss"]))
        floor_stale = float(np.min(out_stale["loss"]))
        target = max(floor_sync, floor_stale)
        t_sync = _time_to_target(out_sync["loss"], out_sync["sim_time"], target)
        t_stale = _time_to_target(
            out_stale["loss"], out_stale["sim_time"], target
        )
        by_scenario[scen] = dict(
            target_loss=target,
            final_loss_sync=floor_sync,
            final_loss_stale=floor_stale,
            time_to_target_sync=t_sync,
            time_to_target_stale=t_stale,
            speedup=(t_sync / t_stale) if t_sync and t_stale else None,
            mean_round_time_sync=float(np.mean(out_sync["sim_time"])),
            mean_round_time_stale=float(np.mean(out_stale["sim_time"])),
            mean_staleness=float(np.mean(out_stale["staleness"])),
        )
    return dict(by_scenario=by_scenario, parity=parity)


# ---------------------------------------------------------------- parent


def _spawn(w: dict) -> dict:
    from benchmarks import common

    return common.spawn_cpu_child("benchmarks.async_bench", w, w["devices"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no perf gate (CI harness check)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child is not None:
        print(json.dumps(_child(json.loads(args.child))))
        return None

    from benchmarks import common

    t0 = time.time()
    w = SMOKE if args.smoke else FULL
    res = _spawn(w)
    for scen, row in res["by_scenario"].items():
        sp = row["speedup"]
        head = (
            f"sync={row['time_to_target_sync']:8.2f} "
            f"stale={row['time_to_target_stale']:8.2f} speedup={sp:.2f}x"
            if sp is not None else "target unreached"
        )
        print(f"  async_bench {scen:11s} {head}  "
              f"mean_round sync={row['mean_round_time_sync']:.2f} "
              f"stale={row['mean_round_time_stale']:.2f} "
              f"mean_staleness={row['mean_staleness']:.2f}")

    heavy = res["by_scenario"]["heavy_tail"]
    parity = res["parity"] or {}
    gate_enforced = not args.smoke
    ok = bool(parity.get("ok", False))
    if gate_enforced:
        ok = ok and (heavy["speedup"] or 0.0) >= ASYNC_TARGET

    payload = dict(
        bench="async_sim_wall_clock_to_target",
        smoke=args.smoke,
        workload=dict(w, model="mlp(2-layer)", selection="uniform"),
        host_cores=os.cpu_count() or 1,
        target_speedup=ASYNC_TARGET,
        gate_enforced=gate_enforced,
        gate_note=(
            f"heavy_tail simulated time-to-equal-final-loss must be >= "
            f"{ASYNC_TARGET}x the synchronous barrier; simulated metrics "
            "are core-count independent, so the gate arms on every full "
            "run; s=0 parity always enforced"
        ),
        parity=parity,
        by_scenario=res["by_scenario"],
        ok=ok,
        total_s=round(time.time() - t0, 2),
    )
    out_path = SMOKE_OUT_PATH if args.smoke else OUT_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    hs = heavy["speedup"]
    hs_str = f"{hs:.2f}x" if hs is not None else "n/a"
    print(common.csv_line(
        "async_stale_vs_sync",
        0.0,
        f"heavy_tail_speedup={hs_str} parity_ok={parity.get('ok')} "
        f"gate_enforced={gate_enforced} ok={ok}",
    ))
    print(f"ok={ok}  wrote {os.path.abspath(out_path)}")
    if not ok:
        raise SystemExit(1)
    return payload


if __name__ == "__main__":
    main()
