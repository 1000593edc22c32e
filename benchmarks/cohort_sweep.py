"""Cohort-size convergence study: rounds-to-target-loss vs k at fixed C.

The throughput half of the ROADMAP cohort-size study lives in
``shard_bench``'s k-sweep (slotted rounds cost ≈cap, not C_loc, local
updates); this module ships the **convergence half**: at a fixed federation
size C, how many rounds does each selection strategy need to reach a common
target loss as the cohort size k sweeps?  Where DPP diversity stops paying
vs uniform is exactly the question the selection surveys pose
(arXiv:2211.01549, arXiv:2310.00198).

Executed the cheap way the engine makes possible (DESIGN.md §§7-8): per k,
ALL strategies × seeds run as ONE ``run_many`` grid over a multi-strategy
``round_fn`` (``lax.switch`` on ``strategy_index``) through the
**capacity-slot** sharded engine (``cohort_cap = k``), so a k-client round
pays k — not C — local updates whatever the cohort size.  The federation is
class-skewed non-IID (each client dominated by two classes) so profile-kernel
diversity has signal to exploit.

Per k the common target is the loss floor every arm reaches; per strategy we
record the mean-over-seeds rounds to hit it, the mean cohort GEMD, and the
grid's steady-state scan throughput (the ``rounds_per_sec`` metric
``check_regression`` tracks).  Like the other gated harnesses the sweep runs
in a subprocess with a **pinned** ``--xla_force_host_platform_device_count``
(1 shard in smoke, the core-count divisor of C otherwise) and best-of-reps
timing, so the throughput baseline cannot drift with whatever XLA_FLAGS the
calling job exports.  Writes ``BENCH_cohort.json``; ``--smoke`` writes
``BENCH_cohort_smoke.json`` at tiny scale (CI harness):

    PYTHONPATH=src python -m benchmarks.cohort_sweep [--smoke]

``--algos`` runs the **local-algorithm axis** instead (DESIGN.md §12): at a
fixed high-skew federation and one cohort size, each registered local
algorithm (fedavg / fedprox / feddyn — a *static* trace constant, so one
``run_many`` grid over strategies × seeds per algorithm) races to the same
target loss, plus one feddyn × bounded-staleness row (the ROADMAP's
never-benchmarked interaction).  The ``ok`` gate asserts the paper-level
claim: at high non-IID skew a drift-correcting objective (fedprox or
feddyn) reaches target in fewer rounds than plain fedavg under DPP
selection.  Writes ``BENCH_algo.json`` / ``BENCH_algo_smoke.json``:

    PYTHONPATH=src python -m benchmarks.cohort_sweep --algos [--smoke]

This is a CPU harness, not a chip measurement: its children run on emulated
host devices with ``JAX_PLATFORMS=cpu`` pinned (``common.spawn_cpu_child``),
and every time it records is a CPU time.
"""

from __future__ import annotations

import argparse
import json
import os
import time

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_cohort.json")
SMOKE_OUT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_cohort_smoke.json"
)

FULL = dict(clients=16, n_c=48, feat=16, hidden=32, ncls=8, steps=2,
            rounds=40, lr=0.1, ks=(2, 4, 8, 16), seeds=2, reps=3, spawns=2)
SMOKE = dict(clients=8, n_c=12, feat=8, hidden=16, ncls=4, steps=2,
             rounds=6, lr=0.1, ks=(2, 8), seeds=1, reps=4, spawns=2)
STRATEGIES = ("fl-dp3s", "fedavg", "fedsae")

ALGO_OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_algo.json")
ALGO_SMOKE_OUT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_algo_smoke.json"
)
# high-skew regime (skew = probability mass on each client's two major
# classes): strong non-IID drift is where the drift-correcting objectives
# earn their keep — the ok gate below asserts exactly that
ALGO_FULL = dict(clients=16, n_c=8, feat=16, hidden=32, ncls=8, steps=32,
                 rounds=48, lr=1.0, k=4, seeds=3, reps=3, spawns=2,
                 skew=1.0, prox_mu=0.1, feddyn_alpha=0.05,
                 staleness_bound=2, scenario="heavy_tail")
ALGO_SMOKE = dict(clients=8, n_c=12, feat=8, hidden=16, ncls=4, steps=2,
                  rounds=6, lr=0.1, k=2, seeds=1, reps=2, spawns=2,
                  skew=0.9, prox_mu=0.1, feddyn_alpha=0.1,
                  staleness_bound=2, scenario="heavy_tail")


def _algo_rows(w: dict):
    """The algorithm axis: name -> FLConfig overrides.  The three registry
    algorithms race synchronously; the ``*_stale`` rows re-run fedavg and
    feddyn under bounded-staleness aggregation (feddyn × staleness is the
    ROADMAP's open interaction question — fedavg_stale is its control)."""
    stale = dict(staleness_bound=w["staleness_bound"], scenario=w["scenario"])
    return {
        "fedavg": dict(local_algo="fedavg"),
        "fedprox": dict(local_algo="fedprox", prox_mu=w["prox_mu"]),
        "feddyn": dict(local_algo="feddyn", feddyn_alpha=w["feddyn_alpha"]),
        "fedavg_stale": dict(local_algo="fedavg", **stale),
        "feddyn_stale": dict(local_algo="feddyn",
                             feddyn_alpha=w["feddyn_alpha"], **stale),
    }


def _pinned_devices(w: dict, smoke: bool) -> int:
    """Device count the child is pinned to: 1 in smoke (a deterministic
    harness check whatever the environment forces), else the largest divisor
    of C the physical cores can host."""
    if smoke:
        return 1
    cores = os.cpu_count() or 1
    c = w["clients"]
    return max(d for d in range(1, min(cores, c) + 1) if c % d == 0)


def _federation(w: dict):
    """Class-skewed non-IID clients over Gaussian class clusters: client c's
    labels concentrate on classes {c, c+1} mod ncls, so per-client mean
    features (the profiles) carry the skew the DPP kernel diversifies over.
    ``w['skew']`` (default 0.8) is the probability mass on the two major
    classes — the algorithm axis pushes it up for a high-drift regime."""
    import numpy as np

    rng = np.random.default_rng(7)
    c, n_c, feat, ncls = w["clients"], w["n_c"], w["feat"], w["ncls"]
    skew = w.get("skew", 0.8)
    means = rng.normal(scale=2.0, size=(ncls, feat)).astype(np.float32)
    xs = np.empty((c, n_c, feat), np.float32)
    ys = np.empty((c, n_c), np.int32)
    for ci in range(c):
        major = np.asarray([ci % ncls, (ci + 1) % ncls])
        probs = np.full((ncls,), (1.0 - skew) / ncls)
        probs[major] += skew / 2.0
        labels = rng.choice(ncls, size=(n_c,), p=probs / probs.sum())
        xs[ci] = means[labels] + rng.normal(size=(n_c, feat)).astype(np.float32)
        ys[ci] = labels
    return xs, ys, means


def _child_run(w: dict, n_shards: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dpp as dpp_lib
    from repro.core import make_strategy
    from repro.core import similarity as similarity_lib
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == n_shards, (jax.device_count(), n_shards)
    c, ncls = w["clients"], w["ncls"]
    xs_np, ys_np, _ = _federation(w)
    xs, ys = jnp.asarray(xs_np), jnp.asarray(ys_np)

    def loss_fn(p, x, y):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"])
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    def init_params(seed):
        rng = np.random.default_rng(100 + seed)
        return {
            "w1": jnp.asarray(
                0.1 * rng.normal(size=(w["feat"], w["hidden"])).astype(np.float32)
            ),
            "b1": jnp.zeros((w["hidden"],), jnp.float32),
            "w2": jnp.asarray(
                0.1 * rng.normal(size=(w["hidden"], ncls)).astype(np.float32)
            ),
            "b2": jnp.zeros((ncls,), jnp.float32),
        }

    mesh = make_client_mesh(n_shards)
    strategies = tuple(make_strategy(s) for s in STRATEGIES)

    by_k = {}
    throughput = {}
    for k in w["ks"]:
        cfg = engine.FLConfig(
            num_clients=c, clients_per_round=k, local_epochs=w["steps"],
            lr=w["lr"], rounds=w["rounds"], eval_every=10 * w["rounds"],
            num_classes=ncls, seed=0, cohort_cap=k,
        )
        states = []
        for seed in range(w["seeds"]):
            params = init_params(seed)
            profiles = xs.mean(axis=1)
            kernel = similarity_lib.kernel_from_profiles(profiles)
            losses0 = jax.jit(jax.vmap(loss_fn, in_axes=(None, 0, 0)))(
                params, xs, ys
            )
            for si, strat in enumerate(strategies):
                eig = (
                    dpp_lib.kdpp_sampler_state(kernel, k)
                    if getattr(strat, "uses_spectral_cache", False)
                    else dpp_lib.identity_sampler_state(c, k)
                )
                states.append(engine.init_server_state(
                    cfg, params, loss_fn, None, xs, ys, strategy=strat,
                    strategy_index=si, key=jax.random.key(1000 * seed + si),
                    profiles=profiles, kernel=kernel, losses=losses0,
                    eig_state=eig,
                ))
        stacked = engine.stack_states(states)
        rf = engine.make_round_fn(cfg, loss_fn, strategies, mesh=mesh)
        out = engine.run_many(rf, stacked, w["rounds"], mesh=mesh)
        jax.block_until_ready(out)  # compile + warm
        best = float("inf")
        for _ in range(w["reps"]):
            t0 = time.perf_counter()
            _, outs = engine.run_many(rf, stacked, w["rounds"], mesh=mesh)
            jax.block_until_ready(outs)
            best = min(best, time.perf_counter() - t0)
        throughput[str(k)] = len(states) * w["rounds"] / best

        runs = engine.unstack_outputs(outs)
        floors = [float(np.min(r["loss"])) for r in runs]
        target = max(floors)
        per_strategy = {}
        for si, name in enumerate(STRATEGIES):
            arm = [runs[seed * len(strategies) + si]
                   for seed in range(w["seeds"])]
            rtt = []
            for r in arm:
                best_loss = np.minimum.accumulate(
                    np.asarray(r["loss"], np.float64)
                )
                hit = np.nonzero(best_loss <= target)[0]
                rtt.append(int(hit[0]) + 1 if hit.size else w["rounds"])
            per_strategy[name] = dict(
                rounds_to_target=float(np.mean(rtt)),
                final_loss=float(np.mean([np.min(r["loss"]) for r in arm])),
                mean_gemd=float(np.mean([np.mean(r["gemd"]) for r in arm])),
            )
        by_k[str(k)] = dict(k=k, target_loss=target, per_strategy=per_strategy)
    return dict(
        by_k=by_k, throughput_rounds_per_sec=throughput,
        workload=dict(w, model="mlp(2-layer)", strategies=STRATEGIES,
                      n_shards=n_shards),
    )


def _algo_child_run(w: dict, n_shards: int) -> dict:
    """The local-algorithm axis (DESIGN.md §12): per algorithm row — a
    *static* trace constant (feddyn even changes the ServerState pytree), so
    the rows are a Python loop — one ``run_many`` grid over strategies ×
    seeds through the capacity-slot engine, all rows on the SAME federation,
    params, and selection key streams (cohorts are algorithm-independent, so
    the races differ only in the local objective)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dpp as dpp_lib
    from repro.core import make_strategy
    from repro.core import similarity as similarity_lib
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == n_shards, (jax.device_count(), n_shards)
    c, ncls, k = w["clients"], w["ncls"], w["k"]
    xs_np, ys_np, _ = _federation(w)
    xs, ys = jnp.asarray(xs_np), jnp.asarray(ys_np)

    def loss_fn(p, x, y):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"])
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    def init_params(seed):
        rng = np.random.default_rng(100 + seed)
        return {
            "w1": jnp.asarray(
                0.1 * rng.normal(size=(w["feat"], w["hidden"])).astype(np.float32)
            ),
            "b1": jnp.zeros((w["hidden"],), jnp.float32),
            "w2": jnp.asarray(
                0.1 * rng.normal(size=(w["hidden"], ncls)).astype(np.float32)
            ),
            "b2": jnp.zeros((ncls,), jnp.float32),
        }

    mesh = make_client_mesh(n_shards)
    strategies = tuple(make_strategy(s) for s in STRATEGIES)

    rows = {}
    throughput = {}
    curves = {}  # row -> strategy -> list over seeds of best-loss curves
    for row_name, overrides in _algo_rows(w).items():
        # capacity-slot compaction assumes a synchronous cohort — the stale
        # rows run resident-mode instead (the engine rejects the combo), and
        # the staleness ring's per-shard layout doesn't stack into a
        # run_many grid, so stale arms run as sequential run_scanned calls
        # (one compiled program, async_bench-style)
        stale = "staleness_bound" in overrides
        cap = None if stale else k
        cfg = engine.FLConfig(
            num_clients=c, clients_per_round=k, local_epochs=w["steps"],
            lr=w["lr"], rounds=w["rounds"], eval_every=10 * w["rounds"],
            num_classes=ncls, seed=0, cohort_cap=cap, **overrides,
        )
        states = []
        for seed in range(w["seeds"]):
            params = init_params(seed)
            profiles = xs.mean(axis=1)
            kernel = similarity_lib.kernel_from_profiles(profiles)
            losses0 = jax.jit(jax.vmap(loss_fn, in_axes=(None, 0, 0)))(
                params, xs, ys
            )
            for si, strat in enumerate(strategies):
                eig = (
                    dpp_lib.kdpp_sampler_state(kernel, k)
                    if getattr(strat, "uses_spectral_cache", False)
                    else dpp_lib.identity_sampler_state(c, k)
                )
                states.append(engine.init_server_state(
                    cfg, params, loss_fn, None, xs, ys, strategy=strat,
                    strategy_index=si, key=jax.random.key(1000 * seed + si),
                    profiles=profiles, kernel=kernel, losses=losses0,
                    eig_state=eig, mesh=mesh if stale else None,
                ))
        rf = engine.make_round_fn(cfg, loss_fn, strategies, mesh=mesh)
        if stale:
            def grid(states=states, rf=rf):
                return [engine.run_scanned(rf, s, w["rounds"], mesh=mesh)[1]
                        for s in states]

            runs = grid()  # compile + warm
            jax.block_until_ready(runs)
            best = float("inf")
            for _ in range(w["reps"]):
                t0 = time.perf_counter()
                runs = grid()
                jax.block_until_ready(runs)
                best = min(best, time.perf_counter() - t0)
        else:
            stacked = engine.stack_states(states)
            out = engine.run_many(rf, stacked, w["rounds"], mesh=mesh)
            jax.block_until_ready(out)  # compile + warm
            best = float("inf")
            for _ in range(w["reps"]):
                t0 = time.perf_counter()
                _, outs = engine.run_many(rf, stacked, w["rounds"], mesh=mesh)
                jax.block_until_ready(outs)
                best = min(best, time.perf_counter() - t0)
            runs = engine.unstack_outputs(outs)
        throughput[row_name] = len(states) * w["rounds"] / best
        curves[row_name] = {}
        rows[row_name] = dict(config=dict(overrides),
                              stale="staleness_bound" in overrides)
        for si, name in enumerate(STRATEGIES):
            arm = [runs[seed * len(strategies) + si]
                   for seed in range(w["seeds"])]
            curves[row_name][name] = [
                np.minimum.accumulate(np.asarray(r["loss"], np.float64))
                for r in arm
            ]

    # common per-strategy target: the loss floor every SYNCHRONOUS algorithm
    # row reaches (stale rows race against the same bar, but don't set it —
    # staleness legitimately trades convergence for wall clock)
    sync_rows = [r for r, rec in rows.items() if not rec["stale"]]
    per_row = {}
    for row_name in rows:
        per_strategy = {}
        for name in STRATEGIES:
            target = max(
                float(cur[-1])
                for r in sync_rows for cur in curves[r][name]
            )
            rtt = []
            for cur in curves[row_name][name]:
                hit = np.nonzero(cur <= target)[0]
                rtt.append(int(hit[0]) + 1 if hit.size else w["rounds"])
            per_strategy[name] = dict(
                target_loss=target,
                rounds_to_target=float(np.mean(rtt)),
                final_loss=float(np.mean([cur[-1]
                                          for cur in curves[row_name][name]])),
            )
        per_row[row_name] = dict(rows[row_name], per_strategy=per_strategy)

    return dict(
        rows=per_row, throughput_rounds_per_sec=throughput,
        workload=dict(w, model="mlp(2-layer)", strategies=STRATEGIES,
                      n_shards=n_shards),
    )


def _spawn(w: dict, n_shards: int, algos: bool = False) -> dict:
    from benchmarks import common

    return common.spawn_cpu_child(
        "benchmarks.cohort_sweep",
        dict(workload=w, n_shards=n_shards, algos=algos), n_shards, timeout=1800,
    )


def _main_algos(smoke: bool):
    """Driver for the local-algorithm axis: spawn, merge throughput
    best-of, evaluate the drift-correction gate, write BENCH_algo[_smoke]."""
    from benchmarks import common

    t0 = time.time()
    w = ALGO_SMOKE if smoke else ALGO_FULL
    n_shards = _pinned_devices(w, smoke)
    res = _spawn(w, n_shards, algos=True)
    for _ in range(w.get("spawns", 1) - 1):
        again = _spawn(w, n_shards, algos=True)
        for rn, rps in again["throughput_rounds_per_sec"].items():
            res["throughput_rounds_per_sec"][rn] = max(
                res["throughput_rounds_per_sec"][rn], rps
            )
    primary = "fl-dp3s"
    rtt = {rn: rec["per_strategy"][primary]["rounds_to_target"]
           for rn, rec in res["rows"].items()}
    # the gate (ISSUE 8 acceptance): at high non-IID skew, a drift-correcting
    # local objective beats plain fedavg to target under DPP selection — and
    # the feddyn × staleness row exists and converges to a finite loss
    win = min(rtt["fedprox"], rtt["feddyn"]) < rtt["fedavg"]
    stale_row = res["rows"].get("feddyn_stale")
    stale_ok = (
        stale_row is not None
        and all(v["final_loss"] == v["final_loss"]  # not NaN
                for v in stale_row["per_strategy"].values())
    )
    ok = bool(win and stale_ok)
    for rn in ("fedavg", "fedprox", "feddyn", "fedavg_stale", "feddyn_stale"):
        rec = res["rows"][rn]
        row = "  ".join(
            f"{n}={rec['per_strategy'][n]['rounds_to_target']:.1f}r"
            for n in STRATEGIES
        )
        print(f"  algo_axis {rn:13s} {row} "
              f"({res['throughput_rounds_per_sec'][rn]:.1f} scan-rounds/s)")
    payload = dict(
        bench="local_algo_rounds_to_target",
        smoke=smoke,
        host_cores=os.cpu_count() or 1,
        primary_strategy=primary,
        ok=ok,
        total_s=round(time.time() - t0, 2),
        **res,
    )
    out_path = ALGO_SMOKE_OUT_PATH if smoke else ALGO_OUT_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(common.csv_line(
        "algo_axis",
        0.0,
        f"{primary} rounds-to-target: "
        + " ".join(f"{rn}={rtt[rn]:.1f}" for rn in sorted(rtt))
        + f" ok={ok}",
    ))
    print(f"wrote {os.path.abspath(out_path)}")
    # the gate only bites at full scale — smoke rounds are too few for a
    # meaningful race (the smoke JSON still records ok for the harness test)
    if not smoke and not ok:
        raise SystemExit(1)
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI harness check)")
    ap.add_argument("--algos", action="store_true",
                    help="local-algorithm axis (DESIGN.md §12) instead of "
                         "the cohort-size sweep")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child is not None:
        spec = json.loads(args.child)
        if spec.get("algos"):
            print(json.dumps(_algo_child_run(spec["workload"],
                                             spec["n_shards"])))
        else:
            spec["workload"]["ks"] = tuple(spec["workload"]["ks"])
            print(json.dumps(_child_run(spec["workload"], spec["n_shards"])))
        return None

    if args.algos:
        return _main_algos(smoke=args.smoke)

    from benchmarks import common

    t0 = time.time()
    w = SMOKE if args.smoke else FULL
    n_shards = _pinned_devices(w, args.smoke)
    res = _spawn(w, n_shards)
    # convergence results are deterministic across spawns; throughput is
    # best-of across independent children (shared-container scheduling noise
    # swings single child measurements — same treatment as shard_bench)
    for _ in range(w.get("spawns", 1) - 1):
        again = _spawn(w, n_shards)
        for kk, rps in again["throughput_rounds_per_sec"].items():
            res["throughput_rounds_per_sec"][kk] = max(
                res["throughput_rounds_per_sec"][kk], rps
            )
    for kk in sorted(res["by_k"], key=int):
        rec = res["by_k"][kk]
        row = "  ".join(
            f"{n}={rec['per_strategy'][n]['rounds_to_target']:.1f}r"
            for n in STRATEGIES
        )
        print(f"  cohort_sweep k={int(kk):3d} target={rec['target_loss']:.4f} "
              f"{row} ({res['throughput_rounds_per_sec'][kk]:.1f} "
              f"scan-rounds/s)")
    payload = dict(
        bench="cohort_size_rounds_to_target",
        smoke=args.smoke,
        host_cores=os.cpu_count() or 1,
        total_s=round(time.time() - t0, 2),
        **res,
    )
    out_path = SMOKE_OUT_PATH if args.smoke else OUT_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    ks = sorted(res["by_k"], key=int)
    dpp_rtt = {k: res["by_k"][k]["per_strategy"]["fl-dp3s"]["rounds_to_target"]
               for k in ks}
    print(common.csv_line(
        "cohort_sweep",
        0.0,
        "fl-dp3s rounds-to-target by k: "
        + " ".join(f"k{k}={dpp_rtt[k]:.1f}" for k in ks),
    ))
    print(f"wrote {os.path.abspath(out_path)}")
    return payload


if __name__ == "__main__":
    main()
