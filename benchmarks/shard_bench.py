"""Mesh-sharded cohort execution benchmark: rounds/sec vs device count.

Measures the scanned federation engine (DESIGN.md §8) on a selection-light,
full-participation workload (k = C, uniform selection, tiny MLP) where the
per-round cost is the cohort's local updates — the regime where the client
mesh axis should scale.  Each device count runs in its OWN subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be set
before jax initialises), so the 1-device baseline engine gets the whole
machine and every sharded config gets exactly its N virtual devices.

The max-device child also re-runs the single-device engine in-process and
asserts the sharded path picked **bit-identical cohorts** with fp32-close
final params (the parity contract of ``tests/test_shard_engine.py``).

A second sweep measures **capacity-slot scheduling** (DESIGN.md §8): at a
fixed device count, k runs from 1 to C comparing slotted
(``cohort_cap = k`` ⇒ ``cap = min(C_loc, k)`` local updates per shard)
against unslotted (``C_loc`` updates whatever the cohort) rounds/sec — the
expected win is ≈ C_loc/cap at small k, because slotting removes *work*,
not just parallelism.  The sweep child asserts slotted-vs-unslotted parity
(bit-identical cohorts, fp32-close params) at every k.

Writes ``BENCH_shard.json`` (repo root).  Two hardware-aware gates:
the ≥2x @ 8 devices *scaling* gate is enforced only when the host has ≥8
physical cores (virtual devices are threads, so wall-clock speedup is
capped at the core count — a 2-core container cannot express an 8-way win
and records ``gate_enforced: false``); the ≥2x *slot* gate (some cap ≤
C_loc/2 must run ≥2x the unslotted round) is enforced whenever the host
has at least as many cores as the sweep's device count, since a work
reduction shows up at any core count that can host the mesh.  Parity is
always enforced.  ``--smoke`` runs tiny shapes (device counts (1, 8) plus
a small-k slot case) with no perf gates and writes a separate
``BENCH_shard_smoke.json`` (CI harness + regression-check input):

    PYTHONPATH=src python -m benchmarks.shard_bench [--smoke]

This is a CPU harness, not a chip measurement: its children run on emulated
host devices with ``JAX_PLATFORMS=cpu`` pinned (``common.spawn_cpu_child``),
and every time it records is a CPU time.
"""

from __future__ import annotations

import argparse
import json
import os
import time

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_shard.json")
SMOKE_OUT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_shard_smoke.json"
)

# selection-light full-participation workloads (k = C): per-round cost is the
# cohort's local SGD scans, psum'd FedAvg is the only cross-device traffic.
# spawns = independent child processes per device count (best-of across
# them): shared-container scheduling noise swings single measurements ~2x
FULL = dict(clients=8, n_c=64, feat=64, hidden=128, steps=32, rounds=10,
            reps=6, spawns=2, device_counts=(1, 2, 4, 8))
SMOKE = dict(clients=8, n_c=16, feat=16, hidden=32, steps=4, rounds=4,
             reps=2, spawns=1, device_counts=(1, 8))
# capacity-slot k-sweep: C_loc = clients/devices residents per shard; the
# slotted round should run ≈ C_loc/min(C_loc, k)× faster than unslotted
FULL_KSWEEP = dict(clients=16, n_c=64, feat=64, hidden=128, steps=32,
                   rounds=10, reps=4, spawns=2, devices=2, ks=(1, 2, 4, 8, 16))
SMOKE_KSWEEP = dict(clients=16, n_c=16, feat=16, hidden=32, steps=4,
                    rounds=4, reps=2, spawns=1, devices=2, ks=(2, 16))
TARGET_SPEEDUP = 2.0
GATE_DEVICES = 8
GATE_MIN_CORES = 8
SLOT_TARGET_SPEEDUP = 2.0  # at some cap <= C_loc/2


# ----------------------------------------------------------------- child


def _mlp_workload(w: dict):
    """Shared tiny-MLP federation for the bench children."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c, n_c, feat, hid = w["clients"], w["n_c"], w["feat"], w["hidden"]
    ncls = 10
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(c, n_c, feat)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, ncls, size=(c, n_c)), jnp.int32)
    params = {
        "w1": jnp.asarray(0.05 * rng.normal(size=(feat, hid)).astype(np.float32)),
        "b1": jnp.zeros((hid,), jnp.float32),
        "w2": jnp.asarray(0.05 * rng.normal(size=(hid, ncls)).astype(np.float32)),
        "b2": jnp.zeros((ncls,), jnp.float32),
    }

    def loss_fn(p, x, y):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"])
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    return loss_fn, xs, ys, params, ncls


def _timed_run(round_fn, state, rounds: int, reps: int):
    """Warm (compile) once, then best-of-``reps`` wall time for one scanned
    run.  Returns ``(best_seconds, (final_state, outputs))`` — shared by
    both bench children so they measure the identical protocol."""
    import jax

    from repro.fl import engine

    out = engine.run_scanned(round_fn, state, rounds)
    jax.block_until_ready(out)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = engine.run_scanned(round_fn, state, rounds)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def _parity(ref, got) -> dict:
    """The bench parity contract (mirrors tests/test_{shard,slot}_engine.py):
    bit-identical cohorts, fp32-close final params.  ``ref``/``got`` are
    ``(final_state, outputs)`` pairs from :func:`_timed_run`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cohorts_ok = bool(np.array_equal(
        np.asarray(ref[1]["selected"]), np.asarray(got[1]["selected"])
    ))
    pdiff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(ref[0].params),
                        jax.tree_util.tree_leaves(got[0].params))
    )
    return dict(
        cohorts_bit_identical=cohorts_ok,
        max_param_diff=pdiff,
        ok=bool(cohorts_ok and pdiff < 1e-5),
    )


def _child(devices: int, w: dict, check_parity: bool) -> dict:
    import jax

    from repro.core import selection as selection_lib
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == devices, (jax.device_count(), devices)
    c = w["clients"]
    loss_fn, xs, ys, params, ncls = _mlp_workload(w)

    cfg = engine.FLConfig(
        num_clients=c, clients_per_round=c, local_epochs=w["steps"], lr=0.02,
        rounds=w["rounds"], eval_every=10 * w["rounds"], num_classes=ncls,
        seed=0,
    )
    strat = selection_lib.UniformSelection()
    state = engine.init_server_state(
        cfg, params, loss_fn, None, xs, ys, strategy=strat,
        profiles=xs.mean(axis=1),
    )
    mesh = make_client_mesh(devices) if devices > 1 else None
    round_fn = engine.make_round_fn(cfg, loss_fn, (strat,), mesh=mesh)
    rounds = w["rounds"]
    # lay the state out ONCE, outside the timed region — the measurement is
    # steady-state rounds/sec, not the one-time host->mesh transfer
    run_state = (
        engine.shard_server_state(state, mesh) if mesh is not None else state
    )

    wall, run = _timed_run(round_fn, run_state, rounds, w["reps"])
    rec = dict(devices=devices, wall_s=wall, rounds_per_sec=rounds / wall)

    if check_parity and mesh is not None:
        ref_fn = engine.make_round_fn(cfg, loss_fn, (strat,))
        rec["parity"] = _parity(engine.run_scanned(ref_fn, state, rounds), run)
    return rec


def _slot_child(devices: int, w: dict) -> dict:
    """Capacity-slot k-sweep: slotted vs unslotted sharded rounds/sec.

    One mesh, one state; for each cohort size k the same federation runs
    through the unslotted sharded round (C_loc local updates per shard) and
    the slot-compacted round (cohort_cap = k ⇒ cap = min(C_loc, k)).
    Selection is identical by construction, so parity is asserted on every
    k — the speedup must come purely from skipping zero-weight updates.
    """
    import dataclasses

    import jax

    from repro.core import selection as selection_lib
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == devices, (jax.device_count(), devices)
    c = w["clients"]
    loss_fn, xs, ys, params, ncls = _mlp_workload(w)
    mesh = make_client_mesh(devices)
    c_loc = c // devices
    rounds = w["rounds"]
    strat = selection_lib.UniformSelection()
    by_k = {}
    for k in w["ks"]:
        cfg = engine.FLConfig(
            num_clients=c, clients_per_round=k, local_epochs=w["steps"],
            lr=0.02, rounds=rounds, eval_every=10 * rounds, num_classes=ncls,
            seed=0,
        )
        state = engine.init_server_state(
            cfg, params, loss_fn, None, xs, ys, strategy=strat,
            profiles=xs.mean(axis=1),
        )
        run_state = engine.shard_server_state(state, mesh)
        rps, runs = {}, {}
        for name, cohort_cap in (("unslotted", None), ("slotted", k)):
            vcfg = dataclasses.replace(cfg, cohort_cap=cohort_cap)
            fn = engine.make_round_fn(vcfg, loss_fn, (strat,), mesh=mesh)
            best, runs[name] = _timed_run(fn, run_state, rounds, w["reps"])
            rps[name] = rounds / best
        by_k[str(k)] = dict(
            k=k, cap=min(c_loc, k),
            rounds_per_sec=rps,
            slot_speedup=rps["slotted"] / rps["unslotted"],
            parity=_parity(runs["unslotted"], runs["slotted"]),
        )
    return dict(devices=devices, clients=c, c_loc=c_loc, by_k=by_k)


# ---------------------------------------------------------------- parent


def _spawn_payload(devices: int, payload: dict) -> dict:
    from benchmarks import common

    return common.spawn_cpu_child("benchmarks.shard_bench", payload, devices)


def _spawn(devices: int, w: dict, check_parity: bool) -> dict:
    return _spawn_payload(
        devices, dict(devices=devices, workload=w, parity=check_parity)
    )


def _spawn_ksweep(w: dict) -> dict:
    return _spawn_payload(
        w["devices"],
        dict(mode="ksweep", devices=w["devices"], workload=w),
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no perf gate (CI harness check)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child is not None:
        spec = json.loads(args.child)
        if spec.get("mode") == "ksweep":
            print(json.dumps(_slot_child(spec["devices"], spec["workload"])))
        else:
            print(json.dumps(
                _child(spec["devices"], spec["workload"], spec["parity"])
            ))
        return None

    from benchmarks import common

    t0 = time.time()
    w = SMOKE if args.smoke else FULL
    kw = SMOKE_KSWEEP if args.smoke else FULL_KSWEEP
    cores = os.cpu_count() or 1
    max_dev = max(w["device_counts"])
    rows = {}
    for n in w["device_counts"]:
        rec = _spawn(n, w, check_parity=(n == max_dev))
        for _ in range(w.get("spawns", 1) - 1):
            again = _spawn(n, w, check_parity=False)
            if again["rounds_per_sec"] > rec["rounds_per_sec"]:
                if "parity" in rec:
                    again["parity"] = rec["parity"]
                rec = again
        rows[str(n)] = rec
        extra = ""
        if "parity" in rec:
            extra = (f"  parity_ok={rec['parity']['ok']} "
                     f"(cohorts={rec['parity']['cohorts_bit_identical']}, "
                     f"param_diff={rec['parity']['max_param_diff']:.2e})")
        print(f"  shard_bench devices={n}  "
              f"{rec['rounds_per_sec']:8.2f} rounds/s{extra}")

    base = rows["1"]["rounds_per_sec"]
    for rec in rows.values():
        rec["speedup_vs_1dev"] = rec["rounds_per_sec"] / base
        # virtual devices are host threads: ideal wall-clock speedup is
        # bounded by physical cores, whatever the device count
        rec["ideal_speedup"] = float(min(rec["devices"], cores))

    # ---- capacity-slot k-sweep (slotted vs unslotted at fixed devices) ----
    sweep = _spawn_ksweep(kw)
    for _ in range(kw.get("spawns", 1) - 1):
        again = _spawn_ksweep(kw)
        for kk, rec in sweep["by_k"].items():
            arec = again["by_k"][kk]
            for variant in rec["rounds_per_sec"]:
                rec["rounds_per_sec"][variant] = max(
                    rec["rounds_per_sec"][variant],
                    arec["rounds_per_sec"][variant],
                )
            rec["slot_speedup"] = (
                rec["rounds_per_sec"]["slotted"]
                / rec["rounds_per_sec"]["unslotted"]
            )
            # best-of applies to throughput only; parity must hold on EVERY
            # spawn that contributed a measurement
            p, ap = rec["parity"], arec["parity"]
            rec["parity"] = dict(
                cohorts_bit_identical=(p["cohorts_bit_identical"]
                                       and ap["cohorts_bit_identical"]),
                max_param_diff=max(p["max_param_diff"], ap["max_param_diff"]),
                ok=bool(p["ok"] and ap["ok"]),
            )
    c_loc = sweep["c_loc"]
    slot_parity_ok = all(r["parity"]["ok"] for r in sweep["by_k"].values())
    small_caps = [r for r in sweep["by_k"].values() if r["cap"] <= c_loc // 2]
    slot_speedup = max((r["slot_speedup"] for r in small_caps), default=0.0)
    # a slot win is WORK reduction, not parallelism: it shows at any core
    # count that can host the sweep's mesh (unlike the dev-scaling gate)
    slot_gate_enforced = (not args.smoke) and cores >= kw["devices"]
    for kk in sorted(sweep["by_k"], key=int):
        rec = sweep["by_k"][kk]
        print(f"  shard_bench slot k={kk:>3s} cap={rec['cap']}/{c_loc}  "
              f"unslotted={rec['rounds_per_sec']['unslotted']:8.2f} r/s  "
              f"slotted={rec['rounds_per_sec']['slotted']:8.2f} r/s  "
              f"speedup={rec['slot_speedup']:.2f}x "
              f"parity_ok={rec['parity']['ok']}")

    speedup = rows[str(max_dev)]["speedup_vs_1dev"]
    parity = rows[str(max_dev)].get("parity", {})
    gate_enforced = (not args.smoke) and cores >= GATE_MIN_CORES
    ok = bool(parity.get("ok", False)) and slot_parity_ok
    if gate_enforced:
        ok = ok and speedup >= TARGET_SPEEDUP
    if slot_gate_enforced:
        ok = ok and slot_speedup >= SLOT_TARGET_SPEEDUP

    payload = dict(
        bench="shard_engine_rounds_per_sec_vs_devices",
        smoke=args.smoke,
        workload=dict(w, model="mlp(2-layer)", selection="uniform-full-cohort"),
        host_cores=cores,
        target_speedup=TARGET_SPEEDUP,
        gate_devices=GATE_DEVICES,
        gate_enforced=gate_enforced,
        gate_note=(
            f"the >= {TARGET_SPEEDUP}x @ {GATE_DEVICES} virtual devices gate "
            f"needs >= {GATE_MIN_CORES} host cores (virtual devices are "
            "threads; speedup ceiling == cores); parity always enforced"
        ),
        speedup_at_max_devices=speedup,
        parity=parity,
        k_sweep=dict(
            sweep,
            workload=dict(kw, model="mlp(2-layer)", selection="uniform"),
            slot_target_speedup=SLOT_TARGET_SPEEDUP,
            slot_gate_enforced=slot_gate_enforced,
            slot_gate_note=(
                f"the >= {SLOT_TARGET_SPEEDUP}x slotted-vs-unslotted gate "
                f"(at some cap <= C_loc/2) needs >= {kw['devices']} host "
                "cores (slot compaction removes work, so it holds at any "
                "core count hosting the mesh); parity always enforced"
            ),
            best_small_cap_speedup=slot_speedup,
        ),
        ok=ok,
        by_devices=rows,
        total_s=round(time.time() - t0, 2),
    )
    out_path = SMOKE_OUT_PATH if args.smoke else OUT_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(common.csv_line(
        "shard_engine_scaling",
        0.0,
        f"speedup@{max_dev}dev={speedup:.2f}x cores={cores} "
        f"gate_enforced={gate_enforced} parity_ok={parity.get('ok')} "
        f"slot_speedup={slot_speedup:.2f}x "
        f"slot_gate_enforced={slot_gate_enforced} "
        f"slot_parity_ok={slot_parity_ok} ok={ok}",
    ))
    print(f"ok={ok}  wrote {os.path.abspath(out_path)}")
    if not ok:
        raise SystemExit(1)
    return payload


if __name__ == "__main__":
    main()
