"""Robust aggregation under injected faults: convergence-to-target vs mean.

Prices the fault-tolerance layer (DESIGN.md §11) on the same tiny-MLP
federation the other engine benches use: four arms share one federation,
one selection strategy, and one key chain —

  * ``clean``          — no faults, plain eq.-(6) mean (the PR-5/6 engine
                         path: ``faults=None`` skips every guard branch at
                         Python level, so this IS the existing program);
  * ``mean_faulty``    — the ``corrupt`` fault model (≈10% of delivered
                         updates NaN'd or norm-scaled garbage) aggregated
                         with plain mean: the unprotected control;
  * ``clipped_faulty`` — same faults, ``clipped_mean`` (norm-clip outliers
                         to the cohort-median threshold);
  * ``trimmed_faulty`` — same faults, ``trimmed_mean`` (reject outliers +
                         non-finite updates from the weighted sum).

The headline gate (full mode only): both robust arms must reach the common
target loss — the clean arm's loss floor × ``TARGET_SLACK`` — while the
mean arm must NOT (its best *finite* round mean stays above target; NaN
rounds are excluded NaN-aware, which only helps the control).  A second
gate proves quarantine feedback: under the deterministic ``lemons`` model
(persistently-garbage clients) with ``quarantine_rounds >= rounds``, every
lemon is selected at most once across the whole run (first pick flags it,
the counter excludes it thereafter), while a ``quarantine_rounds=0``
contrast arm keeps re-selecting them.  The zero-fault parity contract —
sharded clean vs single-device clean, bit-identical cohorts and fp32-close
params — is always enforced, smoke included.

Convergence/quarantine metrics are core-count independent, so those gates
arm on every full run (like async_bench's simulated-time gate); the
rounds/sec numbers are informational and only compared same-host by
check_regression.  Runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count`` (the sharded arm
needs a client mesh; the flag must precede jax init).  Writes
``BENCH_fault.json`` (repo root); ``--smoke`` runs tiny shapes with no
convergence gate and writes ``BENCH_fault_smoke.json`` (CI harness +
check_regression input):

    PYTHONPATH=src python -m benchmarks.fault_bench [--smoke]

This is a CPU harness, not a chip measurement: its children run on emulated
host devices with ``JAX_PLATFORMS=cpu`` pinned (``common.spawn_cpu_child``),
and every time it records is a CPU time.
"""

from __future__ import annotations

import argparse
import json
import os
import time

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_fault.json")
SMOKE_OUT_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_fault_smoke.json"
)

# one federation, four aggregation arms + a quarantine pair.  rounds is
# sized so the clean arm's loss floor is well separated from the mean arm's
# corrupted trajectory (garbage updates are 50x-norm deltas: one hit throws
# plain mean far off the descent path, and k=8 of C=16 at 10% corruption
# hits most rounds)
FULL = dict(clients=16, n_c=32, feat=16, hidden=32, steps=3, k=8, devices=4,
            rounds=40, lr=0.1, reps=2)
SMOKE = dict(clients=8, n_c=8, feat=8, hidden=16, steps=2, k=4, devices=2,
             rounds=8, lr=0.1, reps=1)
FAULT_MODEL = "corrupt"      # ~10% of delivered updates NaN/garbage
LEMON_MODEL = "lemons"       # deterministic persistently-bad clients
TARGET_SLACK = 1.10          # target = clean loss floor x slack


# ----------------------------------------------------------------- child


def _teacher_workload(w: dict):
    """Tiny-MLP federation with LEARNABLE labels (a random linear teacher).

    ``shard_bench._mlp_workload`` labels are random, so 40 rounds barely
    move the loss and a multiplicative target can't separate the arms; here
    the clean arm descends well below init, giving the corrupted-mean
    control real room to fail the target."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c, n_c, feat, hid = w["clients"], w["n_c"], w["feat"], w["hidden"]
    ncls = 10
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(c, n_c, feat)).astype(np.float32)
    teacher = rng.normal(size=(feat, ncls)).astype(np.float32)
    ys = np.argmax(xs.reshape(-1, feat) @ teacher, -1).reshape(c, n_c)
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

    return loss_fn, jnp.asarray(xs), jnp.asarray(ys, jnp.int32), params, ncls


def _child(w: dict) -> dict:
    import jax
    import numpy as np

    from benchmarks.shard_bench import _parity, _timed_run
    from repro.core import selection as selection_lib
    from repro.fl import engine, faults
    from repro.launch.mesh import make_client_mesh

    assert jax.device_count() == w["devices"], (jax.device_count(), w)
    loss_fn, xs, ys, params, ncls = _teacher_workload(w)
    mesh = make_client_mesh(w["devices"])
    strat = selection_lib.UniformSelection()
    base = dict(
        num_clients=w["clients"], clients_per_round=w["k"],
        local_epochs=w["steps"], lr=w["lr"], rounds=w["rounds"],
        eval_every=10 * w["rounds"], num_classes=ncls, seed=0,
    )

    def run(use_mesh=None, **kw):
        cfg = engine.FLConfig(**dict(base, **kw))
        state = engine.init_server_state(
            cfg, params, loss_fn, None, xs, ys, strategy=strat,
            profiles=xs.mean(axis=1), mesh=use_mesh,
        )
        rf = engine.make_round_fn(cfg, loss_fn, (strat,), mesh=use_mesh)
        secs, (st, outs) = _timed_run(rf, state, w["rounds"], w["reps"])
        return secs, st, jax.tree_util.tree_map(np.asarray, outs)

    arms = {}
    kept = {}
    arm_cfgs = dict(
        clean=dict(),
        mean_faulty=dict(faults=FAULT_MODEL, aggregator="mean"),
        clipped_faulty=dict(faults=FAULT_MODEL, aggregator="clipped_mean"),
        trimmed_faulty=dict(faults=FAULT_MODEL, aggregator="trimmed_mean"),
    )
    for name, kw in arm_cfgs.items():
        secs, st, outs = run(use_mesh=mesh, **kw)
        kept[name] = (st, outs)
        row = dict(
            rounds_per_sec=w["rounds"] / secs,
            best_finite_loss=(float(np.nanmin(outs["loss"]))
                              if np.isfinite(outs["loss"]).any() else None),
            final_loss=float(outs["loss"][-1]),
        )
        if "survivors" in outs:
            row.update(
                mean_survivors=float(np.mean(outs["survivors"])),
                flagged_total=int(np.sum(outs["flagged"])),
                identity_rounds=int(np.sum(outs["identity_round"])),
            )
        arms[name] = row

    # zero-fault parity: the sharded clean arm vs the single-device engine
    _, st1, outs1 = run(use_mesh=None)
    parity = _parity((st1, outs1), kept["clean"])

    # quarantine: deterministic lemons + long cooldown -> each lemon picked
    # at most once; the cooldown-0 contrast keeps re-selecting them.  Runs
    # SINGLE-DEVICE on purpose: the guard's norm median is shard-local
    # (DESIGN.md §11 — validation happens inside the shard_map, before the
    # psum), so a shard whose round cohort is a single lemon has no clean
    # reference scale and can miss the flag; the single-device guard sees
    # the whole cohort, which is the regime the quarantine property is
    # defined in
    model = faults.get_fault_model(LEMON_MODEL)
    lemons = np.nonzero(np.asarray(faults.lemon_mask(model, w["clients"])))[0]

    def lemon_picks(outs):
        sel = np.asarray(outs["selected"]).reshape(-1)
        return {int(c): int(np.sum(sel == c)) for c in lemons}

    _, _, out_q = run(use_mesh=None, faults=LEMON_MODEL,
                      aggregator="trimmed_mean",
                      quarantine_rounds=10 * w["rounds"])
    _, _, out_nq = run(use_mesh=None, faults=LEMON_MODEL,
                       aggregator="trimmed_mean", quarantine_rounds=0)
    picks_q = lemon_picks(out_q)
    picks_nq = lemon_picks(out_nq)
    quarantine = dict(
        lemons=[int(c) for c in lemons],
        picks_with_quarantine=picks_q,
        picks_without_quarantine=picks_nq,
        max_picks_with_quarantine=max(picks_q.values()),
        max_picks_without_quarantine=max(picks_nq.values()),
    )
    return dict(arms=arms, parity=parity, quarantine=quarantine)


# ---------------------------------------------------------------- parent


def _spawn(w: dict) -> dict:
    from benchmarks import common

    return common.spawn_cpu_child("benchmarks.fault_bench", w, w["devices"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no convergence gate (CI harness check)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child is not None:
        print(json.dumps(_child(json.loads(args.child))))
        return None

    from benchmarks import common

    t0 = time.time()
    w = SMOKE if args.smoke else FULL
    res = _spawn(w)

    arms = res["arms"]
    clean_floor = arms["clean"]["best_finite_loss"]
    target = clean_floor * TARGET_SLACK
    for name, row in arms.items():
        best = row["best_finite_loss"]
        best_s = f"{best:.4f}" if best is not None else "all-NaN"
        extra = (f" survivors={row['mean_survivors']:.1f} "
                 f"flagged={row['flagged_total']} "
                 f"identity={row['identity_rounds']}"
                 if "mean_survivors" in row else "")
        print(f"  fault_bench {name:15s} best_loss={best_s} "
              f"({row['rounds_per_sec']:6.2f} rounds/s){extra}")
    print(f"  fault_bench target_loss={target:.4f} "
          f"(clean floor {clean_floor:.4f} x {TARGET_SLACK})")

    q = res["quarantine"]
    print(f"  fault_bench lemons={q['lemons']}: "
          f"max picks {q['max_picks_with_quarantine']} with quarantine, "
          f"{q['max_picks_without_quarantine']} without")

    def reaches(row):
        return row["best_finite_loss"] is not None and \
            row["best_finite_loss"] <= target

    parity = res["parity"]
    gate_enforced = not args.smoke
    ok = bool(parity.get("ok", False))
    if gate_enforced:
        ok = ok and reaches(arms["trimmed_faulty"])
        ok = ok and reaches(arms["clipped_faulty"])
        ok = ok and not reaches(arms["mean_faulty"])
        ok = ok and q["max_picks_with_quarantine"] <= 1
        ok = ok and q["max_picks_without_quarantine"] > 1

    payload = dict(
        bench="fault_robust_aggregation_to_target",
        smoke=args.smoke,
        workload=dict(w, model="mlp(2-layer)", selection="uniform",
                      fault_model=FAULT_MODEL, lemon_model=LEMON_MODEL),
        host_cores=os.cpu_count() or 1,
        target_loss=target,
        target_slack=TARGET_SLACK,
        gate_enforced=gate_enforced,
        gate_note=(
            "robust arms (clipped_mean, trimmed_mean) must reach the clean "
            f"loss floor x {TARGET_SLACK} under {FAULT_MODEL} faults while "
            "plain mean must not; quarantined lemons picked <= 1x vs "
            "repeats without quarantine; convergence metrics are core-count "
            "independent so the gate arms on every full run; zero-fault "
            "parity always enforced"
        ),
        parity=parity,
        arms=arms,
        quarantine=q,
        ok=ok,
        total_s=round(time.time() - t0, 2),
    )
    out_path = SMOKE_OUT_PATH if args.smoke else OUT_PATH
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(common.csv_line(
        "fault_robust_vs_mean",
        0.0,
        f"trimmed_ok={reaches(arms['trimmed_faulty'])} "
        f"mean_degrades={not reaches(arms['mean_faulty'])} "
        f"parity_ok={parity.get('ok')} "
        f"gate_enforced={gate_enforced} ok={ok}",
    ))
    print(f"ok={ok}  wrote {os.path.abspath(out_path)}")
    if not ok:
        raise SystemExit(1)
    return payload


if __name__ == "__main__":
    main()
