"""Bring-up smoke run of the system's main paths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the client-mesh path only

One process runs every phase, in this order, and each phase prints one line:

1. ``device`` — ``jax.devices()[0].platform`` must be ``tpu``.
2. ``kernel`` (one line per kernel) — every Pallas kernel once at real
   widths, compiled for the chip (``interpret=False``), against its jnp
   reference in ``repro/kernels/*/ref.py``.
3. ``federation`` — the paper's fl-dp3s federation at paper scale (C=100,
   10 clients per round, 600 samples each, the (16, 32)/128 CNN, E=2
   full-batch GD) through ``init_server_state`` -> ``make_round_fn`` ->
   ``run_scanned`` with the eq.-(14) kernel built by the fused Pallas
   pipeline.
4. ``serving`` — smollm-360m at its published widths in bf16 through
   ``launch.serve.run_continuous`` with flash-decode.

With ``--four-chips`` it runs only the client-mesh federation
(``make_client_mesh(4)``, ``cohort_cap=10``), the same federation on one
device in the same process, and the mesh federation once more with one
planted fault (two clients' data swapped across shards), and checks that the
bound separates the mesh run from the fault.

The last stdout line is ``{"ok": true, "device": {...}}``.  A failed check
exits non-zero before it; there is no CPU fallback and no interpret mode.
Weights and data are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Real widths.  Kernels: the paper's C and profile width Q = FC-1 width
# (and a ragged C), smollm-360m attention, rwkv6-7b's WKV.
C, C_RAGGED, F = 100, 1000, 128
B, S, H, HK, HD = 8, 2048, 15, 5, 64
RWKV_T, RWKV_H = 512, 64
# the paper CNN's first stage in the benchmark: 8 lockstep federations, 600
# samples of 28x28, 16 channels, 5x5 kernel
LOCKSTEP, SAMPLES, HW, CONV1 = 8, 600, 28, 16
ROUNDS = 10  # federation rounds, one and four chips
MESH_CHIPS, COHORT_CAP = 4, 10
# smollm-360m serving: requests, slots, prompt length, largest budget
REQUESTS, SLOTS, PROMPT, MAX_BUDGET = 16, 8, 128, 64

# Error measure for every kernel: max |kernel - reference| over
# max(1, max |reference|).  Each tolerance sits beside its reason.
KERNEL_TOLS = {
    # f32 profiles, MXU at f32 contract precision: only the rounding of the
    # ‖a‖² + ‖b‖² − 2a·b expansion against the reference's direct differences
    "pairwise_sq_dists": 1e-5,
    # f32 throughout (distances, sqrt, min-max, SᵀS over C terms in [0, 1])
    "kernel_from_profiles": 1e-5,
    # f32 inputs, f32 accumulation
    "gram": 1e-5,
    # bf16 q/k/v and a bf16 output: one bf16 rounding (2^-9 relative) of the
    # output plus bf16 rounding of the softmax weights in the p·v matmul
    "flash_decode": 2e-2,
    "flash_attention": 2e-2,
    # bf16 r/k/v and a bf16 output rounded once (2^-9 relative to the output)
    "wkv6.y": 1e-2,
    # f32 state: 512 steps of f32 arithmetic, only the summation order differs
    "wkv6.state": 1e-4,
    # the same routing, and bf16 products exact in f32 in both: only the order
    # of the f32 sums (470,400 terms per weight) differs
    "conv_pool_bwd": 1e-5,
}
# Pallas vs jnp eq.-(14) kernel of the federation's own profiles, both f32.
# Clients' FC-1 means lie close together (squared norms ~1e3, smallest
# squared distances ~0.2), so f32 rounding of ‖a‖² + ‖b‖² − 2a·b is
# amplified ~5e3-fold before the sqrt: each f32 evaluation sits 2e-5 to
# 4e-5 from an f64 one (both are printed), and 1e-4 bounds their distance.
FED_KERNEL_TOL = 1e-4
# One device vs the four-device client mesh, params after ROUNDS rounds
# (max |diff| over max(1, max |param|)).  The two programs are fused
# differently (per-shard partials joined by one psum), so their f32 sums
# differ in the last bits, and this federation's training amplifies that
# (its round-2 loss spikes): exact-f32 CPU runs at 60 samples per client
# differ by 5.0e-6.  On the TPU every f32 conv and matmul multiplies
# operands rounded to bf16, which turns some last-bit differences into 2^-9
# ones: CPU runs with bf16-rounded operands differ by 4.6e-4 at 200 samples
# per client (4.4e-3 at 60), and a v5e 2x2 by 5.148649215698242e-4 at the
# paper's 600, the same in two runs.  A logic fault moves them much
# further: on the v5e 2x2, two clients' data swapped across shards from
# round 5 on moved them by 1.08e-2; in 60-sample CPU runs a swap from round
# 1 moved them by 0.59, one shard's partial dropped before the psum by
# 0.76, a selected slot's weight swapped with a padding slot's by 1.19.
# The bound holds this paper-scale run, ~5x above its rounding reading, and
# the run itself checks that a swap planted from round 1 exceeds it.
MESH_PARAM_TOL = 2.5e-3


def check(ok, msg: str) -> None:
    """Fail the run: exit non-zero with ``msg``, before the verdict line."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL {msg}")


def eq14_f64(profiles) -> np.ndarray:
    """The eq.-(14) DPP kernel in float64 on the host, from direct
    differences (no expansion, so no cancellation)."""
    f = np.asarray(profiles, np.float64)
    s0 = np.sqrt(((f[:, None, :] - f[None, :, :]) ** 2).sum(-1))
    s = 1.0 - (s0 - s0.min()) / (s0.max() - s0.min())
    return s.T @ s


def rel_err(got, want) -> tuple:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != reference {want.shape}")
    check(np.isfinite(got).all(), "non-finite kernel output")
    abs_err = float(np.abs(got - want).max())
    return abs_err, abs_err / max(1.0, float(np.abs(want).max()))


# ------------------------------------------------------------------ device


def device_phase(min_count: int):
    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu", f"no TPU: jax.devices()[0].platform = {d.platform!r}")
    check(len(devices) >= min_count, f"{len(devices)} devices, need {min_count}")
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devices)}")
    return devices


# ----------------------------------------------------------------- kernels


def _report_kernel(name, got, want, tol_key=None):
    abs_err, err = rel_err(got, want)
    tol = KERNEL_TOLS[tol_key or name.split("[")[0]]
    print(f"kernel {name}: max_abs_err={abs_err!r} rel_err={err!r} tol={tol}")
    check(err <= tol, f"kernel {name}: rel_err {err} > {tol}")


def kernel_phase(seed: int) -> None:
    """Each kernel once at the widths above."""
    from repro.kernels.conv_pool_bwd import ops as conv_pool_bwd_ops
    from repro.kernels.conv_pool_bwd import ref as conv_pool_bwd_ref
    from repro.kernels.flash_attention import ref as flash_ref
    from repro.kernels.flash_attention.decode import flash_decode_kernel
    from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
    from repro.kernels.gram import ops as gram_ops
    from repro.kernels.gram import ref as gram_ref
    from repro.kernels.gram.gram import gram_kernel
    from repro.kernels.pairwise_l2 import ref as pw_ref
    from repro.kernels.pairwise_l2.pairwise_l2 import pairwise_sq_dists_kernel
    from repro.kernels.rwkv6_scan import ref as wkv_ref
    from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_kernel

    keys = iter(jax.random.split(jax.random.key(seed), 32))
    normal = lambda shape, dtype=jnp.float32: jax.random.normal(
        next(keys), shape, jnp.float32
    ).astype(dtype)
    exact = jax.default_matmul_precision("highest")

    prof = normal((C, F))
    got = pairwise_sq_dists_kernel(prof, interpret=False)
    with exact:
        want = pw_ref.pairwise_sq_dists_ref(prof) * (1.0 - jnp.eye(C))
    _report_kernel(f"pairwise_sq_dists[C={C},F={F}]", got, want)

    for cc in (C, C_RAGGED):
        prof = normal((cc, F))
        got = gram_ops.kernel_from_profiles(prof)
        with exact:
            want = gram_ref.kernel_from_profiles_ref(prof)
        _report_kernel(f"kernel_from_profiles[C={cc},F={F}]", got, want)

    s = jax.random.uniform(next(keys), (C, C), jnp.float32)
    got = gram_kernel(s, interpret=False)
    with exact:
        want = gram_ref.gram_ref(s)
    _report_kernel(f"gram[{C}x{C}]", got, want)

    bf16 = jnp.bfloat16
    q = normal((B, 1, H, HD), bf16)
    k = normal((B, S, HK, HD), bf16)
    v = normal((B, S, HK, HD), bf16)
    lengths = jax.random.randint(next(keys), (B,), 1, S + 1, jnp.int32)
    got = flash_decode_kernel(q, k, v, lengths, interpret=False)
    with exact:
        want = flash_ref.decode_attention_ref(q, k, v, lengths)
    _report_kernel(f"flash_decode[B={B},S={S},H={H},Hk={HK}]",
                   got, want)

    q = normal((1, S, H, HD), bf16)
    k = normal((1, S, HK, HD), bf16)
    v = normal((1, S, HK, HD), bf16)
    got = flash_attention_kernel(q, k, v, interpret=False)
    with exact:
        want = flash_ref.attention_ref(q, k, v)
    _report_kernel(f"flash_attention[S={S},H={H},Hk={HK}]", got, want)

    shape = (1, RWKV_T, RWKV_H, HD)
    r, k, v = (normal(shape, bf16) for _ in range(3))
    w = jax.random.uniform(next(keys), shape, jnp.float32, 0.5, 0.999)
    u = 0.5 * normal((RWKV_H, HD))
    s0 = normal((1, RWKV_H, HD, HD))
    y, s_out = wkv6_kernel(r, k, v, w, u, s0, interpret=False)
    with exact:
        y_ref, s_ref = wkv_ref.wkv6_scan_ref(r, k, v, w, u, s0)
    name = f"wkv6[T={RWKV_T},H={RWKV_H}]"
    _report_kernel(name + ".y", y, y_ref, "wkv6.y")
    _report_kernel(name + ".state", s_out, s_ref, "wkv6.state")

    # a conv output of few values and a positive bias: windows of equal
    # maxima, which only the first may take the gradient of
    y = jnp.round(2.0 * normal((LOCKSTEP, SAMPLES, HW, HW, CONV1))) / 2.0
    b = jnp.abs(0.3 * normal((LOCKSTEP, CONV1))) + 0.5
    x = normal((LOCKSTEP, SAMPLES, HW, HW, 1))
    g = normal((LOCKSTEP, SAMPLES, HW // 2, HW // 2, CONV1))
    got = jax.vmap(lambda *a: conv_pool_bwd_ops.conv_pool_bwd(*a, (5, 5)))(y, b, x, g)
    with exact:
        want = jax.vmap(lambda *a: conv_pool_bwd_ref.conv_pool_bwd_ref(*a, (5, 5)))(y, b, x, g)
    name = f"conv_pool_bwd[S={LOCKSTEP},N={SAMPLES},C={CONV1}]"
    _report_kernel(name + ".dw", got[0], want[0], "conv_pool_bwd")
    _report_kernel(name + ".db", got[1], want[1], "conv_pool_bwd")


# -------------------------------------------------------------- federation


def paper_federation(seed: int, **cfg_kw):
    """-> (cfg, round_fn builder, initial ServerState) of the paper's
    fl-dp3s federation, its eq.-(14) kernel built by the Pallas pipeline."""
    from repro.configs import paper_cnn
    from repro.core import make_strategy
    from repro.data import make_image_dataset, skewness_partition
    from repro.fl import engine
    from repro.models import cnn

    exp = paper_cnn.paper_scale()
    cfg = dataclasses.replace(
        paper_cnn.fl_config(exp, seed), rounds=ROUNDS, use_pallas_kernel=True,
        **cfg_kw,
    )
    ds = make_image_dataset(n=exp.num_clients * exp.samples_per_client, seed=seed)
    shards = skewness_partition(
        ds.ys, exp.num_clients, 1.0, ds.num_classes,
        samples_per_client=exp.samples_per_client, seed=seed,
    )
    client_xs = np.stack([ds.xs[s] for s in shards])
    client_ys = np.stack([ds.ys[s] for s in shards])
    params = cnn.init_cnn(
        jax.random.key(seed), channels=exp.cnn_channels, fc1_dim=exp.fc1_dim
    )
    strategy = make_strategy("fl-dp3s")
    state = engine.init_server_state(
        cfg, params, cnn.cnn_loss, cnn.apply_with_features,
        client_xs, client_ys, strategy=strategy,
    )

    def round_fn(mesh=None):
        return engine.make_round_fn(
            cfg, cnn.cnn_loss, (strategy,), accuracy_fn=cnn.accuracy, mesh=mesh
        )

    return cfg, round_fn, state


def _check_rounds(outs, cfg, label: str) -> np.ndarray:
    sel = np.asarray(outs["selected"])
    check(sel.shape == (cfg.rounds, cfg.clients_per_round),
          f"{label}: selections {sel.shape}")
    for t, row in enumerate(sel):
        check(len(set(row.tolist())) == cfg.clients_per_round
              and row.min() >= 0 and row.max() < cfg.num_clients,
              f"{label}: round {t + 1} selected {row.tolist()}")
    loss = np.asarray(outs["loss"])
    check(np.isfinite(loss).all(), f"{label}: non-finite losses {loss}")
    return sel


def federation_phase(seed: int) -> None:
    from repro.core import similarity as similarity_lib
    from repro.fl import engine
    from repro.models import cnn

    cfg, round_fn, state = paper_federation(seed)
    with jax.default_matmul_precision("highest"):
        jnp_kernel = similarity_lib.kernel_from_profiles(
            state.profiles, use_kernel=False
        )
    _, kern_err = rel_err(state.kernel, jnp_kernel)
    check(kern_err <= FED_KERNEL_TOL,
          f"federation: Pallas eq.-(14) kernel rel_err {kern_err} > {FED_KERNEL_TOL}")
    exact = eq14_f64(state.profiles)
    pallas_f64_err, jnp_f64_err = (
        rel_err(k, exact)[1] for k in (state.kernel, jnp_kernel)
    )

    final, outs = engine.run_scanned(round_fn(), state, cfg.rounds)
    sel = _check_rounds(outs, cfg, "federation")
    xs = final.client_xs.reshape((-1,) + final.client_xs.shape[2:])
    acc = float(cnn.accuracy(final.params, xs, final.client_ys.reshape(-1)))
    check(np.isfinite(acc), f"federation: final accuracy {acc}")
    platforms = {
        d.platform for leaf in jax.tree_util.tree_leaves(final.params)
        for d in leaf.devices()
    }
    check(platforms == {"tpu"}, f"federation: params live on {platforms}")
    print(
        f"federation: C={cfg.num_clients} k={cfg.clients_per_round} "
        f"rounds={cfg.rounds} kernel_rel_err={kern_err!r} "
        f"(vs f64: pallas={pallas_f64_err!r} jnp={jnp_f64_err!r}) "
        f"losses={np.asarray(outs['loss']).tolist()} final_acc={acc!r} "
        f"distinct_per_round={[len(set(r)) for r in sel.tolist()]} "
        f"params_on={sorted(platforms)}"
    )


def _params_rel_err(a, b) -> float:
    """max |a - b| over the params, over max(1, max |a|)."""
    la, lb = ([np.asarray(x) for x in jax.tree_util.tree_leaves(s.params)]
              for s in (a, b))
    diff = max(float(np.abs(x - y).max()) for x, y in zip(la, lb))
    return diff / max(1.0, max(float(np.abs(x).max()) for x in la))


def _planted_swap(state, sel: np.ndarray, c_loc: int):
    """``state`` with the data of two clients on different shards swapped:
    the lowest-id client of the first round's cohort and the least selected
    client on another shard (lowest id on ties), so that, like a fault on
    the slot path, it acts from the first round.  Selection reads only the
    DPP kernel, so cohorts stay the same and only the local updates go
    wrong, as they would if the slot table pointed at the wrong resident."""
    counts = np.bincount(sel.ravel(), minlength=state.client_xs.shape[0])
    i = int(sel[0].min())
    j = min((c for c in range(counts.size) if c // c_loc != i // c_loc),
            key=lambda c: (counts[c], c))
    perm = np.arange(counts.size)
    perm[[i, j]] = j, i
    return dataclasses.replace(
        state, client_xs=state.client_xs[perm], client_ys=state.client_ys[perm]
    ), (i, j)


def four_chip_phase(seed: int) -> None:
    from repro.fl import engine
    from repro.launch.mesh import make_client_mesh

    cfg, round_fn, state = paper_federation(seed, cohort_cap=COHORT_CAP)
    mesh = make_client_mesh(MESH_CHIPS)
    single, single_outs = engine.run_scanned(round_fn(), state, cfg.rounds)
    mesh_fn = round_fn(mesh)
    meshed, mesh_outs = engine.run_scanned(mesh_fn, state, cfg.rounds, mesh=mesh)
    shards = meshed.client_xs.addressable_shards
    shard_devices = {s.device for s in shards}
    check(len(shard_devices) == MESH_CHIPS and set(mesh.devices.flat) == shard_devices,
          f"four-chip: client axis spans {shard_devices}")
    c_loc = cfg.num_clients // MESH_CHIPS
    rows = {s.data.shape[0] for s in shards}
    check(rows == {c_loc}, f"four-chip: clients per shard {rows}")

    sel_1 = _check_rounds(single_outs, cfg, "four-chip one-device")
    sel_4 = _check_rounds(mesh_outs, cfg, "four-chip mesh")
    faulty, swapped = _planted_swap(state, sel_1, c_loc)
    planted, planted_outs = engine.run_scanned(mesh_fn, faulty, cfg.rounds, mesh=mesh)
    param_err = _params_rel_err(single, meshed)
    planted_err = _params_rel_err(single, planted)
    same = bool(np.array_equal(sel_1, sel_4))
    print(
        f"four-chip: C={cfg.num_clients} k={cfg.clients_per_round} "
        f"cohort_cap={cfg.cohort_cap} rounds={cfg.rounds} "
        f"mesh_devices={[d.id for d in mesh.devices.flat]} "
        f"selections_identical={same} params_rel_err={param_err!r} "
        f"tol={MESH_PARAM_TOL!r} planted_swap={swapped} "
        f"planted_selections_identical="
        f"{bool(np.array_equal(sel_1, np.asarray(planted_outs['selected'])))} "
        f"planted_params_rel_err={planted_err!r} "
        f"losses_1dev={np.asarray(single_outs['loss']).tolist()} "
        f"losses_mesh={np.asarray(mesh_outs['loss']).tolist()} "
        f"losses_planted={np.asarray(planted_outs['loss']).tolist()}",
        flush=True,
    )
    check(same, "four-chip: selections differ")
    check(param_err <= MESH_PARAM_TOL,
          f"four-chip: params rel_err {param_err} > {MESH_PARAM_TOL}")
    check(planted_err > MESH_PARAM_TOL,
          f"four-chip: planted swap {swapped} moved the params by only "
          f"{planted_err} <= {MESH_PARAM_TOL}: the bound cannot see it")


# ----------------------------------------------------------------- serving


def serving_phase(seed: int) -> None:
    from repro.configs import get_arch
    from repro.launch.serve import run_continuous
    from repro.models import transformer as T

    cfg = get_arch("smollm-360m").model
    params = jax.jit(lambda key: T.init_params(key, cfg))(jax.random.key(seed))
    prompts = jax.random.randint(
        jax.random.key(seed + 1), (REQUESTS, PROMPT), 0, cfg.vocab_size,
        jnp.int32,
    )
    budgets = np.random.default_rng(seed).integers(1, MAX_BUDGET + 1, REQUESTS)
    budgets[0] = MAX_BUDGET
    finished, stats = run_continuous(
        cfg, params, prompts, budgets, SLOTS, use_flash=True, seed=seed
    )
    check(sorted(f.seq_id for f in finished) == list(range(REQUESTS)),
          f"serving: finished {sorted(f.seq_id for f in finished)}")
    for f in finished:
        n, want = len(f.tokens), int(budgets[f.seq_id])
        check(n == want, f"serving: request {f.seq_id} made {n} tokens, budget {want}")
        check(f.tokens.min() >= 0 and f.tokens.max() < cfg.vocab_size,
              f"serving: request {f.seq_id} token outside the vocabulary")
    check(stats["compiles"] == {"decode_chunk": 1, "admit": 1},
          f"serving: compiled programs {stats['compiles']}")
    print(
        f"serving: arch={cfg.name} d_model={cfg.d_model} layers={cfg.num_layers} "
        f"vocab={cfg.vocab_size} dtype={cfg.param_dtype} flash_decode=True "
        f"requests={REQUESTS} batch={SLOTS} prompt={PROMPT} "
        f"budgets={budgets.tolist()} tokens={stats['tokens']} "
        f"compiled={stats['compiles']}"
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip client-mesh federation and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        kernel_phase(args.seed)
        federation_phase(args.seed)
        serving_phase(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
