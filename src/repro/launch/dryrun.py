"""Multi-pod dry-run: prove every (arch × input shape × mesh) lowers,
compiles, fits, and report its roofline inputs — without real hardware.

    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
    python -m repro.launch.dryrun --sweep --out results/dryrun.jsonl
    python -m repro.launch.dryrun --arch mixtral-8x7b --shape decode_32k --multi-pod

A CPU harness, not a chip measurement: the lines below pin
``JAX_PLATFORMS=cpu`` and force 512 host platform devices before JAX is
imported, so the dry-run never claims an attached accelerator.  This module
must therefore never be imported by tests/benches directly (they spawn it as
a subprocess) — smoke tests must see 1 device.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import optim as optim_lib  # noqa: E402
from repro.analysis import hlo as hlo_lib  # noqa: E402
from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_arch  # noqa: E402
from repro.configs.registry import ArchSpec  # noqa: E402
from repro.fl import rounds as rounds_lib  # noqa: E402
from repro.launch import sharding as sh  # noqa: E402
from repro.launch.mesh import make_client_mesh, make_production_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402

SHAPE_NAMES = list(INPUT_SHAPES)


# ------------------------------------------------------------------ helpers


def _sds(tree_specs, tree_shapes, mesh):
    """Zip a PartitionSpec tree onto a ShapeDtypeStruct tree."""

    def mk(sdt, spec):
        return jax.ShapeDtypeStruct(
            sdt.shape, sdt.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map(
        mk, tree_shapes, tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _prepend_axis(spec_tree, axis):
    return jax.tree_util.tree_map(
        lambda s: P(axis, *s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def _replicated_like(tree, mesh):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
        tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else "data"


@dataclasses.dataclass
class DryRunCase:
    arch: str
    shape: str
    multi_pod: bool
    reduced: bool = False
    accounting: bool = False  # unroll scans so static HLO counts are exact
    scan_rounds: int = 1  # >1: engine-style lax.scan over N FL rounds

    @property
    def mesh_name(self) -> str:
        return "2x16x16" if self.multi_pod else "16x16"


def _case_config(case: DryRunCase) -> Tuple[ArchSpec, "ModelConfig", Dict]:
    spec = get_arch(case.arch)
    ishape = INPUT_SHAPES[case.shape]
    cfg = spec.long_context_model() if case.shape == "long_500k" else spec.model
    dims = dict(seq=ishape.seq_len, batch=ishape.global_batch, kind=ishape.kind)
    if case.reduced:
        cfg = cfg.reduced(param_dtype="bfloat16", dtype="bfloat16")
        # batch>1 shapes must stay divisible by the data axis (32 multi-pod)
        min_b = (32 if case.multi_pod else 16) if ishape.global_batch > 1 else 1
        dims.update(
            seq=min(dims["seq"], 128),
            batch=max(min(dims["batch"], 8), min_b) if ishape.global_batch > 1 else 1,
        )
        # reduced head/state dims no longer divide the 16-way model axis
        relax = dict(rwkv_heads=None)
        spec = dataclasses.replace(
            spec,
            serve_rules=dict(spec.serve_rules, **relax),
            train_rules=dict(spec.train_rules, **relax),
        )
    return spec, cfg, dims


# ------------------------------------------------------------ step builders


def _make_loss(cfg, uses_embeds: bool):
    if uses_embeds:
        return lambda p, batch: T.lm_loss(
            cfg, p, embeds=batch["embeds"], targets=batch["targets"]
        )
    return lambda p, batch: T.lm_loss(cfg, p, batch["tokens"])


def _uses_embeds(cfg) -> bool:
    return cfg.arch_type == "vlm"


def _train_case(spec, cfg, dims, mesh, multi_pod, steps_unroll=1, scan_rounds=1):
    """Build (step_fn, example_args_sds) for the training shape.

    ``scan_rounds > 1`` wraps the Mode-A round step the way the federation
    engine does (``repro.fl.engine``): N rounds compile into one ``lax.scan``
    program — proving the multi-round engine graph lowers/fits at production
    shapes, with per-round batches stacked on a leading ``(N,)`` axis.
    """
    rules = spec.train_rules
    b, s = dims["batch"], dims["seq"]
    uses_embeds = _uses_embeds(cfg)
    loss_fn = _make_loss(cfg, uses_embeds)

    params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
    pspecs = sh.specs_from_logical(sh.param_logical_specs(cfg), rules, multi_pod)
    params_sds = _sds(pspecs, params_shapes, mesh)
    batch_ax = _batch_axes(multi_pod)

    if spec.fl.mode == "client_parallel":
        n_clients = 32 if multi_pod else 16
        local_b = max(1, b // n_clients)
        steps = spec.fl.local_steps
        # per-client params lay out over the data axis on top of the
        # serve-style model sharding
        serve_pspecs = sh.specs_from_logical(
            sh.param_logical_specs(cfg), spec.serve_rules, multi_pod
        )
        client_specs = _prepend_axis(serve_pspecs, batch_ax)

        def constraint(tree):
            return jax.tree_util.tree_map(
                lambda x, sp: jax.lax.with_sharding_constraint(x, sp),
                tree, client_specs,
                is_leaf=lambda x: isinstance(x, jax.Array),
            )

        micro = max(1, min(spec.fl.micro_batches, local_b))
        while local_b % micro:
            micro -= 1
        if steps_unroll is True:
            micro = 1  # accounting: keep all flops outside rolled loops
        step = rounds_lib.build_client_parallel_round(
            loss_fn, spec.fl.lr, steps, client_constraint=constraint,
            unroll=steps_unroll, micro_batches=micro,
        )
        if uses_embeds:
            batch_shapes = {
                "embeds": jax.ShapeDtypeStruct(
                    (n_clients, steps, local_b, s, cfg.d_model), jnp.bfloat16
                ),
                "targets": jax.ShapeDtypeStruct((n_clients, steps, local_b, s), jnp.int32),
            }
            batch_specs = {
                "embeds": P(batch_ax, None, None, None, None),
                "targets": P(batch_ax, None, None, None),
            }
        else:
            batch_shapes = {
                "tokens": jax.ShapeDtypeStruct((n_clients, steps, local_b, s), jnp.int32)
            }
            batch_specs = {"tokens": P(batch_ax, None, None, None)}
        if scan_rounds > 1:
            inner = step

            def step(params, batches, weights):  # noqa: F811
                def body(p, b):
                    p2, loss = inner(p, b, weights)
                    return p2, loss

                return jax.lax.scan(body, params, batches)

            batch_shapes = {
                k: jax.ShapeDtypeStruct((scan_rounds,) + v.shape, v.dtype)
                for k, v in batch_shapes.items()
            }
            batch_specs = {k: P(None, *v) for k, v in batch_specs.items()}
        batch_sds = _sds(batch_specs, batch_shapes, mesh)
        w_sds = jax.ShapeDtypeStruct(
            (n_clients,), jnp.float32, sharding=NamedSharding(mesh, P(batch_ax))
        )
        return step, (params_sds, batch_sds, w_sds)

    # Mode B: fedsgd_fsdp
    opt = getattr(optim_lib, spec.optimizer)(spec.fl.lr)
    micro = max(1, min(spec.fl.micro_batches, b))
    while b % micro:
        micro -= 1
    if steps_unroll is True:
        micro = 1  # accounting: keep all flops outside rolled loops
    step = rounds_lib.build_fedsgd_step(loss_fn, opt, micro_batches=micro)
    opt_shapes = jax.eval_shape(opt.init, params_shapes)
    opt_specs = sh.optimizer_state_specs(spec.optimizer, pspecs)
    opt_sds = _sds(opt_specs, opt_shapes, mesh)
    if uses_embeds:
        batch_shapes = {
            "embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16),
            "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        batch_specs = {"embeds": P(batch_ax, None, None), "targets": P(batch_ax, None)}
    else:
        batch_shapes = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        batch_specs = {"tokens": P(batch_ax, None)}
    batch_sds = _sds(batch_specs, batch_shapes, mesh)
    return step, (params_sds, opt_sds, batch_sds)


def _serve_case(spec, cfg, dims, mesh, multi_pod, prefill: bool):
    """(step_fn, args_sds) for prefill / decode shapes."""
    rules = spec.serve_rules
    b, s = dims["batch"], dims["seq"]
    uses_embeds = _uses_embeds(cfg)
    batch_ax = _batch_axes(multi_pod) if b > 1 else None

    params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
    pspecs = sh.specs_from_logical(sh.param_logical_specs(cfg), rules, multi_pod)
    params_sds = _sds(pspecs, params_shapes, mesh)

    cache_shapes = jax.eval_shape(lambda: T.init_caches(cfg, b, s))
    crules = dict(rules)
    if batch_ax is None:
        crules["act_batch"] = None
    cspecs = sh.specs_from_logical(sh.cache_logical_specs(cfg), crules, multi_pod)
    caches_sds = _sds(cspecs, cache_shapes, mesh)

    if prefill:
        def step(params, batch, caches):
            tokens = batch.get("tokens")
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            if cfg.pos_style == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, b, s))
            hidden, new_caches, _ = T.forward(
                cfg, params, tokens, positions, caches, embeds=batch.get("embeds")
            )
            logits = T.logits_from_hidden(cfg, params, hidden[:, -1:])
            return logits, new_caches

        if uses_embeds:
            batch_shapes = {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)}
            batch_specs = {"embeds": P(batch_ax, None, None)}
        else:
            batch_shapes = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            batch_specs = {"tokens": P(batch_ax, None)}
        batch_sds = _sds(batch_specs, batch_shapes, mesh)
        return step, (params_sds, batch_sds, caches_sds)

    def step(params, tokens, caches):
        return T.decode_step(cfg, params, tokens, caches)

    tok_sds = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32, sharding=NamedSharding(mesh, P(batch_ax, None))
    )
    return step, (params_sds, tok_sds, caches_sds)


# ----------------------------------------------------- sharded FL engine


def run_fl_sharded_case(num_devices: int = 64, clients: int = 256,
                        clients_per_round: int = 32, rounds: int = 4,
                        cohort_cap: Optional[int] = None,
                        staleness_bound: Optional[int] = None,
                        scenario: Optional[str] = None,
                        candidate_frac: Optional[float] = None,
                        faults: Optional[str] = None,
                        aggregator: str = "mean",
                        local_algo: str = "fedavg",
                        prox_mu: Optional[float] = None,
                        feddyn_alpha: Optional[float] = None) -> Dict:
    """Prove the mesh-sharded federation engine (DESIGN.md §8) lowers and
    compiles at scale: C clients sharded over an N-device client mesh, the
    scanned round's local-update core as a shard_map with psum'd FedAvg.

    Drives the exact production path — ``engine.init_server_state(mesh=...)``
    + ``engine.make_round_fn(mesh=...)`` — on the forced host platform, and
    reports the compiled program's collective footprint (the all-gather-free
    claim is checkable in the HLO: params move only through reduce ops).

    ``cohort_cap`` compiles the capacity-slot variant instead: each shard's
    local-update scan is sized to ``min(C/N, cohort_cap)`` slots, proving the
    k ≪ C round really lowers to slot-count work (visible in the HLO loop
    trip counts) with the psum rendezvous unchanged.

    ``staleness_bound``/``scenario`` compile the bounded-staleness variant
    (DESIGN.md §9): the scan carries the ``s+1``-slot param ring buffer +
    per-shard staleness counters, every shard's base params come from a
    dynamic ring read, and the latency scenario's straggler bookkeeping all
    lower inside the same single-psum round — proving the stale temporal
    dimension fits the compiled-scan contract at production scale.

    ``candidate_frac`` compiles the two-stage funnel variant (DESIGN.md
    §10): the state carries the (Q,) candidate table and a Q×Q kernel +
    spectral cache instead of C×C, selection draws in candidate space and
    gathers back to global ids — proving the funneled round (and its
    shard-local candidate-profile psum at init) lowers on the client mesh.

    ``faults``/``aggregator`` compile the fault-tolerant variant (DESIGN.md
    §11): jit-level fault draws sharded into the round, the update-validation
    guard (finite screening + norm-outlier rejection against the shard-local
    cohort median) inside the shard_map before the unchanged single psum,
    quarantine counters carried in the scan, and the survivors-floor identity
    round — the full robustness layer must lower on the client mesh.

    ``local_algo`` compiles the pluggable local-update variant (DESIGN.md
    §12): ``feddyn`` carries the client-sharded per-client penalty state
    through the scan (gathered/scattered by the same slot machinery),
    proving a stateful local algorithm lowers on the client mesh with the
    aggregation path untouched.
    """
    import numpy as np

    from repro.core import selection as selection_lib
    from repro.fl import engine as engine_lib

    t0 = time.perf_counter()
    case = "fl_sharded_engine"
    if cohort_cap is not None:
        case = "fl_sharded_engine_slotted"
    elif staleness_bound is not None:
        case = "fl_sharded_engine_stale"
    elif candidate_frac is not None:
        case = "fl_sharded_engine_funnel"
    elif faults is not None or aggregator != "mean":
        case = "fl_sharded_engine_faulty"
    elif local_algo != "fedavg":
        case = f"fl_sharded_engine_{local_algo}"
    rec: Dict = {
        "case": case,
        "mesh": f"{num_devices}x1({sh.CLIENT_AXIS})",
        "clients": clients,
        "clients_per_round": clients_per_round,
        "cohort_cap": cohort_cap,
        "staleness_bound": staleness_bound,
        "scenario": scenario,
        "candidate_frac": candidate_frac,
        "faults": faults,
        "aggregator": aggregator,
        "local_algo": local_algo,
        "scan_rounds": rounds,
    }
    try:
        mesh = make_client_mesh(num_devices)
        feat, n_c, ncls = 32, 8, 10
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(clients, n_c, feat)).astype("float32"))
        ys = jnp.asarray(rng.integers(0, ncls, size=(clients, n_c)), jnp.int32)
        params = {
            "w": jnp.asarray(0.01 * rng.normal(size=(feat, ncls)).astype("float32")),
            "b": jnp.zeros((ncls,), jnp.float32),
        }

        def loss_fn(p, x, y):
            logp = jax.nn.log_softmax(x @ p["w"] + p["b"])
            return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

        cfg = engine_lib.FLConfig(
            num_clients=clients, clients_per_round=clients_per_round,
            local_epochs=2, lr=0.1, rounds=rounds, eval_every=rounds,
            num_classes=ncls, seed=0, cohort_cap=cohort_cap,
            staleness_bound=staleness_bound, scenario=scenario,
            candidate_frac=candidate_frac, faults=faults,
            aggregator=aggregator, local_algo=local_algo,
            prox_mu=prox_mu, feddyn_alpha=feddyn_alpha,
        )
        strat = selection_lib.DPPSelection()
        state = engine_lib.init_server_state(
            cfg, params, loss_fn, None, xs, ys, strategy=strat,
            profiles=xs.mean(axis=1), mesh=mesh,
        )
        if candidate_frac is not None:
            rec["candidates"] = int(state.candidates.shape[0])
            rec["kernel_shape"] = list(state.kernel.shape)
        round_fn = engine_lib.make_round_fn(cfg, loss_fn, (strat,), mesh=mesh)
        program = jax.jit(
            lambda s: jax.lax.scan(round_fn, s, None, length=rounds)
        )
        compiled = program.lower(state).compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        rec["collectives"] = hlo_lib.collective_bytes(compiled.as_text())
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    return rec


# ----------------------------------------------------- serving engine


def run_serve_engine_case(arch: str, batch: int = 4, prompt: int = 8,
                          gen: int = 8) -> Dict:
    """Prove the serving engine's two compiled programs (DESIGN.md §13)
    lower and compile for a reduced arch: the ``lax.scan`` decode over the
    per-slot :class:`~repro.serve.DecodeState`, and the continuous-batching
    slot-refill admission (prefill + stable-argsort slot scatter).  Pure
    ``lower().compile()`` on ShapeDtypeStructs — no weights materialised."""
    from repro.serve import (ServeConfig, init_decode_state, make_admit_fn,
                             make_decode_fn, run_scan)

    t0 = time.perf_counter()
    rec: Dict = {"case": "serve_engine", "arch": arch,
                 "batch": batch, "prompt": prompt, "gen": gen}
    try:
        cfg = get_arch(arch).model.reduced(
            param_dtype="float32", dtype="float32", remat=False
        )
        scfg = ServeConfig(batch=batch, cache_len=prompt + gen, max_new=gen)
        params_sds = jax.eval_shape(
            lambda k: T.init_params(k, cfg), jax.random.key(0)
        )
        state_sds = jax.eval_shape(lambda: init_decode_state(cfg, scfg))

        decode_fn = make_decode_fn(cfg, scfg)
        t1 = time.perf_counter()
        scan = jax.jit(lambda p, s: run_scan(decode_fn, p, s, gen - 1))
        scan.lower(params_sds, state_sds).compile()
        rec["scan_compile_s"] = round(time.perf_counter() - t1, 2)

        admit_fn = make_admit_fn(cfg, scfg, prompt)
        prompt_sds = jax.ShapeDtypeStruct((1, prompt), jnp.int32)
        scalar_sds = jax.ShapeDtypeStruct((), jnp.int32)
        key_sds = jax.eval_shape(
            lambda k: jax.random.key_data(k), jax.random.key(0)
        )
        t1 = time.perf_counter()
        jax.jit(admit_fn).lower(
            params_sds, state_sds, prompt_sds, scalar_sds, scalar_sds, key_sds
        ).compile()
        rec["admit_compile_s"] = round(time.perf_counter() - t1, 2)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    return rec


# ------------------------------------------------------------------ runner


def _compile_once(spec, cfg, dims, mesh, multi_pod, steps_unroll=1, scan_rounds=1):
    """Lower+compile one variant; return compiled.

    Buffers are donated the way the production loop would donate them
    (params/opt-state in, updated params/opt-state out; caches in, updated
    caches out) so memory_analysis reflects steady-state aliasing.
    """
    if dims["kind"] == "train":
        step, args = _train_case(spec, cfg, dims, mesh, multi_pod,
                                 steps_unroll=steps_unroll,
                                 scan_rounds=scan_rounds)
        rules = spec.train_rules
        if spec.fl.mode == "client_parallel":
            # the client axis owns 'data'; activation constraints inside the
            # per-client vmap must NOT re-claim it for the local batch dim —
            # doing so forced spurious regathers (§Perf: 5.2x collective
            # reduction on rwkv6 train from this alone).
            rules = dict(rules, act_batch=None)
        donate = (0,) if spec.fl.mode == "client_parallel" else (0, 1)
    else:
        step, args = _serve_case(
            spec, cfg, dims, mesh, multi_pod, prefill=dims["kind"] == "prefill"
        )
        rules = spec.serve_rules
        donate = (2,)  # caches
    with jax.set_mesh(mesh), sh.use_rules(rules, multi_pod):
        compiled = jax.jit(step, donate_argnums=donate).lower(*args).compile()
    return compiled


def _counts(compiled) -> Dict:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    text = compiled.as_text()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": hlo_lib.collective_bytes(text),
        "text": text,
    }


def _accounting_counts(spec, cfg, dims, mesh, multi_pod) -> Dict:
    """Two-point unroll delta: XLA cost analysis counts while bodies once, so
    with the local-step and loss scans fully unrolled and the layer scan at
    unroll u ∈ {1, 2}:  reported(u) = C + u·B  ⇒  exact = reported(1) +
    (R − 1)·(reported(2) − reported(1)), R = layer-scan trip count.
    The rwkv time scan stays rolled (its flops are added analytically in
    analysis.roofline)."""
    import dataclasses as dc

    loss_chunk = max(512, dims["seq"] // 4)
    cfg1 = dc.replace(cfg, scan_unroll=1, loss_unroll=True, loss_chunk=loss_chunk)
    cfg2 = dc.replace(cfg, scan_unroll=2, loss_unroll=True, loss_chunk=loss_chunk)
    reps = cfg.num_layers // len(cfg.block_pattern)
    c1 = _counts(_compile_once(spec, cfg1, dims, mesh, multi_pod, steps_unroll=True))
    c2 = _counts(_compile_once(spec, cfg2, dims, mesh, multi_pod, steps_unroll=True))

    def corr(a, b):
        return a + (reps - 1) * (b - a)

    coll = {}
    keys = set(c1["collectives"]) | set(c2["collectives"])
    for k in keys:
        coll[k] = max(0.0, corr(c1["collectives"].get(k, 0.0), c2["collectives"].get(k, 0.0)))
    return {
        "flops": corr(c1["flops"], c2["flops"]),
        "bytes": corr(c1["bytes"], c2["bytes"]),
        "collectives": coll,
        "layer_reps": reps,
        "raw": {
            "u1": {k: c1[k] for k in ("flops", "bytes")},
            "u2": {k: c2[k] for k in ("flops", "bytes")},
        },
    }


def run_case(case: DryRunCase, dump_hlo: Optional[str] = None,
             mesh_override=None) -> Dict:
    t0 = time.perf_counter()
    spec, cfg, dims = _case_config(case)
    mesh = mesh_override or make_production_mesh(multi_pod=case.multi_pod)
    rec: Dict = {
        "arch": case.arch,
        "shape": case.shape,
        "mesh": case.mesh_name if mesh_override is None else "x".join(
            str(s) for s in mesh.devices.shape
        ),
        "kind": dims["kind"],
        "fl_mode": spec.fl.mode if dims["kind"] == "train" else "serve",
        "reduced": case.reduced,
        "accounting": case.accounting,
        # the scan wrapper only applies to client_parallel train compiles;
        # record the EFFECTIVE value so sweep records stay comparable
        "scan_rounds": case.scan_rounds
        if (
            dims["kind"] == "train"
            and spec.fl.mode == "client_parallel"
            and not case.accounting
        )
        else 1,
    }
    try:
        if case.accounting:
            acc = _accounting_counts(spec, cfg, dims, mesh, case.multi_pod)
            rec["cost"] = {"flops": acc["flops"], "bytes accessed": acc["bytes"]}
            rec["collectives"] = acc["collectives"]
            rec["layer_reps"] = acc["layer_reps"]
            rec["raw"] = acc["raw"]
            rec["ok"] = True
            rec["total_s"] = round(time.perf_counter() - t0, 2)
            return rec

        compiled = _compile_once(spec, cfg, dims, mesh, case.multi_pod,
                                 scan_rounds=case.scan_rounds)
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        rec["params"] = int(
            sum(
                x.size
                for x in jax.tree_util.tree_leaves(
                    jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
                )
            )
        )

        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        except Exception as e:  # pragma: no cover - backend dependent
            rec["memory"] = {"error": str(e)}

        try:
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            rec["cost"] = {
                k: float(v)
                for k, v in cost.items()
                if k in ("flops", "bytes accessed", "utilization operand 0")
                or k.startswith("bytes accessed")
            }
        except Exception as e:  # pragma: no cover
            rec["cost"] = {"error": str(e)}

        text = compiled.as_text()
        rec["collectives"] = hlo_lib.collective_bytes(text)
        rec["hlo_ops"] = hlo_lib.op_histogram(text)
        if dump_hlo:
            os.makedirs(os.path.dirname(dump_hlo) or ".", exist_ok=True)
            with open(dump_hlo, "w") as f:
                f.write(text)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=SHAPE_NAMES)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod for each case")
    ap.add_argument("--sweep", action="store_true", help="all arch x shapes")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs + tiny shapes (CI smoke)")
    ap.add_argument("--accounting", action="store_true",
                    help="unroll scans for exact static HLO counts (§Roofline)")
    ap.add_argument("--scan-rounds", type=int, default=1,
                    help="compile N FL rounds as one engine-style lax.scan "
                         "(client_parallel train shapes; DESIGN.md §7)")
    ap.add_argument("--fl-sharded", action="store_true",
                    help="compile the mesh-sharded federation engine on a "
                         "client mesh (DESIGN.md §8) instead of an arch case")
    ap.add_argument("--fl-devices", type=int, default=64,
                    help="client-mesh size for --fl-sharded")
    ap.add_argument("--fl-cohort-cap", type=int, default=2,
                    help="per-shard slot count (and cohort size) for the "
                         "--fl-sharded capacity-slot case (DESIGN.md §8)")
    ap.add_argument("--fl-staleness-bound", type=int, default=2,
                    help="staleness bound for the --fl-sharded bounded-"
                         "staleness compile case (DESIGN.md §9)")
    ap.add_argument("--fl-candidate-frac", type=float, default=0.25,
                    help="candidate fraction for the --fl-sharded two-stage "
                         "funnel compile case (DESIGN.md §10)")
    ap.add_argument("--serve-engine", action="store_true",
                    help="compile the serving engine's scan-decode and "
                         "continuous slot-refill programs on reduced archs "
                         "(DESIGN.md §13) instead of an arch case")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--dump-hlo", default=None)
    args = ap.parse_args()

    if args.serve_engine:
        # scan decode + continuous slot-refill admission must lower and
        # compile for each cache family: dense GQA KV (smollm), O(1)
        # recurrent state (rwkv6), SWA ring buffer + MoE (mixtral)
        archs = [args.arch] if args.arch else [
            "smollm-360m", "rwkv6-7b", "mixtral-8x7b"
        ]
        recs = [run_serve_engine_case(a) for a in archs]
        any_fail = False
        for rec in recs:
            status = "OK " if rec["ok"] else "FAIL"
            timing = (
                f"scan={rec.get('scan_compile_s', 0):5.1f}s "
                f"admit={rec.get('admit_compile_s', 0):5.1f}s"
                if rec["ok"] else f"  {rec['error'][:120]}"
            )
            print(f"[{status}] serve_engine {rec['arch']:28s} "
                  f"b={rec['batch']} p={rec['prompt']} g={rec['gen']} "
                  f"{rec['total_s']:7.1f}s  {timing}")
            if not rec["ok"]:
                any_fail = True
                print(rec.get("traceback", "")[-800:])
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
        if any_fail:
            raise SystemExit(1)
        return

    if args.fl_sharded:
        # resident-mode round, the capacity-slot variant on a k ≪ C_loc
        # cohort (cap = min(C/N, k)), the bounded-staleness variant (ring
        # buffer + counters under heavy-tail latency, DESIGN.md §9), the
        # two-stage funnel variant (Q×Q candidate kernel, DESIGN.md §10),
        # the fault-tolerant variant (chaos faults + trimmed_mean guard,
        # DESIGN.md §11), and the stateful local-algorithm variant (feddyn's
        # client-sharded penalty state, DESIGN.md §12) — all six must lower
        # and compile
        recs = [
            run_fl_sharded_case(num_devices=args.fl_devices),
            run_fl_sharded_case(
                num_devices=args.fl_devices,
                clients_per_round=args.fl_cohort_cap,
                cohort_cap=args.fl_cohort_cap,
            ),
            run_fl_sharded_case(
                num_devices=args.fl_devices,
                staleness_bound=args.fl_staleness_bound,
                scenario="heavy_tail",
            ),
            run_fl_sharded_case(
                num_devices=args.fl_devices,
                candidate_frac=args.fl_candidate_frac,
            ),
            run_fl_sharded_case(
                num_devices=args.fl_devices,
                faults="chaos",
                aggregator="trimmed_mean",
            ),
            run_fl_sharded_case(
                num_devices=args.fl_devices,
                local_algo="feddyn",
                feddyn_alpha=0.01,
            ),
        ]
        any_fail = False
        for rec in recs:
            status = "OK " if rec["ok"] else "FAIL"
            cap = rec["cohort_cap"]
            stale = rec.get("staleness_bound")
            frac = rec.get("candidate_frac")
            print(
                f"[{status}] {rec['case']} {rec['mesh']:14s} "
                f"C={rec['clients']} k={rec['clients_per_round']}"
                + (f" cap={cap}" if cap is not None else "")
                + (f" stale<=%d(%s)" % (stale, rec["scenario"])
                   if stale is not None else "")
                + (f" Q={rec.get('candidates')}({frac})"
                   if frac is not None else "")
                + (f" faults={rec['faults']}/{rec['aggregator']}"
                   if rec.get("faults") is not None else "")
                + (f" algo={rec['local_algo']}"
                   if rec.get("local_algo", "fedavg") != "fedavg" else "")
                + f" {rec['total_s']:7.1f}s"
                + ("" if rec["ok"] else f"  {rec['error'][:120]}")
            )
            if not rec["ok"]:
                any_fail = True
                print(rec.get("traceback", "")[-800:])
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
        if any_fail:
            raise SystemExit(1)
        return

    if args.sweep:
        cases = [
            DryRunCase(a, s, mp, reduced=args.reduced, accounting=args.accounting,
                       scan_rounds=args.scan_rounds)
            for a in ARCH_NAMES
            for s in SHAPE_NAMES
            for mp in ((False, True) if args.both_meshes else (args.multi_pod,))
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --sweep required"
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        cases = [
            DryRunCase(args.arch, args.shape, mp, reduced=args.reduced,
                       accounting=args.accounting, scan_rounds=args.scan_rounds)
            for mp in meshes
        ]

    done = set()
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"], r.get("reduced", False)))
                except json.JSONDecodeError:
                    pass

    for case in cases:
        key = (case.arch, case.shape, case.mesh_name, case.reduced)
        if key in done:
            print(f"[skip] {key} (cached)")
            continue
        rec = run_case(case, dump_hlo=args.dump_hlo)
        status = "OK " if rec["ok"] else "FAIL"
        print(
            f"[{status}] {case.arch:28s} {case.shape:12s} {case.mesh_name:8s} "
            f"{rec['total_s']:7.1f}s"
            + ("" if rec["ok"] else f"  {rec['error'][:120]}")
        )
        if not rec["ok"]:
            print(rec.get("traceback", "")[-800:])
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
