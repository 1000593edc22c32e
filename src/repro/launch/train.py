"""Training driver: federated (FL-DP³S) or plain pretrain, on real devices.

On this CPU container it runs reduced configs end-to-end (the full configs
are exercised by the dry-run); on a TPU slice the same driver scales via
``--mesh`` because every step is the same pjit program the dry-run compiles.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --mode fl --rounds 30 --selection fl-dp3s
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --mode pretrain --steps 200
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as optim_lib
from repro.checkpoint import latest_step, save
from repro.configs import ARCH_NAMES, get_arch
from repro.core import make_strategy
from repro.data import make_token_dataset
from repro.fl import engine as engine_lib
from repro.fl import rounds as rounds_lib
from repro.fl.faults import AGGREGATORS, FAULT_NAMES
from repro.fl.local_algos import ALGO_NAMES
from repro.fl.scenarios import SCENARIO_NAMES
from repro.fl.staleness import DECAY_FAMILIES
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_client_mesh
from repro.models import transformer as T
from repro.obs import TelemetrySink
from repro.obs import tracing as obs_tracing_lib


def _token_clients(cfg, num_clients, docs_per_client, seq, seed=0):
    """Topic-skewed client corpora (ξ=1-style: one topic per client)."""
    docs, topics = make_token_dataset(
        n_docs=num_clients * docs_per_client * 2,
        doc_len=seq,
        vocab=min(cfg.vocab_size, 512),
        num_topics=min(10, num_clients),
        seed=seed,
    )
    clients = []
    for c in range(num_clients):
        topic = c % min(10, num_clients)
        idx = np.nonzero(topics == topic)[0][:docs_per_client]
        clients.append(docs[idx])
    return np.stack(clients)  # (C, docs, seq)


def run_fl(args):
    """Federated LM training through the scanned engine (DESIGN.md §7).

    Algorithm-1 init (profiles → eq.-14 kernel) runs once on host; then all
    ``--rounds`` rounds — selection, per-client local steps, aggregation,
    loss refresh, topic-GEMD — execute as ONE compiled ``lax.scan``.

    ``--shard-clients N`` lays the federation out over an N-device client
    mesh (DESIGN.md §8): same engine, same scan, with the local-update core
    shard_mapped so each device trains its resident clients and the FedAvg
    reduction runs as psum'd partial sums.  On CPU hosts combine with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    ``--cohort-cap M`` (requires ``--shard-clients``) switches the sharded
    round to capacity-slot scheduling: each shard trains at most
    ``min(C/N, M)`` clients per round instead of all its residents, so a
    small diverse cohort (k ≪ C, the paper's regime) stops paying
    full-federation compute.  ``M`` must be ≥ min(--per-round, C/N);
    ``M = --per-round`` is the natural setting.

    ``--scenario NAME`` attaches a system-heterogeneity model (DESIGN.md
    §9): per-client latency draws priced into a simulated round wall clock
    (reported at the end), and availability-masked selection for scenarios
    with an availability model.  ``--staleness-bound S`` (requires
    ``--shard-clients`` and ``--scenario``) relaxes the sharded round's
    psum barrier to bounded-staleness aggregation: shards that miss the
    scenario deadline contribute partial sums computed on params up to S
    rounds old, weighted by ``--staleness-decay``/``--staleness-alpha``.

    ``--candidate-frac F`` (DESIGN.md §10) turns on the two-stage selection
    funnel: a cheap loss/latency/availability prefilter keeps Q = F·C
    candidates, and the eq.-(14) kernel + k-DPP spectral cache live on the
    Q×Q block — the O(C³) eigh and the C×C Gram never happen (the
    million-client regime).  Composes with every flag above.

    ``--faults NAME`` injects the named fault model (DESIGN.md §11):
    per-client dropout, NaN/garbage/sign-flip corruption, shard blackout.
    ``--aggregator {mean,clipped_mean,trimmed_mean}`` picks the robust
    aggregation mode that screens/clips the faulty updates.  With
    ``--ckpt-every N`` and ``--ckpt DIR`` the full ``ServerState`` snapshots
    every N rounds and a re-launch resumes bit-identically from the latest
    snapshot.

    ``--local-algo {fedavg,fedprox,feddyn}`` (DESIGN.md §12) swaps the
    client-side objective without touching any of the above: e.g.
    ``--local-algo fedprox --prox-mu 0.01`` adds the proximal drift
    penalty, ``--local-algo feddyn --feddyn-alpha 0.01`` carries a
    per-client linear-penalty state across rounds (client-sharded,
    checkpointed with the ServerState).  Composes with every flag above.
    """
    mesh = None
    shard_clients = getattr(args, "shard_clients", 0)
    cohort_cap = getattr(args, "cohort_cap", None)
    staleness_bound = getattr(args, "staleness_bound", None)
    if shard_clients:
        if args.clients % shard_clients:
            raise SystemExit(
                f"--clients={args.clients} must be divisible by "
                f"--shard-clients={shard_clients}"
            )
        mesh = make_client_mesh(shard_clients)
    elif cohort_cap is not None:
        raise SystemExit("--cohort-cap requires --shard-clients")
    elif staleness_bound is not None:
        raise SystemExit("--staleness-bound requires --shard-clients")
    if getattr(args, "ckpt_every", None) is not None and not args.ckpt:
        raise SystemExit("--ckpt-every requires --ckpt DIR")
    spec = get_arch(args.arch)
    cfg = spec.model.reduced(param_dtype="float32", dtype="float32", remat=False)
    params = T.init_params(jax.random.key(args.seed), cfg)
    clients = _token_clients(cfg, args.clients, args.docs_per_client, args.seq)
    c, n_docs, _ = clients.shape
    num_topics = min(10, args.clients)
    # per-doc topic labels (one topic per client) — the engine's GEMD then
    # measures how topic-representative each selected cohort is
    topics = np.stack(
        [np.full((n_docs,), ci % num_topics, np.int32) for ci in range(c)]
    )

    # --- Alg. 1 init: profile every client once, build the eq.-14 kernel ---
    feats = []
    feat_fn = jax.jit(lambda p, xs: T.features(cfg, p, xs)[1].mean(0))
    for ci in range(c):
        feats.append(feat_fn(params, jnp.asarray(clients[ci][: min(8, n_docs)])))
    profiles = jnp.stack(feats)
    strategy = make_strategy(args.selection)

    loss_fn = lambda p, x, y: T.lm_loss(cfg, p, x)  # topics only feed GEMD
    telemetry_path = getattr(args, "telemetry", None)
    flcfg = engine_lib.FLConfig(
        num_clients=c,
        clients_per_round=args.per_round,
        local_batch_size=args.local_batch,
        local_steps=args.local_steps,
        sample_with_replacement=True,
        lr=spec.fl.lr,
        rounds=args.rounds,
        eval_every=max(args.log_every, 1),
        num_classes=num_topics,
        seed=args.seed,
        cohort_cap=cohort_cap,
        staleness_bound=staleness_bound,
        staleness_decay=getattr(args, "staleness_decay", "polynomial"),
        staleness_alpha=getattr(args, "staleness_alpha", 0.5),
        scenario=getattr(args, "scenario", None),
        candidate_frac=getattr(args, "candidate_frac", None),
        faults=getattr(args, "faults", None),
        aggregator=getattr(args, "aggregator", "mean"),
        ckpt_every=getattr(args, "ckpt_every", None),
        local_algo=getattr(args, "local_algo", "fedavg"),
        prox_mu=getattr(args, "prox_mu", None),
        feddyn_alpha=getattr(args, "feddyn_alpha", None),
        telemetry=telemetry_path is not None,
    )
    sink = None
    if telemetry_path:
        sink = TelemetrySink(telemetry_path)
        sink.write_manifest(
            config=dataclasses.asdict(flcfg), mesh=mesh,
            extra={"mode": "fl", "arch": args.arch,
                   "selection": args.selection},
        )
    state = engine_lib.init_server_state(
        flcfg, params, loss_fn, None, clients, topics,
        strategy=strategy, profiles=profiles, losses=jnp.ones((c,)),
        mesh=mesh,
    )
    if flcfg.candidate_frac is not None:
        print(f"[fl:{args.selection}] funnel: C={c} -> "
              f"Q={flcfg.candidate_count()} candidates "
              f"(kernel {state.kernel.shape})")
    round_fn = engine_lib.make_round_fn(flcfg, loss_fn, (strategy,), mesh=mesh)
    # crash-resume (DESIGN.md §11): with --ckpt-every the checkpoint dir
    # holds full ServerState snapshots, so a re-launch picks up from the
    # latest one and runs only the remaining rounds — bit-identical to an
    # uninterrupted run
    start = 0
    if flcfg.ckpt_every is not None and args.ckpt:
        step = latest_step(args.ckpt)
        if step is not None:
            state = engine_lib.restore_server_state(args.ckpt, state, step=step)
            if mesh is not None:
                state = engine_lib.shard_server_state(state, mesh)
            start = int(jax.device_get(state.round))
            print(f"[fl:{args.selection}] resumed round {start} from "
                  f"{args.ckpt}/step_{step:08d}")
    remaining = max(args.rounds - start, 0)
    with obs_tracing_lib.trace(getattr(args, "profile_dir", None)):
        if flcfg.ckpt_every is not None and args.ckpt:
            state, outs = engine_lib.run_checkpointed(
                round_fn, state, remaining, ckpt_dir=args.ckpt,
                ckpt_every=flcfg.ckpt_every, mesh=mesh, sink=sink,
            )
        else:
            state, outs = engine_lib.run_scanned(
                round_fn, state, remaining, mesh=mesh, sink=sink
            )
    sels = np.asarray(outs["selected"]) if remaining else np.zeros((0, 0), int)
    losses = np.asarray(outs["loss"]) if remaining else np.zeros((0,))
    gemds = np.asarray(outs["gemd"]) if remaining else np.zeros((0,))
    rnds = np.asarray(outs["round"]).astype(int) if remaining else np.zeros((0,), int)
    for i, t in enumerate(rnds):
        if t % args.log_every == 0 or t == args.rounds:
            print(f"[fl:{args.selection}] round {t:4d} sel={sels[i].tolist()} "
                  f"loss={losses[i]:.4f} gemd={gemds[i]:.3f}")
    if flcfg.guarded() and remaining:
        # NaN-aware summary: identity rounds and corrupt cohorts report NaN
        # round means by convention — they must not poison the run summary
        surv = np.asarray(outs["survivors"])
        best = (f"{np.nanmin(losses):.4f}" if np.isfinite(losses).any()
                else "n/a (no finite round losses)")
        print(f"[fl:{args.selection}] faults={flcfg.faults or 'none'} "
              f"aggregator={flcfg.aggregator}: "
              f"mean survivors {surv.mean():.1f}/{args.per_round}, "
              f"flagged {int(np.asarray(outs['flagged']).sum())}, "
              f"identity rounds {int(np.asarray(outs['identity_round']).sum())}, "
              f"best finite loss {best}")
    if "sim_time" in outs:
        sim = np.asarray(outs["sim_time"])
        mode = ("bounded-staleness" if staleness_bound is not None
                else "synchronous barrier")
        print(f"[fl:{args.selection}] scenario={args.scenario} ({mode}): "
              f"simulated wall clock {sim.sum():.2f} "
              f"(mean round {sim.mean():.2f})")
    if sink is not None:
        n_ev = sum(sink.event_counts.values())
        sink.close()
        print(f"[fl:{args.selection}] telemetry -> {telemetry_path} "
              f"({n_ev} events; render with "
              f"`python -m repro.analysis.report {telemetry_path}`)")
    params = state.params
    if args.ckpt and flcfg.ckpt_every is None:
        # legacy raw-params snapshot; with --ckpt-every the dir already holds
        # full ServerState snapshots (run_checkpointed) at these steps
        save(args.ckpt, args.rounds, params)
        print(f"checkpoint -> {args.ckpt}")
    return params


def run_pretrain(args):
    spec = get_arch(args.arch)
    cfg = spec.model.reduced(param_dtype="float32", dtype="float32", remat=False)
    params = T.init_params(jax.random.key(args.seed), cfg)
    opt = getattr(optim_lib, spec.optimizer)(getattr(args, "lr", 1e-3))
    opt_state = opt.init(params)
    docs, _ = make_token_dataset(
        n_docs=4096, doc_len=args.seq, vocab=min(cfg.vocab_size, 512), seed=args.seed
    )
    loss_fn = lambda p, batch: T.lm_loss(cfg, p, batch["tokens"])
    step = jax.jit(rounds_lib.build_fedsgd_step(loss_fn, opt, grad_clip=1.0))
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(1, args.steps + 1):
        idx = rng.integers(0, len(docs), size=args.local_batch)
        params, opt_state, loss = step(params, opt_state, {"tokens": jnp.asarray(docs[idx])})
        if i % args.log_every == 0 or i == args.steps:
            tps = i * args.local_batch * args.seq / (time.time() - t0)
            print(f"[pretrain] step {i:5d} loss={float(loss):.4f} tok/s={tps:,.0f}")
    if args.ckpt:
        save(args.ckpt, args.steps, {"params": params, "opt": opt_state})
        print(f"checkpoint -> {args.ckpt}")
    return params


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="smollm-360m")
    ap.add_argument("--mode", choices=("fl", "pretrain"), default="fl")
    ap.add_argument("--selection", default="fl-dp3s")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--per-round", type=int, default=4)
    ap.add_argument("--docs-per-client", type=int, default=16)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--shard-clients", type=int, default=0,
                    help="shard the client axis over an N-device mesh "
                         "(FL mode; DESIGN.md §8)")
    ap.add_argument("--cohort-cap", type=int, default=None,
                    help="capacity-slot scheduling: max cohort clients "
                         "trained per shard (requires --shard-clients; "
                         ">= min(--per-round, clients/shards); the natural "
                         "setting is --per-round)")
    ap.add_argument("--scenario", choices=SCENARIO_NAMES, default=None,
                    help="system-heterogeneity scenario (DESIGN.md §9): "
                         "per-client latency model + optional availability "
                         "masks; prices a simulated round wall clock")
    ap.add_argument("--staleness-bound", type=int, default=None,
                    help="bounded-staleness aggregation: max rounds a shard "
                         "may lag (requires --shard-clients and --scenario; "
                         "0 = synchronous semantics)")
    ap.add_argument("--staleness-decay", choices=DECAY_FAMILIES,
                    default="polynomial",
                    help="staleness-decay weighting family for stale "
                         "contributions")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="decay rate for polynomial/exponential staleness "
                         "weighting")
    ap.add_argument("--candidate-frac", type=float, default=None,
                    help="two-stage selection funnel (DESIGN.md §10): keep "
                         "Q = F*C prefilter candidates and run the DPP on "
                         "the QxQ block only (F in (0, 1]; 1.0 is "
                         "bit-identical to no funnel)")
    ap.add_argument("--faults", choices=FAULT_NAMES, default=None,
                    help="fault-injection model (DESIGN.md §11): per-client "
                         "dropout, NaN/garbage/sign-flip corruption, shard "
                         "blackout — drawn jit-level off the carried key")
    ap.add_argument("--aggregator", choices=AGGREGATORS, default="mean",
                    help="aggregation mode: mean (eq. 6), clipped_mean "
                         "(norm-clip outliers to the cohort-median "
                         "threshold), trimmed_mean (reject outliers)")
    ap.add_argument("--local-algo", choices=ALGO_NAMES, default="fedavg",
                    help="local-update algorithm (DESIGN.md §12): fedavg "
                         "(plain SGD), fedprox (proximal drift penalty), "
                         "feddyn (per-client linear-penalty state)")
    ap.add_argument("--prox-mu", type=float, default=None,
                    help="fedprox proximal coefficient mu (requires "
                         "--local-algo fedprox)")
    ap.add_argument("--feddyn-alpha", type=float, default=None,
                    help="feddyn penalty coefficient alpha (requires "
                         "--local-algo feddyn)")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="snapshot the full ServerState to --ckpt every N "
                         "rounds; a re-launch resumes from the latest "
                         "snapshot bit-identically (requires --ckpt)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write JSONL telemetry (run manifest + per-round "
                         "diagnostics, DESIGN.md §14) to PATH; also turns "
                         "on the in-program Telemetry outputs "
                         "(FLConfig.telemetry)")
    ap.add_argument("--profile-dir", default=None, metavar="PATH",
                    help="capture a jax.profiler trace of the run into PATH "
                         "(TensorBoard-loadable)")
    args = ap.parse_args()
    use_compile_cache()
    (run_fl if args.mode == "fl" else run_pretrain)(args)


if __name__ == "__main__":
    main()
