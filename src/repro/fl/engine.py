"""Functional federation engine — N rounds as one compiled program.

The legacy :class:`~repro.fl.trainer.FLTrainer` runs Algorithm 1 as a host
Python loop: every round pays host↔device round-trips for selection, the loss
refresh, GEMD, and eval, and every (strategy, seed) pair re-runs the whole
loop serially.  This module replaces that with a **pure state machine**
(DESIGN.md §7):

* :class:`ServerState` — one pytree holding everything the server evolves:
  global params, the PRNG key, the profile kernel, last-known local losses,
  the (host-prefitted) cluster labels, the simulated client shards, and the
  round counter.  Because *all* fields are concrete arrays, the state can be
  carried through ``lax.scan`` and stacked/vmapped across seeds and
  strategies.
* :func:`make_round_fn` — builds the pure ``round_fn(state, _) -> (state,
  metrics)`` for a static :class:`FLConfig`: select cohort (via the pure
  ``select_fn`` layer of ``repro.core.selection``, dispatched through
  ``lax.switch`` on ``state.strategy_index``) → build local batches → Mode-A
  round step (eq. 3-6) → refresh last-known losses → GEMD → (conditional)
  eval.  Zero host synchronisation anywhere.
* :func:`run_scanned` — compiles ``num_rounds`` applications of ``round_fn``
  into a single ``lax.scan``; per-round metrics come back as stacked scan
  outputs (one device→host transfer for the whole run).
* :func:`run_many` — vmaps ``run_scanned`` over a stacked batch of states,
  so S seeds × K strategies of the paper protocol execute as **one** XLA
  program (the Fig.-1 / Table-1 sweep workload).

Host-only work (agglomerative cluster fitting, profile refresh for
``reprofile_every``) happens *between* scans: callers run scan segments and
refresh state on the segment boundary (see ``FLTrainer.run``).  The k-DPP
**spectral cache** (``ServerState.eig_state``, DESIGN.md §6) follows the same
lifecycle: :func:`init_server_state` pays the one O(C³) ``eigh``, reprofile
boundaries rebuild it together with the kernel, and the scanned round only
ever draws from it — O(k²·C) per round instead of an in-scan decomposition.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def _checked_shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off: the slot and
    stale bodies return psum'd values whose replication the checker cannot
    infer statically."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

from repro import checkpoint as checkpoint_lib
from repro.core import dpp as dpp_lib
from repro.core import metrics as metrics_lib
from repro.core import profiles as profiles_lib
from repro.core import selection as selection_lib
from repro.core import similarity as similarity_lib
from repro.fl import faults as faults_lib
from repro.fl import local_algos as local_algos_lib
from repro.fl import rounds as rounds_lib
from repro.fl import scenarios as scenarios_lib
from repro.fl import staleness as staleness_lib
from repro.launch.sharding import CLIENT_AXIS, client_axis_spec
from repro.obs import sink as obs_sink_lib
from repro.obs import telemetry as obs_telemetry_lib
from repro.obs import tracing as obs_tracing_lib

__all__ = [
    "FLConfig",
    "ServerState",
    "CLIENT_AXIS",
    "make_round_fn",
    "run_scanned",
    "run_many",
    "run_checkpointed",
    "save_server_state",
    "restore_server_state",
    "stack_states",
    "unstack_outputs",
    "init_server_state",
    "shard_server_state",
    "history_from_outputs",
    "funnel_fields",
    "candidate_profile_block",
]

PyTree = Any


@dataclasses.dataclass
class FLConfig:
    """Static federation protocol configuration (hashable trace constants)."""

    num_clients: int = 100
    clients_per_round: int = 10
    local_epochs: int = 2  # E in eq. (3)
    local_batch_size: Optional[int] = None  # None = full-batch GD (paper eq. 4)
    lr: float = 0.05
    rounds: int = 100
    eval_every: int = 5
    num_classes: int = 10
    seed: int = 0
    reprofile_every: Optional[int] = None  # beyond-paper: refresh profiles
    use_pallas_kernel: bool = False  # pairwise distances through Pallas
    grad_clip: Optional[float] = None  # stabilises late-round full-batch SGD
    local_steps: Optional[int] = None  # explicit steps/round (token workloads)
    sample_with_replacement: bool = False  # iid batch draws instead of perms
    # Capacity-slot scheduling (DESIGN.md §8, sharded path only): max cohort
    # clients trained per shard.  None = legacy resident execution (every
    # resident computes a possibly-zero-weighted update); an int packs each
    # shard's selected residents into cap = min(C_loc, cohort_cap) slots so
    # k ≪ C cohorts stop paying D·(C/D) redundant local updates.  Must be
    # >= min(clients_per_round, C_loc) so no shard can overflow its slots.
    cohort_cap: Optional[int] = None
    # Bounded-staleness aggregation (DESIGN.md §9, sharded path only).
    # None = synchronous psum barrier; an int s lets shards that miss the
    # scenario's round deadline contribute eq.-(6) partial sums computed
    # against params from round t−s_d (s_d <= s, ring buffer in
    # ServerState.param_hist) weighted by the staleness-decay family below.
    # s = 0 reduces bit-identically to the synchronous sharded round.
    # Requires a mesh (make_round_fn validates) and a `scenario`; mutually
    # exclusive with cohort_cap (validated here, not inside jit tracing).
    staleness_bound: Optional[int] = None
    # one default across every surface (FLConfig, train.py --staleness-decay,
    # dryrun): polynomial (1+s)^-alpha, the standard stale-gradient weighting
    staleness_decay: str = "polynomial"  # constant | polynomial | exponential
    staleness_alpha: float = 0.5  # decay rate for polynomial/exponential
    # System-heterogeneity scenario (repro.fl.scenarios registry): drives
    # per-client latency draws (simulated round wall clock in the metrics,
    # straggler/staleness dynamics when staleness_bound is set) and, for
    # scenarios with an availability model, availability-masked selection.
    scenario: Optional[str] = None
    # Two-stage selection funnel (DESIGN.md §10): fraction of the federation
    # surviving the cheap stage-1 prefilter (loss / predicted-latency /
    # availability score, one fused top-Q).  None = no funnel; with a float
    # in (0, 1], Q = candidate_count() candidates carry the (Q, Q) eq.-(14)
    # kernel + spectral cache — the O(C³) eigh and the C×C Gram disappear
    # (the million-client regime).  Candidates are fixed per reprofile
    # segment, so the spectral cache stays valid between boundaries.
    candidate_frac: Optional[float] = None
    # Fault tolerance (DESIGN.md §11).  ``faults`` names a
    # repro.fl.faults.FAULT_MODELS entry injecting per-round client failures
    # (dropout / NaN / garbage / sign-flip / shard blackout) from a salted
    # fold_in stream — faults=None never touches the key chain, so
    # fault-free configs stay bit-identical to the pre-fault engine.
    faults: Optional[str] = None
    # Robust aggregation mode (repro.fl.faults.AGGREGATORS): "mean" is the
    # plain eq.-(6) weighted sum (vulnerable control — a delivered NaN or
    # norm-exploded update flows straight in); "clipped_mean" rescales
    # over-norm deltas to robust_norm_mult × the cohort's median update
    # norm; "trimmed_mean" rejects them (weight 0, safe_div renormalises).
    # Both robust modes always reject non-finite updates and flag offenders
    # for quarantine.  Any aggregator != "mean" (or any fault model) turns
    # the update-validation guard on.
    aggregator: str = "mean"
    robust_norm_mult: float = 3.0  # clip/trim threshold × cohort median norm
    # survivors floor: a guarded round whose weighted sum retains fewer
    # clients becomes an identity round (params carried over, recorded in
    # the scan metrics) instead of aggregating noise/zeros
    min_survivors: int = 1
    # rounds a flagged client is excluded from selection (via the
    # select_avail_fn availability hook); 0 disables the cooldown
    quarantine_rounds: int = 5
    # run_checkpointed snapshot period (rounds); None = no snapshots
    ckpt_every: Optional[int] = None
    # Local-update algorithm (DESIGN.md §12, repro.fl.local_algos registry):
    # what each selected client computes.  "fedavg" is plain local SGD —
    # bit-identical to the pre-registry engine in every mode; "fedprox"
    # folds the proximal pull mu·(w − w_global) into each per-step grad;
    # "feddyn" carries a per-client linear-penalty state (a client-sharded
    # ServerState field) correcting historical drift.  Orthogonal to every
    # other flag: sharding, slots, staleness, faults, and the funnel accept
    # any registered algorithm without forking round bodies.
    local_algo: str = "fedavg"
    prox_mu: Optional[float] = None  # fedprox proximal strength (>= 0)
    feddyn_alpha: Optional[float] = None  # feddyn penalty strength (> 0)
    # In-program telemetry (DESIGN.md §14, repro.obs): when True the round
    # emits a per-round Telemetry pytree of selection / robustness /
    # staleness diagnostics alongside the scan outputs, drained to a JSONL
    # sink at chunk boundaries.  STATIC flag with the repo-wide bit-identity
    # contract: telemetry=False lowers the exact pre-telemetry program (no
    # extra outputs, no key-stream or state changes), and telemetry=True
    # only *adds* output leaves — the carried state and every shared metric
    # stay bit-identical.
    telemetry: bool = False

    def local_algo_obj(self) -> "local_algos_lib.LocalAlgo":
        """The configured :class:`repro.fl.local_algos.LocalAlgo` instance
        (combos already validated by ``__post_init__``)."""
        return local_algos_lib.algo_from_config(
            self.local_algo, self.prox_mu, self.feddyn_alpha
        )

    def guarded(self) -> bool:
        """True when the update-validation / quarantine layer is active."""
        return self.faults is not None or self.aggregator != "mean"

    def candidate_count(self) -> int:
        """Q — stage-1 survivors; ``round(C·frac)`` clamped to
        ``[clients_per_round, num_clients]`` (a cohort must always fit)."""
        assert self.candidate_frac is not None
        q = int(round(self.num_clients * self.candidate_frac))
        return max(self.clients_per_round, min(q, self.num_clients))

    def __post_init__(self):
        # flag-combination contract: every invalid combo dies HERE with one
        # clear ValueError, never inside jit tracing
        if self.staleness_bound is not None:
            if self.staleness_bound < 0:
                raise ValueError(
                    f"staleness_bound={self.staleness_bound} must be >= 0"
                )
            if self.cohort_cap is not None:
                raise ValueError(
                    f"cohort_cap={self.cohort_cap} is incompatible with "
                    f"staleness_bound={self.staleness_bound}: capacity-slot "
                    "compaction assumes a synchronous cohort (every slot "
                    "trains on round-t params) — drop one of the two flags"
                )
            if self.scenario is None:
                raise ValueError(
                    f"staleness_bound={self.staleness_bound} requires a "
                    "latency scenario (set FLConfig.scenario / --scenario): "
                    "without a latency model no shard ever goes stale"
                )
            if self.staleness_decay not in staleness_lib.DECAY_FAMILIES:
                raise ValueError(
                    f"unknown staleness_decay {self.staleness_decay!r}; "
                    f"known: {staleness_lib.DECAY_FAMILIES}"
                )
            if self.staleness_alpha < 0:
                raise ValueError(
                    f"staleness_alpha={self.staleness_alpha} must be >= 0"
                )
        if self.scenario is not None:
            scenarios_lib.get_scenario(self.scenario)  # unknown name raises
        if self.candidate_frac is not None:
            if not (0.0 < self.candidate_frac <= 1.0):
                raise ValueError(
                    f"candidate_frac={self.candidate_frac} must be in (0, 1] "
                    "(1.0 = degenerate funnel, bit-identical to no funnel)"
                )
        if self.aggregator not in faults_lib.AGGREGATORS:
            raise ValueError(
                f"unknown aggregator {self.aggregator!r}; "
                f"known: {list(faults_lib.AGGREGATORS)}"
            )
        if self.faults is not None:
            faults_lib.get_fault_model(self.faults)  # unknown name raises
        if self.guarded():
            if self.robust_norm_mult <= 0:
                raise ValueError(
                    f"robust_norm_mult={self.robust_norm_mult} must be > 0"
                )
            if self.min_survivors < 1:
                raise ValueError(
                    f"min_survivors={self.min_survivors} must be >= 1: with "
                    "0 survivors the weighted sum is all-zero and the "
                    "aggregate would silently zero the params — the floor "
                    "exists so that round degrades to identity instead"
                )
            if self.min_survivors > self.clients_per_round:
                raise ValueError(
                    f"min_survivors={self.min_survivors} > clients_per_round"
                    f"={self.clients_per_round}: every round would be an "
                    "identity round"
                )
            if self.quarantine_rounds < 0:
                raise ValueError(
                    f"quarantine_rounds={self.quarantine_rounds} must be >= 0"
                )
        if self.ckpt_every is not None and self.ckpt_every < 1:
            raise ValueError(
                f"ckpt_every={self.ckpt_every} must be >= 1 (None disables "
                "snapshots)"
            )
        if self.local_algo not in local_algos_lib.LOCAL_ALGOS:
            raise ValueError(
                f"unknown local algorithm {self.local_algo!r}; "
                f"known: {list(local_algos_lib.ALGO_NAMES)}"
            )
        if self.prox_mu is not None:
            if self.local_algo != "fedprox":
                raise ValueError(
                    f"prox_mu={self.prox_mu} only applies to "
                    f"local_algo='fedprox' (got {self.local_algo!r})"
                )
            if self.prox_mu < 0:
                raise ValueError(f"prox_mu={self.prox_mu} must be >= 0")
        if self.feddyn_alpha is not None:
            if self.local_algo != "feddyn":
                raise ValueError(
                    f"feddyn_alpha={self.feddyn_alpha} only applies to "
                    f"local_algo='feddyn' (got {self.local_algo!r})"
                )
            if self.feddyn_alpha <= 0:
                raise ValueError(
                    f"feddyn_alpha={self.feddyn_alpha} must be > 0"
                )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServerState:
    """Everything the server evolves across rounds, as one pytree.

    Leading-axis stacking of several states (see :func:`stack_states`) yields
    a batch state that :func:`run_many` vmaps over — per-seed client shards,
    per-seed params, and per-combination strategy indices all ride along.
    """

    params: PyTree  # global model
    key: jax.Array  # server PRNG key
    round: jax.Array  # int32 scalar, rounds completed
    losses: jax.Array  # (C,) last-known local losses
    kernel: jax.Array  # eq.-(14) DPP kernel: (C, C), or (Q, Q) under funnel
    profiles: jax.Array  # (C, Q_f) eq.-(11) client profiles
    eig_state: dpp_lib.KDPPSamplerState  # spectral cache of ``kernel``
    cluster_labels: jax.Array  # (C,)/(Q,) int32, host-prefitted (0 if unused)
    client_xs: jax.Array  # (C, n_c, ...) simulated client shards
    client_ys: jax.Array  # (C, n_c)
    client_sizes: jax.Array  # (C,) n_c
    client_label_dists: jax.Array  # (C, num_classes)
    global_label_dist: jax.Array  # (num_classes,)
    strategy_index: jax.Array  # int32 scalar into the round_fn's strategies
    # Bounded-staleness bookkeeping (DESIGN.md §9) — None on synchronous
    # configs, so the pytree stays unchanged for every existing path:
    param_hist: Optional[PyTree] = None  # (s+1, ...) ring of param snapshots
    shard_staleness: Optional[jax.Array] = None  # (D,) int32 per-shard lag
    # Two-stage funnel (DESIGN.md §10) — None on unfunneled configs.  When
    # set: (Q,) int32 ascending global ids of the stage-1 survivors, and the
    # kernel / eig_state / cluster_labels above live on the Q-block.  Fixed
    # per reprofile segment (rebuilt with the profiles), replicated.
    candidates: Optional[jax.Array] = None
    # Quarantine cooldowns (DESIGN.md §11) — None unless the update-
    # validation guard is on (cfg.guarded()).  (C,) int32 rounds remaining
    # before a flagged client may be selected again; feeds selection through
    # the select_avail_fn availability hook.  Replicated (selection is
    # replicated trivia, like the staleness counters).
    quarantine: Optional[jax.Array] = None
    # Per-client local-algorithm state (DESIGN.md §12) — None unless the
    # configured algorithm is stateful (FedDyn's linear-penalty h_k).  A
    # pytree whose leaves lead with (C, ...), client-sharded like the data
    # fields (CLIENT_SHARDED_FIELDS), gathered through the slot machinery,
    # and snapshotted by checkpointing like every other leaf.
    algo_state: Optional[PyTree] = None

    @property
    def num_clients(self) -> int:
        return self.losses.shape[0]

    def selection_state(self) -> selection_lib.SelectionState:
        """The per-round :class:`~repro.core.selection.SelectionState` view.

        Under the funnel this is **candidate-space**: the O(Q) gathers of the
        per-client signals are the only per-round funnel cost, and the
        strategies then draw over Q with ``select_global_fn`` mapping the
        picks back to global ids."""
        if self.candidates is None:
            return selection_lib.SelectionState(
                kernel=self.kernel,
                losses=self.losses,
                client_sizes=self.client_sizes,
                cluster_labels=self.cluster_labels,
                eig_state=self.eig_state,
            )
        return selection_lib.SelectionState(
            kernel=self.kernel,
            losses=jnp.take(self.losses, self.candidates),
            client_sizes=jnp.take(self.client_sizes, self.candidates),
            cluster_labels=self.cluster_labels,
            eig_state=self.eig_state,
            candidates=selection_lib.CandidateSet(ids=self.candidates),
        )


# ----------------------------------------------------------------- batches


def _num_batches(n_c: int, batch_size: int) -> int:
    """Minibatches per local epoch: ``max(1, n_c // b)`` (drop-remainder, at
    least one batch).  The ONE definition shared by :func:`_steps_per_round`
    and :func:`batches_from_indices` — sizing the jitted scan and slicing the
    data must agree or per-step batches silently drift."""
    return max(1, n_c // batch_size)


def _steps_per_round(cfg: FLConfig, n_c: int) -> int:
    if cfg.local_steps is not None:
        return cfg.local_steps
    if cfg.local_batch_size is None:
        return cfg.local_epochs  # E full-batch passes (paper eq. 4)
    return cfg.local_epochs * _num_batches(n_c, cfg.local_batch_size)


def batch_indices_from_keys(cfg: FLConfig, keys, n_c: int):
    """Per-client random *index plans*: ``keys[i]`` drives client i's draws.

    Returns ``None`` for full-batch mode (no randomness), the (M, steps, B)
    replacement draws, or the (M, n_c) epoch permutation.  Split from
    :func:`batches_from_indices` so the mesh-sharded round can generate every
    plan at the jit level (replicated, tiny int arrays) and keep only the
    data slicing inside its ``shard_map``: every layout then derives a
    client's batches from the same key, outside any per-shard program.
    """
    if cfg.local_batch_size is None:
        return None
    steps = _steps_per_round(cfg, n_c)
    b = cfg.local_batch_size
    if cfg.sample_with_replacement:
        # token-style workloads: iid uniform draws per step (replacement)
        return jax.vmap(lambda k: jax.random.randint(k, (steps, b), 0, n_c))(keys)
    return jax.vmap(lambda k: jax.random.permutation(k, n_c))(keys)


def batches_from_indices(cfg: FLConfig, ids, xs, ys):
    """Apply :func:`batch_indices_from_keys` plans to M clients' data."""
    n_c = xs.shape[1]
    steps = _steps_per_round(cfg, n_c)
    if cfg.local_batch_size is None:
        # full-batch: each local step sees the whole local dataset
        xb = jnp.broadcast_to(xs[:, None], (xs.shape[0], steps) + xs.shape[1:])
        yb = jnp.broadcast_to(ys[:, None], (ys.shape[0], steps) + ys.shape[1:])
        return (xb, yb)
    b = cfg.local_batch_size
    if cfg.sample_with_replacement:
        xb = jax.vmap(jnp.take, in_axes=(0, 0, None))(xs, ids, 0)
        yb = jax.vmap(jnp.take, in_axes=(0, 0, None))(ys, ids, 0)
        return (xb, yb)
    # clamp to the local dataset: n_c < b means ONE short full batch (the
    # same count _num_batches floors to), not an impossible (nb, b) reshape
    b = min(b, n_c)
    nb = _num_batches(n_c, b)
    perm = ids
    xs = jnp.take_along_axis(
        xs, perm.reshape(perm.shape + (1,) * (xs.ndim - 2)), axis=1
    )
    ys = jnp.take_along_axis(ys, perm, axis=1)
    xb = xs[:, : nb * b].reshape(xs.shape[0], nb, b, *xs.shape[2:])
    yb = ys[:, : nb * b].reshape(ys.shape[0], nb, b)
    reps = cfg.local_epochs
    xb = jnp.tile(xb, (1, reps) + (1,) * (xb.ndim - 2))
    yb = jnp.tile(yb, (1, reps, 1))
    return (xb, yb)


def client_batches_from_keys(cfg: FLConfig, keys, xs, ys):
    """Per-client batch slicing for an explicit (M,) key-per-client vector."""
    return batches_from_indices(
        cfg, batch_indices_from_keys(cfg, keys, xs.shape[1]), xs, ys
    )


def make_client_batches(cfg: FLConfig, key, client_xs, client_ys, sel):
    """Slice the selected clients' data into (C_p, steps, B, ...) batches.

    Pure/jittable; shared by the scanned engine and the legacy trainer loop
    so both execute bit-identical batch construction.
    """
    xs = jnp.take(client_xs, sel, axis=0)
    ys = jnp.take(client_ys, sel, axis=0)
    keys = jax.random.split(key, xs.shape[0])
    return client_batches_from_keys(cfg, keys, xs, ys)


# ---------------------------------------------------------------- round_fn

# fold_in salt branching the scenario's environment stream (latency /
# availability draws) off the carried server key WITHOUT consuming a split:
# the selection/batch key streams stay bit-identical with or without a
# scenario attached.
_ENV_SALT = 0x5CE7A210


def make_round_fn(
    cfg: FLConfig,
    loss_fn: Callable,  # loss_fn(params, x, y) -> scalar
    strategies: Sequence[selection_lib.SelectionStrategy],
    accuracy_fn: Optional[Callable] = None,
    eval_data: Optional[Tuple[jax.Array, jax.Array]] = None,
    sequential_clients: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
) -> Callable[[ServerState, Any], Tuple[ServerState, Dict[str, jax.Array]]]:
    """Build the pure per-round transition ``round_fn(state, _)``.

    ``strategies`` is the static tuple the traced ``state.strategy_index``
    dispatches over via ``lax.switch`` — pass one strategy for single runs or
    the full method grid for :func:`run_many`.  ``accuracy_fn(params, xs, ys)``
    is evaluated every ``cfg.eval_every`` rounds under ``lax.cond`` (NaN on
    the other rounds); with ``eval_data=None`` it scores the union training
    set (the paper's Fig.-1 protocol).

    With ``mesh`` set (DESIGN.md §8) the local-update core runs as a
    ``shard_map`` over the mesh's ``client_axis``: every device executes
    local updates for the clients *resident* in its shard (cohort membership
    becomes a weight mask, so there is no cross-device gather of client
    data), and eq.-(6) aggregation happens as per-shard partial weighted
    sums combined with ``psum`` — the parameter tree is never all-gathered.
    Selection stays replicated (same kernel + key on every device ⇒
    bit-identical cohorts vs. the single-device path); per-client losses are
    refreshed in place on their home shard.  The state must be laid out with
    :func:`shard_server_state` over the same mesh/axis.

    ``cfg.cohort_cap`` switches the sharded body to capacity-slot execution:
    each shard packs its selected residents into ``cap = min(C_loc,
    cohort_cap)`` slots (slot table computed at the jit level from the
    replicated cohort; batch-index plans are generated **sized to slots**,
    ``D·cap`` rows instead of ``C``), runs local updates only over slots,
    and scatters losses back to resident layout — same selection, same
    single-psum aggregation, ``C_loc/cap``× less local-update work for
    k ≪ C cohorts.  Ignored without a mesh (the single-device body already
    gathers exactly the k selected clients).

    ``cfg.scenario`` attaches a system-heterogeneity model (DESIGN.md §9):
    per-round latency draws priced into a ``sim_time`` metric, and — for
    scenarios with an availability model — selection routed through the
    strategies' ``select_avail_fn`` hook (cohorts drawn from available
    clients only; the mask rides the outputs as ``avail``).
    ``cfg.staleness_bound`` additionally relaxes the sharded round's psum
    barrier to bounded-staleness aggregation: shards that miss the
    scenario's deadline contribute eq.-(6) partials computed against ring-
    buffered params from round ``t − s_d`` (``s_d ≤ staleness_bound``),
    scaled by the ``cfg.staleness_decay`` family — same single psum, with
    ``staleness_bound = 0`` reducing bit-identically to the synchronous
    sharded round.  Requires a mesh and a scenario (validated here / in
    ``FLConfig``); the state must carry the staleness fields
    (:func:`init_server_state` builds them).
    """
    strategies = tuple(strategies)
    k = cfg.clients_per_round
    if mesh is not None and cfg.cohort_cap is not None:
        n_shards = mesh.shape[client_axis]
        c_loc_cfg = cfg.num_clients // n_shards
        if cfg.cohort_cap < min(k, c_loc_cfg):
            raise ValueError(
                f"cohort_cap={cfg.cohort_cap} < min(clients_per_round={k}, "
                f"C_loc={c_loc_cfg}): a shard could hold more cohort members "
                "than slots (clients would be silently dropped)"
            )
    if cfg.staleness_bound is not None and mesh is None:
        raise ValueError(
            f"staleness_bound={cfg.staleness_bound} requires the mesh-sharded "
            "engine (pass mesh=...; launchers: --staleness-bound needs "
            "--shard-clients): staleness is a per-shard property"
        )
    scen = (
        scenarios_lib.get_scenario(cfg.scenario)
        if cfg.scenario is not None
        else None
    )
    avail_aware = scen is not None and scen.availability is not None
    # Fault tolerance (DESIGN.md §11): the fault model's per-round draws and
    # the update-validation guard.  guard_on also without a fault model —
    # the robust aggregators screen honest-path updates too.  Quarantine
    # feeds selection through the same availability hook as the scenario, so
    # guarded configs route selection avail-aware even without a scenario.
    fault_model = (
        faults_lib.get_fault_model(cfg.faults) if cfg.faults is not None
        else None
    )
    guard_on = cfg.guarded()
    lemons = (
        faults_lib.lemon_mask(fault_model, cfg.num_clients)
        if fault_model is not None else None
    )
    guard = (
        faults_lib.make_update_guard(
            cfg.aggregator, cfg.robust_norm_mult,
            garbage_scale=(
                fault_model.garbage_scale if fault_model is not None else 1.0
            ),
            inject=fault_model is not None,
        )
        if guard_on else None
    )
    route_avail = avail_aware or guard_on
    batched_loss = lambda p, batch: loss_fn(p, batch[0], batch[1])
    loss_of = jax.vmap(loss_fn, in_axes=(None, 0, 0))
    # the local-update algorithm is a static trace constant (DESIGN.md §12):
    # every round body hands it to the rounds builders; a stateful one
    # threads ServerState.algo_state through gather → update → masked
    # write-back without forking any body
    algo = cfg.local_algo_obj()
    stateful = algo.stateful
    # selection dispatches through select_global_fn — the ONE canonical
    # entry point ``(key, state, k, avail=None)``: without candidates it is
    # exactly the legacy draw; with them the draw runs in candidate space
    # (the avail mask gathered through the shared candidate_availability
    # guard) and the picks come back as global ids, so everything downstream
    # of ``sel`` — batches, aggregation, loss refresh, GEMD, slots,
    # staleness — is untouched by funnelling.  ``avail`` defaulting to None
    # makes the same branch tuple serve both call arities, so avail-routed
    # and plain configs share one construction.
    branches = tuple(
        functools.partial(
            lambda strat, key, sstate, avail=None: strat.select_global_fn(
                key, sstate, k, avail
            ),
            strat,
        )
        for strat in strategies
    )
    steps_of = lambda state: _steps_per_round(cfg, state.client_xs.shape[1])

    def _algo_writeback(full_states, sel_or_mask, cand_states, refresh, scatter):
        """Masked per-client algorithm-state refresh (DESIGN.md §12): a
        client's state advances iff its update was kept (cohort member,
        delivered, unflagged, round above the survivors floor).

        ``scatter=True`` — cohort layout: ``cand_states`` lead with (k, ...)
        and land at ``sel_or_mask`` (the cohort ids); ``scatter=False`` —
        resident layout: ``cand_states`` match ``full_states`` and
        ``refresh`` selects rows in place."""

        def bmask(m, x):
            return m.reshape(m.shape + (1,) * (x.ndim - m.ndim))

        if scatter:
            sel = sel_or_mask
            old = jax.tree_util.tree_map(
                lambda s: jnp.take(s, sel, axis=0), full_states
            )
            kept = jax.tree_util.tree_map(
                lambda n, o: jnp.where(bmask(refresh, n), n, o), cand_states, old
            )
            return jax.tree_util.tree_map(
                lambda full, new: full.at[sel].set(new), full_states, kept
            )
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(bmask(refresh, n), n, o),
            cand_states, full_states,
        )

    def _single_device_body(state, k_batch, sel, draws=None):
        """Cohort gather + vmapped/mapped local updates on one device."""
        batches = make_client_batches(cfg, k_batch, state.client_xs, state.client_ys, sel)
        weights = jnp.take(state.client_sizes, sel)
        round_step = rounds_lib.build_client_parallel_round(
            batched_loss, cfg.lr, steps_of(state), grad_clip=cfg.grad_clip,
            sequential_clients=sequential_clients, update_transform=guard,
            algo=algo,
        )
        g = metrics_lib.gemd(
            state.client_label_dists, state.client_sizes, sel, state.global_label_dist
        )
        state_kw = {}
        if stateful:
            state_kw["client_states"] = jax.tree_util.tree_map(
                lambda s: jnp.take(s, sel, axis=0), state.algo_state
            )
        if guard is None:
            res = round_step(state.params, batches, weights, **state_kw)
            if stateful:
                params, mean_loss, cand_states = res
                refresh = jnp.ones(sel.shape, jnp.bool_)
                algo_state = _algo_writeback(
                    state.algo_state, sel, cand_states, refresh, scatter=True
                )
            else:
                params, mean_loss = res
                algo_state = None
            # refresh last-known losses for the selected clients
            sel_losses = loss_of(
                params, jnp.take(state.client_xs, sel, 0), jnp.take(state.client_ys, sel, 0)
            )
            losses = state.losses.at[sel].set(sel_losses)
            out = (params, mean_loss, losses, g)
            return out + (algo_state,) if stateful else out
        # fault masks gathered to the cohort layout (draws are (C,) rows)
        g_args = (
            () if draws is None else tuple(jnp.take(m, sel) for m in draws)
        )
        res = round_step(state.params, batches, weights, *g_args, **state_kw)
        if stateful:
            params, mean_loss, flagged, survivors, cand_states = res
        else:
            params, mean_loss, flagged, survivors = res
        c = state.losses.shape[0]
        flagged_c = jnp.zeros((c,), jnp.bool_).at[sel].set(flagged)
        delivered = (
            jnp.take(draws.delivered, sel) if draws is not None
            else jnp.ones(sel.shape, jnp.bool_)
        )
        # refresh only trusted participants, and only when the round's
        # aggregate will actually be kept (survivors floor)
        refresh = delivered & ~flagged & (survivors >= cfg.min_survivors)
        sel_losses = loss_of(
            params, jnp.take(state.client_xs, sel, 0), jnp.take(state.client_ys, sel, 0)
        )
        keep = jnp.take(state.losses, sel)
        losses = state.losses.at[sel].set(jnp.where(refresh, sel_losses, keep))
        out = (params, mean_loss, losses, g, flagged_c, survivors)
        if stateful:
            algo_state = _algo_writeback(
                state.algo_state, sel, cand_states, refresh, scatter=True
            )
            return out + (algo_state,)
        return out

    def _resident_batch_plans(state, k_batch, sel):
        """Jit-level per-resident batch *index plans*: every client adopts
        the batch key of its cohort slot, so a selected client sees
        bit-identical batches to the gathered single-device path.  The ONE
        construction shared by the synchronous (:func:`_sharded_body`) and
        bounded-staleness (:func:`_stale_sharded_body`) resident-layout
        bodies — the cross-path bit-identical-batches parity contract lives
        here, and only data slicing / SGD scans / the psum go inside the
        shard_map, so no random bits are drawn per shard."""
        c = state.losses.shape[0]
        n_c = state.client_xs.shape[1]
        slot_full = jnp.argmax(sel[None, :] == jnp.arange(c)[:, None], axis=1)
        key_data = jax.random.key_data(jax.random.split(k_batch, k))
        client_keys = jax.random.wrap_key_data(key_data[slot_full])
        return batch_indices_from_keys(cfg, client_keys, n_c)  # (C, ...) | None

    def _sharded_body(state, k_batch, sel, draws=None):
        """shard_map core: in-place masked local updates + psum'd FedAvg.

        Random index plans come from :func:`_resident_batch_plans` (jit
        level); only data slicing, the local SGD scans, and the psum'd
        aggregation live inside the shard_map.  With the guard on, the fault
        masks (jit-level draws, resident layout) shard over the client axis
        like the index plans; validation/rejection happens inside the
        shard_map strictly before the single psum.
        """
        shard_round = rounds_lib.build_shard_cohort_round(
            batched_loss, cfg.lr, client_axis, grad_clip=cfg.grad_clip,
            sequential_clients=sequential_clients, update_transform=guard,
            algo=algo,
        )
        ids = _resident_batch_plans(state, k_batch, sel)
        n_ids = 0 if ids is None else 1
        mask_args = () if draws is None else tuple(draws)
        # algo_state shards like the data fields (resident layout); the
        # masked write-back happens inside the shard body — per-device
        # state, never psum'd
        state_args = (state.algo_state,) if stateful else ()

        def local_body(sel, params, local_xs, local_ys, local_sizes,
                       local_losses, local_dists, global_dist, *rest):
            if stateful:
                local_states, rest = rest[0], rest[1:]
            else:
                local_states = None
            local_ids = rest[:n_ids]
            fmasks = rest[n_ids:]
            c_loc = local_xs.shape[0]
            gids = lax.axis_index(client_axis) * c_loc + jnp.arange(c_loc)
            mask = jnp.any(sel[None, :] == gids[:, None], axis=1)
            batches = batches_from_indices(
                cfg, local_ids[0] if local_ids else None, local_xs, local_ys
            )
            weights = local_sizes * mask
            # GEMD (eq. 15) partials ride the round's single psum: the cohort
            # label-mix numerator/denominator over this shard's residents
            w = weights.astype(jnp.float32)
            gemd_parts = ((w[:, None] * local_dists).sum(0), jnp.sum(w))
            if guard is None:
                res = shard_round(
                    params, batches, weights, extras=gemd_parts,
                    local_states=local_states,
                )
                if stateful:
                    params, _, mean_loss, (num, den), cand_states = res
                else:
                    params, _, mean_loss, (num, den) = res
                g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
                # loss refresh stays on the client's home shard (no scatter)
                fresh = loss_of(params, local_xs, local_ys)
                losses = jnp.where(mask, fresh, local_losses)
                if stateful:
                    new_states = _algo_writeback(
                        local_states, None, cand_states, mask, scatter=False
                    )
                    return params, mean_loss, losses, g, new_states
                return params, mean_loss, losses, g
            res = shard_round(
                params, batches, weights, extras=gemd_parts, guard_args=fmasks,
                local_states=local_states,
            )
            if stateful:
                (params, _, mean_loss, (num, den), flagged, survivors,
                 cand_states) = res
            else:
                params, _, mean_loss, (num, den), flagged, survivors = res
            g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
            delivered = fmasks[0] if fmasks else jnp.ones_like(mask)
            refresh = (
                mask & delivered & ~flagged
                & (survivors >= cfg.min_survivors)
            )
            fresh = loss_of(params, local_xs, local_ys)
            losses = jnp.where(refresh, fresh, local_losses)
            if stateful:
                new_states = _algo_writeback(
                    local_states, None, cand_states, refresh, scatter=False
                )
                return params, mean_loss, losses, g, flagged, survivors, new_states
            return params, mean_loss, losses, g, flagged, survivors

        lead = P(client_axis)
        id_args = () if ids is None else (ids,)
        out = (P(), P(), lead, P())
        if guard is not None:
            out = out + (lead, P())
        if stateful:
            out = out + (lead,)
        body = _checked_shard_map(
            local_body, mesh=mesh,
            in_specs=(P(), P(), lead, lead, lead, lead, lead, P())
            + (lead,) * len(state_args)
            + (lead,) * (len(id_args) + len(mask_args)),
            out_specs=out,
        )
        return body(
            sel, state.params, state.client_xs, state.client_ys,
            state.client_sizes, state.losses, state.client_label_dists,
            state.global_label_dist, *(state_args + id_args + mask_args),
        )

    def _slot_sharded_body(state, k_batch, sel, draws=None):
        """Capacity-slot shard_map core: per-shard top-``cap`` slot gather.

        The slot table is computed at the jit level from the replicated
        cohort (``sel``): for each shard, a stable argsort over the resident
        cohort mask packs selected residents (ascending local position)
        first, padded with unselected residents up to ``cap`` — padding
        slots carry weight 0 and behave exactly like resident mode's
        zero-weighted clients, only there are ``cap`` of them instead of
        ``C_loc``.  Batch-index plans are generated sized to slots (D·cap
        keyed rows, each slot adopting its client's cohort-position key, so
        selected clients see bit-identical batches to the other paths) and
        shard over the client axis alongside the slot positions.  Inside the
        shard: slot-gather data, build slot batches, ``cap`` local SGD
        scans, the same single psum (FedAvg/loss/GEMD partials), and the
        loss refresh runs over slots only before scattering home.
        """
        c = state.losses.shape[0]
        n_c = state.client_xs.shape[1]
        n_shards = mesh.shape[client_axis]
        c_loc = c // n_shards
        cap = min(c_loc, cfg.cohort_cap)
        shard_round = rounds_lib.build_shard_cohort_round(
            batched_loss, cfg.lr, client_axis, grad_clip=cfg.grad_clip,
            sequential_clients=sequential_clients, cap=cap,
            update_transform=guard, algo=algo,
        )
        in_cohort = jnp.any(
            sel[None, :] == jnp.arange(c)[:, None], axis=1
        ).reshape(n_shards, c_loc)
        # (D, cap) local resident positions: selected-first, stable order
        slot_pos = jnp.argsort(~in_cohort, axis=1, stable=True)[:, :cap]
        slot_gid = slot_pos + jnp.arange(n_shards)[:, None] * c_loc
        slot_cohort = jnp.argmax(
            sel[None, None, :] == slot_gid[..., None], axis=-1
        )  # (D, cap) cohort position (0 for weight-0 padding slots)
        key_data = jax.random.key_data(jax.random.split(k_batch, k))
        slot_keys = jax.random.wrap_key_data(key_data[slot_cohort.reshape(-1)])
        ids = batch_indices_from_keys(cfg, slot_keys, n_c)  # (D*cap, ...) | None
        flat_pos = slot_pos.reshape(-1)  # (D*cap,)
        n_ids = 0 if ids is None else 1
        # fault masks gathered to the slot layout at the jit level (the
        # draws are (C,) resident rows; slots shard like the index plans)
        mask_args = (
            () if draws is None
            else tuple(jnp.take(m, slot_gid.reshape(-1)) for m in draws)
        )
        # resident-layout state rides into the shard body; the slot round
        # gathers it by slot_index and scatters the trained slots back
        state_args = (state.algo_state,) if stateful else ()

        def local_body(sel, slot_index, params, local_xs, local_ys,
                       local_sizes, local_losses, local_dists, global_dist,
                       *rest):
            if stateful:
                local_states, rest = rest[0], rest[1:]
            else:
                local_states = None
            slot_ids = rest[:n_ids]
            fmasks = rest[n_ids:]
            c_loc_ = local_xs.shape[0]
            gids = lax.axis_index(client_axis) * c_loc_ + jnp.arange(c_loc_)
            mask = jnp.any(sel[None, :] == gids[:, None], axis=1)
            weights = local_sizes * mask
            slot_xs = jnp.take(local_xs, slot_index, axis=0)
            slot_ys = jnp.take(local_ys, slot_index, axis=0)
            batches = batches_from_indices(
                cfg, slot_ids[0] if slot_ids else None, slot_xs, slot_ys
            )
            # GEMD (eq. 15) partials are unchanged from resident mode (the
            # resident-layout mask is already O(C_loc) trivia) and ride the
            # round's single psum
            w = weights.astype(jnp.float32)
            gemd_parts = ((w[:, None] * local_dists).sum(0), jnp.sum(w))
            if guard is None:
                res = shard_round(
                    params, batches, weights, slot_index, extras=gemd_parts,
                    local_states=local_states,
                )
                if stateful:
                    params, _, mean_loss, (num, den), cand_states = res
                else:
                    params, _, mean_loss, (num, den) = res
                g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
                # loss refresh over slots only — the cap-not-C_loc saving
                # applies to the refresh pass too; unselected residents keep
                # their last known loss (scatter of distinct local positions,
                # no collisions)
                fresh = loss_of(params, slot_xs, slot_ys)
                keep = jnp.take(local_losses, slot_index)
                slot_mask = jnp.take(mask, slot_index)
                losses = local_losses.at[slot_index].set(
                    jnp.where(slot_mask, fresh, keep)
                )
                if stateful:
                    new_states = _algo_writeback(
                        local_states, None, cand_states, mask, scatter=False
                    )
                    return params, mean_loss, losses, g, new_states
                return params, mean_loss, losses, g
            res = shard_round(
                params, batches, weights, slot_index, extras=gemd_parts,
                guard_args=fmasks, local_states=local_states,
            )
            if stateful:
                (params, _, mean_loss, (num, den), flagged, survivors,
                 cand_states) = res
            else:
                params, _, mean_loss, (num, den), flagged, survivors = res
            g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
            # fmasks are already slot-layout (gathered by slot_gid above)
            slot_delivered = (
                fmasks[0] if fmasks
                else jnp.ones(slot_index.shape, jnp.bool_)
            )
            slot_flagged = jnp.take(flagged, slot_index)
            slot_mask = jnp.take(mask, slot_index)
            refresh = (
                slot_mask & slot_delivered & ~slot_flagged
                & (survivors >= cfg.min_survivors)
            )
            fresh = loss_of(params, slot_xs, slot_ys)
            keep = jnp.take(local_losses, slot_index)
            losses = local_losses.at[slot_index].set(
                jnp.where(refresh, fresh, keep)
            )
            if stateful:
                # refresh scattered home to resident layout: residents no
                # slot covered stay un-refreshed by construction
                r_res = (
                    jnp.zeros(mask.shape, jnp.bool_)
                    .at[slot_index]
                    .set(refresh)
                )
                new_states = _algo_writeback(
                    local_states, None, cand_states, r_res, scatter=False
                )
                return params, mean_loss, losses, g, flagged, survivors, new_states
            return params, mean_loss, losses, g, flagged, survivors

        lead = P(client_axis)
        id_args = () if ids is None else (ids,)
        out = (P(), P(), lead, P())
        if guard is not None:
            out = out + (lead, P())
        if stateful:
            out = out + (lead,)
        body = _checked_shard_map(
            local_body, mesh=mesh,
            in_specs=(P(), lead, P(), lead, lead, lead, lead, lead, P())
            + (lead,) * len(state_args)
            + (lead,) * (len(id_args) + len(mask_args)),
            out_specs=out,
        )
        return body(
            sel, flat_pos, state.params, state.client_xs, state.client_ys,
            state.client_sizes, state.losses, state.client_label_dists,
            state.global_label_dist, *(state_args + id_args + mask_args),
        )

    def _stale_sharded_body(state, k_batch, sel, lat, draws=None):
        """Bounded-staleness shard_map core (DESIGN.md §9).

        Same residents, masks, batch plans, and single psum as
        :func:`_sharded_body`; the difference is each shard's *base* params
        come from the ring buffer at its staleness ``s_d`` (params of round
        ``t − s_d``), and its eq.-(6) partials are scaled by λ(s_d).  All
        staleness bookkeeping — deadline misses from the scenario's
        per-client latency draw, counter dynamics, decay weights, ring
        slots, the simulated round wall clock — is computed at the jit
        level on tiny replicated arrays; only the ring read, the SGD scans,
        and the psum live inside the shard_map.  With ``staleness_bound=0``
        every slow shard is forced to sync, λ ≡ 1, and the ring read
        returns the current params: bit-identical to the synchronous round.
        """
        bound = cfg.staleness_bound
        c = state.losses.shape[0]
        n_shards = mesh.shape[client_axis]
        c_loc = c // n_shards
        t_prev = state.round  # rounds completed; ring slot t_prev holds θ_t
        shard_round = rounds_lib.build_stale_shard_cohort_round(
            batched_loss, cfg.lr, client_axis, grad_clip=cfg.grad_clip,
            sequential_clients=sequential_clients, update_transform=guard,
            algo=algo,
        )
        in_cohort = jnp.any(sel[None, :] == jnp.arange(c)[:, None], axis=1)
        # a shard's round latency is its slowest selected resident (shards
        # with no cohort member are instant and re-sync for free)
        shard_lat = (
            jnp.where(in_cohort, lat, 0.0).reshape(n_shards, c_loc).max(axis=1)
        )
        slow = shard_lat > scen.deadline
        # the POST-update counters price this round's contribution: a shard
        # that misses the deadline delivers work based on pre-miss params
        # (read slot t − s_d with s_d including this round's miss), so a
        # deadline-capped round never aggregates information the simulated
        # clock says arrived after it closed.  Forced shards block the round
        # (full latency) and deliver fresh work with a reset counter.
        new_s, forced = staleness_lib.staleness_step(
            state.shard_staleness, slow, bound
        )
        lam = staleness_lib.decay_weights(
            new_s, cfg.staleness_decay, cfg.staleness_alpha
        )
        read_slot = staleness_lib.read_slots(t_prev, new_s, bound)
        sim_time = staleness_lib.round_sim_time(
            shard_lat, slow, forced, scen.deadline
        )
        ids = _resident_batch_plans(state, k_batch, sel)
        n_ids = 0 if ids is None else 1
        mask_args = () if draws is None else tuple(draws)
        # algo_state shards like the data fields; the drift-correction
        # anchor is automatically the shard's stale ring read (the inner
        # round anchors to its entry base params)
        state_args = (state.algo_state,) if stateful else ()

        def local_body(sel, lam_d, slot_d, hist, local_xs, local_ys,
                       local_sizes, local_losses, local_dists, global_dist,
                       *rest):
            if stateful:
                local_states, rest = rest[0], rest[1:]
            else:
                local_states = None
            local_ids = rest[:n_ids]
            fmasks = rest[n_ids:]
            c_loc_ = local_xs.shape[0]
            gids = lax.axis_index(client_axis) * c_loc_ + jnp.arange(c_loc_)
            mask = jnp.any(sel[None, :] == gids[:, None], axis=1)
            batches = batches_from_indices(
                cfg, local_ids[0] if local_ids else None, local_xs, local_ys
            )
            weights = local_sizes * mask
            # GEMD partials stay λ-free: the metric describes the cohort's
            # label mix, not the staleness-decayed aggregation weights
            w = weights.astype(jnp.float32)
            gemd_parts = ((w[:, None] * local_dists).sum(0), jnp.sum(w))
            if guard is None:
                res = shard_round(
                    hist, slot_d[0], lam_d[0], batches, weights,
                    extras=gemd_parts, local_states=local_states,
                )
                if stateful:
                    params, _, mean_loss, (num, den), cand_states = res
                else:
                    params, _, mean_loss, (num, den) = res
                g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
                # the refresh measures the NEW aggregate on each home shard —
                # fresh params, even when the contribution was stale
                fresh = loss_of(params, local_xs, local_ys)
                losses = jnp.where(mask, fresh, local_losses)
                if stateful:
                    new_states = _algo_writeback(
                        local_states, None, cand_states, mask, scatter=False
                    )
                    return params, mean_loss, losses, g, new_states
                return params, mean_loss, losses, g
            res = shard_round(
                hist, slot_d[0], lam_d[0], batches, weights,
                extras=gemd_parts, guard_args=fmasks,
                local_states=local_states,
            )
            if stateful:
                (params, _, mean_loss, (num, den), flagged, survivors,
                 cand_states) = res
            else:
                params, _, mean_loss, (num, den), flagged, survivors = res
            g = jnp.sum(jnp.abs(metrics_lib.safe_div(num, den) - global_dist))
            delivered = fmasks[0] if fmasks else jnp.ones_like(mask)
            refresh = (
                mask & delivered & ~flagged
                & (survivors >= cfg.min_survivors)
            )
            fresh = loss_of(params, local_xs, local_ys)
            losses = jnp.where(refresh, fresh, local_losses)
            if stateful:
                new_states = _algo_writeback(
                    local_states, None, cand_states, refresh, scatter=False
                )
                return params, mean_loss, losses, g, flagged, survivors, new_states
            return params, mean_loss, losses, g, flagged, survivors

        lead = P(client_axis)
        id_args = () if ids is None else (ids,)
        out = (P(), P(), lead, P())
        if guard is not None:
            out = out + (lead, P())
        if stateful:
            out = out + (lead,)
        body = _checked_shard_map(
            local_body, mesh=mesh,
            in_specs=(P(), lead, lead, P(), lead, lead, lead, lead, lead, P())
            + (lead,) * len(state_args)
            + (lead,) * (len(id_args) + len(mask_args)),
            out_specs=out,
        )
        res = body(
            sel, lam, read_slot, state.param_hist, state.client_xs,
            state.client_ys, state.client_sizes, state.losses,
            state.client_label_dists, state.global_label_dist,
            *(state_args + id_args + mask_args),
        )
        new_algo_state = None
        if stateful:
            res, new_algo_state = res[:-1], res[-1]
        if guard is None:
            params, mean_loss, losses, g = res
            flagged = survivors = None
        else:
            params, mean_loss, losses, g, flagged, survivors = res
            # apply the survivors floor BEFORE the ring write: the ring must
            # record the params the round actually kept, or a resumed /
            # stale read would replay a discarded aggregate
            ok_round = survivors >= cfg.min_survivors
            params = jax.tree_util.tree_map(
                lambda a, o: jnp.where(ok_round, a, o).astype(o.dtype),
                params, state.params,
            )
        hist = staleness_lib.update_param_hist(
            state.param_hist, params, t_prev + 1, bound
        )
        if guard is None:
            out = (params, mean_loss, losses, g, hist, new_s, sim_time)
        else:
            out = (params, mean_loss, losses, g, hist, new_s, sim_time,
                   flagged, survivors)
        return out + (new_algo_state,) if stateful else out

    def round_fn(state: ServerState, _=None):
        t = state.round + 1
        key, k_sel, k_batch = jax.random.split(state.key, 3)
        # the scenario's environment stream branches off the carried key so
        # the selection/batch streams are untouched: a latency-only scenario
        # leaves cohorts and batches bit-identical to a scenario-free run
        lat = avail = None
        if scen is not None:
            k_env = jax.random.fold_in(state.key, _ENV_SALT)
            lat = scen.latency(jax.random.fold_in(k_env, 0), state.num_clients)
            if avail_aware:
                avail = scen.availability(
                    jax.random.fold_in(k_env, 1), t, state.num_clients
                )
        # fault draws branch off the carried key the same way (FAULT_SALT):
        # jit-level tiny boolean rows, generated OUTSIDE the shard_map (the
        # batch-plan rule) and sharded in — faults=None skips all of this,
        # leaving every key stream bit-identical to the pre-fault engine
        draws = None
        if fault_model is not None:
            n_sh = 1 if mesh is None else mesh.shape[client_axis]
            draws = faults_lib.draw_round_faults(
                jax.random.fold_in(state.key, faults_lib.FAULT_SALT),
                fault_model, cfg.num_clients, n_sh, lemons,
            )
        sel_args = (k_sel, state.selection_state())
        if route_avail:
            # quarantined clients are "unavailable" to selection — the same
            # availability hook the scenario uses, masks AND-composed
            sel_mask = avail
            if guard_on:
                q_ok = state.quarantine <= 0
                sel_mask = q_ok if sel_mask is None else (sel_mask & q_ok)
            sel_args = sel_args + (sel_mask,)
        if len(branches) == 1:
            sel = branches[0](*sel_args)
        else:
            sel = lax.switch(state.strategy_index, branches, *sel_args)
        hist = new_s = sim_time = None
        flagged_c = survivors = None
        new_algo = None
        if mesh is None:
            res = _single_device_body(state, k_batch, sel, draws=draws)
        elif cfg.staleness_bound is not None:
            res = _stale_sharded_body(state, k_batch, sel, lat, draws=draws)
        elif cfg.cohort_cap is not None:
            res = _slot_sharded_body(state, k_batch, sel, draws=draws)
        else:
            res = _sharded_body(state, k_batch, sel, draws=draws)
        if stateful:
            # every body appends the already-written-back algo state last
            res, new_algo = res[:-1], res[-1]
        if mesh is not None and cfg.staleness_bound is not None:
            if guard is None:
                params, mean_loss, losses, g, hist, new_s, sim_time = res
            else:
                (params, mean_loss, losses, g, hist, new_s, sim_time,
                 flagged_c, survivors) = res
        elif guard is None:
            params, mean_loss, losses, g = res
        else:
            params, mean_loss, losses, g, flagged_c, survivors = res
        if guard is not None:
            # graceful degradation: a round below the survivors floor keeps
            # the old params (identity round, recorded in the metrics).  The
            # stale body already floored before its ring write; re-applying
            # here is an exact no-op for it.
            ok_round = survivors >= cfg.min_survivors
            params = jax.tree_util.tree_map(
                lambda a, o: jnp.where(ok_round, a, o).astype(o.dtype),
                params, state.params,
            )
        if scen is not None and sim_time is None:
            # synchronous barrier under the scenario: the round closes at
            # the slowest selected client
            c = state.losses.shape[0]
            in_cohort = jnp.any(sel[None, :] == jnp.arange(c)[:, None], axis=1)
            sim_time = jnp.max(jnp.where(in_cohort, lat, 0.0))

        if accuracy_fn is None:
            acc = jnp.float32(jnp.nan)
        else:
            if eval_data is not None:
                exs, eys = eval_data
            else:
                exs = state.client_xs.reshape((-1,) + state.client_xs.shape[2:])
                eys = state.client_ys.reshape(-1)
            acc = lax.cond(
                t % cfg.eval_every == 0,
                lambda p: jnp.asarray(accuracy_fn(p, exs, eys), jnp.float32),
                lambda p: jnp.float32(jnp.nan),
                params,
            )

        updates = dict(params=params, key=key, round=t, losses=losses)
        if hist is not None:
            updates.update(param_hist=hist, shard_staleness=new_s)
        if stateful:
            updates["algo_state"] = new_algo
        if guard_on:
            # quarantine dynamics: freshly flagged clients (re)start the
            # cooldown, everyone else's counter ticks down toward release
            q = jnp.maximum(state.quarantine - 1, 0)
            q = jnp.where(
                flagged_c, jnp.int32(cfg.quarantine_rounds), q
            ).astype(jnp.int32)
            updates["quarantine"] = q
        new_state = dataclasses.replace(state, **updates)
        out = {
            "round": t,
            "acc": acc,
            "gemd": jnp.asarray(g, jnp.float32),
            "loss": jnp.asarray(mean_loss, jnp.float32),
            "selected": sel,
        }
        if scen is not None:
            out["sim_time"] = jnp.asarray(sim_time, jnp.float32)
        if avail_aware:
            out["avail"] = avail
        if cfg.staleness_bound is not None:
            # mean lag the round's contributions were computed at
            out["staleness"] = jnp.mean(new_s.astype(jnp.float32))
        if guard_on:
            out["survivors"] = jnp.asarray(survivors, jnp.int32)
            out["identity_round"] = jnp.asarray(
                survivors < cfg.min_survivors, jnp.int32
            )
            out["flagged"] = jnp.sum(flagged_c.astype(jnp.int32))
            out["quarantined"] = jnp.sum((q > 0).astype(jnp.int32))
        if cfg.telemetry:
            # telemetry only ADDS output leaves — computed entirely from
            # values the round already holds, so the carried state and every
            # existing metric stay bit-identical to telemetry=False
            out["telemetry"] = obs_telemetry_lib.round_telemetry(
                cfg, state, t=t, avail=avail, new_s=new_s,
                flagged=flagged_c, survivors=survivors,
                quarantine=(q if guard_on else None),
            )
        return new_state, out

    return round_fn


# ------------------------------------------------------------------ runners

# Program-cache contract (identity keying): compiled scan/vmap executables
# are cached ON the round_fn object itself (``round_fn.__engine_programs__``),
# keyed by (kind, num_rounds).  Reuse of the compiled program therefore
# requires passing the SAME round_fn object — callers that rebuild a closure
# per call recompile, but the stale executables die with the closure instead
# of accumulating in a global table pinning their closed-over arrays (eval
# data!) alive.  ``FLTrainer`` memoises its round_fn per instance (plus a
# semantics-keyed cross-trainer cache) to hit this cache.  Callables that
# reject attributes (e.g. functools.partial) fall back to a small bounded
# FIFO table.

_FALLBACK_PROGRAMS: Dict = {}
_FALLBACK_LIMIT = 8


def _programs(round_fn) -> Dict:
    cache = getattr(round_fn, "__engine_programs__", None)
    if cache is None:
        cache = {}
        try:
            round_fn.__engine_programs__ = cache
        except AttributeError:
            if round_fn not in _FALLBACK_PROGRAMS:
                while len(_FALLBACK_PROGRAMS) >= _FALLBACK_LIMIT:
                    _FALLBACK_PROGRAMS.pop(next(iter(_FALLBACK_PROGRAMS)))
                _FALLBACK_PROGRAMS[round_fn] = cache
            return _FALLBACK_PROGRAMS[round_fn]
    return cache


def _scanned(round_fn, num_rounds: int):
    cache = _programs(round_fn)
    key = ("scan", num_rounds)
    if key not in cache:
        cache[key] = jax.jit(
            lambda state: lax.scan(round_fn, state, None, length=num_rounds)
        )
    return cache[key]


def run_scanned(
    round_fn, state: ServerState, num_rounds: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
    sink: Optional["obs_sink_lib.TelemetrySink"] = None,
) -> Tuple[ServerState, Dict[str, jax.Array]]:
    """Run ``num_rounds`` rounds as ONE compiled ``lax.scan`` program.

    Returns the final state and the per-round metrics stacked on a leading
    ``(num_rounds,)`` axis.  Re-invocations with the same ``round_fn`` object
    and round count reuse the compiled executable (see the program-cache
    contract above).

    ``mesh`` lays the state out with :func:`shard_server_state` before the
    scan (idempotent if already sharded); pass the mesh the ``round_fn`` was
    built with — single-device round_fns must be run without one.  Slot-capped
    round_fns (``cfg.cohort_cap``, DESIGN.md §8) run through this exact path:
    the state layout is identical (slots are transient inside the round), so
    no extra argument is needed here.

    ``sink`` (DESIGN.md §14) drains the segment's stacked outputs to JSONL
    *after* the compiled scan returns — the chunk-boundary drain rule: the
    host only ever observes scan outputs, never injects callbacks into the
    scan body, so a sink can never change the compiled program.
    """
    if mesh is not None:
        state = shard_server_state(state, mesh, client_axis)
    with obs_tracing_lib.annotate(f"fl.scan_chunk[{num_rounds}]"):
        state, outputs = _scanned(round_fn, num_rounds)(state)
    if sink is not None and num_rounds:
        obs_sink_lib.drain_fl_outputs(sink, outputs)
    return state, outputs


def _vmapped(round_fn, num_rounds: int):
    cache = _programs(round_fn)
    key = ("vmap", num_rounds)
    if key not in cache:
        cache[key] = jax.jit(
            jax.vmap(lambda state: lax.scan(round_fn, state, None, length=num_rounds))
        )
    return cache[key]


def run_many(
    round_fn, stacked_state: ServerState, num_rounds: int,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
) -> Tuple[ServerState, Dict[str, jax.Array]]:
    """Batched simulation: vmap the scanned run over stacked states.

    ``stacked_state`` is a :class:`ServerState` whose every leaf carries a
    leading batch axis (see :func:`stack_states`) — e.g. S seeds × K
    strategies flattened to one axis.  One XLA program executes the whole
    grid; outputs keep the ``(batch, num_rounds, ...)`` layout.  The k-DPP
    spectral caches ride in the stacked state (hoisted out of the vmapped
    round at :func:`init_server_state` time), so no branch of the grid pays
    an in-round ``eigh``.

    With ``mesh``, every grid point's client axis (axis 1 of the stacked
    client fields) lays out over the mesh — the batch axis stays replicated,
    so the D-way cohort parallelism multiplies the grid parallelism.
    Slot-capped round_fns (``cfg.cohort_cap``) compose unchanged: the cap
    applies per grid point inside the vmapped round.
    """
    if mesh is not None:
        stacked_state = shard_server_state(
            stacked_state, mesh, client_axis, batch_dims=1
        )
    return _vmapped(round_fn, num_rounds)(stacked_state)


# -------------------------------------------------------------- crash-resume


def save_server_state(ckpt_dir: str, state: ServerState) -> str:
    """Snapshot the FULL :class:`ServerState` (params, PRNG key, ring
    buffer, staleness counters, spectral cache, candidate set, quarantine
    state — every pytree leaf) under ``<ckpt_dir>/step_<round>/``.

    The typed PRNG key is stored as its raw ``key_data`` (npz can't hold
    extension dtypes); :func:`restore_server_state` re-wraps it.  Sharded
    states gather transparently through ``np.asarray``.
    """
    step = int(jax.device_get(state.round))
    host = dataclasses.replace(state, key=jax.random.key_data(state.key))
    return checkpoint_lib.save(ckpt_dir, step, host)


def restore_server_state(
    ckpt_dir: str, template: ServerState, step: Optional[int] = None
) -> ServerState:
    """Load a :func:`save_server_state` snapshot against a template state
    (e.g. the fresh ``init_server_state`` of the same config).

    Validation (leaf count / shapes / dtypes vs ``tree.json``) happens in
    ``repro.checkpoint.restore`` — a snapshot from a different config raises
    instead of unflattening garbage.  The returned state continues
    **bit-identically**: every carried array, including the PRNG key chain,
    is exactly the value the snapshotting run held after round
    ``state.round``.
    """
    t_host = dataclasses.replace(template, key=jax.random.key_data(template.key))
    restored = checkpoint_lib.restore(ckpt_dir, t_host, step=step)
    key = jax.random.wrap_key_data(jnp.asarray(restored.key))
    return dataclasses.replace(restored, key=key)


def run_checkpointed(
    round_fn, state: ServerState, num_rounds: int,
    ckpt_dir: Optional[str] = None,
    ckpt_every: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
    sink: Optional["obs_sink_lib.TelemetrySink"] = None,
) -> Tuple[ServerState, Dict[str, jax.Array]]:
    """:func:`run_scanned` with periodic :class:`ServerState` snapshots.

    Runs the scan in ``ckpt_every``-round segments, snapshotting the full
    state after each (DESIGN.md §11) — the per-round computation inside each
    segment is the same compiled ``round_fn`` body, so segmenting changes
    nothing numerically, and a crashed run restored from the latest
    ``step_*`` snapshot (:func:`restore_server_state`) continues
    bit-identically (the resume-parity contract: run N ≡ run n → restore →
    run N−n).  With ``ckpt_dir``/``ckpt_every`` unset this IS
    :func:`run_scanned`.
    """
    if ckpt_dir is None or not ckpt_every:
        return run_scanned(
            round_fn, state, num_rounds, mesh=mesh, client_axis=client_axis,
            sink=sink,
        )
    done = 0
    outs: List[Dict[str, Any]] = []
    while done < num_rounds:
        n = min(ckpt_every, num_rounds - done)
        state, seg = run_scanned(
            round_fn, state, n, mesh=mesh, client_axis=client_axis, sink=sink
        )
        # tree_map (not a dict comprehension): the telemetry subtree is a
        # Telemetry pytree, not a bare array
        outs.append(jax.tree_util.tree_map(np.asarray, seg))
        save_server_state(ckpt_dir, state)
        if sink is not None:
            sink.emit("fl_checkpoint", round=int(jax.device_get(state.round)))
        done += n
    if not outs:
        _, empty = run_scanned(
            round_fn, state, 0, mesh=mesh, client_axis=client_axis
        )
        return state, empty
    merged = jax.tree_util.tree_map(
        lambda *xs: np.concatenate(xs, axis=0), *outs
    )
    return state, merged


def stack_states(states: Sequence[ServerState]) -> ServerState:
    """Stack per-run states leaf-wise onto a leading batch axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_outputs(outputs: Dict[str, jax.Array]) -> List[Dict[str, np.ndarray]]:
    """Split ``run_many`` outputs back into one per-run metrics dict each
    (tree-aware: the optional telemetry subtree splits along for the ride).
    """
    outs = jax.tree_util.tree_map(np.asarray, outputs)
    n = jax.tree_util.tree_leaves(outs)[0].shape[0]
    return [jax.tree_util.tree_map(lambda v: v[i], outs) for i in range(n)]


# -------------------------------------------------------------- state build

# ServerState fields carrying one row per client: these shard over the mesh
# client axis; everything else (params, kernel, spectral cache, PRNG key,
# counters) replicates.  The kernel stays replicated on purpose — selection
# needs the full Gram matrix and stays bit-identical across devices.  The
# staleness fields (DESIGN.md §9) also replicate: every device needs the
# full param ring buffer (any shard may read any slot), and the (D,)
# counters are trivia the stale shard_map re-slices per shard.
CLIENT_SHARDED_FIELDS = (
    "losses",
    "profiles",
    "client_xs",
    "client_ys",
    "client_sizes",
    "client_label_dists",
    "algo_state",
)


def shard_server_state(
    state: ServerState,
    mesh: jax.sharding.Mesh,
    client_axis: str = CLIENT_AXIS,
    batch_dims: int = 0,
) -> ServerState:
    """Lay a :class:`ServerState` out over ``mesh``'s client axis.

    Per-client fields (:data:`CLIENT_SHARDED_FIELDS`) get
    ``NamedSharding(mesh, P(clients, ...))`` on their client dimension
    (dimension ``batch_dims`` — pass ``batch_dims=1`` for :func:`stack_states`
    batches); every other field is replicated.  Idempotent: re-sharding an
    already-sharded state is a no-op device_put.  The layout is the same with
    or without ``cfg.cohort_cap``: capacity slots are a transient in-round
    compaction, never part of the persistent state.
    """
    n_shards = mesh.shape[client_axis]
    c = state.losses.shape[batch_dims]
    if c % n_shards:
        raise ValueError(
            f"num_clients={c} not divisible by mesh axis "
            f"{client_axis!r}={n_shards}"
        )
    replicated = NamedSharding(mesh, P())

    def rep(tree):
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, replicated), tree)

    def lead(x):
        spec = client_axis_spec(x.ndim, client_axis, batch_dims=batch_dims)
        return jax.device_put(x, NamedSharding(mesh, spec))

    # tree_map handles pytree-valued fields (algo_state) and Nones alike
    updates = {
        f: jax.tree_util.tree_map(lead, getattr(state, f))
        for f in CLIENT_SHARDED_FIELDS
    }
    for f in dataclasses.fields(state):
        if f.name not in updates:
            updates[f.name] = rep(getattr(state, f.name))
    return ServerState(**updates)


# ------------------------------------------------------------------- funnel

# fold_in salt branching the funnel's stage-1 environment stream (predicted
# latency / availability at the segment boundary) off the caller's key
# WITHOUT consuming a split — the per-round selection/batch key streams stay
# bit-identical funnel-or-not, which the Q=C parity tests assert.
_FUNNEL_SALT = 0xF0A11E17


def candidate_profile_block(
    profiles: jax.Array,
    candidates: jax.Array,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
) -> jax.Array:
    """Gather the Q candidate profile rows (Q, F) — shard-locally on a mesh.

    Without a mesh this is one ``take``.  With one, ``profiles`` is laid out
    over the client axis (:data:`CLIENT_SHARDED_FIELDS`), so each shard
    contributes exactly the candidate rows it owns — non-resident candidate
    slots are zero-filled — and ONE ``psum`` assembles the replicated (Q, F)
    block.  That psum is the funnel's only collective: ``Q·F`` floats cross
    the interconnect, never anything C-sized, and adding the other shards'
    exact zeros leaves the owned rows bit-identical to an unsharded gather
    (the mesh Q=C parity contract).
    """
    cand = jnp.asarray(candidates, jnp.int32)
    profiles = jnp.asarray(profiles)
    if mesh is None:
        return jnp.take(profiles, cand, axis=0)

    def gather(local_f, ids):
        c_loc = local_f.shape[0]
        pos = ids - lax.axis_index(client_axis) * c_loc
        owned = (pos >= 0) & (pos < c_loc)
        rows = jnp.take(local_f, jnp.clip(pos, 0, c_loc - 1), axis=0)
        rows = jnp.where(owned[:, None], rows, jnp.zeros((), local_f.dtype))
        return lax.psum(rows, client_axis)

    body = _checked_shard_map(
        gather, mesh=mesh, in_specs=(P(client_axis), P()), out_specs=P()
    )
    return body(profiles, cand)


def funnel_fields(
    cfg: FLConfig,
    key: jax.Array,
    profiles: jax.Array,
    losses: jax.Array,
    strategy: Optional[selection_lib.SelectionStrategy] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
    round_index: int = 0,
) -> Tuple[jax.Array, jax.Array, dpp_lib.KDPPSamplerState]:
    """Stage 1 of the two-stage funnel (DESIGN.md §10): the segment-boundary
    state pieces ``(candidates, kernel, eig_state)``.

    * **prefilter** — ``funnel_scores`` (running loss × scenario-predicted
      latency × availability; the scenario draws branch off ``key`` via
      ``_FUNNEL_SALT`` as a *prediction* of next-round conditions) and one
      fused ``top_k`` pick Q ascending global ids;
    * **candidate Gram** — the (Q, F) profile block assembled shard-locally
      (:func:`candidate_profile_block`), then the eq.-(14) pipeline on the
      Q-block only (Pallas-fused when ``cfg.use_pallas_kernel``) — min-max
      normalisation runs over the candidate block, NOT a C×C submatrix;
    * **spectral cache** — the O(Q³) eigh + ESP table (or the identity
      placeholder for strategies that never draw from it), replacing the
      O(C³) decomposition entirely.

    Called by :func:`init_server_state` and at every reprofile boundary
    (``FLTrainer.run``) — never per round, so the cache stays valid for the
    whole segment.  Non-candidates never ship a profile row anywhere: the
    privacy note of DESIGN.md §10.
    """
    assert cfg.candidate_frac is not None
    q = cfg.candidate_count()
    c = losses.shape[0]
    lat = avail = None
    scen = (
        scenarios_lib.get_scenario(cfg.scenario) if cfg.scenario is not None
        else None
    )
    if scen is not None:
        k_env = jax.random.fold_in(key, _FUNNEL_SALT)
        lat = scen.latency(jax.random.fold_in(k_env, 0), c)
        if scen.availability is not None:
            avail = scen.availability(
                jax.random.fold_in(k_env, 1), round_index, c
            )
    scores = selection_lib.funnel_scores(losses, avail=avail, latency=lat)
    candidates = selection_lib.funnel_candidates(scores, q)
    fq = candidate_profile_block(
        profiles, candidates, mesh=mesh, client_axis=client_axis
    )
    if cfg.use_pallas_kernel:
        from repro.kernels.gram import ops as gram_ops

        kernel = gram_ops.candidate_kernel_from_profiles(fq)
    else:
        kernel = similarity_lib.kernel_from_profiles(fq, use_kernel=False)
    if strategy is None or getattr(strategy, "uses_spectral_cache", False):
        eig_state = dpp_lib.kdpp_sampler_state(kernel, cfg.clients_per_round)
    else:
        eig_state = dpp_lib.identity_sampler_state(q, cfg.clients_per_round)
    return candidates, kernel, eig_state


def init_server_state(
    cfg: FLConfig,
    params: PyTree,
    loss_fn: Callable,
    feature_fn: Optional[Callable],
    client_xs,
    client_ys,
    strategy: Optional[selection_lib.SelectionStrategy] = None,
    strategy_index: int = 0,
    key: Optional[jax.Array] = None,
    profiles: Optional[jax.Array] = None,
    kernel: Optional[jax.Array] = None,
    losses: Optional[jax.Array] = None,
    cluster_labels: Optional[jax.Array] = None,
    eig_state: Optional[dpp_lib.KDPPSamplerState] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    client_axis: str = CLIENT_AXIS,
) -> ServerState:
    """Algorithm-1 initialisation as a :class:`ServerState`.

    Profiles every client once with the fresh global model (Alg. 1 lines
    2-5), builds the eq.-(14) kernel **and its k-DPP spectral cache** (the
    one O(C³) ``eigh`` — every scanned round then draws in O(k²·C)), takes
    one loss pass for the initial last-known losses, and — when ``strategy``
    is a :class:`~repro.core.selection.ClusterSelection` — runs the one-shot
    host ``fit`` so the per-round draw is pure.  Any precomputed piece can be
    passed in to skip recomputation.  ``mesh`` lays the result out with
    :func:`shard_server_state` for the sharded execution path.

    With ``cfg.candidate_frac`` set (DESIGN.md §10) the kernel, spectral
    cache, and cluster labels are built by :func:`funnel_fields` on the
    Q-candidate block instead — this path never materialises a C×C array,
    and passing a precomputed full-federation ``kernel``/``eig_state`` is a
    :class:`ValueError`.
    """
    client_xs = jnp.asarray(client_xs)
    client_ys = jnp.asarray(client_ys)
    c, n_c = client_xs.shape[0], client_xs.shape[1]
    if profiles is None:
        assert feature_fn is not None, "need feature_fn to compute profiles"
        profiles = profiles_lib.profile_all_clients(
            jax.jit(feature_fn), params, list(client_xs)
        )
    if losses is None:
        losses = jax.jit(jax.vmap(loss_fn, in_axes=(None, 0, 0)))(
            params, client_xs, client_ys
        )
    candidates = None
    if cfg.candidate_frac is not None:
        # Funnel init (DESIGN.md §10): losses come FIRST (they are the
        # stage-1 prefilter score), then every kernel-shaped piece lives on
        # the Q-block — this path never materialises a C×C array.
        if kernel is not None or eig_state is not None:
            raise ValueError(
                "candidate_frac is set: the kernel and spectral cache are "
                "funnel-owned (Q×Q, rebuilt with the candidates) — don't "
                "pass precomputed full-federation kernel/eig_state"
            )
        candidates, kernel, eig_state = funnel_fields(
            cfg,
            key if key is not None else jax.random.key(cfg.seed),
            profiles, losses, strategy=strategy,
            mesh=mesh, client_axis=client_axis,
        )
    if kernel is None:
        kernel = similarity_lib.kernel_from_profiles(
            profiles, use_kernel=cfg.use_pallas_kernel
        )
    if eig_state is None:
        # Pay the O(C³) decomposition only when the strategy's select_fn
        # actually draws from the cache; strategy=None (unknown — e.g. a
        # caller assembling a multi-strategy run_many grid) keeps the real
        # spectrum as the safe default.  The identity placeholder shares the
        # pytree layout, so lax.switch grids stay shape-stable either way.
        if strategy is None or getattr(strategy, "uses_spectral_cache", False):
            eig_state = dpp_lib.kdpp_sampler_state(kernel, cfg.clients_per_round)
        else:
            eig_state = dpp_lib.identity_sampler_state(c, cfg.clients_per_round)
    if cluster_labels is None:
        if isinstance(strategy, selection_lib.ClusterSelection):
            # funnel mode fits the clusters on the SAME fingerprints as the
            # unfunneled path, restricted to the candidate rows — with
            # candidates == arange(C) (Q=C) the labels are bit-identical
            idx = (
                range(c) if candidates is None
                else np.asarray(candidates).tolist()
            )
            gp = jnp.stack([
                profiles_lib.representative_gradient_profile(
                    loss_fn, params, client_xs[i], client_ys[i]
                )
                for i in idx
            ])
            cluster_labels = strategy.fit(gp, cfg.clients_per_round)
        else:
            n_lbl = c if candidates is None else candidates.shape[0]
            cluster_labels = jnp.zeros((n_lbl,), jnp.int32)
    label_dists = jnp.stack([
        metrics_lib.label_distribution(client_ys[i], cfg.num_classes)
        for i in range(c)
    ])
    global_dist = metrics_lib.label_distribution(
        client_ys.reshape(-1), cfg.num_classes
    )
    param_hist = shard_staleness = None
    if cfg.staleness_bound is not None:
        param_hist, shard_staleness = staleness_lib.init_staleness_fields(
            params, cfg.staleness_bound, mesh, client_axis
        )
    # quarantine counters only exist on guarded configs so the pytree (and
    # every compiled program keyed on it) is unchanged for fault-free runs
    quarantine = jnp.zeros((c,), jnp.int32) if cfg.guarded() else None
    # per-client algorithm state only exists for stateful algorithms
    # (DESIGN.md §12) — None keeps the pytree unchanged for fedavg/fedprox
    algo_state = local_algos_lib.init_client_states(
        cfg.local_algo_obj(), params, c
    )
    state = ServerState(
        params=params,
        key=key if key is not None else jax.random.key(cfg.seed),
        round=jnp.asarray(0, jnp.int32),
        losses=losses,
        kernel=kernel,
        profiles=profiles,
        eig_state=eig_state,
        cluster_labels=cluster_labels,
        client_xs=client_xs,
        client_ys=client_ys,
        client_sizes=jnp.full((c,), float(n_c)),
        client_label_dists=label_dists,
        global_label_dist=global_dist,
        strategy_index=jnp.asarray(strategy_index, jnp.int32),
        param_hist=param_hist,
        shard_staleness=shard_staleness,
        candidates=candidates,
        quarantine=quarantine,
        algo_state=algo_state,
    )
    if mesh is not None:
        state = shard_server_state(state, mesh, client_axis)
    return state


# ------------------------------------------------------------------ history


def history_from_outputs(
    outputs: Dict[str, jax.Array],
    eval_every: int,
    final_acc: Optional[float] = None,
) -> Dict[str, List]:
    """Stacked scan outputs -> the legacy FLTrainer history dict.

    Keeps the legacy recording protocol: one entry per round where
    ``t % eval_every == 0``, plus the final round.  ``final_acc`` fills the
    accuracy of a final round that is not an eval round (the scan only
    evaluates on the eval grid)."""
    rounds = np.asarray(outputs["round"]).astype(int)
    hist: Dict[str, List] = {"round": [], "acc": [], "gemd": [], "loss": []}
    if rounds.size == 0:
        # zero-round runs (e.g. a run_many grid scanned for 0 rounds) have
        # no history — not an IndexError on rounds[-1]
        return hist
    acc = np.asarray(outputs["acc"], np.float64)
    gemd = np.asarray(outputs["gemd"], np.float64)
    loss = np.asarray(outputs["loss"], np.float64)
    n = int(rounds[-1])
    for i, t in enumerate(rounds):
        t = int(t)
        if t % eval_every == 0 or t == n:
            a = acc[i]
            if np.isnan(a) and t == n and final_acc is not None:
                a = final_acc
            hist["round"].append(t)
            hist["acc"].append(float(a))
            hist["gemd"].append(float(gemd[i]))
            hist["loss"].append(float(loss[i]))
    return hist
