"""Jitted FL round steps — the distributed heart of the framework.

Two execution modes (DESIGN.md §2):

* ``build_client_parallel_round`` — Mode A (paper-faithful): per-client param
  copies on a leading C_p axis (sharded over the mesh ``data`` axis under
  pjit), ``E`` local SGD steps via ``lax.scan`` with **no cross-client
  collectives inside**, then one eq.-(6) weighted aggregation.  The collective
  term of the roofline is paid once per round instead of once per step —
  the communication-efficiency claim of FL, measurable in §Roofline.
* ``build_fedsgd_step`` — Mode B (paper's E=1 reduction, eq. 9): one global
  weighted-gradient step; params keep a single (optionally FSDP-sharded)
  copy.  Used when per-client copies cannot fit HBM (llama4-maverick).

Both are pure functions of (params, batch pytrees) so ``jax.jit`` +
``in_shardings`` decide the distribution; nothing here touches devices.

Every round builder here consumes **global** client ids / resident masks —
the two-stage selection funnel (DESIGN.md §10) lives entirely upstream in
``SelectionStrategy.select_global_fn``, which hands back global ids whatever
the candidate set was.  That is why slot-capped (``cohort_cap``) and
bounded-staleness execution compose with ``candidate_frac`` with no code
here changing: a funneled cohort is just a cohort by the time it reaches a
round step.

*What* each client computes is pluggable (DESIGN.md §12): every builder
takes an ``algo`` — a :class:`repro.fl.local_algos.LocalAlgo` — whose
per-step gradient hook and per-round state evolution are folded into the
client scan by :func:`build_local_algo_update`.  ``algo=None`` means
FedAvg and keeps every legacy signature, return shape, and compiled graph
untouched; a *stateful* algorithm (FedDyn) extends the signatures with a
per-client state pytree in and a *candidate* new state out — masked
write-back (cohort membership, guard verdicts, survivor floors) is the
engine's job, since only it knows the round's refresh mask.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import optim as optim_lib
from repro.core.metrics import finite_mean, safe_div

__all__ = [
    "weighted_average",
    "make_grad_fn",
    "build_local_update",
    "build_local_algo_update",
    "build_client_parallel_round",
    "build_shard_cohort_round",
    "build_stale_shard_cohort_round",
    "build_fedsgd_step",
    "build_server_opt_round",
]

PyTree = Any
# loss_fn(params, batch) -> scalar loss
LossFn = Callable[[PyTree, PyTree], jax.Array]


def weighted_average(trees: PyTree, weights: jax.Array) -> PyTree:
    """Eq. (6): Σ_c (n_c / Σ n_c) · w_c over the leading client axis."""
    w = safe_div(weights, jnp.sum(weights)).astype(jnp.float32)

    def avg(x):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.float32)
        return jnp.sum(wb * x.astype(jnp.float32), axis=0).astype(x.dtype)

    return jax.tree_util.tree_map(avg, trees)


def make_grad_fn(
    loss_fn: LossFn, micro_batches: int = 1
) -> Callable[[PyTree, PyTree], Tuple[jax.Array, PyTree]]:
    """``grad_fn(params, batch) -> (loss, grad)``, optionally accumulated
    over ``micro_batches`` slices of the batch's leading sample axis —
    identical full-batch gradient, 1/micro_batches the live activations
    (§Perf memory lever).  The one gradient definition shared by every
    local-update algorithm and the Mode-B FedSGD step."""

    def _full_grad(p, batch):
        if micro_batches == 1:
            return jax.value_and_grad(loss_fn)(p, batch)
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((micro_batches, x.shape[0] // micro_batches) + x.shape[1:]),
            batch,
        )

        def acc(carry, mb):
            tot_l, tot_g = carry
            l, g = jax.value_and_grad(loss_fn)(p, mb)
            return (tot_l + l, jax.tree_util.tree_map(jnp.add, tot_g, g)), None

        zeros = jax.tree_util.tree_map(lambda w: jnp.zeros(w.shape, jnp.float32), p)
        (loss, g), _ = lax.scan(acc, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / micro_batches
        return loss * inv, jax.tree_util.tree_map(lambda x: x * inv, g)

    return _full_grad


def build_local_algo_update(
    algo,
    loss_fn: LossFn,
    lr: float,
    grad_clip: Optional[float] = None,
    unroll=1,
    micro_batches: int = 1,
) -> Callable:
    """One client's E local passes of a registered algorithm (DESIGN.md §12).

    The entry ``params`` are the round's base — the anchor every
    drift-correcting term measures against (under bounded staleness that is
    the shard's stale ring read, exactly the params the client trained
    from).  Two signatures, chosen by ``algo.stateful``:

    * stateless — ``local_update(params, steps_batch) -> (params, losses)``,
      the legacy :func:`build_local_update` contract.  The FedAvg identity
      hook makes this trace to the *same* program as the pre-registry SGD
      scan, so ``local_algo="fedavg"`` is bit-identical everywhere.
    * stateful — ``local_update(params, client_state, steps_batch) ->
      (params, new_client_state, losses)``; the state is constant during
      the scan (a per-*round* quantity) and evolved once by
      ``algo.finalize`` after the final step.
    """
    if algo is None:
        from repro.fl.local_algos import FedAvg

        algo = FedAvg()
    _full_grad = make_grad_fn(loss_fn, micro_batches)

    def _scan_steps(params, client_state, anchor, steps_batch):
        # eq. (3)-(5): E SGD passes with the algorithm's per-step grad term
        def one_step(p, batch):
            loss, g = _full_grad(p, batch)
            g = algo.transform_grad(g, p, client_state, anchor)
            if grad_clip is not None:
                g = optim_lib.clip_by_global_norm(g, grad_clip)
            p = jax.tree_util.tree_map(lambda w, gw: (w - lr * gw).astype(w.dtype), p, g)
            return p, loss

        return lax.scan(one_step, params, steps_batch, unroll=unroll)

    if not algo.stateful:

        def local_update(params: PyTree, steps_batch: PyTree):
            return _scan_steps(params, (), params, steps_batch)

        return local_update

    def stateful_local_update(params: PyTree, client_state: PyTree, steps_batch: PyTree):
        anchor = params
        new_params, losses = _scan_steps(params, client_state, anchor, steps_batch)
        new_state = algo.finalize(new_params, client_state, anchor)
        return new_params, new_state, losses

    return stateful_local_update


def build_local_update(
    loss_fn: LossFn,
    lr: float,
    grad_clip: Optional[float] = None,
    unroll=1,
    micro_batches: int = 1,
) -> Callable[[PyTree, PyTree], Tuple[PyTree, jax.Array]]:
    """Deprecated alias for the registry's FedAvg (DESIGN.md §12).

    ``local_update(params, steps_batch) -> (params, losses)`` — the exact
    pre-registry plain-SGD scan, now produced by
    ``build_local_algo_update(get_local_algo("fedavg"), ...)``.  Kept so
    existing imports and the legacy parity oracle keep working; new code
    should go through the registry.
    """
    warnings.warn(
        "build_local_update is deprecated; use "
        "build_local_algo_update(get_local_algo('fedavg'), ...) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.fl.local_algos import get_local_algo

    return build_local_algo_update(
        get_local_algo("fedavg"), loss_fn, lr, grad_clip=grad_clip,
        unroll=unroll, micro_batches=micro_batches,
    )


def build_client_parallel_round(
    loss_fn: LossFn,
    lr: float,
    local_steps: int,
    grad_clip: Optional[float] = None,
    client_constraint: Optional[Callable[[PyTree], PyTree]] = None,
    unroll=1,
    sequential_clients: bool = False,
    micro_batches: int = 1,
    update_transform: Optional[Callable] = None,
    algo=None,
) -> Callable[[PyTree, PyTree, jax.Array], Tuple[PyTree, jax.Array]]:
    """Mode A round step.

    ``round_step(global_params, client_batches, client_weights)`` where every
    leaf of ``client_batches`` has leading shape ``(C_p, local_steps, ...)``
    and ``client_weights`` is ``(C_p,)`` (= n_c).  Returns the aggregated
    global params (eq. 6) and the mean local loss.

    ``client_constraint`` (used by the distributed launchers) applies a
    sharding constraint to the per-client broadcast params so the leading
    client axis lays out over the mesh ``data`` axis.

    ``update_transform`` (DESIGN.md §11) is the fault-injection +
    update-validation guard from ``repro.fl.faults.make_update_guard``,
    applied between the local updates and the eq.-(6) weighted sum.  When
    set, ``round_step(global_params, client_batches, client_weights,
    *guard_args)`` returns ``(agg, mean_loss, flagged, survivors)`` — the
    NaN-aware cohort mean, the per-client quarantine flags, and the count of
    clients left in the weighted sum.  When ``None`` (the default) the
    legacy signature, return, and compiled graph are untouched.

    ``algo`` (DESIGN.md §12) selects the local-update algorithm (``None`` =
    FedAvg, legacy-identical graph).  A *stateful* algorithm adds a required
    keyword ``client_states`` (leaves leading ``(C_p, ...)``) and appends
    the candidate new states as the final return element — the caller owns
    the masked write-back, since only it knows the round's refresh mask.
    """
    local_update = build_local_algo_update(
        algo, loss_fn, lr, grad_clip=grad_clip, unroll=unroll,
        micro_batches=micro_batches,
    )
    stateful = algo is not None and algo.stateful

    def round_step(
        global_params, client_batches, client_weights, *guard_args,
        client_states=None,
    ):
        n_clients = client_weights.shape[0]
        with jax.named_scope("fl.local_update"):
            per_client = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_clients,) + x.shape), global_params
            )
            if client_constraint is not None:
                per_client = client_constraint(per_client)
            operands = (
                (per_client, client_states, client_batches)
                if stateful
                else (per_client, client_batches)
            )
            if sequential_clients:
                # CPU-simulation path: vmapped convs lower to grouped
                # convolutions (XLA-CPU pathology, ~10x slow); on the mesh
                # each device owns one client so vmap is right there,
                # lax.map is right here.
                out = jax.lax.map(lambda args: local_update(*args), operands)
            else:
                out = jax.vmap(local_update)(*operands)
        if stateful:
            new_params, new_states, losses = out
        else:
            new_params, losses = out
        if update_transform is None:
            with jax.named_scope("fl.aggregate"):
                agg = weighted_average(new_params, client_weights)
                mean_loss = jnp.mean(losses)
            if stateful:
                return agg, mean_loss, new_states
            return agg, mean_loss
        with jax.named_scope("fl.aggregate"):
            new_params, w, losses, flagged = update_transform(
                new_params, global_params, client_weights, losses, *guard_args
            )
            agg = weighted_average(new_params, w)
            entry = jnp.mean(losses, axis=tuple(range(1, losses.ndim)))
            mean_loss = finite_mean(entry, where=w > 0)
            survivors = jnp.sum((w > 0).astype(jnp.int32))
        if stateful:
            return agg, mean_loss, flagged, survivors, new_states
        return agg, mean_loss, flagged, survivors

    return round_step


def build_shard_cohort_round(
    loss_fn: LossFn,
    lr: float,
    axis: str,
    grad_clip: Optional[float] = None,
    unroll=1,
    sequential_clients: bool = True,
    micro_batches: int = 1,
    cap: Optional[int] = None,
    update_transform: Optional[Callable] = None,
    algo=None,
) -> Callable[..., Tuple[PyTree, jax.Array, jax.Array, Any]]:
    """Mesh-sharded Mode-A round step for ONE client shard.

    Must be called *inside* a ``shard_map`` body whose mesh carries ``axis``:
    each device runs local updates only for clients resident in its shard,
    then the eq.-(6) aggregation happens as per-shard partial weighted sums
    combined with ``lax.psum`` — the parameter tree is never all-gathered,
    each device contributes exactly its Σ_local w_c·θ_c term.

    Two execution modes, selected by ``cap``:

    * ``cap=None`` (resident mode) —
      ``round_step(global_params, local_batches, local_weights, extras=None)``
      where every leaf of ``local_batches`` has leading shape ``(C_loc,
      local_steps, ...)`` and ``local_weights`` is ``(C_loc,)`` with ``0``
      marking clients outside the round's cohort.  Every resident computes a
      (possibly zero-weighted) update: D·(C/D) work however small the cohort.
    * ``cap=int`` (slot-compacted mode, DESIGN.md §8) —
      ``round_step(global_params, slot_batches, local_weights, slot_index,
      extras=None)``: the caller packs the shard's (at most ``cap =
      min(C_loc, k)``) selected residents into a compact slot axis —
      ``slot_batches`` leaves lead with ``(cap, local_steps, ...)`` and
      ``slot_index`` is ``(cap,)`` distinct local resident positions,
      selected residents first (padding slots point at unselected residents
      and carry weight 0).  Local updates run only over slots, the slot
      weights are gathered from the resident-layout ``local_weights``, and
      per-client losses are scattered back to resident layout — so a
      k-client cohort pays ``cap`` local updates per shard instead of
      ``C_loc``.  Eq.-(6) stays the same partial weighted sums over the same
      nonzero terms (zero-weight slots contribute exact zeros) and the
      single psum rendezvous is unchanged, so aggregation matches resident
      mode to fp32 tolerance.

    Both modes return ``(agg_params, client_losses, mean_loss, extras)``:
    the aggregated global params (replicated), the per-shard client losses
    ``(C_loc,)`` (mean over local steps; **NaN for every client outside the
    round's cohort** — the documented masking convention, so an unselected
    client's stale/zero-weight loss can never be mistaken for a cohort
    measurement), the cohort mean local loss (replicated), and ``extras``
    summed over the axis — callers fold their own per-shard partials (e.g.
    GEMD numerators) into the round's single psum rendezvous instead of
    paying a second one.

    ``update_transform`` (DESIGN.md §11) is the fault-injection +
    update-validation guard from ``repro.fl.faults.make_update_guard``.
    When set, both modes accept ``guard_args=()`` — the per-shard (or
    per-slot) fault-mask rows — apply the guard between the local updates
    and the partial weighted sums (strictly *before* the single psum, so a
    rejected update never crosses a device boundary), and the surviving-
    client count rides that same psum: the return grows to ``(agg,
    client_losses, mean_loss, extras, flagged, survivors)`` with ``flagged``
    in resident layout.  When ``None`` the legacy signature, return, and
    compiled graph are untouched.

    ``algo`` (DESIGN.md §12) selects the local-update algorithm (``None`` =
    FedAvg, legacy-identical graph).  A *stateful* algorithm adds a
    required keyword ``local_states`` — this shard's resident-layout state
    slice, leaves leading ``(C_loc, ...)`` — and appends the candidate new
    states (same layout; slot mode gathers states by ``slot_index`` and
    scatters the trained slots back, untouched residents keep their old
    state) as the final return element.  Per-device state, never psum'd:
    the caller owns the masked write-back.
    """
    local_update = build_local_algo_update(
        algo, loss_fn, lr, grad_clip=grad_clip, unroll=unroll,
        micro_batches=micro_batches,
    )
    stateful = algo is not None and algo.stateful

    def _updates(global_params, batches, n, states=None):
        with jax.named_scope("fl.local_update"):
            per_client = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), global_params
            )
            operands = (
                (per_client, states, batches) if stateful else (per_client, batches)
            )
            if sequential_clients:
                out = jax.lax.map(lambda args: local_update(*args), operands)
            else:
                out = jax.vmap(local_update)(*operands)
        if stateful:
            new_params, new_states, losses = out
            return new_params, losses, new_states
        new_params, losses = out
        return new_params, losses, None

    @jax.named_scope("fl.aggregate")
    def _aggregate(new_params, losses, weights, extras, survivors_local=None):
        # eq. (6) as partial weighted sums: Σ_c w_c·θ_c / Σ_c w_c.  ALL the
        # round's partial reductions ride ONE psum call so the per-round
        # cross-device rendezvous count stays constant in tree size.
        w = weights.astype(jnp.float32)
        mask = (w > 0).astype(jnp.float32)
        entry_losses = jnp.mean(losses, axis=tuple(range(1, losses.ndim)))
        # NaN-aware cohort mean: only finite cohort entries enter tot/cnt
        # (``where``, never ``mask·x`` — 0·NaN = NaN).  All-finite inputs
        # keep the exact pre-guard values: same entries, same reduction
        # order.  A round with no finite cohort entry reports NaN, not 0.
        ok = (mask > 0) & jnp.isfinite(entry_losses)

        def part_leaf(x):
            wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(wb * x.astype(jnp.float32), axis=0)

        partials = jax.tree_util.tree_map(part_leaf, new_params)
        reduced = (
            partials,
            jnp.sum(w),
            jnp.sum(jnp.where(ok, entry_losses, jnp.zeros((), entry_losses.dtype))),
            jnp.sum(ok.astype(jnp.float32)),
            extras,
        )
        if survivors_local is not None:
            reduced = reduced + (survivors_local,)
        reduced = lax.psum(reduced, axis)
        partials, wsum, tot, cnt, extras = reduced[:5]
        inv = safe_div(jnp.float32(1.0), wsum)
        agg = jax.tree_util.tree_map(
            lambda part, x: (part * inv).astype(x.dtype), partials, new_params
        )
        mean_loss = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), jnp.nan)
        masked_losses = jnp.where(mask > 0, entry_losses, jnp.nan)
        if survivors_local is None:
            return agg, masked_losses, mean_loss, extras
        return agg, masked_losses, mean_loss, extras, reduced[5]

    def round_step(
        global_params, local_batches, local_weights, extras=None, guard_args=(),
        local_states=None,
    ):
        new_params, losses, new_states = _updates(
            global_params, local_batches, local_weights.shape[0], local_states
        )
        if update_transform is None:
            out = _aggregate(new_params, losses, local_weights, extras)
            return out + (new_states,) if stateful else out
        with jax.named_scope("fl.aggregate"):
            new_params, w, losses, flagged = update_transform(
                new_params, global_params, local_weights, losses, *guard_args
            )
            survivors_local = jnp.sum((w > 0).astype(jnp.int32))
        agg, client_losses, mean_loss, extras, survivors = _aggregate(
            new_params, losses, w, extras, survivors_local
        )
        out = (agg, client_losses, mean_loss, extras, flagged, survivors)
        return out + (new_states,) if stateful else out

    def slot_round_step(
        global_params, slot_batches, local_weights, slot_index, extras=None,
        guard_args=(), local_states=None,
    ):
        slot_states = (
            jax.tree_util.tree_map(
                lambda s: jnp.take(s, slot_index, axis=0), local_states
            )
            if stateful
            else None
        )
        new_params, losses, new_slot_states = _updates(
            global_params, slot_batches, cap, slot_states
        )
        if stateful:
            # scatter trained slot states back to resident layout; residents
            # no slot covered keep their old state (their refresh mask is
            # False anyway — weight-0 padding slots never pass write-back)
            new_states = jax.tree_util.tree_map(
                lambda full, slot_new: full.at[slot_index].set(slot_new),
                local_states, new_slot_states,
            )
        else:
            new_states = None
        slot_weights = jnp.take(local_weights, slot_index)
        if update_transform is not None:
            with jax.named_scope("fl.aggregate"):
                new_params, slot_weights, losses, slot_flagged = update_transform(
                    new_params, global_params, slot_weights, losses, *guard_args
                )
                survivors_local = jnp.sum((slot_weights > 0).astype(jnp.int32))
            agg, slot_losses, mean_loss, extras, survivors = _aggregate(
                new_params, losses, slot_weights, extras, survivors_local
            )
        else:
            agg, slot_losses, mean_loss, extras = _aggregate(
                new_params, losses, slot_weights, extras
            )
        # scatter slot losses back to resident layout; everything the slots
        # did not cover (and weight-0 padding slots) stays NaN by convention
        client_losses = (
            jnp.full(local_weights.shape, jnp.nan, slot_losses.dtype)
            .at[slot_index]
            .set(slot_losses)
        )
        if update_transform is None:
            out = (agg, client_losses, mean_loss, extras)
            return out + (new_states,) if stateful else out
        # scatter flags the same way: padding slots carry weight 0, so they
        # can never be flagged and the scatter stays collision-free
        flagged = (
            jnp.zeros(local_weights.shape, jnp.bool_)
            .at[slot_index]
            .set(slot_flagged)
        )
        out = (agg, client_losses, mean_loss, extras, flagged, survivors)
        return out + (new_states,) if stateful else out

    return round_step if cap is None else slot_round_step


def build_stale_shard_cohort_round(
    loss_fn: LossFn,
    lr: float,
    axis: str,
    grad_clip: Optional[float] = None,
    unroll=1,
    sequential_clients: bool = True,
    micro_batches: int = 1,
    update_transform: Optional[Callable] = None,
    algo=None,
) -> Callable[..., Tuple[PyTree, jax.Array, jax.Array, Any]]:
    """Bounded-staleness variant of :func:`build_shard_cohort_round`
    (DESIGN.md §9) — same residents, same local updates, same single psum,
    but the shard's *base* params are stale.

    Must run inside a ``shard_map`` body over ``axis``.
    ``round_step(param_hist, read_slot, stale_scale, local_batches,
    local_weights, extras=None)`` where ``param_hist`` is the replicated
    ring buffer of global param snapshots (leaves lead with ``(s+1, ...)``,
    see ``repro.fl.staleness``), ``read_slot`` is this shard's ring index
    (the round-``t − s_d`` snapshot) and ``stale_scale`` is its
    staleness-decay weight λ(s_d).

    The shard reads its base params from the ring, runs the standard
    resident-mode local updates (:func:`build_local_algo_update` via the
    synchronous round — bit-identical per-client math), and contributes
    eq.-(6) partial weighted sums with weights ``λ(s_d)·w_c`` to the SAME
    single psum rendezvous; the psum'd ``Σ λw`` denominator normalises the
    decay (``core.metrics.safe_div``), so the aggregate is a convex
    combination across shards of different staleness.  ``stale_scale`` must
    be > 0 (every decay family satisfies this), which preserves the
    weight-0 ⟺ non-cohort NaN loss-masking convention unchanged; with
    ``read_slot`` pointing at the current round and ``stale_scale = 1`` the
    step is bit-identical to the synchronous round.

    ``algo`` (DESIGN.md §12) passes through to the inner resident round;
    a stateful algorithm adds the ``local_states`` keyword / trailing
    candidate-state return.  The drift-correction anchor is the shard's
    *stale* ring read — the params the clients actually trained from —
    because the inner round anchors to its entry base params.
    """
    inner = build_shard_cohort_round(
        loss_fn, lr, axis, grad_clip=grad_clip, unroll=unroll,
        sequential_clients=sequential_clients, micro_batches=micro_batches,
        update_transform=update_transform, algo=algo,
    )

    def round_step(
        param_hist, read_slot, stale_scale, local_batches, local_weights,
        extras=None, guard_args=(), local_states=None,
    ):
        # the guard's base params are the shard's *stale* ring read — update
        # norms are measured against the params the clients actually trained
        # from, and λ > 0 keeps the weight-0 ⟺ rejected/non-cohort
        # convention intact under the staleness-decay scaling
        base = jax.tree_util.tree_map(
            lambda h: lax.dynamic_index_in_dim(h, read_slot, 0, keepdims=False),
            param_hist,
        )
        if update_transform is None:
            return inner(
                base, local_batches, local_weights * stale_scale, extras=extras,
                local_states=local_states,
            )
        return inner(
            base, local_batches, local_weights * stale_scale, extras=extras,
            guard_args=guard_args, local_states=local_states,
        )

    return round_step


def build_server_opt_round(
    loss_fn: LossFn,
    client_lr: float,
    local_steps: int,
    server_optimizer: optim_lib.Optimizer,
    grad_clip: Optional[float] = None,
) -> Callable:
    """Beyond-paper: FedOpt (Reddi et al.) on top of Mode-A rounds.

    The eq.-(6) aggregate is reinterpreted as a *pseudo-gradient*
    ``Δ = w_global − avg(w_clients)`` and fed to a server optimizer
    (momentum/Adam), which is known to stabilise non-IID training — and
    composes orthogonally with DPP cohort selection.

    ``round_step(params, server_state, batches, weights) ->
    (params, server_state, loss)``.
    """
    inner = build_client_parallel_round(loss_fn, client_lr, local_steps, grad_clip)

    def round_step(params, server_state, client_batches, client_weights):
        agg, loss = inner(params, client_batches, client_weights)
        pseudo_grad = jax.tree_util.tree_map(
            lambda w, a: (w.astype(jnp.float32) - a.astype(jnp.float32)), params, agg
        )
        updates, server_state = server_optimizer.update(pseudo_grad, server_state, params)
        params = optim_lib.apply_updates(params, updates)
        return params, server_state, loss

    return round_step


def build_fedsgd_step(
    loss_fn: LossFn,
    optimizer: optim_lib.Optimizer,
    grad_clip: Optional[float] = None,
    micro_batches: int = 1,
) -> Callable:
    """Mode B step: one optimizer step on the weighted global gradient.

    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.  The
    batch carries all selected clients' data; per-client weighting happens via
    the sample dimension (uniform n_c ⇒ plain mean, matching eq. 9).
    ``micro_batches`` accumulates the gradient over batch slices (exact).
    """

    def grad_of(params, batch):
        if micro_batches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape((micro_batches, x.shape[0] // micro_batches) + x.shape[1:]),
            batch,
        )

        def acc(carry, mb):
            tot_l, tot_g = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (tot_l + l, jax.tree_util.tree_map(jnp.add, tot_g, g)), None

        zeros = jax.tree_util.tree_map(lambda w: jnp.zeros(w.shape, jnp.float32), params)
        (loss, g), _ = lax.scan(acc, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / micro_batches
        return loss * inv, jax.tree_util.tree_map(lambda x: x * inv, g)

    def step(params, opt_state, batch):
        loss, g = grad_of(params, batch)
        if grad_clip is not None:
            g = optim_lib.clip_by_global_norm(g, grad_clip)
        updates, opt_state = optimizer.update(g, opt_state, params)
        params = optim_lib.apply_updates(params, updates)
        return params, opt_state, loss

    return step
