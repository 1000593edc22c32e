"""FLTrainer — Algorithm 1 (FL-DP³S) end-to-end, model-agnostic.

Simulates the full federation on one host: profiles every client once with
the freshly initialised global model (Alg. 1 lines 2-5), builds the eq.-(14)
kernel, then runs rounds: select cohort → vmapped local updates (eq. 3-5) →
eq.-(6) aggregation.  Metrics: training-set accuracy (Fig. 1 protocol), GEMD
per round (Fig. 2), last-known local losses (FedSAE's signal).

Since the engine refactor (DESIGN.md §7) this class is a thin compatibility
wrapper over :mod:`repro.fl.engine`: :meth:`run` packs the server knowledge
into a :class:`~repro.fl.engine.ServerState` and executes all rounds as
``lax.scan`` segments with zero per-round host round-trips, falling back to
the legacy Python loop (:meth:`run_legacy`) only for custom strategies that
don't expose a pure ``select_fn``.

Works for any model exposing ``loss_fn(params, x, y)`` and
``feature_fn(params, x) -> (logits, feats)``; the paper's CNN is the default.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dpp as dpp_lib
from repro.core import metrics as metrics_lib
from repro.core import profiles as profiles_lib
from repro.core import selection as selection_lib
from repro.core import similarity as similarity_lib
from repro.fl import engine as engine_lib
from repro.fl import local_algos as local_algos_lib
from repro.fl import rounds as rounds_lib
from repro.fl import staleness as staleness_lib
from repro.fl.engine import FLConfig
from repro.obs import tracing as obs_tracing_lib

__all__ = ["FLConfig", "FLTrainer"]


@functools.lru_cache(maxsize=64)
def _cached_round_step(loss_fn, lr: float, steps: int, grad_clip=None):
    """One jitted Mode-A round step per (loss_fn, lr, steps) — lets a
    benchmark sweep re-use the compiled XLA program across trainers."""
    batched = lambda p, batch: loss_fn(p, batch[0], batch[1])
    return jax.jit(
        rounds_lib.build_client_parallel_round(
            batched, lr, steps, grad_clip=grad_clip, sequential_clients=True
        )
    )


@functools.lru_cache(maxsize=64)
def _cached_loss_of(loss_fn):
    return jax.jit(jax.vmap(loss_fn, in_axes=(None, 0, 0)))


# round_fns are cached across trainers on the *semantics* of the round, not
# on instance identity, so a benchmark grid (datasets × ξ × seeds) compiles
# each (method, rounds) scan exactly once — the data rides in ServerState.
_ROUND_FN_CACHE: Dict = {}


def _strategy_sig(s: selection_lib.SelectionStrategy):
    return (
        type(s).__module__,
        type(s).__qualname__,
        getattr(s, "mode", None),
        getattr(s, "d", None),
        getattr(s, "use_cache", None),
    )


def _cached_round_fn(cfg: FLConfig, loss_fn, accuracy_fn, strategy, mesh, client_axis):
    key = (
        loss_fn,
        accuracy_fn,
        _strategy_sig(strategy),
        cfg.clients_per_round,
        cfg.local_epochs,
        cfg.local_batch_size,
        cfg.lr,
        cfg.grad_clip,
        cfg.eval_every,
        cfg.local_steps,
        cfg.sample_with_replacement,
        cfg.cohort_cap,
        cfg.staleness_bound,
        cfg.staleness_decay,
        cfg.staleness_alpha,
        cfg.scenario,
        cfg.candidate_frac,
        cfg.faults,
        cfg.aggregator,
        cfg.robust_norm_mult,
        cfg.min_survivors,
        cfg.quarantine_rounds,
        cfg.local_algo,
        cfg.prox_mu,
        cfg.feddyn_alpha,
        cfg.telemetry,
        mesh,
        client_axis,
    )
    if key not in _ROUND_FN_CACHE:
        _ROUND_FN_CACHE[key] = engine_lib.make_round_fn(
            cfg, loss_fn, (strategy,), accuracy_fn=accuracy_fn,
            mesh=mesh, client_axis=client_axis,
        )
    return _ROUND_FN_CACHE[key]


class FLTrainer:
    def __init__(
        self,
        cfg: FLConfig,
        params,
        loss_fn: Callable,
        feature_fn: Callable,
        client_xs: np.ndarray,  # (C, n_c, ...)
        client_ys: np.ndarray,  # (C, n_c)
        strategy: selection_lib.SelectionStrategy,
        eval_xs: Optional[np.ndarray] = None,
        eval_ys: Optional[np.ndarray] = None,
        accuracy_fn: Optional[Callable] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        client_axis: str = engine_lib.CLIENT_AXIS,
    ):
        assert client_xs.shape[0] == cfg.num_clients
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.feature_fn = feature_fn
        self.strategy = strategy
        self.params = params
        # mesh-sharded cohort execution (DESIGN.md §8): the engine path lays
        # ServerState out over the mesh's client axis and runs local updates
        # as a shard_map; run_legacy always stays single-device.  With
        # cfg.cohort_cap set, the sharded rounds run slot-compacted (each
        # shard trains at most min(C_loc, cohort_cap) clients per round) —
        # segments, reprofile boundaries, and re-sharding work unchanged.
        self.mesh = mesh
        self.client_axis = client_axis
        self.client_xs = jnp.asarray(client_xs)
        self.client_ys = jnp.asarray(client_ys)
        self.eval_xs = jnp.asarray(eval_xs) if eval_xs is not None else None
        self.eval_ys = jnp.asarray(eval_ys) if eval_ys is not None else None
        self.accuracy_fn = accuracy_fn
        self.key = jax.random.key(cfg.seed)
        # round_fn memo (engine program-cache contract: executables are keyed
        # on round_fn identity, so the trainer must hand back the same object
        # across run() calls)
        self._round_fn_memo = None
        # k-DPP spectral cache, keyed on the kernel array it was built from;
        # _init_profiles (reprofile boundaries) invalidates it with the kernel
        self._eig_state = None
        self._eig_kernel = None

        n_c = client_xs.shape[1]
        self.client_sizes = jnp.full((cfg.num_clients,), float(n_c))
        self.client_label_dists = metrics_lib.label_distributions(
            self.client_ys, cfg.num_classes
        )
        self.global_label_dist = metrics_lib.label_distribution(
            self.client_ys.reshape(-1), cfg.num_classes
        )

        # --- jitted building blocks (memoised across trainers) -----------
        steps = self._steps_per_round(n_c)
        self._round_step = _cached_round_step(loss_fn, cfg.lr, steps, cfg.grad_clip)
        self._loss_of = _cached_loss_of(loss_fn)

        # history
        self.history: Dict[str, List] = {"round": [], "acc": [], "gemd": [], "loss": []}
        self.round_state = selection_lib.RoundState(
            num_clients=cfg.num_clients,
            client_sizes=self.client_sizes,
        )
        self._init_profiles()
        # initial last-known local losses (one global pass — the server can
        # get these from the initial broadcast in practice)
        self.losses = self._loss_of(self.params, self.client_xs, self.client_ys)
        self.round_state.losses = self.losses

    # ------------------------------------------------------------------
    def _steps_per_round(self, n_c: int) -> int:
        return engine_lib._steps_per_round(self.cfg, n_c)

    def _init_profiles(self):
        """Alg. 1 lines 2-5: one-shot FC-1 profiling + kernel construction."""
        feats = profiles_lib.profile_stacked_clients(
            self.feature_fn, self.params, self.client_xs
        )
        self.round_state.profiles = feats
        if self.cfg.candidate_frac is None:
            self.round_state.kernel = similarity_lib.kernel_from_profiles(
                feats, use_kernel=self.cfg.use_pallas_kernel
            )
        else:
            # funnel (DESIGN.md §10): the kernel lives on the Q-candidate
            # block and is rebuilt per segment by engine.funnel_fields — the
            # trainer never materialises the C×C matrix
            self.round_state.kernel = None
        # the spectral cache decomposes exactly this kernel — invalidate
        self._eig_state = None
        self._eig_kernel = None
        # representative-gradient fingerprints for the Cluster baseline
        if isinstance(self.strategy, selection_lib.ClusterSelection):
            gp = [
                profiles_lib.representative_gradient_profile(
                    self.loss_fn, self.params, self.client_xs[c], self.client_ys[c]
                )
                for c in range(self.cfg.num_clients)
            ]
            self.round_state.grad_profiles = jnp.stack(gp)

    def _make_client_batches(self, key, sel: jax.Array):
        """Slice the selected clients' data into (C_p, steps, B, ...) batches."""
        return engine_lib.make_client_batches(
            self.cfg, key, self.client_xs, self.client_ys, sel
        )

    # ------------------------------------------------------------------
    def _supports_engine(self) -> bool:
        """Pure-selection strategies run scanned; host-only customs fall back.

        A strategy is engine-capable when it overrides the canonical
        ``draw_fn`` — or, pre-registry style, the legacy ``select_fn``
        (which the base ``draw_fn`` dispatches to)."""
        base = selection_lib.SelectionStrategy
        return (
            type(self.strategy).draw_fn is not base.draw_fn
            or type(self.strategy).select_fn is not base.select_fn
        )

    def _cluster_labels(self, candidates=None) -> jax.Array:
        """Host-fitted cluster labels — restricted to the funnel candidate
        rows when ``candidates`` is given, so the fit sees the same
        fingerprints as the unfunneled path (with ``candidates == arange(C)``
        the labels are bit-identical: the Q=C parity contract)."""
        cfg = self.cfg
        if isinstance(self.strategy, selection_lib.ClusterSelection):
            feats = (
                self.round_state.grad_profiles
                if self.round_state.grad_profiles is not None
                else self.round_state.profiles
            )
            if candidates is not None:
                feats = jnp.take(feats, candidates, axis=0)
            return self.strategy.fit(feats, cfg.clients_per_round)
        n = cfg.num_clients if candidates is None else candidates.shape[0]
        return jnp.zeros((n,), jnp.int32)

    def eig_state(self) -> dpp_lib.KDPPSamplerState:
        """Spectral cache of the current kernel (one eigh per kernel refresh).

        Memoised on the kernel array identity; ``_init_profiles`` (i.e. every
        ``reprofile_every`` boundary) drops the memo together with the kernel
        it decomposed, so a stale spectrum can never outlive its kernel.
        Strategies that never draw from the cache get the cheap
        identity-layout placeholder instead of an O(C³) eigh.
        """
        kern = self.round_state.kernel
        if self._eig_state is None or self._eig_kernel is not kern:
            k = self.cfg.clients_per_round
            if getattr(self.strategy, "uses_spectral_cache", False):
                self._eig_state = dpp_lib.kdpp_sampler_state(kern, k)
            else:
                self._eig_state = dpp_lib.identity_sampler_state(
                    self.cfg.num_clients, k
                )
            self._eig_kernel = kern
        return self._eig_state

    def server_state(self) -> engine_lib.ServerState:
        """Pack the trainer's current server knowledge into a ServerState
        (laid out over ``self.mesh``'s client axis when a mesh is set).

        With ``cfg.staleness_bound`` set (DESIGN.md §9) the staleness
        bookkeeping is (re-)initialised from the *current* params: the ring
        buffer starts with every slot at θ_now and the per-shard counters at
        0 — each ``run()`` call opens with a freshly synced federation (the
        scanned segments inside one run carry the evolving ring/counters
        through unchanged)."""
        cfg = self.cfg
        candidates = None
        if cfg.candidate_frac is not None:
            # funnel (DESIGN.md §10): stage-1 prefilter on the *current*
            # losses, candidate kernel + spectral cache on the Q-block
            candidates, kernel, eig_state = engine_lib.funnel_fields(
                cfg, self.key, self.round_state.profiles, self.losses,
                strategy=self.strategy, mesh=self.mesh,
                client_axis=self.client_axis,
                round_index=self.round_state.round,
            )
            cluster_labels = self._cluster_labels(candidates)
        else:
            kernel = self.round_state.kernel
            eig_state = self.eig_state()
            cluster_labels = self._cluster_labels()
        param_hist = shard_staleness = None
        if cfg.staleness_bound is not None:
            param_hist, shard_staleness = staleness_lib.init_staleness_fields(
                self.params, cfg.staleness_bound, self.mesh, self.client_axis
            )
        state = engine_lib.ServerState(
            params=self.params,
            key=self.key,
            round=jnp.asarray(self.round_state.round, jnp.int32),
            losses=self.losses,
            kernel=kernel,
            profiles=self.round_state.profiles,
            eig_state=eig_state,
            cluster_labels=cluster_labels,
            client_xs=self.client_xs,
            client_ys=self.client_ys,
            client_sizes=self.client_sizes,
            client_label_dists=self.client_label_dists,
            global_label_dist=self.global_label_dist,
            strategy_index=jnp.asarray(0, jnp.int32),
            param_hist=param_hist,
            shard_staleness=shard_staleness,
            candidates=candidates,
            quarantine=(
                jnp.zeros((cfg.num_clients,), jnp.int32)
                if cfg.guarded()
                else None
            ),
            algo_state=local_algos_lib.init_client_states(
                cfg.local_algo_obj(), self.params, cfg.num_clients
            ),
        )
        if self.mesh is not None:
            state = engine_lib.shard_server_state(
                state, self.mesh, self.client_axis
            )
        return state

    def round_fn(self):
        """The engine's pure per-round transition for this trainer.

        Memoised on the instance: the engine caches compiled scan programs ON
        the round_fn object (identity keying — see ``engine._programs``), so
        handing back a fresh closure per call would recompile the whole
        program every ``run()``.  The no-eval-data path additionally shares
        one round_fn across trainers with identical round semantics
        (``_cached_round_fn``), letting benchmark sweeps reuse the executable.
        """
        if self._round_fn_memo is None:
            if self.eval_xs is not None:
                # held-out eval data lives in the closure -> per-trainer memo
                self._round_fn_memo = engine_lib.make_round_fn(
                    self.cfg, self.loss_fn, (self.strategy,),
                    accuracy_fn=self.accuracy_fn,
                    eval_data=(self.eval_xs, self.eval_ys),
                    mesh=self.mesh, client_axis=self.client_axis,
                )
            else:
                self._round_fn_memo = _cached_round_fn(
                    self.cfg, self.loss_fn, self.accuracy_fn, self.strategy,
                    self.mesh, self.client_axis,
                )
        return self._round_fn_memo

    def _absorb(self, state: engine_lib.ServerState):
        """Pull the scanned segment's final state back into trainer fields."""
        self.params = state.params
        self.key = state.key
        self.losses = state.losses
        self.round_state.losses = self.losses
        self.round_state.round = int(state.round)

    # ------------------------------------------------------------------
    def run(
        self, rounds: Optional[int] = None, progress: bool = False,
        sink=None,
    ) -> Dict[str, List]:
        """Run rounds through the scanned engine (legacy loop as fallback).

        Profile refreshes (``reprofile_every``) happen on scan-segment
        boundaries: each segment is one compiled ``lax.scan``, then profiles
        / kernel / cluster labels are re-fitted on host and the next segment
        starts from the refreshed state.

        ``sink`` (an :class:`repro.obs.TelemetrySink`, DESIGN.md §14) drains
        each segment's stacked outputs to JSONL at the same boundaries and
        records the reprofile events — strictly host-side, so passing a sink
        never changes the compiled program.
        """
        cfg = self.cfg
        rounds = rounds or cfg.rounds
        if not self._supports_engine():
            if cfg.candidate_frac is not None:
                raise ValueError(
                    "candidate_frac requires a strategy with a pure "
                    "select_fn (the scanned engine path): the legacy host "
                    "loop is unfunneled"
                )
            if cfg.guarded():
                raise ValueError(
                    "faults / robust aggregation require a strategy with a "
                    "pure select_fn (the scanned engine path): the legacy "
                    "host loop has no fault-injection or quarantine layer"
                )
            if cfg.local_algo != "fedavg":
                raise ValueError(
                    f"local_algo={cfg.local_algo!r} requires a strategy with "
                    "a pure draw_fn (the scanned engine path): the legacy "
                    "host loop is hardwired to plain SGD (fedavg)"
                )
            return self.run_legacy(rounds=rounds, progress=progress)

        round_fn = self.round_fn()
        segment = cfg.reprofile_every or rounds
        start_round = self.round_state.round
        done = 0
        outs: List[Dict] = []
        state = self.server_state()
        while done < rounds:
            n = min(segment, rounds - done)
            state, seg_outs = engine_lib.run_scanned(
                round_fn, state, n, sink=sink
            )
            outs.append(jax.tree_util.tree_map(np.asarray, seg_outs))
            done += n
            if done < rounds and cfg.reprofile_every:
                self._absorb(state)
                with obs_tracing_lib.annotate("fl.reprofile"):
                    self._init_profiles()  # host: re-profile + re-fit clusters
                if sink is not None:
                    sink.emit(
                        "fl_reprofile",
                        round=self.round_state.round,
                        funneled=cfg.candidate_frac is not None,
                    )
                if cfg.candidate_frac is not None:
                    # reprofile segments RE-FUNNEL (DESIGN.md §10): fresh
                    # profiles + evolved losses -> new candidate set, new
                    # Q×Q kernel, new spectral cache — the carried key gives
                    # fresh environment predictions without touching the
                    # per-round selection/batch streams
                    cand, kern, eig = engine_lib.funnel_fields(
                        cfg, self.key, self.round_state.profiles,
                        self.losses, strategy=self.strategy,
                        mesh=self.mesh, client_axis=self.client_axis,
                        round_index=self.round_state.round,
                    )
                    state = dataclasses.replace(
                        state,
                        kernel=kern,
                        profiles=self.round_state.profiles,
                        eig_state=eig,
                        cluster_labels=self._cluster_labels(cand),
                        candidates=cand,
                    )
                else:
                    state = dataclasses.replace(
                        state,
                        kernel=self.round_state.kernel,
                        profiles=self.round_state.profiles,
                        eig_state=self.eig_state(),  # re-decompose refreshed kernel
                        cluster_labels=self._cluster_labels(),
                    )
                if self.mesh is not None:
                    # restore the mesh layout on the refreshed host arrays so
                    # every segment reuses one compiled scan program
                    state = engine_lib.shard_server_state(
                        state, self.mesh, self.client_axis
                    )
        self._absorb(state)
        merged = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *outs
        )
        final_acc = None
        total = start_round + rounds
        if total % cfg.eval_every != 0:
            final_acc = self._evaluate()
        hist = engine_lib.history_from_outputs(
            merged, cfg.eval_every, final_acc=final_acc
        )
        for k in self.history:
            self.history[k].extend(hist[k])
        if progress:
            for t, a, g, l in zip(
                hist["round"], hist["acc"], hist["gemd"], hist["loss"]
            ):
                print(
                    f"[{self.strategy.name}] round {t:4d} acc={a:.4f} "
                    f"gemd={g:.3f} loss={l:.4f}"
                )
        return self.history

    def run_legacy(
        self, rounds: Optional[int] = None, progress: bool = False
    ) -> Dict[str, List]:
        """The host loop: one jitted step per round, selection and metrics
        dispatched from host.  Kept as the oracle for the scanned engine (see
        ``benchmarks/engine_bench.py``) and for strategies without a pure
        ``select_fn``.

        Note: selection math is the *current* pure layer for both paths —
        in particular ``ClusterSelection``'s per-round draw is now a jax
        categorical (was a host numpy RNG pre-engine), so 'cluster' cohorts
        differ from pre-engine runs at the same seed (same distribution)."""
        cfg = self.cfg
        rounds = rounds or cfg.rounds
        for t in range(1, rounds + 1):
            self.key, k_sel, k_batch = jax.random.split(self.key, 3)
            self.round_state.round = t
            sel = self.strategy.select(k_sel, self.round_state, cfg.clients_per_round)
            batches = self._make_client_batches(k_batch, sel)
            weights = jnp.take(self.client_sizes, sel)
            self.params, mean_loss = self._round_step(self.params, batches, weights)

            # refresh last-known losses for the selected clients
            sel_losses = self._loss_of(
                self.params, jnp.take(self.client_xs, sel, 0), jnp.take(self.client_ys, sel, 0)
            )
            self.losses = self.losses.at[sel].set(sel_losses)
            self.round_state.losses = self.losses

            g = metrics_lib.gemd(
                self.client_label_dists, self.client_sizes, sel, self.global_label_dist
            )
            if cfg.reprofile_every and t % cfg.reprofile_every == 0:
                self._init_profiles()

            if t % cfg.eval_every == 0 or t == rounds:
                acc = self._evaluate()
                self.history["round"].append(t)
                self.history["acc"].append(float(acc))
                self.history["gemd"].append(float(g))
                self.history["loss"].append(float(mean_loss))
                if progress:
                    print(
                        f"[{self.strategy.name}] round {t:4d} acc={float(acc):.4f} "
                        f"gemd={float(g):.3f} loss={float(mean_loss):.4f}"
                    )
        return self.history

    def _evaluate(self) -> float:
        if self.accuracy_fn is None:
            return float("nan")
        if self.eval_xs is not None:
            return self.accuracy_fn(self.params, self.eval_xs, self.eval_ys)
        # Fig.-1 protocol: accuracy of the global model on the training set
        xs = self.client_xs.reshape((-1,) + self.client_xs.shape[2:])
        ys = self.client_ys.reshape(-1)
        return self.accuracy_fn(self.params, xs, ys)
