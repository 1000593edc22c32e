"""Scan-compiled continuous-batching serving engine (DESIGN.md §13).

The legacy ``launch/serve.py`` loop pays one host→device dispatch per token
and drains a whole batch before admitting new traffic.  This module rebuilds
serving the way ``fl/engine.py`` rebuilt the trainer — as a pure state
machine:

* :class:`DecodeState` — one pytree holding everything a slot batch evolves:
  per-slot model caches (``init_caches(..., per_slot=True)``: every slot at
  its own depth), the last sampled token, the generated-token buffer,
  per-slot generation counters/budgets, active/stop masks, and per-slot
  sampling key streams.
* :func:`make_decode_fn` — one decode step for **all** slots as a pure
  ``state -> state`` body: model ``decode_step`` (optionally through the
  Pallas flash-decode kernel), per-slot sampling, stop handling (budget
  reached or EOS), masked token write-back.  Inactive slots ride along with
  their updates masked — fixed shapes, zero recompilation.
* :func:`run_scan` / :func:`run_while` — N steps as one ``lax.scan``, or a
  while-scan that exits as soon as every slot has stopped (per-slot
  stopping with early wall-clock exit).
* :func:`make_admit_fn` — **slot-based continuous batching**: admit one
  queued sequence into the first free slot entirely at the jit level
  (prefill → sample the first token → scatter cache/buffer rows at the slot
  index via the PR-4 stable-argsort slot table).  Mixed-length traffic
  reuses the same compiled program for every admission — the engine asserts
  this (see :meth:`ServeEngine.compile_counts`).
* :class:`ServeEngine` — the host-side admission queue: chunked scan decode,
  harvest finished slots, refill from the queue, repeat.  The only host
  work is queue bookkeeping between compiled chunks.

Everything is arch-generic through ``models.transformer``: dense GQA caches,
SWA ring buffers (mixtral), RWKV/RG-LRU O(1) recurrent states — a slot row
is whatever the model's cache holds for one sequence.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.obs import tracing as obs_tracing_lib
from repro.serve.sampling import fresh_key_data, sample_tokens

__all__ = [
    "ServeConfig",
    "DecodeState",
    "init_decode_state",
    "make_decode_fn",
    "make_admit_fn",
    "run_scan",
    "run_while",
    "ServeEngine",
]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving knobs (trace constants)."""

    batch: int  # slot count B
    cache_len: int  # per-slot cache capacity (>= prompt + generation budget)
    max_new: int  # output buffer width (>= any per-slot budget)
    temperature: float = 0.0  # 0.0 = greedy (the parity-oracle path)
    eos_id: Optional[int] = None  # None = budget-only stopping
    use_flash: bool = False  # route decode attention through flash-decode
    decode_chunk: int = 8  # scan steps between admission checks

    def __post_init__(self):
        if self.batch < 1:
            raise ValueError(f"batch={self.batch} must be >= 1")
        if self.max_new < 1 or self.max_new > self.cache_len:
            raise ValueError(
                f"max_new={self.max_new} must be in [1, cache_len={self.cache_len}]"
            )
        if self.temperature < 0.0:
            raise ValueError(f"temperature={self.temperature} must be >= 0")
        if self.decode_chunk < 1:
            raise ValueError(f"decode_chunk={self.decode_chunk} must be >= 1")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DecodeState:
    """Everything a slot batch evolves, as one pytree (all leaves lead with
    the slot axis B except the caches, whose unit leaves lead with the layer
    stack: (reps, B, ...) — see ``_scatter_slot_rows``)."""

    caches: PyTree  # per-slot model caches (pos: (B,))
    last_tok: jax.Array  # (B, 1) int32 next decode input
    out_tokens: jax.Array  # (B, max_new) int32 generated tokens
    n_gen: jax.Array  # (B,) int32 generated so far (incl. prefill sample)
    gen_target: jax.Array  # (B,) int32 per-slot generation budget
    active: jax.Array  # (B,) bool slot is decoding
    seq_ids: jax.Array  # (B,) int32 sequence id; -1 = empty (occupancy: set
    # at admission, cleared only at harvest — unlike ``active``, which a
    # budget-1 admission or a stop clears before the host has the tokens)
    sample_keys: jax.Array  # (B, key_words) uint32 per-slot PRNG streams
    step: jax.Array  # () int32 decode steps taken


def init_decode_state(cfg: ModelConfig, scfg: ServeConfig,
                      key: Optional[jax.Array] = None) -> DecodeState:
    """All-empty slots; admission fills them."""
    b = scfg.batch
    key = jax.random.key(0) if key is None else key
    return DecodeState(
        caches=T.init_caches(cfg, b, scfg.cache_len, per_slot=True),
        last_tok=jnp.zeros((b, 1), jnp.int32),
        out_tokens=jnp.zeros((b, scfg.max_new), jnp.int32),
        n_gen=jnp.zeros((b,), jnp.int32),
        gen_target=jnp.zeros((b,), jnp.int32),
        active=jnp.zeros((b,), bool),
        seq_ids=jnp.full((b,), -1, jnp.int32),
        sample_keys=fresh_key_data(key, b),
        step=jnp.zeros((), jnp.int32),
    )


# ------------------------------------------------------------- decode step


def make_decode_fn(cfg: ModelConfig, scfg: ServeConfig) -> Callable:
    """Pure one-token step for all slots: ``(params, state) -> state``.

    Inactive slots run the model too (fixed shapes are the whole point) but
    every visible update — token write, counter, stop mask — is masked, and
    their sampled tokens are pinned to 0.  Their caches do advance; a slot's
    cache is only meaningful between admission and stop, and admission
    rewrites it wholesale.
    """

    def decode_fn(params: PyTree, state: DecodeState) -> DecodeState:
        logits, caches = T.decode_step(
            cfg, params, state.last_tok, state.caches, use_flash=scfg.use_flash
        )
        toks, keys = sample_tokens(logits, state.sample_keys, scfg.temperature)
        toks = jnp.where(state.active, toks, 0)

        # record into each slot's next free cell (masked; clip keeps the
        # scatter in bounds for exhausted slots)
        b = toks.shape[0]
        cell = jnp.minimum(state.n_gen, scfg.max_new - 1)
        cur = state.out_tokens[jnp.arange(b), cell]
        out = state.out_tokens.at[jnp.arange(b), cell].set(
            jnp.where(state.active, toks, cur)
        )
        n_gen = state.n_gen + state.active.astype(jnp.int32)

        # per-slot stopping: budget reached, or EOS sampled
        active = state.active & (n_gen < state.gen_target)
        if scfg.eos_id is not None:
            active &= toks != scfg.eos_id
        return DecodeState(
            caches=caches,
            last_tok=toks[:, None],
            out_tokens=out,
            n_gen=n_gen,
            gen_target=state.gen_target,
            active=active,
            seq_ids=state.seq_ids,
            sample_keys=keys,
            step=state.step + 1,
        )

    return decode_fn


def run_scan(decode_fn: Callable, params: PyTree, state: DecodeState,
             steps: int) -> DecodeState:
    """``steps`` decode steps as one ``lax.scan`` (fixed trip count)."""

    def body(s, _):
        return decode_fn(params, s), None

    state, _ = lax.scan(body, state, None, length=steps)
    return state


def run_while(decode_fn: Callable, params: PyTree, state: DecodeState,
              max_steps: int) -> DecodeState:
    """While-scan with per-slot stopping: exits as soon as every slot is
    done (or at ``max_steps``), so a batch of short sequences doesn't pay
    the long tail's wall-clock."""
    limit = state.step + max_steps

    def cond(s):
        return jnp.any(s.active) & (s.step < limit)

    return lax.while_loop(cond, lambda s: decode_fn(params, s), state)


# ----------------------------------------------------- slot-based admission


def _scatter_slot_rows(dst: jax.Array, src: jax.Array, slot: jax.Array,
                       axis: int) -> jax.Array:
    """Write ``src`` (one slot row, batch dim of size 1 at ``axis``) into
    ``dst`` at index ``slot`` along ``axis``."""
    idx = (slice(None),) * axis + (slot,)
    return dst.at[idx].set(jnp.squeeze(src, axis=axis))


def _scatter_caches(dst: PyTree, src: PyTree, slot: jax.Array) -> PyTree:
    """Slot-scatter a whole cache pytree: unit leaves are layer-stacked
    (reps, B, ...) -> batch at axis 1; remainder leaves lead with B."""
    unit = jax.tree_util.tree_map(
        lambda d, s: _scatter_slot_rows(d, s, slot, axis=1),
        dst["unit"], src["unit"],
    )
    rem = jax.tree_util.tree_map(
        lambda d, s: _scatter_slot_rows(d, s, slot, axis=0),
        dst["rem"], src["rem"],
    )
    return {"unit": unit, "rem": rem}


def make_admit_fn(cfg: ModelConfig, scfg: ServeConfig,
                  prompt_len: int) -> Callable:
    """Jit-level admission: prefill one queued sequence and install it in
    the first free slot.

    ``(params, state, prompt (1, P), gen_target (), seq_id (), key_data)
    -> state``.  The free slot comes from the PR-4 stable-argsort slot
    table (``argsort(seq_ids >= 0, stable=True)[0]`` — empty-first order),
    the prefill runs on a width-1 per-slot cache of the same ``cache_len``
    so every leaf scatters row-for-row, and the first token is sampled from
    the prefill logits with the sequence's own key stream.  One compiled
    program serves every admission — no retracing as traffic mixes lengths.

    Free means *unoccupied* (``seq_ids < 0``), not merely inactive: a
    budget-1 admission finishes at prefill and sits inactive-but-occupied
    until the host harvests it, and a second admission in the same refill
    wave must not overwrite that un-harvested result.
    """

    def admit_fn(params: PyTree, state: DecodeState, prompt: jax.Array,
                 gen_target: jax.Array, seq_id: jax.Array,
                 key_data: jax.Array) -> DecodeState:
        # slot table: stable argsort puts empty (seq_id < 0 -> False) slots
        # first; occupancy, not activity — see the docstring
        slot = jnp.argsort(state.seq_ids >= 0, stable=True)[0]

        caches1 = T.init_caches(cfg, 1, scfg.cache_len, per_slot=True)
        positions = jnp.arange(prompt_len, dtype=jnp.int32)[None, :]
        if cfg.pos_style == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, 1, prompt_len))
        hidden, caches1, _ = T.forward(
            cfg, params, prompt, positions, caches1, use_flash=scfg.use_flash
        )
        logits = T.logits_from_hidden(cfg, params, hidden[:, -1:])
        tok, key_data = sample_tokens(logits, key_data[None], scfg.temperature)
        tok, key_data = tok[0], key_data[0]

        b = scfg.batch
        onehot = jnp.arange(b) == slot
        out_row = jnp.zeros((scfg.max_new,), jnp.int32).at[0].set(tok)
        return DecodeState(
            caches=_scatter_caches(state.caches, caches1, slot),
            last_tok=state.last_tok.at[slot, 0].set(tok),
            out_tokens=state.out_tokens.at[slot].set(out_row),
            n_gen=state.n_gen.at[slot].set(1),
            gen_target=state.gen_target.at[slot].set(gen_target),
            active=state.active | (onehot & (gen_target > 1)),
            seq_ids=state.seq_ids.at[slot].set(seq_id),
            sample_keys=state.sample_keys.at[slot].set(key_data),
            step=state.step,
        )

    return admit_fn


# ------------------------------------------------------------- host engine


@dataclasses.dataclass
class Finished:
    seq_id: int
    tokens: np.ndarray  # (n_gen,) generated tokens (incl. prefill sample)


class ServeEngine:
    """Host-side continuous batching on top of the compiled pieces.

    The host owns only the admission queue and harvest bookkeeping; decode
    runs in compiled chunks of ``scfg.decode_chunk`` steps, and every
    admission reuses one compiled ``admit_fn``.  ``compile_counts()``
    exposes the jit caches so benches/tests can assert zero recompilation
    after warmup.
    """

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params: PyTree,
                 prompt_len: int, key: Optional[jax.Array] = None,
                 telemetry=None):
        if prompt_len < 1:
            raise ValueError(f"prompt_len={prompt_len} must be >= 1")
        if scfg.cache_len < prompt_len + scfg.max_new:
            # an undersized cache wraps its write index (pos % slots in
            # attention.py) and silently corrupts the oldest context
            raise ValueError(
                f"cache_len={scfg.cache_len} < prompt_len + max_new = "
                f"{prompt_len + scfg.max_new}; size the per-slot cache to "
                "hold the full prompt plus the generation budget"
            )
        self.cfg, self.scfg, self.params = cfg, scfg, params
        self.prompt_len = prompt_len
        key = jax.random.key(0) if key is None else key
        self._host_key, state_key = jax.random.split(key)
        self.state = init_decode_state(cfg, scfg, state_key)
        decode_fn = make_decode_fn(cfg, scfg)
        self._chunk = jax.jit(
            lambda p, s: run_scan(decode_fn, p, s, scfg.decode_chunk)
        )
        self._admit = jax.jit(make_admit_fn(cfg, scfg, prompt_len))
        self.finished: List[Finished] = []
        self._queue: List[Tuple[int, np.ndarray, int]] = []
        self._next_id = 0
        # Telemetry (DESIGN.md §14): an optional repro.obs.TelemetrySink.
        # Strictly host-side — events are emitted from the queue bookkeeping
        # between compiled chunks (submit / admit / harvest / chunk
        # boundaries), so telemetry=None is byte-identical behaviour and a
        # sink can never add a compiled program (compile_counts() stays 2).
        self._sink = telemetry
        self._t_submit: Dict[int, float] = {}
        self._pending_admits: List[Tuple[int, int]] = []

    # -- queue ------------------------------------------------------------

    def submit(self, prompt: np.ndarray, gen_target: int) -> int:
        """Queue one prompt (``(prompt_len,)`` int tokens); returns seq id."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.shape != (self.prompt_len,):
            raise ValueError(
                f"prompt must be ({self.prompt_len},), got {prompt.shape}"
            )
        if not 1 <= gen_target <= self.scfg.max_new:
            raise ValueError(
                f"gen_target={gen_target} must be in [1, {self.scfg.max_new}]"
            )
        seq_id = self._next_id
        self._next_id += 1
        self._queue.append((seq_id, prompt, gen_target))
        if self._sink is not None:
            self._t_submit[seq_id] = time.perf_counter()
            self._sink.emit(
                "serve_submit", seq_id=seq_id, gen_target=gen_target,
                queue_depth=len(self._queue),
            )
        return seq_id

    # -- engine steps ------------------------------------------------------

    def _refill(self) -> None:
        # free = unoccupied (seq_id < 0), not merely inactive: stopped slots
        # keep their seq_id until harvest and must not be admitted over
        free = int((np.asarray(self.state.seq_ids) < 0).sum())
        n = min(free, len(self._queue))
        admitted = []
        for _ in range(n):
            seq_id, prompt, tgt = self._queue.pop(0)
            self._host_key, sub = jax.random.split(self._host_key)
            with obs_tracing_lib.annotate("serve.admit"):
                self.state = self._admit(
                    self.params, self.state, jnp.asarray(prompt)[None],
                    jnp.int32(tgt), jnp.int32(seq_id), fresh_key_data(sub, 1)[0],
                )
            # budget-1 sequences finish at admission (prefill sampled their
            # only token); harvest them below like any stopped slot
            admitted.append((seq_id, len(self._queue)))
        if self._sink is not None:
            # TTFT is emitted from _harvest, right after its done-mask fetch
            # — a sync on the same dependency chain as the wave's prefills,
            # which the telemetry-off path pays identically.  Blocking here
            # instead would serialise admit dispatches the off path
            # pipelines, and the gap between the two sync points is one
            # fused elementwise op on (batch,) arrays.
            self._pending_admits.extend(admitted)
        self._harvest()

    def _harvest(self) -> None:
        """Collect slots that stopped (budget/EOS) and mark them free."""
        st = self.state
        done = np.asarray(~st.active & (st.seq_ids >= 0) & (st.n_gen > 0))
        if self._sink is not None and self._pending_admits:
            # the done-mask fetch above blocked on the admit wave's prefills
            # — the admitted sequences' first tokens exist as of now
            now = time.perf_counter()
            occupancy = int((np.asarray(st.seq_ids) >= 0).sum())
            for seq_id, depth in self._pending_admits:
                self._sink.emit(
                    "serve_admit", seq_id=seq_id,
                    ttft_s=round(now - self._t_submit.get(seq_id, now), 6),
                    queue_depth=depth, occupancy=occupancy,
                )
            self._pending_admits = []
        if not done.any():
            return
        out = np.asarray(st.out_tokens)
        n_gen = np.asarray(st.n_gen)
        ids = np.asarray(st.seq_ids)
        for slot in np.nonzero(done)[0]:
            self.finished.append(
                Finished(int(ids[slot]), out[slot, : int(n_gen[slot])].copy())
            )
            if self._sink is not None:
                seq_id = int(ids[slot])
                now = time.perf_counter()
                t_sub = self._t_submit.pop(seq_id, now)
                self._sink.emit(
                    "serve_finish", seq_id=seq_id,
                    n_tokens=int(n_gen[slot]),
                    latency_s=round(now - t_sub, 6),
                )
        mask = jnp.asarray(done)
        self.state = dataclasses.replace(
            st, seq_ids=jnp.where(mask, -1, st.seq_ids),
            n_gen=jnp.where(mask, 0, st.n_gen),
        )

    def run(self, drain: bool = False) -> List[Finished]:
        """Drive queue + slots to completion; returns finished sequences in
        completion order.

        ``drain=True`` only admits at wave boundaries (every slot idle) —
        the drain-and-refill contrast arm for the continuous-batching
        benches: same compiled admit/decode programs, worse scheduling."""
        self._maybe_refill(drain)
        while self._queue or bool(np.any(np.asarray(self.state.active))):
            if bool(np.any(np.asarray(self.state.active))):
                if self._sink is None:
                    with obs_tracing_lib.annotate("serve.decode_chunk"):
                        self.state = self._chunk(self.params, self.state)
                else:
                    self._timed_chunk()
            self._harvest()
            self._maybe_refill(drain)
        return self.finished

    def _timed_chunk(self) -> None:
        """One decode chunk with a ``serve_chunk`` event: chunk wall time,
        exact tokens generated (n_gen delta), tok/s, slot occupancy and
        queue depth.  Only runs with a sink attached — the telemetry-off
        path never pays the extra sync."""
        n_before, active_arr = jax.device_get(
            (self.state.n_gen, self.state.active)
        )
        active = int(active_arr.sum())
        t0 = time.perf_counter()
        with obs_tracing_lib.annotate("serve.decode_chunk"):
            self.state = self._chunk(self.params, self.state)
        n_after = jax.device_get(self.state.n_gen)
        dt = time.perf_counter() - t0
        tokens = int((n_after - n_before).sum())
        self._sink.emit(
            "serve_chunk", steps=self.scfg.decode_chunk, tokens=tokens,
            dt_s=round(dt, 6), tok_s=round(tokens / max(dt, 1e-9), 1),
            active_slots=active, batch=self.scfg.batch,
            queue_depth=len(self._queue),
        )

    def _maybe_refill(self, drain: bool) -> None:
        if drain and bool(np.any(np.asarray(self.state.active))):
            self._harvest()
            return
        self._refill()

    def reset(self, key: Optional[jax.Array] = None) -> None:
        """Fresh state/queue/results; compiled programs are kept (benches
        time repeat traffic without re-paying compilation)."""
        if key is not None:
            self._host_key, key = jax.random.split(key)
        else:
            self._host_key, key = jax.random.split(self._host_key)
        self.state = init_decode_state(self.cfg, self.scfg, key)
        self.finished = []
        self._queue = []
        self._next_id = 0
        self._t_submit = {}
        self._pending_admits = []

    # -- introspection -----------------------------------------------------

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program counts per jitted entry point (warmup leaves
        exactly one each; continuous traffic must not add more)."""
        return {
            "decode_chunk": self._chunk._cache_size(),
            "admit": self._admit._cache_size(),
        }
