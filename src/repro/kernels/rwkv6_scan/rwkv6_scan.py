"""RWKV6 WKV recurrence kernel — the TPU answer to the CUDA wkv6 kernel.

The CUDA original keeps the per-head (hd×hd) state in registers/shared memory
with warp-level parallelism over the value dim; the TPU adaptation keeps the
state in a VMEM fp32 scratch that *persists across the sequential time-chunk
grid dimension*, processes ``block_t`` tokens per grid step entirely out of
VMEM, and expresses the per-token update as rank-1 outer products over the
(hd_k × hd_v) state — vector-unit work with hd-wide lanes (hd = 64 → full
native lanes; no warp shuffles exist or are needed).

Grid: (B, H, T/block_t) — time is innermost/sequential per (batch, head).
Recurrence (per head):

    y_t = r_tᵀ (S + diag(u ⊙ k_t) v_tᵀ)
    S  ← diag(w_t) S + k_t v_tᵀ

Inputs r/k/v/w are (B, T, H, hd); the kernel reads them head-major,
(B, H, T, hd), so every block ends in (block_t, hd) as the TPU's (8, 128)
block rule wants.  Each chunk is upcast once into f32 VMEM scratch, where a
token's row can be loaded at any offset (bf16 rows are packed in pairs and
cannot).  The initial state (B, H, hd, hd) streams in once at chunk 0 and
the final state streams out at the last chunk (decode hand-off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_kernel"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, r_scr, k_scr, v_scr, w_scr, y_scr, *, bt, n_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _load_state():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    for src, dst in ((r_ref, r_scr), (k_ref, k_scr), (v_ref, v_scr), (w_ref, w_scr)):
        dst[...] = src[0, 0].astype(jnp.float32)
    u = jnp.transpose(u_ref[0].astype(jnp.float32))  # (hd, 1)

    def step(t, s):
        row = pl.ds(t, 1)
        r, k, v, w = r_scr[row, :], k_scr[row, :], v_scr[row, :], w_scr[row, :]
        kv = jnp.transpose(k) * v  # (hd_k, hd_v) rank-1
        y_scr[row, :] = jnp.sum((s + u * kv) * jnp.transpose(r), axis=0, keepdims=True)
        return jnp.transpose(w) * s + kv

    s_scr[...] = lax.fori_loop(0, bt, step, s_scr[...])
    y_ref[0, 0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ti == n_t - 1)
    def _store_state():
        sout_ref[0, 0] = s_scr[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6_kernel(
    r: jax.Array,  # (B, T, H, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1)
    u: jax.Array,  # (H, hd)
    s0: jax.Array,  # (B, H, hd, hd) fp32
    block_t: int = 128,
    interpret: bool = False,
):
    """-> (y (B, T, H, hd), final state (B, H, hd, hd))."""
    b, t, h, hd = r.shape
    bt = min(block_t, t)
    tp = -(-t // bt) * bt
    pad = ((0, 0), (0, 0), (0, tp - t), (0, 0))
    # head-major (B, H, T, hd); pad with w=1, k=0 so padded steps leave the
    # state untouched
    r, k, v = (jnp.pad(x.transpose(0, 2, 1, 3), pad) for x in (r, k, v))
    w = jnp.pad(w.transpose(0, 2, 1, 3), pad, constant_values=1.0)
    n_t = tp // bt

    seq_spec = pl.BlockSpec((1, 1, bt, hd), lambda bi, hi, ti: (bi, hi, ti, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ti: (bi, hi, 0, 0))
    chunk = pltpu.VMEM((bt, hd), jnp.float32)
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, n_t=n_t),
        grid=(b, h, n_t),
        in_specs=[
            seq_spec,
            seq_spec,
            seq_spec,
            seq_spec,
            pl.BlockSpec((1, 1, hd), lambda bi, hi, ti: (hi, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tp, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)] + [chunk] * 5,
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, hd), s0)
    return y[:, :, :t].transpose(0, 2, 1, 3), s_out
