"""Tiled Gram kernel ``L = XᵀX`` + the fused eq.-(14) normalise-and-Gram.

Two Pallas entry points, both accumulating (bm × bn) fp32 output tiles in
VMEM over the row (reduction) dimension, with the contraction running on the
MXU (``preferred_element_type=float32`` — bf16 inputs accumulate in fp32):

* :func:`gram_kernel` — plain ``XᵀX`` for an (M, N) matrix, zero-padded to
  tile multiples (zero rows contribute nothing, so no masking is needed).
* :func:`normalized_gram_kernel` — the back half of the fused
  profiles→DPP-kernel pipeline: takes the padded distance matrix ``S0`` from
  ``pairwise_l2.pairwise_dists_stats_kernel`` plus the min-max scalars and
  applies the eq.-(14) **normalise epilogue in the tile prologue** —
  ``S = 1 − (S0 − lo)/rng`` with pad rows masked to 0 — before the Gram
  contraction.  One launch produces ``L = SᵀS`` without ``S`` ever
  materialising in HBM.

Grid: (N/bm, N/bn, M/bk), K innermost (sequential on TPU).  The default
(128, 128, 128) tiles keep the working set ≈ 0.2 MB ≪ VMEM and all matmul
dims 128-aligned.  The normalise-and-Gram kernel pads the client axis up to
its tiles instead of clamping a tile to C (a (128, 100) block at the
paper's C = 100 breaks the TPU's (8, 128) block rule) and reads the two
min-max scalars from SMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu_precision

__all__ = ["gram_kernel", "normalized_gram_kernel"]


def _pad_up(x: int, b: int) -> int:
    return -(-x // b) * b


def _gram_body(a_ref, b_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=mxu_precision(a_ref.dtype),
    )


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def gram_kernel(
    x: jax.Array,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """X (M, N) -> XᵀX (N, N) in fp32 (bf16 inputs keep fp32 accumulation)."""
    m, n = x.shape
    bm, bn, bk = min(block_m, n), min(block_n, n), min(block_k, m)
    np_ = max(_pad_up(n, bm), _pad_up(_pad_up(n, bm), bn))
    mp = _pad_up(m, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, np_ - n)))
    out = pl.pallas_call(
        _gram_body,
        grid=(np_ // bm, np_ // bn, mp // bk),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.float32),
        interpret=interpret,
    )(xp, xp)
    return out[:n, :n]


def _norm_gram_body(a_ref, b_ref, stats_ref, out_ref, *, c, bk, compute_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo = stats_ref[0, 0]
    rng = stats_ref[0, 1]
    # eq.-(14) epilogue fused into the contraction prologue: similarity
    # S = 1 − (S0 − lo)/rng; pad rows (the reduction dim) masked to 0 so the
    # garbage region of the padded S0 never reaches the accumulator.
    rows = k_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    sa = jnp.where(rows < c, 1.0 - (a_ref[...] - lo) / rng, 0.0)
    sb = jnp.where(rows < c, 1.0 - (b_ref[...] - lo) / rng, 0.0)
    out_ref[...] += jax.lax.dot_general(
        sa.astype(compute_dtype), sb.astype(compute_dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=mxu_precision(compute_dtype),
    )


@functools.partial(
    jax.jit,
    static_argnames=("c", "block_m", "block_n", "block_k", "compute_dtype", "interpret"),
)
def normalized_gram_kernel(
    s0: jax.Array,
    lo: jax.Array,
    rng: jax.Array,
    c: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    compute_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Padded distances S0 (P, P) + min-max scalars -> DPP kernel L (c, c).

    ``c`` is the real client count (rows/cols ≥ c of ``s0`` are pad garbage);
    ``compute_dtype`` is the MXU input dtype for the contraction (bf16 for
    bf16 profiles — accumulation stays fp32).
    """
    p = s0.shape[0]
    bm, bn, bk = block_m, block_n, block_k
    pp = _pad_up(p, math.lcm(bm, bn, bk))
    s0p = jnp.pad(s0, ((0, pp - p), (0, pp - p)))
    stats = jnp.stack([lo, rng]).astype(jnp.float32).reshape(1, 2)
    out = pl.pallas_call(
        functools.partial(_norm_gram_body, c=c, bk=bk, compute_dtype=compute_dtype),
        grid=(pp // bm, pp // bn, pp // bk),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (lo, rng)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pp, pp), jnp.float32),
        interpret=interpret,
    )(s0p, s0p, stats)
    return out[:c, :c]
