"""Tiled pairwise squared-L2 distance kernel (the paper's O(C²·Q) hot spot).

Computes ``D2[m, n] = ‖F[m] − F[n]‖²`` for a profile matrix ``F (C, Q)`` via
the MXU-friendly decomposition, accumulated per K-tile:

    D2 = Σ_k ( rowsum(A_k²) + rowsum(B_k²)ᵀ − 2 A_k B_kᵀ )

Grid: (C/bm, C/bn, Q/bk) — the K dim is innermost (sequential on TPU), the
(bm × bn) fp32 output tile lives in VMEM across the K loop.  A and B tiles
are (bm × bk) / (bn × bk) VMEM blocks; the −2·A·Bᵀ term is a (bm×bk)·(bk×bn)
MXU matmul.  Tile defaults (128, 128, 512) keep the working set
(2·128·512 + 128·128)·4 B ≈ 0.6 MB ≪ 16 MB VMEM and the matmul dims
128-aligned for the MXU.

Two entry points:

* :func:`pairwise_sq_dists_kernel` — plain squared distances (clamped,
  zero diagonal).
* :func:`pairwise_dists_stats_kernel` — the fused eq.-(14) front end: the
  last K iteration runs a **sqrt epilogue** in-tile (clamp → pin diagonal →
  ``√``) and reduces each tile's masked min/max into one (8, 128) stats tile
  per output tile (the scalar broadcast over a full vreg: Mosaic stores no
  scalars to VMEM), so the min-max normalisation scalars cost one tiny
  reduction instead of a second O(C²) pass.  Feeds the ``gram`` kernel
  (``repro.kernels.gram``), making profiles → DPP kernel two launches.

C is padded up to a multiple of the row/column tiles, never clamped to a
tile of C rows: a C-wide block (e.g. C = 100) breaks the TPU's (8, 128)
block rule once a second kernel tiles the padded result.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mxu_precision

__all__ = ["pairwise_dists_stats_kernel", "pairwise_sq_dists_kernel"]

# one f32 vreg: the smallest tile a per-tile statistic can be stored as
_STAT_TILE = (8, 128)


def _padded_operands(f, block_m, block_n, block_k):
    """Tile sizes and the zero-padded profiles for a (C, Q) matrix: C up to a
    common multiple of the row/column tiles, Q up to the K tile (or Q itself
    when it is the smaller)."""
    c, q = f.shape
    bk = min(block_k, q)
    cp = -(-c // math.lcm(block_m, block_n)) * math.lcm(block_m, block_n)
    qp = -(-q // bk) * bk
    return bk, cp, jnp.pad(f, ((0, cp - c), (0, qp - q)))


def _kernel(a_ref, b_ref, out_ref):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)  # (bm, bk)
    b = b_ref[...].astype(jnp.float32)  # (bn, bk)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)  # (bm, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)  # (bn, 1)
    ab = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=mxu_precision(a_ref.dtype),
    )  # (bm, bn) on the MXU
    out_ref[...] += a2 + b2.T - 2.0 * ab


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def pairwise_sq_dists_kernel(
    f: jax.Array,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """F (C, Q) -> D2 (C, C); pads C and Q up to tile multiples internally."""
    c = f.shape[0]
    bm, bn = block_m, block_n
    bk, cp, fp = _padded_operands(f, bm, bn, block_k)
    grid = (cp // bm, cp // bn, fp.shape[1] // bk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((cp, cp), jnp.float32),
        interpret=interpret,
    )(fp, fp)
    d2 = out[:c, :c]
    # numerical hygiene to match the reference contract: clamp & zero diag
    d2 = jnp.maximum(d2, 0.0)
    return d2 * (1.0 - jnp.eye(c, dtype=d2.dtype))


def _stats_kernel(a_ref, b_ref, out_ref, mn_ref, mx_ref, *, c, bm, bn):
    i, j, k_idx = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)  # (bm, bk)
    b = b_ref[...].astype(jnp.float32)  # (bn, bk)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)  # (bm, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)  # (bn, 1)
    ab = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=mxu_precision(a_ref.dtype),
    )  # (bm, bn) on the MXU
    out_ref[...] += a2 + b2.T - 2.0 * ab

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _epilogue():
        # clamp → pin the diagonal (distance to self is exactly 0, which
        # makes min(S⁰) = 0, eq. 14) → sqrt, all while the tile is in VMEM
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        d2 = jnp.maximum(out_ref[...], 0.0)
        s0 = jnp.sqrt(jnp.where(rows == cols, 0.0, d2))
        out_ref[...] = s0
        # masked per-tile min/max (pad region excluded) for the eq.-(14)
        # min-max normalisation, broadcast over the tile's stats vreg and
        # reduced to scalars by the caller
        valid = (rows < c) & (cols < c)
        lo = jnp.min(jnp.where(valid, s0, jnp.inf), keepdims=True)
        hi = jnp.max(jnp.where(valid, s0, -jnp.inf), keepdims=True)
        mn_ref[...] = jnp.broadcast_to(lo, mn_ref.shape)
        mx_ref[...] = jnp.broadcast_to(hi, mx_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def pairwise_dists_stats_kernel(
    f: jax.Array,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
):
    """F (C, Q) -> (S0 (Cp, Cp), lo, hi): L2 distances + min/max scalars.

    ``S0`` is returned at the padded tile size (rows/cols ≥ C hold garbage —
    downstream consumers mask on the real C); ``lo``/``hi`` are the exact
    min/max over the real (C, C) region, fp monotonicity making them equal
    to the reference's post-sqrt extrema.
    """
    c = f.shape[0]
    bm, bn = block_m, block_n
    bk, cp, fp = _padded_operands(f, bm, bn, block_k)
    grid = (cp // bm, cp // bn, fp.shape[1] // bk)
    stats_shape = (grid[0] * _STAT_TILE[0], grid[1] * _STAT_TILE[1])
    s0, mn, mx = pl.pallas_call(
        functools.partial(_stats_kernel, c=c, bm=bm, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec(_STAT_TILE, lambda i, j, k: (i, j)),
            pl.BlockSpec(_STAT_TILE, lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, cp), jnp.float32),
            jax.ShapeDtypeStruct(stats_shape, jnp.float32),
            jax.ShapeDtypeStruct(stats_shape, jnp.float32),
        ],
        interpret=interpret,
    )(fp, fp)
    return s0, jnp.min(mn), jnp.max(mx)
