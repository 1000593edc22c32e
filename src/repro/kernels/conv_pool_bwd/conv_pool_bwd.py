"""The backward of ``maxpool2x2(relu(conv(x, w) + b))`` for an input that
takes no gradient, as one Pallas pass over the conv output.

XLA differentiates the stage in three full-size passes over the activation
(the pool's select-and-scatter, the ReLU mask with the bias gradient, the
weight-gradient convolution), each through HBM.  This kernel reads the conv
output once and keeps the activation's gradient ``dy`` in VMEM.

Layout: samples in lanes, (federation, channel) rows in sublanes, the
layout XLA's TPU convolution gives the conv output of a lockstep batch, so
the operands are bitcasts of it:

* ``y``  (H, W, R, N) f32: the conv output before the bias; R = S·C rows
  (S lockstep federations × C channels, federation-major), N samples;
* ``g``  (H/2, W/2, R, N) f32: the cotangent of the pooled activation;
* ``b``  (R, 1) f32: each row's bias;
* ``xp`` (G, H+KH−1, W+KW−1, Gp, Np) f32: each federation's one-channel
  input, zero-padded as SAME pads it, in G groups of S/G federations, each
  group's padded to Gp (a multiple of 8) and the samples to Np (a multiple
  of the lane block).

Grid (G groups, Np / ``block_n`` lane blocks, H/2 pooled rows), all
sequential; a group's R/G rows are one block.  Per group the two outputs
stay in VMEM across its lane blocks and rows and accumulate:

* ``acc`` (G, P, R/G) f32, rows (kh, kw, s') and P = KH·KW·Gp rounded up
  to 16: ``acc[grp, (kh, kw, s'), (s, c)] = Σ_{n,h,w} xp[grp, h+kh, w+kw, s', n] · dy[h, w, (s, c), n]``
  for s, s' of the group, with bf16 operands and f32 accumulation, the
  product XLA's TPU convolution forms at default precision.  The weight
  gradient is its s' = s diagonal.  One group's rows fill an MXU pass, so
  the work and the VMEM of a step grow linearly with S;
* ``db`` (R, block_n) f32: Σ dy over rows, columns and lane blocks, in f32.
  The bias gradient is its sum over lanes.

Per 2×2 window the kernel routes ``g`` to the first maximum of
``relu(y + b)`` in row-major window order, which is what XLA's
select-and-scatter with ``ge`` does, ties included, and masks it by
``y + b > 0`` (ReLU's derivative).  Lanes past N are masked out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["conv_pool_bwd_kernel"]

_ROW_TILE = 16  # bf16 sublane tile: the rows of the MXU operands


def _pad_up(x: int, b: int) -> int:
    return -(-x // b) * b


def _body(y_ref, g_ref, b_ref, xp_ref, acc_ref, db_ref, *, n, kh, kw):
    nb, i = pl.program_id(1), pl.program_id(2)

    @pl.when((nb == 0) & (i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    rows, block_n = db_ref.shape
    taps = kh * kw * xp_ref.shape[2]
    pad = acc_ref.shape[0] - taps
    bias = jnp.broadcast_to(b_ref[...], (rows, block_n))
    lane = nb * block_n + lax.broadcasted_iota(jnp.int32, (rows, block_n), 1)
    live = lane < n

    def window(j, carry):
        # the window's four positions in row-major order
        h = [jnp.maximum(y_ref[a, 2 * j + c] + bias, 0.0) for a in (0, 1) for c in (0, 1)]
        m23 = jnp.maximum(h[2], h[3])
        m123 = jnp.maximum(h[1], m23)
        first = [h[0] >= m123]
        taken = first[0]
        first.append(~taken & (h[1] >= m23))
        taken = taken | first[1]
        first.append(~taken & (h[2] >= h[3]))
        first.append(~(taken | first[2]))
        # ReLU's derivative at the chosen position: y + b > 0 there exactly
        # when the window's maximum is positive
        routed = jnp.where((jnp.maximum(h[0], m123) > 0.0) & live, g_ref[0, j], 0.0)
        db_ref[...] += routed
        dy = [jnp.where(f, routed, 0.0) for f in first]
        patches = [
            xp_ref[pl.ds(2 * i + a, kh), pl.ds(2 * j + c, kw)].reshape(taps, block_n)
            for a in (0, 1) for c in (0, 1)
        ]
        lhs = jnp.concatenate(patches, axis=1)
        if pad:
            lhs = jnp.concatenate([lhs, jnp.zeros((pad, lhs.shape[1]), lhs.dtype)], axis=0)
        acc_ref[...] += lax.dot_general(
            lhs.astype(jnp.bfloat16), jnp.concatenate(dy, axis=1).astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return carry

    lax.fori_loop(0, g_ref.shape[1], window, 0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def conv_pool_bwd_kernel(
    y: jax.Array,
    g: jax.Array,
    b: jax.Array,
    xp: jax.Array,
    block_n: int = 128,
    interpret: bool = False,
):
    """(y, g, b, xp) in the layout above -> (acc, db) as above."""
    h, w, rows, n = y.shape
    groups, hp, wp, gp, np_ = xp.shape
    kh, kw = hp - h + 1, wp - w + 1
    if h % 2 or w % 2 or g.shape != (h // 2, w // 2, rows, n) or b.shape != (rows, 1):
        raise ValueError(f"conv_pool_bwd: shapes y {y.shape}, g {g.shape}, b {b.shape}")
    if rows % groups or (rows // groups) % 8:
        raise ValueError(f"conv_pool_bwd: {rows} rows in {groups} groups of whole sublane tiles")
    if gp % 8 or np_ % block_n or np_ < n:
        raise ValueError(f"conv_pool_bwd: xp {xp.shape} is not padded for blocks of {block_n}")
    rg = rows // groups
    p = _pad_up(kh * kw * gp, _ROW_TILE)
    blocks = 4 * (2 * w * rg + w // 2 * rg + hp * wp * gp) * block_n
    return pl.pallas_call(
        functools.partial(_body, n=n, kh=kh, kw=kw),
        grid=(groups, np_ // block_n, h // 2),
        in_specs=[
            pl.BlockSpec((2, w, rg, block_n), lambda grp, nb, i: (i, 0, grp, nb)),
            pl.BlockSpec((1, w // 2, rg, block_n), lambda grp, nb, i: (i, 0, grp, nb)),
            pl.BlockSpec((rg, 1), lambda grp, nb, i: (grp, 0)),
            pl.BlockSpec((pl.Squeezed(), hp, wp, gp, block_n),
                         lambda grp, nb, i: (grp, 0, 0, 0, nb)),
        ],
        out_specs=[
            pl.BlockSpec((pl.Squeezed(), p, rg), lambda grp, nb, i: (grp, 0, 0)),
            pl.BlockSpec((rg, block_n), lambda grp, nb, i: (grp, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((groups, p, rg), jnp.float32),
            jax.ShapeDtypeStruct((rows, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # the double-buffered input blocks and the window's temporaries
            vmem_limit_bytes=2 * blocks + (16 << 20),
        ),
        interpret=interpret,
    )(y, g, b, xp)
