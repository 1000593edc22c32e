"""Public wrapper of the first-stage backward kernel, in the model's NHWC
layout, one federation per call and a lockstep batch under ``jax.vmap``.

A call is a lockstep batch of one federation.  Under ``vmap`` the
wrapper's own batching rule folds the mapped axis into the batch's
federations, so the kernel gets the whole lockstep batch as one call, its
federations stacked into the kernel's rows (S·C rows, in groups of up to 8
federations, one grid step each), and ``pallas_call``'s batching rule never
adds a grid axis over the federations: that rule puts the federation axis
first, and the conv output, which XLA's TPU convolution lays out as
(H, W, S·C, N) with the samples in lanes, would be copied whole to match.
Transposed to that layout, the conv output and its pooled cotangent are
bitcasts.  Nested ``vmap``s (clients inside federations) fold in turn.

On CPU (this container) the kernel body runs under ``interpret=True``; on
TPU it compiles to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv_pool_bwd.conv_pool_bwd import conv_pool_bwd_kernel

__all__ = ["conv_pool_bwd"]

_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_up(x: int, b: int) -> int:
    return -(-x // b) * b


def _group(s: int) -> int:
    """Federations per kernel row block: the largest divisor of S up to 8."""
    return max(d for d in range(1, min(s, 8) + 1) if s % d == 0)


def _lockstep(y, b, x, g, kh: int, kw: int):
    """(dw, db) of S federations: y (S, N, H, W, C), b (S, C),
    x (S, N, H, W, 1), g (S, N, H/2, W/2, C) -> (S, KH, KW, 1, C), (S, C)."""
    s, n, h, w, c = y.shape
    gs = _group(s)
    groups, gp = s // gs, _pad_up(gs, 8)
    block_n = _LANES if n >= _LANES else n
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    xp = jnp.pad(
        x[..., 0].reshape(groups, gs, n, h, w).transpose(0, 3, 4, 1, 2).astype(jnp.float32),
        ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, gp - gs),
         (0, _pad_up(n, block_n) - n)),
    )
    acc, db = conv_pool_bwd_kernel(
        y.transpose(2, 3, 0, 4, 1).reshape(h, w, s * c, n),
        g.transpose(2, 3, 0, 4, 1).reshape(h // 2, w // 2, s * c, n),
        b.reshape(s * c, 1),
        xp,
        block_n=block_n,
        interpret=_interpret(),
    )
    acc = acc[:, : kh * kw * gp].reshape(groups, kh, kw, gp, gs, c)[:, :, :, :gs]
    dw = jnp.diagonal(acc, axis1=3, axis2=4)  # (G, KH, KW, C, gs): each row's own federation
    dw = dw.transpose(0, 4, 1, 2, 3).reshape(s, kh, kw, c)
    return dw[:, :, :, None, :], db.sum(axis=1).reshape(s, c)


@functools.cache
def _stage_bwd(kh: int, kw: int):
    @jax.custom_batching.custom_vmap
    def lockstep(y, b, x, g):
        return _lockstep(y, b, x, g, kh, kw)

    @lockstep.def_vmap
    def _fold_rule(axis_size, in_batched, *args):
        # (A, S, ...) -> (A·S, ...): merging leading axes, no copy
        args = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, batched in zip(args, in_batched)]
        folded = lockstep(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        return tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in folded), (True, True)

    return lockstep


def conv_pool_bwd(y, b, x, g, kernel_hw):
    """Weight and bias gradients of ``maxpool2x2(relu(y + b))`` with
    ``y = conv(x, w)`` (SAME, stride 1, one input channel, kernel
    ``kernel_hw``): y (N, H, W, C) f32, b (C,), x (N, H, W, 1),
    g (N, H/2, W/2, C) the pooled activation's cotangent ->
    dw (KH, KW, 1, C), db (C,).  H, W even; ``g`` reaches the first
    maximum of each window, as XLA's max-pool gradient routes it."""
    dw, db = _stage_bwd(*kernel_hw)(y[None], b[None], x[None], g[None])
    return dw[0], db[0]
