"""Blocked causal GQA flash attention for TPU.

Online-softmax over KV tiles (Rabe & Staats / FlashAttention), adapted to the
TPU memory hierarchy: q/k/v tiles are explicit VMEM blocks, the two matmuls
per tile ((bq×hd)·(hd×bk) and (bq×bk)·(bk×hd)) land on the MXU, and the
softmax running stats (m, l) plus the (bq×hd) accumulator persist in VMEM
scratch across the sequential innermost KV grid dimension.

Grid: (B, H, S/bq, S/bk) — the KV dim is innermost/sequential.  The kernel
runs on a head-major layout, (B, H, S, hd), so every block's last two dims
are (tile, hd) as the TPU's (8, 128) block rule wants; the public
(B, S, H, hd) arrays are transposed on the way in and out.  GQA is handled
in the BlockSpec index maps: query head ``h`` reads KV head ``h // (H / Hk)``
— no repeated-KV materialisation in HBM.

Causality + optional sliding window are applied as in-tile masks; KV tiles
entirely above the diagonal (or entirely outside the window) write nothing
(`pl.when` guards), which on TPU skips their DMA+compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel"]

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, window, bq, bk, n_kv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile is relevant iff some kv pos <= some q pos (causal) and, with a
    # window, some kv pos is inside the window of some q pos.
    q_end = q_start + bq - 1
    relevant = k_start <= q_end
    if window is not None:
        relevant = relevant & ((k_start + bk) > (q_start - window + 1))

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_cur)  # (bq, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_cur

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_kernel(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, Hk, hd)
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    assert causal, "only the causal decoder path is implemented"
    b, s, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk
    bq = min(block_q, s)
    bk = min(block_k, s)
    sp = -(-s // max(bq, bk)) * max(bq, bk)
    pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
    # head-major (B, H, S, hd): blocks end in (tile, hd)
    q, k, v = (jnp.pad(x.transpose(0, 2, 1, 3), pad) for x in (q, k, v))
    n_q, n_kv = sp // bq, sp // bk
    grid = (b, h, n_q, n_kv)

    kernel = functools.partial(
        _kernel,
        scale=hd**-0.5,
        window=window,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                        for shape in ((bq, 1), (bq, 1), (bq, hd))],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :s].transpose(0, 2, 1, 3)
