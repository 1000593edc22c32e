"""Flash-decode: single-query attention against cached KV for TPU.

The serving engine's decode step attends one new query per slot to that
slot's valid cache prefix.  This kernel is the decode-shaped sibling of
``flash_attention._kernel``: the same online-softmax over KV tiles, the same
VMEM scratch discipline (running (m, l) stats + accumulator persist across
the sequential innermost KV grid dimension), the same GQA handling via
BlockSpec index maps.  Two decode-specific twists:

* all Hk KV heads of a cache tile are read at once, in the model's own
  (B, S, Hk, hd) layout: the wrapper views the cache as (B, S, Hk·hd), a
  free reshape, so a (bk, Hk·hd) block obeys the TPU's (8, 128) block rule
  and the cache is read in place, never transposed or copied.  The query
  side is block-diagonal: query head h keeps its hd values in the columns
  of its KV head, zeros elsewhere, so one (H, Hk·hd) × (Hk·hd, bk) MXU
  matmul gives every head's scores against its own KV head.  The p·v
  matmul yields (H, Hk·hd); the wrapper keeps each head's own hd columns.
  Decode is bound by reading the cache, so the Hk-fold MXU work is cheap;
* causality degenerates to a **per-slot valid length**: slot ``b`` may only
  attend cache entries ``< lengths[b]`` (its prefill + decoded prefix).
  The lengths ride in as a scalar-prefetch (SMEM) operand and are masked
  in-tile; KV tiles entirely past the length skip their compute via
  ``pl.when``.

Grid: (B, S/bk) with the KV dim innermost/sequential.  Ragged lengths
(heterogeneous slots) cost nothing extra: masking is per-tile arithmetic.
The KV tile is the largest of ``block_k``, ``block_k/2``, ... (down to 16)
that divides S; only a cache length with no such divisor is padded, which
copies the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode_kernel"]

NEG_INF = -1.0e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, bk, n_kv):
    ki = pl.program_id(1)
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]  # this slot's valid cache prefix

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # (H, Hk·hd) block-diagonal
        k = k_ref[0].astype(jnp.float32)  # (bk, Hk·hd)
        v = v_ref[0].astype(jnp.float32)  # (bk, Hk·hd)
        h = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (H, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (h, bk), 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_scr[...]  # (H, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)  # (H, bk)
        alpha = jnp.exp(m_prev - m_cur)  # (H, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_cur

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)  # empty slot -> 0


def _kv_tile(s: int, block_k: int) -> int:
    """The largest of block_k, block_k/2, ... (down to 16) dividing s."""
    bk = min(block_k, s)
    while s % bk and bk % 32 == 0:
        bk //= 2
    return bk


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_kernel(
    q: jax.Array,  # (B, 1, H, hd) — one query per slot
    k: jax.Array,  # (B, S, Hk, hd) — cached keys
    v: jax.Array,  # (B, S, Hk, hd)
    lengths: jax.Array,  # (B,) int32 valid cache prefix per slot
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, hd = q.shape
    assert sq == 1, "flash-decode is the single-query path"
    s, hk = k.shape[1], k.shape[2]
    g = h // hk
    bk = _kv_tile(s, block_k)
    sp = -(-s // bk) * bk
    # (B, S, Hk·hd): a view of the cache, padded only when no tile divides S
    k, v = (
        jnp.pad(x.reshape(b, s, hk * hd), ((0, 0), (0, sp - s), (0, 0)))
        for x in (k, v)
    )
    n_kv = sp // bk
    kv_head = jnp.arange(h) // g  # the KV head each query head reads
    onehot = (kv_head[:, None] == jnp.arange(hk)[None, :]).astype(q.dtype)
    q_bd = (q[:, 0, :, None, :] * onehot[None, :, :, None]).reshape(b, h, hk * hd)

    kernel = functools.partial(_decode_kernel, scale=hd**-0.5, bk=bk, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # lengths (B,) int32, in SMEM
            grid=(b, n_kv),
            in_specs=[
                pl.BlockSpec((1, h, hk * hd), lambda bi, ki, ln: (bi, 0, 0)),
                pl.BlockSpec((1, bk, hk * hd), lambda bi, ki, ln: (bi, ki, 0)),
                pl.BlockSpec((1, bk, hk * hd), lambda bi, ki, ln: (bi, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, hk * hd), lambda bi, ki, ln: (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in ((h, 1), (h, 1), (h, hk * hd))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hk * hd), jnp.float32),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_bd, k, v)
    # each query head keeps the hd columns of its own KV head
    out = out.reshape(b, h, hk, hd)[:, jnp.arange(h), kv_head]
    return out[:, None].astype(q.dtype)
