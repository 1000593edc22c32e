"""Per-kernel allclose vs the ref.py oracles, swept over shapes/dtypes.

All kernels run in interpret mode on CPU (TPU is the compile target)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels.pairwise_l2 import ops as pw_ops
from repro.kernels.pairwise_l2 import ref as pw_ref
from repro.kernels.rwkv6_scan import ops as wkv_ops
from repro.kernels.rwkv6_scan import ref as wkv_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------ pairwise_l2


@pytest.mark.parametrize(
    "c,q", [(4, 3), (10, 7), (100, 128), (130, 257), (64, 512)]
)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_pairwise_l2_sweep(c, q, dtype):
    f = jnp.asarray(RNG.normal(size=(c, q))).astype(dtype)
    got = np.asarray(pw_ops.pairwise_sq_dists(f))
    want = np.asarray(pw_ref.pairwise_sq_dists_ref(f)) * (1 - np.eye(c))
    tol = 5e-2 * max(1.0, want.max()) if dtype == jnp.bfloat16 else 1e-3 * max(1.0, want.max())
    np.testing.assert_allclose(got, want, atol=tol)
    assert (got >= 0).all()
    np.testing.assert_allclose(np.diag(got), 0.0, atol=0)


def test_pairwise_l2_block_shape_property():
    """Property: result is block-shape independent."""
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=10, deadline=None)
    @hypothesis.given(
        c=st.integers(2, 40),
        q=st.integers(1, 64),
        bm=st.sampled_from([8, 16, 128]),
        bk=st.sampled_from([8, 32, 512]),
    )
    def check(c, q, bm, bk):
        f = jnp.asarray(np.random.default_rng(c * 100 + q).normal(size=(c, q)).astype(np.float32))
        got = np.asarray(pw_ops.pairwise_sq_dists(f, block_m=bm, block_n=bm, block_k=bk))
        want = np.asarray(pw_ref.pairwise_sq_dists_ref(f)) * (1 - np.eye(c))
        np.testing.assert_allclose(got, want, atol=1e-3 * max(1.0, want.max()))

    check()


# (gram / fused profiles→kernel coverage lives in tests/test_gram_kernels.py,
# which is deliberately hypothesis-free so it runs in minimal containers)

# ------------------------------------------------------------ flash attention


@pytest.mark.parametrize(
    "b,s,h,hk,hd,window,bq,bk",
    [
        (2, 64, 4, 2, 32, None, 32, 32),
        (1, 100, 4, 4, 16, None, 32, 16),   # padded, MHA
        (2, 64, 8, 2, 32, 16, 32, 32),      # GQA + window
        (1, 128, 4, 1, 64, 32, 64, 32),     # MQA + window
        (1, 32, 2, 2, 8, None, 8, 8),
    ],
)
def test_flash_attention_sweep(b, s, h, hk, hd, window, bq, bk):
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, s, hk, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, s, hk, hd)).astype(np.float32))
    got = flash_ops.flash_attention(q, k, v, window=window, block_q=bq, block_k=bk)
    want = flash_ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 64, 4, 32))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 64, 2, 32))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 64, 2, 32))).astype(jnp.bfloat16)
    got = flash_ops.flash_attention(q, k, v)
    want = flash_ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_flash_attention_rejects_bad_heads():
    q = jnp.zeros((1, 8, 3, 4))
    k = v = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(ValueError):
        flash_ops.flash_attention(q, k, v)


# ------------------------------------------------------------ rwkv6 scan


@pytest.mark.parametrize(
    "b,t,h,hd,bt",
    [(2, 64, 2, 16, 32), (1, 100, 3, 32, 64), (2, 33, 1, 64, 16), (1, 16, 2, 8, 16)],
)
def test_rwkv6_scan_sweep(b, t, h, hd, bt):
    r = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(0.4, 0.99, size=(b, t, h, hd)).astype(np.float32))
    u = jnp.asarray(RNG.normal(size=(h, hd)).astype(np.float32))
    s0 = jnp.asarray(RNG.normal(size=(b, h, hd, hd)).astype(np.float32))
    y1, s1 = wkv_ops.wkv6(r, k, v, w, u, s0, block_t=bt)
    y2, s2 = wkv_ref.wkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=5e-4)


def test_rwkv6_state_handoff_equals_one_shot():
    """Running T in two halves with state hand-off == one shot (decode path)."""
    b, t, h, hd = 1, 32, 2, 16
    r = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, t, h, hd)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(0.5, 0.99, size=(b, t, h, hd)).astype(np.float32))
    u = jnp.asarray(RNG.normal(size=(h, hd)).astype(np.float32))
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    y_full, s_full = wkv_ops.wkv6(r, k, v, w, u, s0, block_t=16)
    y1, s_mid = wkv_ops.wkv6(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u, s0, block_t=16)
    y2, s_end = wkv_ops.wkv6(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u, s_mid, block_t=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_end), np.asarray(s_full), atol=1e-4)
