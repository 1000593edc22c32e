"""Client data profiling (paper §3.1, Theorem 1).

Each client summarises its local dataset by the *mean vector of the FC-1
outputs* of the (shared, freshly initialised) global model — eq. (11):
``f_c = [u_1^c, …, u_Q^c]``.  By Theorem 1 (CLT over the weighted inputs of
each FC-1 neuron) the per-neuron output is asymptotically Gaussian with mean
``u_q = Σ_v ω_{q,v} μ_v + b_q`` — a linear image of the mean latent feature
vector, i.e. a distribution fingerprint that leaks far less than a label
histogram and is uploaded once (B·Q bits).

Models plug in via ``apply_with_features(params, x) -> (logits, feats)`` where
``feats`` is the designated profile layer output:
* paper CNN: FC-1 *pre-activation* outputs (exactly Theorem 1's ``h_q``);
* decoder LMs: mean-over-tokens of the pre-logits hidden state (the analogue
  of "first dense layer after the feature extractor"; see DESIGN.md §3).

Also implements the Fig.-3 ablation baselines: gradient profiles and
representative-gradient profiles (Fraboni et al., ICML'21).
"""

from __future__ import annotations

import functools
from typing import Callable, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "fc1_profile",
    "gradient_profile",
    "representative_gradient_profile",
    "profile_all_clients",
    "profile_stacked_clients",
]

FeatureFn = Callable[..., Tuple[jax.Array, jax.Array]]


def fc1_profile(feature_fn: FeatureFn, params, xs: jax.Array, batch_size: int = 256) -> jax.Array:
    """Mean FC-1 output over a client's local dataset (eq. 11).

    ``feature_fn(params, x_batch) -> (logits, feats)`` with feats (B, Q).
    Streams in fixed-size batches so the profile pass is O(batch) memory.

    A client with an **empty** local dataset (n = 0) gets the zero profile of
    width Q — probed with an empty forward batch so the width matches every
    populated client's row and ``profile_all_clients`` can still stack.
    (The mean of zero samples is undefined; zero is the neutral element of
    the eq.-(14) similarity pipeline and keeps the kernel finite.)
    """
    n = xs.shape[0]
    if n == 0:
        _, feats = feature_fn(params, xs[:0])
        # width from the static shape: reshape(0, -1) is ambiguous on a
        # zero-row array, so flatten the trailing dims by hand
        width = int(np.prod(feats.shape[1:]))
        return jnp.zeros((width,), feats.dtype)
    total = None
    for start in range(0, n, batch_size):
        xb = xs[start : start + batch_size]
        _, feats = feature_fn(params, xb)
        feats = feats.reshape(feats.shape[0], -1)
        s = jnp.sum(feats, axis=0)
        total = s if total is None else total + s
    return total / n


def gradient_profile(
    loss_fn: Callable, params, xs: jax.Array, ys: jax.Array, max_dim: int = 4096
) -> jax.Array:
    """Fig.-3 ablation: profile = flattened loss gradient on the local data.

    Truncated/strided to ``max_dim`` entries so profiles stay comparable in
    size with FC-1 profiles (the paper's point is that gradients are a *worse*
    and much heavier fingerprint).
    """
    g = jax.grad(loss_fn)(params, xs, ys)
    flat = jnp.concatenate([x.reshape(-1) for x in jax.tree_util.tree_leaves(g)])
    if flat.shape[0] > max_dim:
        stride = flat.shape[0] // max_dim
        flat = flat[: stride * max_dim : stride]
    return flat


def representative_gradient_profile(
    loss_fn: Callable, params, xs: jax.Array, ys: jax.Array, layer: str = "out"
) -> jax.Array:
    """Fig.-3 ablation: representative gradients (Fraboni et al. Alg. 2 input).

    Uses only the output-layer gradient — the low-dimensional "representative"
    slice used by clustered sampling.
    """
    g = jax.grad(loss_fn)(params, xs, ys)
    leaves = {"/".join(map(str, p)): v for p, v in _flatten_with_paths(g)}
    picked = [v for k, v in sorted(leaves.items()) if layer in k]
    if not picked:  # fall back to the last parameter tensor
        picked = [jax.tree_util.tree_leaves(g)[-1]]
    return jnp.concatenate([p.reshape(-1) for p in picked])


def _flatten_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        yield tuple(getattr(p, "key", getattr(p, "idx", str(p))) for p in path), leaf


def profile_all_clients(
    feature_fn: FeatureFn, params, client_data: Iterable[jax.Array], batch_size: int = 256
) -> jax.Array:
    """Stack eq.-(11) profiles for every client: -> (C, Q).

    In deployment each client computes its own row locally and uploads it once
    (Algorithm 1 lines 2-4); here we loop over the simulated clients, which
    may hold different numbers of samples.  A stacked federation takes
    :func:`profile_stacked_clients` instead: one dispatch, not a loop.
    """
    rows = [fc1_profile(feature_fn, params, xs, batch_size=batch_size) for xs in client_data]
    return jnp.stack(rows, axis=0)


@functools.partial(jax.jit, static_argnums=0, static_argnames="batch_size")
def profile_stacked_clients(
    feature_fn: FeatureFn, params, client_xs: jax.Array, batch_size: int = 256
) -> jax.Array:
    """Eq.-(11) profiles of a stacked ``(C, n, ...)`` federation -> (C, Q),
    in one dispatch.

    The same mean as :func:`fc1_profile`, as one compiled program per
    ``(feature_fn, batch_size)`` and shape (jit's cache on the static
    arguments), so a new federation with new params reuses the executable.
    Memory is bounded by one block of samples, as in the per-client loop:
    ``lax.map`` runs over the clients and, inside a client, a scan over
    blocks of ``min(batch_size, n)`` samples; the ragged tail block is
    zero-padded and its padded rows are masked out of the sum, so they add
    exactly zero.  ``n == 0`` gives the zero profile of width Q per client.
    """
    c, n = client_xs.shape[:2]
    sample = client_xs.shape[2:]
    bs = max(1, min(batch_size, n))
    probe = jax.eval_shape(
        lambda p, xb: feature_fn(p, xb)[1],
        params, jax.ShapeDtypeStruct((bs, *sample), client_xs.dtype),
    )
    width = int(np.prod(probe.shape[1:]))
    if n == 0:
        return jnp.zeros((c, width), probe.dtype)
    nb = -(-n // bs)
    keep = (jnp.arange(nb * bs) < n).reshape(nb, bs, 1)

    def client_profile(xs):
        pad = jnp.zeros((nb * bs - n, *sample), xs.dtype)
        blocks = jnp.concatenate([xs, pad]).reshape(nb, bs, *sample)

        def add_block(total, block):
            xb, kb = block
            _, feats = feature_fn(params, xb)
            feats = jnp.where(kb, feats.reshape(bs, width), 0)
            return total + jnp.sum(feats, axis=0), None

        total, _ = lax.scan(add_block, jnp.zeros((width,), probe.dtype), (blocks, keep))
        return total / n

    return lax.map(client_profile, client_xs)
