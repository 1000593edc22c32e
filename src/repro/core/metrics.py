"""Diversity / heterogeneity metrics.

GEMD (group earth mover's distance, paper eq. 15) quantifies how far the
label distribution of the selected cohort's *union* dataset is from the global
label distribution; lower = more diverse/representative cohort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "safe_div",
    "finite_mean",
    "gemd",
    "label_distribution",
    "label_distributions",
    "cohort_label_distribution",
]


def safe_div(num: jax.Array, den: jax.Array, eps: float = 1e-30) -> jax.Array:
    """``num / max(den, eps)`` — the weighted-sum denominator guard.

    One shared definition for every Σwᵢ·xᵢ / Σwᵢ normalisation (eq. 6 FedAvg,
    eq. 15 cohort label mix): an all-zero weight vector yields 0, never
    inf/NaN.  ``eps`` floors only the denominator, so any real weight sum
    (≥ 1 sample) is untouched.
    """
    return num / jnp.maximum(den, eps)


def finite_mean(x: jax.Array, where: jax.Array = None) -> jax.Array:
    """Mean over the finite (optionally ``where``-masked) entries of ``x``.

    The NaN-aware round-mean helper (DESIGN.md §11): NaN is the documented
    non-cohort loss mask and a NaN/Inf-corrupt client's loss report is
    garbage, so round summaries reduce only over finite entries.  Returns
    NaN (not 0) when nothing qualifies — a dead round must not read as
    perfect convergence.  ``jnp.where`` (never ``mask·x``) keeps a masked
    NaN from poisoning the sum, and the reduction order over the kept
    entries matches a plain masked sum, so all-finite inputs are
    bit-identical to the pre-guard mean.
    """
    ok = jnp.isfinite(x)
    if where is not None:
        ok = ok & where
    tot = jnp.sum(jnp.where(ok, x, jnp.zeros((), x.dtype)))
    cnt = jnp.sum(ok.astype(jnp.float32))
    return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), jnp.nan)


def label_distribution(ys: jax.Array, num_classes: int) -> jax.Array:
    """Empirical label distribution P(y = j) of one dataset."""
    counts = jnp.bincount(ys.astype(jnp.int32), length=num_classes)
    return counts / jnp.maximum(jnp.sum(counts), 1)


@functools.partial(jax.jit, static_argnames="num_classes")
def label_distributions(client_ys: jax.Array, num_classes: int) -> jax.Array:
    """:func:`label_distribution` of every row of stacked ``(C, n)`` labels
    -> (C, num_classes), in one dispatch; bit-identical to the per-row
    calls (the counts are integers)."""
    return jax.vmap(lambda ys: label_distribution(ys, num_classes))(client_ys)


def cohort_label_distribution(
    client_dists: jax.Array, client_sizes: jax.Array, selected: jax.Array
) -> jax.Array:
    """Size-weighted label distribution of the union of selected clients.

    ``client_dists``: (C, N) per-client label distributions P_c(y = j);
    ``client_sizes``: (C,) n_c; ``selected``: (k,) int indices.
    """
    n = client_sizes[selected].astype(jnp.float32)
    d = client_dists[selected]
    return safe_div((n[:, None] * d).sum(0), n.sum())


def gemd(
    client_dists: jax.Array,
    client_sizes: jax.Array,
    selected: jax.Array,
    global_dist: jax.Array,
) -> jax.Array:
    """Group earth mover's distance of a cohort (paper eq. 15).

    ``G(C_t) = Σ_j | Σ_c n_c P_c(j) / Σ_c n_c − P_g(j) |``
    """
    mix = cohort_label_distribution(client_dists, client_sizes, selected)
    return jnp.sum(jnp.abs(mix - global_dist))
