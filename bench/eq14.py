"""Plain reference of the eq.-(14) kernel L = SᵀS from a federation's
(C, F) profiles, shared by every configuration's reference: the kernel
depends on the profiles alone, not on the model that made them.

float32 profiles: worked out in float64 on the host.  The control's
(``dtype=bfloat16``): in float32 at ``Precision.HIGH`` on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["eq14_kernel"]

F32 = jnp.float32


def eq14_kernel(f, dtype=F32) -> np.ndarray:
    """Eq. (14) and L = SᵀS."""
    if dtype == F32:
        f = np.asarray(f, np.float64)
        f = f - f.mean(0)  # distances are translation-invariant
        sq = (f * f).sum(1)
        d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * (f @ f.T), 0.0)
        np.fill_diagonal(d2, 0.0)
        s0 = np.sqrt(d2)
        s = 1.0 - (s0 - s0.min()) / (s0.max() - s0.min())
        return s.T @ s
    return np.asarray(_eq14_high(jnp.asarray(f, F32)), np.float64)


@jax.jit
def _eq14_high(f):
    hi = lax.Precision.HIGH
    f = f - f.mean(0)
    sq = (f * f).sum(1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * jnp.dot(f, f.T, precision=hi), 0.0)
    s0 = jnp.sqrt(d2 * (1.0 - jnp.eye(f.shape[0], dtype=F32)))
    s = 1.0 - (s0 - s0.min()) / (s0.max() - s0.min())
    return jnp.dot(s.T, s, precision=hi)
