"""Pallas TPU kernels for the paper's hot spots and the serving engine."""

import jax
import jax.numpy as jnp


def mxu_precision(dtype):
    """Contract precision for a kernel matmul over ``dtype`` operands: f32
    operands ask the MXU for full f32 (``HIGHEST``), so the kernels meet
    their ~1e-5 f32 contract on the chip; narrower operands are exact in a
    single bf16 pass and keep the default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
