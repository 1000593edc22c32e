"""Pure-jnp oracle for the first-stage backward kernel."""

import jax
import jax.numpy as jnp

__all__ = ["conv_pool_bwd_ref"]


def conv_pool_bwd_ref(y, b, x, g, kernel_hw):
    """The kernel's contract as plain XLA ops, one federation:
    y (N, H, W, C) conv output before the bias, b (C,), x (N, H, W, 1),
    g (N, H/2, W/2, C) -> dw (KH, KW, 1, C), db (C,).

    ``g`` goes to the first maximum of ``relu(y + b)`` in each 2×2 window
    (row-major; ``argmax`` takes the first of equal values), masked by
    ``y + b > 0``; ``db`` sums that in f32; ``dw`` sums bf16(x)·bf16(dy)
    products in f32."""
    n, h, w, c = y.shape
    kh, kw = kernel_hw
    z = (y + b).reshape(n, h // 2, 2, w // 2, 2, c)
    win = jnp.maximum(z, 0.0).transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4, c)
    first = jax.nn.one_hot(jnp.argmax(win, axis=3), 4, axis=3, dtype=jnp.bool_)
    routed = jnp.where(first, g[:, :, :, None, :], 0.0)
    routed = routed.reshape(n, h // 2, w // 2, 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    dy = jnp.where(z > 0, routed, 0.0).reshape(n, h, w, c)
    db = jnp.sum(dy, axis=(0, 1, 2))
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    xp = jnp.pad(x[..., 0], ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw))).astype(jnp.bfloat16)
    dyb = dy.astype(jnp.bfloat16)
    dw = jnp.stack([
        jnp.stack([
            jnp.einsum("nhw,nhwc->c", xp[:, i:i + h, j:j + w], dyb,
                       preferred_element_type=jnp.float32)
            for j in range(kw)
        ])
        for i in range(kh)
    ])
    return dw[:, :, None, :], db
