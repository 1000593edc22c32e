"""The paper's CNN: two conv layers + two fully-connected layers (§4).

Pure-JAX (init/apply pairs).  ``apply_with_features`` exposes the FC-1
*pre-activation* outputs — exactly the ``h_q`` of Theorem 1 — for data
profiling (eq. 11).  Four parameter-initialisation schemes are provided for
the Fig. 4-6 robustness experiments: kaiming_{uniform,normal} and
xavier_{uniform,normal}.

On a TPU the first stage's backward (the pool's gradient routing, the ReLU
mask, the bias and weight gradients) is one Pallas pass
(``kernels/conv_pool_bwd``); its forward stays the XLA ops of every stage.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.conv_pool_bwd import ops as conv_pool_bwd_ops

__all__ = ["init_cnn", "apply_cnn", "apply_with_features", "cnn_loss", "accuracy", "INIT_SCHEMES"]

# the named scope of the first stage's backward kernel on its op-name path
BWD_SCOPE = "conv1_bwd"


def _fan_in_out(shape):
    if len(shape) == 4:  # HWIO conv kernel
        rf = shape[0] * shape[1]
        return shape[2] * rf, shape[3] * rf
    return shape[0], shape[1]


def _kaiming_uniform(key, shape, dtype=jnp.float32):
    fan_in, _ = _fan_in_out(shape)
    bound = jnp.sqrt(6.0 / fan_in)
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _kaiming_normal(key, shape, dtype=jnp.float32):
    fan_in, _ = _fan_in_out(shape)
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def _xavier_uniform(key, shape, dtype=jnp.float32):
    fan_in, fan_out = _fan_in_out(shape)
    bound = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _xavier_normal(key, shape, dtype=jnp.float32):
    fan_in, fan_out = _fan_in_out(shape)
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / (fan_in + fan_out))


INIT_SCHEMES = {
    "kaiming_uniform": _kaiming_uniform,
    "kaiming_normal": _kaiming_normal,
    "xavier_uniform": _xavier_uniform,
    "xavier_normal": _xavier_normal,
}


def init_cnn(
    key: jax.Array,
    num_classes: int = 10,
    in_hw: Tuple[int, int] = (28, 28),
    channels: Tuple[int, int] = (16, 32),
    fc1_dim: int = 128,
    scheme: str = "kaiming_uniform",
) -> Dict:
    """Initialise the 2-conv/2-FC CNN; FC-1 width = Q = profile dimension."""
    init = INIT_SCHEMES[scheme]
    k = jax.random.split(key, 4)
    h, w = in_hw
    flat = (h // 4) * (w // 4) * channels[1]  # two 2x2 maxpools
    return {
        "conv1": {"w": init(k[0], (5, 5, 1, channels[0])), "b": jnp.zeros((channels[0],))},
        "conv2": {"w": init(k[1], (5, 5, channels[0], channels[1])), "b": jnp.zeros((channels[1],))},
        "fc1": {"w": init(k[2], (flat, fc1_dim)), "b": jnp.zeros((fc1_dim,))},
        "fc2": {"w": init(k[3], (fc1_dim, num_classes)), "b": jnp.zeros((num_classes,))},
    }


def _conv(x, w):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _maxpool2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def _stage(x, w, b):
    """conv + bias → ReLU → 2×2/2 max-pool."""
    return _maxpool2(jax.nn.relu(_conv(x, w) + b))


def _pallas_backward() -> bool:
    return jax.default_backend() == "tpu"


def _bf16_products() -> bool:
    """Whether the default matmul precision is one bf16 pass, the products
    the kernel forms; a higher one keeps XLA's backward."""
    return jax.config.jax_default_matmul_precision in (None, "default", "fastest", "bfloat16")


@jax.custom_vjp
def _stage_kernel_bwd(x, w, b):
    """``_stage`` whose backward is the Pallas kernel (for an x that takes
    no gradient)."""
    return _stage(x, w, b)


def _stage_kernel_bwd_fwd(x, w, b):
    if x.perturbed:  # an input that takes a gradient: XLA's backward
        out, vjp = jax.vjp(_stage, x.value, w.value, b.value)
        return out, (vjp,)
    y = _conv(x.value, w.value)
    return _maxpool2(jax.nn.relu(y + b.value)), (y, w.value, b.value, x.value)


def _stage_kernel_bwd_bwd(res, g):
    if len(res) == 1:
        return res[0](g)
    y, w, b, x = res
    # not an ``fl.*`` stage: the kernel stays in its caller's stage
    # (``fl.local_update`` in the federation engine)
    with jax.named_scope(BWD_SCOPE):
        dw, db = conv_pool_bwd_ops.conv_pool_bwd(y, b, x, g, w.shape[:2])
    return None, dw, db


_stage_kernel_bwd.defvjp(_stage_kernel_bwd_fwd, _stage_kernel_bwd_bwd, symbolic_zeros=True)


def _first_stage(x, p):
    """The first stage; its backward is the Pallas kernel on a TPU where the
    kernel covers the shapes (one input channel, even H and W, channels in
    whole sublane tiles, float32) and the precision (bf16 products)."""
    _, h, w, cin = x.shape
    c = p["w"].shape[-1]
    if (_pallas_backward() and _bf16_products() and cin == 1 and h % 2 == 0 and w % 2 == 0
            and c % 8 == 0 and x.dtype == p["w"].dtype == p["b"].dtype == jnp.float32):
        return _stage_kernel_bwd(x, p["w"], p["b"])
    return _stage(x, p["w"], p["b"])


def apply_with_features(params: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Forward pass returning (logits, FC-1 pre-activations).

    The FC-1 pre-activation is the Theorem-1 variable whose per-neuron mean
    over the local dataset forms the client's data profile f_c (eq. 11).
    """
    h = _first_stage(x, params["conv1"])
    h = _stage(h, params["conv2"]["w"], params["conv2"]["b"])
    h = h.reshape(h.shape[0], -1)
    fc1_pre = h @ params["fc1"]["w"] + params["fc1"]["b"]
    h = jax.nn.relu(fc1_pre)
    logits = h @ params["fc2"]["w"] + params["fc2"]["b"]
    return logits, fc1_pre


def apply_cnn(params: Dict, x: jax.Array) -> jax.Array:
    return apply_with_features(params, x)[0]


def cnn_loss(params: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    logits = apply_cnn(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=1))


@functools.partial(jax.jit, static_argnames=("batch_size",))
def accuracy(params: Dict, x: jax.Array, y: jax.Array, batch_size: int = 2048) -> jax.Array:
    """Full-dataset accuracy via scan over fixed-size chunks (pads tail)."""
    n = x.shape[0]
    pad = (-n) % batch_size
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    yp = jnp.pad(y, (0, pad), constant_values=-1)
    xb = xp.reshape(-1, batch_size, *x.shape[1:])
    yb = yp.reshape(-1, batch_size)

    def body(acc, xy):
        xc, yc = xy
        pred = jnp.argmax(apply_cnn(params, xc), axis=-1)
        return acc + jnp.sum((pred == yc) & (yc >= 0)), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.int32), (xb, yb))
    return total / n
