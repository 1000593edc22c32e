"""Plain reference of the CNN federation, independent of the program.

Written from the paper's description (§3-§4) in ``jax.numpy``: the
2-conv/2-FC CNN (5x5 'SAME' convolutions, ReLU, 2x2 max-pools, FC-1, FC-2),
its cross-entropy loss, a client's E full-batch gradient-descent steps,
eq.-(6) aggregation weighted by the clients' sample counts, accuracy and
the eq.-(11) FC-1 profiles (the eq.-(14) kernel is ``bench/eq14.py``).
The initial weights follow the same recipe as the program's
(Kaiming-uniform on fan-in, zero biases, one key split four ways), so that
the reference starts where the program starts without taking the
program's weights.

Every function reads its sizes and settings from the configuration
``cfg``.  Every product runs at the precision it states
(``matmul_precision``: on the chip, ``"default"`` is one bfloat16 pass of
the MXU with float32 accumulation), on float32 arrays.  ``dtype=bfloat16``
gives the control: the same arithmetic on bfloat16 arrays.  Every pass
runs in blocks, so the reference fits beside what the run keeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["init_params", "profiles", "replay", "accuracy"]

F32 = jnp.float32


def _prec(precision: str):
    return lax.Precision[precision.upper()]


def init_params(key, cfg: dict) -> dict:
    h, w = cfg["image_hw"]
    c1, c2 = cfg["channels"]
    q, k_out = cfg["fc1_dim"], cfg["num_classes"]
    shapes = [(5, 5, 1, c1), (5, 5, c1, c2), ((h // 4) * (w // 4) * c2, q), (q, k_out)]
    keys = jax.random.split(key, 4)
    out = {}
    for name, k, shape in zip(("conv1", "conv2", "fc1", "fc2"), keys, shapes):
        fan_in = shape[0] * shape[1] * shape[2] if len(shape) == 4 else shape[0]
        bound = jnp.sqrt(6.0 / fan_in)
        out[name] = {"w": jax.random.uniform(k, shape, F32, -bound, bound),
                     "b": jnp.zeros((shape[-1],), F32)}
    return out


def _forward(params, x, dtype, precision):
    """(logits, FC-1 pre-activations) of a batch x (B, H, W, 1)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    prec = _prec(precision)

    def conv(h, layer):
        y = lax.conv_general_dilated(h, layer["w"], (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                     precision=prec)
        return y + layer["b"]

    def pool(h):
        return lax.reduce_window(h, -jnp.inf, lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = pool(jax.nn.relu(conv(x.astype(dtype), p["conv1"])))
    h = pool(jax.nn.relu(conv(h, p["conv2"])))
    h = h.reshape(h.shape[0], -1)
    fc1 = jnp.dot(h, p["fc1"]["w"], precision=prec) + p["fc1"]["b"]
    logits = jnp.dot(jax.nn.relu(fc1), p["fc2"]["w"], precision=prec) + p["fc2"]["b"]
    return logits, fc1


def _loss(params, x, y, dtype, precision):
    logits, _ = _forward(params, x, dtype, precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def profiles(params, client_xs, cfg: dict, dtype=F32):
    """(C, F) eq.-(11) profiles: each client's mean FC-1 pre-activation."""
    return _profiles(params, client_xs, dtype=dtype, precision=cfg["matmul_precision"])


@functools.partial(jax.jit, static_argnames=("dtype", "precision", "block"))
def _profiles(params, client_xs, dtype, precision, block=50):
    def one(x):
        return _forward(params, x, dtype, precision)[1].astype(F32).mean(0).astype(dtype)

    return lax.map(one, client_xs, batch_size=block)


def replay(params0, client_xs, client_ys, cohorts, cfg: dict, dtype=F32, keep=1.0):
    """Rounds with the given cohorts (R, k): each client takes
    ``local_epochs`` full-batch GD steps at ``lr`` from the global params on
    the first ``keep`` share of its samples, then the eq.-(6) mean weighted
    by sample counts.
    -> (params after the R rounds, per-round mean local loss (R,))."""
    return _replay(params0, client_xs, client_ys, cohorts, lr=float(cfg["lr"]),
                   steps=int(cfg["local_epochs"]), dtype=dtype,
                   precision=cfg["matmul_precision"], keep=keep)


@functools.partial(jax.jit, static_argnames=("lr", "steps", "dtype", "precision", "keep"))
def _replay(params0, client_xs, client_ys, cohorts, lr, steps, dtype, precision, keep):
    grad = jax.value_and_grad(lambda p, x, y: _loss(p, x, y, dtype, precision))

    def client(p, x, y):
        n = int(round(x.shape[0] * keep))
        x, y = x[:n], y[:n]

        def step(p, _):
            loss, g = grad(p, x, y)
            p = jax.tree_util.tree_map(lambda a, b: (a - lr * b.astype(a.dtype)).astype(a.dtype), p, g)
            return p, loss

        return lax.scan(step, p, None, length=steps)

    def one_round(p, cohort):
        xs, ys = client_xs[cohort], client_ys[cohort]
        new, losses = jax.vmap(client, in_axes=(None, 0, 0))(p, xs, ys)
        w = jnp.full((cohort.shape[0],), float(client_xs.shape[1]), F32)
        w = w / w.sum()
        agg = jax.tree_util.tree_map(
            lambda a, o: jnp.tensordot(w.astype(a.dtype), a, axes=1).astype(o.dtype),
            new, p)
        return agg, losses.astype(F32).mean()

    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params0)
    return lax.scan(one_round, p, cohorts)


def accuracy(params, xs, ys, cfg: dict, dtype=F32):
    """Share of ``xs`` whose largest logit is the label."""
    return _accuracy(params, xs, ys, dtype=dtype, precision=cfg["matmul_precision"])


@functools.partial(jax.jit, static_argnames=("dtype", "precision", "block"))
def _accuracy(params, xs, ys, dtype, precision, block=4096):
    n = xs.shape[0]
    pad = (-n) % block
    xb = jnp.pad(xs, ((0, pad),) + ((0, 0),) * (xs.ndim - 1)).reshape(-1, block, *xs.shape[1:])
    yb = jnp.pad(ys, (0, pad), constant_values=-1).reshape(-1, block)
    hits = lax.map(lambda a: jnp.sum(jnp.argmax(_forward(params, a[0], dtype, precision)[0], -1) == a[1]),
                   (xb, yb))
    return hits.sum() / n
