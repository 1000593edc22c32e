"""Plain reference of the bigram token federation, independent of the
program: the embedding read as a one-hot product, the head, the
next-token cross-entropy, a client's E full-batch gradient-descent steps,
eq.-(6) aggregation weighted by the clients' sample counts, next-token
accuracy and the eq.-(11) profiles (each client's mean embedding of the
tokens it reads).  The initial weights follow the program's recipe (one key
split in two: normal embedding rows, the head Kaiming-uniform on its
fan-in, a zero bias).

Every function reads its sizes and settings from ``cfg``: the vocabulary
and width, the learning rate and steps, and the precision of the products
(``matmul_precision``).  ``dtype=bfloat16`` gives the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["init_params", "profiles", "replay", "accuracy"]

F32 = jnp.float32


def init_params(key, cfg: dict) -> dict:
    v, d = cfg["vocab_size"], cfg["embed_dim"]
    k_emb, k_head = jax.random.split(key)
    bound = jnp.sqrt(6.0 / d)
    return {"embed": jax.random.normal(k_emb, (v, d), F32),
            "head": {"w": jax.random.uniform(k_head, (d, v), F32, -bound, bound),
                     "b": jnp.zeros((v,), F32)}}


def _forward(params, x, vocab, dtype, precision):
    """(logits (B, T-1, V), embeddings (B, T-1, D)) of token rows x (B, T)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    prec = lax.Precision[precision.upper()]
    h = jnp.dot(jax.nn.one_hot(x[:, :-1], vocab, dtype=dtype), p["embed"], precision=prec)
    return jnp.dot(h, p["head"]["w"], precision=prec) + p["head"]["b"], h


def _loss(params, x, vocab, dtype, precision):
    logits, _ = _forward(params, x, vocab, dtype, precision)
    logp = jax.nn.log_softmax(logits.astype(F32))
    return -jnp.mean(jnp.sum(logp * jax.nn.one_hot(x[:, 1:], vocab, dtype=F32), axis=-1))


def profiles(params, client_xs, cfg: dict, dtype=F32):
    """(C, D) eq.-(11) profiles."""
    return _profiles(params, client_xs, vocab=cfg["vocab_size"], dtype=dtype,
                     precision=cfg["matmul_precision"])


@functools.partial(jax.jit, static_argnames=("vocab", "dtype", "precision"))
def _profiles(params, client_xs, vocab, dtype, precision):
    def one(x):
        h = _forward(params, x, vocab, dtype, precision)[1]
        return h.astype(F32).mean(axis=(0, 1)).astype(dtype)

    return lax.map(one, client_xs)


def replay(params0, client_xs, client_ys, cohorts, cfg: dict, dtype=F32, keep=1.0):
    """Rounds with the given cohorts (R, k): each client takes
    ``local_epochs`` full-batch GD steps at ``lr`` from the global params on
    the first ``keep`` share of its samples, then the eq.-(6) mean weighted
    by sample counts.  -> (params after the R rounds, per-round mean local
    loss (R,))."""
    return _replay(params0, client_xs, cohorts, vocab=cfg["vocab_size"],
                   lr=float(cfg["lr"]), steps=int(cfg["local_epochs"]), dtype=dtype,
                   precision=cfg["matmul_precision"], keep=keep)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "lr", "steps", "dtype", "precision", "keep"))
def _replay(params0, client_xs, cohorts, vocab, lr, steps, dtype, precision, keep):
    grad = jax.value_and_grad(lambda p, x: _loss(p, x, vocab, dtype, precision))

    def client(p, x):
        x = x[:int(round(x.shape[0] * keep))]

        def step(p, _):
            loss, g = grad(p, x)
            return jax.tree_util.tree_map(lambda a, b: (a - lr * b).astype(a.dtype), p, g), loss

        return lax.scan(step, p, None, length=steps)

    def one_round(p, cohort):
        new, losses = jax.vmap(client, in_axes=(None, 0))(p, client_xs[cohort])
        w = jnp.full((cohort.shape[0],), 1.0 / cohort.shape[0], F32)  # equal sample counts
        agg = jax.tree_util.tree_map(
            lambda a, o: jnp.tensordot(w.astype(a.dtype), a, axes=1).astype(o.dtype), new, p)
        return agg, losses.astype(F32).mean()

    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params0)
    return lax.scan(one_round, p, cohorts)


def accuracy(params, xs, ys, cfg: dict, dtype=F32):
    """Share of held-out positions whose largest logit is the next token."""
    return _accuracy(params, xs, vocab=cfg["vocab_size"], dtype=dtype,
                     precision=cfg["matmul_precision"])


@functools.partial(jax.jit, static_argnames=("vocab", "dtype", "precision"))
def _accuracy(params, xs, vocab, dtype, precision):
    logits, _ = _forward(params, xs, vocab, dtype, precision)
    return jnp.mean(jnp.argmax(logits, axis=-1) == xs[:, 1:])
