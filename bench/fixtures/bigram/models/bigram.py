"""The system under test for the bigram token configuration: a bigram
language model (an embedding of width ``embed_dim`` read straight into
logits over ``vocab_size`` tokens) federated by the program's engine
through its normal path (``init_server_state`` -> ``make_round_fn`` ->
``run_scanned`` / ``run_many``).

Each sample is a sequence of ``seq_len`` tokens drawn from one topic's
Markov chain; the topic is the sample's label, which the label histograms
and eq. 15 read.  The loss is the next-token cross-entropy, the eq.-(11)
profile the mean embedding of a sample's tokens, and the accuracy the share
of held-out positions whose largest logit is the next token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import make_strategy
from repro.fl import engine

from bench import flops

__all__ = ["TINY_SIZES", "make_deployment", "accuracy", "System"]

# the counts cut, every width kept
TINY_SIZES = {"num_clients": 12, "clients_per_round": 3, "samples_per_client": 16,
              "test_samples": 64, "max_rounds": 20}


# -------------------------------------------------------------- deployment


def _labels(num_clients: int, n: int, topics: int, share: float) -> jax.Array:
    """(C, n) topics: ``round(share·n)`` samples of topic c mod K, then the
    other topics in turn."""
    c = jnp.arange(num_clients)[:, None]
    i = jnp.arange(n)[None, :]
    n_dom = int(round(share * n))
    other = (c % topics + 1 + (i - n_dom) % (topics - 1)) % topics
    return jnp.where(i < n_dom, c % topics, other).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("seq_len",))
def _sequences(key, chains, topics, seq_len: int) -> jax.Array:
    """One sequence of ``seq_len`` tokens per entry of ``topics``, each from
    its topic's chain: a uniform first token, then the chain's steps."""
    vocab = chains.shape[-1]

    def one(k, topic):
        k0, ks = jax.random.split(k)
        first = jax.random.randint(k0, (), 0, vocab)

        def step(tok, kk):
            nxt = jax.random.categorical(kk, chains[topic, tok]).astype(jnp.int32)
            return nxt, nxt

        _, rest = lax.scan(step, first, jax.random.split(ks, seq_len - 1))
        return jnp.concatenate([first[None], rest])

    flat = topics.reshape(-1)
    seqs = jax.vmap(one)(jax.random.split(key, flat.shape[0]), flat)
    return seqs.reshape(*topics.shape, seq_len)


def make_deployment(cfg: dict, key):
    """(client_xs (C, n, T), client_ys (C, n), test_xs (N, T), test_ys (N,)):
    each topic a Markov chain over the vocabulary with sharp rows, drawn
    from ``key``; client c's samples mostly of topic c mod K; the held-out
    set cycles through the topics."""
    topics, vocab = cfg["num_classes"], cfg["vocab_size"]
    k_chain, k_train, k_test = jax.random.split(key, 3)
    chains = cfg["chain_sharpness"] * jax.random.normal(k_chain, (topics, vocab, vocab))
    ys = _labels(cfg["num_clients"], cfg["samples_per_client"], topics, cfg["topic_share"])
    test_ys = jnp.arange(cfg["test_samples"], dtype=jnp.int32) % topics
    return (_sequences(k_train, chains, ys, cfg["seq_len"]), ys,
            _sequences(k_test, chains, test_ys, cfg["seq_len"]), test_ys)


# ------------------------------------------------------------------- model


def init_bigram(key, vocab: int, dim: int) -> dict:
    """Normal embedding rows; the head Kaiming-uniform on its fan-in, zero
    bias."""
    k_emb, k_head = jax.random.split(key)
    bound = jnp.sqrt(6.0 / dim)
    return {"embed": jax.random.normal(k_emb, (vocab, dim), jnp.float32),
            "head": {"w": jax.random.uniform(k_head, (dim, vocab), jnp.float32, -bound, bound),
                     "b": jnp.zeros((vocab,), jnp.float32)}}


def apply_with_features(params: dict, x: jax.Array):
    """(logits (B, T-1, V) of each next token, profile features (B, D): the
    mean embedding of the tokens read)."""
    h = params["embed"][x[:, :-1]]
    return h @ params["head"]["w"] + params["head"]["b"], h.mean(axis=1)


def bigram_loss(params: dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; the topic labels ``y`` are not read."""
    logits, _ = apply_with_features(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))


@jax.jit
def accuracy(params: dict, test_xs: jax.Array, test_ys: jax.Array) -> jax.Array:
    """One federation's held-out accuracy: the share of positions whose
    largest logit is the next token."""
    logits, _ = apply_with_features(params, test_xs)
    return jnp.mean(jnp.argmax(logits, axis=-1) == test_xs[:, 1:])


class System:
    """One configuration's federation under one selection strategy."""

    def __init__(self, cfg: dict, strategy: str):
        self.cfg = cfg
        self.fl = engine.FLConfig(
            num_clients=cfg["num_clients"],
            clients_per_round=cfg["clients_per_round"],
            local_epochs=cfg["local_epochs"],
            lr=cfg["lr"],
            rounds=cfg["max_rounds"],
            eval_every=cfg["eval_every"],
            num_classes=cfg["num_classes"],
            use_pallas_kernel=cfg["use_pallas_kernel"],
        )
        self.strategy = make_strategy(strategy)
        self.round_fn = engine.make_round_fn(self.fl, bigram_loss, (self.strategy,))

    def init_params(self, key):
        return init_bigram(key, self.cfg["vocab_size"], self.cfg["embed_dim"])

    def init_state(self, params, key, client_xs, client_ys):
        return engine.init_server_state(
            self.fl, params, bigram_loss, apply_with_features,
            client_xs, client_ys, strategy=self.strategy, key=key,
        )

    def stack(self, states):
        return engine.stack_states(states)

    def run_chunk(self, state, rounds: int, lockstep: int):
        if lockstep == 1:
            return engine.run_scanned(self.round_fn, state, rounds)
        return engine.run_many(self.round_fn, state, rounds)

    def accuracy(self, params, test_xs, test_ys, lockstep: int):
        if lockstep == 1:
            return accuracy(params, test_xs, test_ys)
        return jax.vmap(accuracy, in_axes=(0, None, None))(params, test_xs, test_ys)

    # ---------------------------------------------------- counted from shapes
    def _forward_flops(self) -> int:
        """One sample: the head's product at every position but the last."""
        c = self.cfg
        return 2 * (c["seq_len"] - 1) * c["embed_dim"] * c["vocab_size"]

    def round_flops(self, eval_round: bool) -> int:
        """k clients x n samples x E full-batch steps (the head's forward,
        its weight gradient and the gradient into the embedding rows: three
        products), the cohort's loss refresh, and the held-out forward on an
        evaluation round."""
        c = self.cfg
        k, n, e = c["clients_per_round"], c["samples_per_client"], c["local_epochs"]
        fwd = self._forward_flops()
        total = k * n * e * 3 * fwd + k * n * fwd
        if eval_round:
            total += c["test_samples"] * fwd
        return total

    def init_flops(self) -> int:
        """Every client's initial loss (the profiles are gathers, with no
        products) and the eq.-(14) kernels."""
        c = self.cfg
        eq14 = sum(v["flops"] for v in self.eq14_kernels().values())
        return c["num_clients"] * c["samples_per_client"] * self._forward_flops() + eq14

    def eq14_kernels(self) -> dict:
        return flops.eq14_kernels(self.cfg["num_clients"], self.cfg["embed_dim"])
