"""Synthetic non-IID client data, made on the device from one key.

The recipe is the one of the program's ``make_image_dataset`` and
``skewness_partition`` (class prototypes that are smoothed random fields,
each sample a prototype scaled by U(0.7, 1.3), shifted by up to 3 pixels
each way, plus N(0, 0.6²) noise, the whole set standardised; client c's
dominant class is c mod K, a share ξ of its samples are of that class and
the rest cycle through the other classes), written here in ``jax.numpy`` so
that a federation's samples take one jitted call on the chip instead of a
host loop.
The labels are laid out per client directly, so no pool is drawn from and
none runs out; the order of samples inside a client is not shuffled, which
full-batch local updates and mean profiles cannot see.

One call, :func:`make_federation_data`, returns the clients' training data
``(C, n, H, W, 1)``, their labels ``(C, n)``, and an IID held-out test set.

:func:`make_deployment` is what a run uses.  As MNIST is one fixed dataset
that each seed of the paper's protocol partitions anew, the images come
from the configuration's ``dataset_seed``, and the run's seed draws which
client holds which image: each class's images are shuffled over that
class's places, so every client keeps its labels.  Every seed thus runs
the same dataset in another partition, with its own initial weights and
cohorts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["client_labels", "make_federation_data", "shuffle_within_classes",
           "make_deployment"]

NOISE = 0.6
MAX_SHIFT = 3
ALPHA_RANGE = (0.7, 1.3)
BLUR_PASSES = 3
CHUNK = 12288  # samples generated per step: bounds the set-up's peak memory


def _smooth_field(key, h: int, w: int) -> jax.Array:
    f = jax.random.normal(key, (h, w), jnp.float32)
    for _ in range(BLUR_PASSES):  # 5-point box blur -> smooth blobs
        f = (f + jnp.roll(f, 1, 0) + jnp.roll(f, -1, 0)
             + jnp.roll(f, 1, 1) + jnp.roll(f, -1, 1)) / 5.0
    return (f - f.mean()) / (f.std() + 1e-8)


def client_labels(num_clients: int, n: int, num_classes: int, xi: float) -> jax.Array:
    """(C, n) int32 labels of the ξ protocol: ``round(ξ·n)`` samples of the
    dominant class c mod K, then the other classes in turn, ascending."""
    c = jnp.arange(num_clients)[:, None]
    i = jnp.arange(n)[None, :]
    dom = c % num_classes
    n_dom = int(round(float(xi) * n))
    m = (i - n_dom) % (num_classes - 1)  # index into the other classes
    other = jnp.where(m < dom, m, m + 1)
    return jnp.where(i < n_dom, dom, other).astype(jnp.int32)


def _raw_samples(protos, key, labels):
    """Unnormalised images (m, H, W) for ``labels`` (m,)."""
    m = labels.shape[0]
    k_a, k_x, k_y, k_n = jax.random.split(key, 4)
    alpha = jax.random.uniform(k_a, (m, 1, 1), jnp.float32, *ALPHA_RANGE)
    sx = jax.random.randint(k_x, (m,), -MAX_SHIFT, MAX_SHIFT + 1)
    sy = jax.random.randint(k_y, (m,), -MAX_SHIFT, MAX_SHIFT + 1)
    imgs = protos[labels] * alpha
    imgs = jax.vmap(lambda im, a, b: jnp.roll(im, (a, b), axis=(0, 1)))(imgs, sx, sy)
    return imgs + NOISE * jax.random.normal(k_n, imgs.shape, jnp.float32)


def _group(num_clients: int, n: int) -> int:
    """Clients per generation step: the largest divisor of C whose samples
    fit in one CHUNK, so the steps tile the clients with no padding."""
    return max(g for g in range(1, num_clients + 1)
               if num_clients % g == 0 and (g * n <= CHUNK or g == 1))


def _test_steps(labels):
    """Pad the flat test labels to whole chunks: (steps, CHUNK)."""
    pad = (-labels.shape[0]) % CHUNK
    return jnp.pad(labels, (0, pad)).reshape(-1, CHUNK)


@functools.partial(
    jax.jit,
    static_argnames=("num_clients", "n", "num_classes", "xi", "n_test", "hw"),
)
def make_federation_data(key, *, num_clients, n, num_classes, xi, n_test, hw=(28, 28)):
    """-> (client_xs (C, n, H, W, 1), client_ys (C, n), test_xs (T, H, W, 1),
    test_ys (T,)), all float32 / int32, standardised over train and test."""
    h, w = hw
    k_proto, k_train, k_test, k_lbl = jax.random.split(key, 4)
    protos = jax.vmap(lambda k: _smooth_field(k, h, w))(
        jax.random.split(k_proto, num_classes)
    )
    train_y = client_labels(num_clients, n, num_classes, xi).reshape(-1)
    test_y = jax.random.randint(k_lbl, (n_test,), 0, num_classes, jnp.int32)
    g = _group(num_clients, n)
    parts = [
        (k_train, train_y.reshape(num_clients // g, g * n), num_clients * n),
        (k_test, _test_steps(test_y), n_test),
    ]

    def gen(k, ys, step):
        return _raw_samples(protos, jax.random.fold_in(k, step), ys)

    def moments(k, ys_steps, total):
        def body(acc, xs):
            step, ys = xs
            x = gen(k, ys, step)
            valid = step * ys.shape[0] + jnp.arange(ys.shape[0]) < total
            x = jnp.where(valid[:, None, None], x, 0.0)
            return (acc[0] + x.sum(), acc[1] + (x * x).sum()), None

        steps = jnp.arange(ys_steps.shape[0])
        (s, s2), _ = lax.scan(body, (0.0, 0.0), (steps, ys_steps))
        return s, s2

    s = s2 = 0.0
    for k, ys_steps, total in parts:
        a, b = moments(k, ys_steps, total)
        s, s2 = s + a, s2 + b
    count = (num_clients * n + n_test) * h * w
    mean = s / count
    std = jnp.sqrt(jnp.maximum(s2 / count - mean * mean, 0.0))

    def normalised(k, ys_steps, total):
        steps = jnp.arange(ys_steps.shape[0])
        xs = lax.map(lambda a: (gen(k, a[1], a[0]) - mean) / (std + 1e-8),
                     (steps, ys_steps))
        return xs.reshape(-1, h, w, 1)[:total]

    train_x, test_x = (normalised(*part) for part in parts)
    return (train_x.reshape(num_clients, n, h, w, 1),
            train_y.reshape(num_clients, n), test_x, test_y)


@jax.jit
def shuffle_within_classes(key, client_xs, client_ys):
    """The clients' images with each class's images shuffled, by ``key``,
    over the places that hold that class; the labels stay where they are."""
    y = client_ys.reshape(-1)
    u = jax.random.uniform(key, y.shape)
    places = jnp.argsort(y, stable=True)  # grouped by class, in order
    drawn = jnp.lexsort((u, y))  # the same groups, shuffled inside each
    src = jnp.zeros_like(places).at[places].set(drawn)
    flat = client_xs.reshape((-1,) + client_xs.shape[2:])
    return flat[src].reshape(client_xs.shape), client_ys


def make_deployment(cfg: dict, partition_key):
    """(client_xs, client_ys, test_xs, test_ys) of a run: the dataset of
    the configuration's ``dataset_seed``, partitioned by ``partition_key``."""
    xs, ys, test_xs, test_ys = make_federation_data(
        jax.random.key(int(cfg["dataset_seed"])), num_clients=cfg["num_clients"],
        n=cfg["samples_per_client"], num_classes=cfg["num_classes"], xi=cfg["xi"],
        n_test=cfg["test_samples"], hw=tuple(cfg["image_hw"]))
    xs, ys = shuffle_within_classes(partition_key, xs, ys)
    return jax.block_until_ready((xs, ys, test_xs, test_ys))
