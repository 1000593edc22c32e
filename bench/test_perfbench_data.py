"""The on-device data recipe against the program's host recipe
(``make_image_dataset`` + ``skewness_partition``): the same distribution
at a small size, though not the same draws."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from bench.data import client_labels, make_federation_data, shuffle_within_classes
from repro.data import make_image_dataset, skewness_partition

C, N, K = 20, 60, 10


def _lag1(img):
    """Mean correlation of horizontally and vertically adjacent pixels."""
    a = np.corrcoef(img[:, :-1].ravel(), img[:, 1:].ravel())[0, 1]
    b = np.corrcoef(img[:-1, :].ravel(), img[1:, :].ravel())[0, 1]
    return (a + b) / 2


def _stats(xs, ys):
    xs = xs.reshape(-1, 28, 28)
    ys = ys.reshape(-1)
    means = np.stack([xs[ys == j].mean(0) for j in range(K)])
    between = ((means[ys] - xs.mean(0)) ** 2).mean()
    return {
        "mean": float(xs.mean()), "std": float(xs.std()),
        "proto_smoothness": float(np.mean([_lag1(m) for m in means])),
        "sample_smoothness": float(np.mean([_lag1(x) for x in xs[:200]])),
        "class_share": float(between / xs.var()),
    }


@pytest.fixture(scope="module")
def both():
    ours = [np.asarray(a) for a in make_federation_data(
        jax.random.key(3), num_clients=C, n=N, num_classes=K, xi=0.8, n_test=400)]
    ds = make_image_dataset(n=4 * C * N, seed=3)  # pools that never run out
    shards = skewness_partition(ds.ys, C, 0.8, K, samples_per_client=N, seed=3)
    host = (np.stack([ds.xs[s] for s in shards]), np.stack([ds.ys[s] for s in shards]))
    return ours, host


def test_shapes_and_standardisation(both):
    (xs, ys, tx, ty), _ = both
    assert xs.shape == (C, N, 28, 28, 1) and ys.shape == (C, N)
    assert tx.shape == (400, 28, 28, 1) and ty.shape == (400,)
    allx = np.concatenate([xs.reshape(-1), tx.reshape(-1)])
    assert abs(allx.mean()) < 1e-4 and abs(allx.std() - 1) < 1e-4
    assert set(np.unique(ty)) <= set(range(K))


def test_same_distribution_as_the_host_recipe(both):
    (xs, ys, _, _), (hx, hy) = both
    ours, host = _stats(xs, ys), _stats(hx, hy)
    for key in ("mean", "std"):
        assert abs(ours[key] - host[key]) < 0.05, (key, ours, host)
    for key in ("proto_smoothness", "sample_smoothness", "class_share"):
        assert abs(ours[key] - host[key]) < 0.1 * abs(host[key]) + 0.02, (key, ours, host)


def test_label_skew_matches_the_partition(both):
    (_, ys, _, _), (_, hy) = both
    for c in range(C):
        mine = np.bincount(ys[c], minlength=K)
        theirs = np.bincount(hy[c], minlength=K)
        assert mine[c % K] == theirs[c % K] == round(0.8 * N)
        assert sorted(mine) == sorted(theirs)


def test_labels_of_the_xi_protocol():
    y = np.asarray(client_labels(3, 10, 4, 0.5))
    assert y.tolist() == [[0] * 5 + [1, 2, 3, 1, 2],
                          [1] * 5 + [0, 2, 3, 0, 2],
                          [2] * 5 + [0, 1, 3, 0, 1]]


def test_partition_shuffles_images_within_classes_only():
    xs, ys = (np.asarray(a) for a in make_federation_data(
        jax.random.key(4), num_clients=C, n=N, num_classes=K, xi=0.8, n_test=10)[:2])
    a_xs, a_ys = (np.asarray(v) for v in shuffle_within_classes(jax.random.key(1), xs, ys))
    b_xs = np.asarray(shuffle_within_classes(jax.random.key(1), xs, ys)[0])
    c_xs = np.asarray(shuffle_within_classes(jax.random.key(2), xs, ys)[0])
    assert (a_ys == ys).all() and (a_xs == b_xs).all() and not (a_xs == c_xs).all()
    flat, moved = xs.reshape(C * N, -1), a_xs.reshape(C * N, -1)
    for j in range(K):
        here = ys.reshape(-1) == j  # the class's images, as a set, stay on its places
        key = lambda m: sorted(map(bytes, m))  # noqa: E731
        assert key(flat[here]) == key(moved[here])
    assert (moved != flat).any(axis=1).mean() > 0.5
