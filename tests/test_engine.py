"""Engine tests: the scanned federation must reproduce the legacy Python
loop (identical cohorts, metrics within a few float32 ulp), and vmapped
batched simulation must match per-case runs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_strategy
from repro.data import make_image_dataset, skewness_partition
from repro.fl import FLConfig, FLTrainer, engine
from repro.models import cnn

C, N, HW = 10, 30, 14


@pytest.fixture(scope="module")
def federation():
    ds = make_image_dataset(n=C * N, seed=3, h=HW, w=HW)
    shards = skewness_partition(ds.ys, C, 1.0, 10, samples_per_client=N, seed=0)
    return (
        np.stack([ds.xs[s] for s in shards]),
        np.stack([ds.ys[s] for s in shards]),
    )


def _trainer(federation, name, rounds=6, seed=0, **cfg_kw):
    cxs, cys = federation
    params = cnn.init_cnn(
        jax.random.key(seed), in_hw=(HW, HW), channels=(4, 8), fc1_dim=32
    )
    cfg = FLConfig(
        num_clients=C, clients_per_round=3, rounds=rounds, local_epochs=1,
        lr=0.05, eval_every=2, seed=seed, **cfg_kw,
    )
    return FLTrainer(
        cfg, params, cnn.cnn_loss, cnn.apply_with_features, cxs, cys,
        make_strategy(name), accuracy_fn=cnn.accuracy,
    )


# The scanned program and the host loop compute the same float32 values, but
# XLA fuses the two programs differently (GEMD's reductions among them), so
# a metric may land a few ulp apart.  Cohorts are integer draws and must be
# identical.
MAX_ULP = 4


def _assert_engine_matches_legacy(federation, name, rounds):
    tr_eng = _trainer(federation, name, rounds=rounds)
    _, outs = engine.run_scanned(tr_eng.round_fn(), tr_eng.server_state(), rounds)
    h_eng = _trainer(federation, name, rounds=rounds).run()

    tr_leg = _trainer(federation, name, rounds=rounds)
    picks = []
    select = tr_leg.strategy.select
    tr_leg.strategy.select = lambda *a: picks.append(np.asarray(select(*a))) or picks[-1]
    h_leg = tr_leg.run_legacy()

    np.testing.assert_array_equal(np.asarray(outs["selected"]), np.stack(picks))
    assert h_eng["round"] == h_leg["round"]
    for k in ("acc", "gemd", "loss"):
        np.testing.assert_array_max_ulp(
            np.float32(h_eng[k]), np.float32(h_leg[k]), maxulp=MAX_ULP
        )


@pytest.mark.parametrize("name", ["fedavg", "fl-dp3s"])
def test_scanned_matches_legacy_history(federation, name):
    """run() (scanned engine) == run_legacy() (host loop), ≥5 rounds."""
    _assert_engine_matches_legacy(federation, name, rounds=6)


def test_scanned_matches_legacy_cluster_and_fedsae(federation):
    """The host-fit + pure-draw split (cluster) and loss-weighted sampling
    (fedsae) also reproduce the loop."""
    for name in ("cluster", "fedsae"):
        _assert_engine_matches_legacy(federation, name, rounds=5)


def test_run_scanned_outputs_per_round(federation):
    tr = _trainer(federation, "fedavg", rounds=4)
    state, outs = engine.run_scanned(tr.round_fn(), tr.server_state(), 4)
    assert np.asarray(outs["gemd"]).shape == (4,)
    assert np.asarray(outs["selected"]).shape == (4, 3)
    assert int(state.round) == 4
    # acc is evaluated on the eval grid only (eval_every=2) — NaN elsewhere
    acc = np.asarray(outs["acc"])
    assert np.isnan(acc[0]) and np.isfinite(acc[1])


def test_run_many_matches_sequential():
    """vmapped multi-(seed, strategy) simulation == per-case scanned runs."""
    c, n, hw, rounds = 6, 8, 10, 3
    ds = make_image_dataset(n=c * n, seed=5, h=hw, w=hw)
    shards = skewness_partition(ds.ys, c, 1.0, 10, samples_per_client=n, seed=0)
    cxs = np.stack([ds.xs[s] for s in shards])
    cys = np.stack([ds.ys[s] for s in shards])
    strategies = (make_strategy("fedavg"), make_strategy("fl-dp3s"))
    cfg = FLConfig(
        num_clients=c, clients_per_round=2, rounds=rounds, local_epochs=1,
        lr=0.05, eval_every=rounds, seed=0,
    )
    round_fn = engine.make_round_fn(cfg, cnn.cnn_loss, strategies)
    states = []
    for si in range(2):
        for seed in range(2):
            params = cnn.init_cnn(
                jax.random.key(seed), in_hw=(hw, hw), channels=(1, 2), fc1_dim=8
            )
            st = engine.init_server_state(
                dataclasses.replace(cfg, seed=seed), params, cnn.cnn_loss,
                cnn.apply_with_features, cxs, cys,
                strategy=strategies[si], strategy_index=si,
            )
            states.append(st)
    stacked = engine.stack_states(states)
    _, outs = engine.run_many(round_fn, stacked, rounds)
    per_case = engine.unstack_outputs(outs)
    assert len(per_case) == 4
    for i, st in enumerate(states):
        _, ref = engine.run_scanned(round_fn, st, rounds)
        for k in ("gemd", "loss"):
            np.testing.assert_allclose(
                per_case[i][k], np.asarray(ref[k]), rtol=1e-5, atol=1e-6,
                err_msg=f"case {i} key {k}",
            )


def test_reprofile_refreshes_kernel_in_engine_path(federation):
    """reprofile_every runs scan segments with a host profile refresh between
    them; the trainer's kernel must change once params have moved."""
    tr = _trainer(federation, "fl-dp3s", rounds=4, reprofile_every=2)
    k0 = np.asarray(tr.round_state.kernel).copy()
    tr.run()
    k1 = np.asarray(tr.round_state.kernel)
    assert tr.round_state.round == 4
    assert not np.allclose(k0, k1)


def test_history_from_outputs_final_round_fill():
    outs = {
        "round": np.asarray([1, 2, 3]),
        "acc": np.asarray([np.nan, 0.5, np.nan]),
        "gemd": np.asarray([1.0, 0.9, 0.8]),
        "loss": np.asarray([2.0, 1.5, 1.2]),
    }
    h = engine.history_from_outputs(outs, eval_every=2, final_acc=0.7)
    assert h["round"] == [2, 3]
    assert h["acc"] == [0.5, 0.7]


def test_history_from_outputs_empty_run():
    """Zero-round outputs (e.g. a run_many grid scanned for 0 rounds) yield
    an empty history, not an IndexError."""
    outs = {
        "round": np.zeros((0,), np.int32),
        "acc": np.zeros((0,), np.float32),
        "gemd": np.zeros((0,), np.float32),
        "loss": np.zeros((0,), np.float32),
    }
    h = engine.history_from_outputs(outs, eval_every=2)
    assert h == {"round": [], "acc": [], "gemd": [], "loss": []}


def test_steps_per_round_uses_shared_num_batches():
    """_steps_per_round and batches_from_indices must agree on batches/epoch
    (one shared _num_batches helper — drop-remainder, at least one)."""
    cfg = FLConfig(num_clients=4, clients_per_round=2, local_epochs=3,
                   local_batch_size=4)
    for n_c in (3, 4, 9, 10):
        steps = engine._steps_per_round(cfg, n_c)
        nb = engine._num_batches(n_c, cfg.local_batch_size)
        assert steps == cfg.local_epochs * nb
        ids = jnp.stack([jax.random.permutation(jax.random.key(0), n_c)])
        xs = jnp.zeros((1, n_c, 2))
        ys = jnp.zeros((1, n_c), jnp.int32)
        xb, yb = engine.batches_from_indices(cfg, ids, xs, ys)
        assert xb.shape[1] == steps and yb.shape[1] == steps


def test_make_client_batches_full_batch_mode():
    cfg = FLConfig(num_clients=4, clients_per_round=2, local_epochs=3)
    xs = jnp.arange(4 * 5 * 2, dtype=jnp.float32).reshape(4, 5, 2)
    ys = jnp.arange(4 * 5, dtype=jnp.int32).reshape(4, 5)
    xb, yb = engine.make_client_batches(
        cfg, jax.random.key(0), xs, ys, jnp.asarray([1, 3])
    )
    assert xb.shape == (2, 3, 5, 2) and yb.shape == (2, 3, 5)
    np.testing.assert_array_equal(np.asarray(xb[0, 0]), np.asarray(xs[1]))


def test_make_client_batches_with_replacement():
    cfg = FLConfig(
        num_clients=4, clients_per_round=2, local_batch_size=3, local_steps=5,
        sample_with_replacement=True,
    )
    xs = jnp.arange(4 * 7, dtype=jnp.float32).reshape(4, 7)
    ys = jnp.arange(4 * 7, dtype=jnp.int32).reshape(4, 7)
    xb, yb = engine.make_client_batches(
        cfg, jax.random.key(0), xs, ys, jnp.asarray([0, 2])
    )
    assert xb.shape == (2, 5, 3) and yb.shape == (2, 5, 3)
    # draws come from the selected client's own shard
    assert set(np.asarray(xb[0]).ravel().tolist()) <= set(np.asarray(xs[0]).tolist())
    assert set(np.asarray(xb[1]).ravel().tolist()) <= set(np.asarray(xs[2]).tolist())
