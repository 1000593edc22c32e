"""Federation initialisation over the client axis: the eq.-(11) profile
pass and the label histograms as one compiled program each
(``profile_stacked_clients``, ``label_distributions``), against the
per-client loops they replace (``fc1_profile``, ``label_distribution``)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import make_strategy
from repro.core import metrics as metrics_lib
from repro.core import profiles as profiles_lib
from repro.fl import FLConfig, FLTrainer, engine
from repro.models import cnn

FEAT, Q, NCLS, HW = 6, 5, 4, 12


def linear_features(params, x):
    h = x @ params["w"] + params["b"]
    return h, h


def linear_loss(params, x, y):
    logp = jax.nn.log_softmax(x @ params["w"][:, :NCLS] + params["b"][:NCLS])
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def _linear_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(FEAT, Q)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(Q,)).astype(np.float32))}


def _cnn_params(seed=0):
    return cnn.init_cnn(jax.random.key(seed), in_hw=(HW, HW), channels=(4, 8), fc1_dim=16)


MODELS = {
    "linear": (linear_features, _linear_params, (FEAT,)),
    "cnn": (cnn.apply_with_features, _cnn_params, (HW, HW, 1)),
}


def _client_xs(c, n, sample, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(c, n, *sample)).astype(np.float32))


def _rel_err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("n,block", [(20, 8), (16, 8), (7, 256), (600, 256)])
def test_stacked_profiles_match_the_per_client_loop(model, n, block):
    feature_fn, make_params, sample = MODELS[model]
    params = make_params()
    xs = _client_xs(3, n, sample)
    got = profiles_lib.profile_stacked_clients(feature_fn, params, xs, batch_size=block)
    want = profiles_lib.profile_all_clients(feature_fn, params, list(xs), batch_size=block)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel_err(got, want) <= 1e-5


@pytest.mark.parametrize("n,block", [(20, 8), (600, 256), (5, 4)])
def test_padding_adds_nothing(n, block):
    """The zero-padded rows of the ragged tail block would read -inf here
    (log of 0): masked out of the sum, they leave the mean of the real
    samples."""
    def log_features(params, x):
        h = jnp.log(x) * params["s"]
        return h, h

    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.uniform(0.5, 2.0, size=(4, n, 3)).astype(np.float32))
    params = {"s": jnp.float32(1.5)}
    got = profiles_lib.profile_stacked_clients(log_features, params, xs, batch_size=block)
    assert np.isfinite(np.asarray(got)).all()
    want = 1.5 * np.log(np.asarray(xs, np.float64)).mean(axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_empty_clients_give_zero_rows_of_width_q(model):
    feature_fn, make_params, sample = MODELS[model]
    params = make_params()
    got = profiles_lib.profile_stacked_clients(feature_fn, params, _client_xs(3, 0, sample))
    width = Q if model == "linear" else 16
    assert got.shape == (3, width)
    assert (np.asarray(got) == 0.0).all()


@pytest.mark.parametrize("model", sorted(MODELS))
def test_new_params_reuse_the_compiled_profile_program(model):
    _, make_params, sample = MODELS[model]

    def feature_fn(params, x):  # a function of its own: a cache of its own
        return MODELS[model][0](params, x)

    xs = _client_xs(2, 10, sample)
    before = profiles_lib.profile_stacked_clients._cache_size()
    a = profiles_lib.profile_stacked_clients(feature_fn, make_params(0), xs, batch_size=4)
    b = profiles_lib.profile_stacked_clients(feature_fn, make_params(1), xs, batch_size=4)
    assert profiles_lib.profile_stacked_clients._cache_size() == before + 1
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("c,n,ncls", [(5, 9, 4), (12, 60, 10), (3, 1, 3)])
def test_label_histograms_match_the_per_client_loop_bit_for_bit(c, n, ncls):
    rng = np.random.default_rng(c * n)
    ys = rng.integers(0, ncls, size=(c, n))
    ys[0] = np.where(ys[0] == ncls - 1, 0, ys[0])  # client 0 lacks the last class
    ys = jnp.asarray(ys, jnp.int32)
    got = metrics_lib.label_distributions(ys, ncls)
    want = jnp.stack([metrics_lib.label_distribution(y, ncls) for y in ys])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(got[0, ncls - 1]) == 0.0


def _federation(c, n=10, seed=3):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(c, n, FEAT)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, NCLS, size=(c, n)), jnp.int32)
    return xs, ys


def _init(c, xs, ys):
    cfg = FLConfig(num_clients=c, clients_per_round=3, local_epochs=1, lr=0.1,
                   num_classes=NCLS)
    return engine.init_server_state(cfg, _linear_params(), linear_loss, linear_features,
                                    xs, ys, strategy=make_strategy("fl-dp3s"),
                                    key=jax.random.key(0))


def test_init_server_state_matches_the_per_client_loops():
    xs, ys = _federation(12, n=300)
    state = _init(12, xs, ys)
    want_dists = jnp.stack([metrics_lib.label_distribution(y, NCLS) for y in ys])
    np.testing.assert_array_equal(np.asarray(state.client_label_dists),
                                  np.asarray(want_dists))
    want_profiles = profiles_lib.profile_all_clients(
        jax.jit(linear_features), _linear_params(), list(xs))
    np.testing.assert_allclose(np.asarray(state.profiles), np.asarray(want_profiles),
                               rtol=1e-5, atol=1e-6)


def test_trainer_shares_the_engines_initialisation():
    xs, ys = _federation(12)
    state = _init(12, xs, ys)
    cfg = FLConfig(num_clients=12, clients_per_round=3, local_epochs=1, lr=0.1,
                   num_classes=NCLS)
    tr = FLTrainer(cfg, _linear_params(), linear_loss, linear_features, np.asarray(xs),
                   np.asarray(ys), make_strategy("fl-dp3s"))
    np.testing.assert_array_equal(np.asarray(tr.client_label_dists),
                                  np.asarray(state.client_label_dists))
    np.testing.assert_array_equal(np.asarray(tr.round_state.profiles),
                                  np.asarray(state.profiles))


def _dispatches_by_phase(trace_dir, phases):
    """Top-level ``PjitFunction`` host events inside each named span."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
    spans = {p: [(s, e) for s, e, n in events if n == p] for p in phases}
    counts = dict.fromkeys(phases, 0)
    end = float("-inf")
    for s, e, name in sorted(events):
        if not name.startswith("PjitFunction(") or s < end:
            continue
        end = e
        for p in phases:
            counts[p] += any(a <= s < b for a, b in spans[p])
    return counts


def test_profile_and_label_dispatches_do_not_grow_with_the_clients(tmp_path):
    phases = ("fl.init.profiles", "fl.init.label_dists")
    counts = {}
    for c in (12, 48):
        xs, ys = _federation(c)
        jax.block_until_ready(_init(c, xs, ys))  # compiles outside the trace
        d = str(tmp_path / str(c))
        jax.profiler.start_trace(d)
        try:
            jax.block_until_ready(_init(c, xs, ys))
        finally:
            jax.profiler.stop_trace()
        counts[c] = _dispatches_by_phase(d, phases)
    assert counts[12]["fl.init.profiles"] >= 1
    assert counts[48] == counts[12], counts
