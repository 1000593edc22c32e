"""Stable names inside the federation engine (DESIGN.md §14): every round
body carries the ``fl.*`` named scopes, the two programs are named
``fl_scan`` / ``fl_run_many``, and the compile marker lands inside the
host span of the step that compiled.  The spans and the trace readers
that use them are tested with the benchmark (``bench/``)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import make_strategy
from repro.fl import engine
from repro.launch.sharding import CLIENT_AXIS
from repro.obs import tracing

FEAT, N_C, NCLS, C, K = 8, 6, 4, 8, 3
ROUND_SCOPES = ("fl.select", "fl.batches", "fl.local_update", "fl.aggregate",
                "fl.loss_refresh", "fl.gemd", "fl.eval")


def linear_loss(params, x, y):
    logp = jax.nn.log_softmax(x @ params["w"] + params["b"])
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def linear_features(params, x):
    h = x @ params["w"]
    return h + params["b"], h


def linear_accuracy(params, x, y):
    return jnp.mean(jnp.argmax(x @ params["w"] + params["b"], -1) == y)


def _state(cfg, mesh=None):
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(C, N_C, FEAT)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, NCLS, size=(C, N_C)), jnp.int32)
    params = {"w": jnp.asarray(0.1 * rng.normal(size=(FEAT, NCLS)).astype(np.float32)),
              "b": jnp.zeros((NCLS,), jnp.float32)}
    strat = make_strategy("fl-dp3s")
    state = engine.init_server_state(cfg, params, linear_loss, linear_features, xs, ys,
                                     strategy=strat, key=jax.random.key(1), mesh=mesh)
    rf = engine.make_round_fn(cfg, linear_loss, (strat,), accuracy_fn=linear_accuracy,
                              mesh=mesh)
    return rf, state


BODIES = {
    "single": {},
    "sharded": {"mesh": True},
    "slot": {"mesh": True, "cohort_cap": K},
    "stale": {"mesh": True, "staleness_bound": 1, "scenario": "uniform"},
}


@pytest.mark.parametrize("body", sorted(BODIES))
def test_every_round_body_carries_the_scopes(body):
    kw = dict(BODIES[body])
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:1]), (CLIENT_AXIS,))
            if kw.pop("mesh", False) else None)
    cfg = engine.FLConfig(num_clients=C, clients_per_round=K, local_epochs=2, lr=0.1,
                          eval_every=2, num_classes=NCLS, **kw)
    rf, state = _state(cfg, mesh)
    if mesh is not None:
        state = engine.shard_server_state(state, mesh)
    text = engine._scanned(rf, 2).lower(state).as_text(debug_info=True)
    assert "@jit_fl_scan" in text
    missing = [s for s in ROUND_SCOPES if s not in text]
    assert not missing, missing


def test_run_many_program_is_named_and_scoped():
    cfg = engine.FLConfig(num_clients=C, clients_per_round=K, local_epochs=2, lr=0.1,
                          eval_every=2, num_classes=NCLS)
    rf, state = _state(cfg)
    lowered = engine._vmapped(rf, 2).lower(engine.stack_states([state, state]))
    text = lowered.as_text(debug_info=True)
    assert "@jit_fl_run_many" in text
    missing = [s for s in ROUND_SCOPES if s not in text]
    assert not missing, missing
    # the scopes survive into the compiled program's op metadata, which is
    # what a device trace names an operation by
    compiled = lowered.compile().as_text()
    assert compiled.startswith("HloModule jit_fl_run_many")
    assert all(f"/{s}" in compiled or f"({s})" in compiled
               for s in ("fl.select", "fl.local_update", "fl.aggregate"))


def _host_events(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    return events


def test_compile_marker_lands_inside_the_compiling_span(tmp_path):
    x = jnp.arange(16.0)
    (x + 1.0).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.annotate("fl.test_step"):
            jax.jit(lambda a: jnp.cos(a) * 3.0 + 1.0)(x).block_until_ready()
        with tracing.annotate("fl.test_cached"):
            (x + 1.0).block_until_ready()  # compiled before the trace
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    markers = [(s, e) for n, s, e in events if n == tracing.COMPILE_MARKER]
    (step,) = [(s, e) for n, s, e in events if n == "fl.test_step"]
    (cached,) = [(s, e) for n, s, e in events if n == "fl.test_cached"]
    inside = [m for m in markers if step[0] <= m[0] and m[1] <= step[1]]
    assert len(inside) == 1
    assert not [m for m in markers if cached[0] <= m[0] <= cached[1]]
