"""``conv1_bwd_ms`` on traces recorded here: gradient steps of a small CNN
inside an ``fl.local_update`` scope, with the first stage's backward kernel
engaged as on a TPU (interpreted) and without it.  The kernel's operations
stay in ``local_update_ms``; the reader finds them by their own scope."""

from __future__ import annotations

import types

import jax
import pytest

from bench import harness, scopes
from repro.models import cnn


def _traced_steps(path, monkeypatch, engaged):
    """A context for the readers over a trace of three jitted steps."""
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: engaged)
    kp, kx, ky = jax.random.split(jax.random.key(7), 3)
    params = cnn.init_cnn(kp, num_classes=4, in_hw=(8, 8), channels=(8, 8), fc1_dim=16)
    x = jax.random.normal(kx, (6, 8, 8, 1))
    y = jax.random.randint(ky, (6,), 0, 4)

    @jax.jit
    def step(p):
        with jax.named_scope("fl.local_update"):
            g = jax.grad(cnn.cnn_loss)(p, x, y)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    jax.block_until_ready(step(params))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        for _ in range(3):
            params = step(params)
        jax.block_until_ready(params)
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(scopes, "TRACE_DIR", path)
    return types.SimpleNamespace(stats={"fed_rounds": 3}, span=(0.0, float("inf")))


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("engaged", [True, False])
def test_kernel_ops_are_read_and_stay_in_the_local_update(tmp_path, monkeypatch, engaged):
    ctx = _traced_steps(tmp_path, monkeypatch, engaged)
    found = scopes.of(ctx)
    kernel = _read("conv1_bwd_ms", ctx)
    local = _read("local_update_ms", ctx)
    # every scoped operation is the local update's: none left for another stage
    assert {sc for _, _, sc, _ in found.ops if sc is not None} == {"fl.local_update"}
    assert local == pytest.approx(1e3 * found.seconds(None, *ctx.span) / 3)
    if engaged:
        assert 0 < kernel < local
    else:
        assert kernel == 0.0
