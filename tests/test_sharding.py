"""Sharding-layer tests: spec trees mirror param/cache trees; rules resolve;
Mode-A/B step functions lower under a small mesh (in-process, 1 device)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, get_arch
from repro.launch import sharding as sh
from repro.models import transformer as T


@pytest.mark.parametrize("name", ["granite-3-2b", "mixtral-8x7b", "rwkv6-7b",
                                  "recurrentgemma-9b", "llama4-maverick-400b-a17b"])
def test_param_spec_tree_matches_param_tree(name):
    cfg = get_arch(name).model.reduced()
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
    logical = sh.param_logical_specs(cfg)
    specs = sh.specs_from_logical(logical, get_arch(name).serve_rules)
    # tree structures must match leaf-for-leaf
    jax.tree_util.tree_map(
        lambda sdt, spec: None
        if len(spec) <= len(sdt.shape)
        else pytest.fail(f"{spec} too long for {sdt.shape}"),
        shapes, specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )


@pytest.mark.parametrize("name", ["granite-3-2b", "rwkv6-7b", "recurrentgemma-9b"])
def test_cache_spec_tree_matches_cache_tree(name):
    cfg = get_arch(name).model.reduced()
    shapes = jax.eval_shape(lambda: T.init_caches(cfg, 2, 64))
    specs = sh.specs_from_logical(
        sh.cache_logical_specs(cfg), get_arch(name).serve_rules
    )
    flat_a = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, shapes,
                               is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)))
    flat_b = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, specs,
                               is_leaf=lambda x: isinstance(x, P)))
    assert flat_a == flat_b


def test_resolve_axis_multipod():
    assert sh.resolve_axis("data", True) == ("pod", "data")
    assert sh.resolve_axis("data", False) == "data"
    assert sh.resolve_axis("model", True) == "model"
    assert sh.resolve_axis(None, True) is None


def test_constrain_is_noop_without_rules():
    x = jnp.ones((4, 4))
    y = sh.constrain(x, "act_batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_applies_under_rules_and_mesh():
    mesh = jax.make_mesh(
        (1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )

    def f(x):
        return sh.constrain(x, "act_batch", None) * 2

    with jax.set_mesh(mesh), sh.use_rules({"act_batch": "data"}):
        out = jax.jit(f)(jnp.ones((4, 4)))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_optimizer_state_specs_shapes():
    pspecs = {"w": P("data", "model"), "b": P(None)}
    adam = sh.optimizer_state_specs("adam", pspecs)
    assert adam.mu == pspecs and adam.nu == pspecs
    af = sh.optimizer_state_specs("adafactor", pspecs)
    assert af.vr["w"] == P("data")
    assert af.vc["w"] == P("model")
    assert af.vr["b"] == P(None)
    assert sh.optimizer_state_specs("sgd", pspecs) == ()


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_every_arch_logical_spec_covers_every_leaf(name):
    spec = get_arch(name)
    cfg = spec.model
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.key(0))
    logical = sh.param_logical_specs(cfg)
    n_shapes = len(jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)))
    n_specs = len(jax.tree_util.tree_leaves(logical, is_leaf=lambda x: isinstance(x, sh.Ax)))
    assert n_shapes == n_specs, (name, n_shapes, n_specs)
