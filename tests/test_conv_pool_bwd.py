"""The first conv stage's backward kernel (interpret mode) against its jnp
oracle and against XLA's own gradient of the stage, and the CNN with the
kernel engaged against the CNN without it.

On the CPU the model keeps XLA's backward; these tests engage the kernel by
replacing the model's backend check, so the kernel body runs interpreted.
Sizes are tiny: a lockstep axis under ``vmap``, a partial lane block
(N = 130 against blocks of 128), and tie-heavy inputs, where equal positive
maxima share a 2×2 window and only the first may take the gradient.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv_pool_bwd import ops
from repro.kernels.conv_pool_bwd import ref
from repro.models import cnn

KERNEL_HW = (5, 5)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _inputs(seed, s, n, h, w, c, ties):
    """y, b, x, g of ``s`` federations; with ``ties`` the conv output takes
    few values and the bias is positive, so windows hold equal maxima."""
    ky, kb, kx, kg = jax.random.split(jax.random.key(seed), 4)
    y = jax.random.normal(ky, (s, n, h, w, c))
    b = 0.3 * jax.random.normal(kb, (s, c))
    if ties:
        y = jnp.round(2.0 * y) / 2.0
        b = jnp.abs(b) + 0.5
    x = jax.random.normal(kx, (s, n, h, w, 1))
    g = jax.random.normal(kg, (s, n, h // 2, w // 2, c))
    return y, b, x, g


@pytest.fixture
def engaged(monkeypatch):
    """The CNN's first stage with the kernel's backward, as on a TPU."""
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)


CASES = {
    "one_federation": (1, 6, 8, 8, 16, False),
    "lockstep_ties": (3, 6, 8, 8, 16, True),
    "partial_lane_block": (2, 130, 4, 6, 8, True),
    # two kernel row blocks of 6 federations each
    "groups_of_federations": (12, 6, 4, 4, 8, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ref(case):
    s, n, h, w, c, ties = CASES[case]
    y, b, x, g = _inputs(0, s, n, h, w, c, ties)
    dw, db = jax.vmap(lambda *a: ops.conv_pool_bwd(*a, KERNEL_HW))(y, b, x, g)
    dw_ref, db_ref = jax.vmap(lambda *a: ref.conv_pool_bwd_ref(*a, KERNEL_HW))(y, b, x, g)
    assert dw.shape == (s, 5, 5, 1, c) and db.shape == (s, c)
    # the same bf16 products and f32 sums, in another order
    assert _rel(dw, dw_ref) <= 1e-6
    assert _rel(db, db_ref) <= 1e-6
    # one federation at a time: the call without vmap
    dw0, db0 = ops.conv_pool_bwd(y[0], b[0], x[0], g[0], KERNEL_HW)
    assert _rel(dw0, dw_ref[0]) <= 1e-6 and _rel(db0, db_ref[0]) <= 1e-6


def test_kernel_under_nested_vmap_and_a_shared_operand():
    """Clients vmapped inside vmapped federations (the client-vmap round
    under ``run_many``), and a bias shared by every federation: the
    wrapper's batching rule takes the inner axis, ``pallas_call``'s the
    outer one, and an unbatched operand is broadcast."""
    y, b, x, g = _inputs(4, 6, 6, 4, 4, 8, True)
    split = lambda a: a.reshape((2, 3) + a.shape[1:])
    fn = lambda *a: ops.conv_pool_bwd(*a, KERNEL_HW)
    oracle = lambda *a: ref.conv_pool_bwd_ref(*a, KERNEL_HW)
    args = [split(a) for a in (y, b, x, g)]
    for got, want in zip(jax.vmap(jax.vmap(fn))(*args), jax.vmap(jax.vmap(oracle))(*args)):
        assert _rel(got, want) <= 1e-6
    shared = (0, None, 0, 0)
    for got, want in zip(jax.vmap(fn, in_axes=shared)(y, b[0], x, g),
                         jax.vmap(oracle, in_axes=shared)(y, b[0], x, g)):
        assert _rel(got, want) <= 1e-6


def _stage_grads(stage, x, w, b, g):
    """The stage's output and its (dw, db); x takes no gradient."""
    out, vjp = jax.vjp(lambda w_, b_: stage(x, w_, b_), w, b)
    return out, vjp(g)


def test_stage_vjp_matches_xla_exactly_where_products_are_exact():
    """Binary images with flat zero regions, integer weights (two output
    channels all zero, so their conv output is 0 everywhere and every
    window of relu(0 + b) ties) and a positive bias; a cotangent exact in
    bf16.  Every product is then exact in both backwards, so the weight
    gradient differs from XLA's only by f32 summation order, and a window
    routed to any maximum but the first would move it."""
    kx, kw, kg = jax.random.split(jax.random.key(1), 3)
    n, hw, c = 6, 8, 16
    x = (jax.random.uniform(kx, (n, hw, hw, 1)) < 0.3).astype(jnp.float32)
    x = x.at[:, :4, :4].set(0.0)
    w = jax.random.randint(kw, (5, 5, 1, c), -2, 3).astype(jnp.float32)
    w = w.at[..., :2].set(0.0)
    b = jnp.linspace(0.25, 1.0, c)
    g = jax.random.normal(kg, (n, hw // 2, hw // 2, c)).astype(jnp.bfloat16).astype(jnp.float32)
    out, (dw, db) = _stage_grads(cnn._stage_kernel_bwd, x, w, b, g)
    out_xla, (dw_xla, db_xla) = _stage_grads(cnn._stage, x, w, b, g)
    assert np.array_equal(out, out_xla)
    assert _rel(dw, dw_xla) <= 1e-6 and _rel(db, db_xla) <= 1e-6
    assert np.abs(np.asarray(dw_xla)[..., :2]).max() > 0  # the tied channels carry gradient


@pytest.mark.parametrize("lockstep", [1, 3])
def test_stage_vjp_matches_xla_to_bf16_operand_rounding(lockstep):
    """Random inputs: the bias gradient is f32 in both backwards; the
    weight gradient's products take bf16 operands here (as XLA's TPU
    convolution does at default precision) and f32 ones in XLA's CPU
    convolution, so each product may differ by 2·2^-9 of its size."""
    keys = jax.random.split(jax.random.key(2), 4)
    n, hw, c = 6, 8, 16
    x = jax.random.normal(keys[0], (lockstep, n, hw, hw, 1))
    w = 0.2 * jax.random.normal(keys[1], (lockstep, 5, 5, 1, c))
    b = 0.1 * jax.random.normal(keys[2], (lockstep, c))
    g = jax.random.normal(keys[3], (lockstep, n, hw // 2, hw // 2, c))
    grads = jax.vmap(lambda *a: _stage_grads(cnn._stage_kernel_bwd, *a))
    xla = jax.vmap(lambda *a: _stage_grads(cnn._stage, *a))
    out, (dw, db) = grads(x, w, b, g)
    out_xla, (dw_xla, db_xla) = xla(x, w, b, g)
    assert np.array_equal(out, out_xla)
    assert _rel(db, db_xla) <= 1e-6
    # Σ |x|·|dy| per weight, from XLA's own gradient of |x| against |dy|:
    # dy is XLA's cotangent of the conv output
    y = jax.vmap(cnn._conv)(x, w)
    _, pool_vjp = jax.vjp(lambda z: jax.vmap(cnn._maxpool2)(jax.nn.relu(z)),
                          y + b[:, None, None, None, :])
    (dy,) = pool_vjp(g)
    _, conv_vjp = jax.vjp(lambda w_: jax.vmap(cnn._conv)(jnp.abs(x), w_), w)
    (scale,) = conv_vjp(jnp.abs(dy))
    assert np.all(np.abs(np.asarray(dw) - np.asarray(dw_xla))
                  <= 2.0 ** -7 * np.asarray(scale) + 1e-6)


def _model(seed=3, n=6):
    kp, kx, ky = jax.random.split(jax.random.key(seed), 3)
    params = cnn.init_cnn(kp, num_classes=4, in_hw=(8, 8), channels=(16, 8), fc1_dim=16)
    x = jax.random.normal(kx, (n, 8, 8, 1))
    y = jax.random.randint(ky, (n,), 0, 4)
    return params, x, y


def test_forward_is_bit_identical_with_the_kernel_engaged(monkeypatch):
    params, x, y = _model()
    plain = cnn.apply_with_features(params, x)
    loss_plain, grads_plain = jax.value_and_grad(cnn.cnn_loss)(params, x, y)
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    kernel = cnn.apply_with_features(params, x)
    loss_kernel, grads_kernel = jax.value_and_grad(cnn.cnn_loss)(params, x, y)
    for a, b in zip(plain, kernel):
        assert np.array_equal(a, b)
    assert np.array_equal(loss_plain, loss_kernel)
    # the stages downstream of the first take the same cotangents
    for layer in ("conv2", "fc1", "fc2"):
        for k in ("w", "b"):
            assert np.array_equal(grads_plain[layer][k], grads_kernel[layer][k])
    assert _rel(grads_kernel["conv1"]["b"], grads_plain["conv1"]["b"]) <= 1e-6
    assert _rel(grads_kernel["conv1"]["w"], grads_plain["conv1"]["w"]) <= 2e-2


def test_an_input_that_takes_a_gradient_keeps_xlas_backward(engaged):
    params, x, y = _model()
    got = jax.grad(cnn.cnn_loss, argnums=(0, 1))(params, x, y)
    jaxpr = str(jax.make_jaxpr(jax.grad(cnn.cnn_loss, argnums=1))(params, x, y))
    assert "pallas_call" not in jaxpr
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cnn, "_pallas_backward", lambda: False)
        want = jax.grad(cnn.cnn_loss, argnums=(0, 1))(params, x, y)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


def test_a_higher_precision_keeps_xlas_backward(engaged):
    """The kernel forms one bf16 pass of products: under a higher default
    matmul precision the stage keeps XLA's backward, whose products are
    exact in f32."""
    params, x, y = _model()
    grad = jax.grad(cnn.cnn_loss)
    assert "pallas_call" in str(jax.make_jaxpr(grad)(params, x, y))
    for precision in ("highest", "float32", "high"):
        with jax.default_matmul_precision(precision):
            assert "pallas_call" not in str(jax.make_jaxpr(grad)(params, x, y)), precision
    with jax.default_matmul_precision("bfloat16"):
        assert "pallas_call" in str(jax.make_jaxpr(grad)(params, x, y))


def test_kernel_engages_only_on_a_tpu_and_covered_shapes(monkeypatch):
    params, x, y = _model()

    def calls_kernel(p, xs):
        return "pallas_call" in str(jax.make_jaxpr(jax.grad(cnn.cnn_loss))(p, xs, y))

    assert not calls_kernel(params, x)  # the CPU keeps XLA's backward
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    assert calls_kernel(params, x)
    odd = cnn.init_cnn(jax.random.key(0), num_classes=4, in_hw=(7, 7),
                       channels=(16, 8), fc1_dim=16)
    assert not calls_kernel(odd, x[:, :7, :7])  # odd H, W: the pool drops a row
    narrow = cnn.init_cnn(jax.random.key(0), num_classes=4, in_hw=(8, 8),
                          channels=(4, 8), fc1_dim=16)
    assert not calls_kernel(narrow, x)  # channels in part of a sublane tile
