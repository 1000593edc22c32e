"""Every Pallas kernel compiles for a TPU v5e at the widths the system runs.

Interpret mode cannot see the chip compiler's rules (the (8, 128) block
rule, no scalar stores to VMEM), so each kernel is compiled here with
``interpret=False`` for a described ``v5e:2x2`` topology: nothing runs, but
the compiler refuses what the chip would refuse.  Widths: the paper's
C = 100 clients with 128-wide profiles (and a ragged C = 1000), smollm-360m
decode and prefill attention (H=15, Hk=5, hd=64, S=2048, bf16), the
rwkv6-7b WKV (64 heads of 64, T=512), and the paper CNN's first-stage
backward (8 lockstep federations × 16 channels, 600 samples of 28×28): alone,
at larger lockstep batches, inside the paper-scale local update (also with
clients vmapped inside the federations), and inside the federation's
one-device scan and its four-chip client mesh.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker running this
file loads the TPU library.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.conv_pool_bwd import ops as conv_pool_bwd_ops
from repro.kernels.conv_pool_bwd.conv_pool_bwd import conv_pool_bwd_kernel
from repro.kernels.flash_attention.decode import flash_decode_kernel
from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.gram.gram import gram_kernel, normalized_gram_kernel
from repro.kernels.pairwise_l2.pairwise_l2 import (
    pairwise_dists_stats_kernel,
    pairwise_sq_dists_kernel,
)
from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_kernel
from repro.models import cnn

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
C, F, C_RAGGED = 100, 128, 1000
B, S, H, HK, HD = 8, 2048, 15, 5, 64  # smollm-360m attention
RWKV_T, RWKV_H = 512, 64  # rwkv6-7b
# the paper CNN's local update in the benchmark: 8 lockstep federations,
# E = 2 full-batch steps over 600 samples, conv1 16 channels
LOCKSTEP, LOCAL_STEPS, SAMPLES, HW, CONV1 = 8, 2, 600, 28, 16
# the federation programs at paper widths with few clients, so the state
# builds quickly here: 8 clients, 4 a round, 2 per chip on the client mesh
FED_CLIENTS, FED_K = 8, 4


def _eq14_pipeline(f):
    """The fused profiles -> DPP-kernel pipeline, both launches compiled."""
    s0, lo, hi = pairwise_dists_stats_kernel(f, interpret=False)
    return normalized_gram_kernel(
        s0, lo, jnp.maximum(hi - lo, 1e-30), f.shape[0], interpret=False
    )


# name -> (function of the arguments, argument (shape, dtype) list)
CASES = {
    "pairwise_sq_dists": (
        lambda f: pairwise_sq_dists_kernel(f, interpret=False),
        [((C, F), F32)],
    ),
    "pairwise_dists_stats": (
        lambda f: pairwise_dists_stats_kernel(f, interpret=False),
        [((C, F), F32)],
    ),
    "gram": (lambda x: gram_kernel(x, interpret=False), [((C, C), F32)]),
    "normalized_gram": (
        lambda s0, lo, rng: normalized_gram_kernel(s0, lo, rng, C, interpret=False),
        [((128, 128), F32), ((), F32), ((), F32)],
    ),
    "eq14_pipeline_ragged": (_eq14_pipeline, [((C_RAGGED, F), F32)]),
    "flash_decode": (
        lambda q, k, v, n: flash_decode_kernel(q, k, v, n, interpret=False),
        [((B, 1, H, HD), BF16), ((B, S, HK, HD), BF16), ((B, S, HK, HD), BF16),
         ((B,), I32)],
    ),
    # the serving smoke's cache: prompt 128 + budget 64, read in 64-row tiles
    "flash_decode_serve_cache": (
        lambda q, k, v, n: flash_decode_kernel(q, k, v, n, interpret=False),
        [((B, 1, H, HD), BF16), ((B, 192, HK, HD), BF16), ((B, 192, HK, HD), BF16),
         ((B,), I32)],
    ),
    "flash_attention": (
        lambda q, k, v: flash_attention_kernel(q, k, v, interpret=False),
        [((1, S, H, HD), BF16), ((1, S, HK, HD), BF16), ((1, S, HK, HD), BF16)],
    ),
    # the lockstep batch's rows (federation, channel) in sublanes, samples in
    # lanes: a partial last lane block (600 = 4·128 + 88)
    "conv_pool_bwd": (
        lambda y, g, b, xp: conv_pool_bwd_kernel(y, g, b, xp, interpret=False),
        [((HW, HW, LOCKSTEP * CONV1, SAMPLES), F32),
         ((HW // 2, HW // 2, LOCKSTEP * CONV1, SAMPLES), F32),
         ((LOCKSTEP * CONV1, 1), F32), ((1, HW + 4, HW + 4, LOCKSTEP, 640), F32)],
    ),
    "wkv6": (
        lambda r, k, v, w, u, s0: wkv6_kernel(r, k, v, w, u, s0, interpret=False),
        [((1, RWKV_T, RWKV_H, HD), BF16)] * 3
        + [((1, RWKV_T, RWKV_H, HD), F32), ((RWKV_H, HD), F32),
           ((1, RWKV_H, HD, HD), F32)],
    ),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    entries compiled for a described chip cannot be read back without it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _local_update(params, xs, ys):
    """One client's E full-batch gradient steps of the paper CNN."""
    def step(p, batch):
        loss, g = jax.value_and_grad(cnn.cnn_loss)(p, *batch)
        return jax.tree_util.tree_map(lambda w, gw: w - 0.05 * gw, p, g), loss

    return jax.lax.scan(step, params, (xs, ys))


def _compile_local_update(one_chip, clients=None):
    """The local update vmapped over the lockstep federations and, with
    ``clients``, over that many clients inside each."""
    lead = (LOCKSTEP,) if clients is None else (LOCKSTEP, clients)
    fn = jax.vmap(_local_update) if clients is None else jax.vmap(jax.vmap(_local_update))
    params = jax.eval_shape(lambda: cnn.init_cnn(jax.random.key(0)))
    args = [
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype, sharding=one_chip), params),
        jax.ShapeDtypeStruct(lead + (LOCAL_STEPS, SAMPLES, HW, HW, 1), F32, sharding=one_chip),
        jax.ShapeDtypeStruct(lead + (LOCAL_STEPS, SAMPLES), I32, sharding=one_chip),
    ]
    return jax.jit(fn).lower(*args).compile()


def _ops(hlo: str, opcodes):
    """(opcode, dims) of every instruction of ``hlo`` with one of ``opcodes``."""
    pattern = re.compile(r"^\s+%\S+ = \w+\[([0-9,]*)\]\S* (" + "|".join(opcodes) + r")\(")
    return [(m.group(2), tuple(int(d) for d in m.group(1).split(",") if d))
            for m in map(pattern.match, hlo.splitlines()) if m]


@pytest.fixture
def kernel_engaged(monkeypatch):
    """The CNN's first stage differentiates through the compiled kernel, as
    on a TPU."""
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    monkeypatch.setattr(conv_pool_bwd_ops, "_interpret", lambda: False)


def _activation_copies(hlo: str, activation: int):
    return [dims for _, dims in _ops(hlo, ["copy", "copy-start", "transpose"])
            if math.prod(dims) == activation]


def _assert_one_kernel(hlo: str, activation: int, xla_hlo: str = ""):
    """Stage 1's select-and-scatter is gone (stage 2's, at 14×14, stays),
    the kernel is there, and it adds no copy of the stage-1 activation
    (``activation`` elements) to those of XLA's backward (``xla_hlo``)."""
    scatters = _ops(hlo, ["select-and-scatter"])
    assert all(HW not in dims for _, dims in scatters), scatters
    assert "tpu_custom_call" in hlo and "conv_pool_bwd_kernel" in hlo
    copies = _activation_copies(hlo, activation)
    assert len(copies) <= len(_activation_copies(xla_hlo, activation)), copies


def test_first_stage_backward_is_one_kernel_in_the_local_update(one_chip, monkeypatch):
    """The paper-scale local update, vmapped over the lockstep federations,
    with the first stage's backward engaged as on a TPU: stage 1's
    select-and-scatter is gone, the kernel is there, the conv output reaches
    it with no copy, and the program needs no more memory than with XLA's
    backward."""
    xla = _compile_local_update(one_chip)
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    monkeypatch.setattr(conv_pool_bwd_ops, "_interpret", lambda: False)
    kernel = _compile_local_update(one_chip)
    stage1 = (LOCKSTEP, SAMPLES, HW, HW, CONV1)

    assert ("select-and-scatter", stage1) in _ops(xla.as_text(), ["select-and-scatter"])
    _assert_one_kernel(kernel.as_text(), math.prod(stage1))
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= xla.memory_analysis().temp_size_in_bytes)


def test_first_stage_backward_with_clients_vmapped_inside_the_federations(
        one_chip, monkeypatch):
    """Two clients vmapped inside each of the 8 federations: the wrapper
    folds both axes into one lockstep batch of 16, and the kernel reads the
    conv output with no copy of its own (the forward's pool, under both
    backwards, copies it once to its own layout)."""
    xla = _compile_local_update(one_chip, clients=2)
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    monkeypatch.setattr(conv_pool_bwd_ops, "_interpret", lambda: False)
    kernel = _compile_local_update(one_chip, clients=2)
    _assert_one_kernel(kernel.as_text(), LOCKSTEP * 2 * SAMPLES * HW * HW * CONV1,
                       xla.as_text())


# lockstep federations, samples, channels: S = 13 runs 13 groups of one
# federation; S = 64 and S = 32 at FEMNIST's conv1 width run groups of 8
LARGER_BATCHES = {"S13": (13, SAMPLES, CONV1), "S64": (64, SAMPLES, CONV1),
                  "S32_C32": (32, 204, 32)}


@pytest.mark.parametrize("case", sorted(LARGER_BATCHES))
def test_first_stage_backward_compiles_for_larger_lockstep_batches(
        one_chip, kernel_engaged, case):
    """One kernel row block per group of up to 8 federations, so the VMEM a
    grid step asks for does not grow with S."""
    s, n, c = LARGER_BATCHES[case]
    spec = lambda *shape: jax.ShapeDtypeStruct((s,) + shape, F32, sharding=one_chip)
    fn = jax.vmap(lambda *a: conv_pool_bwd_ops.conv_pool_bwd(*a, (5, 5)))
    compiled = jax.jit(fn).lower(spec(n, HW, HW, c), spec(c), spec(n, HW, HW, 1),
                                 spec(n, HW // 2, HW // 2, c)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _federation(cohort_cap=None):
    """(cfg, strategy, initial state) of the paper's fl-dp3s federation at
    paper widths with FED_CLIENTS clients."""
    from repro.configs import paper_cnn
    from repro.core import make_strategy
    from repro.fl import engine

    exp = dataclasses.replace(paper_cnn.paper_scale(), num_clients=FED_CLIENTS,
                              clients_per_round=FED_K)
    cfg = dataclasses.replace(paper_cnn.fl_config(exp), cohort_cap=cohort_cap)
    kx, ky, kp = jax.random.split(jax.random.key(0), 3)
    xs = jax.random.uniform(kx, (FED_CLIENTS, SAMPLES, HW, HW, 1))
    ys = jax.random.randint(ky, (FED_CLIENTS, SAMPLES), 0, 10)
    params = cnn.init_cnn(kp, channels=exp.cnn_channels, fc1_dim=exp.fc1_dim)
    strategy = make_strategy("fl-dp3s")
    state = engine.init_server_state(cfg, params, cnn.cnn_loss, cnn.apply_with_features,
                                     xs, ys, strategy=strategy)
    return cfg, strategy, state


def _abstract(state, sharding_of):
    from repro.fl import engine

    return engine.ServerState(**{
        f.name: jax.tree_util.tree_map(
            lambda a, name=f.name: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                        sharding=sharding_of(name, a)),
            getattr(state, f.name))
        for f in dataclasses.fields(state)})


@pytest.mark.parametrize("path", ["one_federation", "client_mesh"])
def test_first_stage_backward_in_the_federation_programs(topo, one_chip, monkeypatch, path):
    """One round of the federation's scan with the kernel engaged: one
    federation on one chip (``run_scanned``, the kernel's S = 1 call), and
    the four-chip client mesh with capacity slots (``shard_map``, as
    ``chip_smoke.py --four-chips`` runs it), the state laid out as
    ``shard_server_state`` lays it."""
    from repro.fl import engine

    mesh = None
    if path == "client_mesh":
        cfg, strategy, state = _federation(cohort_cap=FED_K)
        mesh = Mesh(np.asarray(topo.devices), (engine.CLIENT_AXIS,))

        def sharding_of(name, a):
            spec = (engine.client_axis_spec(a.ndim, engine.CLIENT_AXIS)
                    if name in engine.CLIENT_SHARDED_FIELDS else P())
            return NamedSharding(mesh, spec)
    else:
        cfg, strategy, state = _federation()
        sharding_of = lambda name, a: one_chip
    monkeypatch.setattr(cnn, "_pallas_backward", lambda: True)
    monkeypatch.setattr(conv_pool_bwd_ops, "_interpret", lambda: False)
    round_fn = engine.make_round_fn(cfg, cnn.cnn_loss, (strategy,), accuracy_fn=cnn.accuracy,
                                    mesh=mesh)
    compiled = engine._scanned(round_fn, 1).lower(_abstract(state, sharding_of)).compile()
    hlo = compiled.as_text()
    scatters = _ops(hlo, ["select-and-scatter"])
    assert all(HW not in dims for _, dims in scatters), scatters
    assert "tpu_custom_call" in hlo and "conv_pool_bwd_kernel" in hlo
