"""Every Pallas kernel compiles for a TPU v5e at the widths the system runs.

Interpret mode cannot see the chip compiler's rules (the (8, 128) block
rule, no scalar stores to VMEM), so each kernel is compiled here with
``interpret=False`` for a described ``v5e:2x2`` topology: nothing runs, but
the compiler refuses what the chip would refuse.  Widths: the paper's
C = 100 clients with 128-wide profiles (and a ragged C = 1000), smollm-360m
decode and prefill attention (H=15, Hk=5, hd=64, S=2048, bf16), and the
rwkv6-7b WKV (64 heads of 64, T=512).

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker running this
file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.decode import flash_decode_kernel
from repro.kernels.flash_attention.flash_attention import flash_attention_kernel
from repro.kernels.gram.gram import gram_kernel, normalized_gram_kernel
from repro.kernels.pairwise_l2.pairwise_l2 import (
    pairwise_dists_stats_kernel,
    pairwise_sq_dists_kernel,
)
from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_kernel

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
C, F, C_RAGGED = 100, 128, 1000
B, S, H, HK, HD = 8, 2048, 15, 5, 64  # smollm-360m attention
RWKV_T, RWKV_H = 512, 64  # rwkv6-7b


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
