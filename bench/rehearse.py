"""Compile a cell's programs at their real sizes for a described TPU v5e,
without the chip, and print each compiled program's memory analysis.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload paper-cnn.table1 --lockstep 8 16

Nothing runs: the TPU compiler refuses here what the chip would refuse
(a kernel's tiling, a program that does not fit), and says how many bytes
each program needs.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lockstep", type=int, nargs="*", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.core import dpp
    from repro.kernels.gram import ops as gram_ops
    from repro.models import cnn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cell = harness.load_cell(args.workload)
    cfg = cell.cfg
    system = harness.load_module("models", cfg["model"], cell.root).System(cfg, cell.traffic["strategy"])
    c, n = cfg["num_clients"], cfg["samples_per_client"]
    hw = tuple(cfg["image_hw"])
    f32 = jnp.float32

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    def report(name, fn, *shapes):
        compiled = jax.jit(fn).lower(*on_chip(shapes)).compile()
        m = compiled.memory_analysis()
        print(f"{name}: argument_bytes={m.argument_size_in_bytes} "
              f"output_bytes={m.output_size_in_bytes} temp_bytes={m.temp_size_in_bytes} "
              f"generated_code_bytes={m.generated_code_size_in_bytes}", flush=True)

    xs = jax.ShapeDtypeStruct((c, n) + hw + (1,), f32)
    ys = jax.ShapeDtypeStruct((c, n), jnp.int32)
    params = jax.eval_shape(lambda: system.init_params(jax.random.key(0)))
    report("profile_forward[one client]", cnn.apply_with_features, params,
           jax.ShapeDtypeStruct((n,) + hw + (1,), f32))
    report("eq14_kernel", gram_ops.kernel_from_profiles,
           jax.ShapeDtypeStruct((c, cfg["fc1_dim"]), f32))
    report("kdpp_sampler_state", lambda k: dpp.kdpp_sampler_state(k, cfg["clients_per_round"]),
           jax.ShapeDtypeStruct((c, c), f32))
    report("initial_losses",
           lambda p, x, y: jax.vmap(cnn.cnn_loss, in_axes=(None, 0, 0))(p, x, y),
           params, xs, ys)
    test = (jax.ShapeDtypeStruct((cfg["test_samples"],) + hw + (1,), f32),
            jax.ShapeDtypeStruct((cfg["test_samples"],), jnp.int32))
    report("accuracy", cnn.accuracy, params, *test)

    def build_state(x, y):
        p = system.init_params(jax.random.key(0))
        return system.init_state(p, jax.random.key(1), x, y)

    state = jax.eval_shape(build_state, xs, ys)
    chunk = int(cfg["eval_every"])
    for s in args.lockstep or [int(cell.traffic["lockstep"])]:
        if s == 1:
            report(f"run_scanned[{chunk} rounds]",
                   lambda st: jax.lax.scan(system.round_fn, st, None, length=chunk), state)
        else:
            stacked = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct((s,) + a.shape, a.dtype), state)
            report(f"run_many[S={s}, {chunk} rounds]",
                   jax.vmap(lambda st: jax.lax.scan(system.round_fn, st, None, length=chunk)),
                   stacked)
            report(f"accuracy[S={s}]", jax.vmap(cnn.accuracy, in_axes=(0, None, None)),
                   jax.tree_util.tree_map(
                       lambda a: jax.ShapeDtypeStruct((s,) + a.shape, a.dtype), params), *test)


if __name__ == "__main__":
    main()
