"""Run one cell of the benchmark on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Finds an accelerator with as many chips as the cell asks for, or exits
   non-zero and prints no result.  There is no CPU fallback.
2. Builds the cell from its files (``bench/configs``, ``bench/workloads``),
   has the configuration's adapter (``bench/models``) make the client data
   on the device from ``--seed``, and warms up the
   cell's programs through JAX's persistent compilation cache, kept in
   ``.jax_cache`` inside the checkout.  All of that is ``setup_s``.
3. Runs federations back to back for ``--seconds`` (whole federations: the
   last one started finishes), then replays a sample of them, drawn from
   the seed, with the plain reference to decide ``correct``.
4. Prints the result as the last line of standard output.  With
   ``--trace 0`` its metrics are the cell's end-to-end metrics; with
   ``--trace 1`` the window runs under the profiler and its metrics are the
   cell's per-layer metrics, read from the trace, with a ``breakdown`` of
   the device operations that took most time and the idle time by what the
   host was doing.  The numbers compared, each with its limit, come last
   in that line and as the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def _environment() -> None:
    """The compile cache lives in the checkout, whatever the machine sets,
    and caches every program, however quick to compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # a program over the default 192 MiB limit would compile anew in every run
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(4 << 30)
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def _fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(code)


def _devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: jax.devices()[0].platform = {devices[0].platform!r}")
    if len(devices) < chips:
        _fail(f"{len(devices)} chips, the cell needs {chips}")
    return devices[:chips]


class CompileCounter:
    """Counts backend compilations and persistent-cache loads."""

    def __init__(self):
        import jax

        self.compiles = self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.loads += 1


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _environment()
    from bench import harness  # noqa: E402  (needs the paths above)

    cell = harness.load_cell(args.workload)
    devices = _devices(int(cell.entry["chips"]))

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from bench import flops
    from bench.federations import Runner, derive_key

    cfg = cell.cfg
    peak = flops.peaks(devices[0].device_kind)
    counter = CompileCounter()
    model = harness.load_module("models", cfg["model"], cell.root)
    system = model.System(cfg, cell.traffic["strategy"])
    data = model.make_deployment(cfg, jax.random.key(derive_key(args.seed, 0)))
    runner = Runner(system, cfg, cell.traffic, data, args.seed)
    runner.warm_up()
    setup_s = time.perf_counter() - T_START
    compiles_setup = counter.compiles

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    c0 = counter.compiles
    try:
        stats = runner.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    compiles_window = counter.compiles - c0
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"attempted": stats["attempted"], "failed": stats["failed"]}
    if args.trace:
        from bench import trace as trace_lib

        trace = trace_lib.load(str(TRACE_DIR))
        ctx = harness.MetricContext(trace, stats, runner.records, cfg, cell.traffic,
                                    system, peak)
        lo, hi = ctx.span
        result["metrics"] = harness.read_metrics(cell, ctx)
        device["busy_s"] = trace.busy_seconds(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": trace.top_ops(lo, hi),
                               "idle_gaps": trace.idle_gaps(lo, hi)}
        print(json.dumps({"modules": {s: trace.modules_in_spans(s) for s in
                                      ("bench.init", "bench.chunk", "bench.eval")}}),
              file=sys.stderr)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        reached = max(1, stats["reached"])
        result["metrics"] = {
            "time_to_target_s": {"value": stats["window_s"] / reached, "unit": "s"},
            "rounds_per_s": {"value": stats["fed_rounds"] / stats["window_s"],
                             "unit": "rounds/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    t_check = time.perf_counter()
    correct, checks, each = harness.check_window(runner, cell, args.seed)
    print(json.dumps({
        "window": {k: stats[k] for k in ("window_s", "batches", "reached", "fed_rounds",
                                         "rounds_to_target")},
        "compiles": {"setup": compiles_setup, "window": compiles_window,
                     "cache_loads": counter.loads},
        "check_s": time.perf_counter() - t_check, "federations_checked": each,
    }), file=sys.stderr)
    out = {"correct": bool(correct), **result, "device": device, "checks": checks}
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    line = json.dumps({k: out[k] for k in order if k in out})
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {harness.fmt_limit(c['limit'])}",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)


if __name__ == "__main__":
    main()
