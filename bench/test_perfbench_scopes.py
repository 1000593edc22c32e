"""The program's own spans, scopes and compile markers, read from a trace
recorded here around a tiny cell's window: the ``fl.init`` phases, the
``fl.chunk`` span, the device time by ``fl.*`` scope (from the HLO the
trace stores), and the five readers that use them."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from bench import harness, scopes, trace
from bench.federations import Runner, derive_key
from bench.tiny import tiny

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("init_idle_ms", "init_dispatches", "compiles_per_fed", "local_update_ms",
           "dpp_draw_ms")


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """A traced window of the tiny paper-cnn.table1 cell (two federations
    in lockstep through ``run_many``)."""
    d = tmp_path_factory.mktemp("scopes")
    cell = tiny(harness.load_cell("paper-cnn.table1"))
    cfg = cell.cfg
    model = harness.load_module("models", cfg["model"])
    system = model.System(cfg, cell.traffic["strategy"])
    seed = 2**33 + 5
    runner = Runner(system, cfg, cell.traffic,
                    model.make_deployment(cfg, jax.random.key(derive_key(seed, 0))), seed)
    runner.warm_up()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        stats = runner.window(0.3)
    finally:
        jax.profiler.stop_trace()
    return {"dir": d, "cell": cell, "system": system, "runner": runner, "stats": stats,
            "trace": trace.load(str(d))}


def _ctx(window, monkeypatch, t=None):
    monkeypatch.setattr(scopes, "TRACE_DIR", window["dir"])
    cell = window["cell"]
    return harness.MetricContext(t or window["trace"], window["stats"],
                                 window["runner"].records, cell.cfg, cell.traffic,
                                 window["system"], PEAK)


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


def test_init_phases_nest_in_fl_init_and_cover_it(window):
    t = window["trace"]
    inits = t.span_intervals("fl.init")
    assert len(inits) == window["stats"]["attempted"] >= 2
    outer = t.span_intervals("bench.init")
    assert all(any(a <= s and e <= b for a, b in outer) for s, e in inits)
    kids = [(s, e, n) for s, e, n in t.spans if n.startswith("fl.init.")]
    assert {n for _, _, n in kids} >= {"fl.init.profiles", "fl.init.losses", "fl.init.kernel",
                                      "fl.init.spectral", "fl.init.label_dists"}
    for s, e in inits:
        inside = [(a, b) for a, b, _ in kids if s <= a < e]
        assert all(b <= e for _, b in inside)  # nested, not overlapping the end
        assert sum(b - a for a, b in trace.merge(inside)) >= 0.95 * (e - s)


def test_chunk_span_sits_inside_the_callers_span(window):
    t = window["trace"]
    chunks, callers = t.span_intervals("fl.chunk"), t.span_intervals("bench.chunk")
    assert len(chunks) == len(callers) >= 2
    assert all(a <= s and e <= b for (s, e), (a, b) in zip(chunks, callers))


def test_stored_hlo_matches_the_compiled_text(tmp_path):
    def fl_probe(x):
        with jax.named_scope("fl.select"):
            y = jnp.sort(x, axis=-1)
        with jax.named_scope("fl.local_update"):
            return jnp.tanh(y @ y.T).sum(axis=0)

    f = jax.jit(fl_probe)
    x = jnp.linspace(0.0, 1.0, 64).reshape(8, 8)
    compiled = f.lower(x).compile().as_text()
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    stored = scopes.hlo_op_names(open(trace.find_xplane(str(tmp_path)), "rb").read())
    want = dict(re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]+)"', compiled,
                           flags=re.M))
    assert want and any(scopes.scope_of(v) == "fl.select" for v in want.values())
    got = {name: op for (prog, name), (_, op) in stored.items() if prog == "jit_fl_probe"}
    assert {k: got.get(k) for k in want} == want


def test_scopes_name_the_rounds_stages(window):
    found = scopes.load(window["dir"])
    assert found.scoped()
    names = {sc for _, _, sc, _ in found.ops if sc}
    assert names >= {"fl.select", "fl.batches", "fl.local_update", "fl.aggregate",
                     "fl.loss_refresh", "fl.gemd"}
    lo, hi = window["trace"].window()
    assert found.seconds("fl.local_update", lo, hi) > found.seconds("fl.select", lo, hi) > 0
    assert found.seconds(None, lo, hi) <= window["trace"].busy_seconds(lo, hi) * (1 + 1e-9)


def test_readers_read_the_window(window, monkeypatch):
    ctx = _ctx(window, monkeypatch)
    got = {m: _read(m, ctx) for m in READERS}
    assert all(v is not None for v in got.values()), got
    stats, t = window["stats"], window["trace"]
    chunk_ms = 1e3 * t.busy_in_spans("bench.chunk") / stats["fed_rounds"]
    assert 0 < got["local_update_ms"] <= chunk_ms
    assert 0 < got["dpp_draw_ms"] < got["local_update_ms"]
    assert got["init_idle_ms"] > 0
    assert got["init_dispatches"] >= window["cell"].cfg["num_clients"]
    # a fresh jit(vmap(loss)) in every initialisation: one compile per
    # federation, marked inside its fl.init.losses span
    assert got["compiles_per_fed"] >= 1
    losses = t.span_intervals("fl.init.losses")
    found = scopes.of(ctx)
    assert sum(any(s <= m <= e for s, e in losses) for m in found.markers) >= stats["attempted"]


def test_a_count_of_none_reads_zero(window, monkeypatch):
    ctx = _ctx(window, monkeypatch)
    found = scopes.of(ctx)
    found.markers = []
    found.ops = [op for op in found.ops if op[2] != "fl.select"]
    assert _read("compiles_per_fed", ctx) == 0.0
    assert _read("dpp_draw_ms", ctx) == 0.0


def test_readers_read_nothing_without_the_programs_marks(tmp_path, monkeypatch):
    """A program without the spans, scopes and markers (as before them)
    gives these readers nothing to read."""
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.init"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.chunk"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    stats = {"attempted": 1, "fed_rounds": 5}
    ctx = harness.MetricContext(trace.load(str(tmp_path)), stats, [], {}, {}, None, PEAK)
    assert {m: _read(m, ctx) for m in READERS} == dict.fromkeys(READERS)
