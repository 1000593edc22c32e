"""The benchmark's yardstick on the CPU: operation and byte counts against
hand counts, the peaks table, the trace reduction on a trace recorded
here, and the window's arithmetic."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import flops, trace
from bench.federations import FedRecord, Runner

TINY = {"image_hw": [8, 8], "channels": [2, 4], "fc1_dim": 3, "num_classes": 5,
        "clients_per_round": 2, "samples_per_client": 7, "local_epochs": 3,
        "test_samples": 11, "num_clients": 6, "eval_every": 5}


def test_cnn_counts_match_hand_counts():
    m = flops.cnn_layer_macs(TINY)
    # conv1: 8x8 outputs x 2 channels x 25 taps x 1 input channel
    assert m == {"conv1": 8 * 8 * 2 * 25, "conv2": 4 * 4 * 4 * 25 * 2,
                 "fc1": 2 * 2 * 4 * 3, "fc2": 3 * 5}
    fwd = 2 * (3200 + 3200 + 48 + 15)
    assert flops.cnn_forward_flops(TINY) == fwd
    assert flops.cnn_profile_flops(TINY) == 2 * (3200 + 3200 + 48)
    assert flops.cnn_train_flops(TINY) == 3 * fwd - 2 * 3200
    plain = 2 * 7 * 3 * flops.cnn_train_flops(TINY) + 2 * 7 * fwd
    assert flops.round_flops(TINY, False) == plain
    assert flops.round_flops(TINY, True) == plain + 11 * fwd
    per_round = lambda e: flops.round_flops(TINY, e)  # noqa: E731
    assert flops.rounds_flops(per_round, 5, 12) == 10 * plain + 2 * (plain + 11 * fwd)


def test_paper_cnn_round_is_about_a_quarter_teraflop():
    cfg = {"image_hw": [28, 28], "channels": [16, 32], "fc1_dim": 128, "num_classes": 10,
           "clients_per_round": 10, "samples_per_client": 600, "local_epochs": 2,
           "test_samples": 10000}
    assert flops.cnn_forward_flops(cfg) == 2 * 3_024_384
    assert 0.24e12 < flops.round_flops(cfg, False) < 0.25e12


def test_eq14_counts():
    k = flops.eq14_kernels(c=3, f=4)
    assert k["pairwise_dists_stats"] == {"flops": 2 * 9 * 4, "bytes": 4 * (12 + 9)}
    assert k["normalized_gram"] == {"flops": 2 * 27, "bytes": 4 * 18}
    cfg = dict(TINY)
    assert flops.init_flops(cfg) == 6 * 7 * (flops.cnn_profile_flops(cfg)
                                            + flops.cnn_forward_flops(cfg)) + 2 * 36 * 3 + 2 * 216


def test_peaks_table_is_keyed_by_device_kind():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(300, 20, peak) == 3.0  # compute-bound
    assert flops.roofline_seconds(100, 50, peak) == 5.0  # memory-bound


def test_interval_arithmetic():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    busy = [(0, 3), (5, 8)]
    assert trace.covered(busy, [(2, 6)]) == 2  # 2..3 and 5..6
    assert trace.covered(busy, [(-1, 10)]) == 6
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    tl = trace.Timeline([(0, 10, "outer"), (2, 4, "inner"), (6, 9, "other")])
    assert [tl.at(t) for t in (1, 3, 5, 7, 9.5, 11)] == ["outer", "inner", "outer",
                                                          "other", "outer", None]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace recorded here: two spans around jitted calls, and an idle
    stretch inside a third span."""
    import time

    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.chunk"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.init"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    return trace.load(str(d))


def test_trace_reduction_on_a_recorded_trace(cpu_trace):
    t = cpu_trace
    assert t.devices == ["/host:CPU"] and t.ops
    lo, hi = t.window()
    window_s = (hi - lo) / 1e9
    busy = t.busy_seconds(lo, hi)
    assert 0 < busy < window_s
    # every operation ran inside a chunk span; none in the idle init span
    assert t.busy_in_spans("bench.chunk") == pytest.approx(busy, rel=1e-6)
    assert t.busy_in_spans("bench.init") == 0
    assert t.modules_in_spans("bench.chunk") == ["jit__lambda"]
    assert t.module_seconds(["jit__lambda"])["jit__lambda"] >= busy * (1 - 1e-9)
    gaps = dict(t.idle_gaps(lo, hi))
    assert gaps["bench.init"] >= 0.045  # the sleep, attributed to its span
    assert sum(gaps.values()) == pytest.approx(window_s - busy, rel=1e-6)
    top = t.top_ops(lo, hi)
    assert top and all(name.startswith("jit__lambda:") for name, _ in top)


class _Clock:
    """A fake system whose chunks take known time: the window's arithmetic
    without the program."""

    def __init__(self, accs, chunk_s):
        self.accs, self.chunk_s, self.calls = list(accs), chunk_s, 0

    def init_params(self, key):
        return {"w": jnp.zeros(())}

    def init_state(self, params, key, xs, ys):
        return _State(params)

    def stack(self, states):
        return _State(jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[s.params for s in states]))

    def run_chunk(self, state, rounds, lockstep):
        import time

        time.sleep(self.chunk_s)
        sel = jnp.tile(jnp.arange(2), (rounds, 1))
        if lockstep > 1:
            sel = jnp.broadcast_to(sel, (lockstep,) + sel.shape)
        return state, {"selected": sel, "loss": jnp.ones(sel.shape[:-1])}

    def accuracy(self, params, xs, ys, lockstep):
        acc = self.accs[self.calls % len(self.accs)]
        self.calls += 1
        return jnp.full((lockstep,), acc) if lockstep > 1 else jnp.asarray(acc)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _State:
    params: dict
    kernel: jax.Array = dataclasses.field(default_factory=lambda: jnp.zeros((2, 2)))


def _runner(accs, chunk_s, lockstep=1, max_rounds=15):
    cfg = dict(TINY, num_clients=4, clients_per_round=2, eval_every=5,
               max_rounds=max_rounds, target_accuracy=0.5)
    data = (None, None, None, None)
    return Runner(_Clock(accs, chunk_s), cfg, {"lockstep": lockstep}, data, seed=3)


def test_window_holds_whole_federations_and_rates_use_the_measured_time():
    # each federation: chunk at 0.2 (miss), chunk at 0.9 (target): 10 rounds
    r = _runner([0.2, 0.9], chunk_s=0.02)
    r.warm_up()
    r.system.calls = 0
    stats = r.window(0.2)
    assert stats["attempted"] == stats["reached"] == len(r.records) >= 2
    assert all(rec.reached_at == 10 and rec.rounds_run == 10 for rec in r.records)
    # the window ran past its 0.2 s to finish the federation in flight
    assert stats["window_s"] >= max(0.2, 2 * 0.02 * stats["attempted"])
    assert stats["fed_rounds"] == 10 * stats["attempted"]
    assert stats["rounds_to_target"] == [10] * stats["attempted"]
    assert r.invalid_rounds == 0


def test_missed_targets_count_as_failed_and_lockstep_counts_every_member():
    r = _runner([0.1], chunk_s=0.05, lockstep=3, max_rounds=15)
    stats = r.window(0.0)  # no batch starts after 0 s: nothing runs
    assert stats["attempted"] == 0
    stats = r.window(0.1)  # one batch of three chunks outlasts the window
    assert stats["batches"] == 1 and stats["attempted"] == 3 and stats["failed"] == 3
    assert stats["fed_rounds"] == 3 * 15
    assert r.records == [FedRecord(0, s, None, 15) for s in range(3)]
