"""repro.obs — structured telemetry for both engines (DESIGN.md §14).

Three layers, strictly separated so nothing here ever changes a compiled
program:

* :mod:`repro.obs.telemetry` — the jit side: a :class:`Telemetry` pytree of
  per-round diagnostics that rides the federation scan outputs when (and
  only when) ``FLConfig.telemetry`` is set.  The flag is static, so
  ``telemetry=False`` configs lower bit-identical XLA programs — the same
  convention faults / funnel / staleness follow.
* :mod:`repro.obs.sink` — the host side: a JSONL event emitter
  (:class:`TelemetrySink`) plus the run manifest (config dict + stable
  hash, jax/device/mesh info, git SHA).  Events are drained at scan-chunk /
  admit / harvest boundaries only — never from inside a scan body.
* :mod:`repro.obs.tracing` — thin ``jax.profiler`` wrappers
  (:func:`trace`, :func:`annotate`) with no-op fallbacks, so profiler
  support costs nothing when no trace is active, and the ``obs.compile``
  marker recorded at every compile or compile-cache load.

This package depends only on jax/numpy/stdlib — ``fl/`` and ``serve/``
import it, never the reverse.
"""

from repro.obs.sink import (
    TelemetrySink,
    config_hash,
    drain_fl_outputs,
    load_events,
    run_manifest,
)
from repro.obs.telemetry import Telemetry, round_telemetry
from repro.obs.tracing import annotate, trace

__all__ = [
    "Telemetry",
    "TelemetrySink",
    "annotate",
    "config_hash",
    "drain_fl_outputs",
    "load_events",
    "round_telemetry",
    "trace",
]
