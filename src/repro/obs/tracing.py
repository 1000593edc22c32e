"""Profiler hooks: thin wrappers over ``jax.profiler`` (DESIGN.md §14).

Two context managers:

* :func:`trace` — one per run, wrapping the whole driver in
  ``jax.profiler.trace(dir)`` (TensorBoard-loadable); a ``None`` dir is a
  no-op so launchers can pass ``--profile-dir`` through unconditionally.
* :func:`annotate` — named host spans (``jax.profiler.TraceAnnotation``)
  around the hot boundaries: federation initialisation and its phases,
  scan chunks, selection reprofiles, serve decode chunks and admissions.
  Annotations are cheap enough to apply unconditionally — they only record
  when a trace is active.

And one marker: importing this module registers a ``jax.monitoring``
listener that records an :data:`COMPILE_MARKER` annotation each time JAX
compiles a program or loads one from the persistent compilation cache.
The listener runs on the compiling thread right after the compile, so the
marker falls inside the span of the step that compiled.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

__all__ = ["annotate", "trace", "COMPILE_MARKER"]

COMPILE_MARKER = "obs.compile"
# JAX times a backend compile and a persistent-cache load under this one
# event (the cache's own retrieval event fires inside the same call, so
# listening to it as well would mark every load twice)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def trace(profile_dir: Optional[str]) -> Iterator[None]:
    """Profile the enclosed block into ``profile_dir`` (no-op when None)."""
    if not profile_dir:
        yield
        return
    with jax.profiler.trace(str(profile_dir)):
        yield


def annotate(name: str):
    """A named profiler span."""
    return jax.profiler.TraceAnnotation(name)


def _mark_compile(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        with annotate(COMPILE_MARKER):
            pass


jax.monitoring.register_event_duration_secs_listener(_mark_compile)
