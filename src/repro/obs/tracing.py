"""Profiler hooks: thin wrappers over ``jax.profiler`` (DESIGN.md §14).

Two context managers:

* :func:`trace` — one per run, wrapping the whole driver in
  ``jax.profiler.trace(dir)`` (TensorBoard-loadable); a ``None`` dir is a
  no-op so launchers can pass ``--profile-dir`` through unconditionally.
* :func:`annotate` — named host spans (``jax.profiler.TraceAnnotation``)
  around the hot boundaries: scan chunks, selection reprofiles, serve
  decode chunks and admissions.  Annotations are cheap enough to apply
  unconditionally — they only record when a trace is active.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

__all__ = ["annotate", "trace"]


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
