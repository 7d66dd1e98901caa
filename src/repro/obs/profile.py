"""Optional ``jax.profiler`` trace capture for benchmark runs.

``maybe_trace(None)`` is a free no-op, so callers can thread the
``--profile DIR`` flag straight through.  Traces are viewable with
TensorBoard / Perfetto (see README "Observability").  A profiler that
cannot start or stop raises: a run asked to trace must not finish
silently without its trace.
"""
from __future__ import annotations

import contextlib
import os


@contextlib.contextmanager
def maybe_trace(trace_dir: str | None):
    """Capture a jax.profiler trace into ``trace_dir`` if given."""
    if not trace_dir:
        yield None
        return
    import jax
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()
