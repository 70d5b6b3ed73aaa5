"""On-demand jax.profiler capture windows (/debug/profile), ISSUE 11.

Gated behind LOCALAI_PROFILE (the capture output directory): profiling
allocates device trace buffers and perturbs serving, so it must be an
explicit operator opt-in, not a reachable default. One capture at a time —
jax.profiler keeps process-global state. This module is the declared
measurement point outside the trace-safety lint targets.
"""

from __future__ import annotations

import threading
import time

_capture_lock = threading.Lock()

MAX_SECONDS = 30.0


def trace_options(jax):
    """The tracer levels every capture of this repo uses (an operator's
    /debug/profile and the benchmark's traced run alike): host TraceMe
    annotations on, so the engine's `loop/<phase>` and `dispatch/<program>`
    spans are in the capture; the Python tracer off, which would record
    every call of the serving threads. {} on a jax without ProfileOptions."""
    try:
        opts = jax.profiler.ProfileOptions()
    except AttributeError:
        return {}
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return {"profiler_options": opts}


def capture(dirpath: str, seconds: float) -> dict:
    """Run one profiler capture window (blocking). Raises RuntimeError
    when a capture is already in flight or the profiler fails."""
    seconds = max(0.1, min(float(seconds), MAX_SECONDS))
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    try:
        import jax

        t0 = time.monotonic()
        jax.profiler.start_trace(dirpath, **trace_options(jax))
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return {
            "dir": dirpath,
            "seconds": round(time.monotonic() - t0, 3),
        }
    finally:
        _capture_lock.release()
