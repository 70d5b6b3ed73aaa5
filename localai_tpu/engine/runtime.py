"""Pipelined engine-loop runtime helpers (ISSUE 17, docs/ENGINE_RUNTIME.md).

Three small host-side pieces keep jax async dispatch saturated without
touching program semantics:

- `ControlStager` — a dirty-diff cache for per-dispatch host→device
  control state. The loop's steady decode state barely changes between
  blocks (same sampling pack, same page table), yet the serial loop paid
  a fresh `jnp.asarray` per field per dispatch. The stager keys each
  control operand, compares the current host bytes against the last
  uploaded copy, and returns the cached device array on a match — the
  steady-state block issues at most ONE H2D control transfer (and zero
  when nothing changed). 2-D tables additionally take a row-diff partial
  upload when only a few rows moved (one slot grew its page row). Safe
  by construction: every cached operand is a NON-donated argument of the
  decode/spec programs (the donation-safety lint pins that), so reusing
  the same device array across dispatches is sound.
- `LoopPhases` — a monotonic phase accumulator
  (drain/purge/admit/prep/commit/dispatch/pull/process/housekeeping/wait)
  whose vector rides the `loop_iter` journal event, so loop overhead per
  block is attributable from the journal alone; while a profiler capture
  runs, each phase is also a `loop/<phase>` span on the capture's clock.
- `DeadlineIndex` — a lazy-deletion min-heap of absolute monotonic
  deadlines. Submit pushes each request's deadline / queue-timeout
  expiry; the loop's housekeeping tick asks "is anything due?" in O(1)
  instead of scanning every pending request every iteration.
"""

from __future__ import annotations

import heapq
import math
import threading
import time

import jax.numpy as jnp
import numpy as np

from localai_tpu.ops import ptable as pt

# Host-phase names for one loop iteration, in emit order. journal.py's
# LOOP_PHASES mirrors this tuple (import direction runs journal <- here so
# the observe layer stays engine-free).
LOOP_PHASES = (
    "drain",         # staged journal events moved into the ring
    "purge",         # pending purge + active-deadline enforcement
    "admit",         # admission (slot claim + prefill dispatch)
    "prep",          # control-plan build (pack/variant/growth/spec plan)
    "commit",        # H2D control commit (the one batched transfer)
    "dispatch",      # decode/spec block dispatch + chunk advance
    "pull",          # blocked on the device: an in-flight result's D2H pull
    "process",       # in-flight result processing (token posting)
    "housekeeping",  # budgeted sidecar tick (spill, deferred saves)
    "wait",          # idle / waiting on an in-flight block
)


# The longest a `loop/<phase>` span stays open before it is closed and opened
# again (LoopPhases). It is also the slice in which the loop waits for a
# device result, so that its `pull` phase is spans of this length.
SPAN_SLICE_S = 0.01


class _CtrlEntry:
    __slots__ = ("host", "dev", "out")

    def __init__(self, host, dev, out):
        self.host = host
        self.dev = dev
        self.out = out


class ControlStager:
    """Dirty-diff H2D commit cache for the engine loop's control operands.

    `commit(key, host)` returns a device array equal to `host`, uploading
    only when the host bytes changed since the last commit under the same
    key. An optional `build` hook derives the value actually handed to
    the program (views/casts of the uploaded array) — it runs only on
    upload, so derived views are cached too.
    """

    def __init__(self):
        # thread: instance-owned — each stager belongs to one engine and
        # is touched only by that engine's loop thread (bench/tests read
        # the counters best-effort after the fact).
        self._cache: dict[str, _CtrlEntry] = {}
        self.uploads = 0        # full-array H2D transfers issued
        self.row_uploads = 0    # partial (row-diff) transfers issued
        self.skips = 0          # commits satisfied entirely from cache
        self.commits = 0        # total commit() calls

    def commit(self, key: str, host: np.ndarray, build=None):
        """Device value for `host`, reusing the previous upload when the
        bytes are unchanged. What is uploaded is a private copy of `host`
        (the cache's own), so callers keep ownership and may mutate their
        array freely afterwards: `jnp.asarray` of a 64-byte-aligned numpy
        array is zero-copy on the CPU backend, and an upload may still be
        in flight elsewhere, while the engine rewrites a slot's table row
        the moment the block that shipped it is dispatched (Engine._park)."""
        self.commits += 1
        ent = self._cache.get(key)
        if (ent is not None and ent.host.shape == host.shape
                and ent.host.dtype == host.dtype):
            rows = pt.dirty_rows(ent.host, host)
            if rows.size == 0:
                self.skips += 1
                return ent.out
            if (host.ndim == 2 and 0 < rows.size <= max(1, host.shape[0] // 2)):
                # Few rows moved (a slot grew its page row): ship only
                # those rows. jnp's .at returns a NEW array — the old one
                # was never donated, so in-flight dispatches that captured
                # it keep reading consistent state.
                dev = ent.dev.at[rows].set(jnp.asarray(host[rows]))
                out = build(dev) if build is not None else dev
                self._cache[key] = _CtrlEntry(host.copy(), dev, out)
                self.row_uploads += 1
                return out
        kept = host.copy()
        dev = jnp.asarray(kept)
        out = build(dev) if build is not None else dev
        self._cache[key] = _CtrlEntry(kept, dev, out)
        self.uploads += 1
        return out

    def invalidate(self, key: str | None = None) -> None:
        """Drop one cached operand (or all of them) — the next commit
        re-uploads. Used when device state is rebuilt wholesale (model
        reload) rather than for ordinary staleness, which the byte diff
        already catches."""
        if key is None:
            self._cache.clear()
        else:
            self._cache.pop(key, None)

    def transfers(self) -> int:
        """Total H2D transfers issued (full + partial) — the probe the
        steady-state one-transfer-per-block test asserts on."""
        return self.uploads + self.row_uploads


class LoopPhases:
    """Per-phase host milliseconds of the engine loop, and the same phases
    as spans on the profiler's clock.

    The loop calls `begin(name)` where a phase starts; the phase runs until
    the next `begin` (or `end()`). Each phase is accumulated in `ms` (the
    vector that rides the coalesced `loop_iter` journal event) and, while a
    profiler capture is running, is one `loop/<name>` TraceAnnotation on the
    loop thread, so a device idle gap in the trace lies under the phase that
    caused it. Beginning the phase that is already running keeps its span:
    the loop spins at about 1 kHz while it waits, and consecutive `wait`s
    are one span, not a thousand. Only a span older than SPAN_SLICE_S is
    closed and opened again, because the profiler records a span when it
    ENDS: one that is open when a capture stops is lost whole, and one that
    began before the capture has no start. Slices bound both losses. With no
    capture running `begin` costs one clock read and `is_enabled()`; no
    annotation object is made.

    `sync()` settles the running phase's time into `ms` without closing
    its span; `vector()`/`total()` read `ms`, `reset()` starts the next
    window.
    """

    __slots__ = ("names", "ms", "iters", "_mark", "_cur", "_span",
                 "_span_t0", "_annotate", "_enabled")

    def __init__(self, names=LOOP_PHASES, annotate=None):
        if annotate is None:
            from jax.profiler import TraceAnnotation as annotate
        # thread: instance-owned — loop-thread state, read best-effort by
        # metrics/bench after generation completes.
        self.names = tuple(names)
        # thread: instance-owned — see above; the clock and counters below
        # are written only by the owning engine's loop thread.
        self.ms = {n: 0.0 for n in self.names}
        # thread: instance-owned — see above.
        self.iters = 0
        # thread: instance-owned — see above.
        self._mark = 0.0
        # thread: instance-owned — the running phase and its open span.
        self._cur = None
        # thread: instance-owned — see above.
        self._span = None
        # thread: instance-owned — see above.
        self._span_t0 = 0.0
        self._annotate = annotate
        self._enabled = annotate.is_enabled

    def begin(self, name: str) -> None:
        if name == self._cur:
            # Keep the span, unless it is a slice old; open one if a capture
            # started in the middle of the phase.
            if (self._span is None
                    or time.monotonic() - self._span_t0 >= SPAN_SLICE_S):
                self._close_span()
                self._open_span()
            return
        self.end()
        self._cur = name
        self._open_span()

    def _open_span(self) -> None:
        if self._enabled():
            self._span = self._annotate("loop/" + self._cur)
            self._span.__enter__()
            self._span_t0 = time.monotonic()

    def _close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _settle(self) -> None:
        now = time.monotonic()
        if self._cur is not None:
            self.ms[self._cur] += (now - self._mark) * 1000.0
        self._mark = now

    def end(self) -> None:
        """Close the running phase (loop exit, or before a new one)."""
        self._settle()
        self._cur = None
        self._close_span()

    def sync(self) -> None:
        self._settle()
        if self._cur is not None:
            self.begin(self._cur)  # slices the span, or opens it late

    def total(self, exclude: tuple = ("wait",)) -> float:
        return sum(v for n, v in self.ms.items() if n not in exclude)

    def vector(self) -> list:
        return [self.ms[n] for n in self.names]

    def reset(self) -> None:
        for n in self.names:
            self.ms[n] = 0.0
        self.iters = 0


class DeadlineIndex:
    """Lazy-deletion min-heap of absolute `time.monotonic()` deadlines.

    Submit-side threads push; the loop's housekeeping gate peeks. Entries
    are never individually removed — a deadline that resolved early
    (request finished, cancel) just pops as a no-op when it comes due, so
    `due()` may fire a tick with nothing to purge; the purge scan it
    triggers is the same one the serial loop ran every iteration.
    """

    def __init__(self):
        self._heap: list = []
        self._lock = threading.Lock()

    def push(self, t: float) -> None:
        with self._lock:
            heapq.heappush(self._heap, float(t))

    def next_due(self) -> float:
        with self._lock:
            return self._heap[0] if self._heap else math.inf

    def due(self, now: float) -> bool:
        """True when the earliest deadline has passed; pops every expired
        entry so the next peek is O(1) again."""
        with self._lock:
            if not self._heap or self._heap[0] > now:
                return False
            while self._heap and self._heap[0] <= now:
                heapq.heappop(self._heap)
            return True
