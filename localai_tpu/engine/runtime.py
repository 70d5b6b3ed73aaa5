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
  Each phase's time is also accounted to a cause (ISSUE 51): inside a jax
  call (`call`), in the collector on the loop's own thread, off the CPU,
  or Python's own; and the longest single stretch of a window is kept.
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

from localai_tpu.observe import gcwatch
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


# The phases in which the loop has nothing to do but wait: for work (`wait`)
# or for a device result (`pull`). The others are the working phases.
IDLE_PHASES = ("wait", "pull")

# A stretch of one working phase this long is journalled as its own
# `loop_stall` event. The busiest cell's phases are 2-56 ms a block (PERF.md
# section 6 "PR 43") and the pauses nobody could explain 290-680 ms (PR 46's
# dumps): 100 ms is above anything a block's work takes and well below those.
STALL_MS = 100.0

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

    def __init__(self, call):
        # thread: instance-owned — each stager belongs to one engine and
        # is touched only by that engine's loop thread (bench/tests read
        # the counters best-effort after the fact).
        self._cache: dict[str, _CtrlEntry] = {}
        # The door an upload goes through: the owning loop's
        # `LoopPhases.call`, so that the time inside jax is the call's.
        self._call = call
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
                with self._call("call/ctrl_upload",
                                bytes=int(rows.size) * host[0].nbytes):
                    dev = ent.dev.at[rows].set(jnp.asarray(host[rows]))
                    out = build(dev) if build is not None else dev
                self._cache[key] = _CtrlEntry(host.copy(), dev, out)
                self.row_uploads += 1
                return out
        kept = host.copy()
        with self._call("call/ctrl_upload", bytes=kept.nbytes):
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


class _Call:
    """One `LoopPhases.call`: the time from entering to leaving it is the
    call's, and while a capture runs it is the span `name` with `stats`."""

    __slots__ = ("ph", "name", "stats", "span", "mine", "t", "cpu", "gc")

    def __init__(self, ph, name, stats):
        self.ph = ph
        self.name = name
        self.stats = stats
        self.span = None

    def __enter__(self):
        ph = self.ph
        if ph._enabled():
            self.span = ph._annotate(self.name, **self.stats)
            self.span.__enter__()
        # Some of the engine's device calls are also reached from request
        # threads (a span export's page gather): there the call is its span
        # alone, the account is the loop thread's.
        self.mine = ph._owner is None or ph._owner == threading.get_ident()
        if self.mine:
            ph._depth += 1
            if ph._depth == 1:  # a call inside a call is the outer one's
                self.gc = ph.collector.ms
                self.cpu = ph._cpu()
                self.t = ph._wall()
        return self

    def __exit__(self, *exc):
        ph = self.ph
        if self.mine:
            if ph._depth == 1:
                ph._c_ms += (ph._wall() - self.t) * 1000.0
                ph._c_cpu += (ph._cpu() - self.cpu) * 1000.0
                ph._c_gc += ph.collector.ms - self.gc
            ph._depth -= 1
        if self.span is not None:
            self.span.__exit__(*exc)
        return False


class LoopPhases:
    """Per-phase host milliseconds of the engine loop, each accounted to a
    cause, and the same phases as spans on the profiler's clock.

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
    capture running `begin` costs a wall and a thread-CPU clock read and
    `is_enabled()`; no annotation object is made.

    Beside `ms[phase]`, a window keeps where the phase's time went
    (ISSUE 51). `call_ms`: between entering and leaving `call(name, ...)`,
    the one door for every call the loop thread makes into jax outside
    `pull`. `gc_ms`: in the collector on this thread, outside calls
    (`collector.ms`, written by `observe/gcwatch`; a pause that ended inside
    a call is the call's). `off_ms`: wall time less this thread's CPU time,
    outside calls: the loop had work and was not running, because another
    thread held the interpreter or the machine ran something else (the idle
    phases, which ask not to run, book none). It is a SIGNED sum: a kernel
    that charges CPU time by the timer tick gives one interval a tick too
    much and the next too little, and only their sum is right; a reader
    sums first and holds the sum at 0 or above, and a single stretch's part
    is good to a tick. The rest of a working phase is the loop thread
    running Python. `late_ms` / `late_max`: how much later
    than asked the loop's timed waits came back (`wait`, `sleep`), which
    lies in `wait` and `pull` and in none of the above.

    A stretch is the time spent in ONE working phase from a `begin` of it
    to the next `begin` of another (or to `end()`, or to `sync()`, the end of
    a loop iteration); beginning the running phase again does not end it.
    The window's longest is kept with its parts and what it did (`note`),
    and one of `STALL_MS` or more is also put on `stalls` for the loop to
    journal as `loop_stall`.

    `sync()` settles the running phase's time into `ms` without closing
    its span; `vector()`/`total()`/`causes()` read the window, `reset()`
    starts the next one.
    """

    __slots__ = ("names", "ms", "call_ms", "gc_ms", "off_ms", "late_ms",
                 "late_max", "longest", "stalls", "late_max_ever",
                 "stretch_max_ever", "stall_count", "iters", "collector",
                 "_mark", "_cpu_mark", "_gc_mark", "_cur", "_span",
                 "_span_t0", "_annotate", "_enabled", "_wall", "_cpu",
                 "_owner", "_depth", "_c_ms", "_c_cpu", "_c_gc", "_did_a",
                 "_did_b", "_long_ms", "_idle")

    def __init__(self, names=LOOP_PHASES, annotate=None, wall=None, cpu=None):
        if annotate is None:
            from jax.profiler import TraceAnnotation as annotate
        # thread: instance-owned — loop-thread state, read best-effort by
        # metrics/bench after generation completes.
        self.names = tuple(names)
        # thread: instance-owned — see above; the clocks and counters below
        # are written only by the owning engine's loop thread.
        self.ms = {n: 0.0 for n in self.names}
        # thread: instance-owned — see above.
        self.call_ms = {n: 0.0 for n in self.names}
        # thread: instance-owned — see above.
        self.gc_ms = {n: 0.0 for n in self.names}
        # thread: instance-owned — see above.
        self.off_ms = {n: 0.0 for n in self.names}
        # thread: instance-owned — see above.
        self.late_ms = 0.0
        # thread: instance-owned — see above.
        self.late_max = 0.0
        # thread: instance-owned — the window's longest stretch, or None:
        # [phase, ms, call ms, collector ms, off-CPU ms, did a, did b].
        self.longest = None
        # thread: instance-owned — stretches of STALL_MS and more that the
        # loop has not journalled yet (same seven values).
        self.stalls = []
        # thread: instance-owned — since start, for Engine.metrics().
        self.late_max_ever = 0.0
        # thread: instance-owned — see above.
        self.stretch_max_ever = 0.0
        # thread: instance-owned — see above.
        self.stall_count = 0
        # thread: instance-owned — see above.
        self.iters = 0
        # thread: instance-owned — the loop thread's collector total: the
        # loop registers it with `gcwatch.WATCH.enter` when it starts, and
        # the hook adds the pauses that ran on that thread.
        self.collector = gcwatch.LoopTotal()
        # thread: instance-owned — see above.
        self._mark = 0.0
        # thread: instance-owned — see above.
        self._cpu_mark = 0.0
        # thread: instance-owned — see above.
        self._gc_mark = 0.0
        # thread: instance-owned — the running phase and its open span.
        self._cur = None
        # thread: instance-owned — see above.
        self._span = None
        # thread: instance-owned — see above.
        self._span_t0 = 0.0
        self._annotate = annotate
        self._enabled = annotate.is_enabled
        self._wall = wall if wall is not None else time.monotonic
        self._cpu = cpu if cpu is not None else time.thread_time
        # thread: instance-owned — the loop thread's ident once a loop owns
        # this (`own`); None: whoever calls.
        self._owner = None
        # thread: instance-owned — calls open, and the wall, CPU and
        # collector ms spent inside calls since the last settle.
        self._depth = 0
        # thread: instance-owned — see above.
        self._c_ms = 0.0
        # thread: instance-owned — see above.
        self._c_cpu = 0.0
        # thread: instance-owned — see above.
        self._c_gc = 0.0
        # thread: instance-owned — `longest`'s ms (0: none yet).
        self._long_ms = 0.0
        # thread: instance-owned — what the running stretch did (`note`).
        self._did_a = 0.0
        # thread: instance-owned — see above.
        self._did_b = 0.0
        self._idle = frozenset(IDLE_PHASES)

    def begin(self, name: str) -> None:
        if name == self._cur:
            # Keep the span, unless it is a slice old; open one if a capture
            # started in the middle of the phase.
            if (self._span is None
                    or self._wall() - self._span_t0 >= SPAN_SLICE_S):
                self._close_span()
                self._open_span()
            return
        self.end()
        self._cur = name
        self._open_span()

    def own(self) -> None:
        """The calling thread is the loop this belongs to."""
        self._owner = threading.get_ident()

    def call(self, name: str, **stats) -> _Call:
        """Context manager around one call into jax: a dispatch, an upload,
        the start of a copy. Its time is booked to the running phase's
        `call_ms`; while a capture runs it is the span `name` with `stats`."""
        return _Call(self, name, stats)

    def note(self, a: float, b: float) -> None:
        """What the running stretch did: `process` counts tokens posted and
        requests finished, the other phases programs dispatched and rows."""
        self._did_a += a
        self._did_b += b

    def wait(self, event, timeout: float) -> bool:
        """`event.wait(timeout)`; a wait that came back by timeout books how
        much later than asked it did."""
        t0 = self._wall()
        if event.wait(timeout=timeout):
            return True
        self._late((self._wall() - t0 - timeout) * 1000.0)
        return False

    def sleep(self, seconds: float) -> None:
        t0 = self._wall()
        time.sleep(seconds)
        self._late((self._wall() - t0 - seconds) * 1000.0)

    def _late(self, ms: float) -> None:
        if ms > 0.0:
            self.late_ms += ms
            if ms > self.late_max:
                self.late_max = ms
                if ms > self.late_max_ever:
                    self.late_max_ever = ms

    def _open_span(self) -> None:
        if self._enabled():
            self._span = self._annotate("loop/" + self._cur)
            self._span.__enter__()
            self._span_t0 = self._wall()

    def _close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _settle(self) -> None:
        """Book the time since the last settle to the running phase and its
        causes; that time is one stretch of the phase."""
        now = self._wall()
        cpu = self._cpu()
        gc_all = self.collector.ms
        cur = self._cur
        if cur is not None:
            dt = (now - self._mark) * 1000.0
            self.ms[cur] += dt
            call = self._c_ms
            out = dt - call  # outside calls
            if call:
                self.call_ms[cur] += call
                if out < 0.0:
                    out = 0.0
            pause = gc_all - self._gc_mark - self._c_gc
            if pause > 0.0:
                if pause > out:
                    pause = out
                self.gc_ms[cur] += pause
            else:
                pause = 0.0
            if cur not in self._idle:
                # Signed: where the kernel charges a thread's CPU time by
                # the timer tick (10 ms on the chip's host) an interval
                # reads a tick too much or too little; the sum is right.
                off = out - ((cpu - self._cpu_mark) * 1000.0 - self._c_cpu)
                self.off_ms[cur] += off
                if off < 0.0:
                    off = 0.0
                elif off > out - pause:
                    off = out - pause
                if dt > self._long_ms or dt >= STALL_MS:
                    stretch = [cur, dt, call, pause, off,
                               self._did_a, self._did_b]
                    if dt > self._long_ms:
                        self._long_ms = dt
                        self.longest = stretch
                        if dt > self.stretch_max_ever:
                            self.stretch_max_ever = dt
                    if dt >= STALL_MS:
                        self.stall_count += 1
                        self.stalls.append(stretch)
            self._did_a = self._did_b = 0.0
        if self._c_ms:
            self._c_ms = self._c_cpu = self._c_gc = 0.0
        self._mark = now
        self._cpu_mark = cpu
        self._gc_mark = gc_all

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

    def working(self, per_phase: dict) -> float:
        """Sum of `call_ms`, `gc_ms` or `off_ms` over the working phases."""
        return sum(v for n, v in per_phase.items() if n not in self._idle)

    def extras(self, stall=None) -> list:
        """What rides a `loop_iter` event beside the vectors
        (`journal.LOOP_EXTRA`): the window's late wake-ups, sum and maximum,
        then its longest stretch: the phase's index (-1: none), its ms, call,
        collector and off-CPU ms and the two things it did. With `stall`, a
        `loop_stall`'s: that stretch, and no late wake-ups (they are the
        window's, not the stretch's)."""
        late = [0.0, 0.0] if stall else [self.late_ms, self.late_max]
        stretch = stall or self.longest
        if stretch is None:
            return late + [-1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]
        return late + [float(self.names.index(stretch[0]))] + stretch[1:]

    def causes(self) -> list:
        """The window's account as the `loop_iter` event carries it: a row
        each for call, collector and off-CPU ms, by phase."""
        names = self.names
        return [[self.call_ms[n] for n in names],
                [self.gc_ms[n] for n in names],
                [self.off_ms[n] for n in names]]

    def reset(self) -> None:
        for n in self.names:
            self.ms[n] = 0.0
            self.call_ms[n] = 0.0
            self.gc_ms[n] = 0.0
            self.off_ms[n] = 0.0
        self.late_ms = self.late_max = 0.0
        self.longest = None
        self._long_ms = 0.0
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
