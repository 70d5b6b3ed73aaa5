"""What the collector costs the process, and the engine loop (ISSUE 51).

One `gc.callbacks` hook for the process, installed by the first engine loop
that starts (`enter`) and removed by the last that stops (`leave`). On
`"start"` it stamps `time.monotonic()`; on `"stop"` it adds the pause to the
counters below (all pauses, their total and maximum, the generation-2
ones), to the collecting thread's own total when that thread is a
registered engine loop (`LoopPhases` books the difference to the phase that
ended), and to a small preallocated ring `(t_end, generation, ms, collected,
thread)` that each loop drains into its journal as `gc_pause` events. While a
profiler capture runs the pause is also a `host/gc` span (stat `generation`)
on the thread that collected, so a device gap can be read as "under host/gc".

The callback runs on whichever thread's allocation tripped the collector, in
the middle of whatever that thread was doing, locks held and all. So it takes
no lock and builds no container: not `EventJournal.stage()` (a thread that
holds the sidecar lock and trips a collection would wait for itself), not
`append` (the ring there is the loop's alone). CPython runs one collection at
a time and keeps `collecting` set through both callbacks, so this module's
ring has one writer at any moment without a lock of its own.
"""

from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation

RING = 256  # pauses kept for the loops to drain; a loop drains every iteration

# Pauses shorter than this are counted only, never journalled (`drain`).
JOURNAL_MS = 1.0


class LoopTotal:
    """One engine loop thread's collector time: `ms` is written by the hook
    when the collection ran on that thread, read by `LoopPhases`."""

    __slots__ = ("ms",)

    def __init__(self):
        self.ms = 0.0


class _Watch:
    """The hook's state. One instance for the process (`WATCH`)."""

    def __init__(self):
        # thread: lock-guarded — `_lock` guards enter/leave alone (install,
        # remove, the registry of loops); the hook itself takes no lock.
        self._lock = threading.Lock()
        self._loops: dict[int, LoopTotal] = {}
        self._installed = False
        self._t_start = 0.0
        self._span = None
        # counters: written by the hook (one collection at a time), read
        # best-effort by Engine.metrics()
        self.pauses = 0
        self.pause_ms_total = 0.0
        self.pause_ms_max = 0.0
        self.gen2_pauses = 0
        # the ring: parallel preallocated lists, `n` pauses ever written
        self.n = 0
        self._t = [0.0] * RING
        self._gen = [0] * RING
        self._ms = [0.0] * RING
        self._collected = [0] * RING
        self._thread = [0] * RING

    # ---------------- the hook ---------------- #

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            if TraceAnnotation.is_enabled():
                self._span = TraceAnnotation("host/gc",
                                             generation=info["generation"])
                self._span.__enter__()
            self._t_start = time.monotonic()
            return
        now = time.monotonic()
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)
        ms = (now - self._t_start) * 1000.0
        gen = info["generation"]
        ident = threading.get_ident()
        self.pauses += 1
        self.pause_ms_total += ms
        if ms > self.pause_ms_max:
            self.pause_ms_max = ms
        if gen == 2:
            self.gen2_pauses += 1
        loop = self._loops.get(ident)
        if loop is not None:
            loop.ms += ms
        i = self.n % RING
        self._t[i] = now
        self._gen[i] = gen
        self._ms[i] = ms
        self._collected[i] = info["collected"]
        self._thread[i] = ident
        self.n += 1

    # ---------------- engines ---------------- #

    def enter(self, total: LoopTotal) -> None:
        """The calling thread is an engine loop from now on, and `total` (its
        `LoopPhases.collector`) takes the pauses that run on it; installs
        the hook if it is the first."""
        with self._lock:
            self._loops[threading.get_ident()] = total
            if not self._installed:
                gc.callbacks.append(self._on_gc)
                self._installed = True

    def leave(self) -> None:
        """The calling loop thread is done; the last one removes the hook."""
        with self._lock:
            self._loops.pop(threading.get_ident(), None)
            if not self._loops and self._installed:
                gc.callbacks.remove(self._on_gc)
                self._installed = False

    def drain(self, seen: int, emit) -> int:
        """Hand every pause of `JOURNAL_MS` and more written since `seen` to
        `emit(t_end, generation, ms, on_this_thread)`; returns the
        new `seen`. A loop calls it from its own thread with its own cursor,
        so several engines each journal every pause."""
        n = self.n
        if n == seen:
            return n
        me = threading.get_ident()
        for k in range(max(seen, n - RING), n):
            i = k % RING
            if self._ms[i] >= JOURNAL_MS:
                emit(self._t[i], self._gen[i], self._ms[i],
                     self._thread[i] == me)
        return n

    def recent(self) -> list[dict]:
        """The ring, oldest first, every pause however short (best-effort
        copy for a dump: `tools/cell_journal.py`)."""
        n = self.n
        return [{"t": self._t[k % RING], "generation": self._gen[k % RING],
                 "ms": self._ms[k % RING],
                 "collected": self._collected[k % RING],
                 "thread": self._thread[k % RING]}
                for k in range(max(0, n - RING), n)]

    def counters(self) -> dict[str, float]:
        return {
            "host_gc_pauses": float(self.pauses),
            "host_gc_pause_ms_total": float(self.pause_ms_total),
            "host_gc_pause_ms_max": float(self.pause_ms_max),
            "host_gc_gen2_pauses": float(self.gen2_pauses),
        }


WATCH = _Watch()
