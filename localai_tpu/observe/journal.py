"""Bounded ring-buffer event journal for the engine loop (ISSUE 11).

Design constraints, in order:

- The append path is called from the engine loop between decode-block
  dispatches, so it must be O(1), allocation-free, and never touch the
  device. Storage is a preallocated numpy structured array; appends write
  FIELD-WISE into fixed storage (no tuple/dict is built), and the only
  state change is a monotonically-growing sequence counter. The loop
  thread is the single writer — no lock on the hot path.
- Other threads DO emit lifecycle events (submit queues a request on a
  caller thread, span export runs on HTTP threads). Those `stage()` into a
  small locked sidecar list the loop thread drains at the top of each
  iteration (`drain_staged` — same idiom as the engine's span inbox), so
  the ring stays single-writer.
- Readers (`snapshot`) are best-effort: they copy the buffer and walk it
  by sequence number. A reader racing the writer can observe a freshly
  overwritten slot — acceptable for a flight recorder; the alternative is
  a lock on every append.

Event types are declared here (`EVENTS`); the fault subset
(`FAULT_EVENTS`) mirrors `localai_tpu.testing.faults.SITES` one-to-one and
the `journal-events` lint pass (tools/lint) checks BOTH directions, the
same contract the `fault-sites` pass enforces for `faults.fire()` calls.
"""

from __future__ import annotations

import threading
import time

import numpy as np

# Lifecycle + loop events. Order is the wire code (index), so append-only.
BASE_EVENTS = (
    "queued",        # request entered the pending queue (staged; rid)
    "admitted",      # slot claimed, admission program dispatched (slot, a=plen)
    "chunk",         # one mid prefill chunk dispatched (slot, a=tokens)
    "first_token",   # admission result produced the first token (slot)
    "decode_block",  # decode/spec block dispatched (a=block size, b=dispatch ms)
    "loop_iter",     # coalesced loop-iteration window (a=occupancy, b=host ms
    #                  spent this window outside the wait phase; the
    #                  per-phase host-ms breakdown rides the `phases` vector,
    #                  and where each phase's ms went `calls`, `gc`, `off`:
    #                  inside a jax call, in the collector on the loop's
    #                  thread, off the CPU; `late` is how late the loop's timed
    #                  waits came back, `longest` the window's longest stretch
    #                  of one working phase: engine/runtime.LoopPhases)
    "preempt",       # slot preempted for pool pressure (slot, a=ctx rows)
    "swap_out",      # preempt-swap image written to the host tier (a=bytes)
    "swap_in",       # swap resume restored pool pages (slot, a=bytes)
    "resume",        # recompute resume re-admitted (slot)
    "prefix_hit",    # admission mapped a cached span (slot, a=matched tokens)
    "span_export",   # prefix span framed for transfer (staged; a=tokens)
    "span_import",   # transfer frame merged into the host tier (a=tokens)
    "terminal",      # request finished (slot, a=completion tokens)
    "error",         # a dispatch failed; affected requests got error events
    "loop_dead",     # the engine loop died (postmortem follows)
    "profile",       # a jax.profiler capture window ran (a=seconds)
    "spec_draft",    # verify round dispatched (a=drafted tokens, b=window)
    "spec_verify",   # verify round processed (a=drafted, b=emitted tokens)
    "page_spill",    # cold middle pages copied to host, device pages freed
    #                  (slot, a=pages, b=bytes; docs/LONG_CONTEXT.md)
    "page_restore",  # spilled pages swapped back into fresh pool pages
    #                  (slot, a=pages, b=bytes)
    "forked",        # slot forked off a freshly-admitted sibling (slot=branch,
    #                  a=shared prompt/boundary rows, b=source slot;
    #                  docs/TREE_SAMPLING.md)
    "member_state",  # cluster replica lifecycle transition (staged; rid=
    #                  replica name, a=new state index, b=old state index —
    #                  indices into cluster.scheduler.MEMBER_STATES;
    #                  docs/CLUSTER.md "Membership lifecycle", ISSUE 19)
    "breaker_open",  # per-replica circuit breaker tripped open (staged;
    #                  rid=replica name, a=consecutive failures)
    "breaker_probe", # half-open breaker admitted its ONE probe call
    #                  (staged; rid=replica name, a=total probes) — chaos
    #                  runs assert ≤1 per half-open window from these
    "breaker_close", # breaker closed again after a successful probe
    #                  (staged; rid=replica name)
    "reroute_replay",# grammar-bearing request rerouted mid-stream: emitted
    #                  tokens replayed through a fresh grammar machine on
    #                  the survivor (staged; rid, a=replayed tokens,
    #                  b=reroute attempt number; docs/CLUSTER.md)
    "affinity_handoff",  # a draining/dead replica's span affinity moved to
    #                  a survivor instead of being dropped (staged; rid=
    #                  source replica, a=digests moved)
    "decode_first",  # the request's first token from a decode block was
    #                  posted (rid, slot; a=blocks in flight when it was
    #                  admitted): first_token -> decode_first is the wait
    #                  to join the decode stream
    "decode_rows",   # a decode or spec block's results were processed
    #                  (a=rows dispatched: steps x compiled batch rows,
    #                  b=rows that carried a token a handle received)
    "decode_rows_lost",  # the same block's other rows (a=overshoot: live at
    #                  dispatch, no token; b=empty: not live at dispatch);
    #                  a + b + decode_rows.b = decode_rows.a
    "moe_experts",   # a MoE model's decode block was processed (a=expert
    #                  slots offered: steps x MoE layers x experts, b=of
    #                  those, experts at least one compiled row chose)
    "moe_load",      # the same block's load (a=sum over step and layer of
    #                  the busiest expert's rows, b=sum of the mean rows per
    #                  expert: compiled rows x top-k / experts)
    "moe_here",      # the same block under an expert share (a=picks the
    #                  router made: steps x MoE layers x compiled rows x
    #                  top-k, b=of those, picks of an expert held here)
    "state_rows",    # a hybrid model's decode block was processed (a=rows of
    #                  the recurrent state it updated: steps x compiled rows x
    #                  KDA layers, b=of those, rows of a live tenant)
    "latent_rows",   # a decode block of an MLA model under the paged pool
    #                  was dispatched (a=latent rows its live slots held at
    #                  dispatch x its steps: what the block's page walks
    #                  read, its own rows ride in the window; b=the pool's
    #                  rows x its steps)
    "window_rows",   # a decode block of a model with window layers was
    #                  dispatched (a=rows one window layer's reader walks in
    #                  it: each live slot's rows at dispatch cut to the ring,
    #                  x its steps; b=the same at full length)
    "window_state",  # once, at start, a model with window layers (a=rows a
    #                  slot's ring holds in each window layer, b=bytes of
    #                  all slots' rings over all window layers)
    "kv_pool",       # once, at start, beside `window_state` or `s6_state`
    #                  (a=pages the KV manager hands out, b=the pool's bytes:
    #                  the full / attention layers' rows alone; over
    #                  (a + 1) x the page's rows, the bytes a token holds)
    "s6_state",      # once, at start, a model with S6 (Mamba-1) layers
    #                  (a=float32 values of a slot's state in ONE layer, its
    #                  shape [d_state, d_inner] as a product; b=bytes of one
    #                  slot's row over all S6 layers, state and conv inputs)
    "prefix_reuse_off",  # once, at start: prefix-span reuse was asked for
    #                  and is off (a hybrid model's prefix would need a
    #                  snapshot of its recurrent state; a=entries asked for)
    "slot_turnover", # one per `terminal` (rid, slot; a=1 when the slot index
    #                  had been handed on before the request's `done` was
    #                  posted, Engine._park, else 0; b=1)
    "admit_split",   # a hybrid model's admission group of one bucket went
    #                  out as several programs under the byte bound of
    #                  engine/state.py (a=programs, b=requests of the group)
    "admit_rows",    # one admission program was dispatched (a=rows it was
    #                  compiled for: group size x bucket, or a chunk's or a
    #                  cached tail's own rows; b=prompt tokens in them)
    "moe_admit_rows",  # an admission program under an expert share came
    #                  back whose expert matmuls took the grouped kernel
    #                  (a=(row, pick) pairs it was compiled for: rows x
    #                  top-k x MoE layers, b=of those, the rows in a held
    #                  expert's group, the ones the kernel visits)
    "loop_stall",    # one stretch of a working phase took runtime.STALL_MS or
    #                  more (a=index into LOOP_PHASES, b=its ms; `stretch`
    #                  holds its call, collector and off-CPU ms and what it
    #                  did: process tokens posted and requests finished, else
    #                  programs dispatched and rows)
    "gc_pause",      # a collection of 1 ms or more ended at `t`, anywhere in
    #                  the process (a=generation, b=ms; slot 0 when it ran on
    #                  this engine's loop thread, -1 elsewhere;
    #                  observe/gcwatch.py)
)

# One journal event type per fault-injection site (faults.SITES), checked
# both directions by the journal-events lint pass: a site added without an
# event type (or vice versa) is a finding. Literal on purpose — the check
# is AST-level, like fault-sites.
FAULT_EVENTS = (
    "fault_device_dispatch",
    "fault_engine_loop",
    "fault_page_alloc",
    "fault_host_swap",
    "fault_manager_load",
    "fault_cluster_dispatch",
    "fault_span_transfer",
    "fault_host_partition",
    "fault_slow_network",
    "fault_collective_dispatch",
    "fault_adapter_fetch",
    "fault_spec_verify",
    "fault_page_spill",
    "fault_control_commit",
    "fault_slot_fork",
    "fault_gauge_scrape",
)

EVENTS = BASE_EVENTS + FAULT_EVENTS
CODES = {name: i for i, name in enumerate(EVENTS)}
_WITH_EXTRA = (CODES["loop_iter"], CODES["loop_stall"])

# Host-phase names for one loop_iter window (engine/runtime.LOOP_PHASES is
# the writer-side source; this copy keeps the observe layer engine-free and
# a unit test pins the two tuples equal). The per-event `ph` vector stores
# milliseconds per phase in this order.
LOOP_PHASES = (
    "drain", "purge", "admit", "prep", "commit", "dispatch", "pull",
    "process", "housekeeping", "wait",
)

# What a `loop_iter` / `loop_stall` event carries beside its vectors, in the
# order LoopPhases.extras() writes it: the window's late wake-ups (sum and
# maximum; loop_iter only), then one stretch: its phase (index into
# LOOP_PHASES, -1 = none), its ms, the call, collector and off-CPU ms inside
# it, and the two things it did.
LOOP_EXTRA = ("late_ms", "late_max", "phase", "ms", "call", "gc", "off",
              "did_a", "did_b")
_CAUSES = ("calls", "gc", "off")

_DTYPE = np.dtype([
    ("t", np.float64),      # time.monotonic() at emit
    ("code", np.int16),     # index into EVENTS
    ("slot", np.int16),     # engine slot, -1 = engine-wide
    ("a", np.float64),      # event-specific scalar (see EVENTS comments)
    ("b", np.float64),      # second event-specific scalar
    ("rid", "U40"),         # request id (empty for engine-wide events)
    ("ph", np.float32, (len(LOOP_PHASES),)),  # loop_iter host-phase ms
    # loop_iter: of those ms, per phase, in calls / collector / off the CPU.
    # Written and read for loop_iter alone; another event's slot keeps
    # whatever was there.
    ("causes", np.float32, (len(_CAUSES), len(LOOP_PHASES))),
    ("extra", np.float32, (len(LOOP_EXTRA),)),  # loop_iter, loop_stall
])


_STAGED_CAP = 1024


def _read_account(rec, d: dict) -> None:
    """A loop_iter's `calls` / `gc` / `off` (ms by phase), `late` and
    `longest`, or a loop_stall's `stretch`, into the snapshot's dict."""
    x = dict(zip(LOOP_EXTRA, (float(v) for v in rec["extra"])))
    stretch = None
    if x["phase"] >= 0:
        stretch = {"phase": LOOP_PHASES[int(x["phase"])], "ms": x["ms"],
                   "call": x["call"], "gc": x["gc"], "off": x["off"],
                   "did": [x["did_a"], x["did_b"]]}
    if d["event"] == "loop_stall":
        d["stretch"] = stretch
        return
    for name, row in zip(_CAUSES, rec["causes"]):
        d[name] = {LOOP_PHASES[k]: float(v) for k, v in enumerate(row) if v}
    d["late"] = {"ms": x["late_ms"], "max": x["late_max"]}
    d["longest"] = stretch


class EventJournal:
    """Fixed-capacity ring of typed events. Single writer (the engine
    loop); `stage()` is the cross-thread entry point."""

    def __init__(self, capacity: int = 4096):
        self.capacity = max(int(capacity), 8)
        # thread: single-writer engine-loop — the ring is written by the
        # loop thread alone (appends + staged drain); snapshot() readers
        # are deliberately best-effort (may see a freshly overwritten slot)
        self._buf = np.zeros(self.capacity, dtype=_DTYPE)
        self.n = 0  # total events ever appended (monotonic sequence)
        self._staged: list[tuple] = []
        self._staged_lock = threading.Lock()
        self.dropped_staged = 0
        # Wall-clock anchor so exports can place monotonic stamps in time.
        self.t0_mono = time.monotonic()
        self.t0_wall = time.time()

    # ---------------- write side ---------------- #

    # thread: engine-loop-only
    def append(self, event: str, rid: str = "", slot: int = -1,
               a: float = 0.0, b: float = 0.0, phases=None, causes=None,
               extra=None) -> None:
        """Writer-thread append: O(1), no allocation, no lock, no device.
        The `# thread:` declaration makes the single-writer convention
        machine-checked (thread-affinity lint pass): any call chain from a
        non-loop root is a finding — cross-thread emitters use stage().
        `phases` (loop_iter only) is a LOOP_PHASES-ordered ms sequence,
        `causes` (loop_iter only) LoopPhases.causes(), `extra` (loop_iter and
        loop_stall) LoopPhases.extras()."""
        self._append_raw(time.monotonic(), event, rid, slot, a, b, phases,
                         causes, extra)

    # thread: engine-loop-only
    def append_at(self, t: float, event: str, slot: int = -1,
                  a: float = 0.0, b: float = 0.0) -> None:
        """Writer-thread append of something that ended at `t`, before now
        (a collector pause the loop drained from gcwatch's ring)."""
        self._append_raw(t, event, "", slot, a, b)

    def _append_raw(self, t: float, event: str, rid: str, slot: int,
                    a: float, b: float, phases=None, causes=None,
                    extra=None) -> None:
        i = self.n % self.capacity
        buf = self._buf
        buf["t"][i] = t
        buf["code"][i] = CODES[event]
        buf["slot"][i] = slot
        buf["a"][i] = a
        buf["b"][i] = b
        buf["rid"][i] = rid
        buf["ph"][i] = phases if phases is not None else 0.0
        if extra is not None:
            buf["extra"][i] = extra
            buf["causes"][i] = causes if causes is not None else 0.0
        self.n += 1

    def stage(self, event: str, rid: str = "", slot: int = -1,
              a: float = 0.0, b: float = 0.0) -> None:
        """Cross-thread emit: park the event for the writer thread to
        append in order. Bounded — a stalled writer drops (and counts)
        staged events instead of growing without limit."""
        rec = (time.monotonic(), event, rid, slot, a, b)
        with self._staged_lock:
            if len(self._staged) >= _STAGED_CAP:
                self.dropped_staged += 1
                return
            self._staged.append(rec)

    # thread: engine-loop-only
    def staged(self) -> bool:
        """Anything waiting for `drain_staged`? Unlocked peek (len() is
        atomic in CPython); the loop asks every iteration."""
        return bool(self._staged)

    def drain_staged(self) -> None:
        """Writer thread: move staged events into the ring (original
        timestamps preserved)."""
        if not self._staged:  # unlocked peek — len() is atomic in CPython
            return
        with self._staged_lock:
            staged, self._staged = self._staged, []
        for rec in staged:
            self._append_raw(*rec)

    # ---------------- read side ---------------- #

    def snapshot(self, last: int | None = None) -> list[dict]:
        """Best-effort ordered copy of the retained events (ring tail +
        currently staged), oldest first. Safe from any thread."""
        n = self.n
        buf = self._buf.copy()
        start = max(0, n - self.capacity)
        out = []
        for seq in range(start, n):
            rec = buf[seq % self.capacity]
            d = {
                "seq": seq,
                "t": float(rec["t"]),
                "event": EVENTS[int(rec["code"])],
                "slot": int(rec["slot"]),
                "a": float(rec["a"]),
                "b": float(rec["b"]),
                "rid": str(rec["rid"]),
            }
            ph = rec["ph"]
            if ph.any():
                d["phases"] = {LOOP_PHASES[k]: float(v)
                               for k, v in enumerate(ph) if v}
            if rec["code"] in _WITH_EXTRA:
                _read_account(rec, d)
            out.append(d)
        with self._staged_lock:
            staged = list(self._staged)
        for t, event, rid, slot, a, b in staged:
            out.append({
                "seq": -1, "t": float(t), "event": event, "slot": int(slot),
                "a": float(a), "b": float(b), "rid": str(rid),
            })
        out.sort(key=lambda e: e["t"])
        if last is not None and last >= 0:
            out = out[-last:]
        return out
