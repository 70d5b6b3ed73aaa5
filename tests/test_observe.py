"""Request-lifecycle tracing + flight recorder (ISSUE 11,
docs/OBSERVABILITY.md).

The acceptance contract pinned here:

- an end-to-end paged request (chunked admit, ≥1 preempt/resume, streamed
  output) yields a /debug/trace span tree whose phase durations sum to
  within 5% of measured wall time;
- every lifecycle — cancel, deadline expiry, queue shed, injected
  engine_loop death — produces a COMPLETE trace ending in exactly one
  terminal event;
- /debug/timeline emits valid Chrome trace-event JSON (Perfetto-loadable
  shape);
- an injected engine_loop fault produces a postmortem file containing the
  dying request's journal tail;
- journal-on vs journal-off decode stays within noise.
"""

import gc
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest
import yaml

from localai_tpu.config import ApplicationConfig
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.observe import gcwatch
from localai_tpu.observe import journal as ojournal
from localai_tpu.observe import timeline as otimeline
from localai_tpu.observe import trace as otrace
from localai_tpu.observe.journal import EventJournal
from localai_tpu.observe.trace import STORE, RequestTrace
from localai_tpu.testing import faults

PAGE = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


def _mk_engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=2, max_seq=128, min_prefill_bucket=16)
    defaults.update(kw)
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


def _drain(handle):
    evs = list(handle)
    assert evs, "empty stream"
    assert evs[-1].kind in ("done", "error"), evs
    return evs


def _one_leg(rid):
    legs = STORE.get(rid)
    assert legs, f"no trace recorded for {rid}"
    return legs[-1]


def _assert_complete(leg):
    j = leg.to_json()
    assert j["complete"], j
    assert j["terminal_events"] == 1, j
    assert j["events"][0]["name"] == "queued", j
    assert j["events"][-1]["name"] == "terminal", j
    # Spans tile the leg: durations sum to wall_ms exactly (float noise).
    span_sum = sum(s["duration_ms"] for s in j["spans"])
    assert abs(span_sum - j["wall_ms"]) < 1.0, (span_sum, j["wall_ms"])
    return j


# --------------------------------------------------------------------- #
# Journal unit behavior
# --------------------------------------------------------------------- #


def test_journal_ring_bounds_and_order():
    j = EventJournal(16)
    for i in range(40):
        j.append("decode_block", slot=i % 4, a=float(i))
    snap = j.snapshot()
    assert len(snap) == 16  # bounded by capacity
    assert [e["a"] for e in snap] == [float(i) for i in range(24, 40)]
    assert [e["seq"] for e in snap] == list(range(24, 40))
    assert j.n == 40
    # Tail slicing.
    assert [e["a"] for e in j.snapshot(last=4)] == [36.0, 37.0, 38.0, 39.0]


def test_journal_staged_cross_thread_events():
    j = EventJournal(64)

    def producer():
        for _ in range(20):
            j.stage("queued", rid="r1")

    ts = [threading.Thread(target=producer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # Staged events are visible to snapshot even before the drain...
    assert sum(1 for e in j.snapshot() if e["event"] == "queued") == 80
    # ...and the writer thread folds them into the ring in order.
    j.drain_staged()
    assert j.n == 64 or j.n == 80  # ring keeps the tail; n counts all
    assert j.n == 80
    assert not j._staged


def test_journal_staged_bounded():
    j = EventJournal(8)
    for _ in range(ojournal._STAGED_CAP + 10):
        j.stage("queued")
    assert j.dropped_staged == 10


def _loop_account(j, t=None):
    """One loop_iter window with every new field set, one loop_stall and one
    gc_pause, as the engine loop writes them."""
    n = len(ojournal.LOOP_PHASES)
    phases = [0.0] * n
    phases[ojournal.LOOP_PHASES.index("commit")] = 40.0
    phases[ojournal.LOOP_PHASES.index("process")] = 150.0
    causes = [[0.0] * n for _ in range(3)]
    causes[0][ojournal.LOOP_PHASES.index("commit")] = 30.0   # calls
    causes[1][ojournal.LOOP_PHASES.index("process")] = 120.0  # collector
    causes[2][ojournal.LOOP_PHASES.index("commit")] = 4.0    # off the CPU
    stretch = [float(ojournal.LOOP_PHASES.index("process")), 150.0, 0.0,
               120.0, 0.0, 48.0, 2.0]
    j.append("loop_stall", a=stretch[0], b=150.0, extra=[0.0, 0.0] + stretch)
    j.append("loop_iter", a=3.0, b=190.0, phases=phases, causes=causes,
             extra=[7.0, 5.0] + stretch)
    j.append_at(j.t0_mono + 0.5, "gc_pause", slot=0, a=2.0, b=120.0)


def test_loop_iter_carries_where_each_phases_ms_went():
    j = EventJournal(16)
    j.append("decode_block", a=16.0, b=1.0)
    _loop_account(j)
    snap = {e["event"]: e for e in j.snapshot()}
    it = snap["loop_iter"]
    assert it["phases"] == {"commit": 40.0, "process": 150.0}
    assert it["calls"] == {"commit": 30.0}
    assert it["gc"] == {"process": 120.0}
    assert it["off"] == {"commit": 4.0}
    assert it["late"] == {"ms": 7.0, "max": 5.0}
    want = {"phase": "process", "ms": 150.0, "call": 0.0, "gc": 120.0,
            "off": 0.0, "did": [48.0, 2.0]}
    assert it["longest"] == want
    assert snap["loop_stall"]["stretch"] == want
    assert snap["loop_stall"]["b"] == 150.0
    assert (snap["gc_pause"]["slot"], snap["gc_pause"]["a"]) == (0, 2.0)
    # another event's slot of the ring says nothing about the loop
    assert not {"calls", "longest", "stretch"} & set(snap["decode_block"])
    # a window in which no stretch ended
    j.append("loop_iter", phases=[0.0] * 9 + [30.0], causes=None,
             extra=[0.0, 0.0, -1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    quiet = max(j.snapshot(), key=lambda e: e["seq"])
    assert quiet["longest"] is None and quiet["calls"] == {}
    # the two events are the last of BASE_EVENTS: wire codes are append-only
    assert ojournal.BASE_EVENTS[-2:] == ("loop_stall", "gc_pause")


def test_timeline_draws_a_stall_and_a_pause_as_durations():
    j = EventJournal(16)
    _loop_account(j)
    evs = {e["name"]: e for e in otimeline.chrome_trace({"e": j})["traceEvents"]
           if e.get("cat") == "engine"}
    pause = evs["gc_pause"]
    assert pause["ph"] == "X" and pause["dur"] == pytest.approx(120e3)
    assert pause["ts"] + pause["dur"] == pytest.approx(0.5e6)  # ends at its t
    stall = evs["loop_stall"]
    assert stall["ph"] == "X" and stall["dur"] == pytest.approx(150e3)
    assert stall["args"]["stretch"]["did"] == [48.0, 2.0]
    assert evs["loop_iter"]["args"]["gc"] == {"process": 120.0}
    assert evs["loop_iter"]["args"]["longest"]["phase"] == "process"


def test_a_collection_under_the_sidecar_lock_returns_and_is_journalled(
        monkeypatch):
    """The hook runs on whichever thread tripped the collector, under
    whatever that thread holds: here the journal's sidecar lock, which
    stage() would wait for forever."""
    monkeypatch.setattr(gcwatch, "JOURNAL_MS", 0.0)
    watch = gcwatch.WATCH
    j = EventJournal(64)
    before = watch.counters()
    total = gcwatch.LoopTotal()
    watch.enter(total)  # this thread stands in for an engine loop
    try:
        seen = watch.n

        def collect_under_the_lock():
            with j._staged_lock:
                gc.collect()

        t = threading.Thread(target=collect_under_the_lock)
        t.start()
        t.join(timeout=60.0)
        assert not t.is_alive()
        gc.collect()  # and one on the "loop" thread itself
        after = watch.counters()
        assert after["host_gc_pauses"] >= before["host_gc_pauses"] + 2
        assert after["host_gc_gen2_pauses"] >= before["host_gc_gen2_pauses"] + 2
        assert after["host_gc_pause_ms_total"] > before["host_gc_pause_ms_total"]
        assert after["host_gc_pause_ms_max"] > 0.0
        assert total.ms > 0.0  # only the second ran on this thread
        ring = [p for p in watch.recent() if p["generation"] == 2][-2:]
        assert ring[0]["thread"] == t.ident
        assert ring[1]["thread"] == threading.get_ident()
        assert total.ms == pytest.approx(
            sum(p["ms"] for p in watch.recent()[seen - watch.n:]
                if p["thread"] == threading.get_ident()))
        seen = watch.drain(seen, lambda t_end, gen, ms, mine:
                           j.append_at(t_end, "gc_pause",
                                       slot=0 if mine else -1, a=gen, b=ms))
        assert seen == watch.n
        pauses = [e for e in j.snapshot() if e["event"] == "gc_pause"
                  and e["a"] == 2.0]
        assert [e["slot"] for e in pauses[-2:]] == [-1, 0]
        assert all(e["b"] > 0.0 for e in pauses)
    finally:
        watch.leave()
    assert watch._on_gc not in gc.callbacks


def test_the_collectors_hook_lives_as_long_as_an_engine_loop(tiny):
    watch = gcwatch.WATCH
    assert watch._on_gc not in gc.callbacks
    a = _mk_engine(tiny)
    b = _mk_engine(tiny)
    try:
        a.generate([1, 2, 3], max_new_tokens=2, ignore_eos=True)
        assert gc.callbacks.count(watch._on_gc) == 1
        a.stop()
        assert gc.callbacks.count(watch._on_gc) == 1  # b's loop still runs
        n = watch.counters()["host_gc_pauses"]
        gc.collect()
        assert b.metrics()["host_gc_pauses"] > n
    finally:
        a.stop()
        b.stop()
    assert watch._on_gc not in gc.callbacks


def test_journal_fault_events_mirror_sites():
    """Runtime mirror of the journal-events lint pass."""
    assert set(ojournal.FAULT_EVENTS) == {
        f"fault_{s}" for s in faults.SITES
    }
    # Every declared event has a stable code.
    assert len(ojournal.EVENTS) == len(set(ojournal.EVENTS))
    assert all(e in ojournal.CODES for e in ojournal.EVENTS)


# --------------------------------------------------------------------- #
# traceparent + span derivation units
# --------------------------------------------------------------------- #


def test_traceparent_roundtrip():
    tp = otrace.new_traceparent()
    parsed = otrace.parse_traceparent(tp)
    assert parsed is not None
    tid, sid = parsed
    assert len(tid) == 32 and len(sid) == 16
    assert otrace.parse_traceparent("garbage") is None
    assert otrace.parse_traceparent("") is None
    assert otrace.parse_traceparent(
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
    # Header casing/whitespace tolerated.
    assert otrace.parse_traceparent("  " + tp.upper() + " ") == parsed


def test_trace_inherits_traceparent_and_tiles_phases():
    tp = otrace.new_traceparent()
    tr = RequestTrace("req-x", traceparent=tp, engine="e0")
    assert tr.trace_id == otrace.parse_traceparent(tp)[0]
    tr.note("queued")
    tr.note("admitted")
    tr.note("first_token")
    tr.note("decode_first")
    tr.note("preempt")
    tr.note("resumed")

    class _Done:
        kind = "done"
        finish_reason = "stop"
        error = None
        completion_tokens = 3

    tr.terminal(_Done())
    tr.terminal(_Done())  # duplicate terminals are ignored
    j = tr.to_json()
    assert j["terminal_events"] == 1
    names = [s["name"] for s in j["spans"]]
    assert names == ["queue", "admit", "join", "decode", "preempted", "decode"]
    # to_json rounds span durations to µs precision — tolerate that.
    assert abs(sum(s["duration_ms"] for s in j["spans"]) - j["wall_ms"]) < 0.05


def test_store_annotate_and_retire():
    tr = RequestTrace("req-annot")
    STORE.register(tr)
    STORE.annotate("req-annot", "reroute", dead_replica="r0")
    assert any(n == "reroute" for _, n, _a in tr.events)

    class _Err:
        kind = "error"
        finish_reason = None
        error = "boom"
        completion_tokens = 0

    tr.terminal(_Err())
    # Retired to the done ring, still retrievable.
    assert STORE.get_json("req-annot")["legs"][0]["complete"]
    # Annotating a completed request is a no-op, not an error.
    STORE.annotate("req-annot", "late")


# --------------------------------------------------------------------- #
# Metrics: named labeled histograms + gauge-source registration race
# --------------------------------------------------------------------- #


def test_metrics_named_histograms_render():
    from localai_tpu.server.app import Metrics

    m = Metrics()
    m.observe("api_call", 0.2, {"path": "/v1/chat/completions"})
    m.observe("ttft", 0.05, {"model": "m1"})
    m.observe("inter_token", 0.004, {"model": "m1"})
    out = m.render()
    # Back-compat: api_call renders with path labels as before.
    assert "# HELP localai_api_call" in out
    assert "# TYPE localai_api_call histogram" in out
    assert 'localai_api_call_bucket{path="/v1/chat/completions",le="0.25"} 1' in out
    assert 'localai_api_call_count{path="/v1/chat/completions"} 1' in out
    # New histograms get their own HELP/TYPE blocks and labels.
    assert "# HELP localai_ttft" in out
    assert "# TYPE localai_ttft histogram" in out
    assert 'localai_ttft_bucket{model="m1",le="0.05"} 1' in out
    assert 'localai_inter_token_count{model="m1"} 1' in out


def test_metrics_gauge_source_registration_is_locked():
    """The _gauge_sources append/iterate race (ISSUE 11 satellite):
    registering sources from one thread while another renders must never
    lose a registration or corrupt the render."""
    from localai_tpu.server.app import Metrics

    m = Metrics()
    stop = threading.Event()
    errors = []

    def renderer():
        try:
            while not stop.is_set():
                m.render()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=renderer)
    t.start()
    try:
        for i in range(200):
            m.add_gauge_source(
                lambda i=i: [("localai_test_gauge", {"i": str(i)}, 1.0)]
            )
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errors, errors
    out = m.render()
    assert 'localai_test_gauge{i="199"} 1.0' in out
    assert len(m._gauge_sources) == 200


# --------------------------------------------------------------------- #
# The acceptance lifecycle: chunked admit + preempt/resume, phases ≈ wall
# --------------------------------------------------------------------- #


def test_paged_chunked_preempt_lifecycle_trace(tiny):
    eng = _mk_engine(tiny, max_slots=2, max_seq=256, kv_pages=5,
                     kv_page_size=PAGE, prefill_chunk=32,
                     trace_journal_events=4096)
    try:
        # 40-token prompts: each admission books 2 pages (prompt + headroom)
        # so BOTH slots go active (4 of 5 pages), and on-demand growth
        # toward 256 rows (4 pages each) then genuinely exhausts the pool
        # mid-decode — a preemption, not admission backpressure.
        prompts = [[(i * 31 + j) % 255 + 1 for j in range(40)]
                   for i in range(2)]
        walls = {}
        results = {}

        def one(i):
            rid = f"lifecycle-{i}"
            t0 = time.monotonic()
            h = eng.submit(GenRequest(
                prompt_ids=prompts[i], max_new_tokens=10_000,
                ignore_eos=True, request_id=rid,
                traceparent=otrace.new_traceparent(),
            ))
            evs = _drain(h)
            walls[rid] = time.monotonic() - t0
            results[rid] = evs

        threads = [threading.Thread(target=one, args=(i,), name=f"lc-{i}")
                   for i in range(2)]
        threads[0].start()
        time.sleep(0.3)  # the older request admits first (becomes survivor)
        threads[1].start()
        for t in threads:
            t.join(timeout=300)
        assert all(not t.is_alive() for t in threads)
        # The pool (5 pages for 2×256-row demand) forced ≥1 preemption.
        assert eng.metrics()["kv_preemptions"] >= 1
        assert eng.metrics()["chunked_admissions"] >= 1

        preempts = resumes = 0
        for i in range(2):
            rid = f"lifecycle-{i}"
            evs = results[rid]
            assert evs[-1].kind == "done"
            assert sum(1 for e in evs if e.kind == "token") > 0  # streamed
            leg = _one_leg(rid)
            j = _assert_complete(leg)
            names = [e["name"] for e in j["events"]]
            preempts += names.count("preempt")
            resumes += names.count("resumed")
            # Phase durations sum to within 5% of externally measured wall.
            span_sum_s = sum(s["duration_ms"] for s in j["spans"]) / 1000.0
            wall = walls[rid]
            assert abs(span_sum_s - wall) <= max(0.05 * wall, 0.25), (
                rid, span_sum_s, wall, j["spans"])
        assert preempts >= 1, "no trace recorded the preemption"
        assert resumes >= 1, "no trace recorded the resume"

        # The journal saw the same lifecycle.
        events = {e["event"] for e in eng.journal.snapshot()}
        assert {"queued", "admitted", "chunk", "decode_block", "loop_iter",
                "preempt", "terminal"} <= events
        # Timeline export is valid Chrome trace-event JSON.
        tl = otimeline.chrome_trace({"tiny": eng.journal})
        _assert_chrome_trace(tl)
    finally:
        eng.stop()


def _assert_chrome_trace(tl):
    assert isinstance(tl, dict)
    evs = tl["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M"), ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] > 0
    # JSON-serializable end to end (what /debug/timeline returns).
    parsed = json.loads(json.dumps(tl))
    assert parsed["traceEvents"]


# --------------------------------------------------------------------- #
# Every termination path yields a complete trace with ONE terminal
# --------------------------------------------------------------------- #


def test_trace_cancel_while_pending(tiny):
    eng = _mk_engine(tiny, max_slots=1)
    try:
        blocker = eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=10_000, ignore_eos=True,
            request_id="cancel-blocker"))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(
            prompt_ids=[5, 5], max_new_tokens=4, request_id="cancel-victim"))
        time.sleep(0.05)
        victim.cancel()
        _drain(victim)
        _assert_complete(_one_leg("cancel-victim"))
        blocker.cancel()
        _drain(blocker)
        _assert_complete(_one_leg("cancel-blocker"))
    finally:
        eng.stop()


def test_trace_deadline_expiry(tiny):
    eng = _mk_engine(tiny, max_slots=1)
    try:
        blocker = eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=10_000, ignore_eos=True,
            request_id="dl-blocker"))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(
            prompt_ids=[5, 5], max_new_tokens=4, deadline_s=0.3,
            request_id="dl-victim"))
        evs = _drain(victim)
        assert evs[-1].kind == "error"
        j = _assert_complete(_one_leg("dl-victim"))
        assert "deadline" in j["events"][-1]["attrs"]["error"]
        blocker.cancel()
        _drain(blocker)
    finally:
        eng.stop()


def test_trace_queue_shed(tiny):
    from localai_tpu.engine import QueueFullError

    eng = _mk_engine(tiny, max_slots=1, max_pending=1)
    try:
        held = [eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=10_000, ignore_eos=True,
            request_id=f"shed-held-{i}")) for i in range(1)]
        deadline = time.monotonic() + 30
        while not eng.h_active.any() and time.monotonic() < deadline:
            time.sleep(0.01)
        held.append(eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=10_000, ignore_eos=True,
            request_id="shed-held-1")))
        shed_rid = None
        for i in range(4):
            rid = f"shed-{i}"
            try:
                held.append(eng.submit(GenRequest(
                    prompt_ids=[7, 7], max_new_tokens=2, request_id=rid)))
            except QueueFullError:
                shed_rid = rid
                break
        assert shed_rid is not None
        # The shed request's trace still completed (one error terminal).
        j = _assert_complete(_one_leg(shed_rid))
        assert "queue full" in j["events"][-1]["attrs"]["error"]
        for h in held:
            h.cancel()
        for h in held:
            _drain(h)
    finally:
        eng.stop()


def test_queue_wait_timing_field(tiny):
    eng = _mk_engine(tiny, max_slots=1)
    try:
        blocker = eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_new_tokens=400, ignore_eos=True))
        time.sleep(0.2)
        victim = eng.submit(GenRequest(prompt_ids=[5, 5], max_new_tokens=2,
                                       ignore_eos=True))
        evs = _drain(victim)
        final = evs[-1]
        assert final.kind == "done"
        # The victim waited behind the blocker — queue wait is visible.
        assert final.timing_queue_wait > 0.0
        _drain(blocker)
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Flight recorder: injected loop death → postmortem with journal tail
# --------------------------------------------------------------------- #


def _kill_engine(eng, timeout=30.0):
    # threads= scopes the injection to THIS engine's loop: any other live
    # engine in the process would otherwise race for the single fault.
    with faults.active(faults.FaultSchedule(
            seed=0, rate=1.0, sites=("engine_loop",), max_faults=1,
            threads={eng._thread.ident})):
        eng._wake.set()
        deadline = time.monotonic() + timeout
        while not eng.is_dead and time.monotonic() < deadline:
            time.sleep(0.01)
    assert eng.is_dead, "injected engine_loop fault did not kill the loop"
    t = eng._thread
    if t is not None:
        t.join(timeout=timeout)


def test_loop_death_writes_postmortem(tiny, tmp_path):
    eng = _mk_engine(tiny, max_slots=2, max_seq=256, kv_pages=10,
                     kv_page_size=PAGE, postmortem_dir=str(tmp_path))
    try:
        handles = [eng.submit(GenRequest(
            prompt_ids=list(range(1, 30)), max_new_tokens=10_000,
            ignore_eos=True, request_id=f"pm-{i}")) for i in range(3)]
        time.sleep(0.3)  # let some admit and decode
        _kill_engine(eng)
        for h in handles:
            evs = _drain(h)
            assert evs[-1].kind == "error"
        pm_path = eng.postmortem_path
        assert pm_path and pm_path.startswith(str(tmp_path)), pm_path
        with open(pm_path) as f:
            pm = json.load(f)
        assert "engine loop died" in pm["reason"]
        assert pm["pool"]["free_pages"] == eng.ecfg.kv_pages  # released
        # The dying requests are named, and the journal tail contains
        # their lifecycle events (the BENCH_r05 class becomes a read).
        dying = {s["rid"] for s in pm["slots"]} | set(pm["pending"])
        assert dying & {f"pm-{i}" for i in range(3)}, pm
        tail_rids = {e["rid"] for e in pm["journal"] if e["rid"]}
        assert tail_rids & dying, (tail_rids, dying)
        tail_events = [e["event"] for e in pm["journal"]]
        assert "queued" in tail_events
        assert "loop_dead" in tail_events
        assert "fault_engine_loop" in tail_events  # attributable injection
        # Every traced request still completed (error terminal).
        for i in range(3):
            _assert_complete(_one_leg(f"pm-{i}"))
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Overhead: journal on vs off within noise
# --------------------------------------------------------------------- #


def test_journal_overhead_within_noise(tiny):
    eng = _mk_engine(tiny, max_slots=2)
    try:
        eng.generate([1, 2, 3], max_new_tokens=8, ignore_eos=True)  # warm

        def round_(n_tokens=96):
            t0 = time.monotonic()
            _, ev = eng.generate([4, 5, 6], max_new_tokens=n_tokens,
                                 ignore_eos=True)
            assert ev.kind == "done"
            return time.monotonic() - t0

        saved = eng._journal
        assert saved is not None  # default-on
        eng._journal = None
        off = min(round_() for _ in range(3))
        eng._journal = saved
        on = min(round_() for _ in range(3))
        # Journal appends are a few field writes into preallocated storage
        # per BLOCK, not per token — anything past 2x is a real regression,
        # not CPU noise.
        assert on <= off * 2.0 + 0.05, (on, off)
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Span-transfer trace continuity (frame header carries the trace id)
# --------------------------------------------------------------------- #


def test_span_frame_carries_trace_id():
    import numpy as np

    from localai_tpu.cluster import transfer

    geom = {"page_size": PAGE, "layers": 1, "kv_heads": 1, "head_dim": 4,
            "dtype": "float32"}
    hk = np.arange(2 * PAGE * 4, dtype=np.float32).reshape(1, 2, PAGE, 1, 4)
    hv = hk + 1
    frame = transfer.encode_span(
        key=list(range(PAGE * 2)), valid=PAGE * 2, hk=hk, hv=hv, geom=geom,
        trace_id="chatcmpl-trace-1",
    )
    meta = transfer.span_meta(frame)
    assert meta["trace"] == "chatcmpl-trace-1"
    assert meta["valid"] == PAGE * 2
    # decode_span is unchanged (v1 importers ignore the extra key).
    key, valid, rk, rv = transfer.decode_span(frame, geom)
    assert valid == PAGE * 2
    assert (rk == hk).all() and (rv == hv).all()
    # Frames without a trace id simply omit the key.
    bare = transfer.encode_span(key=[1] * PAGE, valid=PAGE, hk=hk, hv=hv,
                                geom=geom)
    assert "trace" not in transfer.span_meta(bare)
    assert transfer.span_meta(b"garbage") == {}


def test_cross_leg_trace_shares_trace_id(tiny):
    """Two engine legs under one traceparent (the disaggregated shape)
    group as ONE trace id at /debug/trace."""
    eng = _mk_engine(tiny)
    try:
        tp = otrace.new_traceparent()
        for suffix in ("", ":prefill"):
            _drain(eng.submit(GenRequest(
                prompt_ids=[1, 2, 3], max_new_tokens=2, ignore_eos=True,
                request_id=f"xleg{suffix}", traceparent=tp)))
        a = STORE.get_json("xleg")
        b = STORE.get_json("xleg:prefill")
        assert a and b
        assert a["trace_ids"] == b["trace_ids"]
        assert a["trace_ids"] == [otrace.parse_traceparent(tp)[0]]
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# HTTP surfaces: /debug/trace, /debug/timeline, /debug/profile, /metrics
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def api(tmp_path_factory):
    from localai_tpu.server import ModelManager, Router, create_server
    from localai_tpu.server.openai_api import OpenAIApi

    d = tmp_path_factory.mktemp("models")
    (d / "tiny-obs.yaml").write_text(yaml.safe_dump({
        "name": "tiny-obs", "model": "tiny", "context_size": 128,
        "max_slots": 2, "max_tokens": 8, "temperature": 0.0,
    }))
    app_cfg = ApplicationConfig(address="127.0.0.1", port=0,
                                models_dir=str(d))
    manager = ModelManager(app_cfg)
    router = Router()
    OpenAIApi(manager).register(router)
    server = create_server(app_cfg, router)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{port}", manager
    server.shutdown()
    manager.shutdown()


def _post(base, path, payload, headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return r.read().decode(), r.status


def test_http_trace_and_timeline(api):
    base, _mgr = api
    tp = otrace.new_traceparent()
    out = _post(base, "/v1/chat/completions", {
        "model": "tiny-obs", "max_tokens": 6,
        "messages": [{"role": "user", "content": "hello"}],
    }, headers={"traceparent": tp})
    rid = out["id"]
    body, status = _get(base, f"/debug/trace/{rid}")
    assert status == 200
    data = json.loads(body)
    assert data["request_id"] == rid
    # The client's traceparent seeded the trace id.
    assert data["trace_ids"] == [otrace.parse_traceparent(tp)[0]]
    leg = data["legs"][-1]
    assert leg["complete"] and leg["terminal_events"] == 1
    assert [s["name"] for s in leg["spans"]][:4] == ["queue", "admit",
                                                     "join", "decode"]
    # Unknown request → 404.
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base, "/debug/trace/no-such-request")
    assert e.value.code == 404

    # Timeline: valid Chrome trace JSON with the model's process row.
    body, status = _get(base, "/debug/timeline")
    assert status == 200
    tl = json.loads(body)
    _assert_chrome_trace(tl)
    names = {e["args"].get("name") for e in tl["traceEvents"]
             if e["ph"] == "M"}
    assert "tiny-obs" in names
    # ?model= filter, and 404 for unknown model.
    json.loads(_get(base, "/debug/timeline?model=tiny-obs")[0])
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base, "/debug/timeline?model=nope")
    assert e.value.code == 404


def test_http_profile_gated(api, monkeypatch):
    base, _mgr = api
    monkeypatch.delenv("LOCALAI_PROFILE", raising=False)
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(base, "/debug/profile", {"seconds": 0.1})
    assert e.value.code == 403


def test_http_lifecycle_histograms_render(api):
    base, _mgr = api
    _post(base, "/v1/chat/completions", {
        "model": "tiny-obs", "max_tokens": 6,
        "messages": [{"role": "user", "content": "again"}],
    })
    body, _ = _get(base, "/metrics")
    for hist in ("ttft", "queue_wait", "admit"):
        assert f"# TYPE localai_{hist} histogram" in body, hist
        assert f'localai_{hist}_count{{model="tiny-obs"}}' in body, hist
    # api_call histogram unchanged, engine journal gauges exported.
    assert "localai_api_call_bucket" in body
    assert 'localai_engine_journal_events{model="tiny-obs"}' in body


@pytest.mark.parametrize("stream", [False, True], ids=["unstreamed", "streamed"])
def test_usage_carries_queue_wait_beside_prompt_processing(api, stream):
    """With the Extra-Usage header `usage` holds the three timings of the
    final TokenEvent; the queue wait is what /debug/trace calls `queue`."""
    base, _mgr = api
    req = urllib.request.Request(
        base + "/v1/chat/completions",
        data=json.dumps({
            "model": "tiny-obs", "max_tokens": 4, "stream": stream,
            "messages": [{"role": "user", "content": "hello"}],
        }).encode(),
        headers={"Content-Type": "application/json", "Extra-Usage": "1"})
    with urllib.request.urlopen(req, timeout=120) as r:
        body = r.read().decode()
    if stream:
        frames = [json.loads(line[6:]) for line in body.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        usage = next(f["usage"] for f in reversed(frames) if f.get("usage"))
    else:
        usage = json.loads(body)["usage"]
    for key in ("timing_queue_wait", "timing_prompt_processing",
                "timing_token_generation"):
        assert usage[key] >= 0.0, (key, usage)
