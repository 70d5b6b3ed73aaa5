"""The measurement inside the program (ISSUE 24, docs/OBSERVABILITY.md
section 5): program and kernel names, loop phases as spans, the decode-row
account, the `decode_first` event and the `join` phase."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import engine as engine_mod
from localai_tpu.engine import runtime
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.observe import journal as jmod
from localai_tpu.observe.trace import STORE


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=4, max_seq=128, min_prefill_bucket=16,
                    block_sizes=(4, 16))
    defaults.update(kw)
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


def _run(eng, lengths, tag="r"):
    """Submit one request per entry of `lengths` (tokens to generate) and
    wait for all; returns the request ids."""
    rids = [f"{tag}-{i}-{time.monotonic_ns()}" for i in range(len(lengths))]
    handles = [eng.submit(GenRequest(
        prompt_ids=[1, 5, 9, 3 + i], max_new_tokens=n, temperature=0.0,
        ignore_eos=True, request_id=rid))
        for i, (n, rid) in enumerate(zip(lengths, rids))]
    for h, n in zip(handles, lengths):
        _text, final = h.result()
        assert final.kind == "done" and final.completion_tokens == n
    return rids


@pytest.fixture(scope="module")
def mixed_run(tiny):
    """Three requests on four slots (one slot idle), ending at different
    steps inside their blocks; then a second wave through the same slots."""
    eng = _engine(tiny)
    try:
        rids = _run(eng, [3, 11, 22], "a") + _run(eng, [6, 2], "b")
        deadline = time.monotonic() + 10.0
        while eng.metrics()["active_slots"] and time.monotonic() < deadline:
            time.sleep(0.01)
        yield eng, rids, [3, 11, 22, 6, 2]
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# C: counters where the work happens
# --------------------------------------------------------------------- #


def test_row_counters_add_up_exactly(mixed_run):
    eng, _rids, lengths = mixed_run
    m = eng.metrics()
    d, p, o, e = (m[f"decode_rows_{k}"]
                  for k in ("dispatched", "posted", "overshoot", "empty"))
    assert d == p + o + e and d > 0
    # every token but each request's first (from its admission) was posted
    # by a decode block
    assert p == sum(n - 1 for n in lengths)
    # a slot stayed idle and requests ended inside their blocks
    assert e > 0 and o > 0
    blocks = [ev for ev in eng.journal.snapshot()
              if ev["event"] == "decode_block"]
    assert d == sum(ev["a"] for ev in blocks) * eng.ecfg.max_slots


def test_decode_first_is_journalled_once_per_request(mixed_run):
    eng, rids, lengths = mixed_run
    evs = eng.journal.snapshot()
    for rid, n in zip(rids, lengths):
        mine = [ev["event"] for ev in evs if ev["rid"] == rid]
        assert mine.count("decode_first") == (1 if n > 1 else 0), (rid, mine)
        assert mine.index("first_token") < mine.index("decode_first")
        assert mine.index("decode_first") < mine.index("terminal")
    firsts = [ev for ev in evs if ev["event"] == "decode_first"]
    assert all(ev["a"] >= 0 and ev["slot"] >= 0 for ev in firsts)


def test_trace_tiles_queue_admit_join_decode(mixed_run):
    _eng, rids, _lengths = mixed_run
    for rid in rids:
        j = STORE.get(rid)[-1].to_json()
        names = [s["name"] for s in j["spans"]]
        assert names == ["queue", "admit", "join", "decode"], names
        total = sum(s["duration_ms"] for s in j["spans"])
        assert abs(total - j["wall_ms"]) <= 0.05 * j["wall_ms"] + 0.05


def test_a_request_of_one_token_never_joins(tiny):
    eng = _engine(tiny)
    try:
        (rid,) = _run(eng, [1], "one")
        names = [s["name"] for s in STORE.get(rid)[-1].to_json()["spans"]]
        assert names == ["queue", "admit", "join"]
        assert not [ev for ev in eng.journal.snapshot()
                    if ev["event"] == "decode_first"]
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# B: loop phases
# --------------------------------------------------------------------- #


def test_pull_is_a_phase_and_blocked_time_is_part_of_host_time(mixed_run):
    eng, _rids, _lengths = mixed_run
    assert runtime.LOOP_PHASES == jmod.LOOP_PHASES
    i = runtime.LOOP_PHASES.index("pull")
    assert runtime.LOOP_PHASES[i + 1] == "process"
    iters = [ev for ev in eng.journal.snapshot() if ev["event"] == "loop_iter"]
    seen = set().union(*(ev.get("phases", {}) for ev in iters))
    assert {"pull", "process"} <= seen <= set(jmod.LOOP_PHASES)
    # loop_iter.b is host ms, always: the window's phases outside `wait`
    for ev in iters:
        ph = ev.get("phases", {})
        host = sum(v for k, v in ph.items() if k != "wait")
        assert ev["b"] == pytest.approx(host, rel=1e-3, abs=1e-3)
    m = eng.metrics()
    assert 0.0 <= m["loop_blocked_ms_total"] <= m["loop_host_ms_total"]
    pulls = sum(ev.get("phases", {}).get("pull", 0.0) for ev in iters)
    assert m["loop_blocked_ms_total"] == pytest.approx(pulls, rel=1e-3, abs=1e-3)


class _Span:
    """Stands in for jax.profiler.TraceAnnotation."""

    log: list = []
    enabled = True

    def __init__(self, name, **stats):
        self.name = name

    def __enter__(self):
        _Span.log.append(("open", self.name))

    def __exit__(self, *exc):
        _Span.log.append(("close", self.name))

    @staticmethod
    def is_enabled():
        return _Span.enabled


def test_loop_phases_open_one_span_per_phase_and_merge_waits():
    _Span.log, _Span.enabled = [], True
    ph = runtime.LoopPhases(annotate=_Span)
    for name in ("admit", "prep", "commit", "dispatch", "wait", "wait",
                 "wait", "pull", "process", "wait"):
        ph.begin(name)
        ph.sync()  # what the loop does at the end of every iteration
    ph.end()
    want = []
    for name in ("admit", "prep", "commit", "dispatch", "wait", "pull",
                 "process", "wait"):
        want += [("open", f"loop/{name}"), ("close", f"loop/{name}")]
    assert _Span.log == want
    assert all(v >= 0.0 for v in ph.ms.values())
    assert ph.total() == pytest.approx(
        sum(v for k, v in ph.ms.items() if k != "wait"))


def test_loop_phases_make_no_span_without_a_capture():
    _Span.log, _Span.enabled = [], False
    ph = runtime.LoopPhases(annotate=_Span)
    ph.begin("admit")
    ph.begin("wait")
    _Span.enabled = True   # a capture starts in the middle of a phase
    ph.begin("wait")       # the next spin of the same phase opens its span
    ph.begin("pull")
    _Span.enabled = False  # and stops in the middle of another
    ph.begin("process")
    with ph.call("dispatch/decode_block", n=4, live=2):
        pass               # a call is no object either without a capture
    ph.end()
    assert _Span.log == [("open", "loop/wait"), ("close", "loop/wait"),
                         ("open", "loop/pull"), ("close", "loop/pull")]
    assert set(k for k, v in ph.ms.items() if v > 0) <= {
        "admit", "wait", "pull", "process"}
    _Span.log, _Span.enabled = [], True
    ph.begin("dispatch")
    with ph.call("dispatch/decode_block", n=4, live=2):
        pass               # and under one it is the span it always was
    ph.end()
    assert _Span.log == [
        ("open", "loop/dispatch"), ("open", "dispatch/decode_block"),
        ("close", "dispatch/decode_block"), ("close", "loop/dispatch")]


def test_a_long_phase_is_spans_of_one_slice(monkeypatch):
    """The profiler records a span when it ends, so one that is open when a
    capture stops is lost: a phase is cut into slices of SPAN_SLICE_S. On a
    clock the test turns (six workers' compiles took most of a 20 ms
    wall-clock window from this thread): steps of 2**-12 s, slices of eight."""
    _Span.log, _Span.enabled = [], True
    now = [128.0]
    monkeypatch.setattr(runtime, "SPAN_SLICE_S", 2.0 ** -9)
    ph = runtime.LoopPhases(annotate=_Span, wall=lambda: now[0],
                            cpu=lambda: now[0])
    for _ in range(80):
        ph.begin("pull")   # what the loop does every slice of its wait
        now[0] += 2.0 ** -12
    ph.end()
    opens = [e for e in _Span.log if e == ("open", "loop/pull")]
    assert len(opens) == 10  # 80 steps in slices of eight
    assert _Span.log == [("open", "loop/pull"), ("close", "loop/pull")] * len(opens)
    assert ph.ms["pull"] == pytest.approx(80 * 2.0 ** -12 * 1e3)
    assert ph.total() == ph.ms["pull"]


def test_the_loops_spans_reach_a_real_capture(tiny, tmp_path):
    """The engine-loop thread's phases and the dispatch annotation are host
    events of a jax.profiler capture taken as /debug/profile takes it."""
    from jax.profiler import ProfileData

    from localai_tpu.observe import profile as oprofile

    eng = _engine(tiny)
    try:
        _run(eng, [2], "warm")
        jax.profiler.start_trace(str(tmp_path), **oprofile.trace_options(jax))
        _run(eng, [9, 9], "cap")
        gc.collect()  # the engine's hook is in: a collection is a span too
        time.sleep(0.05)
        jax.profiler.stop_trace()
    finally:
        eng.stop()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names, blocks, admits, pauses, uploads = set(), [], [], [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("loop/"):
                    names.add(ev.name)
                if ev.name == "dispatch/decode_block":
                    blocks.append(dict(ev.stats))
                if ev.name == "dispatch/admit":
                    admits.append(dict(ev.stats))
                if ev.name == "host/gc":
                    pauses.append(dict(ev.stats))
                if ev.name == "call/ctrl_upload":
                    uploads.append(dict(ev.stats))
    assert {"loop/wait", "loop/pull", "loop/process", "loop/dispatch"} <= names
    assert names <= {f"loop/{p}" for p in jmod.LOOP_PHASES}
    assert blocks and all(b["n"] in (4, 16) and 1 <= b["live"] <= 4
                          for b in blocks)
    # the dispatch spans went through LoopPhases.call with the stats they had
    assert admits and all(a["m"] >= 1 and a["bucket"] == 16
                          and 4 <= a["tokens"] <= 4 * a["m"] for a in admits)
    # the forced collection, and the first block's control upload
    assert any(p["generation"] == 2 for p in pauses)
    assert uploads and all(u["bytes"] > 0 for u in uploads)


# --------------------------------------------------------------------- #
# D: every stretch of the loop has a cause (ISSUE 51)
# --------------------------------------------------------------------- #


class _Clocks:
    """A wall clock and the loop thread's CPU clock, turned by hand (ms)."""

    def __init__(self):
        self.wall = 64.0
        self.cpu = 8.0

    def run(self, ms):          # the thread runs Python
        self.wall += ms / 1000.0
        self.cpu += ms / 1000.0

    def away(self, ms, cpu_ms=0.0):  # the thread is not on the CPU
        self.wall += ms / 1000.0
        self.cpu += cpu_ms / 1000.0

    def phases(self):
        return runtime.LoopPhases(annotate=_Span, wall=lambda: self.wall,
                                  cpu=lambda: self.cpu)


class _Never:
    """An Event nobody sets: wait() comes back by timeout, `late` ms late."""

    def __init__(self, clk, late):
        self.clk, self.late = clk, late

    def wait(self, timeout):
        self.clk.away(timeout * 1000.0 + self.late)
        return False


def test_every_ms_of_a_working_phase_has_exactly_one_cause():
    _Span.enabled = False
    clk = _Clocks()
    ph = clk.phases()
    gcw = ph.collector  # the hook adds this thread's pauses to it
    ph.begin("admit")
    clk.run(10.0)                       # Python
    with ph.call("dispatch/admit", m=2, bucket=16, tokens=20):
        clk.away(30.0, cpu_ms=1.0)      # inside jax, mostly waiting
        gcw.ms += 5.0                   # a pause in there is the call's
    ph.note(1, 32)
    clk.away(6.0)                       # another thread has the interpreter
    clk.run(2.0)
    clk.run(3.0)
    gcw.ms += 3.0                       # the collector, on this thread
    ph.begin("wait")
    assert not ph.wait(_Never(clk, 300.0), 0.001)  # asked 1 ms, got 301
    ph.begin("process")
    clk.run(150.0)
    ph.note(48, 2)
    ph.sync()                           # the end of the loop's iteration
    assert ph.ms["admit"] == pytest.approx(51.0)
    assert ph.call_ms["admit"] == pytest.approx(30.0)
    assert ph.gc_ms["admit"] == pytest.approx(3.0)
    assert ph.off_ms["admit"] == pytest.approx(6.0)
    assert ph.ms["process"] == pytest.approx(150.0)
    work = [n for n in ph.names if n not in runtime.IDLE_PHASES]
    python = {n: ph.ms[n] - ph.call_ms[n] - ph.gc_ms[n] - ph.off_ms[n]
              for n in work}
    assert python["admit"] == pytest.approx(12.0)
    assert python["process"] == pytest.approx(150.0)
    # the four causes add up to the working phases' ms, exactly
    assert (sum(python.values()) + ph.working(ph.call_ms)
            + ph.working(ph.gc_ms) + ph.working(ph.off_ms)
            == pytest.approx(ph.total(exclude=runtime.IDLE_PHASES), abs=1e-9))
    # the late wake-up is in `wait`, and in none of them
    assert (ph.late_ms, ph.late_max) == (pytest.approx(300.0),) * 2
    assert ph.ms["wait"] == pytest.approx(301.0)
    assert ph.call_ms["wait"] == ph.off_ms["wait"] == ph.gc_ms["wait"] == 0.0
    # the longest stretch, with its parts and what it did
    assert ph.longest[0] == "process"
    assert ph.longest[1:] == pytest.approx([150.0, 0.0, 0.0, 0.0, 48.0, 2.0],
                                           abs=1e-6)
    assert ph.stalls == [ph.longest] and ph.stall_count == 1
    stretch = [float(ph.names.index("process")), 150.0, 0.0, 0.0, 0.0, 48.0,
               2.0]
    assert ph.extras() == pytest.approx([300.0, 300.0] + stretch, abs=1e-6)
    # a stall's record carries the stretch and not the window's late pair
    assert ph.extras(stall=ph.stalls[0]) == pytest.approx(
        [0.0, 0.0] + stretch, abs=1e-6)
    assert ph.causes()[0][ph.names.index("admit")] == pytest.approx(30.0)
    ph.reset()
    assert ph.longest is None and ph.late_max == 0.0
    assert (ph.stretch_max_ever, ph.late_max_ever) == (
        pytest.approx(150.0), pytest.approx(300.0))  # since start


def test_a_thread_clock_that_ticks_in_steps_still_sums_to_the_truth():
    """The chip's host charges a thread's CPU time by the 10 ms timer tick
    (my chip run A, PR 51): a 4 ms stretch reads 0 or 10 ms of CPU. Held at 0
    stretch by stretch, every short phase read as off the CPU whole."""
    _Span.enabled = False
    clk = _Clocks()
    ph = runtime.LoopPhases(annotate=_Span, wall=lambda: clk.wall,
                            cpu=lambda: int(clk.cpu * 100.0) / 100.0)
    ph.begin("wait")
    for _ in range(100):       # 400 ms of Python, 100 ms truly off the CPU
        ph.begin("prep")
        clk.run(4.0)
        ph.begin("housekeeping")
        clk.away(1.0)
        ph.begin("wait")
        clk.away(3.0)
    ph.sync()
    assert ph.ms["prep"] == pytest.approx(400.0)
    off = ph.off_ms["prep"] + ph.off_ms["housekeeping"]
    assert off == pytest.approx(100.0, abs=10.5)  # to a tick
    assert ph.longest[4] >= 0.0  # one stretch's part is never negative


def test_beginning_the_running_phase_again_does_not_end_its_stretch():
    _Span.enabled = False
    clk = _Clocks()
    ph = clk.phases()
    ph.begin("process")
    clk.run(60.0)
    ph.begin("process")   # what _process_entry does at every step
    clk.run(60.0)
    ph.begin("wait")
    assert ph.longest[:2] == ["process", pytest.approx(120.0)]
    assert len(ph.stalls) == 1
    ph.reset()
    ph.stalls.clear()
    ph.begin("process")
    clk.run(60.0)
    ph.begin("prep")      # another phase between them: two stretches
    clk.run(1.0)
    ph.begin("process")
    clk.run(60.0)
    ph.end()
    assert ph.longest[:2] == ["process", pytest.approx(60.0)]
    assert ph.stalls == [] and ph.ms["process"] == pytest.approx(120.0)


@pytest.fixture(scope="module")
def stalled_run(tiny):
    """A warm engine; a quiet run of it; then a run in which posting one
    token takes 150 ms. Yields (events of the quiet run, of the slow one,
    the engine's metrics after both)."""
    eng = _engine(tiny)
    try:
        _run(eng, [9, 9], "warm")
        t_quiet = time.monotonic()
        _run(eng, [9, 9], "quiet")
        t_slow = time.monotonic()
        post, armed = eng._post_token, [True]

        def slow_post(*a, **kw):
            if armed[0]:
                armed[0] = False
                time.sleep(0.15)
            return post(*a, **kw)

        eng._post_token = slow_post
        _run(eng, [9, 9], "slow")
        time.sleep(0.1)  # a last iteration, so the window is flushed
        evs = eng.journal.snapshot()
        yield ([e for e in evs if t_quiet <= e["t"] < t_slow],
               [e for e in evs if e["t"] >= t_slow], eng.metrics())
    finally:
        eng.stop()


def test_a_stall_in_process_is_journalled_with_what_it_posted(stalled_run):
    _quiet, slow, m = stalled_run
    stalls = [e for e in slow if e["event"] == "loop_stall"]
    mine = [e for e in stalls if e["stretch"]["phase"] == "process"]
    assert len(mine) == 1, stalls
    st = mine[0]["stretch"]
    assert mine[0]["b"] == pytest.approx(st["ms"]) and st["ms"] >= 150.0
    assert jmod.LOOP_PHASES[int(mine[0]["a"])] == "process"
    # the thread slept: off the CPU, not in a call, not collecting
    assert st["off"] >= 140.0 and st["call"] == 0.0
    assert st["did"][0] >= 1  # tokens it posted
    # the window that holds it names it as its longest stretch
    longest = [e["longest"] for e in slow if e["event"] == "loop_iter"
               and e["longest"] and e["longest"]["ms"] >= 150.0]
    assert [x["phase"] for x in longest] == ["process"]
    assert m["loop_stalls"] >= 1 and m["loop_stretch_ms_max"] >= 150.0


def test_a_quiet_run_journals_no_stall_in_process(stalled_run):
    quiet, _slow, _m = stalled_run
    iters = [e for e in quiet if e["event"] == "loop_iter"]
    assert iters and all({"calls", "gc", "off", "late", "longest"} <= set(e)
                         for e in iters)
    assert not [e for e in quiet if e["event"] == "loop_stall"
                and e["stretch"]["phase"] == "process"]
    for e in iters:  # no cause is larger than the phase it is a part of
        for k in ("calls", "gc", "off"):
            for phase, v in e[k].items():
                assert v <= e["phases"].get(phase, 0.0) * 1.001 + 1e-3


def test_the_loops_causes_are_counters_too(mixed_run):
    eng, _rids, _lengths = mixed_run
    m = eng.metrics()
    parts = (m["loop_call_ms_total"] + m["loop_gc_ms_total"]
             + m["loop_off_cpu_ms_total"])
    assert 0.0 < m["loop_call_ms_total"] <= parts
    assert parts <= m["loop_host_ms_total"] - m["loop_blocked_ms_total"] + 1e-6
    assert m["loop_stretch_ms_max"] > 0.0 and m["loop_late_ms_max"] >= 0.0
    assert m["host_gc_pauses"] >= m["host_gc_gen2_pauses"] >= 0
    assert m["host_gc_pause_ms_total"] >= m["host_gc_pause_ms_max"] >= 0.0
    assert "loop_host_overhead_per_block_ms" not in m


# --------------------------------------------------------------------- #
# A: names on the device
# --------------------------------------------------------------------- #


def _program_names(eng):
    names = set()
    for cache in (eng._block_cache, eng._admit_cache, eng._snap_cache):
        for fn in cache.values():
            # a background AOT compile publishes the executable itself,
            # which carries no function name
            if not isinstance(fn, jax.stages.Compiled):
                names.add(getattr(fn, "__name__", None))
    for fn in (eng._prefill_fn, eng._embed_fn, eng._score_fn):
        names.add(fn.__name__)
    return names


@pytest.mark.parametrize("kw", [
    dict(),
    dict(kv_pages=24, kv_page_size=16, prefix_cache_min=8),
], ids=["dense", "paged_prefix"])
def test_every_engine_program_has_a_name_of_the_allowed_set(tiny, kw):
    eng = _engine(tiny, **kw)
    try:
        prompt = [1] + [7, 8, 9, 10] * 6
        for _ in range(2):  # the second admission may hit the prefix cache
            _t, final = eng.submit(GenRequest(
                prompt_ids=prompt, max_new_tokens=6, temperature=0.0,
                ignore_eos=True)).result()
            assert final.kind == "done"
        names = _program_names(eng)
    finally:
        eng.stop()
    assert {"decode_block", "admit"} <= names
    assert names <= engine_mod.PROGRAM_NAMES, names - engine_mod.PROGRAM_NAMES
    assert "wrapped" not in names and None not in names


def test_a_program_outside_the_allowed_set_is_refused():
    with pytest.raises(AssertionError):
        engine_mod._named_jit(lambda x: x, "wrapped")
    fn = engine_mod._named_jit(lambda x: x + 1, "page_copy")
    assert "jit_page_copy" in fn.lower(jnp.zeros((2,))).as_text()[:200]


def _lower_int8():
    from localai_tpu.models.quant import quantize_tensor
    from localai_tpu.ops import quant_matmul as Q

    w = quantize_tensor(jnp.ones((256, 256), jnp.bfloat16))
    x = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda x, w: Q.dispatch_matmul(x, w, impl="pallas")).lower(x, w)


def _lower_int4():
    from localai_tpu.models.quant import quantize_tensor_g4
    from localai_tpu.ops import quant_matmul as Q

    w = quantize_tensor_g4(jnp.ones((256, 256), jnp.bfloat16))
    x = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda x, w: Q.dispatch_matmul(x, w, impl="pallas")).lower(x, w)


def _lower_unembed():
    from localai_tpu.models.quant import unembed_matmul

    w = {"q": jnp.ones((512, 256), jnp.int8), "s": jnp.ones((512, 1), jnp.float32)}
    h = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda h, w: unembed_matmul(h, w, impl="pallas")).lower(h, w)


def _lower_paged():
    from localai_tpu.ops.paged_flash import paged_decode_partials

    q = jnp.ones((2, 4, 128), jnp.bfloat16)
    pool = jnp.ones((5, 16, 2, 128), jnp.bfloat16)
    table = jnp.asarray(np.arange(4, dtype=np.int32).reshape(2, 2) + 1)
    limits = jnp.asarray([20, 9], jnp.int32)
    return jax.jit(lambda *a: paged_decode_partials(*a, interpret=True)).lower(
        q, pool, pool, table, limits)


def _lower_flash():
    from localai_tpu.ops.flash import flash_prefill_attention

    q = jnp.ones((1, 128, 4, 64), jnp.bfloat16)
    kv = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    return flash_prefill_attention.lower(
        q, kv, kv, jnp.asarray([100], jnp.int32), interpret=True)


def _lower_lora():
    from localai_tpu.ops.lora_matmul import _lora_call

    x = jnp.ones((4, 128), jnp.bfloat16)
    a = jnp.ones((2, 128, 8), jnp.bfloat16)
    b = jnp.ones((2, 8, 128), jnp.bfloat16)
    return jax.jit(_lora_call).lower(x, a, b, jnp.asarray([0, 1, 1, 0], jnp.int32))


@pytest.mark.parametrize("name,lower", [
    ("int8_matmul", _lower_int8), ("int4_matmul", _lower_int4),
    ("int8_unembed", _lower_unembed), ("paged_attention", _lower_paged),
    ("flash_prefill", _lower_flash), ("lora_matmul", _lower_lora),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_pallas_kernel_carries_its_name_into_the_lowered_program(name, lower):
    text = lower().as_text(debug_info=True)
    assert f"{name}/pallas_call" in text, name


def test_layer_scopes_are_in_the_decode_steps_lowering(tiny):
    from localai_tpu.models import llama

    cfg, params = tiny
    B, n = 2, 4
    cache = llama.KVCache(
        k=jnp.zeros((cfg.num_layers, B, 32, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16),
        v=jnp.zeros((cfg.num_layers, B, 32, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16))
    local = jnp.zeros((cfg.num_layers, B, n, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16)
    tok = jnp.zeros((B,), jnp.int32)
    text = jax.jit(lambda p, t, pos, c, lk, lv, s: llama.decode_step_windowed(
        cfg, p, t, pos, c, lk, lv, s)).lower(
        params, tok, tok, cache, local, local, jnp.int32(0)).as_text(debug_info=True)
    for scope in ("layer_weights", "layer_kv_pool", "attention", "mlp", "lm_head"):
        assert f"{scope}/" in text, scope
