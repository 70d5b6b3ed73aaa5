"""The readers PR 51 added, on hand-made journals and planes: the identity
with `loop_busy`, the edges of the clean window, and None on a journal that
lacks the new fields (the parent commit's, under this PR's benchmark files)."""

import pytest

from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import loop_busy, loop_causes, span_idle

MS = 1e6  # ns
NEW = ("loop_python_ms_per_block", "loop_in_call_ms_per_block",
       "loop_off_cpu_ms_per_block", "loop_stretch_ms_max",
       "loop_stall_explained_share", "loop_wake_late_ms_max",
       "host_gc_pause_ms_max")


def loop_iter(t, phases, calls=None, gc=None, off=None, late=(0.0, 0.0),
              longest=None):
    return {"t": t, "event": "loop_iter", "rid": "", "slot": -1, "a": 1.0,
            "b": sum(v for k, v in phases.items() if k != "wait"),
            "phases": phases, "calls": calls or {}, "gc": gc or {},
            "off": off or {}, "late": {"ms": late[0], "max": late[1]},
            "longest": longest}


def stretch(phase, ms, call=0.0, gc=0.0, off=0.0, did=(0.0, 0.0)):
    return {"phase": phase, "ms": ms, "call": call, "gc": gc, "off": off,
            "did": list(did)}


def stall(t, st):
    return {"t": t, "event": "loop_stall", "rid": "", "slot": -1, "a": 7.0,
            "b": st["ms"], "stretch": st}


def pause(t, ms, generation=2, slot=-1):
    return {"t": t, "event": "gc_pause", "rid": "", "slot": slot,
            "a": float(generation), "b": ms}


def block(t):
    return {"t": t, "event": "decode_block", "rid": "", "slot": -1, "a": 16.0,
            "b": 1.0}


def traced(journal, t0=100.0, seconds=50.0, trace_s=12.0):
    """A traced run's context: the capture begins at t0 + 19, the marked span
    is [t0 + 21, t0 + 33] (starting a capture takes two seconds here)."""
    return {"journal": journal, "t0": t0, "seconds": seconds,
            "cell": {"cell": {"trace_s": trace_s}},
            "trace": {"t_start": t0 + 21.0, "t_end": t0 + 33.0}}


@pytest.fixture
def journal():
    return [
        # before the capture: clean. The first window began before t0.
        loop_iter(101.0, {"process": 900.0}, longest=stretch("process", 900.0)),
        block(102.0),
        loop_iter(103.0, {"commit": 60.0, "wait": 5.0}, calls={"commit": 50.0},
                  late=(3.0, 2.0), longest=stretch("commit", 55.0, call=50.0)),
        pause(110.0, 40.0),
        stall(115.0, stretch("process", 300.0, gc=280.0, did=(512, 3))),
        pause(118.99, 30.0),   # ends before the capture begins: clean
        # the capture starts at 119 and stalls an admission for 1.5 s
        pause(119.02, 500.0),  # began before 119, ends after: left out
        stall(120.9, stretch("admit", 1500.0, off=1490.0, did=(1, 256))),
        loop_iter(120.95, {"admit": 1500.0}, off={"admit": 1490.0},
                  late=(800.0, 700.0), longest=stretch("admit", 1500.0)),
        # the marked span [121, 133]: its first window began outside it
        loop_iter(121.5, {"process": 2000.0}, longest=stretch("process", 2000.0)),
        block(122.0),
        loop_iter(123.0, {"commit": 100.0, "dispatch": 20.0, "process": 30.0,
                          "pull": 400.0, "wait": 50.0},
                  calls={"commit": 80.0, "dispatch": 15.0},
                  gc={"process": 10.0}, off={"commit": 5.0, "process": 2.0},
                  late=(4.0, 1.5), longest=stretch("commit", 100.0, call=80.0)),
        stall(124.0, stretch("commit", 120.0, call=90.0, off=6.0)),
        block(125.0),
        loop_iter(126.0, {"commit": 120.0, "process": 40.0, "pull": 350.0},
                  calls={"commit": 90.0}, off={"commit": 6.0},
                  late=(1.0, 1.0), longest=stretch("commit", 120.0, call=90.0)),
        pause(127.0, 22.0, generation=1, slot=0),
        # after the marked span the benchmark parses the capture: left out
        stall(140.0, stretch("process", 2500.0, off=2400.0)),
        loop_iter(140.1, {"process": 2500.0}, off={"process": 2400.0},
                  late=(900.0, 900.0), longest=stretch("process", 2500.0)),
    ]


def test_the_four_causes_add_up_to_loop_busy_over_the_marked_span(journal):
    ctx = traced(journal)
    python, call, off = (loop_causes.read(ctx, w)
                         for w in ("python", "call", "off"))
    # two blocks; the windows at 123.0 and 126.0 (the first of the span is
    # left out, as loop_busy leaves it out)
    assert call == pytest.approx((80.0 + 15.0 + 90.0) / 2)
    assert off == pytest.approx((5.0 + 2.0 + 6.0) / 2)
    collector = 10.0 / 2
    busy = loop_busy.read(ctx)
    assert busy == pytest.approx((100 + 20 + 30 + 120 + 40) / 2)
    assert python + call + collector + off == pytest.approx(busy)
    # an untraced run: the whole journal is the span
    whole = {"journal": journal}
    assert (sum(loop_causes.read(whole, w) for w in ("python", "call", "off"))
            + 10.0 / 3 == pytest.approx(loop_busy.read(whole)))


def test_the_clean_window_ends_where_the_capture_begins(journal):
    ctx = traced(journal)
    assert loop_causes.clean_pieces(ctx) == [(100.0, 119.0), (121.0, 133.0)]
    # held against the run's own record: a capture of 16 s that ended no
    # earlier than the mark did began no earlier than 133 - 16 = 117
    ctx["trace"]["capture_wall_s"] = 16.0
    assert loop_causes.clean_pieces(ctx) == [(100.0, 119.0), (121.0, 133.0)]
    # ... and where run.py has come to place the capture otherwise (the
    # recomputed begin after the mark, or seconds before the capture can have
    # begun), the clean window is the marked span alone
    moved = traced(journal, trace_s=4.0)  # recomputed: 123, the mark at 121
    assert loop_causes.clean_pieces(moved) == [(121.0, 133.0)]
    ctx["trace"]["capture_wall_s"] = 12.5  # began at 120.5 or later
    assert loop_causes.clean_pieces(ctx) == [(121.0, 133.0)]
    del ctx["trace"]["capture_wall_s"]
    # the 1.5 s and 2.5 s stretches and the 2.0 s window that began outside
    # the span are the capture's; the 900 ms one began before t0
    assert loop_causes.read(ctx, "stretch_max") == 120.0
    assert loop_causes.read(ctx, "late_max") == 2.0
    # pauses: 40 and 30 before the capture, 22 in the span; not the 500
    assert loop_causes.read(ctx, "gc_pause_max") == 40.0
    # stalls: 300 (280 explained) before, 120 (96 explained) in the span
    assert loop_causes.read(ctx, "stall_explained") == pytest.approx(
        100.0 * (280.0 + 96.0) / 420.0)
    # an untraced run has no capture to leave out
    whole = {"journal": journal}
    assert loop_causes.read(whole, "stretch_max") == 2500.0
    assert loop_causes.read(whole, "late_max") == 900.0
    assert loop_causes.read(whole, "gc_pause_max") == 500.0


def test_a_window_without_a_stall_or_a_pause_reads_100_and_0():
    quiet = [loop_iter(1.0, {"process": 5.0}),
             block(1.5),
             loop_iter(2.0, {"process": 5.0}, longest=stretch("process", 5.0))]
    ctx = {"journal": quiet}
    assert loop_causes.read(ctx, "stall_explained") == 100.0
    assert loop_causes.read(ctx, "gc_pause_max") == 0.0
    assert loop_causes.read(ctx, "stretch_max") == 5.0


def test_the_table_names_every_stall_and_what_was_left_out(journal, capsys):
    loop_causes._printed.clear()
    loop_causes.read(traced(journal), "python")
    loop_causes.read(traced(journal), "call")   # printed once a journal
    err = capsys.readouterr().err
    assert err.count("the marked span") == 1
    assert "identity, ms a block: python" in err
    clean, left = err.split("LEFT OUT")
    assert "process 300.0 ms = call 0.0 + collector 280.0" in clean
    assert "did 512 / 3" in clean and "generation 1, 22.0 ms, on the loop" in clean
    assert "3 collections of 1 ms and more, 92.0 ms in all, 22.0 ms" in clean
    assert "admit 1500.0 ms" not in clean and "admit 1500.0 ms" in left
    assert "process 2500.0 ms" in left and "largest 900.0 ms" in left


def test_the_parents_journal_reads_none_everywhere(journal):
    old = [{k: v for k, v in e.items()
            if k not in ("calls", "gc", "off", "late", "longest")}
           for e in journal if e["event"] not in ("loop_stall", "gc_pause")]
    ctx = traced(old)
    assert loop_busy.read(ctx) is not None  # what it read before, it reads
    for name in NEW:
        assert S.reader(name)(ctx) is None, name
    ctx["trace"]["capture"] = capture([("host/gc", 10 * MS, 5 * MS)])
    assert S.reader("idle_under_gc_share")(ctx) is None


def test_every_new_metric_is_declared_with_its_reader(journal):
    ctx = traced(journal)
    ctx["trace"]["capture"] = capture([])
    per_layer = {m["name"]: m for m in S.manifest()["per_layer"]}
    for name in NEW + ("idle_under_gc_share",):
        m = per_layer[name]
        assert m["layer"] == "engine loop"
        # journal events are spans of the program's, a capture the device's
        assert m["source"] == ("device_trace" if name.startswith("idle_")
                               else "program_span")
        assert S.reader(name)(ctx) is not None, name
    assert per_layer["idle_under_gc_share"]["workloads"] == [
        "solar-open2-250b-int8-ep8.decode-saturated",
        "mistral-7b-bf16-tp4.decode-saturated"]
    names = [m["name"] for m in S.manifest()["per_layer"]]
    at = [names.index(n) for n in NEW + ("idle_under_gc_share",)]
    assert at == sorted(at) and at[0] > names.index("idle_attributed_share")


# ---- idle under a host span, on the trace's clock ------------------------ #


def capture(host_events, ops=None):
    """A 100 ms marked window on one chip that idles in [20, 30) and
    [60, 64) ms."""
    ops = ops or [("fusion.1", 0.0, 20 * MS), ("fusion.2", 30 * MS, 30 * MS),
                  ("fusion.3", 64 * MS, 36 * MS)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": {"XLA Ops": list(ops)}},
        {"name": "/host:CPU", "lines": {"python3": [
            (TRD.WINDOW_MARK, 0.0, 100 * MS),
            ("loop/process", 0.0, 100 * MS)] + list(host_events)}},
    ], "dispatch": []}


def test_idle_under_a_collection_is_the_overlap_of_its_spans(journal):
    ctx = traced(journal)
    # one collection covers 6 of the first gap's 10 ms, another lies under
    # busy time, a third covers the second gap whole; a span of another name
    # counts for nothing
    ctx["trace"]["capture"] = capture([
        ("host/gc", 24 * MS, 10 * MS), ("host/gc", 40 * MS, 5 * MS),
        ("host/gc", 59 * MS, 6 * MS), ("call/ctrl_upload", 20 * MS, 10 * MS)])
    got = span_idle.read(ctx, "host/gc", edge_ms=0.0)
    assert got == pytest.approx(100.0 * (6.0 + 4.0) / 14.0)
    # the planes went to idle_phases as they were: it still reads its own
    from benchmark.reducers import idle_phases
    assert idle_phases.read(ctx, edge_ms=0.0) == pytest.approx(100.0)
    # no collection in the capture
    ctx["trace"]["capture"] = capture([])
    assert span_idle.read(ctx, "host/gc", edge_ms=0.0) == 0.0
    # no capture, or no window mark
    assert span_idle.read({"journal": journal}, "host/gc") is None
    cap = capture([("host/gc", 24 * MS, 10 * MS)])
    cap["planes"][1]["lines"]["python3"] = [("host/gc", 24 * MS, 10 * MS)]
    ctx["trace"]["capture"] = cap
    assert span_idle.read(ctx, "host/gc") is None


# ---- tools/cell_journal.py: the window by the gauges alone ---------------- #


def test_the_gauges_rise_is_the_window_and_is_held_against_the_journal(
        journal):
    from tools import cell_journal

    before = {"loop_blocks": 5.0, "loop_host_ms_total": 1000.0,
              "loop_blocked_ms_total": 100.0, "loop_call_ms_total": 400.0,
              "loop_gc_ms_total": 1.0, "loop_off_cpu_ms_total": 50.0,
              "loop_stalls": 2.0, "host_gc_pauses": 10.0,
              "host_gc_gen2_pauses": 1.0, "host_gc_pause_ms_total": 20.0}
    in_calls = 50.0 + 80.0 + 15.0 + 90.0  # the journal's, every window
    after = {"loop_blocks": 8.0, "loop_host_ms_total": 9620.0,
             "loop_blocked_ms_total": 850.0,
             "loop_call_ms_total": 400.0 + in_calls,
             "loop_gc_ms_total": 11.0, "loop_off_cpu_ms_total": 3953.0,
             "loop_stalls": 6.0, "host_gc_pauses": 14.0,
             "host_gc_gen2_pauses": 4.0, "host_gc_pause_ms_total": 612.0,
             "loop_stretch_ms_max": 2500.0, "loop_late_ms_max": 900.0,
             "host_gc_pause_ms_max": 500.0}
    dump = {"events": journal, "before": before, "after": after}
    rise, since, held = cell_journal.counters(dump)
    assert ("3 blocks, busy 7870.0 ms = in call 235.0 + collector 10.0 + "
            "off-CPU 3903.0 + python 3722.0; 4 stalls") in rise
    assert "collected 4 times for 592.0 ms, 3 of them generation 2" in rise
    assert "loop_stretch_ms_max 2500.0, loop_late_ms_max 900.0" in since
    assert held.endswith("holds 235.0 of the 235.0 ms in calls the gauges "
                         "count")
    # a ring that overwrote the first windows holds less than the gauges
    dump["events"] = journal[4:]
    assert cell_journal.counters(dump)[2].endswith(
        "the ring has overwritten part of the window")
    # ... unless the second scrape came late (a traced run parses its
    # capture before it scrapes): then that is what the line says
    dump["scraped_after_s"] = 14.2
    assert cell_journal.counters(dump)[2].endswith(
        "the second scrape came 14.2 s after the window's end")
    # the parent's scrapes have no such gauges: nothing is printed
    old = {k: v for k, v in after.items() if k == "loop_blocks"}
    assert cell_journal.counters({"events": [], "before": old,
                                  "after": old}) == []
