import os

import pytest

from benchmark.harness import trace_reduce as TRD

MS = 1e6  # ns


def chip(n, ops, modules=()):
    return {"name": f"/device:TPU:{n}",
            "lines": {"XLA Ops": list(ops), "XLA Modules": list(modules)}}


def test_union_self_time_gaps_and_collectives():
    ops = [
        ("while.1", 0 * MS, 10 * MS),          # spans its body's ops
        ("fusion.2", 1 * MS, 3 * MS),
        ("all-reduce.3", 5 * MS, 2 * MS),
        ("fusion.2", 20 * MS, 5 * MS),         # after a 10 ms gap
        ("copy.4", 30 * MS, 10 * MS),          # after a 5 ms gap
    ]
    planes = [chip(0, ops, [("jit_block", 0, 10 * MS), ("jit_block", 20 * MS, 20 * MS)]),
              {"name": "/host:CPU", "lines": {"python": [("x", 0, 100 * MS)]}},
              {"name": "/device:TPU:0 SparseCore 0", "lines": {"XLA Ops": [("y", 0, 99 * MS)]}}]
    r = TRD.reduce(planes)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.040) and r["window_from"] == "device_events"
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["idle_share_worst"] == pytest.approx(1 - 25 / 40)
    assert r["collective_s"] == pytest.approx(0.002)
    ops_self = dict((n, t) for n, t in r["device_ops"])
    assert ops_self["while.1"] == pytest.approx(0.005)   # 10 - 3 - 2
    assert ops_self["fusion.2"] == pytest.approx(0.008)
    assert ops_self["copy.4"] == pytest.approx(0.010)
    assert [g for _, g in r["idle_gaps"]] == pytest.approx([0.010, 0.005])
    m = r["modules"]["jit_block"]
    assert m["count"] == 2 and m["total_s"] == pytest.approx(0.030)
    assert m["whole"] == {"count": 0, "mean_s": None}   # fewer than three runs


def test_whole_executions_leave_out_the_two_cut_ends_by_position():
    # a 12 s capture of 2.72 s blocks: cut, whole, whole, whole, cut. BOTH cut
    # ends are short; dropping by rank would keep one of them in the mean
    mods = [("jit_wrapped(1)", t * 1e9, d * 1e9) for t, d in
            [(0.0, 0.9), (0.9, 2.72), (3.62, 2.72), (6.34, 2.72), (9.06, 1.1)]]
    r = TRD.reduce([chip(0, [("f", 0, 10.16e9)], mods)])
    assert r["modules"]["jit_wrapped(1)"] == {
        "count": 5, "total_s": pytest.approx(0.9 + 3 * 2.72 + 1.1),
        "whole": {"count": 3, "mean_s": pytest.approx(2.72)}}


def test_the_cut_ends_are_the_chips_first_and_last_events_of_any_module():
    # an admission program runs whole between two blocks; the block before
    # it was cut by the capture's start, the last admission by its end
    mods = [("block", 0.0, 1 * MS), ("admit", 1 * MS, 2 * MS),
            ("block", 3 * MS, 5 * MS), ("block", 8 * MS, 5 * MS),
            ("admit", 13 * MS, 1 * MS)]
    two = [chip(n, [("f", 0, 14 * MS)], mods) for n in (0, 1)]
    m = TRD.reduce(two)["modules"]
    assert m["block"]["count"] == 3
    assert m["block"]["whole"] == {"count": 2, "mean_s": pytest.approx(0.005)}
    assert m["admit"]["whole"] == {"count": 1, "mean_s": pytest.approx(0.002)}


def test_idle_is_taken_over_the_hosts_marked_window_not_the_device_events():
    # the host marked 0..100 ms; the chip worked 20..60 ms and 70..90 ms, and
    # an event cut by the capture's start reaches into the window by 5 ms
    host = {"name": "/host:CPU", "lines": {"python3": [
        ("other", -5 * MS, 1 * MS), (TRD.WINDOW_MARK, 0.0, 100 * MS)]}}
    ops = [("f", -10 * MS, 15 * MS), ("f", 20 * MS, 40 * MS),
           ("all-reduce.1", 70 * MS, 20 * MS), ("f", 98 * MS, 30 * MS)]
    r = TRD.reduce([host, chip(0, ops)])
    assert r["window_from"] == "host_mark"
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.005 + 0.040 + 0.020 + 0.002)
    assert r["idle_share_worst"] == pytest.approx(1 - 0.067 / 0.100)
    assert r["collective_s"] == pytest.approx(0.020)
    # the stalls at both edges are gaps like any other
    assert [g for _, g in r["idle_gaps"]] == pytest.approx([0.015, 0.010, 0.008])
    assert r["device_span_s"] == pytest.approx(0.138)


def test_short_names_keep_name_op_and_shape():
    long = ('%closed_call.67 = (f32[32,8,4,128]{3,2,1,0:T(4,128)S(1)}, f32[32,8]{1,0}) '
            'custom-call(s32[32,32]{1,0:T(8,128)S(1)} %copy-done.1), custom_call_target="x"')
    assert TRD.short_name(long) == "closed_call.67 custom-call f32[32,8,4,128]"
    assert TRD.short_name("jit_wrapped(123)") == "jit_wrapped(123)"


def test_two_chips_mean_busy_worst_idle():
    a = chip(0, [("f", 0, 10 * MS)])
    b = chip(1, [("f", 0, 4 * MS), ("f", 9 * MS, 1 * MS)])
    r = TRD.reduce([a, b])
    assert r["chips"] == 2 and r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["idle_share_worst"] == pytest.approx(0.5)
    assert r["busy_s_per_chip"] == pytest.approx([0.010, 0.005])


def test_a_trace_with_no_chip_gives_nothing():
    assert TRD.reduce([{"name": "/host:CPU", "lines": {"t": [("x", 0, 5)]}}]) == {}


def test_the_hosts_mark_is_found_in_a_real_capture(tmp_path):
    """What `run.traced_window` does, on the CPU backend: the annotation is a
    host event on the capture's clock, as long as the marked sleep."""
    import time

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TRD.WINDOW_MARK):
        time.sleep(0.2)
    jax.profiler.stop_trace()
    planes = TRD.load_planes(TRD.find_xplane(str(tmp_path)))
    lo, hi = TRD.marked_window(planes)
    assert 0.2 <= (hi - lo) / 1e9 < 0.3


def test_recorded_trace_reduces_to_known_numbers():
    """A small `.xplane.pb` recorded on the chip (see its .json beside it for
    what was run and what the reduction gave when it was recorded)."""
    import json

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    path = os.path.join(here, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in benchmark/tests/data")
    with open(os.path.join(here, "small.xplane.json")) as f:
        want = json.load(f)
    r = TRD.reduce(TRD.load_planes(path))
    assert r["chips"] == want["chips"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["device_ops"][0][0] == want["device_ops"][0][0]
