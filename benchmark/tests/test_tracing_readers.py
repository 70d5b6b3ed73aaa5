"""The readers PR 24 added, on hand-made planes and journal lists (the form
test_trace_reduce.py uses). Every one of them returns None on a context that
lacks what it reads, so the parent commit's run and the rehearsal still print
a line."""

import pytest

from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import capture as CAP
from benchmark.reducers import (idle_phases, journal_ratio, journal_wait,
                                kernel_ops, loop_busy, module_share)

MS = 1e6  # ns
NEW = ("decode_rows_useful_share", "decode_rows_overshoot_share",
       "decode_join_wait_ms_p50", "queue_wait_ms_p95",
       "loop_host_busy_ms_per_block", "admit_device_share",
       "kernel_time_share", "paged_attention_hbm_roofline_share",
       "quant_matmul_hbm_roofline_share", "idle_attributed_share")


def chip(n, ops, modules=()):
    return {"name": f"/device:TPU:{n}",
            "lines": {"XLA Ops": list(ops), "XLA Modules": list(modules)}}


def host(events):
    return {"name": "/host:CPU", "lines": {"python3": list(events)}}


def counters(before, after):
    return {"before": {"metrics": before}, "after": {"metrics": after}}


# ---- the row account and the loop's busy time, from the journal ---------- #


def ev(t, event, rid="", a=0.0, b=0.0, phases=None):
    e = {"t": t, "event": event, "rid": rid, "slot": 0, "a": a, "b": b}
    if phases is not None:
        e["phases"] = phases
    return e


def test_row_shares_are_sums_over_the_windows_blocks_in_percent():
    journal = [ev(1.0, "decode_rows", a=2048.0, b=1400.0),
               ev(1.0, "decode_rows_lost", a=500.0, b=148.0),
               ev(2.0, "loop_iter"),
               ev(4.0, "decode_rows", a=512.0, b=200.0),
               ev(4.0, "decode_rows_lost", a=300.0, b=12.0)]
    ctx = {"journal": journal}
    assert journal_ratio.read(ctx, ["decode_rows", "b"], ["decode_rows", "a"]) \
        == pytest.approx(100.0 * 1600 / 2560)
    assert journal_ratio.read(ctx, ["decode_rows_lost", "a"], ["decode_rows", "a"]) \
        == pytest.approx(100.0 * 800 / 2560)
    # no block processed in the window, or a program that keeps no account
    assert journal_ratio.read({"journal": journal[2:3]}, ["decode_rows", "b"],
                              ["decode_rows", "a"]) is None


def test_loop_busy_time_leaves_out_wait_and_the_blocked_pull():
    journal = [
        ev(0.5, "loop_iter", phases={"process": 900.0, "pull": 5.0}),  # before the span
        ev(1.0, "decode_block", a=64.0),
        ev(1.1, "loop_iter", phases={"commit": 40.0, "dispatch": 20.0, "wait": 7.0}),
        ev(4.0, "loop_iter", phases={"pull": 2900.0, "process": 100.0}),
        ev(4.1, "decode_block", a=64.0),
        ev(4.2, "loop_iter", phases={"admit": 30.0, "prep": 10.0}),
        ev(9.0, "decode_block", a=64.0),                               # after it
    ]
    # the span's first window began before it and is left out
    ctx = {"journal": journal, "trace": {"t_start": 0.9, "t_end": 5.0}}
    assert loop_busy.read(ctx) == pytest.approx(140.0 / 2)
    # an untraced run: the whole window, again without its first loop_iter
    assert loop_busy.read({"journal": journal, "trace": None}) == \
        pytest.approx(200.0 / 3)
    # the parent's phase vectors have no pull: its host time is not busy time
    old = [ev(1.0, "decode_block", a=64.0), ev(1.05, "loop_iter", phases={}),
           ev(1.1, "loop_iter", phases={"process": 3000.0, "commit": 40.0})]
    assert loop_busy.read({"journal": old, "trace": None}) is None


# ---- journal ------------------------------------------------------------ #


def test_join_wait_counts_only_requests_whose_both_events_are_in_the_window():
    journal = [
        ev(0.0, "decode_first", "early"),      # its first_token was before the window
        ev(1.0, "first_token", "a"), ev(1.5, "loop_iter"),
        ev(2.0, "first_token", "b"),
        ev(4.0, "decode_first", "a"),          # 3,000 ms
        ev(4.0, "decode_first", "b"),          # 2,000 ms
        ev(5.0, "first_token", "c"),
        ev(6.0, "decode_first", "c"),          # 1,000 ms
        ev(7.0, "first_token", "late"),        # its decode_first falls outside
    ]
    assert sorted(journal_wait.waits_ms(journal, "first_token", "decode_first")) \
        == pytest.approx([1000.0, 2000.0, 3000.0])
    ctx = {"journal": journal}
    assert journal_wait.read(ctx, "first_token", "decode_first", 50) == \
        pytest.approx(2000.0)
    assert journal_wait.read(ctx, "first_token", "decode_first", 95) == \
        pytest.approx(2900.0)
    assert journal_wait.read(ctx, "queued", "admitted", 95) is None
    # a traced run: only what ended before the capture was read (t_end)
    traced = {"journal": journal, "trace": {"t_start": 0.0, "t_end": 4.5}}
    assert journal_wait.read(traced, "first_token", "decode_first", 50,
                             until_capture_read=True) == pytest.approx(2500.0)
    assert journal_wait.read(traced, "first_token", "decode_first", 50) == \
        pytest.approx(2000.0)


def test_queue_wait_pairs_the_first_admission_after_each_queued():
    journal = [ev(0.0, "queued", "a"), ev(0.1, "queued", "b"),
               ev(0.3, "admitted", "a"), ev(1.1, "admitted", "b"),
               ev(2.0, "admitted", "b")]   # a resume is not a second wait
    assert journal_wait.waits_ms(journal, "queued", "admitted") == \
        pytest.approx([300.0, 1000.0])


# ---- modules ------------------------------------------------------------ #


def reduced(modules):
    return {"trace": {"reduced": {"modules": {
        n: {"count": 1, "total_s": t, "whole": {"count": 1, "mean_s": t}}
        for n, t in modules.items()}}}}


def test_module_share_is_by_name_prefix():
    ctx = reduced({"jit_decode_block(1)": 9.6, "jit_admit(2)": 0.2,
                   "jit_admit_cached_paged(3)": 0.1,
                   "jit_prefill_chunk_final(4)": 0.05, "jit_scatter(5)": 0.05})
    got = module_share.read(ctx, ["jit_admit", "jit_prefill_chunk"])
    assert got == pytest.approx(100.0 * 0.35 / 10.0)
    # a decode block alone: the share is 0, not absent
    assert module_share.read(reduced({"jit_decode_block(1)": 1.0}),
                             ["jit_admit"]) == 0.0
    # the parent's capture names every program jit_wrapped: nothing to say
    assert module_share.read(reduced({"jit_wrapped(1)": 9.0,
                                      "jit_wrapped(2)": 1.0}),
                             ["jit_admit"]) is None


# ---- kernels ------------------------------------------------------------ #

PA = "%paged_attention.6 = (f32[32,8,4,128]{3,2,1,0}) custom-call(s32[32,32] %x)"
MM = "%int8_matmul.47 = bf16[1,32,14336]{2,1,0} custom-call(bf16[1,32,4096] %y)"
UN = "%int8_unembed.1 = f32[32,32000]{1,0} custom-call(bf16[32,4096] %h)"
CP = "%dynamic-slice_bitcast_fusion.9 = bf16[257,128,8,128]{3,2,1,0} fusion(%p)"


def block_ops(t0):
    """One 10 ms decode block of 2 steps: a `while` envelope over, per step,
    attention 1 ms, a copy 2 ms, a matmul 1.5 ms; then the head 0.5 ms."""
    ops = [("%while.34 = (s32[]) while(%t)", t0, 10 * MS)]
    for s in range(2):
        a = t0 + s * 5 * MS
        ops += [(PA, a, 1 * MS), (CP, a + 1 * MS, 2 * MS),
                (MM, a + 3 * MS, 1.5 * MS), (UN, a + 4.5 * MS, 0.5 * MS)]
    return ops


def kernel_capture(n=2):
    ops, mods = [], []
    for k in range(4):                      # the first is cut by the capture
        ops += block_ops(k * 10 * MS)
        mods.append(("jit_decode_block(7)", k * 10 * MS, 10 * MS))
    # an admission between nothing and nothing: its matmuls are not a step's
    ops.append((MM, 40 * MS, 3 * MS))
    mods.append(("jit_admit(9)", 40 * MS, 3 * MS))
    mods.append(("jit_decode_block(7)", 43 * MS, 1 * MS))
    ops.append(("%while.34 = (s32[]) while(%t)", 43 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 30})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def test_kernel_names_are_read_off_the_instruction_name():
    assert CAP.kernel_of(PA) == "paged_attention"
    assert CAP.kernel_of("%int4_matmul = bf16[8] custom-call()") == "int4_matmul"
    assert CAP.kernel_of("int8_matmul.3") == "int8_matmul"
    assert CAP.kernel_of(CP) is None
    assert CAP.kernel_of("%closed_call.67 = f32[8] custom-call()") is None
    assert CAP.kernel_of("%paged_attention_helper.1 = f32[8] fusion()") is None


def test_kernel_self_time_with_nested_while_envelopes():
    cap = kernel_capture()
    ops = cap["planes"][0]["lines"]["XLA Ops"]
    self_ns = CAP.kernel_self_ns(ops)
    assert self_ns["paged_attention"] == pytest.approx(8 * 1 * MS)
    assert self_ns["int8_matmul"] == pytest.approx(8 * 1.5 * MS + 3 * MS)
    assert self_ns["int8_unembed"] == pytest.approx(8 * 0.5 * MS)
    # kernels 27 ms of 44 ms busy; the envelope's own time is not a kernel's
    assert kernel_ops.time_share(cap) == pytest.approx(100.0 * 27 / 44)


def test_kernel_time_per_step_is_taken_inside_whole_decode_blocks():
    cap = kernel_capture()
    assert CAP.block_steps(cap) == 2.0
    runs = CAP.whole_runs_of(cap["planes"][0], CAP.DECODE_BLOCK)
    assert runs == [(10 * MS, 20 * MS), (20 * MS, 30 * MS), (30 * MS, 40 * MS)]
    assert kernel_ops.per_step_s(cap, ("paged_attention",)) == pytest.approx(1e-3)
    # the admission's matmul is outside every decode block
    assert kernel_ops.per_step_s(cap, kernel_ops.QUANT) == pytest.approx(2e-3)
    cap["dispatch"] = []      # no annotation: no step count, no number
    assert kernel_ops.per_step_s(cap, ("paged_attention",)) is None


def roofline_ctx(cap):
    config = S.config("mistral-7b-int8")
    return {"trace": {"capture": cap, "t_start": 0.0, "t_end": 1.0},
            "stamps": {"requests": [
                {"send": -1.0, "end": None, "prompt_tokens": 100, "chunks": [-0.5] * 28}]},
            "config": config, "cell": {"chips": 1},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_kernel_rooflines_use_the_benchmarks_byte_counts():
    from benchmark.harness import costs

    ctx = roofline_ctx(kernel_capture())
    cfg = ctx["config"]
    kv = 128 * costs.kv_bytes_per_token(cfg, cfg["bytes_per_kv"])
    assert kernel_ops.read(ctx, "paged_attention_roofline") == pytest.approx(
        100.0 * (kv / 819e9) / 1e-3)
    w = costs.weight_bytes(cfg, cfg["bytes_per_weight"])
    assert kernel_ops.read(ctx, "quant_matmul_roofline") == pytest.approx(
        100.0 * (w / 819e9) / 2e-3)
    with pytest.raises(ValueError):
        kernel_ops.read(ctx, "no_such_metric")


def test_a_capture_without_named_kernels_gives_no_kernel_metric():
    ops = [("%closed_call.67 = f32[8] custom-call()", 0.0, 5 * MS), (CP, 5 * MS, 5 * MS)]
    cap = {"planes": [chip(0, ops, [("jit_wrapped(1)", 0.0, 10 * MS)] * 3)],
           "dispatch": []}
    ctx = roofline_ctx(cap)
    for metric in ("time_share", "paged_attention_roofline", "quant_matmul_roofline"):
        assert kernel_ops.read(ctx, metric) is None


# ---- idle --------------------------------------------------------------- #


def test_idle_overlap_with_a_gap_that_straddles_two_phase_spans():
    # window 0..100 ms; the chip idles 40..60 ms and 90..100 ms
    spans = [(TRD.WINDOW_MARK, 0.0, 100 * MS),
             ("loop/pull", 0.0, 45 * MS),        # 5 ms of the first gap
             ("loop/process", 45 * MS, 10 * MS),  # 10 ms of it
             # 55..60 ms lies under no span
             ("loop/dispatch", 60 * MS, 5 * MS),
             ("loop/wait", 92 * MS, 20 * MS),     # 8 ms of the second gap
             ("dispatch/decode_block", 60 * MS, 1 * MS)]
    ops = [("f", -5 * MS, 45 * MS), ("f", 60 * MS, 30 * MS)]
    planes = [host(spans), chip(0, ops)]
    idle, per = idle_phases.by_phase(planes)
    assert idle == pytest.approx(30 * MS)
    assert per == pytest.approx({"pull": 5 * MS, "process": 10 * MS, "wait": 8 * MS})
    ctx = {"trace": {"capture": {"planes": planes, "dispatch": []}}}
    assert idle_phases.read(ctx, edge_ms=0.0) == pytest.approx(100.0 * 23 / 30)
    # the window's last 4 ms left out (the span open at a capture's end is
    # lost, and so is the device op it cut): 6 ms of the second gap remain
    idle, per = idle_phases.by_phase(planes, edge_ms=4.0)
    assert idle == pytest.approx(26 * MS) and per["wait"] == pytest.approx(4 * MS)
    assert idle_phases.read(ctx, edge_ms=4.0) == pytest.approx(100.0 * 19 / 26)
    # no loop span (the parent), no window mark, or no chip's plane: nothing
    assert idle_phases.by_phase([host(spans[:1]), chip(0, ops)]) is None
    assert idle_phases.by_phase([host(spans[1:]), chip(0, ops)]) is None
    assert idle_phases.by_phase([host(spans)]) is None


def test_idle_share_has_a_value_however_little_the_chip_idles():
    # a traced run of the change has to report the metric: 3 us of idle in a
    # 100 ms window, 2 of them under a span; and a chip that never idles
    spans = [(TRD.WINDOW_MARK, 0.0, 100 * MS), ("loop/pull", 0.0, 50.002 * MS),
             ("loop/process", 50.003 * MS, 49.997 * MS)]
    ops = [("f", 0.0, 50 * MS), ("f", 50.003 * MS, 49.997 * MS)]
    ctx = {"trace": {"capture": {"planes": [host(spans), chip(0, ops)],
                                 "dispatch": []}}}
    assert idle_phases.read(ctx, edge_ms=0.0) == pytest.approx(100.0 * 2 / 3)
    ctx["trace"]["capture"]["planes"][1] = chip(0, [("f", 0.0, 100 * MS)])
    assert idle_phases.read(ctx, edge_ms=0.0) == 100.0


def test_two_chips_sum_their_idle():
    spans = [(TRD.WINDOW_MARK, 0.0, 10 * MS), ("loop/wait", 0.0, 10 * MS)]
    planes = [host(spans), chip(0, [("f", 0.0, 8 * MS)]),
              chip(1, [("f", 0.0, 6 * MS)])]
    idle, per = idle_phases.by_phase(planes)
    assert idle == pytest.approx(6 * MS) and per == pytest.approx({"wait": 6 * MS})


# ---- every reader on a context that lacks what it reads -------------------- #


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_the_parents_context(name):
    """The parent's run: counters and journal without the new keys and
    events; once with a trace whose capture holds only `jit_wrapped` modules
    and no span, once without a trace at all (--trace 1 on the CPU
    rehearsal). Neither raises, both give None; only the queue wait, whose
    two events the parent journals already, may give a value."""
    old = {"loop_host_ms_total": 5.0, "loop_blocks": 2.0, "tokens_generated": 9.0}
    journal = [ev(0.0, "first_token", "a"), ev(1.0, "terminal", "a")]
    planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
              chip(0, [("%closed_call.67 = f32[8] custom-call()", 0.0, 9 * MS)],
                   [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
    base = {**counters(old, {k: v + 1 for k, v in old.items()}),
            "journal": journal, "stamps": {"requests": []},
            "config": S.config("mistral-7b-int8"), "cell": {"chips": 1},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    traced = {**base, "trace": {
        "t_start": 0.0, "t_end": 1.0, "reduced": TRD.reduce(planes),
        "capture": {"planes": planes, "dispatch": []}}}
    for ctx in (traced, {**base, "trace": None},
                {**base, "trace": {"dir": "/nonexistent", "error": "x"}}):
        assert S.reader(name)(ctx) is None


def test_every_new_metric_is_in_the_manifest_with_a_reader_file():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for name in NEW:
        assert listed[name]["layer"] in layers and listed[name]["moves"] in e2e
        assert callable(S.reader(name))
    assert listed["quant_matmul_hbm_roofline_share"]["workloads"] == [
        "mistral-7b-int8.decode-saturated"]
