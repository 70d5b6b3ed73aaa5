"""The readers PR 37 added: the raw `.xplane.pb` reader on the recorded trace,
and device time by (program, scope) and per prompt token on hand-made planes.
Each returns None on a context that lacks what it reads, so the parent
commit's run and the rehearsal still print a line."""

import io
import os
import sys

import pytest

from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X
from benchmark.reducers import admit_per_token, journal_ratio, scope_share

MS = 1e6  # ns
DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
ADMIT = ["jit_admit", "jit_prefill_chunk"]
NEW = ("admit_rows_useful_share", "admit_device_us_per_prompt_token",
       "admit_attention_mix_share", "admit_mlp_share",
       "admit_layer_slices_share", "device_unscoped_share",
       "device_control_share")


def op(name, start, dur, pid, tf_op="", flops=0.0, nbytes=0.0):
    return X.Op(name, start, dur, pid, tf_op, flops, nbytes)


def chip(n, ops, modules):
    return {"name": f"/device:TPU:{n}", "ops": list(ops),
            "modules": [X.Module(name, s, d, X.fingerprint(name))
                        for name, s, d in modules],
            "dispatch": [],
            "lines": {"XLA Ops": [(o.name, o.start_ns, o.dur_ns) for o in ops],
                      "XLA Modules": list(modules)}}


def host(events=(), dispatch=()):
    return {"name": "/host:CPU", "ops": [], "modules": [],
            "dispatch": list(dispatch), "lines": {"python3": list(events)}}


# ---- the raw reader, on the trace recorded on a v5 lite --------------------- #


def test_the_raw_reader_finds_what_profiledata_hides():
    planes = X.read_planes(DATA)
    tpu = [p for p in planes if p["name"] == "/device:TPU:0"]
    assert len(tpu) == 1 and len(tpu[0]["modules"]) == 12
    fusion = [o for o in tpu[0]["ops"] if o.name.startswith("%fusion = ")]
    assert len(fusion) == 12
    assert {o.tf_op for o in fusion} == {"jit(<lambda>)/dot_general:"}
    assert {o.program_id for o in fusion} == {15110319609580777085}
    assert {(o.flops, o.bytes_accessed) for o in fusion} == {(33816576.0, 393216.0)}
    assert {m.name for m in tpu[0]["modules"]} == {
        "jit__lambda(15110319609580777085)"}
    assert {m.program_id for m in tpu[0]["modules"]} == {15110319609580777085}
    # nothing of tensorflow was needed to read it
    assert "tensorflow" not in sys.modules


def test_the_raw_reader_sees_the_events_profiledata_sees():
    mine = {p["name"]: p for p in X.read_planes(DATA)}
    for theirs in TRD.load_planes(DATA):
        lines = mine[theirs["name"]]["lines"]
        assert set(lines) == set(theirs["lines"])
        for name, events in theirs["lines"].items():
            assert [e[0] for e in events] == [e[0] for e in lines[name]]
            for a, b in zip(events, lines[name]):
                assert abs(a[1] - b[1]) < 1.0 and abs(a[2] - b[2]) < 1.0
    red = TRD.reduce([{"name": n, "lines": p["lines"]} for n, p in mine.items()])
    assert red["chips"] == 1 and red["busy_s"] > 0


def test_self_time_per_event_leaves_a_while_none_of_its_bodys():
    ops = [op("%while.1", 0, 100, 1), op("%a", 10, 30, 1), op("%b", 50, 40, 1),
           op("%c", 200, 10, 1)]
    own, parent = X.self_ns(ops)
    assert own == [30.0, 30.0, 40.0, 10.0]
    assert parent == [True, False, False, False]


# ---- device time by program and scope --------------------------------------- #


def test_an_op_belongs_to_the_leaf_its_path_ends_in():
    leaf = scope_share.leaf_of
    assert leaf("jit(decode_block)/control/while/body/layer/while/body/"
                "attention/proj/dot_general:") == "attention/proj"
    assert leaf("jit(admit)/layer/while/body/rms_norm/mul:") == "layer"
    assert leaf("jit(admit)/layer/while/body/cond/branch_1_fun/mlp/experts/"
                "layer_weights/dynamic_slice:") == "slices"
    assert leaf("jit(admit)/shard_map/layer/while/body/closed_call/attention/"
                "mix/reshape;attention/mix/reshape:") == "attention/mix"
    assert leaf("jit(decode_block)/control/while/body/sample/jit(_where)/"
                "select_n:") == "sample"
    # the parent's bare scopes, a bare primitive, no name at all
    assert leaf("jit(admit)/while/body/attention/dot_general:") == "none"
    assert leaf("jit(admit)/while/body/mlp/dot_general:") == "none"
    assert leaf("shift_right_logical:") == "none"
    # XLA:TPU's own name for what it rewrites `lax.ragged_dot` into
    assert leaf("ragged-dot-none.1:") == "mlp/experts"
    assert leaf("") == "none"


def _scoped_planes():
    """Two programs on one chip, a 100 ms window marked by the host; an
    admission cut by the window's start (its ops before the mark are out)."""
    A, D = 11, 22
    ops = [
        # admission, whole: a while spanning three ops and a copy XLA made
        op("%while.3", 10 * MS, 38 * MS, A, "jit(admit)/layer/while:"),
        op("%fusion.1", 12 * MS, 10 * MS, A,
           "jit(admit)/layer/while/body/attention/mix/dot_general:", 4e9, 2e6),
        op("%ragged-dot.1", 22 * MS, 20 * MS, A,
           "jit(admit)/layer/while/body/mlp/experts/ragged_dot:", 8e9, 4e6),
        op("%slice.1", 42 * MS, 4 * MS, A,
           "jit(admit)/layer/while/body/mlp/experts/layer_weights/dynamic_slice:"),
        op("%copy-start.1", 50 * MS, 2 * MS, A),
        # decode block
        op("%paged_attention.1", 60 * MS, 30 * MS, D,
           "jit(decode_block)/control/while/body/layer/while/body/attention/"
           "mix/paged_attention/pallas_call:"),
        op("%fusion.9", 90 * MS, 6 * MS, D,
           "jit(decode_block)/control/while/body/sample/argmax:"),
        # before the mark: not counted
        op("%fusion.1", -5 * MS, 4 * MS, A,
           "jit(admit)/layer/while/body/attention/mix/dot_general:"),
    ]
    modules = [("jit_admit(11)", -6 * MS, 5 * MS), ("jit_admit(11)", 10 * MS, 42 * MS),
               ("jit_decode_block(22)", 60 * MS, 36 * MS)]
    return [chip(0, ops, modules),
            host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]


def test_scope_share_is_self_time_inside_the_window_over_the_programs_own():
    ctx = {"trace": {"xplanes": _scoped_planes()}}
    # admission inside the window: while 4 (38 less 34 nested), mix 10,
    # experts 20, slice 4, unnamed copy 2 = 40 ms
    assert scope_share.read(ctx, "attention/mix", ADMIT) == pytest.approx(25.0)
    assert scope_share.read(ctx, "mlp", ADMIT) == pytest.approx(50.0)
    assert scope_share.read(ctx, "slices", ADMIT) == pytest.approx(10.0)
    assert scope_share.read(ctx, "layer", ADMIT) == pytest.approx(10.0)
    assert scope_share.read(ctx, "none", ADMIT) == pytest.approx(5.0)
    # all programs: 2 of 76 ms carry no scope
    assert scope_share.read(ctx, "none") == pytest.approx(100.0 * 2 / 76)
    assert scope_share.read(ctx, "attention/mix", ["jit_decode_block"]) \
        == pytest.approx(100.0 * 30 / 36)
    assert scope_share.read(ctx, "mlp", ["jit_spec_block"]) is None


def test_an_op_under_no_leaf_inside_an_engine_program_reads_control_not_none():
    """Every engine program's body is traced under `control`, so an op that
    fell out of its scope moves `device_control_share`, and
    `device_unscoped_share` only where jax's name is lost on the way."""
    planes = _scoped_planes()
    planes[0]["ops"].append(op(
        "%fusion.7", 96 * MS, 4 * MS, 22,
        "jit(decode_block)/control/while/body/while/body/dot_general:"))
    ctx = {"trace": {"xplanes": planes}}
    spec = S._json(S.BENCH, "layer_metrics", "device_control_share.json")
    assert spec["reducer"] == "scope_share"
    assert scope_share.read(ctx, **spec["args"]) == pytest.approx(100.0 * 4 / 80)
    assert scope_share.read(ctx, "none") == pytest.approx(100.0 * 2 / 80)


def test_the_table_names_every_program_and_scope_with_xlas_counts():
    tables = scope_share.account(_scoped_planes())
    assert tables[0][("jit_admit", "mlp/experts")] == [20 * MS, 8e9, 4e6]
    assert tables[0][("jit_admit", "layer")][1] == 0.0  # a while's counts are its body's
    out = io.StringIO()
    scope_share.print_table(tables, S.peaks("TPU v5 lite"), 0.1, out=out)
    text = out.getvalue()
    assert "jit_admit: 40.000 ms" in text and "jit_decode_block: 36.000 ms" in text
    assert "attention/mix" in text and "of peak: flops" in text


def test_scope_share_is_the_mean_over_chips_and_none_without_scopes():
    one = _scoped_planes()
    two = chip(1, [op("%f", 10 * MS, 10 * MS, 11,
                      "jit(admit)/layer/while/body/attention/mix/dot_general:"),
                   op("%g", 20 * MS, 30 * MS, 11,
                      "jit(admit)/layer/while/body/mlp/dense/dot_general:")],
               [("jit_admit(11)", 10 * MS, 40 * MS)])
    ctx = {"trace": {"xplanes": one + [two]}}
    assert scope_share.read(ctx, "attention/mix", ADMIT) == pytest.approx((25.0 + 25.0) / 2)
    # a program that writes no scope of the vocabulary: nothing to report
    bare = [chip(0, [op("%f", 10 * MS, 10 * MS, 11, "jit(admit)/while/body/attention/dot_general:")],
                 [("jit_admit(11)", 10 * MS, 10 * MS)]),
            host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]
    assert scope_share.read({"trace": {"xplanes": bare}}, "none") is None
    assert scope_share.read({"trace": None}, "none") is None
    assert scope_share.read({}, "none") is None


# ---- device time per prompt token ------------------------------------------- #


def _span(name, start, m, bucket, tokens):
    return (name, start, 1 * MS, {"m": m, "bucket": bucket, "tokens": tokens})


def _admissions():
    """A decode block cut by the capture's start, an admission dispatched
    BEFORE the capture began (no span), three admissions with spans, the last
    one cut by the capture's end."""
    modules = [
        ("jit_decode_block(22)", 0, 9 * MS),      # cut: first of the line
        ("jit_admit(11)", 10 * MS, 5 * MS),       # its span was never recorded
        ("jit_admit(11)", 20 * MS, 6 * MS),       # (2, 256) 400 tokens
        ("jit_decode_block(22)", 26 * MS, 9 * MS),
        ("jit_admit(33)", 40 * MS, 3 * MS),       # (1, 128) 100 tokens
        ("jit_admit(11)", 50 * MS, 8 * MS),       # cut: last of the line
    ]
    dispatch = [_span("dispatch/admit", 15 * MS, 2, 256, 400),
                _span("dispatch/admit", 30 * MS, 1, 128, 100),
                _span("dispatch/admit", 45 * MS, 2, 256, 300),
                ("dispatch/decode_block", 5 * MS, 1 * MS, {"n": 64})]
    return [chip(0, [], modules), host(dispatch=dispatch)]


def test_admission_executions_pair_with_their_dispatch_spans_in_order():
    planes = _admissions()
    # offset 0 would pair (2, 256) with fingerprint 11, then (1, 128) with 11
    # too: refused; offset 1 is consistent and every span precedes its run
    assert [c[:3] for c in admit_per_token.per_chip(planes, ADMIT)] \
        == [(9 * MS, 500.0, 2)]
    ctx = {"trace": {"xplanes": planes}}
    assert admit_per_token.read(ctx, ADMIT) == pytest.approx(9e3 / 500)


def test_an_execution_cut_by_the_captures_edge_is_left_out():
    planes = _admissions()
    planes[0]["modules"].append(X.Module("jit_decode_block(22)", 60 * MS, 2 * MS, 22))
    # now the third admission is whole: 8 ms and 300 tokens more
    assert [c[:3] for c in admit_per_token.per_chip(planes, ADMIT)] \
        == [(17 * MS, 800.0, 3)]


def test_no_pairing_is_better_than_a_wrong_one():
    planes = _admissions()
    # the same (m, bucket) twice where the programs differ: offset 1 is out,
    # and any later offset leaves most spans without an execution
    planes[1]["dispatch"][1] = _span("dispatch/admit", 30 * MS, 2, 256, 100)
    assert admit_per_token.pair(
        admit_per_token.admission_runs(planes[0], ADMIT),
        admit_per_token.spans(planes, ADMIT)) is None
    # a broken pairing shows as a missing metric, not as an approximate one
    assert admit_per_token.read({"trace": {"xplanes": planes}}, ADMIT) is None
    # the parent: spans without `tokens`
    old = _admissions()
    old[1]["dispatch"] = [(n, s, d, {k: v for k, v in st.items() if k != "tokens"})
                          for n, s, d, st in old[1]["dispatch"]]
    assert admit_per_token.read({"trace": {"xplanes": old}}, ADMIT) is None
    assert admit_per_token.read({}, ADMIT) is None


def test_the_cross_check_sets_the_same_executions_on_both_sides():
    """The journal holds one `admit_rows` a program in dispatch order: the
    spans' tokens are a run of its `b`s, and the execution the capture holds
    from before its first span is the event before that run."""
    planes = _admissions()
    journal = [{"t": float(i), "event": "admit_rows", "a": 512.0, "b": b}
               for i, b in enumerate((250.0, 350.0, 400.0, 100.0, 300.0, 120.0))]
    journal.insert(2, {"t": 1.5, "event": "admitted", "a": 350.0, "b": 2.0})
    sp = admit_per_token.spans(planes, ADMIT)
    rows = [e for e in journal if e["event"] == "admit_rows"]
    assert admit_per_token.journal_run(rows, sp) == 2
    assert admit_per_token.journal_run(rows + rows, sp) is None  # two runs
    assert admit_per_token.journal_run(rows[:4], sp) is None     # no run
    ctx = {"trace": {"xplanes": planes, "reduced": {"modules": {
        "jit_admit(11)": {"total_s": 0.019}, "jit_admit(33)": {"total_s": 0.003},
        "jit_decode_block(22)": {"total_s": 0.018}}}}, "journal": journal}
    chips = admit_per_token.per_chip(planes, ADMIT)
    out = io.StringIO()
    # 18 us a token x (350 + 400 + 100) tokens of the three whole executions
    # = 15.3 ms, where the module line holds 5 + 6 + 3 = 14 ms; with the cut
    # one (300 tokens, 8 ms seen) 20.7 against 22 ms
    residual = admit_per_token.crosscheck(ctx, ADMIT, 18.0, chips, sp, out=out)
    assert residual == pytest.approx(100.0 * (15.3 / 14.0 - 1.0))
    text = out.getvalue()
    assert "1.0 executions dispatched before the capture began took 5.000 ms for 350 tokens" in text
    assert "1.0 cut by an end of the capture show 8.000 ms for 300 tokens" in text
    assert f"with the cut ones {100.0 * (20.7 / 22.0 - 1.0):+.2f}%" in text
    # a journal that lost the run: said, and nothing compared
    ctx["journal"] = journal[:3]
    assert admit_per_token.crosscheck(ctx, ADMIT, 18.0, chips, sp, out=out) is None
    assert "no cross-check" in out.getvalue()


def test_per_token_is_the_mean_chips_on_four():
    planes = _admissions()
    second = chip(1, [], [(n, s, 2 * d) for n, s, d in
                          planes[0]["lines"]["XLA Modules"]])
    ctx = {"trace": {"xplanes": [planes[0], second, planes[1]]}}
    assert admit_per_token.read(ctx, ADMIT) == pytest.approx(1.5 * 9e3 / 500)


# ---- the row account and the manifest --------------------------------------- #


def test_admit_rows_useful_share_is_tokens_over_rows_of_the_windows_programs():
    journal = [{"t": 1.0, "event": "admit_rows", "a": 512.0, "b": 300.0},
               {"t": 2.0, "event": "admit_rows", "a": 1024.0, "b": 800.0},
               {"t": 2.0, "event": "admitted", "a": 300.0, "b": 2.0}]
    spec = S._json(S.BENCH, "layer_metrics", "admit_rows_useful_share.json")
    assert spec["reducer"] == "journal_ratio"
    assert journal_ratio.read({"journal": journal}, **spec["args"]) \
        == pytest.approx(100.0 * 1100 / 1536)
    assert journal_ratio.read({"journal": journal[2:]}, **spec["args"]) is None


def test_the_new_metrics_are_registered_for_every_cell_and_read_nothing_from_nothing():
    man = S.manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert "workloads" not in entries[name]
        assert entries[name]["moves"] == "out_tokens_per_s"
        assert len(S._json(S.BENCH, "layer_metrics", f"{name}.json")["what"]) > 40
        assert S.reader(name)({"journal": [], "trace": None}) is None
    for w in man["workloads"]:
        assert set(NEW) <= {m["name"] for m in S.cell(w["name"])["per_layer"]}
