"""What the AI21-Jamba2 configuration brought: `costs_s6_mqa` against the
model's published size and a step's bytes by hand (ISSUE 55's table), its
plain reference's Mamba-1 layer against closed forms (a constant input's
state is a geometric sum; a prompt of one token; the conv's edge) and against
float64 loops, the readers of its five metrics on hand-made captures whose
times are the chip's own (and never over 100%), and its files. It pins
nothing about the manifest's length or order."""

import numpy as np
import pytest

from benchmark.harness import costs_s6_mqa as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import s6_mqa_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.tests import test_scope_readers as SR
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "ai21-jamba2-3b-int8"
CELL = CONFIG + ".decode-reasoning"
NEW = ("s6mqa_scan_state_hbm_roofline_share",
       "s6mqa_proj_matmul_hbm_roofline_share",
       "s6mqa_paged_attention_hbm_roofline_share",
       "s6mqa_decode_hbm_roofline_share", "s6mqa_s6_mix_share")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_published_size():
    cfg = S.config(CONFIG)
    M = 1e6
    assert costs.layers(cfg) == {"s6": 26, "mqa": 2, "all": 28}
    assert costs.d_inner(cfg) == 5120
    s6 = costs.s6_layer_params(cfg)
    # W_in 26.21 M, W_x 0.98 M, W_dt 0.82 M, W_out 13.11 M
    assert s6["int8"] == (2560 * 10240 + 5120 * 192 + 160 * 5120
                          + 5120 * 2560)
    assert s6["int8"] == pytest.approx(41.12 * M, rel=1e-3)
    assert s6["f32"] == 5120 * 16 + 2 * 5120  # A_log, D, the step's bias
    assert s6["bf16"] == 5 * 5120 + 160 + 16 + 16  # taps, bias, three norms
    assert sum(s6.values()) == pytest.approx(41.23 * M, rel=1e-3)
    assert costs.mqa_layer_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert costs.mqa_layer_params(cfg) == pytest.approx(13.76 * M, rel=1e-3)
    assert costs.mlp_params(cfg) == 3 * 2560 * 8192  # 62.91 M
    # the model card's "3B": 1,072 + 27.5 + 1,761.6 + 167.8 M and the norms
    assert costs.param_count(cfg) == pytest.approx(3.03e9, rel=2e-3)
    # a slot's row: 26 layers x (16 x 5120 float32 + 3 x 5120 bf16); a
    # token's K/V: 2 layers x (K + V) x one head of 128 x bf16
    assert costs.state_bytes_per_row(cfg) == 26 * (327_680 + 30_720)
    assert 128 * costs.state_bytes_per_row(cfg) == pytest.approx(
        1.19e9, rel=3e-3)
    assert costs.kv_bytes_per_token(cfg, 2) == 1024
    # the pool of the YAML: 2,689 pages x 128 rows
    y = cfg["yaml"]
    assert (y["kv_pages"] + 1) * y["kv_page_size"] * 1024 == pytest.approx(
        0.35e9, rel=0.01)


def test_a_steps_bytes_by_hand():
    cfg = S.config(CONFIG)
    int8 = (26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
            + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128) + 28 * 3 * 2560 * 8192)
    assert costs.proj_matmul_bytes(cfg, 1) == int8
    small = 26 * (4 * (5120 * 16 + 2 * 5120) + 2 * (5 * 5120 + 192))
    head = 65536 * 2560 * 2  # the tied matrix as held: bfloat16
    w = costs.weight_bytes(cfg, 1)
    assert w == int8 + small + head
    assert w == pytest.approx(3.21e9, rel=3e-3)
    # the kernel: the state read and written, 2 x 327,680 B a slot and layer
    assert costs.s6_state_bytes_per_row(cfg) == 26 * 2 * 327_680
    assert 128 * costs.s6_state_bytes_per_row(cfg) == pytest.approx(
        2.18e9, rel=1e-3)
    operands = 26 * (3 * 5120 + 2 * 16) * 4
    conv = 2 * 26 * 3 * 5120 * 2
    assert 128 * conv == pytest.approx(0.20e9, rel=0.03)
    live = 128 * 1150
    step = costs.decode_step_bytes(cfg, 128, live, 1, 2)
    assert step == w + 128 * (26 * 2 * 327_680 + operands + conv) + live * 1024
    # ISSUE 55's step at 128 slots of some 1,150 tokens, 5.56 GB, counted an
    # int8 head and no operands: 5.95 with the bfloat16 head the tree holds
    # (0.34 GB) and the kernel's rows (0.20 GB)
    assert step == pytest.approx(5.95e9, rel=0.01)
    state = 128 * costs.s6_state_bytes_per_row(cfg)
    assert 0.36 < state / step < 0.40  # the selective update alone
    mixers = 26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert 0.57 < (state + 128 * (operands + conv) + mixers) / step < 0.63
    assert step / 819e9 == pytest.approx(7.26e-3, rel=0.01)  # seconds a step


# ---- the reference against closed forms --------------------------------------- #


def _layer_weights(rng, D, E, N, R, c=4):
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    return {"attn_norm": 1.0 + r_(D) * 0.2, "w_in": r_(D, 2 * E),
            "conv_w": r_(c, E), "conv_b": r_(E) * 0.3, "w_x": r_(E, R + 2 * N),
            "dt_norm": 1.0 + r_(R) * 0.2, "b_norm": 1.0 + r_(N) * 0.2,
            "c_norm": 1.0 + r_(N) * 0.2, "w_dt": r_(R, E),
            "dt_bias": r_(E) - 1.0, "A_logT": r_(N, E),
            "ssm_D": 1.0 + r_(E) * 0.3, "wo": r_(E, D)}


def _numpy_s6_layer(x, w, eps=1e-6, inner_norms=True):
    """One Mamba-1 (`jamba`) layer over x [T, D] in float64 loops."""
    T, D = x.shape
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    rms = lambda a, g: a / np.sqrt(np.mean(a ** 2, -1, keepdims=True) + eps) * g  # noqa: E731
    a = rms(x, w["attn_norm"])
    xz = a @ w["w_in"]
    E = xz.shape[1] // 2
    pre, z = xz[:, :E], xz[:, E:]
    N, R = w["b_norm"].shape[0], w["dt_norm"].shape[0]
    c = w["conv_w"].shape[0]
    A = -np.exp(w["A_logT"])  # [N, E]
    h = np.zeros((N, E))
    out = []
    for t in range(T):
        u = sum(w["conv_w"][i] * pre[t - (c - 1) + i]
                for i in range(c) if t - (c - 1) + i >= 0) + w["conv_b"]
        u = silu(u)
        rbc = u @ w["w_x"]
        r, B, C = rbc[:R], rbc[R:R + N], rbc[R + N:]
        if inner_norms:
            r, B, C = rms(r, w["dt_norm"]), rms(B, w["b_norm"]), rms(C, w["c_norm"])
        d = np.log1p(np.exp(r @ w["w_dt"] + w["dt_bias"]))
        for n in range(N):
            h[n] = np.exp(d * A[n]) * h[n] + d * u * B[n]
        y = (h * C[:, None]).sum(0) + w["ssm_D"] * u
        out.append(x[t] + (y * silu(z[t])) @ w["wo"])
    return np.stack(out)


def _ref_layer(x, w, **kw):
    import jax.numpy as jnp

    from benchmark.reference import s6_mqa_dense as REF

    return np.asarray(REF.s6_layer(
        jnp.asarray(x, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        eps=1e-6, **kw))


@pytest.mark.parametrize("T", [1, 3, 7])  # one token; under the conv's 4 taps
def test_reference_s6_layer_matches_float64_loops(T):
    """A prompt of one token, one shorter than the conv (its edge: zeros
    before the start, tap c-1 on the current token, the bias before the
    silu), and one past it; with and without the inner norms; with
    `kv_round` the held conv inputs and the state are rounded, the first
    token's conv inputs are not."""
    rng = np.random.default_rng(3)
    D, E, N, R = 6, 8, 3, 2
    w = _layer_weights(rng, D, E, N, R)
    x = rng.normal(0.0, 0.5, (T, D))
    got = _ref_layer(x, w)
    np.testing.assert_allclose(got, _numpy_s6_layer(x, w), atol=2e-5)
    plain = _ref_layer(x, w, inner_norms=False)
    np.testing.assert_allclose(plain, _numpy_s6_layer(x, w, inner_norms=False),
                               atol=2e-5)
    assert np.max(np.abs(plain - got)) > 1e-3  # the norms weigh
    held = _ref_layer(x, w, kv_round="fp8")
    assert np.max(np.abs(held[0] - got[0])) < 3e-2  # a bfloat16 state alone
    if T > 1:
        assert np.max(np.abs(held[1:] - got[1:])) > 1e-4
    with pytest.raises(ValueError):
        _ref_layer(x, w, kv_round="fp4")


def test_a_constant_inputs_state_is_a_geometric_sum():
    """Weights that make every token's x, dt, B and C the same: after t + 1
    tokens h[n, c] = dt x B (1 - a^(t+1)) / (1 - a) with a = exp(dt A[n]),
    and y follows; read through the reference's own layer."""
    D, E, N, R, T, eps = 4, 4, 2, 1, 9, 1e-6
    w = {"attn_norm": np.ones(D), "w_in": np.zeros((D, 2 * E)),
         "conv_w": np.zeros((4, E)), "conv_b": np.full(E, 0.7),
         "w_x": np.zeros((E, R + 2 * N)), "dt_norm": np.ones(R),
         "b_norm": np.ones(N), "c_norm": np.ones(N), "w_dt": np.zeros((R, E)),
         "dt_bias": np.full(E, -0.5),
         "A_logT": np.log(np.array([[1.0] * E, [3.0] * E])),
         "ssm_D": np.full(E, 2.0), "wo": np.eye(E, D)}
    w["w_x"][:, R:] = 1.0  # B and C read a constant off x; r stays 0
    w["w_in"][:, E:] = 0.25  # z: a constant too, so the gate is one number
    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    u = silu(0.7)  # x_t: the conv reads zeros, its bias alone is left
    d = np.log1p(np.exp(-0.5))  # the step: r = 0, the bias alone
    v = E * u
    bc = v / np.sqrt(v * v + eps)  # a constant row under a norm of ones
    gate = silu(4 * 0.25 / np.sqrt(1.0 + eps))
    a = np.exp(d * -np.array([1.0, 3.0]))
    got = _ref_layer(np.ones((T, D)), w)
    for t in range(T):
        h = d * u * bc * (1.0 - a ** (t + 1)) / (1.0 - a)  # [N], every channel
        y = (h * bc).sum() + 2.0 * u
        np.testing.assert_allclose(got[t], 1.0 + y * gate, rtol=2e-5)


# ---- the readers ------------------------------------------------------------ #

PAG = "%paged_attention.2 = (f32[128,20,128], f32[128,20,128]) custom-call(%q)"
S6K = ("%s6_decode.1 = (f32[128,5120], f32[26,128,16,5120]) "
       "custom-call(%l)")
MM = "%int8_matmul.3 = bf16[1,128,10240]{2,1,0} custom-call(%x)"


def capture(n=2, s6_ms=3.77, pag_ms=0.25, mm_ms=4.4, step_ms=9.5):
    """Four decode blocks (the first is cut by the capture), each a `while`
    envelope over n steps whose kernels take what the chip showed at 128
    slots (PERF.md section 6, PR 55: 3.77 ms of s6_decode a step)."""
    ops, mods = [], []
    blk = n * step_ms
    for k in range(4):
        t = k * blk * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, blk * MS))
        for s in range(n):
            t0 = t + s * step_ms * MS
            ops += [(S6K, t0, s6_ms * MS), (PAG, t0 + s6_ms * MS, pag_ms * MS),
                    (MM, t0 + (s6_ms + pag_ms) * MS, mm_ms * MS)]
        mods.append(("jit_decode_block(7)", t, blk * MS))
    mods.append(("jit_decode_block(7)", 4 * blk * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 128})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def scoped_planes():
    """One chip, a 100 ms window: a 40 ms decode block of which 26 ms are the
    S6 operator (its in-projection, the kernel, a fusion XLA named after the
    gate), and an admission whose scan is not the block's."""
    D, A = 22, 11
    block = "jit(decode_block)/control/while/body/layer/while/body/"
    ops = [
        SR.op("%int8_matmul.12", 10 * MS, 4 * MS, D,
              block + "s6_mix/attention/proj/int8_matmul/pallas_call:"),
        SR.op("%s6_decode.1", 14 * MS, 20 * MS, D,
              block + "s6_mix/attention/mix/s6_decode/pallas_call:"),
        SR.op("%fusion.174", 34 * MS, 2 * MS, D,
              block + "s6_mix/attention/out/mul:"),
        SR.op("%paged_attention.1", 36 * MS, 2 * MS, D,
              block + "cond/branch_1_fun/attention/mix/paged_attention/pallas_call:"),
        SR.op("%int8_matmul.7", 38 * MS, 12 * MS, D,
              block + "mlp/dense/int8_matmul/pallas_call:"),
        SR.op("%fusion.5", 60 * MS, 8 * MS, A,
              "jit(admit)/layer/while/body/s6_mix/attention/mix/s6_prefill/"
              "while/body/mul:"),
        SR.op("%s6_decode.1", -25 * MS, 20 * MS, D,  # before the mark
              block + "s6_mix/attention/mix/s6_decode/pallas_call:"),
    ]
    modules = [("jit_decode_block(22)", 10 * MS, 40 * MS),
               ("jit_admit(11)", 60 * MS, 8 * MS)]
    return [SR.chip(0, ops, modules),
            SR.host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]


def context(cap=None, xplanes=None, block_s=0.019):
    class Ecfg:
        max_slots = 128
        kv_page_size = 128

    live = [{"send": -1.0, "end": None, "prompt_tokens": 300,
             "chunks": [-0.5] * 850} for _ in range(128)]
    return {"trace": {"capture": cap, "xplanes": xplanes, "t_start": 0.0,
                      "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": block_s}}}}},
            # 128 requests live at 1,150 tokens each; 32 sent with no token
            # yet: in the queue, holding nothing
            "stamps": {"requests": live + [
                {"send": -1.0, "end": None, "prompt_tokens": 400, "chunks": []}
            ] * 32},
            "journal": [ev(0.1, "decode_block", a=2.0),
                        ev(0.15, "loop_iter", a=1.0)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_rooflines_count_what_moves_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "s6_decode") == pytest.approx(3.77e-3)
    assert kernel_step_s(cap, "paged_attention") == pytest.approx(2.5e-4)
    assert kernel_step_s(cap, "int8_matmul") == pytest.approx(4.4e-3)
    # every compiled row's state read and written, live or not
    state = 128 * 26 * 2 * 327_680
    got = S.reader("s6mqa_scan_state_hbm_roofline_share")(ctx)
    assert got == pytest.approx(100.0 * (state / 819e9) / 3.77e-3)
    assert 70.0 < got < 71.5  # 2.66 ms at the peak rate: what the chip showed
    # live tokens as they are, 1,024 B each; the queue's prompts hold nothing
    assert s6_mqa_roofline.live_tokens(ctx) == 128 * 1150
    assert S.reader("s6mqa_paged_attention_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (128 * 1150 * 1024 / 819e9) / 2.5e-4)
    assert S.reader("s6mqa_proj_matmul_hbm_roofline_share")(
        ctx) == pytest.approx(
            100.0 * (costs.proj_matmul_bytes(cfg, 1) / 819e9) / 4.4e-3)
    # the whole step: 19 ms a block of 2 steps (the journal's block size)
    step = costs.decode_step_bytes(cfg, 128, 128 * 1150, 1, 2)
    assert S.reader("s6mqa_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 9.5e-3)
    with pytest.raises(ValueError):
        s6_mqa_roofline.read(ctx, "no_such_metric")


@pytest.mark.parametrize("name", [n for n in NEW if "roofline" in n])
@pytest.mark.parametrize("slack", [1.0, 1.02, 1.5, 4.0])
def test_no_share_reads_over_100_percent_while_time_is_at_least_the_floor(
        name, slack):
    """Each kernel at `slack` x the least time its counted bytes need at the
    chip's peak rate (1.0: a kernel AT the roofline, which no kernel
    reaches): the share is 100 / slack, never more. The counts err low, so
    a real kernel's time, which moves at least these bytes, reads under it."""
    cfg = S.config(CONFIG)
    floor = {
        "s6": 128 * costs.s6_state_bytes_per_row(cfg) / 819e9,
        "pag": 128 * 1150 * 1024 / 819e9,
        "mm": costs.proj_matmul_bytes(cfg, 1) / 819e9,
    }
    whole = costs.decode_step_bytes(cfg, 128, 128 * 1150, 1, 2) / 819e9
    t = {k: v * slack * 1e3 for k, v in floor.items()}
    step_ms = max(whole * slack * 1e3, sum(t.values()))
    ctx = context(capture(s6_ms=t["s6"], pag_ms=t["pag"], mm_ms=t["mm"],
                          step_ms=step_ms), block_s=2 * step_ms / 1e3)
    got = S.reader(name)(ctx)
    assert 0.0 < got <= 100.0 / slack + 1e-6, (name, got)


def test_s6_mix_share_is_the_decode_blocks_own():
    ctx = context(xplanes=scoped_planes())
    assert S.reader("s6mqa_s6_mix_share")(ctx) == pytest.approx(65.0)
    # the existing reader drops the word and books each op to its leaf
    from benchmark.reducers import scope_share
    assert scope_share.leaf_of(
        "jit(decode_block)/control/while/body/layer/while/body/s6_mix/"
        "attention/mix/s6_decode/pallas_call:") == "attention/mix"
    assert scope_share.leaf_of(
        "jit(admit)/layer/while/body/s6_mix/attention/mix/s6_prefill/while/"
        "body/mul:") == "attention/mix"
    assert scope_share.read({"trace": ctx["trace"]}, "attention/mix",
                            ["jit_decode_block"]) == pytest.approx(55.0)
    assert scope_share.read({"trace": ctx["trace"]}, "mlp/dense",
                            ["jit_decode_block"]) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the model (its capture has no such kernel or
    scope), an untraced run: None, never an exception."""
    assert S.reader(name)({**context(), "trace": None}) is None
    if name == "s6mqa_s6_mix_share":
        planes = scoped_planes()
        planes[0]["ops"] = [o for o in planes[0]["ops"] if "s6_mix" not in o.tf_op]
        assert S.reader(name)(context(xplanes=planes)) is None
        return
    planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
              chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                   [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
    kernelless = context({"planes": planes, "dispatch": []})
    for ctx in ({**context(capture()), "peaks": None}, context(), kernelless):
        assert S.reader(name)(ctx) is None
    # another model's decode block (no s6_decode in it): the whole-step share
    # is not this model's to report
    other = capture()
    other["planes"][0]["lines"][TRD.OPS_LINE] = [
        e for e in other["planes"][0]["lines"][TRD.OPS_LINE]
        if "s6_decode" not in e[0]]
    if name in ("s6mqa_scan_state_hbm_roofline_share",
                "s6mqa_decode_hbm_roofline_share"):
        assert S.reader(name)(context(other)) is None


# ---- the files ----------------------------------------------------------------- #


def test_the_new_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "out_tokens_per_s"
        assert listed[name]["unit"] == "%"
        assert listed[name]["source"] == "device_trace"
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "decode-reasoning", 1)
    assert all(len(e["why"]) <= 200 for e in man["workloads"] + man["configs"])
    # admission in this cell is read by admit_device_share; the per-token
    # pairing lists the cells it was accepted in and not this one
    per_token = listed["admit_device_us_per_prompt_token"]["workloads"]
    assert CELL not in per_token and len(per_token) == 9
    cell = S.cell(CELL)
    y = cell["config"]["yaml"]
    assert cell["cell"]["load"]["clients"] * 4 == y["max_slots"] * 5  # 1.25
    assert cell["cell"]["trace_s"] == 12.0
    assert cell["config"]["reduced"] == [] == next(
        c for c in man["configs"] if c["name"] == CONFIG)["reduced"]
    assert (y["model"], y["quantization"], y["kv_pages"], y["kv_page_size"],
            y["context_size"]) == ("ai21-jamba2-3b", "int8", 2688, 128, 4096)
    assert y["max_slots"] in (128, 64)  # 64: the issue's one re-sizing
    assert y.get("prefill_chunk") is None
    assert cell["config"]["reference"] == "s6_mqa_dense"
    assert "expert_share" not in y and "stage_layers" not in y
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"admit_device_share", "device_idle_share", "kernel_time_share",
            "hbm_peak_gb", "loop_python_ms_per_block",
            "host_gc_pause_ms_max"} <= names
    assert "admit_device_us_per_prompt_token" not in names
    assert not any(n.endswith("decode_hbm_roofline_share") and n not in NEW
                   for n in names)
    # the same mix as the other reasoning cells
    assert S.cell("glm-4.7-flash-int8-ep8.decode-reasoning")["mix"] == cell["mix"]


def test_the_file_holds_every_number_of_the_published_config():
    """Every number of the catalog's `config` under its own key, nothing
    reduced; every assumed size with its reason."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == []
    for word in ("head_dim", "positions", "inner_norms", "layer_order",
                 "feed_forward", "conv_state", "precision", "A_log",
                 "dt_bias", "D", "weights"):
        assert word in cfg["assumed"], word
    for key in ("deployment", "departures", "sizing"):
        assert cfg[key]
    assert cfg["check"]["prompt_tokens"] == [48, 200, 700, 2000]
    assert cfg["check"]["new_tokens"] == 17
