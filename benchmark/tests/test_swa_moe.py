"""What the Laguna-XS.2 configuration brought: `costs_swa_moe` against the
model's published size and a step's bytes by hand, its plain reference
against closed forms (the YaRN table, the half-rotated head, the window's
edge, the per-head gate), and the readers of its nine metrics on hand-made
contexts (the helpers are test_tracing_readers.py's)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import costs_swa_moe as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import swa_moe_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.reference import swa_gqa_moe as REF
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "laguna-xs.2-int8-ep8"
CELL = CONFIG + ".decode-reasoning"
NEW = ("swamoe_window_attention_hbm_roofline_share",
       "swamoe_paged_attention_hbm_roofline_share",
       "swamoe_held_experts_hbm_roofline_share",
       "swamoe_proj_matmul_hbm_roofline_share",
       "swamoe_decode_hbm_roofline_share", "swamoe_window_rows_saved_share",
       "swamoe_held_expert_active_share", "swamoe_routed_here_share",
       "swamoe_held_load_max_over_mean")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_published_size():
    cfg = S.config(CONFIG)
    held = costs.held_params(cfg)
    assert costs.layers(cfg) == {"full": 10, "window": 30, "dense": 1, "moe": 39}
    assert costs.heads(cfg) == {"full": 48, "window": 64}
    full, win = (costs.attn_layer_params(cfg, k) for k in ("full", "window"))
    assert full["int8"] == 2 * 2048 * 6144 + 2 * 2048 * 1024  # 29.36 M
    assert win["int8"] == 2 * 2048 * 8192 + 2 * 2048 * 1024  # 37.75 M
    assert (full["small"], win["small"]) == (2048 * 48, 2048 * 64)
    assert costs.expert_params(cfg) == 3 * 2048 * 512  # 3.146 M
    assert held["experts_held"] == 39 * 32 * 3 * 2048 * 512  # 3.93 B
    assert held["shared_experts"] == 39 * 3 * 2048 * 512
    assert held["routers"] == 39 * 2048 * 256  # all 256 outputs, not the 32 held
    assert held["dense_mlp"] == 3 * 2048 * 8192
    assert held["head"] == held["embedding"] == 100352 * 2048
    # the model card's 33.4 B
    assert costs.param_count(cfg) == pytest.approx(33.4e9, rel=2e-3)
    # a position's K and V in one layer, a token's pool rows, a slot's rings
    assert costs.kv_row_bytes(cfg, 2) == 4096
    assert costs.paged_bytes(cfg, 1, 2) == 40960
    assert costs.window_bytes(cfg, 512, 2) == 62914560
    y = cfg["yaml"]
    assert y["max_slots"] * 62914560 == pytest.approx(4.03e9, rel=2e-3)
    assert y["kv_pages"] * y["kv_page_size"] * 40960 == pytest.approx(
        4.70e9, rel=2e-3)


def test_a_steps_bytes_by_hand():
    cfg = S.config(CONFIG)
    proj = 10 * (2 * 2048 * 6144 + 2 * 2048 * 1024) \
        + 30 * (2 * 2048 * 8192 + 2 * 2048 * 1024) \
        + 39 * 3 * 2048 * 512 + 3 * 2048 * 8192
    small = 10 * 2048 * 48 + 30 * 2048 * 64 + 39 * 2048 * 256
    experts = 39 * 32 * 3 * 2048 * 512
    head = 100352 * 2048
    assert costs.proj_matmul_bytes(cfg, 1) == proj
    w = costs.weight_bytes(cfg, 1)
    assert w == proj + head + experts + 2 * small
    assert w == pytest.approx(5.78e9, rel=5e-3)  # ISSUE 53's 5.8 GB
    assert costs.weight_bytes(cfg, 1, 0.5) == w - experts / 2
    assert costs.held_expert_bytes(cfg, 1, 0.5) == experts / 2
    # 64 full rings and 74,000 live tokens: 3.9 + 3.0 GB beside the weights,
    # the two readers some 54% of the step
    step = costs.decode_step_bytes(cfg, 64 * 512, 74000, 1, 2)
    assert step == w + 64 * 512 * 4096 * 30 + 74000 * 4096 * 10
    assert (step - w) / step == pytest.approx(0.55, abs=0.01)


# ---- the reference ------------------------------------------------------------ #


def test_the_yarn_table_of_the_published_full_layer():
    """Over dim 64 at theta 5e5, factor 64 from 4,096, beta 64 / 1: low 5,
    high 16; pairs 0-5 extrapolated, 16-31 interpolated, a linear ramp
    between."""
    inv = REF.yarn_inv(64, 5e5, 64.0, 4096, 64.0, 1.0)
    i = np.arange(32)
    extrap = 5e5 ** (-2.0 * i / 64)
    corr = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(5e5))  # noqa: E731
    assert (math.floor(corr(64)), math.ceil(corr(1))) == (5, 16)
    np.testing.assert_allclose(inv[:6], extrap[:6])
    np.testing.assert_allclose(inv[16:], extrap[16:] / 64)
    ramp = (i[6:16] - 5) / 11
    np.testing.assert_allclose(
        inv[6:16], extrap[6:16] / 64 * ramp + extrap[6:16] * (1 - ramp))
    a = REF.arch_of(S.config(CONFIG) | {
        "layer_kinds": ("gqa", "swa"), "num_heads": 48, "swa_heads": 64,
        "num_kv_heads": 8, "head_dim": 128, "partial_rotary": 0.5,
        "rope_attn_factor": 1.4158883083359672, "rope_scaling_factor": 64.0,
        "rope_theta": 5e5, "rope_original_max_position": 4096,
        "rope_beta_fast": 64.0, "rope_beta_slow": 1.0,
        "rope_local_theta": 1e4, "rms_eps": 1e-6, "first_k_dense": 1,
        "num_experts_per_token": 8, "routed_scaling_factor": 2.5,
        "expert_share": (0, 8), "num_experts": 256})
    np.testing.assert_allclose(a["inv"]["gqa"], inv)
    np.testing.assert_allclose(a["inv"]["swa"], 1e4 ** (-2.0 * np.arange(64) / 128))
    assert a["amp"] == {"gqa": pytest.approx(0.1 * math.log(64) + 1), "swa": 1.0}
    assert (a["lo"], a["window"]) == (0, 512)


def test_half_a_head_is_rotated_and_carries_the_amplitude():
    x = jax.random.normal(jax.random.key(0), (3, 2, 16), jnp.float32)
    pos = jnp.asarray([0, 7, 300])
    inv = np.asarray([1.0, 0.3, 0.05, 0.001])  # 8 of 16 lanes rotate
    y = REF.rotate(x, pos, inv, amp=1.5)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])  # passed, no amplitude
    np.testing.assert_allclose(y[0, :, :8], 1.5 * x[0, :, :8], rtol=1e-6)
    for t, p in enumerate([0, 7, 300]):
        for i in range(4):
            c, s = math.cos(p * inv[i]), math.sin(p * inv[i])
            a, b = np.asarray(x[t, :, i]), np.asarray(x[t, :, i + 4])
            np.testing.assert_allclose(y[t, :, i], 1.5 * (a * c - b * s), atol=1e-5)
            np.testing.assert_allclose(y[t, :, i + 4], 1.5 * (b * c + a * s),
                                       atol=1e-5)
    whole = REF.rotate(x, pos, np.ones(8) * 0.1)  # the window layers' form
    np.testing.assert_allclose((whole ** 2).sum(-1), (x ** 2).sum(-1), rtol=1e-5)


def _layer(D=16, H=2, K=1, d=8, gate=0.0):
    """q = k = 0 (uniform weights over what a query may attend), v the normed
    input's first d dims, W_o the identity, the gate's operand `gate`."""
    return {"attn_norm": jnp.ones((D,)), "wg_head": jnp.zeros((D, H)) + gate,
            "wq": jnp.zeros((D, H * d)), "wk": jnp.zeros((D, K * d)),
            "wv": jnp.pad(jnp.eye(d), ((0, D - d), (0, 0))), "wo": jnp.eye(D)}


def test_the_windows_edge_and_the_gate_a_head():
    """Position i attends j iff 0 <= i - j < window, its own among them; a
    head's output is scaled by ONE number, sigmoid of its gate's operand."""
    T, W = 24, 8
    h = jax.random.normal(jax.random.key(3), (T, 16), jnp.float32)
    kw = dict(heads=2, kv_heads=1, inv=(1.0, 0.1, 0.01, 0.001), amp=1.0,
              eps=1e-6)
    v = np.asarray(REF._rms_norm(h, jnp.ones((16,)), 1e-6)[:, :8])
    mean = lambda lo, i: v[lo: i + 1].mean(0)  # noqa: E731
    out = np.asarray(REF.attention(h, _layer(), window=W, **kw) - h)
    want = np.stack([mean(max(0, i - W + 1), i) for i in range(T)]) / 2
    np.testing.assert_allclose(out[:, :8], want, atol=1e-5)
    np.testing.assert_allclose(out[:, 8:], want, atol=1e-5)
    full = np.asarray(REF.attention(h, _layer(), window=0, **kw) - h)
    np.testing.assert_allclose(
        full[:, :8], np.stack([mean(0, i) for i in range(T)]) / 2, atol=1e-5)
    np.testing.assert_allclose(full[:W], out[:W], atol=1e-6)
    assert np.abs(full[W:] - out[W:]).max() > 1e-3
    # the gate's operand is the NORMED input times W_g: a column of ones of
    # W_g for head 1 alone gives it sigmoid(sum of the normed row)
    lw = _layer()
    lw["wg_head"] = lw["wg_head"].at[:, 1].set(1.0)
    g = np.asarray(jax.nn.sigmoid(REF._rms_norm(h, jnp.ones((16,)), 1e-6).sum(-1)))
    gated = np.asarray(REF.attention(h, lw, window=W, **kw) - h)
    np.testing.assert_allclose(gated[:, :8], want, atol=1e-5)
    np.testing.assert_allclose(gated[:, 8:], 2 * want * g[:, None], atol=1e-5)
    # scored a block of query rows at a time: the same rows
    long_h = jax.random.normal(jax.random.key(4), (2 * REF.Q_BLOCK, 16))
    a = REF.attention(long_h, _layer(), window=W, **kw)
    b = REF.attention(long_h[: REF.Q_BLOCK], _layer(), window=W, **kw)
    np.testing.assert_allclose(a[: REF.Q_BLOCK], b, atol=1e-6)


def test_reference_rounds_both_kinds_rows_to_eight_bits():
    h = jax.random.normal(jax.random.key(5), (16, 16), jnp.float32)
    kw = dict(heads=2, kv_heads=1, inv=(1.0, 0.1, 0.01, 0.001), amp=1.0,
              eps=1e-6, window=0)
    exact = REF.attention(h, _layer(), **kw)
    rounded = REF.attention(h, _layer(), kv_round="fp8", **kw)
    err = float(jnp.abs(exact - rounded).max())
    assert 1e-3 < err < 0.1  # 3 mantissa bits of v, uniform weights
    with pytest.raises(ValueError, match="unknown kv rounding"):
        REF.attention(h, _layer(), kv_round="int3", **kw)


# ---- the readers -------------------------------------------------------------- #


WIN = ("%window_attention.3 = (f32[64,64,128], f32[64,64,128]) "
       "custom-call(%q)")
PAG = ("%paged_attention.5 = (f32[64,48,128], f32[64,48,128]) "
       "custom-call(%q)")
MM = "%int8_matmul.176 = bf16[1,64,2048]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.182 = bf16[32,64,2048]{2,1,0} custom-call(%x)"  # held experts


def capture(n=2):
    """Four decode blocks of 60 ms (the first is cut by the capture), each a
    `while` envelope over n steps of 7 ms of the window reader, 5 ms of the
    page walk, 3 ms of projections and 6 ms of the held experts' matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 60 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 60 * MS))
        for s in range(n):
            t0 = t + s * 25 * MS
            ops += [(WIN, t0, 7 * MS), (PAG, t0 + 7 * MS, 5 * MS),
                    (MM, t0 + 12 * MS, 3 * MS), (EXP, t0 + 15 * MS, 6 * MS)]
        mods.append(("jit_decode_block(7)", t, 60 * MS))
    mods.append(("jit_decode_block(7)", 240 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 64})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def context(cap=None, journal=None):
    class Ecfg:
        max_slots = 64
        kv_pages = 896
        kv_page_size = 128

    return {"trace": {"capture": cap, "xplanes": None, "t_start": 0.0,
                      "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.06}}}}},
            "stamps": {"requests": [
                {"send": -1.0, "end": None, "prompt_tokens": 300, "chunks": []}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                # a block of 2 steps whose 64 slots held 70,000 rows, 30,000
                # of them inside their rings; one of 4 steps at 76,000 and
                # 32,000: 6 steps, 74,000 and 31,333 a step
                ev(0.1, "decode_rows", a=2 * 64.0, b=2 * 60.0),
                ev(0.1, "window_rows", a=2 * 30000.0, b=2 * 70000.0),
                ev(0.5, "decode_rows", a=4 * 64.0, b=4 * 60.0),
                ev(0.5, "window_rows", a=4 * 32000.0, b=4 * 76000.0),
                ev(0.2, "moe_experts", a=2496.0, b=1872.0),
                ev(0.2, "moe_here", a=640.0, b=96.0),
                ev(0.2, "moe_load", a=16.0, b=8.0),
                ev(0.6, "moe_experts", a=2496.0, b=1872.0),
                ev(0.6, "moe_here", a=640.0, b=64.0),
                ev(0.6, "moe_load", a=8.0, b=8.0)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = context()
    assert S.reader("swamoe_held_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("swamoe_routed_here_share")(ctx) == pytest.approx(12.5)
    assert S.reader("swamoe_held_load_max_over_mean")(ctx) == pytest.approx(150.0)
    rows, live = swa_moe_roofline.rows_a_step(ctx)
    assert (rows, live) == (pytest.approx(188000 / 6), pytest.approx(74000.0))
    # no capture needed for what the window saves
    assert S.reader("swamoe_window_rows_saved_share")(
        {**ctx, "trace": None}) == pytest.approx(100 * (1 - 188000 / 444000))


def test_rooflines_count_the_bytes_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "window_attention") == pytest.approx(7e-3)
    assert kernel_step_s(cap, "paged_attention") == pytest.approx(5e-3)
    assert kernel_step_s(cap, "int8_matmul", lead=32) == pytest.approx(6e-3)
    assert kernel_step_s(cap, "int8_matmul", lead=1) == pytest.approx(3e-3)
    rows = 188000 / 6
    got = S.reader("swamoe_window_attention_hbm_roofline_share")(ctx)
    assert got == pytest.approx(100.0 * (rows * 4096 * 30 / 819e9) / 7e-3)
    assert 60.0 < got < 75.0
    got = S.reader("swamoe_paged_attention_hbm_roofline_share")(ctx)
    assert got == pytest.approx(100.0 * (74000 * 4096 * 10 / 819e9) / 5e-3)
    experts = 0.75 * 39 * 32 * 3 * 2048 * 512
    assert S.reader("swamoe_held_experts_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (experts / 819e9) / 6e-3)
    proj = costs.proj_matmul_bytes(cfg, 1)
    assert S.reader("swamoe_proj_matmul_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (proj / 819e9) / 3e-3)
    step = costs.decode_step_bytes(cfg, rows, 74000, 1, 2, 0.75)
    whole = S.reader("swamoe_decode_hbm_roofline_share")(ctx)
    assert whole == pytest.approx(100.0 * (step / 819e9) / 30e-3)
    assert 0.0 < whole < 100.0
    with pytest.raises(ValueError):
        swa_moe_roofline.read(ctx, "no_such_metric")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the model (it journals no window rows and no
    routing, its capture has no such kernel), an untraced run: None, never
    an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)({**context(journal=other), "trace": None}) is None
    assert S.reader(name)(context(capture(), journal=other)) is None
    if "roofline" not in name:
        return
    planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
              chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                   [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
    kernelless = context({"planes": planes, "dispatch": []})
    for ctx in ({**context(), "trace": None},
                {**context(capture()), "peaks": None}, context()):
        assert S.reader(name)(ctx) is None
    if name != "swamoe_decode_hbm_roofline_share":
        assert S.reader(name)(kernelless) is None


# ---- the cell ------------------------------------------------------------------ #


def test_the_cell_is_the_glm_cells_traffic_under_another_model():
    cell, glm = S.cell(CELL), S.cell("glm-4.7-flash-int8-ep8.decode-reasoning")
    assert cell["mix"] == glm["mix"]
    assert cell["cell"]["load"] == glm["cell"]["load"]
    assert cell["chips"] == 1
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= listed
    for m in S.manifest()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
    cfg = cell["config"]
    assert cfg["reduced"] == ["num_experts"] and cfg["num_experts"] == 32
    assert cfg["published"] == {"num_experts": 256}
    assert cfg["reference"] == "swa_gqa_moe"


def test_the_file_holds_every_number_of_the_published_config():
    """The catalog's row, key by key: what differs is in `reduced`."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    cfg = S.config(CONFIG)
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == want
        else:
            assert cfg[key] == want, key
    assert cfg["source"] == row["source_url"]
