"""What the Granite-4.0-H configuration brought: `costs_ssd_gqa` against the
model's published size and a step's bytes by hand, its plain reference's SSD
layer and router against cases computed by hand in numpy, the readers of its
nine metrics on hand-made contexts (the helpers are
test_tracing_readers.py's and test_scope_readers.py's), and its entries in
the manifest."""

import numpy as np
import pytest

from benchmark.harness import costs_ssd_gqa as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import ssd_gqa_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.tests import test_scope_readers as SR
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "granite-4.0-h-small-int8-ep8"
CELL = CONFIG + ".decode-saturated"
NEW = ("ssdgqa_ssd_state_hbm_roofline_share",
       "ssdgqa_paged_attention_hbm_roofline_share",
       "ssdgqa_held_experts_hbm_roofline_share",
       "ssdgqa_decode_hbm_roofline_share", "ssdgqa_ssd_mix_share",
       "ssdgqa_held_expert_active_share", "ssdgqa_routed_here_share",
       "ssdgqa_held_load_max_over_mean",
       "ssdgqa_proj_matmul_hbm_roofline_share")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_published_size():
    cfg = S.config(CONFIG)
    held = costs.held_params(cfg)
    M = 1e6
    assert costs.layers(cfg) == {"ssd": 36, "gqa": 4, "moe": 40}
    assert (costs.d_inner(cfg), costs.conv_dim(cfg)) == (8192, 8448)
    ssd = costs.ssd_layer_params(cfg)
    assert ssd["int8"] == 4096 * 16768 + 8192 * 4096  # 102.2 M
    assert ssd["small"] == 5 * 8448 + 8192 + 3 * 128
    assert costs.gqa_layer_params(cfg) == pytest.approx(41.9 * M, rel=2e-3)
    assert costs.expert_params(cfg) == 3 * 4096 * 768  # 9.437 M
    assert costs.shared_params(cfg) == 3 * 4096 * 1536  # 18.9 M
    assert costs.router_params(cfg) == 4096 * 72  # all 72, not the 9 held
    assert held["experts_held"] == 40 * 9 * 3 * 4096 * 768  # 3.40 B
    assert held["embedding"] == 100352 * 4096  # once: the head is tied
    # the model card's 32B-A9B
    assert costs.param_count(cfg) == pytest.approx(32.2e9, rel=3e-3)
    assert costs.active_params(cfg) == pytest.approx(9.2e9, rel=0.01)
    # a slot's row: 36 layers x (128 x 64 x 128 float32 + 3 x 8448 bf16); a
    # token's K/V: 4 layers x 2 x 8 heads x 128 x bf16
    assert costs.state_bytes_per_row(cfg) == 36 * (4 * 2 ** 20 + 3 * 8448 * 2)
    assert 32 * costs.state_bytes_per_row(cfg) == pytest.approx(4.89e9, rel=2e-3)
    assert costs.kv_bytes_per_token(cfg, 2) == 16384
    # the pool of the YAML: 257 pages x 128 rows
    assert 257 * 128 * costs.kv_bytes_per_token(cfg, 2) == pytest.approx(
        0.54e9, rel=0.01)


def test_a_steps_bytes_by_hand():
    cfg = S.config(CONFIG)
    int8 = 36 * (4096 * 16768 + 8192 * 4096) + 4 * (
        2 * 4096 * 4096 + 2 * 4096 * 1024) + 40 * 3 * 4096 * 1536
    small = 36 * (5 * 8448 + 8192 + 3 * 128) + 40 * 4096 * 72
    experts = 40 * 9 * 3 * 4096 * 768
    head = 100352 * 4096
    w = costs.weight_bytes(cfg, 1)
    assert w == int8 + 2 * (small + head) + experts
    assert w == pytest.approx(8.86e9, rel=3e-3)  # ISSUE 46's count
    # half the (layer, held expert) pairs idle: half the held experts' bytes
    assert costs.weight_bytes(cfg, 1, 0.5) == w - experts / 2
    assert costs.held_expert_bytes(cfg, 1, 0.5) == experts / 2
    # the kernel: the state read and written, dt x in and y out, the decay's
    # rows, B and C, float32, over 36 layers
    row = 36 * 4 * (2 * 128 * 64 * 128 + 2 * 128 * 64 + 128 * 128 + 2 * 128)
    assert costs.ssd_kernel_bytes_per_row(cfg) == row
    assert 32 * 36 * 2 * 4 * 2 ** 20 == pytest.approx(9.66e9, rel=1e-3)
    assert 32 * row == pytest.approx(9.82e9, rel=1e-3)
    conv = 2 * 36 * 3 * 8448 * 2
    step = costs.decode_step_bytes(cfg, 32, 32 * 512, 1, 2, 0.75)
    assert step == (w - experts / 4 + 32 * (row + conv) + 32 * 512 * 16384)
    # ISSUE 46's step: 32 slots of some 512 tokens, every held expert active
    full = costs.decode_step_bytes(cfg, 32, 32 * 512, 1, 2)
    assert full == pytest.approx(19.1e9, rel=0.01)
    assert 32 * row / full > 0.5  # the new kernel moves more than half of it
    assert full / 819e9 == pytest.approx(23.3e-3, rel=0.01)  # seconds a step


# ---- the reference ---------------------------------------------------------- #


def _numpy_ssd_layer(x, w, H, N, res, eps=1e-5):
    """One Mamba-2 layer over x [T, D] in float64 loops, one group."""
    T, D = x.shape
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    a = x / np.sqrt(np.mean(x ** 2, -1, keepdims=True) + eps) * w["attn_norm"]
    zxd = a @ w["w_in"]
    di = (zxd.shape[1] - 2 * N - H) // 2
    P = di // H
    z, pre, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * N], zxd[:, -H:]
    c = w["conv_w"].shape[0]
    S = np.zeros((H, P, N))
    out = []
    for t in range(T):
        u = sum(w["conv_w"][i] * pre[t - (c - 1) + i]
                for i in range(c) if t - (c - 1) + i >= 0) + w["conv_b"]
        u = silu(u)
        xt, B, C = u[:di].reshape(H, P), u[di:di + N], u[di + N:]
        step = np.log1p(np.exp(dt[t] + w["dt_bias"]))
        A = -np.exp(w["A_log"])
        y = np.zeros((H, P))
        for h in range(H):
            S[h] = np.exp(step[h] * A[h]) * S[h] + step[h] * np.outer(xt[h], B)
            y[h] = S[h] @ C + w["ssm_D"][h] * xt[h]
        g = y.reshape(di) * silu(z[t])
        g = g / np.sqrt(np.mean(g ** 2) + eps) * w["o_norm"]
        out.append(x[t] + res * (g @ w["wo"]))
    return np.stack(out)


def test_reference_ssd_layer_matches_a_five_token_case_by_hand():
    """Five tokens through one Mamba-2 layer, float64 loops: the split orders
    z | xBC | dt and x | B | C, tap c-1 on the current token with zeros before
    the start, the bias before the silu, the skip, the gate BEFORE the norm,
    the residual multiplier; with `kv_round` the held conv inputs and the
    state are rounded, the first token's output is not."""
    import jax.numpy as jnp

    from benchmark.reference import ssd_gqa_moe as REF

    rng = np.random.default_rng(3)
    T, D, H, P, N, c = 5, 6, 2, 4, 3, 4
    di, cd = H * P, H * P + 2 * N
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    w = {"attn_norm": 1.0 + r_(D) * 0.2, "w_in": r_(D, di + cd + H),
         "conv_w": r_(c, cd), "conv_b": r_(cd) * 0.3, "dt_bias": r_(H) - 1.0,
         "A_log": r_(H), "ssm_D": 1.0 + r_(H) * 0.3,
         "o_norm": 1.0 + r_(di) * 0.2, "wo": r_(di, D)}
    x = r_(T, D)
    held = {**w, "w_z": w["w_in"][:, :di], "w_xbc": w["w_in"][:, di:di + cd],
            "w_dt": w["w_in"][:, di + cd:]}  # the three column blocks, as held
    args = (jnp.asarray(x, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in held.items()
             if k != "w_in"})
    kw = dict(heads=H, state=N, groups=1, eps=1e-5, res=0.22)
    got = np.asarray(REF.ssd_layer(*args, **kw))
    np.testing.assert_allclose(got, _numpy_ssd_layer(x, w, H, N, 0.22),
                               atol=2e-5)
    held = np.asarray(REF.ssd_layer(*args, kv_round="fp8", **kw))
    assert np.max(np.abs(held[0] - got[0])) < 2e-2  # a bfloat16 state alone
    assert np.max(np.abs(held[1:] - got[1:])) > 1e-3
    # the state alone in bfloat16: the first token's output is the bundle's
    # (no conv input is held yet), the later ones lie nearer the float32's
    state = np.asarray(REF.ssd_layer(*args, kv_round="state-bf16", **kw))
    np.testing.assert_array_equal(state[0], held[0])
    assert 0.0 < np.max(np.abs(state[1:] - got[1:])) < np.max(
        np.abs(held[1:] - got[1:]))
    with pytest.raises(ValueError):
        REF.ssd_layer(*args, kv_round="fp4", **kw)


def test_reference_router_is_a_softmax_over_the_picks():
    import jax.numpy as jnp

    from benchmark.reference import ssd_gqa_moe as REF

    m = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [-3.0, 5.0, 4.0, -23.0]],
                         jnp.float32)
    g, e = REF.route(m, router, top_k=2)
    assert np.asarray(e).tolist() == [[0, 1], [1, 2]]
    two = np.exp([1.0, 0.0]) / np.exp([1.0, 0.0]).sum()  # a gap of 1 both times
    np.testing.assert_allclose(g, [two, two], rtol=1e-6)
    # not the softmax over all four, cut to the picks
    allp = np.exp([2.0, 1.0, 0.0, -1.0]) / np.exp([2.0, 1.0, 0.0, -1.0]).sum()
    assert abs(float(g[0, 0]) - allp[0]) > 0.05


# ---- the readers ------------------------------------------------------------ #

PAG = "%paged_attention.2 = (f32[32,32,128], f32[32,32,128]) custom-call(%q)"
SSD = ("%ssd_decode.1 = (f32[32,2,64,64], f32[36,32,128,64,128]) "
       "custom-call(%l)")
MM = "%int8_matmul.3 = bf16[1,32,16768]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.7 = bf16[9,32,768]{2,1,0} custom-call(%x)"  # held experts


def capture(n=2):
    """Four decode blocks of 60 ms (the first is cut by the capture), each a
    `while` envelope over n steps of 15 ms of the SSD kernel, 0.5 ms paged
    attention, 2 ms of a projection and 5 ms of the held experts' matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 60 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 60 * MS))
        for s in range(n):
            t0 = t + s * 25 * MS
            ops += [(SSD, t0, 15 * MS), (PAG, t0 + 15 * MS, 0.5 * MS),
                    (MM, t0 + 15.5 * MS, 2 * MS), (EXP, t0 + 17.5 * MS, 5 * MS)]
        mods.append(("jit_decode_block(7)", t, 60 * MS))
    mods.append(("jit_decode_block(7)", 240 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 32})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def scoped_planes():
    """One chip, a 100 ms window: a 40 ms decode block of which 26 ms are the
    SSD operator (its in-projection, the kernel, a fusion XLA named after
    the gated norm), and an admission whose SSD ops are not the block's."""
    D, A = 22, 11
    block = "jit(decode_block)/control/while/body/layer/while/body/"
    ops = [
        SR.op("%int8_matmul.12", 10 * MS, 4 * MS, D,
              block + "ssd_mix/attention/proj/int8_matmul/pallas_call:"),
        SR.op("%ssd_decode.1", 14 * MS, 20 * MS, D,
              block + "ssd_mix/attention/mix/ssd_decode/pallas_call:"),
        SR.op("%fusion.174", 34 * MS, 2 * MS, D,
              block + "ssd_mix/attention/out/mul:"),
        SR.op("%paged_attention.1", 36 * MS, 2 * MS, D,
              block + "cond/branch_1_fun/attention/mix/paged_attention/pallas_call:"),
        SR.op("%int8_matmul.7", 38 * MS, 12 * MS, D,
              block + "mlp/experts/int8_matmul/pallas_call:"),
        SR.op("%fusion.5", 60 * MS, 8 * MS, A,
              "jit(admit)/layer/while/body/ssd_mix/attention/mix/mul:"),
        SR.op("%ssd_decode.1", -25 * MS, 20 * MS, D,  # before the mark
              block + "ssd_mix/attention/mix/ssd_decode/pallas_call:"),
    ]
    modules = [("jit_decode_block(22)", 10 * MS, 40 * MS),
               ("jit_admit(11)", 60 * MS, 8 * MS)]
    return [SR.chip(0, ops, modules),
            SR.host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]


def context(cap=None, journal=None, xplanes=None):
    class Ecfg:
        max_slots = 32
        kv_page_size = 128

    return {"trace": {"capture": cap, "xplanes": xplanes, "t_start": 0.0,
                      "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.06}}}}},
            # one request live: 100 prompt + 29 streamed tokens = two pages
            "stamps": {"requests": [
                {"send": -1.0, "end": None, "prompt_tokens": 100,
                 "chunks": [-0.5] * 29},
                # sent, no token yet: in the queue, holding no page
                {"send": -1.0, "end": None, "prompt_tokens": 300, "chunks": []}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                ev(0.2, "moe_experts", a=720.0, b=540.0),
                ev(0.2, "moe_here", a=640.0, b=96.0),
                ev(0.2, "moe_load", a=16.0, b=8.0),
                ev(0.6, "moe_experts", a=720.0, b=540.0),
                ev(0.6, "moe_here", a=640.0, b=64.0),
                ev(0.6, "moe_load", a=8.0, b=8.0)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = context()
    assert S.reader("ssdgqa_held_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("ssdgqa_routed_here_share")(ctx) == pytest.approx(12.5)
    assert S.reader("ssdgqa_held_load_max_over_mean")(ctx) == pytest.approx(150.0)


def test_rooflines_count_the_bytes_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "ssd_decode") == pytest.approx(15e-3)
    assert kernel_step_s(cap, "paged_attention") == pytest.approx(5e-4)
    assert kernel_step_s(cap, "int8_matmul", lead=9) == pytest.approx(5e-3)
    # every compiled row's state read and written, with the kernel's operands
    state = 32 * costs.ssd_kernel_bytes_per_row(cfg)
    got = S.reader("ssdgqa_ssd_state_hbm_roofline_share")(ctx)
    assert got == pytest.approx(100.0 * (state / 819e9) / 15e-3)
    assert 75.0 < got < 85.0  # 12 ms at the peak rate
    # 129 tokens are two whole pages of 128 rows, 16,384 B a token
    assert S.reader("ssdgqa_paged_attention_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (256 * 16384 / 819e9) / 5e-4)
    # three quarters of the (layer, held expert) pairs were chosen
    experts = 0.75 * 40 * 9 * 3 * 4096 * 768
    assert S.reader("ssdgqa_held_experts_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (experts / 819e9) / 5e-3)
    # the dense dequant-matmul: every int8 matrix outside the experts once
    assert kernel_step_s(cap, "int8_matmul", lead=1) == pytest.approx(2e-3)
    proj = (36 * (4096 * 16768 + 8192 * 4096) + 4 * 2 * 4096 * (4096 + 1024)
            + 40 * 3 * 4096 * 1536)
    assert costs.proj_matmul_bytes(cfg, 1) == proj
    assert S.reader("ssdgqa_proj_matmul_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (proj / 819e9) / 2e-3)
    # the whole step: 60 ms a block of 2 steps (the journal's decode_block size)
    step = costs.decode_step_bytes(cfg, 32, 256, 1, 2, 0.75)
    assert S.reader("ssdgqa_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 30e-3)
    assert 0.0 < S.reader("ssdgqa_decode_hbm_roofline_share")(ctx) < 100.0
    with pytest.raises(ValueError):
        ssd_gqa_roofline.read(ctx, "no_such_metric")


def test_ssd_mix_share_is_the_decode_blocks_own():
    ctx = context(xplanes=scoped_planes())
    assert S.reader("ssdgqa_ssd_mix_share")(ctx) == pytest.approx(65.0)
    # the existing reader drops the word and books each op to its leaf
    from benchmark.reducers import scope_share
    assert scope_share.leaf_of(
        "jit(decode_block)/control/while/body/layer/while/body/ssd_mix/"
        "attention/mix/ssd_decode/pallas_call:") == "attention/mix"
    assert scope_share.read({"trace": ctx["trace"]}, "attention/mix",
                            ["jit_decode_block"]) == pytest.approx(55.0)
    assert scope_share.read({"trace": ctx["trace"]}, "attention/proj",
                            ["jit_decode_block"]) == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the model (it journals no routing, its capture has
    no such kernel or scope), an untraced run: None, never an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)({**context(journal=other), "trace": None}) is None
    if name == "ssdgqa_ssd_mix_share":
        planes = scoped_planes()
        planes[0]["ops"] = [o for o in planes[0]["ops"] if "ssd_mix" not in o.tf_op]
        assert S.reader(name)(context(xplanes=planes)) is None
        return
    if "roofline" not in name:
        assert S.reader(name)(context(capture(), journal=other)) is None
        return
    planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
              chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                   [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
    kernelless = context({"planes": planes, "dispatch": []})
    for ctx in ({**context(), "trace": None},
                {**context(capture()), "peaks": None}, context()):
        assert S.reader(name)(ctx) is None
    if name != "ssdgqa_decode_hbm_roofline_share":
        assert S.reader(name)(kernelless) is None
    if name in ("ssdgqa_decode_hbm_roofline_share",
                "ssdgqa_held_experts_hbm_roofline_share"):
        assert S.reader(name)(context(capture(), journal=other)) is None


# ---- the manifest ------------------------------------------------------------ #


def test_the_new_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] in layers
        assert listed[name]["moves"] == "out_tokens_per_s"
        assert listed[name]["unit"] == "%"
    # nobody else's list holds the cell
    for m in man["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["chips"]) == ("decode-saturated", 1)
    assert all(len(e["why"]) <= 200 for e in man["workloads"] + man["configs"])
    cell = S.cell(CELL)
    assert cell["cell"]["load"]["clients"] == 40
    assert cell["cell"]["trace_s"] == 12.0
    cfg = cell["config"]
    assert cfg["reduced"] == ["num_local_experts"] == next(
        c for c in man["configs"] if c["name"] == CONFIG)["reduced"]
    y = cfg["yaml"]
    assert (y["model"], y["quantization"], y["max_slots"], y["kv_pages"],
            y["kv_page_size"], y["context_size"], y["expert_share"]) == (
                "granite-4.0-h-small", "int8", 32, 256, 128, 4096, [0, 8])
    assert y.get("prefill_chunk") is None and cfg["reference"] == "ssd_gqa_moe"
    assert "stage_layers" not in y and "vocab_rows" not in y
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert {"admit_device_us_per_prompt_token", "device_idle_share",
            "kernel_time_share", "hbm_peak_gb"} <= names
    assert not {"decode_hbm_roofline_share", "moe_decode_hbm_roofline_share",
                "paged_attention_hbm_roofline_share",
                "kdagqa_decode_hbm_roofline_share",
                "convgqa_decode_hbm_roofline_share"} & names
    # the 25 metrics that list no cells are every cell's, this one's too
    assert len([m for m in cell["per_layer"] if "workloads" not in m]) == 25
    # the same mix, slots and clients as the whole-model one-chip cells
    for other in ("mistral-7b-int8", "olmoe-1b-7b-int8", "lfm2-8b-a1b-int8"):
        o = S.cell(other + ".decode-saturated")
        assert o["mix"] == cell["mix"]
        assert o["cell"]["load"] == cell["cell"]["load"]
        assert o["config"]["yaml"]["max_slots"] == y["max_slots"]
        assert o["config"]["yaml"]["kv_pages"] == y["kv_pages"]


def test_the_file_holds_every_number_of_the_published_config():
    """Every number of the catalog's `config` under its own key but the one
    reduced, which is the held count beside the published one; every assumed
    size with its reason."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"granite-4.0-h-small"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k == "num_local_experts":
            assert (cfg[k], cfg["published"][k]) == (9, v == 72 and 72)
        else:
            assert cfg[k] == v, k
    for word in ("head_dim", "in_proj_split", "conv_state", "gated_norm",
                 "time_step_limit", "attention", "router", "shared_mlp",
                 "multipliers", "precision", "A_log", "dt_bias", "D",
                 "weights"):
        assert word in cfg["assumed"], word
    assert "chip 0 of the 8" in cfg["deployment"]
    assert "4.4" in cfg["deployment"] and "35.6" in cfg["deployment"]
