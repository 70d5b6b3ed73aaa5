"""What the LFM2 configuration brought: `costs_conv_gqa` against the model's
published size and a step's bytes by hand, its plain reference's conv
operator and router against cases computed by hand in numpy, the readers of
its six metrics on hand-made contexts (the helpers are
test_tracing_readers.py's and test_scope_readers.py's), and its entries in
the manifest."""

import numpy as np
import pytest

from benchmark.harness import costs_conv_gqa as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import conv_gqa_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.tests import test_scope_readers as SR
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "lfm2-8b-a1b-int8"
CELL = CONFIG + ".decode-saturated"
NEW = ("convgqa_decode_hbm_roofline_share", "convgqa_experts_hbm_roofline_share",
       "convgqa_paged_attention_hbm_roofline_share", "convgqa_conv_mix_share",
       "convgqa_expert_active_share", "convgqa_load_max_over_mean")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_published_size():
    cfg = S.config(CONFIG)
    held = costs.held_params(cfg)
    M = 1e6
    assert costs.layers(cfg) == {"conv": 18, "gqa": 6, "dense": 2, "moe": 22}
    assert costs.head_dim(cfg) == 64
    assert held["conv"] == 18 * (4 * 2048 * 2048 + 3 * 2048)  # 302 M
    assert held["attention"] == pytest.approx(62.9 * M, rel=1e-3)
    assert held["dense_mlp"] == 2 * 3 * 2048 * 7168  # 88 M
    assert costs.expert_params(cfg) == 3 * 2048 * 1792  # 11.0 M
    assert held["experts"] == pytest.approx(7751 * M, rel=1e-4)
    assert held["routers"] == 22 * 2048 * 32
    assert held["embedding"] == 65536 * 2048  # once: the head is tied
    # the model card's 8.3B-A1.5B
    assert costs.param_count(cfg) == pytest.approx(8.34e9, rel=1e-2)
    assert costs.param_count(cfg) == pytest.approx(8.339e9, rel=1e-3)
    assert costs.active_params(cfg) == pytest.approx(1.5e9, rel=0.06)
    # a slot's row: 18 layers x 2 inputs x 2048 x bf16; a token's K/V: 6 layers
    # x 2 x 8 heads x 64 x bf16
    assert costs.conv_bytes_per_row(cfg) == 147456
    assert costs.kv_bytes_per_token(cfg, 2) == 12288
    # the pool of the YAML: 256 pages x 128 rows
    assert 256 * 128 * costs.kv_bytes_per_token(cfg, 2) == pytest.approx(
        0.40e9, rel=0.01)


def test_a_steps_bytes_by_hand():
    cfg = S.config(CONFIG)
    int8 = 18 * 4 * 2048 * 2048 + 6 * (2 * 2048 * 2048 + 2 * 2048 * 512) \
        + 2 * 3 * 2048 * 7168
    small = 18 * 3 * 2048 + 22 * 2048 * 32
    experts = 22 * 32 * 3 * 2048 * 1792
    head = 65536 * 2048
    w = costs.weight_bytes(cfg, 1)
    assert w == int8 + 2 * (small + head) + experts
    assert w == pytest.approx(8.47e9, rel=2e-3)
    assert experts / w == pytest.approx(0.915, rel=5e-3)
    # half the (layer, expert) pairs idle: half the experts' bytes
    assert costs.weight_bytes(cfg, 1, 0.5) == w - experts / 2
    assert costs.expert_bytes(cfg, 1, 0.5) == experts / 2
    step = costs.decode_step_bytes(cfg, 64, 64 * 512, 1, 2, 0.75)
    assert step == (w - experts / 4 + 2 * 64 * 147456 + 64 * 512 * 12288)
    # ISSUE 42's floor: whole experts, 32 slots of 768 tokens
    full = costs.decode_step_bytes(cfg, 32, 32 * 768, 1, 2)
    assert full == pytest.approx(8.78e9, rel=5e-3)
    assert full / 819e9 == pytest.approx(10.7e-3, rel=0.01)  # seconds a step


# ---- the reference ---------------------------------------------------------- #


def test_reference_conv_operator_matches_a_three_token_case_by_hand():
    """Three tokens through one conv layer, float64 loops: the split order
    b, c, z, tap L-1 on the current token, zeros before the start, no
    activation; with `kv_round` the two HELD inputs alone are rounded."""
    import jax.numpy as jnp

    from benchmark.reference import conv_gqa_moe as REF

    rng = np.random.default_rng(3)
    T, D, L = 4, 6, 3
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    w = {"attn_norm": 1.0 + r_(D) * 0.2, "w_in": r_(D, 3 * D),
         "conv_w": r_(L, D), "wo": r_(D, D)}
    x = r_(T, D)
    args = (jnp.asarray(x, jnp.float32),
            {k: jnp.asarray(v, jnp.float32) for k, v in w.items()})
    got = np.asarray(REF.conv_operator(*args, eps=1e-5))
    a = x / np.sqrt(np.mean(x ** 2, -1, keepdims=True) + 1e-5) * w["attn_norm"]
    bcz = a @ w["w_in"]
    b, c, z = bcz[:, :D], bcz[:, D:2 * D], bcz[:, 2 * D:]
    u = b * z
    want = []
    for t in range(T):
        v = sum(w["conv_w"][i] * u[t - (L - 1) + i]
                for i in range(L) if t - (L - 1) + i >= 0)
        want.append(x[t] + (c[t] * v) @ w["wo"])
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5)
    held = np.asarray(REF.conv_operator(*args, eps=1e-5, kv_round="fp8"))
    np.testing.assert_allclose(held[0], got[0], atol=1e-6)  # nothing held yet
    assert np.max(np.abs(held[1:] - got[1:])) > 1e-3


def test_reference_router_renormalises_with_one_in_a_million():
    import jax.numpy as jnp

    from benchmark.reference import conv_gqa_moe as REF

    m = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [-20.0, -21.0, -22.0, -23.0]],
                         jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9], jnp.float32)
    g, e = REF.route(m, router, bias, top_k=2, scaling=1.0)
    s = 1.0 / (1.0 + np.exp(-np.asarray(router, np.float64)))
    # token 0: the bias lifts expert 3 (0.269 + 0.9) over 1 and 2; its weight
    # is its PLAIN score
    assert sorted(np.asarray(e[0]).tolist()) == [0, 3]
    pick = s[0, np.asarray(e[0])]
    np.testing.assert_allclose(g[0], pick / (pick.sum() + 1e-6), rtol=1e-6)
    # token 1: scores of 2e-9 and less, so the 1e-6 is nearly all of the sum
    pick = s[1, np.asarray(e[1])]
    np.testing.assert_allclose(g[1], pick / (pick.sum() + 1e-6), rtol=1e-5)
    assert float(np.asarray(g[1]).sum()) < 0.01


# ---- the readers ------------------------------------------------------------ #

PAG = "%paged_attention.2 = (f32[64,32,128], f32[64,32,128]) custom-call(%q)"
MM = "%int8_matmul.3 = bf16[1,64,6144]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.7 = bf16[32,64,1792]{2,1,0} custom-call(%x)"  # the experts


def capture(n=2):
    """Four decode blocks of 10 ms (the first is cut by the capture), each a
    `while` envelope over n steps of 0.5 ms paged attention + 0.25 ms of a
    projection + 3 ms of the expert stack's matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 10 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 10 * MS))
        for s in range(n):
            t0 = t + s * 4 * MS
            ops += [(PAG, t0, 0.5 * MS), (MM, t0 + 0.5 * MS, 0.25 * MS),
                    (EXP, t0 + 0.75 * MS, 3 * MS)]
        mods.append(("jit_decode_block(7)", t, 10 * MS))
    mods.append(("jit_decode_block(7)", 40 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 64})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def scoped_planes():
    """One chip, a 100 ms window: a 40 ms decode block of which 4 ms are the
    conv operator (a fusion XLA named after W_out's reshape and the
    out-projection itself, as the chip names them), and an admission whose
    conv ops are not the block's."""
    D, A = 22, 11
    block = "jit(decode_block)/control/while/body/layer/while/body/"
    ops = [
        SR.op("%fusion.174", 10 * MS, 3 * MS, D,
              block + "conv_mix/attention/out/reshape:"),
        SR.op("%int8_matmul.129", 13 * MS, 1 * MS, D,
              block + "conv_mix/attention/out/int8_matmul/pallas_call:"),
        SR.op("%paged_attention.1", 14 * MS, 6 * MS, D,
              block + "cond/branch_1_fun/attention/mix/paged_attention/pallas_call:"),
        SR.op("%int8_matmul.7", 20 * MS, 30 * MS, D,
              block + "mlp/experts/int8_matmul/pallas_call:"),
        SR.op("%fusion.5", 60 * MS, 8 * MS, A,
              "jit(admit)/layer/while/body/conv_mix/attention/mix/mul:"),
        SR.op("%fusion.174", -5 * MS, 4 * MS, D,  # before the mark
              block + "conv_mix/attention/out/reshape:"),
    ]
    modules = [("jit_decode_block(22)", 10 * MS, 40 * MS),
               ("jit_admit(11)", 60 * MS, 8 * MS)]
    return [SR.chip(0, ops, modules),
            SR.host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]


def context(cap=None, journal=None, xplanes=None):
    class Ecfg:
        max_slots = 64
        kv_page_size = 128

    return {"trace": {"capture": cap, "xplanes": xplanes, "t_start": 0.0,
                      "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.08}}}}},
            # one request live: 100 prompt + 29 streamed tokens = two pages
            "stamps": {"requests": [
                {"send": -1.0, "end": None, "prompt_tokens": 100,
                 "chunks": [-0.5] * 29},
                # sent, no token yet: in the queue, holding no page
                {"send": -1.0, "end": None, "prompt_tokens": 300, "chunks": []}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                ev(0.2, "moe_experts", a=704.0, b=528.0),
                ev(0.2, "moe_load", a=96.0, b=25.6),
                ev(0.6, "moe_experts", a=704.0, b=528.0),
                ev(0.6, "moe_load", a=32.0, b=25.6)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = context()
    assert S.reader("convgqa_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("convgqa_load_max_over_mean")(ctx) == pytest.approx(250.0)


def test_rooflines_count_the_bytes_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "paged_attention") == pytest.approx(5e-4)
    assert kernel_step_s(cap, "int8_matmul", lead=32) == pytest.approx(3e-3)
    # 129 tokens are two whole pages of 128 rows, 12,288 B a token
    assert S.reader("convgqa_paged_attention_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (256 * 12288 / 819e9) / 5e-4)
    # three quarters of the (layer, expert) pairs were chosen
    experts = 0.75 * 22 * 32 * 3 * 2048 * 1792
    assert S.reader("convgqa_experts_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (experts / 819e9) / 3e-3)
    # the whole step: 80 ms a block of 2 steps (the journal's decode_block size)
    step = costs.decode_step_bytes(cfg, 64, 256, 1, 2, 0.75)
    assert S.reader("convgqa_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 40e-3)
    assert 0.0 < S.reader("convgqa_decode_hbm_roofline_share")(ctx) < 100.0
    with pytest.raises(ValueError):
        conv_gqa_roofline.read(ctx, "no_such_metric")


def test_conv_mix_share_is_the_decode_blocks_own():
    ctx = context(xplanes=scoped_planes())
    assert S.reader("convgqa_conv_mix_share")(ctx) == pytest.approx(10.0)
    # the existing reader drops the word and books each op to its leaf
    from benchmark.reducers import scope_share
    assert scope_share.leaf_of(
        "jit(decode_block)/control/while/body/layer/while/body/conv_mix/"
        "attention/mix/mul:") == "attention/mix"
    assert scope_share.read({"trace": ctx["trace"]}, "attention/out",
                            ["jit_decode_block"]) == pytest.approx(10.0)
    assert scope_share.read({"trace": ctx["trace"]}, "attention/mix",
                            ["jit_decode_block"]) == pytest.approx(15.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the model (it journals no routing, its capture has
    no such kernel or scope), an untraced run: None, never an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)({**context(journal=other), "trace": None}) is None
    if name == "convgqa_conv_mix_share":
        planes = scoped_planes()
        planes[0]["ops"] = [o for o in planes[0]["ops"] if "conv_mix" not in o.tf_op]
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
    if name != "convgqa_decode_hbm_roofline_share":
        assert S.reader(name)(kernelless) is None
    if name != "convgqa_paged_attention_hbm_roofline_share":
        assert S.reader(name)(context(capture(), journal=other)) is None


# ---- the manifest ------------------------------------------------------------ #


def test_the_six_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] in layers
        assert listed[name]["moves"] == "out_tokens_per_s"
    # nobody else's list holds the cell
    for m in man["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["chips"]) == ("decode-saturated", 1)
    cell = S.cell(CELL)
    assert cell["cell"]["load"]["clients"] == 40
    assert cell["cell"]["trace_s"] == 12.0
    cfg = cell["config"]
    assert cfg["reduced"] == [] == next(
        c for c in man["configs"] if c["name"] == CONFIG)["reduced"]
    y = cfg["yaml"]
    assert (y["model"], y["quantization"], y["max_slots"], y["kv_pages"],
            y["kv_page_size"], y["context_size"]) == (
                "lfm2-8b-a1b", "int8", 32, 256, 128, 4096)
    assert y.get("prefill_chunk") is None and cfg["reference"] == "conv_gqa_moe"
    assert "expert_share" not in y and "stage_layers" not in y
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert {"admit_device_us_per_prompt_token", "device_idle_share",
            "kernel_time_share", "hbm_peak_gb"} <= names
    assert not {"decode_hbm_roofline_share", "moe_decode_hbm_roofline_share",
                "paged_attention_hbm_roofline_share",
                "kdagqa_decode_hbm_roofline_share"} & names
    # the same mix, slots and clients as the two other whole-model one-chip
    # cells: ISSUE 42 named them, so that the three differ in the model alone
    for other in ("mistral-7b-int8", "olmoe-1b-7b-int8"):
        o = S.cell(other + ".decode-saturated")
        assert o["mix"] == cell["mix"]
        assert o["cell"]["load"] == cell["cell"]["load"]
        assert o["config"]["yaml"]["max_slots"] == y["max_slots"]
        assert o["config"]["yaml"]["kv_pages"] == y["kv_pages"]


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
                   if '"LFM2-8B-A1B"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    for word in ("tie_word_embeddings", "head_dim", "norm_topk_eps",
                 "in_proj_split", "conv_state", "precision", "weights"):
        assert word in cfg["assumed"], word
    assert "the whole model" in cfg["deployment"]
