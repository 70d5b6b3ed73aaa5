"""What the Solar-Open2 configuration brought: `costs_kda_gqa` against the
deployment's table (PERF.md section 4) and the model's published size, its
plain reference's two layers against cases computed by hand in numpy, the
readers of its seven metrics on hand-made contexts (the helpers are
test_tracing_readers.py's), and its entries in the manifest."""

import numpy as np
import pytest

from benchmark.harness import costs_kda_gqa as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import kda_gqa_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "solar-open2-250b-int8-ep8"
CELL = CONFIG + ".decode-saturated"
NEW = ("kdagqa_kda_state_hbm_roofline_share",
       "kdagqa_paged_attention_hbm_roofline_share",
       "kdagqa_held_experts_hbm_roofline_share",
       "kdagqa_decode_hbm_roofline_share", "kdagqa_held_expert_active_share",
       "kdagqa_routed_here_share", "kdagqa_held_load_max_over_mean")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_deployments_table_and_the_published_size():
    cfg = S.config(CONFIG)
    held = costs.held_params(cfg)
    M = 1e6
    assert costs.layers(cfg) == {"gqa": 1, "kda": 3, "moe": 4}
    assert costs.layers(cfg, published=True) == {"gqa": 12, "kda": 36, "moe": 48}
    assert held["kda_attention"] / 3 == pytest.approx(137.8 * M, rel=1e-3)
    assert costs.gqa_layer_params(cfg) == pytest.approx(109.0 * M, rel=1e-3)
    assert held["kda_attention"] + held["gqa_attention"] == pytest.approx(
        0.522e9, rel=5e-3)
    assert costs.expert_params(cfg) == 3 * 4096 * 1280  # 15.73 M
    assert held["experts_held"] == 4 * 40 * 3 * 4096 * 1280  # 2.52 G
    assert held["shared_router"] == pytest.approx(0.068e9, rel=0.03)
    assert held["head"] == held["embedding"] == 24576 * 4096
    # the model card's 250B-A15B, from the published depth, experts and rows
    assert costs.param_count(cfg) == pytest.approx(250.3e9, rel=2e-4)
    assert costs.active_params(cfg) == pytest.approx(14.7e9, rel=5e-3)
    # a slot's state: 3 layers x (64 x 128 x 128 float32 + 3 x 3 x 8192 bf16)
    assert costs.state_bytes_per_row(cfg) == 3 * (2 ** 22 + 147456)
    assert costs.kda_matrix_bytes_per_row(cfg) == 2 * 3 * 2 ** 22
    assert 64 * costs.state_bytes_per_row(cfg) == pytest.approx(0.834e9, rel=2e-3)
    # keys and values: 1 layer x 2 x 8 heads x 128 x bf16
    assert costs.kv_bytes_per_token(cfg, 2) == 4096
    # the pool of the YAML: 512 pages x 128 rows
    assert 512 * 128 * costs.kv_bytes_per_token(cfg, 2) == pytest.approx(
        0.27e9, rel=0.01)


def test_a_steps_bytes_count_every_held_expert_every_row_and_whole_pages():
    cfg = S.config(CONFIG)
    w = costs.weight_bytes(cfg, 1)
    assert w == pytest.approx(3.22e9, rel=2e-3)  # int8 matrices + bf16 leaves
    assert costs.held_expert_bytes(cfg, 1) == 4 * 40 * 3 * 4096 * 1280
    step = costs.decode_step_bytes(cfg, 64, 64 * 512, 1, 2)
    assert step == w + 2 * 64 * costs.state_bytes_per_row(cfg) + 64 * 512 * 4096
    assert step == pytest.approx(5.02e9, rel=2e-3)
    # the state is the second term: two thirds of what the held experts are
    assert 64 * costs.kda_matrix_bytes_per_row(cfg) == pytest.approx(1.61e9, rel=2e-3)


# ---- the reference ---------------------------------------------------------- #


def _sig(z):
    return 1.0 / (1.0 + np.exp(-z))


def test_reference_kda_layer_matches_a_two_token_case_with_beta_to_two():
    """Two tokens through one KDA layer, float64 loops, beta = 2 sigmoid(.)
    with a W_beta that drives it past 1 on both tokens."""
    import jax.numpy as jnp

    from benchmark.reference import kda_gqa_moe as REF

    rng = np.random.default_rng(3)
    D, H, d, r, c = 6, 2, 4, 3, 4
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    w = {"attn_norm": 1.0 + r_(D) * 0.2, "wq": r_(D, H * d), "wk": r_(D, H * d),
         "wv": r_(D, H * d), "wo": r_(H * d, D), "conv_w": r_(c, 3 * H * d),
         "f_down": r_(D, r), "f_up": r_(r, H * d), "dt_bias": r_(H * d) - 1.0,
         "A_log": r_(H), "w_beta": r_(D, H) * 3.0, "g_down": r_(D, r),
         "g_up": r_(r, H * d), "o_norm": 1.0 + r_(d) * 0.2}
    x = r_(2, D)
    got = np.asarray(REF.kda_attention(
        jnp.asarray(x, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        heads=H, eps=1e-5))
    S_ = np.zeros((H, d, d))
    pre, want, betas = [], [], []
    for t in range(2):
        a = x[t] / np.sqrt(np.mean(x[t] ** 2) + 1e-5) * w["attn_norm"]
        pre.append(np.concatenate([a @ w["wq"], a @ w["wk"], a @ w["wv"]]))
        y = sum(w["conv_w"][c - 1 - i] * pre[t - i] for i in range(t + 1))
        y = (y * _sig(y)).reshape(3, H, d)
        f = (a @ w["f_down"]) @ w["f_up"] + w["dt_bias"]
        g = -np.exp(w["A_log"])[:, None] * np.log1p(np.exp(f)).reshape(H, d)
        beta = 2.0 * _sig(a @ w["w_beta"])
        betas.append(beta)
        gate = _sig((a @ w["g_down"]) @ w["g_up"]).reshape(H, d)
        out = np.zeros((H, d))
        for h in range(H):
            q = y[0, h] / np.sqrt(np.sum(y[0, h] ** 2) + 1e-6) / np.sqrt(d)
            k = y[1, h] / np.sqrt(np.sum(y[1, h] ** 2) + 1e-6)
            S_[h] = np.exp(g[h])[:, None] * S_[h]
            S_[h] = S_[h] + beta[h] * np.outer(k, y[2, h] - k @ S_[h])
            o = q @ S_[h]
            out[h] = o / np.sqrt(np.mean(o ** 2) + 1e-5) * w["o_norm"] * gate[h]
        want.append(x[t] + out.reshape(-1) @ w["wo"])
    assert np.max(betas) > 1.2  # the case does pass 1
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5)


def test_reference_gqa_layer_is_causal_gated_and_without_rotation():
    """Three tokens through one softmax layer, float64 loops: each query head
    reads its group's KV head, no position enters but the causal mask, and
    the output is gated from the layer's normed input before W_o."""
    import jax.numpy as jnp

    from benchmark.reference import kda_gqa_moe as REF

    rng = np.random.default_rng(5)
    T, D, H, K, hd = 3, 6, 4, 2, 4
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    w = {"attn_norm": 1.0 + r_(D) * 0.2, "wq": r_(D, H * hd), "wk": r_(D, K * hd),
         "wv": r_(D, K * hd), "wo": r_(H * hd, D), "wg": r_(D, H * hd)}
    x = r_(T, D)
    got = np.asarray(REF.gqa_attention(
        jnp.asarray(x, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        heads=H, kv_heads=K, eps=1e-5))
    a = x / np.sqrt(np.mean(x ** 2, -1, keepdims=True) + 1e-5) * w["attn_norm"]
    q = (a @ w["wq"]).reshape(T, H, hd)
    k = (a @ w["wk"]).reshape(T, K, hd)
    v = (a @ w["wv"]).reshape(T, K, hd)
    want = []
    for t in range(T):
        o = np.zeros((H, hd))
        for h in range(H):
            kh = h // (H // K)
            s = np.array([q[t, h] @ k[u, kh] for u in range(t + 1)]) / np.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            o[h] = sum(p[u] * v[u, kh] for u in range(t + 1))
        want.append(x[t] + (o.reshape(-1) * _sig(a[t] @ w["wg"])) @ w["wo"])
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5)
    # a permutation of the EARLIER tokens leaves the last token's output as
    # it was: nothing but the mask knows an order
    perm = np.array([1, 0, 2])
    again = np.asarray(REF.gqa_attention(
        jnp.asarray(x[perm], jnp.float32),
        {k_: jnp.asarray(v_, jnp.float32) for k_, v_ in w.items()},
        heads=H, kv_heads=K, eps=1e-5))
    np.testing.assert_allclose(again[2], got[2], atol=2e-5)


def test_the_cache_control_rounds_both_kinds_of_cache():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kda_gqa_moe as REF

    rng = np.random.default_rng(3)
    T, D, H, d, r = 96, 16, 2, 8, 4
    rnd = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)  # noqa: E731
    lw = {"attn_norm": jnp.ones((D,)), "wq": rnd(D, H * d), "wk": rnd(D, H * d),
          "wv": rnd(D, H * d), "wo": rnd(H * d, D), "conv_w": rnd(4, 3 * H * d),
          "f_down": rnd(D, r), "f_up": rnd(r, H * d),
          "dt_bias": jnp.full((H * d,), -7.0), "A_log": jnp.zeros((H,)),
          "w_beta": rnd(D, H), "g_down": rnd(D, r), "g_up": rnd(r, H * d),
          "o_norm": jnp.ones((d,))}
    x = rnd(T, D)
    plain = REF.kda_attention(x, lw, heads=H, eps=1e-5)
    held = REF.kda_attention(x, lw, heads=H, eps=1e-5, kv_round="fp8")
    assert 1e-4 < float(jnp.max(jnp.abs(plain - held))) < 0.5
    text = str(jax.make_jaxpr(lambda a: REF.kda_attention.__wrapped__(
        a, lw, heads=H, eps=1e-5, kv_round="fp8"))(x))
    assert "reduce_precision" in text
    gw = {"attn_norm": jnp.ones((D,)), "wq": rnd(D, 4 * d), "wk": rnd(D, 2 * d),
          "wv": rnd(D, 2 * d), "wo": rnd(4 * d, D), "wg": rnd(D, 4 * d)}
    kw = dict(heads=4, kv_heads=2, eps=1e-5)
    whole = REF.gqa_attention(x, gw, **kw)
    gap = jnp.max(jnp.abs(whole - REF.gqa_attention(x, gw, kv_round="fp8", **kw)))
    assert 1e-3 < float(gap) / float(jnp.max(jnp.abs(whole))) < 0.2
    with pytest.raises(ValueError, match="kv rounding"):
        REF.gqa_attention(x, gw, kv_round="int3", **kw)


# ---- the readers ------------------------------------------------------------ #

KDA = "%kda_decode.4 = (f32[64,64,128], f32[3,64,64,128,128]) custom-call(%a)"
PAG = "%paged_attention.2 = (f32[64,64,128], f32[64,64,128]) custom-call(%q)"
MM = "%int8_matmul.3 = bf16[1,64,8192]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.7 = bf16[40,64,1280]{2,1,0} custom-call(%x)"  # held experts


def capture(n=2):
    """Four decode blocks of 10 ms (the first is cut by the capture), each a
    `while` envelope over n steps of 2 ms KDA + 0.5 ms paged attention +
    0.25 ms of a projection + 0.75 ms of the expert stack's matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 10 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 10 * MS))
        for s in range(n):
            t0 = t + s * 4 * MS
            ops += [(KDA, t0, 2 * MS), (PAG, t0 + 2 * MS, 0.5 * MS),
                    (MM, t0 + 2.5 * MS, 0.25 * MS),
                    (EXP, t0 + 2.75 * MS, 0.75 * MS)]
        mods.append(("jit_decode_block(7)", t, 10 * MS))
    mods.append(("jit_decode_block(7)", 40 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 64})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def context(cap=None, journal=None):
    class Ecfg:
        max_slots = 64
        kv_page_size = 128

    return {"trace": {"capture": cap, "t_start": 0.0, "t_end": 1.0,
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
                ev(0.2, "moe_experts", a=640.0, b=512.0),
                ev(0.2, "moe_here", a=8192.0, b=1024.0),
                ev(0.2, "moe_load", a=96.0, b=25.6),
                ev(0.6, "moe_experts", a=640.0, b=512.0),
                ev(0.6, "moe_here", a=8192.0, b=1024.0),
                ev(0.6, "moe_load", a=32.0, b=25.6)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = context()
    assert S.reader("kdagqa_held_expert_active_share")(ctx) == pytest.approx(80.0)
    assert S.reader("kdagqa_routed_here_share")(ctx) == pytest.approx(12.5)
    assert S.reader("kdagqa_held_load_max_over_mean")(ctx) == pytest.approx(250.0)


def test_rooflines_count_what_the_kernels_move_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "kda_decode") == pytest.approx(2e-3)
    assert kernel_step_s(cap, "paged_attention") == pytest.approx(5e-4)
    assert kernel_step_s(cap, "int8_matmul", lead=40) == pytest.approx(7.5e-4)
    # every compiled row, whatever the tenants
    kda = 64 * costs.kda_matrix_bytes_per_row(cfg)
    assert S.reader("kdagqa_kda_state_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (kda / 819e9) / 2e-3)
    # 129 tokens are two whole pages of 128 rows
    assert kda_gqa_roofline.paged_tokens(ctx) == 256
    assert S.reader("kdagqa_paged_attention_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (256 * 4096 / 819e9) / 5e-4)
    # every held expert, chosen or not
    assert S.reader("kdagqa_held_experts_hbm_roofline_share")(
        ctx) == pytest.approx(
            100.0 * (4 * 40 * 3 * 4096 * 1280 / 819e9) / 7.5e-4)
    # the whole step: 80 ms a block of 2 steps (the journal's decode_block size)
    step = costs.decode_step_bytes(cfg, 64, 256, 1, 2)
    assert S.reader("kdagqa_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 40e-3)
    assert 0.0 < S.reader("kdagqa_decode_hbm_roofline_share")(ctx) < 100.0
    with pytest.raises(ValueError):
        kda_gqa_roofline.read(ctx, "no_such_metric")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the kernels or the events, an untraced run, a
    capture without the kernel: None, never an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)({**context(journal=other), "trace": None}) is None
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
    if name != "kdagqa_decode_hbm_roofline_share":
        assert S.reader(name)(kernelless) is None


# ---- the manifest ------------------------------------------------------------ #


def test_the_seven_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] in layers
        assert listed[name]["moves"] == "out_tokens_per_s"
    assert [m["name"] for m in man["per_layer"][-len(NEW):]] == list(NEW)
    # nobody else's list was touched
    for m in man["per_layer"][:-len(NEW)]:
        assert CELL not in m.get("workloads", [])
    assert man["workloads"][-1]["name"] == CELL
    assert man["workloads"][-1]["traffic"] == "decode-saturated"
    cell = S.cell(CELL)
    assert cell["chips"] == 1 and cell["cell"]["load"]["clients"] == 80
    assert cell["cell"]["trace_s"] == 12.0
    cfg = cell["config"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"] == man["configs"][-1]["reduced"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 40, 24576)
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 320,
                                "vocab_size": 196608}
    y = cfg["yaml"]
    assert y["expert_share"] == [0, 8] and y["vocab_rows"] == 24576
    assert "decode_block_sizes" not in y  # the 64-step block of every cell
    assert (y["stage_layers"], y["max_slots"], y["kv_pages"],
            y["kv_page_size"], y["context_size"]) == (4, 64, 512, 128, 4096)
    assert y.get("prefill_chunk") is None and cfg["reference"] == "kda_gqa_moe"
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert not {"decode_hbm_roofline_share", "hybrid_decode_hbm_roofline_share",
                "paged_attention_hbm_roofline_share",
                "kda_state_hbm_roofline_share"} & names
    # the same mix, slots and clients as the other hybrid cell
    kimi = S.cell("kimi-linear-48b-a3b-int8-ep8.decode-saturated")
    assert kimi["mix"] == cell["mix"]
    assert kimi["cell"]["load"] == cell["cell"]["load"]
    assert kimi["config"]["yaml"]["max_slots"] == y["max_slots"]


def test_the_file_holds_every_number_of_the_published_config():
    """Every number of the catalog's `config` under its own key, but for the
    three keys `reduced` names; nested groups whole; no width among them."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"Solar-Open2-250B"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v, k
        else:
            assert cfg[k] == v, k
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    for word in ("gate", "gqa_plain", "kda_gate_rank", "kda_beta", "router",
                 "intermediate_size", "dt_bias", "weights"):
        assert any(word in k for k in cfg["assumed"]), word
    assert "twelve pipeline stages" in cfg["deployment"]
    assert "chip 0 of stage 0" in cfg["deployment"]
