"""What the Kimi-Linear configuration brought: `costs_hybrid` against the
deployment's table (PERF.md section 4) and the model's published size, its
plain reference against a one-token case computed by hand in numpy, the
readers of its seven metrics on hand-made contexts (the helpers are
test_tracing_readers.py's), and its entries in the manifest."""

import numpy as np
import pytest

from benchmark.harness import costs, costs_hybrid
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import hybrid_roofline
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "kimi-linear-48b-a3b-int8-ep8"
CELL = CONFIG + ".decode-saturated"
OLD_CELLS = ["mistral-7b-int8.decode-saturated",
             "mistral-7b-bf16-tp4.decode-saturated",
             "olmoe-1b-7b-int8.decode-saturated"]
NEW = ("kda_state_hbm_roofline_share", "latent_attention_hbm_roofline_share",
       "hybrid_decode_hbm_roofline_share", "moe_held_expert_active_share",
       "moe_routed_here_share", "held_experts_hbm_roofline_share",
       "moe_held_load_max_over_mean")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_deployments_table_and_the_published_size():
    cfg = S.config(CONFIG)
    held = costs_hybrid.held_params(cfg)
    M = 1e6
    assert held["kda_attention"] / 20 == pytest.approx(39.5 * M, rel=2e-3)
    assert held["kda_attention"] == pytest.approx(790 * M, rel=1e-3)
    assert held["mla_attention"] / 7 == pytest.approx(29.1 * M, rel=2e-3)
    assert held["mla_attention"] == pytest.approx(204 * M, rel=2e-3)
    assert held["shared_router_dense"] == pytest.approx(263 * M, rel=1e-3)
    assert costs_hybrid.expert_params(cfg) == 3 * 2304 * 1024
    assert held["experts_held"] == 26 * 32 * 3 * 2304 * 1024  # 5,889 M
    assert held["head"] == held["embedding"] == 163840 * 2304
    # the model card's 48B-A3B: 49.1 B with all 256 experts a layer
    assert costs_hybrid.param_count(cfg) == pytest.approx(49.1e9, rel=1e-3)
    # a slot's state: 20 layers x (32 x 128 x 128 float32 + 3 x 3 x 4096 bf16)
    assert costs_hybrid.state_bytes_per_row(cfg) == 20 * (2 ** 21 + 73728)
    assert costs_hybrid.kda_matrix_bytes_per_row(cfg) == 2 * 20 * 2 ** 21
    assert 64 * costs_hybrid.state_bytes_per_row(cfg) == pytest.approx(
        2.68e9 + 0.094e9, rel=2e-3)
    # a latent row as the kernel reads it: 640 values (576 padded) x 7 layers
    assert costs_hybrid.latent_bytes_per_token(cfg, 2) == 7 * 640 * 2
    # against the dense-GQA count the accepted step metric would have used
    assert costs.kv_bytes_per_token(cfg, 2) / costs_hybrid.latent_bytes_per_token(
        cfg, 2) > 27


def test_a_steps_bytes_weight_the_held_experts_and_count_the_state_twice():
    cfg = S.config(CONFIG)
    full = costs_hybrid.weight_bytes(cfg, 1)
    held = costs_hybrid.held_params(cfg)["experts_held"]
    assert full == pytest.approx(7.6e9, rel=5e-3)  # int8 matrices + bf16 leaves
    assert costs_hybrid.weight_bytes(cfg, 1, 0.5) == pytest.approx(
        full - 0.5 * held)
    step = costs_hybrid.decode_step_bytes(cfg, 64, 25600, 1, 2, 1.0)
    assert step == (full + 2 * 64 * costs_hybrid.state_bytes_per_row(cfg)
                    + 25600 * 8960)
    assert step == pytest.approx(13.4e9, rel=5e-3)
    # what the new mechanisms move against what the held experts do
    assert step - full > 0.9 * held


# ---- the reference ---------------------------------------------------------- #


def test_reference_kda_layer_matches_a_two_token_case_computed_by_hand():
    """Two tokens through one KDA layer, float64 loops: the conv sees a zero
    before the first token, the state starts at zero, the second token reads
    what the first wrote, decayed per channel."""
    import jax.numpy as jnp

    from benchmark.reference import kda_mla_moe as REF

    rng = np.random.default_rng(3)
    D, H, d, r, c = 6, 2, 4, 3, 4
    r_ = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    w = {"attn_norm": 1.0 + r_(D) * 0.2, "wq": r_(D, H * d), "wk": r_(D, H * d),
         "wv": r_(D, H * d), "wo": r_(H * d, D), "conv_w": r_(c, 3 * H * d),
         "f_down": r_(D, r), "f_up": r_(r, H * d), "dt_bias": r_(H * d) - 1.0,
         "A_log": r_(H), "w_beta": r_(D, H), "g_down": r_(D, r),
         "g_up": r_(r, H * d), "o_norm": 1.0 + r_(d) * 0.2}
    x = r_(2, D)
    got = np.asarray(REF.kda_attention(
        jnp.asarray(x, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
        heads=H, eps=1e-5))
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
    S_ = np.zeros((H, d, d))
    pre, want = [], []
    for t in range(2):
        a = x[t] / np.sqrt(np.mean(x[t] ** 2) + 1e-5) * w["attn_norm"]
        pre.append(np.concatenate([a @ w["wq"], a @ w["wk"], a @ w["wv"]]))
        y = sum(w["conv_w"][c - 1 - i] * pre[t - i] for i in range(t + 1))
        y = (y * sig(y)).reshape(3, H, d)
        f = (a @ w["f_down"]) @ w["f_up"] + w["dt_bias"]
        g = -np.exp(w["A_log"])[:, None] * np.log1p(np.exp(f)).reshape(H, d)
        beta = sig(a @ w["w_beta"])
        gate = sig((a @ w["g_down"]) @ w["g_up"]).reshape(H, d)
        out = np.zeros((H, d))
        for h in range(H):
            q = y[0, h] / np.sqrt(np.sum(y[0, h] ** 2) + 1e-6) / np.sqrt(d)
            k = y[1, h] / np.sqrt(np.sum(y[1, h] ** 2) + 1e-6)
            S_[h] = np.exp(g[h])[:, None] * S_[h]
            S_[h] = S_[h] + beta[h] * np.outer(k, y[2, h] - k @ S_[h])
            o = q @ S_[h]
            out[h] = o / np.sqrt(np.mean(o ** 2) + 1e-5) * w["o_norm"] * gate[h]
        want.append(x[t] + out.reshape(-1) @ w["wo"])
    np.testing.assert_allclose(got, np.stack(want), atol=2e-5)


def test_the_cache_control_rounds_the_state_with_an_op_no_compiler_drops():
    """`kv_round="fp8"` holds the state in bfloat16 through
    `lax.reduce_precision`: on the chip a cast to bfloat16 and back inside
    the scan changed nothing at all (PERF.md section 6, PR 31), and the
    control then read like the float32 reference."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kda_mla_moe as REF

    rng = np.random.default_rng(3)
    T, D, H, d, r = 96, 16, 2, 8, 4
    rnd = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    lw = {"attn_norm": jnp.ones((D,)), "wq": rnd(D, H * d), "wk": rnd(D, H * d),
          "wv": rnd(D, H * d), "wo": rnd(H * d, D), "conv_w": rnd(4, 3 * H * d),
          "f_down": rnd(D, r), "f_up": rnd(r, H * d),
          "dt_bias": jnp.full((H * d,), -7.0), "A_log": jnp.zeros((H,)),
          "w_beta": rnd(D, H), "g_down": rnd(D, r), "g_up": rnd(r, H * d),
          "o_norm": jnp.ones((d,))}
    x = rnd(T, D)
    plain = REF.kda_attention(x, lw, heads=H, eps=1e-5)
    held = REF.kda_attention(x, lw, heads=H, eps=1e-5, kv_round="fp8")
    gap = float(jnp.max(jnp.abs(plain - held)))
    assert 1e-4 < gap < 0.5, gap
    text = str(jax.make_jaxpr(lambda a: REF.kda_attention.__wrapped__(
        a, lw, heads=H, eps=1e-5, kv_round="fp8"))(x))
    assert "reduce_precision" in text


# ---- the readers ------------------------------------------------------------ #

KDA = "%kda_decode.4 = (f32[64,32,128], f32[20,64,32,128,128]) custom-call(%a)"
LAT = "%latent_paged_attention.2 = (f32[64,32,640]) custom-call(%q)"
MM = "%int8_matmul.3 = bf16[1,64,4096]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.7 = bf16[32,64,1024]{2,1,0} custom-call(%x)"  # held experts


def hybrid_capture(n=2):
    """Four decode blocks of 10 ms (the first is cut by the capture), each
    a `while` envelope over n steps of 2 ms KDA + 0.5 ms latent + 0.25 ms
    of a projection + 0.75 ms of the expert stack's matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 10 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 10 * MS))
        for s in range(n):
            t0 = t + s * 4 * MS
            ops += [(KDA, t0, 2 * MS), (LAT, t0 + 2 * MS, 0.5 * MS),
                    (MM, t0 + 2.5 * MS, 0.25 * MS),
                    (EXP, t0 + 2.75 * MS, 0.75 * MS)]
        mods.append(("jit_decode_block(7)", t, 10 * MS))
    mods.append(("jit_decode_block(7)", 40 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 48})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def hybrid_ctx(cap=None, journal=None):
    class Ecfg:
        max_slots = 64

    return {"trace": {"capture": cap, "t_start": 0.0, "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.08}}}}},
            "stamps": {"requests": [{"send": -1.0, "end": None,
                                     "prompt_tokens": 100, "chunks": [-0.5] * 28}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                ev(0.2, "state_rows", a=2560.0, b=1920.0),
                ev(0.2, "moe_experts", a=1664.0, b=1248.0),
                ev(0.2, "moe_here", a=26624.0, b=3328.0),
                ev(0.2, "moe_load", a=312.0, b=104.0),
                ev(0.6, "state_rows", a=2560.0, b=1920.0),
                ev(0.6, "moe_experts", a=1664.0, b=1248.0),
                ev(0.6, "moe_here", a=26624.0, b=3328.0),
                ev(0.6, "moe_load", a=208.0, b=104.0)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = hybrid_ctx()
    assert S.reader("moe_held_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("moe_routed_here_share")(ctx) == pytest.approx(12.5)
    assert S.reader("moe_held_load_max_over_mean")(ctx) == pytest.approx(250.0)
    assert hybrid_roofline.live_rows(ctx) == pytest.approx(48.0)


def test_rooflines_take_live_rows_live_tokens_and_each_kernels_own_time():
    ctx = hybrid_ctx(hybrid_capture())
    cfg = ctx["config"]
    assert hybrid_roofline.kernel_step_s(ctx["trace"]["capture"],
                                         "kda_decode") == pytest.approx(2e-3)
    assert hybrid_roofline.kernel_step_s(
        ctx["trace"]["capture"], "latent_paged_attention") == pytest.approx(5e-4)
    # `kda_decode_helper` or a fusion that only mentions it is not the kernel
    assert hybrid_roofline.kernel_step_s(ctx["trace"]["capture"], "kda") is None
    kda = 48 * costs_hybrid.kda_matrix_bytes_per_row(cfg)
    assert S.reader("kda_state_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (kda / 819e9) / 2e-3)
    lat = 128 * 8960  # one request live: 100 prompt + 28 streamed tokens
    assert S.reader("latent_attention_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (lat / 819e9) / 5e-4)
    # the expert stack's calls alone (result [32 held, rows, out]), not the
    # projections': three quarters of the held experts' bytes were chosen
    assert hybrid_roofline.kernel_step_s(
        ctx["trace"]["capture"], "int8_matmul", lead=32) == pytest.approx(7.5e-4)
    assert hybrid_roofline.kernel_step_s(
        ctx["trace"]["capture"], "int8_matmul") == pytest.approx(1e-3)
    held = 26 * 32 * 3 * 2304 * 1024 * 0.75
    assert S.reader("held_experts_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (held / 819e9) / 7.5e-4)
    # the whole step: 80 ms a block of 2 steps (the journal's decode_block size)
    step = costs_hybrid.decode_step_bytes(cfg, 48, 128, 1, 2, 0.75)
    assert S.reader("hybrid_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 40e-3)
    assert S.reader("hybrid_decode_hbm_roofline_share")(ctx) < 100.0
    with pytest.raises(ValueError):
        hybrid_roofline.read(ctx, "no_such_metric")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """The parent's run, another model's cell, an untraced run: no
    `state_rows` / `moe_here` event, no such kernel, or no capture. None,
    never an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)(hybrid_ctx(hybrid_capture(), journal=other)) is None
    assert S.reader(name)({**hybrid_ctx(journal=other), "trace": None}) is None
    if "roofline" in name:
        planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
                  chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                       [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
        kernelless = hybrid_ctx({"planes": planes, "dispatch": []})
        for ctx in ({**hybrid_ctx(), "trace": None},
                    {**hybrid_ctx(hybrid_capture()), "peaks": None}):
            assert S.reader(name)(ctx) is None
        if "hybrid" not in name:
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
    assert [m["name"] for m in man["per_layer"][-len(NEW):]] == [
        *NEW[:3], *NEW[3:5], *NEW[5:]]  # appended, in the order they came
    # the two dense-GQA shares keep to the cells they were accepted in: here
    # they would count 249 KB a token of keys and values against the real 9
    for name in ("decode_hbm_roofline_share",
                 "paged_attention_hbm_roofline_share"):
        assert listed[name]["workloads"] == OLD_CELLS
    cell = S.cell(CELL)
    assert cell["chips"] == 1 and cell["cell"]["load"]["clients"] == 80
    cfg = cell["config"]
    assert cfg["reduced"] == ["num_experts"] and cfg["num_experts"] == 32
    assert cfg["published"]["num_experts"] == 256
    assert cfg["yaml"]["expert_share"] == [0, 8] and cfg["yaml"]["max_slots"] == 64
    assert cfg["yaml"].get("prefill_chunk") is None
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert not {"decode_hbm_roofline_share", "moe_expert_active_share",
                "paged_attention_hbm_roofline_share"} & names


def test_the_file_holds_every_number_of_the_published_config():
    """Every number of the catalog's `config` under its own key, but for the
    one key `reduced` names."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
