"""What the OLMoE configuration brought: its plain reference against a
two-expert case computed by hand in numpy, `costs_moe` against the model's
published size, and the readers of its four metrics on hand-made contexts
(the helpers are test_tracing_readers.py's)."""

import numpy as np
import pytest

from benchmark.harness import costs, costs_moe
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.reducers import journal_ratio, moe_roofline
from benchmark.tests.test_tracing_readers import (MS, chip, ev, host,
                                                  kernel_capture)

CELL = "olmoe-1b-7b-int8.decode-saturated"
NEW = ("moe_expert_active_share", "moe_load_max_over_mean",
       "moe_decode_hbm_roofline_share", "moe_quant_matmul_hbm_roofline_share")


# ---- the reference ---------------------------------------------------------- #


def _hand_layer(x, w, eps, top_k):
    """One OLMoE layer for ONE token at position 0 (its attention sees only
    itself, so the head split, the rotary angle 0 and the softmax over one key
    all drop out: attention is v Wo), float64, loops and no vector tricks."""
    def rms(v, g):
        return v / np.sqrt(np.mean(v * v) + eps) * g

    a = rms(x, w["attn_norm"])
    x = x + (a @ w["wv"]) @ w["wo"]
    m = rms(x, w["mlp_norm"])
    logits = m @ w["router"]
    p = np.exp(logits - logits.max())
    p = p / p.sum()
    chosen = np.argsort(-p)[:top_k]
    out = np.zeros_like(x)
    for e in chosen:                      # weights as they are: no renormalising
        g = m @ w["w_gate"][e]
        act = g / (1.0 + np.exp(-g)) * (m @ w["w_up"][e])
        out += p[e] * (act @ w["w_down"][e])
    return x + out


def test_reference_matches_a_two_expert_case_computed_by_hand():
    from benchmark.reference import moe_qknorm as REF

    rng = np.random.default_rng(5)
    D, F, E, V, heads = 8, 6, 2, 11, 2
    cfg = {"num_heads": heads, "num_kv_heads": heads, "rope_theta": 10000.0,
           "rms_eps": 1e-5, "num_layers": 2, "num_experts_per_token": 1}
    r = lambda *s: rng.normal(0.0, 0.5, s)  # noqa: E731
    g = lambda *s: 1.0 + rng.normal(0.0, 0.2, s)  # noqa: E731
    layers = {"attn_norm": g(2, D), "mlp_norm": g(2, D), "q_norm": g(2, D),
              "k_norm": g(2, D), "wq": r(2, D, D), "wk": r(2, D, D),
              "wv": r(2, D, D), "wo": r(2, D, D), "router": r(2, D, E),
              "w_gate": r(2, E, D, F), "w_up": r(2, E, D, F),
              "w_down": r(2, E, F, D)}
    params = {"embed": r(V, D), "final_norm": g(D), "lm_head": r(V, D),
              "layers": layers}
    as32 = lambda t: {k: (as32(v) if isinstance(v, dict)  # noqa: E731
                          else np.asarray(v, np.float32)) for k, v in t.items()}
    got = REF.forward(as32(params), cfg, [7], [0], pad_to=4)[0]
    x = params["embed"][7]
    for li in range(2):
        x = _hand_layer(x, {k: v[li] for k, v in layers.items()}, 1e-5, 1)
    x = x / np.sqrt(np.mean(x * x) + 1e-5) * params["final_norm"]
    logits = params["lm_head"] @ x
    want = logits - (logits.max() + np.log(np.sum(np.exp(logits - logits.max()))))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_takes_top_k_of_all_experts_without_renormalising():
    """Two tokens, top-2 of 4 experts, int8 expert leaves: against the same
    sum written over the chosen experts only, in numpy."""
    import jax.numpy as jnp

    from benchmark.reference import moe_qknorm as REF

    rng = np.random.default_rng(9)
    T, D, F, E = 3, 8, 4, 4
    h = rng.normal(0, 1, (T, D)).astype(np.float32)
    f = lambda *s: rng.normal(0, 0.5, s).astype(np.float32)  # noqa: E731

    def q8(w):
        s = np.abs(w).max(axis=-2, keepdims=True) / 127.0
        return {"q": jnp.asarray(np.round(w / s), jnp.int8), "s": jnp.asarray(s)}

    raw = {"w_gate": f(E, D, F), "w_up": f(E, D, F), "w_down": f(E, F, D)}
    lw = {"mlp_norm": jnp.ones((D,)), "router": jnp.asarray(f(D, E)),
          **{k: q8(v) for k, v in raw.items()}}
    got = np.asarray(REF.experts(jnp.asarray(h), lw, top_k=2, eps=1e-5))
    deq = {k: np.asarray(lw[k]["q"], np.float64) * np.asarray(lw[k]["s"], np.float64)
           for k in raw}
    want = h.astype(np.float64).copy()
    for t in range(T):
        m = h[t] / np.sqrt(np.mean(h[t].astype(np.float64) ** 2) + 1e-5)
        logits = m @ np.asarray(lw["router"], np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        for e in np.argsort(-p)[:2]:
            g = m @ deq["w_gate"][e]
            want[t] += p[e] * ((g / (1 + np.exp(-g)) * (m @ deq["w_up"][e]))
                               @ deq["w_down"][e])
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the byte counts -------------------------------------------------------- #


def test_costs_moe_counts_the_published_model():
    cfg = S.config("olmoe-1b-7b-int8")
    assert costs_moe.param_count(cfg) == pytest.approx(6.92e9, rel=2e-3)  # "7B": 6.9 B
    assert costs_moe.expert_params(cfg) == 3 * 2048 * 1024 * 64 * 16  # 6.44 B
    # active per token: everything but the experts, plus 8 of the 64
    active = (costs_moe.param_count(cfg) - costs_moe.expert_params(cfg) * 56 / 64)
    assert active == pytest.approx(1.28e9, rel=2e-2)  # "1B": 1.3 B active
    full = costs_moe.weight_bytes(cfg, 1)
    assert full == costs_moe.attention_params(cfg) + costs_moe.head_params(cfg) \
        + costs_moe.expert_params(cfg)
    assert costs_moe.weight_bytes(cfg, 1, 0.5) == pytest.approx(
        full - 0.5 * costs_moe.expert_params(cfg))
    # the dense count sees one expert's width a layer: far less than a step reads
    assert full / costs.weight_bytes(cfg, 1) > 13
    kv = costs.kv_bytes_per_token(cfg, 2)
    assert kv == 2 * 16 * 128 * 2 * 16  # the same 131 KB a token as mistral-7b
    assert costs_moe.decode_step_bytes(cfg, 1000, 1, 2, 1.0) == full + 1000 * kv


# ---- the readers ------------------------------------------------------------ #


def moe_ctx(cap=None, journal=None):
    return {"trace": {"capture": cap, "t_start": 0.0, "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.02}}}}},
            "stamps": {"requests": [{"send": -1.0, "end": None,
                                     "prompt_tokens": 100, "chunks": [-0.5] * 28}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                ev(0.2, "moe_experts", a=2048.0, b=2000.0),
                ev(0.2, "moe_load", a=20.0, b=8.0),
                ev(0.6, "moe_experts", a=2048.0, b=1072.0),
                ev(0.6, "moe_load", a=28.0, b=8.0)],
            "config": S.config("olmoe-1b-7b-int8"), "cell": {"chips": 1},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_routing_shares_sum_the_windows_blocks():
    ctx = moe_ctx()
    assert S.reader("moe_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("moe_load_max_over_mean")(ctx) == pytest.approx(300.0)
    assert moe_roofline.active_share(ctx) == pytest.approx(0.75)
    assert journal_ratio.total(ctx["journal"], "moe_load", "b") == 16.0


def test_moe_rooflines_weight_the_experts_by_the_active_share():
    ctx = moe_ctx(kernel_capture())
    cfg = ctx["config"]
    w = costs_moe.weight_bytes(cfg, 1, 0.75)
    # kernel_capture: int8_matmul + int8_unembed take 2 ms a step
    assert S.reader("moe_quant_matmul_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (w / 819e9) / 2e-3)
    # the whole step: 20 ms a block of 2 steps (the journal's decode_block size)
    step = costs_moe.decode_step_bytes(cfg, 128, 1, 2, 0.75)
    assert S.reader("moe_decode_hbm_roofline_share")(ctx) == pytest.approx(
        100.0 * (step / 819e9) / 10e-3)
    with pytest.raises(ValueError):
        moe_roofline.read(ctx, "no_such_metric")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_no_routing_is_journalled(name):
    """The parent's run of the cell, a dense model, an untraced run: no
    `moe_experts` event, or no capture. None, never an exception."""
    dense = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)(moe_ctx(kernel_capture(), journal=dense)) is None
    assert S.reader(name)({**moe_ctx(journal=dense), "trace": None}) is None
    if "roofline" in name:
        untraced = {**moe_ctx(), "trace": None}
        planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
                  chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                       [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
        spanless = moe_ctx({"planes": planes, "dispatch": []})
        spanless["trace"]["reduced"] = TRD.reduce(planes)
        for ctx in (untraced, {**moe_ctx(), "peaks": None}):
            assert S.reader(name)(ctx) is None
        if "quant" in name:
            assert S.reader(name)(spanless) is None


def test_the_four_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] in layers
        assert listed[name]["moves"] == "out_tokens_per_s"
    cell = S.cell(CELL)
    assert cell["chips"] == 1 and cell["cell"]["load"]["clients"] == 40
    assert cell["config"]["reduced"] == [] and cell["config"]["yaml"].get(
        "prefill_chunk") is None
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert "quant_matmul_hbm_roofline_share" not in names
