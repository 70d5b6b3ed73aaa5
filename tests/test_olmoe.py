"""OLMoE (allenai/OLMoE-1B-7B): full-width q/k RMS norm, a router that
softmaxes over ALL experts, takes the top k and does not renormalise, no
shared expert; and the one rule that picks the MoE implementation.

Three independent statements of the block are held against each other at the
`tiny-olmoe` width on the CPU: the program (`Engine.submit`, prefill then
decode through the cache), the benchmark's plain float32 reference
(`benchmark/reference/moe_qknorm.py`, which shares no code with
`localai_tpu/models/`), and HF transformers' `OlmoeForCausalLM`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_cases as M
from benchmark.harness import check as C
from benchmark.reference import moe_qknorm as REF
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch

CFG = get_arch("tiny-olmoe")
# Log-probability units, system against the float32 reference over 9 generated
# positions. The honest error of this 2-layer bf16 model is 0.0078 at worst
# over the four right cases (bf16 activations; int8 weights are read by both
# sides); the wrong variants land at 0.016 (fp8 cache), 0.19 (renormalised
# top-k) and 0.32 (per-head norm). 0.012 sits between, 1.5 x the honest worst.
TOLERANCE = 0.012
PROMPT, NEW = 40, 9  # the admission's token + one 8-step decode block


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights whose q/k norm weights are NOT all ones (ones would
    hide a norm applied over the wrong axis or with the wrong weight)."""
    params = L.init_params(cfg, jax.random.key(7), scale=0.05)
    k1, k2 = jax.random.split(jax.random.key(8))
    lay = dict(params["layers"])
    for name, k in (("q_norm", k1), ("k_norm", k2)):
        w = 1.0 + 0.3 * jax.random.normal(k, lay[name].shape, jnp.float32)
        lay[name] = w.astype(lay[name].dtype)
    params = {**params, "layers": lay}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


def _engine(cfg, params, paged=False, **kw):
    return M._engine(cfg, params, max_seq=128, trace_journal_events=256,
                     kv_pages=24 if paged else 0, **kw)


def _errors(cfg, params, ref_params, **eng_kw):
    """The benchmark's own comparison: greedy decode with top-20 logprobs
    through the engine, the plain reference teacher-forced over the same ids."""
    prompt = C.sample_prompts(11, cfg.vocab_size, [PROMPT])[0]
    eng = _engine(cfg, params, **eng_kw)
    try:
        rec = C.run_system(eng, [prompt], NEW)[0]
        ref = C.reference_logprobs(REF.forward, ref_params, CFG, prompt, rec["ids"])
        return C.compare(rec, ref), eng.metrics(), eng.journal.snapshot()
    finally:
        eng.stop()


@pytest.mark.parametrize("quantize", ["", "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_agrees_with_the_plain_reference(paged, quantize):
    params = _seeded(quantize=quantize)
    err, metrics, journal = _errors(CFG, params, params, paged=paged)
    assert C.verdict([err], TOLERANCE), err
    # the decode block's routing came back with its tokens
    slots = metrics["moe_expert_slots"]
    assert slots == 8 * CFG.num_layers * CFG.num_experts  # one 8-step block
    assert 0 < metrics["moe_expert_slots_hit"] <= slots
    assert metrics["moe_rows_busiest"] >= metrics["moe_rows_mean"] > 0
    ev = {e["event"]: e for e in journal if e["event"].startswith("moe_")}
    assert ev["moe_experts"]["a"] == slots
    assert ev["moe_experts"]["b"] == metrics["moe_expert_slots_hit"]
    assert ev["moe_load"]["a"] == metrics["moe_rows_busiest"]
    assert ev["moe_load"]["b"] == 8 * CFG.num_layers * 2 * 2 / 8  # rows x k / E


def _per_head_norm():
    """Gemma-3's per-head q/k norm where OLMoE norms the whole projection."""
    cfg = dataclasses.replace(CFG, qk_norm_full=False, qk_norm=True)
    params = _seeded()
    lay = dict(params["layers"])
    lay["q_norm"] = lay["q_norm"][:, : cfg.head_dim_]
    lay["k_norm"] = lay["k_norm"][:, : cfg.head_dim_]
    return cfg, {**params, "layers": lay}, {}


WRONG = {
    "per_head_qk_norm": _per_head_norm,
    "renormalised_top_k": lambda: (
        dataclasses.replace(CFG, norm_topk_prob=True), _seeded(), {}),
    "fp8_cache": lambda: (CFG, _seeded(), {"kv_cache_dtype": "fp8"}),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant):
    cfg, params, eng_kw = WRONG[variant]()
    err, _m, _j = _errors(cfg, params, _seeded(), **eng_kw)
    assert not C.verdict([err], TOLERANCE), err


def test_router_scores_all_experts_then_takes_top_k_as_they_are():
    params = _seeded()
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(1), (5, CFG.hidden_size), jnp.float32)
    w, sel = L._moe_route(CFG, lp, x)
    p = np.asarray(jax.nn.softmax(
        x @ lp["router"].astype(jnp.float32), axis=-1), np.float64)
    order = np.argsort(-p, axis=-1)[:, : CFG.num_experts_per_token]
    np.testing.assert_array_equal(np.asarray(sel), order)
    np.testing.assert_allclose(np.asarray(w), np.take_along_axis(p, order, 1),
                               rtol=1e-5)
    assert np.all(np.asarray(w).sum(-1) < 0.99)  # not renormalised
    # Mixtral's order (softmax over the chosen logits) is the renormalised one
    w_mix, _ = L._moe_route(dataclasses.replace(CFG, moe_family="mixtral"), lp, x)
    np.testing.assert_allclose(np.asarray(w_mix).sum(-1), 1.0, rtol=1e-5)


# --------------------------------------------------------------------------- #
# The MoE choice: wide rows take sort + ragged_dot on the quantized stack
# --------------------------------------------------------------------------- #


def _grouped_int8(w, group=16):
    """{"gq", "gs"} group-wise symmetric int8 of w [..., in, out]."""
    *lead, n_in, n_out = w.shape
    wg = w.reshape(*lead, n_in // group, group, n_out)
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=-2, keepdims=True) / 127.0, 1e-9)
    return {"gq": jnp.clip(jnp.round(wg / s), -127, 127).astype(jnp.int8),
            "gs": s}


QUANTIZERS = {
    "int8": Q.quantize_tensor,
    "grouped_int8": _grouped_int8,
    "int4": lambda w: Q.quantize_tensor_g4(w, 16),
}


@pytest.mark.parametrize("kernel", ["auto", "pallas"])
@pytest.mark.parametrize("form", sorted(QUANTIZERS))
def test_wide_rows_run_top_k_and_match_all_experts(form, kernel, monkeypatch):
    """Above the rule's row bound `_mlp` takes `_moe_ragged` on the quantized
    stack, still stacked over layers, and gives what `_moe_dense` gives on
    the same weights, to float32 reduction order. In an admission program
    (`admit`) where the grouped kernel engages ("pallas": interpret mode
    here, a TPU chip in a cell) the bound is the measured crossover and the
    kernel reads the stack in place; where it does not ("auto" off the TPU),
    and in every decode program, the bound is the stacked kernel's row limit
    (above it `ragged_dot` runs on the layer's slice, or the kernel)."""
    from localai_tpu.ops import quant_matmul as QM

    cfg = dataclasses.replace(CFG, dtype="float32", quant_kernel=kernel)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), _seeded(cfg))
    stack = dict(params["layers"])
    for k in ("w_gate", "w_up", "w_down"):
        stack[k] = QUANTIZERS[form](stack[k])
    layer = jnp.int32(1)
    lp = {k: (Q.StackedLayer(v, layer) if Q.is_quantized(v) else v[1])
          for k, v in stack.items()}
    bound = (QM.MOE_ALL_EXPERTS_MAX_ROWS if kernel == "pallas"
             else L.QUANT_PALLAS_MAX_ROWS)
    rows = bound + 44
    x = jax.random.normal(jax.random.key(2), (rows, cfg.hidden_size), jnp.float32)
    took, real = [], L._moe_ragged

    def spy(*a, **k):
        took.append("ragged")
        return real(*a, **k)

    monkeypatch.setattr(L, "_moe_ragged", spy)
    grouped = []
    monkeypatch.setattr(QM, "note_grouped", lambda: grouped.append(1))
    admit = []
    wide = L._mlp(cfg, lp, x, admit=admit)
    assert took == ["ragged"]
    assert len(grouped) == (3 if kernel == "pallas" else 0)
    # the kernel's walk is reported where it ran: every sorted row is held
    pairs = rows * cfg.num_experts_per_token
    assert [a.tolist() for a in admit] == (
        [[pairs, pairs]] if kernel == "pallas" else [])
    dense = L._moe_dense(cfg, lp, x)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(dense),
                               rtol=2e-4, atol=2e-6)
    # an admission of few rows stays on the all-experts form, and a decode
    # program (no `admit`: 32- and 64-row blocks, a verify chunk of 32 slots
    # x 5, the widest the stacked kernel serves) whatever serves wider rows
    took.clear()
    for n in (32, 64):
        L._mlp(cfg, lp, x[:n], admit=[])
    for n in (32, 64, rows, 160, L.QUANT_PALLAS_MAX_ROWS):
        xn = jnp.resize(x, (n, cfg.hidden_size))
        L._mlp(cfg, lp, xn.reshape(32, 5, -1) if n == 160 else xn)
    assert took == ([] if kernel == "pallas" else ["ragged"])  # 108 | 300
    assert QM.MOE_ALL_EXPERTS_MAX_ROWS >= 64


# --------------------------------------------------------------------------- #
# The decode step hands the kernels the expert stack
# --------------------------------------------------------------------------- #


# dense-cache programs hold no paged-attention site (ops/stacked.SiteCounts)
_NO_PAGED_SITES = {"paged_attention_stacked": 0, "paged_attention_sliced": 0,
                   "paged_attention_native": 0, "paged_attention_f32": 0,
                   "paged_attention_multipage": 0,
                   "paged_attention_onepage": 0,
                   "paged_attention_stream": 0,
                   "paged_attention_prefetch": 0,
                   "paged_attention_value_lanes": 0,
                   "paged_attention_value_row": 0,
                   "pool_write_inplace": 0, "pool_write_scatter": 0,
                   "ssd_decode_pallas": 0, "ssd_decode_xla": 0,
                   "s6_decode_pallas": 0, "s6_decode_xla": 0}


def _pallas_calls(jaxpr, name):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub, name))
    return found


def _moe_decode_step(B):
    cfg = dataclasses.replace(CFG, quant_kernel="pallas")
    params = _seeded(cfg, "int8")
    n, kv = 4, (cfg.num_kv_heads, cfg.head_dim_)
    cache = L.KVCache(
        k=jnp.zeros((cfg.num_layers, B, 32, *kv), jnp.bfloat16),
        v=jnp.zeros((cfg.num_layers, B, 32, *kv), jnp.bfloat16))
    local = jnp.zeros((cfg.num_layers, B, n, *kv), jnp.bfloat16)
    tok = jnp.arange(B, dtype=jnp.int32) % cfg.vocab_size
    fn = lambda p, t, pos, c, lk, lv, s: L.decode_step_windowed(  # noqa: E731
        cfg, p, t, pos, c, lk, lv, s)
    return cfg, fn, (params, tok, tok % 8, cache, local, local, jnp.int32(0))


def test_moe_decode_step_reads_experts_out_of_the_stack():
    """No int8 operand of a kernel is a per-layer copy: the four attention
    projections take [L, in, out], the three expert matmuls [L·E, in, out]
    (block layer·E + e), and the site counter saw 4 + 3 stacked."""
    from localai_tpu.ops.stacked import SiteCounts

    cfg, fn, args = _moe_decode_step(2)
    sites = SiteCounts()
    with sites.tracing("decode_block"):
        jaxpr = jax.make_jaxpr(fn)(*args)
    calls = _pallas_calls(jaxpr.jaxpr, "int8_matmul")
    lead = sorted(
        [v.aval for v in eqn.invars if v.aval.dtype == jnp.int8][0].shape[0]
        for eqn in calls)
    L_, E = cfg.num_layers, cfg.num_experts
    assert lead == [L_] * 4 + [L_ * E] * 3
    # and nowhere in the step is an int8 array sliced to one layer
    sliced = [v.aval.shape for eqn in _all_eqns(jaxpr.jaxpr)
              if eqn.primitive.name == "dynamic_slice"
              for v in eqn.outvars if v.aval.dtype == jnp.int8]
    assert sliced == []
    assert sites.by_program == {
        "decode_block": {"traces": 1, "stacked": 7, "sliced": 0, "grouped": 0,
                         "wholerow": 7, "narrowed": 0, **_NO_PAGED_SITES}}


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_moe_step_above_the_row_limit_counts_seven_sliced_sites(kernel):
    """A wide program: the four attention projections slice their layer in
    front of the XLA form either way; the three expert matmuls take the
    grouped kernel on the stack (4 sliced + 3 grouped), or, where it does
    not engage, `ragged_dot` on the layer's slice (4 + 3 sliced)."""
    from localai_tpu.ops.quant_matmul import QUANT_PALLAS_MAX_ROWS
    from localai_tpu.ops.stacked import SiteCounts

    cfg, fn, args = _moe_decode_step(QUANT_PALLAS_MAX_ROWS + 1)
    cfg = dataclasses.replace(cfg, quant_kernel=kernel)
    sites = SiteCounts()
    with sites.tracing("admit"):
        jaxpr = jax.make_jaxpr(lambda *a: L.decode_step_windowed(cfg, *a))(*args)
    assert not _pallas_calls(jaxpr.jaxpr, "int8_matmul")
    calls = _pallas_calls(jaxpr.jaxpr, "int8_grouped_matmul")
    took = kernel == "pallas"
    assert len(calls) == (3 if took else 0)
    for eqn in calls:  # the stack as it is stored: [L·E, in, out]
        (lead,) = [v.aval.shape[0] for v in eqn.invars if v.aval.dtype == jnp.int8]
        assert lead == cfg.num_layers * cfg.num_experts
    assert sites.by_program["admit"] == {
        "traces": 1, "stacked": 0, "sliced": 4 if took else 7,
        "grouped": 3 if took else 0, "wholerow": 3 if took else 0,
        "narrowed": 0, **_NO_PAGED_SITES}


@pytest.mark.parametrize("slots,window", [(32, 3), (32, 5), (64, 4), (96, 1)])
def test_verify_chunks_and_wide_decode_blocks_keep_all_experts(slots, window):
    """The decode entry points never take the admission bound: a verify
    chunk of B·(k+1) rows and a decode block of more than 64 slots run
    all-experts on the stacked kernel up to its row limit where the grouped
    kernel would engage, as they did before it existed (ROADMAP S4: no
    idle-expert skipping in decode until a cell has real routing)."""
    from localai_tpu.ops import quant_matmul as QM
    from localai_tpu.ops.stacked import SiteCounts

    rows = slots * window
    assert QM.MOE_ALL_EXPERTS_MAX_ROWS < rows <= QM.QUANT_PALLAS_MAX_ROWS
    cfg, step, args = _moe_decode_step(slots)
    params, tok, pos, cache, *_ = args
    if window == 1:
        fn, args = step, args
    else:
        toks = jnp.tile(tok[:, None], (1, window))
        at = pos[:, None] + jnp.arange(window)[None]
        fn = lambda p, t, a, c: L.decode_chunk(cfg, p, t, a, c)  # noqa: E731
        args = (params, toks, at, cache)
    sites = SiteCounts()
    with sites.tracing("decode"):
        jaxpr = jax.make_jaxpr(fn)(*args)
    assert not _pallas_calls(jaxpr.jaxpr, "int8_grouped_matmul")
    lead = sorted(
        [v.aval for v in eqn.invars if v.aval.dtype == jnp.int8][0].shape[0]
        for eqn in _pallas_calls(jaxpr.jaxpr, "int8_matmul"))
    assert lead == [cfg.num_layers] * 4 + [cfg.num_layers * cfg.num_experts] * 3
    assert sites.by_program["decode"]["grouped"] == 0
    assert sites.by_program["decode"]["stacked"] == 7


# --------------------------------------------------------------------------- #
# Loader, presets, synthetic init
# --------------------------------------------------------------------------- #


def test_hf_round_trip_and_torch_parity(tmp_path):
    """save → arch_from_hf_config → load gives the same logits, and HF's own
    `OlmoeForCausalLM` reading the same files agrees with the program and
    with the plain reference."""
    from localai_tpu.engine.weights import (
        arch_from_hf_config,
        load_hf_checkpoint,
        save_hf_checkpoint,
    )

    cfg = dataclasses.replace(CFG, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), _seeded(cfg))
    d = str(tmp_path / "ckpt")
    save_hf_checkpoint(cfg, params, d)
    arch = dataclasses.replace(arch_from_hf_config(d), dtype="float32")
    for f in ("qk_norm_full", "moe_family", "num_experts",
              "num_experts_per_token", "norm_topk_prob", "intermediate_size",
              "num_kv_heads", "rms_eps", "tie_embeddings"):
        assert getattr(arch, f) == getattr(cfg, f), f
    loaded = load_hf_checkpoint(arch, d)
    ids = C.sample_prompts(3, cfg.vocab_size, [24])[0]

    def logits(c, p):
        h, _, _ = L._forward_hidden(
            c, p, jnp.asarray([ids], jnp.int32),
            jnp.asarray([len(ids)], jnp.int32), collect_kv=False)
        return np.asarray(L._unembed(c, p, h.astype(jnp.float32))[0])

    ours = logits(cfg, params)
    np.testing.assert_allclose(logits(arch, loaded), ours, atol=1e-5)

    torch = pytest.importorskip("torch")
    from transformers import OlmoeForCausalLM

    model = OlmoeForCausalLM.from_pretrained(d, torch_dtype=torch.float32).eval()
    with torch.no_grad():
        hf = model(input_ids=torch.tensor([ids])).logits[0].float().numpy()
    assert np.abs(ours - hf).max() < 2e-4
    ref = REF.forward(params, cfg, ids, list(range(len(ids))), pad_to=32)
    hf_lp = np.asarray(jax.nn.log_softmax(jnp.asarray(hf), axis=-1))
    assert np.abs(ref - hf_lp).max() < 2e-4

    int8 = load_hf_checkpoint(arch, d, quantize="int8")
    assert int8["layers"]["w_gate"]["q"].shape == (
        cfg.num_layers, cfg.num_experts, cfg.hidden_size, cfg.moe_inter_size)
    assert int8["layers"]["w_gate"]["q"].dtype == jnp.int8


def test_published_preset_and_its_quantized_tree():
    cfg = get_arch("olmoe-1b-7b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, cfg.intermediate_size, cfg.moe_inter_size,
            cfg.num_experts, cfg.num_experts_per_token, cfg.vocab_size) == (
        16, 2048, 16, 16, 128, 1024, 1024, 64, 8, 50304)
    assert (cfg.n_shared_experts, cfg.first_k_dense, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.tie_embeddings) == (0, 0, False, 1.0, False)
    shapes = jax.eval_shape(lambda k: L.init_params(cfg, k), jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 6.9e9 < n < 6.93e9  # the published 6.9 B
    assert shapes["layers"]["q_norm"].shape == (16, 2048)
    # the synthetic int8 init draws an expert stack a layer at a time
    tree = Q.init_params_quantized(CFG, jax.random.key(0))
    q = np.asarray(tree["layers"]["w_up"]["q"])
    assert q.shape == (CFG.num_layers, CFG.num_experts, CFG.hidden_size,
                       CFG.moe_inter_size)
    assert tree["layers"]["w_up"]["s"].shape == (
        CFG.num_layers, CFG.num_experts, 1, CFG.moe_inter_size)
    assert not np.array_equal(q[0], q[1]) and not np.array_equal(q[0, 0], q[0, 1])
    assert np.abs(q).max() == 127
