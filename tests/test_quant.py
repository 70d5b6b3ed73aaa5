"""Weight-only int8 / int4 quantization: numerical closeness, engine serving
(dense + MoE + tp mesh, the Pallas kernels against the XLA form), load-time
quantization and config plumbing. The kernels themselves are
tests/test_quant_kernels.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params, prefill
from localai_tpu.models.quant import matmul, quantize_params, quantize_tensor, unembed_matmul
from localai_tpu.parallel.mesh import MeshPlan


def test_quantize_tensor_roundtrip_error():
    w = jax.random.normal(jax.random.key(0), (64, 128), jnp.float32) * 0.1
    qt = quantize_tensor(w)
    assert qt["q"].dtype == jnp.int8
    deq = qt["q"].astype(jnp.float32) * qt["s"]
    rel = float(jnp.abs(deq - w).max() / jnp.abs(w).max())
    assert rel < 0.01  # per-channel int8: <1% of the channel max

    x = jax.random.normal(jax.random.key(1), (4, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(matmul(x, qt)), np.asarray(x @ w), rtol=0.1, atol=0.05
    )


def test_unembed_matmul_quantized_close():
    w = jax.random.normal(jax.random.key(0), (512, 64), jnp.float32) * 0.1  # [V, D]
    s = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(s, 1e-9)), -127, 127).astype(jnp.int8)
    h = jax.random.normal(jax.random.key(1), (3, 64), jnp.float32)
    got = unembed_matmul(h, {"q": q, "s": s})
    want = unembed_matmul(h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0.15, atol=0.1)


@pytest.mark.parametrize("arch", ["tiny", "tiny-moe"])
def test_quantized_prefill_close_to_full(arch):
    cfg = get_arch(arch)
    params = init_params(cfg, jax.random.key(0))
    qparams = quantize_params(cfg, params, "int8")
    toks = jnp.zeros((1, 32), jnp.int32).at[0, :6].set(jnp.arange(1, 7))
    lens = jnp.array([6], jnp.int32)
    full, _, _ = prefill(cfg, params, toks, lens)
    quant, _, _ = prefill(cfg, qparams, toks, lens)
    cos = float(jnp.sum(full * quant) / (jnp.linalg.norm(full) * jnp.linalg.norm(quant)))
    assert cos > 0.99, f"quantized logits diverged (cos={cos})"


def test_quantized_engine_serves_and_matches_mostly():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    full = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                  engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16))
    quant = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                   engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16),
                   quantization="int8")
    full.start(); quant.start()
    try:
        t_full, ev_f = full.generate([65, 66, 67], max_new_tokens=8, ignore_eos=True)
        t_quant, ev_q = quant.generate([65, 66, 67], max_new_tokens=8, ignore_eos=True)
        assert ev_q.completion_tokens == 8
        # int8 rounding may flip near-tie argmaxes on random init; require a
        # matching prefix rather than full equality.
        assert t_quant[:2] == t_full[:2]
        # rerank/embeddings paths run on quantized weights too
        scores = quant.rerank([65, 66], [[67, 68], [1, 2]])
        assert scores.shape == (2,)
        vecs = quant.embed([[65, 66, 67]])
        assert np.isfinite(vecs).all()
    finally:
        full.stop()
        quant.stop()


def test_quantized_tp_mesh_serves():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 mesh_plan=MeshPlan(tp=2),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16),
                 quantization="int8")
    eng.start()
    try:
        _, ev = eng.generate([10, 20], max_new_tokens=6, ignore_eos=True)
        assert ev.completion_tokens == 6
    finally:
        eng.stop()


def test_quantization_config_plumbs_through(tmp_path):
    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager

    d = tmp_path / "models"
    d.mkdir()
    (d / "q.yaml").write_text(yaml.safe_dump({
        "name": "q", "model": "tiny", "context_size": 64, "max_tokens": 4,
        "quantization": "int8",
    }))
    mgr = ModelManager(ApplicationConfig(models_dir=str(d)))
    lm = mgr.get("q")
    assert isinstance(lm.engine.params["layers"]["wq"], dict)  # quantized form
    text, ev = lm.engine.generate([65], max_new_tokens=2, ignore_eos=True)
    assert ev.kind == "done"
    mgr.shutdown()


def test_load_time_host_quantization(tmp_path):
    """Checkpoint → host-side int8 → engine placement without a bf16 tree
    ever materializing on device (the 8B-on-one-chip path)."""
    import jax as _jax

    from localai_tpu.engine.weights import load_hf_checkpoint, save_hf_checkpoint
    from localai_tpu.models.quant import is_prequantized

    cfg = get_arch("tiny")
    params = init_params(cfg, _jax.random.key(0))
    d = str(tmp_path / "ckpt")
    save_hf_checkpoint(cfg, params, d)

    qparams = load_hf_checkpoint(cfg, d, quantize="int8")
    assert is_prequantized(qparams)
    assert qparams["layers"]["wq"]["q"].dtype == jnp.int8
    assert qparams["lm_head"]["q"].dtype == jnp.int8

    eng = Engine(cfg, qparams, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16),
                 quantization="int8")
    eng.start()
    try:
        _, ev = eng.generate([65, 66], max_new_tokens=6, ignore_eos=True)
        assert ev.completion_tokens == 6
        # Device-quantized engine from the same weights behaves the same.
        eng2 = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                      engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16),
                      quantization="int8")
        eng2.start()
        try:
            t1, _ = eng.generate([7, 8, 9], max_new_tokens=6, ignore_eos=True)
            t2, _ = eng2.generate([7, 8, 9], max_new_tokens=6, ignore_eos=True)
            assert t1 == t2
        finally:
            eng2.stop()
    finally:
        eng.stop()


def test_prequantized_tp_mesh_placement(tmp_path):
    from localai_tpu.engine.weights import load_hf_checkpoint, save_hf_checkpoint

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    d = str(tmp_path / "ckpt")
    save_hf_checkpoint(cfg, params, d)
    qparams = load_hf_checkpoint(cfg, d, quantize="int8")
    eng = Engine(cfg, qparams, ByteTokenizer(cfg.vocab_size),
                 mesh_plan=MeshPlan(tp=2),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128, min_prefill_bucket=16),
                 quantization="int8")
    eng.start()
    try:
        _, ev = eng.generate([10, 20], max_new_tokens=4, ignore_eos=True)
        assert ev.completion_tokens == 4
    finally:
        eng.stop()


def test_init_params_quantized_matches_quantize_params_structure():
    """Leaf-wise quantized init builds the exact tree shape quantize_params
    produces (so shardings/engine treat both identically), without ever
    materializing the full bf16 tree."""
    from localai_tpu.models.quant import init_params_quantized, quantize_params

    for arch in ("tiny", "tiny-moe"):
        cfg = get_arch(arch)
        want = quantize_params(cfg, init_params(cfg, jax.random.key(0)))
        got = init_params_quantized(cfg, jax.random.key(0))
        ws = jax.tree.structure(want)
        gs = jax.tree.structure(got)
        assert ws == gs, f"{arch}: {ws} != {gs}"
        for (pw, w), (pg, g) in zip(
            jax.tree_util.tree_flatten_with_path(want)[0],
            jax.tree_util.tree_flatten_with_path(got)[0],
        ):
            assert pw == pg
            assert w.shape == g.shape, f"{arch} {pw}: {w.shape} != {g.shape}"
            assert w.dtype == g.dtype, f"{arch} {pw}: {w.dtype} != {g.dtype}"


def test_init_params_quantized_serves():
    from localai_tpu.models.quant import init_params_quantized

    cfg = get_arch("tiny")
    eng = Engine(cfg, init_params_quantized(cfg, jax.random.key(0)),
                 ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                         min_prefill_bucket=16))
    eng.start()
    try:
        _, ev = eng.generate([65, 66, 67], max_new_tokens=6, ignore_eos=True)
        assert ev.completion_tokens == 6
    finally:
        eng.stop()


def test_int4_grouped_matmul_close():
    from localai_tpu.models.quant import dequantize_tensor, matmul, quantize_tensor_g4

    w = init_params(get_arch("tiny"), jax.random.key(3))["layers"]["w_up"][0]
    q = quantize_tensor_g4(w)
    assert q["g4"].dtype == jnp.uint8
    assert q["g4"].shape == (w.shape[0] // 32, 16, w.shape[1])
    deq = dequantize_tensor(q)
    rel = float(jnp.abs(deq - w.astype(jnp.float32)).max() / jnp.abs(w).max())
    assert rel < 0.1, rel  # 4-bit grid on random normals
    x = jax.random.normal(jax.random.key(4), (4, w.shape[0]), jnp.bfloat16)
    got = matmul(x, q)
    want = x @ w
    relmm = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert relmm < 0.2, relmm


def test_int4_engine_serves_dense_and_moe():
    for arch in ("tiny", "tiny-moe"):
        cfg = get_arch(arch)
        eng = Engine(cfg, init_params(cfg, jax.random.key(0)),
                     ByteTokenizer(cfg.vocab_size),
                     engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                             min_prefill_bucket=16),
                     quantization="int4")
        eng.start()
        try:
            _, ev = eng.generate([65, 66, 67], max_new_tokens=6, ignore_eos=True)
            assert ev.completion_tokens == 6, arch
        finally:
            eng.stop()


def test_int4_tp_mesh_serves():
    cfg = get_arch("tiny")
    eng = Engine(cfg, init_params(cfg, jax.random.key(0)),
                 ByteTokenizer(cfg.vocab_size),
                 mesh_plan=MeshPlan(tp=2),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                         min_prefill_bucket=16),
                 quantization="int4")
    eng.start()
    try:
        _, ev = eng.generate([10, 20], max_new_tokens=6, ignore_eos=True)
        assert ev.completion_tokens == 6
    finally:
        eng.stop()


def test_int4_load_time_host_quantization(tmp_path):
    """HF checkpoint + quantization: int4 → grouped-4bit weights on load
    (not silently int8)."""
    from localai_tpu.engine.weights import load_hf_checkpoint, save_hf_checkpoint

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    d = str(tmp_path / "ckpt")
    save_hf_checkpoint(cfg, params, d)
    loaded = load_hf_checkpoint(cfg, d, quantize="int4")
    wq = loaded["layers"]["wq"]
    assert isinstance(wq, dict) and "g4" in wq
    assert isinstance(loaded["lm_head"], dict) and "q" in loaded["lm_head"]
    with pytest.raises(ValueError):
        load_hf_checkpoint(cfg, d, quantize="int5")


def test_manager_preset_int4_and_none(tmp_path):
    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager

    d = tmp_path / "models"
    d.mkdir()
    (d / "q4.yaml").write_text(yaml.safe_dump({
        "name": "q4", "model": "tiny", "context_size": 64, "max_tokens": 4,
        "quantization": "int4",
    }))
    (d / "qn.yaml").write_text(yaml.safe_dump({
        "name": "qn", "model": "tiny", "context_size": 64, "max_tokens": 4,
        "quantization": "none",
    }))
    mgr = ModelManager(ApplicationConfig(models_dir=str(d), max_active_models=4))
    try:
        lm = mgr.get("q4")
        assert "g4" in lm.engine.params["layers"]["wq"]  # actually int4
        _, ev = lm.engine.generate([65], max_new_tokens=2, ignore_eos=True)
        assert ev.kind == "done"
        lm2 = mgr.get("qn")
        assert not isinstance(lm2.engine.params["layers"]["wq"], dict)
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("arch", ["tiny", "tiny-moe"])
def test_engine_gauges_count_stacked_and_sliced_sites(arch):
    """Engine.metrics() totals the sites of every program traced; the decode
    block (2 rows) takes the stack at all seven, no kernel call is handed a
    slice, every kernel call got whole-row weight blocks from the block rule
    (dense and MoE), and what the XLA engine slices it says too."""
    cfg = get_arch(arch)
    params = init_params(cfg, jax.random.key(0))
    seen = {}
    for impl in ("pallas", "xla"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                    min_prefill_bucket=16, quant_kernel=impl),
            quantization="int8",
        )
        try:
            _, ev = eng.generate(list(range(1, 20)), max_new_tokens=6,
                                 ignore_eos=True)
            assert ev.kind == "done"
            seen[impl] = (dict(eng.quant_sites.by_program), eng.metrics())
        finally:
            eng.stop()
    by_program, metrics = seen["pallas"]
    block = by_program["decode_block"]
    assert block["stacked"] == 7 * block["traces"] and block["sliced"] == 0
    assert (block["wholerow"], block["narrowed"]) == (block["stacked"], 0)
    assert metrics["quant_matmul_stacked_sites"] == sum(
        p["stacked"] for p in by_program.values())
    assert metrics["quant_matmul_sliced_sites"] == 0
    assert metrics["quant_matmul_wholerow_sites"] == sum(
        p["wholerow"] for p in by_program.values()) >= block["stacked"]
    assert metrics["quant_matmul_narrowed_sites"] == 0
    by_program, metrics = seen["xla"]
    assert metrics["quant_matmul_stacked_sites"] == 0
    assert metrics["quant_matmul_sliced_sites"] == sum(
        p["sliced"] for p in by_program.values()) >= 7
    assert metrics["quant_matmul_wholerow_sites"] == 0
    assert metrics["quant_matmul_narrowed_sites"] == 0


@pytest.mark.parametrize("arch,mode", [
    ("tiny", "int4"),
    # DeepSeek layout: two stacks (the MoE stack starts at layer 1, lo > 0),
    # MLA's extra projections, quantized experts and the shared expert.
    ("tiny-mla", "int8"),
])
def test_quant_engine_pallas_matches_xla(arch, mode):
    """End-to-end: a quantized engine forced onto the Pallas dequant-matmul
    kernels (interpret mode on CPU) decodes the same greedy tokens as the
    XLA dequant path — quant_kernel is the dispatch knob, exactly like
    paged_kernel for the attention kernel."""
    cfg = get_arch(arch)
    params = init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 20))
    texts = {}
    for impl in ("xla", "pallas"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                    min_prefill_bucket=16, quant_kernel=impl),
            quantization=mode,
        )
        try:
            text, ev = eng.generate(prompt, max_new_tokens=6, ignore_eos=True)
            assert ev.kind == "done"
            texts[impl] = text
        finally:
            eng.stop()
    assert texts["pallas"] == texts["xla"]


@pytest.mark.slow
@pytest.mark.multichip
def test_quant_engine_pallas_tp2_matches_xla(multichip):
    """Sharded dispatch end-to-end: tp=2 int4 engine on the forced CPU mesh,
    Pallas (shard_map + psum boundary) vs XLA dequant — same greedy tokens,
    and the engine serves normally."""
    if multichip is True:
        return
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 16))
    texts = {}
    for impl in ("xla", "pallas"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            mesh_plan=MeshPlan(tp=2),
            engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                    min_prefill_bucket=16, quant_kernel=impl),
            quantization="int4",
        )
        try:
            text, ev = eng.generate(prompt, max_new_tokens=6, ignore_eos=True)
            assert ev.completion_tokens == 6
            texts[impl] = text
        finally:
            eng.stop()
    assert texts["pallas"] == texts["xla"]


def test_quant_kernel_validation_and_env(monkeypatch):
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError):
        Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
               engine_cfg=EngineConfig(max_slots=1, max_seq=64,
                                       quant_kernel="nope"))
    # Env override wins over the EngineConfig default and lands on cfg.
    monkeypatch.setenv("LOCALAI_QUANT_KERNEL", "xla")
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(max_slots=1, max_seq=64,
                                         min_prefill_bucket=16))
    try:
        assert eng.ecfg.quant_kernel == "xla"
        assert eng.cfg.quant_kernel == "xla"
    finally:
        eng.stop()
