"""Qwen2-VL parity tests (VERDICT r4 item 5): native-resolution vision
tower, m-rope position streams, and serving integration — all checked
against the real transformers torch implementation on a fabricated
checkpoint in the exact HF layout.

Reference: the vLLM backend serves Qwen2-VL via multimodal passthrough
(/root/reference/backend/python/vllm/backend.py:211-243); the round-1
target list names "Llava-1.6 / Qwen2-VL".
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytest.importorskip("transformers")

from localai_tpu.models import qwen2_vl as QV

# tiny geometry
VOCAB = 300
HIDDEN, LAYERS, HEADS, KV_HEADS, INTER = 64, 2, 4, 2, 128
V_DEPTH, V_DIM, V_HEADS, V_PATCH = 2, 32, 2, 4
MROPE = [2, 3, 3]  # sums to head_dim/2 = 8
IMG_TOKEN, VSTART, VEND = 299, 297, 298


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    import torch
    from transformers import Qwen2VLConfig, Qwen2VLForConditionalGeneration

    cfg = Qwen2VLConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=INTER,
        num_hidden_layers=LAYERS, num_attention_heads=HEADS,
        num_key_value_heads=KV_HEADS, max_position_embeddings=512,
        rms_norm_eps=1e-6, rope_theta=10000.0, tie_word_embeddings=False,
        rope_scaling={"type": "mrope", "mrope_section": MROPE},
        image_token_id=IMG_TOKEN, vision_start_token_id=VSTART,
        vision_end_token_id=VEND, bos_token_id=1, eos_token_id=2,
        vision_config=dict(
            depth=V_DEPTH, embed_dim=V_DIM, num_heads=V_HEADS, mlp_ratio=2,
            in_channels=3, patch_size=V_PATCH, spatial_merge_size=2,
            temporal_patch_size=2, hidden_size=HIDDEN,
        ),
    )
    torch.manual_seed(0)
    model = Qwen2VLForConditionalGeneration(cfg).eval()
    d = tmp_path_factory.mktemp("tiny-qwen2vl")
    model.save_pretrained(str(d), safe_serialization=True)
    return str(d), model


def _image(h=24, w=16, seed=0):
    return (np.random.default_rng(seed).random((h, w, 3)) * 255).astype(np.uint8)


def _vcfg(ckpt_dir):
    c = QV.vision_config_from_hf(ckpt_dir)
    # tiny pixel budget so the test image is used as-is
    import dataclasses

    return dataclasses.replace(c, min_pixels=8 * 8, max_pixels=1 << 28)


def test_preprocess_matches_hf_processor(ckpt):
    from transformers.models.qwen2_vl.image_processing_qwen2_vl import (
        Qwen2VLImageProcessor,
    )

    ckpt_dir, _ = ckpt
    cfg = _vcfg(ckpt_dir)
    img = _image()
    proc = Qwen2VLImageProcessor(
        patch_size=V_PATCH, merge_size=2, temporal_patch_size=2,
        min_pixels=cfg.min_pixels, max_pixels=cfg.max_pixels,
    )
    want = proc(images=[img], return_tensors="np")
    patches, grid = QV.preprocess(cfg, img)
    np.testing.assert_array_equal(
        np.asarray([grid]), want["image_grid_thw"])
    np.testing.assert_allclose(
        patches, want["pixel_values"], atol=2e-3, rtol=1e-3)


def test_vision_tower_matches_hf(ckpt):
    import torch

    ckpt_dir, model = ckpt
    cfg = _vcfg(ckpt_dir)
    params = QV.load_hf_qwen2_vl_vision(cfg, ckpt_dir)
    img = _image(32, 16, seed=1)
    patches, grid = QV.preprocess(cfg, img)
    angles = QV._vision_rope_angles(cfg, grid)
    got = np.asarray(QV.vision_forward(
        cfg, params, jnp.asarray(patches), jnp.asarray(angles)))
    visual = getattr(model, "visual", None) or model.model.visual
    with torch.no_grad():
        want = visual(
            torch.from_numpy(patches),
            grid_thw=torch.tensor([list(grid)], dtype=torch.long),
        ).numpy()
    assert got.shape == want.shape == (grid[1] * grid[2] // 4, HIDDEN)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-4)


def _prompt_with_image(grid):
    n_img = grid[0] * (grid[1] // 2) * (grid[2] // 2)
    # HF's get_rope_index locates images via vision_start_token_id
    pre = [5, 7, VSTART]
    post = [VEND, 11, 12]
    ids = pre + [IMG_TOKEN] * n_img + post
    return ids, len(pre), n_img


def test_mrope_positions_match_hf_get_rope_index(ckpt):
    import torch

    ckpt_dir, model = ckpt
    grid = (1, 6, 4)
    ids, offset, n_img = _prompt_with_image(grid)
    fn = getattr(model, "get_rope_index", None) or model.model.get_rope_index
    want, want_delta = fn(
        torch.tensor([ids]), image_grid_thw=torch.tensor([list(grid)]),
    )
    pos3, delta = QV.mrope_positions_for_span(len(ids), offset, grid)
    np.testing.assert_array_equal(pos3, want[:, 0].numpy())
    assert delta == int(want_delta[0])


def test_full_prefill_logits_match_hf(ckpt):
    import torch

    from localai_tpu.engine.weights import arch_from_hf_config, load_hf_checkpoint
    from localai_tpu.models import llama

    import dataclasses

    ckpt_dir, model = ckpt
    arch = arch_from_hf_config(ckpt_dir)
    assert tuple(arch.mrope_section) == tuple(MROPE)
    assert arch.attn_qkv_bias
    arch = dataclasses.replace(arch, dtype="float32")  # bitwise-tight parity
    params = load_hf_checkpoint(arch, ckpt_dir)

    cfg = _vcfg(ckpt_dir)
    vparams = QV.load_hf_qwen2_vl_vision(cfg, ckpt_dir)
    img = _image(24, 16, seed=2)
    patches, grid = QV.preprocess(cfg, img)
    angles = QV._vision_rope_angles(cfg, grid)
    feats = np.asarray(QV.vision_forward(
        cfg, vparams, jnp.asarray(patches), jnp.asarray(angles)))

    ids, offset, n_img = _prompt_with_image(grid)
    pos3, _delta = QV.mrope_positions_for_span(len(ids), offset, grid)

    with torch.no_grad():
        want = model(
            input_ids=torch.tensor([ids]),
            pixel_values=torch.from_numpy(patches),
            image_grid_thw=torch.tensor([list(grid)]),
        ).logits[0, -1].numpy()

    S = 32  # bucket
    toks = np.zeros((1, S), np.int32)
    toks[0, : len(ids)] = ids
    mrope = np.zeros((1, 3, S), np.int32)
    mrope[0, :, : len(ids)] = pos3
    logits, _, _ = llama.prefill(
        jax.tree_util.tree_map(lambda x: x, arch), params,
        jnp.asarray(toks), jnp.asarray([len(ids)], jnp.int32),
        inject=(jnp.asarray(feats[None]), jnp.asarray([offset], jnp.int32)),
        mrope=jnp.asarray(mrope),
    )
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=2e-3,
                               rtol=2e-3)


def test_engine_greedy_continuation_matches_hf_generate(ckpt):
    """End-to-end decode parity: the engine's cached-KV decode (plain rope
    at row + delta) must reproduce HF generate token-for-token — the
    strongest check that the m-rope delta bookkeeping is right."""
    import torch

    from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
    from localai_tpu.engine.weights import arch_from_hf_config, load_hf_checkpoint

    import dataclasses

    ckpt_dir, model = ckpt
    arch = dataclasses.replace(arch_from_hf_config(ckpt_dir), dtype="float32")
    params = load_hf_checkpoint(arch, ckpt_dir)
    cfg = _vcfg(ckpt_dir)
    vparams = QV.load_hf_qwen2_vl_vision(cfg, ckpt_dir)
    img = _image(24, 16, seed=3)
    patches, grid = QV.preprocess(cfg, img)
    feats = np.asarray(QV.vision_forward(
        cfg, vparams, jnp.asarray(patches),
        jnp.asarray(QV._vision_rope_angles(cfg, grid))))
    ids, offset, n_img = _prompt_with_image(grid)
    pos3, _ = QV.mrope_positions_for_span(len(ids), offset, grid)

    n_new = 6
    with torch.no_grad():
        out = model.generate(
            input_ids=torch.tensor([ids]),
            pixel_values=torch.from_numpy(patches),
            image_grid_thw=torch.tensor([list(grid)]),
            max_new_tokens=n_new, do_sample=False,
        )
    want = out[0, len(ids):].tolist()

    tok = ByteTokenizer(arch.vocab_size)
    eng = Engine(arch, params, tok,
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                         min_prefill_bucket=16))
    eng.start()
    try:
        handle = eng.submit(GenRequest(
            prompt_ids=ids, max_new_tokens=n_new, ignore_eos=True,
            image_embeds=feats, image_offset=offset, mrope_positions=pos3,
        ))
        text, done = handle.result()
    finally:
        eng.stop()
    # Token ids stream through UTF-8 reassembly (multi-byte lead bytes are
    # held until complete), so compare the DECODED text — byte-identical
    # decode implies token-identical generation for the byte tokenizer.
    assert done.completion_tokens == n_new
    assert text == tok.decode(want), (text, want)


def test_chat_completions_with_image_e2e(ckpt, tmp_path):
    """Manager detects the qwen2_vl layout; /v1/chat/completions with a
    data-URI image serves through the native-resolution tower + m-rope."""
    import base64
    import io
    import threading
    import urllib.request

    import yaml
    from PIL import Image

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager, Router, create_server
    from localai_tpu.server.openai_api import OpenAIApi

    ckpt_dir, _ = ckpt
    # tokenizer: the chat path needs one; ByteTokenizer-compatible ids via
    # a plain template (no tokenizer.json in the fabricated checkpoint).
    (tmp_path / "qv.yaml").write_text(yaml.safe_dump({
        "name": "qv", "model": ckpt_dir, "backend": "vlm",
        "context_size": 128, "max_slots": 2, "max_tokens": 8,
        "temperature": 0.0, "template": {"family": "chatml"},
    }))
    app_cfg = ApplicationConfig(address="127.0.0.1", port=0,
                                models_dir=str(tmp_path))
    mgr = ModelManager(app_cfg)
    router = Router()
    OpenAIApi(mgr).register(router)
    server = create_server(app_cfg, router)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        lm = mgr.get("qv")
        assert getattr(lm.vision, "kind", "") == "qwen2_vl"
        buf = io.BytesIO()
        Image.fromarray(_image(24, 16, seed=4)).save(buf, format="PNG")
        uri = "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()
        req = urllib.request.Request(
            base + "/v1/chat/completions",
            data=json.dumps({
                "model": "qv", "max_tokens": 4,
                "messages": [{"role": "user", "content": [
                    {"type": "text", "text": "what is this?"},
                    {"type": "image_url", "image_url": {"url": uri}},
                ]}],
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=600) as r:
            out = json.loads(r.read())
        assert out["choices"][0]["message"]["content"] is not None
        assert out["usage"]["prompt_tokens"] > 6  # includes the image span
    finally:
        server.shutdown()
        mgr.shutdown()
