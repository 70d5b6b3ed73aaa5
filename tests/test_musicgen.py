"""MusicGen sound generation: HF checkpoint round-trip parity against the
torch reference (VERDICT r3 item 4 — real prompt-to-audio must exist; the
reference serves MusicgenForConditionalGeneration,
backend/python/transformers/backend.py:489-539). Same fixture standard as
test_vits: a tiny random checkpoint saved in the published layout."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
from localai_tpu.models import musicgen as M  # noqa: E402


@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    """A tiny random MusicgenForConditionalGeneration in the real HF layout,
    plus a WordLevel text tokenizer AutoTokenizer can load."""
    # imported here and not at the top: `transformers`' model classes pull in
    # TensorFlow, 7 s that every worker of the run would pay at collection
    from transformers import EncodecConfig
    from transformers import MusicgenConfig as HFMusicgenConfig
    from transformers import MusicgenForConditionalGeneration, T5Config
    from transformers.models.musicgen.configuration_musicgen import (
        MusicgenDecoderConfig,
    )

    d = tmp_path_factory.mktemp("musicgen")
    t5 = T5Config(
        vocab_size=99, d_model=16, d_kv=4, d_ff=32, num_layers=2, num_heads=4,
        relative_attention_num_buckets=8, relative_attention_max_distance=16,
    )
    dec = MusicgenDecoderConfig(
        vocab_size=32, hidden_size=24, num_hidden_layers=2,
        num_attention_heads=4, ffn_dim=48, num_codebooks=4, audio_channels=1,
        pad_token_id=32, bos_token_id=32,  # real checkpoints: pad == vocab_size
    )
    # num_quantizers = 1000·bw // (frame_rate·10); tiny ratios → frame_rate
    # 4000, so bw=160 yields the 4 codebooks the decoder expects.
    enc = EncodecConfig(
        target_bandwidths=[160.0], sampling_rate=32000, audio_channels=1,
        num_filters=8, hidden_size=12, codebook_size=32, codebook_dim=12,
        upsampling_ratios=[4, 2], num_lstm_layers=2, num_residual_layers=1,
        use_causal_conv=False, norm_type="weight_norm", normalize=False,
        kernel_size=3, last_kernel_size=3, residual_kernel_size=3,
        dilation_growth_rate=2,
    )
    cfg = HFMusicgenConfig.from_sub_models_config(t5, enc, dec)
    torch.manual_seed(0)
    model = MusicgenForConditionalGeneration(cfg)
    model.eval()
    model.generation_config.pad_token_id = 32
    model.generation_config.bos_token_id = 32
    model.generation_config.decoder_start_token_id = 32
    model.save_pretrained(str(d), safe_serialization=True)

    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    words = ["music", "happy", "sad", "rock", "jazz", "drum", "guitar", "a", "the"]
    vocab = {"<pad>": 0, "</s>": 1, "<unk>": 2}
    for i, w in enumerate(words):
        vocab[w] = i + 3
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", eos_token="</s>", unk_token="<unk>",
    )
    fast.save_pretrained(str(d))
    return str(d), model


def test_config_and_detection(tiny_ckpt):
    ckpt_dir, _model = tiny_ckpt
    assert M.is_musicgen_dir(ckpt_dir)
    cfg = M.config_from_hf(ckpt_dir)
    assert cfg.num_codebooks == 4 and cfg.vocab_size == 32
    assert cfg.enc_ratios == (4, 2) and cfg.hop_length == 8
    assert cfg.frame_rate == 4000  # 32000 / 8 for the tiny ratios
    assert cfg.pad_token_id == 32  # == vocab_size (the delay pad / start token)


def test_t5_encoder_matches_torch(tiny_ckpt):
    ckpt_dir, model = tiny_ckpt
    cfg, params = M.load_musicgen(ckpt_dir)
    ids = np.array([[5, 9, 3, 1, 0, 0]], np.int32)
    mask = np.array([[1, 1, 1, 1, 0, 0]], np.float32)

    with torch.no_grad():
        ref = model.text_encoder(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
        ).last_hidden_state
        ref = model.enc_to_dec_proj(ref) * torch.tensor(mask)[..., None]
    got = M.encode_text(cfg, params, jnp.asarray(ids), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), ref.numpy(), atol=2e-5)


def test_decoder_logits_match_torch(tiny_ckpt):
    ckpt_dir, model = tiny_ckpt
    cfg, params = M.load_musicgen(ckpt_dir)
    B, K, S, T = 1, 4, 7, 5
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (B, K, S)).astype(np.int32)
    tokens[:, :, 0] = cfg.pad_token_id  # start token
    ids = np.array([[4, 6, 8, 1, 0]], np.int32)
    mask = np.array([[1, 1, 1, 1, 0]], np.float32)

    enc = M.encode_text(cfg, params, jnp.asarray(ids), jnp.asarray(mask))
    got = M.decoder_logits(cfg, params, jnp.asarray(tokens), enc, jnp.asarray(mask))

    with torch.no_grad():
        th_enc = model.text_encoder(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
        ).last_hidden_state
        th_enc = model.enc_to_dec_proj(th_enc) * torch.tensor(mask)[..., None]
        out = model.decoder(
            input_ids=torch.tensor(tokens.reshape(B * K, S), dtype=torch.long),
            encoder_hidden_states=th_enc,
            encoder_attention_mask=torch.tensor(mask, dtype=torch.long),
        ).logits  # [B, K, S, V]
    np.testing.assert_allclose(np.asarray(got), out.numpy().reshape(B, K, S, -1),
                               atol=3e-4)


def test_encodec_decode_matches_torch(tiny_ckpt):
    ckpt_dir, model = tiny_ckpt
    cfg, params = M.load_musicgen(ckpt_dir)
    rng = np.random.default_rng(2)
    F = 24
    codes = rng.integers(0, cfg.enc_codebook_size, (1, cfg.num_codebooks, F)).astype(np.int32)

    got = M.encodec_decode(cfg, params, jnp.asarray(codes))
    with torch.no_grad():
        ref = model.audio_encoder.decode(
            torch.tensor(codes[None], dtype=torch.long), [None]
        ).audio_values  # [B, 1, samples]
    assert got.shape == (1, F * cfg.hop_length)
    np.testing.assert_allclose(np.asarray(got), ref.numpy()[:, 0, :], atol=2e-4)


def test_greedy_generation_matches_hf(tiny_ckpt):
    """End-to-end greedy (CFG=3) generation: delay pattern + doubled-batch
    guidance + EnCodec decode must reproduce HF generate(do_sample=False)."""
    ckpt_dir, model = tiny_ckpt
    cfg, params = M.load_musicgen(ckpt_dir)
    ids = np.array([[5, 9, 1]], np.int32)
    mask = np.array([[1, 1, 1]], np.float32)
    frames = 12

    enc = M.encode_text(cfg, params, jnp.asarray(ids), jnp.asarray(mask))
    codes = M.generate_codes(
        cfg, params, enc, jnp.asarray(mask), jax.random.key(0), frames,
        3.0, 1.0, False, 0,
    )
    wav = M.encodec_decode(cfg, params, codes)

    with torch.no_grad():
        out = model.generate(
            input_ids=torch.tensor(ids, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
            do_sample=False, guidance_scale=3.0,
            # HF's max_length counts the start token: F frames survive the
            # delay-pattern revert when max_new_tokens = F + K - 1.
            max_new_tokens=frames + cfg.num_codebooks - 1,
        )
    assert wav.shape[-1] == out.shape[-1]
    np.testing.assert_allclose(np.asarray(wav), out.numpy()[:, 0, :], atol=5e-3)


def test_sampled_codes_in_range_and_deterministic(tiny_ckpt):
    ckpt_dir, _model = tiny_ckpt
    cfg, params = M.load_musicgen(ckpt_dir)
    ids = np.array([[4, 1]], np.int32)
    mask = np.ones_like(ids, np.float32)
    enc = M.encode_text(cfg, params, jnp.asarray(ids), jnp.asarray(mask))
    a = M.generate_codes(cfg, params, enc, jnp.asarray(mask), jax.random.key(7),
                         8, 3.0, 1.0, True, 10)
    b = M.generate_codes(cfg, params, enc, jnp.asarray(mask), jax.random.key(7),
                         8, 3.0, 1.0, True, 10)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).min() >= 0 and np.asarray(a).max() < cfg.vocab_size


def test_musicgen_engine_and_api(tiny_ckpt, tmp_path):
    """Manager auto-detects the checkpoint; /v1/sound-generation returns a
    WAV of the requested duration (reference: /v1/sound-generation route)."""
    import yaml

    from localai_tpu.audio import read_wav
    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server.app import Request
    from localai_tpu.server.audio_api import AudioApi
    from localai_tpu.server.manager import ModelManager
    from localai_tpu.server.openai_api import OpenAIApi

    ckpt_dir, _model = tiny_ckpt
    (tmp_path / "music.yaml").write_text(yaml.safe_dump({
        "name": "music", "backend": "musicgen", "model": ckpt_dir,
    }))
    manager = ModelManager(ApplicationConfig(models_dir=str(tmp_path)))
    try:
        base = OpenAIApi(manager)
        api = AudioApi(manager, base)

        req = Request(
            method="POST", path="/v1/sound-generation", params={}, query={},
            headers={}, body={"model_id": "music", "text": "happy rock",
                              "duration_seconds": 0.004, "do_sample": True},
        )
        resp = api.sound_generation(req)
        assert resp.content_type == "audio/wav"
        samples, sr = read_wav(resp.body)
        assert sr == 32000
        # 0.004 s at frame_rate 4000 → 16 frames → 128 samples at hop 8
        assert len(samples) == 128

        eng = manager.get("music").engine
        s1, _ = eng.generate_sound("drum guitar", duration_s=0.004, seed=3)
        s2, _ = eng.generate_sound("drum guitar", duration_s=0.004, seed=3)
        np.testing.assert_array_equal(s1, s2)
    finally:
        manager.shutdown()
