"""VITS TTS: HF checkpoint round-trip parity against the torch reference
(VERDICT r2 item 7 — a real published-voice architecture must load and
match; same standard as whisper's HF round-trip test)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
from localai_tpu.models import vits as V  # noqa: E402


@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    """A tiny random VitsModel saved in the real HF layout."""
    # imported here and not at the top: `transformers`' model classes pull in
    # TensorFlow, 7 s that every worker of the run would pay at collection
    from transformers import VitsConfig as HFVitsConfig
    from transformers import VitsModel

    d = tmp_path_factory.mktemp("vits")
    cfg = HFVitsConfig(
        vocab_size=40, hidden_size=16, num_hidden_layers=2, num_attention_heads=2,
        window_size=4, ffn_dim=32, ffn_kernel_size=3, flow_size=16,
        spectrogram_bins=9, prior_encoder_num_flows=2,
        prior_encoder_num_wavenet_layers=2, posterior_encoder_num_wavenet_layers=2,
        duration_predictor_num_flows=2, duration_predictor_flow_bins=4,
        depth_separable_num_layers=2, duration_predictor_kernel_size=3,
        duration_predictor_filter_channels=16,
        upsample_initial_channel=16, upsample_rates=[2, 2],
        upsample_kernel_sizes=[4, 4], resblock_kernel_sizes=[3],
        resblock_dilation_sizes=[[1, 3]], wavenet_dilation_rate=1,
        sampling_rate=16000,
    )
    torch.manual_seed(0)
    model = VitsModel(cfg)
    model.eval()
    model.save_pretrained(str(d), safe_serialization=True)
    vocab = {"<pad>": 0}
    for i, ch in enumerate("abcdefghijklmnopqrstuvwxyz ?!.,'-"):
        vocab[ch] = i + 1
    with open(d / "vocab.json", "w") as f:
        json.dump(vocab, f)
    with open(d / "tokenizer_config.json", "w") as f:
        json.dump({"add_blank": True, "normalize": True}, f)
    return str(d), model


def test_vits_waveform_matches_torch(tiny_ckpt):
    """Deterministic (noise=0) JAX synthesis must match torch sample-for-sample."""
    ckpt_dir, model = tiny_ckpt
    cfg, params, tok = V.load_vits(ckpt_dir)
    assert V.is_vits_dir(ckpt_dir)

    ids = tok.encode("hello world")
    assert ids[0] == 0 and len(ids) % 2 == 1  # blank-interleaved

    model.noise_scale = 0.0
    model.noise_scale_duration = 0.0
    model.speaking_rate = 1.0
    with torch.no_grad():
        out = model(input_ids=torch.tensor([ids]))
    ref = out.waveform[0].numpy()
    n_ref = int(out.sequence_lengths[0])

    T = len(ids)
    up = int(np.prod(cfg.upsample_rates))
    frames = n_ref // up + 16  # static budget; sized from the reference run
    wav, n_valid = V.synthesize(
        cfg, params, jnp.asarray([ids], jnp.int32), frames,
        jnp.zeros((1, 2, T)), jnp.zeros((1, frames, cfg.flow_size)),
    )
    n = int(n_valid[0])
    assert n == n_ref, (n, n_ref)
    got = np.asarray(wav[0][:n])
    assert np.allclose(got, ref[:n], atol=2e-4), float(np.abs(got - ref[:n]).max())


def test_vits_token_bucket_padding_matches_exact(tiny_ckpt):
    """A token-bucketed (padded + masked) run must reproduce the exact-length
    run sample-for-sample — this is what lets VitsEngine compile once per
    (token, frame) bucket instead of once per text length."""
    ckpt_dir, _ = tiny_ckpt
    cfg, params, tok = V.load_vits(ckpt_dir)
    ids = tok.encode("bucketed run")
    T, TB, frames = len(ids), 64, 256
    exact, n_exact = V.synthesize(
        cfg, params, jnp.asarray([ids], jnp.int32), frames,
        jnp.zeros((1, 2, T)), jnp.zeros((1, frames, cfg.flow_size)),
    )
    padded = np.zeros((1, TB), np.int32)
    padded[0, :T] = ids
    bucketed, n_bucket = V.synthesize(
        cfg, params, jnp.asarray(padded), frames,
        jnp.zeros((1, 2, TB)), jnp.zeros((1, frames, cfg.flow_size)),
        n_tokens=jnp.asarray([T], jnp.int32),
    )
    n = int(n_exact[0])
    assert int(n_bucket[0]) == n
    a, b = np.asarray(exact[0][:n]), np.asarray(bucketed[0][:n])
    assert np.allclose(a, b, atol=2e-5), float(np.abs(a - b).max())


def test_vits_speaking_rate_changes_length(tiny_ckpt):
    ckpt_dir, _ = tiny_ckpt
    cfg, params, tok = V.load_vits(ckpt_dir)
    ids = jnp.asarray([tok.encode("speaking rate test")], jnp.int32)
    T = ids.shape[1]
    frames = 96 * T  # generous budget so neither run clips
    _, n_slow = V.synthesize(cfg, params, ids, frames,
                             jnp.zeros((1, 2, T)), jnp.zeros((1, frames, cfg.flow_size)),
                             speaking_rate=1.0)
    _, n_fast = V.synthesize(cfg, params, ids, frames,
                             jnp.zeros((1, 2, T)), jnp.zeros((1, frames, cfg.flow_size)),
                             speaking_rate=4.0)
    assert int(n_slow[0]) > int(n_fast[0])


def test_vits_serves_through_manager(tiny_ckpt, tmp_path):
    """backend: tts + an HF VITS dir loads the neural voice and synthesizes
    through the uniform engine interface (manager auto-detection)."""
    import yaml

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager

    ckpt_dir, _ = tiny_ckpt
    (tmp_path / "voice.yaml").write_text(yaml.safe_dump({
        "name": "voice", "backend": "tts", "model": ckpt_dir,
    }))
    manager = ModelManager(ApplicationConfig(models_dir=str(tmp_path)))
    try:
        lm = manager.get("voice")
        from localai_tpu.engine.audio_engine import VitsEngine

        assert isinstance(lm.engine, VitsEngine)
        samples, sr = lm.engine.synthesize("hello from the tpu")
        assert sr == lm.engine.cfg.sample_rate
        assert samples.ndim == 1 and len(samples) > 0
        assert np.isfinite(samples).all()
        chunks = list(lm.engine.synthesize_stream("one. two. three."))
        assert len(chunks) == 3
    finally:
        manager.shutdown()
