"""Learned voice-activity detection: a small conv + GRU network in JAX.

The reference runs the silero-vad ONNX net (backend/go/silero-vad/vad.go:
13-33 — STFT front end, conv encoder, recurrent context, per-chunk speech
probability). Same shape here, TPU-native: log-mel frames → 1-D conv stack →
GRU over time (lax.scan) → per-frame speech probability, then the identical
run-length post-processing the energy detector uses (audio/vad.py). Weights
load from a safetensors file; `train_synthetic` fits the net on generated
speech-like/noise data so a working model can be produced offline (silero's
published weights are ONNX-only and the build environment has no egress —
the test trains and verifies separation end-to-end).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.audio.vad import VADSegment

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class VadNetConfig:
    n_mels: int = 40
    conv_channels: int = 32
    hidden: int = 48
    frame_hop_s: float = 0.01  # log-mel hop (features.HOP / SAMPLE_RATE)


def init_params(cfg: VadNetConfig, key) -> Params:
    k = iter(jax.random.split(key, 8))

    def rnd(shape, scale=0.3):
        return jax.random.normal(next(k), shape, jnp.float32) * scale / np.sqrt(shape[-2] if len(shape) > 1 else 1)

    C, H = cfg.conv_channels, cfg.hidden
    return {
        "conv1_w": rnd((5, cfg.n_mels, C)),  # [k, in, out] conv over time
        "conv1_b": jnp.zeros((C,)),
        "conv2_w": rnd((3, C, C)),
        "conv2_b": jnp.zeros((C,)),
        # GRU: gates [z, r, n] stacked.
        "gru_wx": rnd((C, 3 * H)),
        "gru_wh": rnd((H, 3 * H)),
        "gru_b": jnp.zeros((3 * H,)),
        "head_w": rnd((H, 1)),
        "head_b": jnp.zeros((1,)),
    }


def _conv_t(x, w, b):
    """x [B, T, C_in], w [k, C_in, C_out] — 'same' conv over time."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding="SAME",
        dimension_numbers=("NHC", "HIO", "NHC"),
    ) + b


def forward(cfg: VadNetConfig, p: Params, mel: jnp.ndarray) -> jnp.ndarray:
    """mel [B, T, n_mels] (log-mel) → speech probability [B, T]."""
    x = jax.nn.relu(_conv_t(mel, p["conv1_w"], p["conv1_b"]))
    x = jax.nn.relu(_conv_t(x, p["conv2_w"], p["conv2_b"]))  # [B, T, C]
    H = p["gru_wh"].shape[0]
    B = x.shape[0]

    def step(h, xt):  # xt [B, C]
        g = xt @ p["gru_wx"] + p["gru_b"]
        gh = h @ p["gru_wh"]
        z = jax.nn.sigmoid(g[:, :H] + gh[:, :H])
        r = jax.nn.sigmoid(g[:, H:2 * H] + gh[:, H:2 * H])
        n = jnp.tanh(g[:, 2 * H:] + r * gh[:, 2 * H:])
        h = (1 - z) * n + z * h
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros((B, H)), x.transpose(1, 0, 2))
    logits = hs.transpose(1, 0, 2) @ p["head_w"] + p["head_b"]  # [B, T, 1]
    return jax.nn.sigmoid(logits[..., 0])


def features(audio: np.ndarray, cfg: VadNetConfig, sample_rate: int = 16_000) -> jnp.ndarray:
    """[T_samples] → log-mel [1, T_frames, n_mels]."""
    from localai_tpu.audio.features import log_mel_spectrogram
    from localai_tpu.audio.wav import resample

    x = np.asarray(audio, np.float32)
    if sample_rate != 16_000:
        x = resample(x, sample_rate, 16_000)
    mel = log_mel_spectrogram(jnp.asarray(x), n_mels=cfg.n_mels)  # [T, n_mels]
    return mel[None]


def detect(
    cfg: VadNetConfig,
    p: Params,
    audio: np.ndarray,
    sample_rate: int = 16_000,
    threshold: float = 0.5,
    min_speech_ms: float = 90.0,
    min_silence_ms: float = 150.0,
    pad_ms: float = 30.0,
) -> list[VADSegment]:
    """Speech segments via the learned frame probabilities + the same
    run-length smoothing as energy_vad (silero post-processing semantics)."""
    mel = features(audio, cfg, sample_rate)
    probs = np.asarray(forward(cfg, p, mel)[0])  # [T_frames]
    hop_s = cfg.frame_hop_s
    active = probs > threshold

    min_speech = max(1, int(min_speech_ms / 1000 / hop_s))
    min_sil = max(1, int(min_silence_ms / 1000 / hop_s))
    segs: list[list[int]] = []
    start = None
    for i, a in enumerate(active):
        if a and start is None:
            start = i
        elif not a and start is not None:
            segs.append([start, i])
            start = None
    if start is not None:
        segs.append([start, len(active)])
    merged: list[list[int]] = []
    for s in segs:
        if merged and s[0] - merged[-1][1] < min_sil:
            merged[-1][1] = s[1]
        else:
            merged.append(s)
    pad = pad_ms / 1000.0
    total = len(audio) / sample_rate
    return [
        VADSegment(start=max(0.0, s * hop_s - pad), end=min(total, e * hop_s + pad))
        for s, e in merged
        if e - s >= min_speech
    ]


# --------------------------------------------------------------------------- #
# Persistence + offline training
# --------------------------------------------------------------------------- #


def save_params(path: str, p: Params) -> None:
    from safetensors.numpy import save_file

    # Host copies go through a jitted device-side flatten into a FRESH
    # default-layout buffer, so the bytes that reach the file never depend
    # on the layout XLA chose for a jit output.
    canon = jax.jit(lambda a: jnp.reshape(a, (-1,)))

    def pull(v):
        arr = jnp.asarray(v)
        return np.array(canon(arr), copy=True).reshape(arr.shape)

    save_file({k: pull(v) for k, v in p.items()}, path)


def load_params(path: str) -> Params:
    from safetensors import safe_open

    out: Params = {}
    with safe_open(path, framework="numpy") as f:
        for name in f.keys():
            # copy=True: get_tensor returns a view into safetensors' own
            # buffer; the runtime's h2d upload may be deferred past this
            # context's exit, after which the view reads freed memory
            # (observed as one tensor loading garbage).
            out[name] = jnp.asarray(np.array(f.get_tensor(name), copy=True))
    return out


def config_from_params(p: Params) -> VadNetConfig:
    """Recover the net shape from the weights so a checkpoint trained with a
    non-default VadNetConfig loads correctly (the safetensors file is the
    single source of truth; nothing else is persisted)."""
    conv1_w = np.asarray(p["conv1_w"])
    gru_wx = np.asarray(p["gru_wx"])
    return VadNetConfig(
        n_mels=int(conv1_w.shape[1]),
        conv_channels=int(conv1_w.shape[2]),
        hidden=int(gru_wx.shape[1]) // 3,
    )


def find_weights(model_dir: str) -> Optional[str]:
    for name in ("vad.safetensors", "model.safetensors"):
        path = os.path.join(model_dir, name)
        if os.path.isfile(path):
            return path
    return None


def packaged_weights() -> Optional[str]:
    """The in-tree pretrained artifact (assets/vad-base.safetensors), trained
    offline by train_formant on the formant-synthesis corpus — the zero-
    egress stand-in for silero's published weights. None if not shipped."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "assets", "vad-base.safetensors")
    return path if os.path.isfile(path) else None


def synth_batch(cfg: VadNetConfig, rng: np.random.Generator, n: int = 8,
                seconds: float = 2.0, sr: int = 16_000):
    """Generated training data: harmonic, pitch-modulated bursts (speech-like)
    embedded in noise, labeled per mel frame."""
    from localai_tpu.audio.features import HOP

    T = int(seconds * sr)
    xs, ys = [], []
    for _ in range(n):
        noise = rng.normal(0, 0.02, T).astype(np.float32)
        label = np.zeros(T, np.float32)
        for _burst in range(rng.integers(1, 4)):
            s = int(rng.uniform(0, 0.7) * T)
            d = int(rng.uniform(0.2, 0.5) * sr)
            e = min(T, s + d)
            t = np.arange(e - s) / sr
            f0 = rng.uniform(90, 250)
            f0_t = f0 * (1 + 0.1 * np.sin(2 * np.pi * rng.uniform(2, 5) * t))
            sig = sum(
                rng.uniform(0.2, 1.0) / (h + 1) * np.sin(2 * np.pi * h * np.cumsum(f0_t) / sr)
                for h in range(1, 6)
            )
            env = 0.3 * np.abs(np.sin(2 * np.pi * rng.uniform(2, 6) * t)) + 0.1
            noise[s:e] += (sig * env).astype(np.float32)
            label[s:e] = 1.0
        xs.append(noise)
        frames = label[: (T // HOP) * HOP].reshape(-1, HOP)
        ys.append((frames.mean(axis=1) > 0.5).astype(np.float32))
    mels = jnp.concatenate([features(x, cfg) for x in xs], axis=0)
    y = jnp.asarray(np.stack(ys))[:, : mels.shape[1]]
    return mels, y


def _fit(cfg: VadNetConfig, make_batch, steps: int, seed: int, lr: float,
         refresh_every: int) -> Params:
    import optax

    params = init_params(cfg, jax.random.key(seed))
    tx = optax.adam(lr)
    opt = tx.init(params)

    def loss_fn(p, mel, y):
        probs = forward(cfg, p, mel)
        T = min(probs.shape[1], y.shape[1])
        pr, yy = probs[:, :T], y[:, :T]
        eps = 1e-6
        return -jnp.mean(yy * jnp.log(pr + eps) + (1 - yy) * jnp.log(1 - pr + eps))

    @jax.jit
    def step(p, opt, mel, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, mel, y)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), opt, loss

    mel, y = make_batch()
    for i in range(steps):
        if refresh_every and i % refresh_every == refresh_every - 1:
            mel, y = make_batch()  # fresh data — don't memorize one batch
        params, opt, _loss = step(params, opt, mel, y)
    return params


def train_synthetic(cfg: VadNetConfig, steps: int = 120, seed: int = 0,
                    lr: float = 3e-3) -> Params:
    """Fit the net on quick synthetic speech/noise bursts (smoke-level; the
    shipped artifact uses train_formant)."""
    rng = np.random.default_rng(seed)
    return _fit(cfg, lambda: synth_batch(cfg, rng, n=16), steps, seed, lr, 30)


def frame_labels(ys: list, n_frames: int):
    """Sample labels → per-mel-frame targets [B, n_frames]."""
    from localai_tpu.audio.features import HOP

    out = []
    for label in ys:
        frames = label[: (len(label) // HOP) * HOP].reshape(-1, HOP)
        f = (frames.mean(axis=1) > 0.5).astype(np.float32)
        out.append(f[:n_frames])
    return jnp.asarray(np.stack(out))


def real_noise_clips(sr: int = 16_000) -> list:
    """Real RECORDED non-speech audio available in the zero-egress image
    (pygame's example clips: music, door slams, impacts) — used as hard
    negatives and as mixing backgrounds so the net doesn't fire on real-
    world acoustics the formant synthesizer can't produce. Returns [] when
    unavailable (training then falls back to synthetic-only noise)."""
    import glob

    from localai_tpu.audio.wav import resample

    try:
        import pygame.examples  # noqa: F401 — locate the data dir

        base = os.path.join(os.path.dirname(pygame.examples.__file__), "data")
    except Exception:  # noqa: BLE001 — optional corpus
        return []
    from scipy.io import wavfile

    out = []
    for f in sorted(glob.glob(os.path.join(base, "*.wav"))):
        try:
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                rate, x = wavfile.read(f)
        except Exception:  # noqa: BLE001 — ADPCM etc.
            continue
        x = np.asarray(x, np.float32)
        if x.ndim == 2:
            x = x.mean(axis=1)
        peak = float(np.abs(x).max()) or 1.0
        x = x / peak * 0.5
        if rate != sr:
            x = resample(x, rate, sr)
        if len(x) >= sr // 4:
            out.append(x.astype(np.float32))
    return out


def _crop_to(clip: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    if len(clip) >= n:
        s = int(rng.integers(0, len(clip) - n + 1))
        return clip[s: s + n]
    reps = -(-n // len(clip))
    return np.tile(clip, reps)[:n]


def train_formant(cfg: VadNetConfig, steps: int = 600, seed: int = 0,
                  lr: float = 3e-3, batch_pos: int = 12, batch_neg: int = 6,
                  real_noise: Optional[list] = None):
    """Train on the formant-synthesis corpus (audio/formant_speech.py):
    glottal-source + formant-resonator utterances with word-internal pauses,
    mixed into white/pink/babble/hum noise at 0-30 dB SNR, against hard
    negatives (tones, chords, mains hum, clicks).

    real_noise (r5): real RECORDED clips (real_noise_clips) are mixed as
    additional backgrounds UNDER half the positives and appended as pure
    negatives — the r4 artifact fired on real music (28% of frames on an
    instrumental clip) because every negative it ever saw was synthetic.
    This is what the shipped assets/vad-base.safetensors artifact was
    produced by (see tools/train_vad.py)."""
    from localai_tpu.audio import formant_speech as FS

    rng = np.random.default_rng(seed)
    real = real_noise or []

    def make_batch():
        xs, ys = FS.corpus_batch(rng, n_pos=batch_pos, n_neg=batch_neg)
        if real:
            # Real backgrounds under half the positives (labels unchanged).
            for i in range(0, batch_pos, 2):
                clip = real[int(rng.integers(0, len(real)))]
                bg = _crop_to(clip, len(xs[i]), rng)
                snr = rng.uniform(0.1, 0.5)  # background well below speech
                xs[i] = (xs[i] + snr * bg).astype(np.float32)
            # Pure real negatives.
            for _ in range(max(2, batch_neg // 2)):
                clip = real[int(rng.integers(0, len(real)))]
                n = len(xs[0])
                xs.append(_crop_to(clip, n, rng) * float(rng.uniform(0.5, 1.5)))
                ys.append(np.zeros(n, np.float32))
        mels = jnp.concatenate([features(x, cfg) for x in xs], axis=0)
        y = frame_labels(ys, mels.shape[1])
        return mels, y

    return _fit(cfg, make_batch, steps, seed, lr, refresh_every=10)


def evaluate_real_negatives(cfg: VadNetConfig, p: Params,
                            clips: Optional[list] = None) -> dict:
    """Frame false-positive rate on real recorded non-speech audio.
    Returns {"fp_rate", "n_clips", "worst"}; n_clips 0 when no real audio
    is available in the image."""
    clips = real_noise_clips() if clips is None else clips
    if not clips:
        return {"fp_rate": 0.0, "n_clips": 0, "worst": 0.0}
    rates = []
    for x in clips:
        mel = features(x, cfg)
        probs = np.asarray(forward(cfg, p, mel)[0])
        rates.append(float((probs > 0.5).mean()))
    return {"fp_rate": float(np.mean(rates)), "n_clips": len(clips),
            "worst": float(np.max(rates))}


def evaluate(cfg: VadNetConfig, p: Params, seed: int = 999,
             n_clips: int = 24) -> dict:
    """Held-out frame metrics on fresh formant-corpus clips: returns
    {"f1", "precision", "recall", "neg_fp_rate"}."""
    from localai_tpu.audio import formant_speech as FS

    rng = np.random.default_rng(seed)
    xs, ys = FS.corpus_batch(rng, n_pos=n_clips, n_neg=n_clips // 2)
    mels = jnp.concatenate([features(x, cfg) for x in xs], axis=0)
    y = np.asarray(frame_labels(ys, mels.shape[1]))
    probs = np.asarray(forward(cfg, p, mels))[:, : y.shape[1]]
    pred = probs > 0.5
    pos = y[:n_clips] > 0.5
    tp = float((pred[:n_clips] & pos).sum())
    fp = float((pred[:n_clips] & ~pos).sum())
    fn = float((~pred[:n_clips] & pos).sum())
    prec = tp / max(tp + fp, 1.0)
    rec = tp / max(tp + fn, 1.0)
    f1 = 2 * prec * rec / max(prec + rec, 1e-9)
    neg_fp = float(pred[n_clips:].mean()) if len(pred) > n_clips else 0.0
    return {"f1": f1, "precision": prec, "recall": rec, "neg_fp_rate": neg_fp}
