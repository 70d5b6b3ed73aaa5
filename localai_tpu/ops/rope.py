"""Rotary position embeddings with linear and llama3 frequency scaling.

The reference forwards rope knobs to llama.cpp (core/config/model_config.go:231-237
`rope_scaling`, `rope_freq_base`); here the same knobs select the frequency
schedule used by the JAX model. Frequencies are computed once per call in
float32; XLA constant-folds them under jit when positions are traced but the
config is static.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from localai_tpu.models.config import ArchConfig


def rope_frequencies(cfg: ArchConfig) -> jnp.ndarray:
    """Per-pair inverse frequencies [head_dim/2], float32.

    Implements every scaling family the reference forwards to its engines
    (core/config/model_config.go:231-237 rope_scaling/yarn params →
    grpc-server.cpp params_parse): linear, llama-3 NTK-by-parts, yarn, and
    phi-3 longrope. The matching attention-amplitude factor (yarn mscale /
    longrope scaling) is served by `rope_query_amp`."""
    # Under MLA only the qk_rope_head_dim slice of q/k rotates (HF deepseek
    # configs set head_dim to the same value, but don't rely on it); under
    # `partial_rotary` a GQA head's leading `rotary_dim` lanes, and every
    # schedule below (yarn's ramp too) is computed over those alone.
    hd = cfg.qk_rope_head_dim if cfg.is_mla else cfg.rotary_dim
    dims = jnp.arange(0, hd, 2, dtype=jnp.float32)
    inv_freq = 1.0 / (cfg.rope_theta ** (dims / hd))
    if cfg.rope_scaling == "linear":
        inv_freq = inv_freq / cfg.rope_scaling_factor
    elif cfg.rope_scaling == "llama3":
        # Llama-3.1/3.2 long-context NTK-by-parts scaling.
        low_wavelen = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high_wavelen = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        wavelen = 2.0 * math.pi / inv_freq
        scaled = inv_freq / cfg.rope_scaling_factor
        smooth = (cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor) / (
            cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
        )
        smooth = jnp.clip(smooth, 0.0, 1.0)
        mid = (1.0 - smooth) * scaled + smooth * inv_freq
        inv_freq = jnp.where(wavelen > low_wavelen, scaled, jnp.where(wavelen < high_wavelen, inv_freq, mid))
    elif cfg.rope_scaling == "yarn":
        # YaRN (Peng et al.): interpolate low frequencies by `factor`,
        # extrapolate high frequencies unchanged, with a linear ramp between
        # the beta_fast/beta_slow rotation counts (HF _compute_yarn_parameters).
        factor = cfg.rope_scaling_factor
        orig = cfg.rope_original_max_position

        def correction_dim(n_rot: float) -> float:
            return (hd * math.log(orig / (n_rot * 2 * math.pi))) / (
                2 * math.log(cfg.rope_theta)
            )

        low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
        high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), hd - 1)
        ramp = jnp.clip((dims / 2 - low) / max(high - low, 1e-3), 0.0, 1.0)
        extrapolation_factor = 1.0 - ramp
        inv_freq = (
            inv_freq / factor * (1.0 - extrapolation_factor)
            + inv_freq * extrapolation_factor
        )
    elif cfg.rope_scaling == "longrope":
        # Phi-3 LongRoPE ("su"): a published per-frequency rescale table.
        # The long table serves when the deployment window exceeds the
        # original training window (the static serving choice; the reference
        # delegates the same decision to its engines per max context).
        use_long = cfg.max_position > cfg.rope_original_max_position
        table = cfg.rope_long_factor if use_long else cfg.rope_short_factor
        if table is None:
            raise ValueError(
                "rope_scaling 'longrope' requires long/short factor tables"
            )
        ext = jnp.asarray(table, jnp.float32)
        if ext.shape[0] != hd // 2:
            raise ValueError(
                f"longrope factor table has {ext.shape[0]} entries, head_dim "
                f"{hd} needs {hd // 2}"
            )
        inv_freq = 1.0 / (ext * cfg.rope_theta ** (dims / hd))
    elif cfg.rope_scaling not in (None, ""):
        raise ValueError(f"unknown rope_scaling {cfg.rope_scaling!r}")
    return inv_freq


def rope_frequencies_local(cfg: ArchConfig) -> jnp.ndarray | None:
    """Sliding (local) layers' inverse frequencies, or None when all layers
    share one schedule. Gemma-3 runs local layers on their own UNSCALED base
    (rope_local_base_freq) while global layers use rope_theta + scaling."""
    if not cfg.rope_local_theta:
        return None
    hd = cfg.head_dim_
    dims = jnp.arange(0, hd, 2, dtype=jnp.float32)
    return 1.0 / (cfg.rope_local_theta ** (dims / hd))


def rope_query_amp(cfg: ArchConfig) -> float:
    """Static query pre-multiplier carrying the scaling family's attention-
    amplitude correction. HF scales BOTH cos/sin tables by `attention_factor`
    m (so scores gain m²); scaling q alone by m² is mathematically identical
    and keeps the cached K unmodified. Under `partial_rotary` the tables
    reach the rotated lanes alone, and so does this (`_attn_proj_qkv`)."""
    if cfg.rope_scaling == "yarn":
        m = (
            cfg.rope_attn_factor
            if cfg.rope_attn_factor is not None
            else 0.1 * math.log(cfg.rope_scaling_factor) + 1.0
        )
        return float(m * m)
    if cfg.rope_scaling == "longrope":
        if cfg.rope_attn_factor is not None:
            m = cfg.rope_attn_factor
        else:
            factor = cfg.max_position / max(cfg.rope_original_max_position, 1)
            m = (
                math.sqrt(1.0 + math.log(factor) / math.log(cfg.rope_original_max_position))
                if factor > 1.0
                else 1.0
            )
        return float(m * m)
    return 1.0


def rope_rotate(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Split-half rotation from precomputed angles [..., seq, rot/2];
    x: [..., seq, heads, head_dim]. rot = head_dim rotates the whole head;
    fewer angles (`ArchConfig.partial_rotary`) rotate the leading rot lanes
    in half-split pairs over those lanes (i with i + rot/2) and pass the
    rest through."""
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, rot/2]
    sin = jnp.sin(angles)[..., None, :]
    rot = 2 * angles.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rot], 2, axis=-1)
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rot < x.shape[-1]:
        out.append(xf[..., rot:])
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate half-pairs. x: [..., seq, heads, head_dim], positions: [..., seq]."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., seq, hd/2]
    return rope_rotate(x, angles)


def mrope_angles(pos3: jnp.ndarray, inv_freq: jnp.ndarray,
                 sections: tuple) -> jnp.ndarray:
    """Qwen2-VL multimodal rope angles.

    pos3 [B, 3, S] carries (temporal, height, width) position streams per
    token; `sections` (e.g. (16, 24, 24), summing to head_dim/2) assigns
    each frequency index to one stream — HF Qwen2VLAttention splits the
    duplicated cos/sin tables into mrope_section*2 chunks and takes chunk i
    from stream i%3, which reduces to per-frequency stream selection over
    the first half. Returns angles [B, S, head_dim/2] for rope_rotate.
    Text-only prompts (all three streams equal) reduce exactly to
    apply_rope; that is what makes plain-rope decode with a per-slot
    position delta valid after a multimodal prefill."""
    import numpy as np

    assert sum(sections) == inv_freq.shape[0], (sections, inv_freq.shape)
    axis_of = jnp.asarray(np.repeat(np.arange(3), sections))  # [hd/2]
    pos_sel = jnp.take(pos3, axis_of, axis=1)  # [B, hd/2, S]
    return pos_sel.transpose(0, 2, 1).astype(jnp.float32) * inv_freq
