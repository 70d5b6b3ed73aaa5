"""Latent diffusion (Stable-Diffusion-1.5-class) in JAX, loading real HF
diffusers-layout checkpoints.

Reference: the diffusers backend's dynamic pipeline registry
(/root/reference/backend/python/diffusers/backend.py:27-120) serves SD/SDXL
class models; the GGML SD backend (backend/go/stablediffusion-ggml) covers
the same ground natively. TPU-native shape: the three submodels (CLIP text
encoder, UNet2DCondition, VAE) are plain jitted functions over NHWC arrays —
convs lower to MXU through XLA, the denoise step jits once per (batch, size)
and lax.scan's over scheduler steps on device.

Checkpoint layout (diffusers): model_index.json + {text_encoder,unet,vae}/
config.json + *.safetensors with torch names. Weights load into flat
name→array dicts (1:1 with the published names, so parity is auditable);
convs transpose OIHW→HWIO, linears transpose to [in, out] at load.

Schedulers: DDIM (eta=0) and Euler-ancestral, both over the scaled-linear
beta schedule the SD family trains with.
"""

from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("localai_tpu.latent_diffusion")

Params = dict[str, jnp.ndarray]

# The serving scheduler surface (reference: diffusers backend.py:100-168
# A1111 name mapping). "_karras" suffix and "k_" prefix both select Karras
# sigma spacing for the k-diffusion family.
K_SCHEDULERS = ("euler", "euler_a", "dpmpp_2m", "heun", "lms", "dpm_2",
                "dpm_2_a", "dpmpp_sde", "dpmpp_2m_sde")
T_SCHEDULERS = ("ddim", "pndm", "unipc")
SUPPORTED_SCHEDULERS = frozenset(
    T_SCHEDULERS + K_SCHEDULERS
    + tuple(f"{s}_karras" for s in K_SCHEDULERS)
    + tuple(f"k_{s}" for s in K_SCHEDULERS)
)


def resolve_scheduler(name: str) -> tuple[str, bool]:
    """A serving scheduler name → (base scheduler, Karras sigma spacing).
    `k_<base>` and `<base>_karras` are two spellings of one thing; the
    timestep family (ddim, pndm, unipc) has no Karras variant."""
    base, karras = name, False
    if base.startswith("k_"):
        base, karras = base[2:], True
    if base.endswith("_karras"):
        base, karras = base[: -len("_karras")], True
    if (base not in K_SCHEDULERS + T_SCHEDULERS
            or (karras and base in T_SCHEDULERS)):
        raise ValueError(
            f"unknown scheduler {base!r} (supported: "
            + ", ".join(T_SCHEDULERS + K_SCHEDULERS)
            + ", plus _karras/k_ variants of "
            + ", ".join(K_SCHEDULERS) + ")"
        )
    return base, karras


# --------------------------------------------------------------------------- #
# Configs (subset of the diffusers configs we consume)
# --------------------------------------------------------------------------- #


@dataclass
class CLIPTextConfig:
    vocab_size: int = 49408
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 77
    hidden_act: str = "quick_gelu"
    layer_norm_eps: float = 1e-5
    projection_dim: int = 0  # >0: CLIPTextModelWithProjection (SDXL encoder 2)
    eos_token_id: int = 49407  # pooling position (HF CLIP semantics)


@dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    sample_size: int = 64
    block_out_channels: tuple = (320, 640, 1280, 1280)
    down_block_types: tuple = (
        "CrossAttnDownBlock2D", "CrossAttnDownBlock2D",
        "CrossAttnDownBlock2D", "DownBlock2D",
    )
    up_block_types: tuple = (
        "UpBlock2D", "CrossAttnUpBlock2D",
        "CrossAttnUpBlock2D", "CrossAttnUpBlock2D",
    )
    layers_per_block: int = 2
    attention_head_dim: Any = 8  # int or per-block list
    cross_attention_dim: int = 768
    norm_num_groups: int = 32
    flip_sin_to_cos: bool = True
    freq_shift: int = 0
    # SDXL: transformer depth per level ([1, 2, 10] for the base model) and
    # the "text_time" micro-conditioning pathway (pooled text embedding +
    # six size/crop ids fourier-embedded into the time embedding).
    transformer_layers_per_block: Any = 1  # int or per-block list
    addition_embed_type: str = ""  # "" | "text_time"
    addition_time_embed_dim: int = 256
    projection_class_embeddings_input_dim: int = 0

    def heads_for(self, block_idx: int) -> int:
        # diffusers quirk: UNet2DConditionModel's `attention_head_dim` is
        # used as the NUMBER of heads (upstream keeps the misnomer for
        # back-compat; SD1.5's 8 and SDXL's [5,10,20] are head counts).
        if isinstance(self.attention_head_dim, (list, tuple)):
            return int(self.attention_head_dim[block_idx])
        return int(self.attention_head_dim)

    def tx_depth_for(self, block_idx: int) -> int:
        if isinstance(self.transformer_layers_per_block, (list, tuple)):
            return int(self.transformer_layers_per_block[block_idx])
        return int(self.transformer_layers_per_block)


@dataclass
class VAEConfig:
    in_channels: int = 3
    out_channels: int = 3
    latent_channels: int = 4
    block_out_channels: tuple = (128, 256, 512, 512)
    layers_per_block: int = 2
    norm_num_groups: int = 32
    scaling_factor: float = 0.18215
    # Flux-class VAEs recenter latents: z_model = (z - shift) * scale.
    shift_factor: float = 0.0

    @property
    def spatial_scale(self) -> int:
        """Pixel-per-latent factor: one 2x resampler between each block
        pair (8 for the SD family's 4-block VAE)."""
        return 2 ** (len(self.block_out_channels) - 1)


@dataclass
class SDPipelineConfig:
    text: CLIPTextConfig = field(default_factory=CLIPTextConfig)
    unet: UNetConfig = field(default_factory=UNetConfig)
    vae: VAEConfig = field(default_factory=VAEConfig)
    # SDXL second text encoder (OpenCLIP bigG class); None for the SD family.
    text2: Optional[CLIPTextConfig] = None
    # scaled-linear schedule (SD family)
    num_train_timesteps: int = 1000
    beta_start: float = 0.00085
    beta_end: float = 0.012
    prediction_type: str = "epsilon"  # | "v_prediction"

    @property
    def is_xl(self) -> bool:
        return self.text2 is not None


# --------------------------------------------------------------------------- #
# Primitive layers (NHWC)
# --------------------------------------------------------------------------- #


def _conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
          stride: int = 1, pad: int = 1) -> jnp.ndarray:
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + b.astype(x.dtype)


def _linear(x: jnp.ndarray, p: Params, name: str) -> jnp.ndarray:
    return x @ p[f"{name}.weight"].astype(x.dtype) + p[f"{name}.bias"].astype(x.dtype)


def _group_norm(x: jnp.ndarray, w, b, groups: int = 32, eps: float = 1e-6) -> jnp.ndarray:
    c = x.shape[-1]
    g = groups
    # normalize over all spatial positions and the in-group channels
    xf = x.astype(jnp.float32).reshape(x.shape[0], -1, g, c // g)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = xf.var(axis=(1, 3), keepdims=True)
    xn = (xf - mean) * jax.lax.rsqrt(var + eps)
    xn = xn.reshape(x.shape).astype(x.dtype)
    return xn * w.astype(x.dtype) + b.astype(x.dtype)


def _layer_norm(x: jnp.ndarray, w, b, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype)) * w.astype(x.dtype) + b.astype(x.dtype)


def _attention(q, k, v, heads: int) -> jnp.ndarray:
    """q [B, Nq, C], k/v [B, Nk, C] → [B, Nq, C]."""
    B, Nq, C = q.shape
    hd = C // heads
    q = q.reshape(B, Nq, heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, -1, heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, -1, heads, hd).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / np.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out.transpose(0, 2, 1, 3).reshape(B, Nq, C)


def get_timestep_embedding(t: jnp.ndarray, dim: int,
                           flip_sin_to_cos: bool = True,
                           freq_shift: float = 0.0) -> jnp.ndarray:
    """diffusers get_timestep_embedding semantics (t [B] → [B, dim])."""
    half = dim // 2
    exponent = -np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
    exponent = exponent / (half - freq_shift)
    emb = jnp.exp(exponent)[None, :] * t.astype(jnp.float32)[:, None]
    sin, cos = jnp.sin(emb), jnp.cos(emb)
    return jnp.concatenate([cos, sin] if flip_sin_to_cos else [sin, cos], axis=-1)


# --------------------------------------------------------------------------- #
# CLIP text encoder (causal; quick-gelu)
# --------------------------------------------------------------------------- #


def clip_hidden_states(cfg: CLIPTextConfig, p: Params,
                       ids: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, 77] int32 → (penultimate hidden [B, 77, C], final normed [B, 77, C]).

    The penultimate state (hidden_states[-2], no final norm) is what SDXL
    conditions on from both encoders; the final normed state is SD1.5's
    context and the source of the pooled projection."""
    B, S = ids.shape
    h = p["text_model.embeddings.token_embedding.weight"][ids]
    h = h + p["text_model.embeddings.position_embedding.weight"][None, :S]
    mask = jnp.triu(jnp.full((S, S), -jnp.inf, jnp.float32), k=1)

    def act(x):
        if cfg.hidden_act == "quick_gelu":
            return x * jax.nn.sigmoid(1.702 * x)
        return jax.nn.gelu(x, approximate=False)

    penultimate = h
    for i in range(cfg.num_hidden_layers):
        if i == cfg.num_hidden_layers - 1:
            penultimate = h  # hidden_states[-2]: before the last layer
        pre = f"text_model.encoder.layers.{i}"
        r = h
        h = _layer_norm(h, p[f"{pre}.layer_norm1.weight"], p[f"{pre}.layer_norm1.bias"],
                        cfg.layer_norm_eps)
        q = _linear(h, p, f"{pre}.self_attn.q_proj")
        k = _linear(h, p, f"{pre}.self_attn.k_proj")
        v = _linear(h, p, f"{pre}.self_attn.v_proj")
        hd = cfg.hidden_size // cfg.num_attention_heads
        qh = q.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
        kh = k.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
        vh = v.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) / np.sqrt(hd)
        probs = jax.nn.softmax(scores + mask, axis=-1).astype(vh.dtype)
        a = jnp.einsum("bhqk,bhkd->bhqd", probs, vh).transpose(0, 2, 1, 3).reshape(B, S, -1)
        h = r + _linear(a, p, f"{pre}.self_attn.out_proj")
        r = h
        h = _layer_norm(h, p[f"{pre}.layer_norm2.weight"], p[f"{pre}.layer_norm2.bias"],
                        cfg.layer_norm_eps)
        h = r + _linear(act(_linear(h, p, f"{pre}.mlp.fc1")), p, f"{pre}.mlp.fc2")
    final = _layer_norm(
        h, p["text_model.final_layer_norm.weight"],
        p["text_model.final_layer_norm.bias"], cfg.layer_norm_eps,
    )
    return penultimate, final


def clip_encode(cfg: CLIPTextConfig, p: Params, ids: jnp.ndarray) -> jnp.ndarray:
    """[B, 77] int32 → last hidden state [B, 77, C] (what SD conditions on)."""
    return clip_hidden_states(cfg, p, ids)[1]


def clip_pooled_projection(cfg: CLIPTextConfig, p: Params, ids: jnp.ndarray,
                           final: jnp.ndarray) -> jnp.ndarray:
    """CLIPTextModelWithProjection pooling: the first EOS position's final
    hidden state through text_projection (no bias). HF semantics: legacy
    configs (eos_token_id == 2) take argmax of the ids (EOS is the highest
    id in the CLIP vocab); otherwise the first eos_token_id occurrence."""
    if cfg.eos_token_id == 2:
        eos_pos = jnp.argmax(ids, axis=-1)
    else:
        eos_pos = jnp.argmax((ids == cfg.eos_token_id).astype(jnp.int32), axis=-1)
    pooled = jnp.take_along_axis(final, eos_pos[:, None, None], axis=1)[:, 0]
    if "text_projection.weight" in p:
        pooled = pooled @ p["text_projection.weight"].astype(pooled.dtype)
    return pooled


# --------------------------------------------------------------------------- #
# UNet2DCondition
# --------------------------------------------------------------------------- #


def _resnet(p: Params, pre: str, x: jnp.ndarray, temb: jnp.ndarray,
            groups: int) -> jnp.ndarray:
    h = _group_norm(x, p[f"{pre}.norm1.weight"], p[f"{pre}.norm1.bias"], groups)
    h = _conv(jax.nn.silu(h), p[f"{pre}.conv1.weight"], p[f"{pre}.conv1.bias"])
    if f"{pre}.time_emb_proj.weight" in p:
        t = _linear(jax.nn.silu(temb), p, f"{pre}.time_emb_proj")
        h = h + t[:, None, None, :]
    h = _group_norm(h, p[f"{pre}.norm2.weight"], p[f"{pre}.norm2.bias"], groups)
    h = _conv(jax.nn.silu(h), p[f"{pre}.conv2.weight"], p[f"{pre}.conv2.bias"])
    if f"{pre}.conv_shortcut.weight" in p:
        x = _conv(x, p[f"{pre}.conv_shortcut.weight"], p[f"{pre}.conv_shortcut.bias"], pad=0)
    return x + h


def _basic_transformer(p: Params, pre: str, h: jnp.ndarray, ctx: jnp.ndarray,
                       heads: int) -> jnp.ndarray:
    # self-attention
    r = h
    n = _layer_norm(h, p[f"{pre}.norm1.weight"], p[f"{pre}.norm1.bias"])
    q = n @ p[f"{pre}.attn1.to_q.weight"].astype(h.dtype)
    k = n @ p[f"{pre}.attn1.to_k.weight"].astype(h.dtype)
    v = n @ p[f"{pre}.attn1.to_v.weight"].astype(h.dtype)
    h = r + _linear(_attention(q, k, v, heads), p, f"{pre}.attn1.to_out.0")
    # cross-attention over text states
    r = h
    n = _layer_norm(h, p[f"{pre}.norm2.weight"], p[f"{pre}.norm2.bias"])
    q = n @ p[f"{pre}.attn2.to_q.weight"].astype(h.dtype)
    k = ctx @ p[f"{pre}.attn2.to_k.weight"].astype(ctx.dtype)
    v = ctx @ p[f"{pre}.attn2.to_v.weight"].astype(ctx.dtype)
    h = r + _linear(_attention(q, k.astype(h.dtype), v.astype(h.dtype), heads),
                    p, f"{pre}.attn2.to_out.0")
    # geglu feed-forward
    r = h
    n = _layer_norm(h, p[f"{pre}.norm3.weight"], p[f"{pre}.norm3.bias"])
    proj = _linear(n, p, f"{pre}.ff.net.0.proj")
    a, gate = jnp.split(proj, 2, axis=-1)
    return r + _linear(a * jax.nn.gelu(gate), p, f"{pre}.ff.net.2")


def _spatial_transformer(p: Params, pre: str, x: jnp.ndarray, ctx: jnp.ndarray,
                         heads: int, groups: int, depth: int = 1) -> jnp.ndarray:
    B, H, W, C = x.shape
    r = x
    h = _group_norm(x, p[f"{pre}.norm.weight"], p[f"{pre}.norm.bias"], groups)
    use_linear = p[f"{pre}.proj_in.weight"].ndim == 2
    if use_linear:
        h = h.reshape(B, H * W, C)
        h = _linear(h, p, f"{pre}.proj_in")
    else:
        h = _conv(h, p[f"{pre}.proj_in.weight"], p[f"{pre}.proj_in.bias"], pad=0)
        h = h.reshape(B, H * W, C)
    for d in range(depth):  # SDXL stacks up to 10 blocks per attention
        h = _basic_transformer(p, f"{pre}.transformer_blocks.{d}", h, ctx, heads)
    if use_linear:
        h = _linear(h, p, f"{pre}.proj_out").reshape(B, H, W, C)
    else:
        h = h.reshape(B, H, W, C)
        h = _conv(h, p[f"{pre}.proj_out.weight"], p[f"{pre}.proj_out.bias"], pad=0)
    return h + r


def unet_forward(cfg: UNetConfig, p: Params, sample: jnp.ndarray,
                 t: jnp.ndarray, ctx: jnp.ndarray,
                 added_text: Optional[jnp.ndarray] = None,
                 added_time_ids: Optional[jnp.ndarray] = None,
                 ctrl_residuals: Optional[tuple] = None) -> jnp.ndarray:
    """sample [B, H, W, C_lat], t [B], ctx [B, S, C_txt] → eps/v pred.

    SDXL micro-conditioning (addition_embed_type "text_time"): added_text
    [B, 1280] (encoder-2 pooled projection) and added_time_ids [B, 6]
    (orig_h, orig_w, crop_top, crop_left, target_h, target_w) are fourier-
    embedded and added into the time embedding.

    ctrl_residuals: (down_residuals list, mid_residual) from
    controlnet_forward — added to the matching skip connections and the mid
    block output (diffusers ControlNetModel consumption contract)."""
    g = cfg.norm_num_groups
    temb = get_timestep_embedding(
        t, cfg.block_out_channels[0], cfg.flip_sin_to_cos, cfg.freq_shift
    ).astype(sample.dtype)
    temb = _linear(temb, p, "time_embedding.linear_1")
    temb = _linear(jax.nn.silu(temb), p, "time_embedding.linear_2")
    if cfg.addition_embed_type == "text_time":
        B = sample.shape[0]
        tids = get_timestep_embedding(
            added_time_ids.reshape(-1), cfg.addition_time_embed_dim,
            cfg.flip_sin_to_cos, cfg.freq_shift,
        ).reshape(B, -1).astype(sample.dtype)  # [B, 6*addition_dim]
        add = jnp.concatenate([added_text.astype(sample.dtype), tids], axis=-1)
        aug = _linear(add, p, "add_embedding.linear_1")
        aug = _linear(jax.nn.silu(aug), p, "add_embedding.linear_2")
        temb = temb + aug

    h = _conv(sample, p["conv_in.weight"], p["conv_in.bias"])
    skips = [h]
    for bi, btype in enumerate(cfg.down_block_types):
        pre = f"down_blocks.{bi}"
        heads = cfg.heads_for(bi)
        for li in range(cfg.layers_per_block):
            h = _resnet(p, f"{pre}.resnets.{li}", h, temb, g)
            if btype == "CrossAttnDownBlock2D":
                h = _spatial_transformer(
                    p, f"{pre}.attentions.{li}", h, ctx, heads, g,
                    cfg.tx_depth_for(bi),
                )
            skips.append(h)
        if f"{pre}.downsamplers.0.conv.weight" in p:
            h = _conv(h, p[f"{pre}.downsamplers.0.conv.weight"],
                      p[f"{pre}.downsamplers.0.conv.bias"], stride=2)
            skips.append(h)

    if ctrl_residuals is not None:
        down_res, mid_res = ctrl_residuals
        skips = [s + r for s, r in zip(skips, down_res)]

    last = len(cfg.block_out_channels) - 1
    h = _resnet(p, "mid_block.resnets.0", h, temb, g)
    h = _spatial_transformer(
        p, "mid_block.attentions.0", h, ctx,
        cfg.heads_for(last), g, cfg.tx_depth_for(last),
    )
    h = _resnet(p, "mid_block.resnets.1", h, temb, g)
    if ctrl_residuals is not None:
        h = h + mid_res

    for bi, btype in enumerate(cfg.up_block_types):
        pre = f"up_blocks.{bi}"
        heads = cfg.heads_for(last - bi)
        for li in range(cfg.layers_per_block + 1):
            skip = skips.pop()
            h = jnp.concatenate([h, skip], axis=-1)
            h = _resnet(p, f"{pre}.resnets.{li}", h, temb, g)
            if btype == "CrossAttnUpBlock2D":
                h = _spatial_transformer(
                    p, f"{pre}.attentions.{li}", h, ctx, heads, g,
                    cfg.tx_depth_for(last - bi),
                )
        if f"{pre}.upsamplers.0.conv.weight" in p:
            B, H, W, C = h.shape
            h = jax.image.resize(h, (B, H * 2, W * 2, C), "nearest")
            h = _conv(h, p[f"{pre}.upsamplers.0.conv.weight"],
                      p[f"{pre}.upsamplers.0.conv.bias"])

    h = _group_norm(h, p["conv_norm_out.weight"], p["conv_norm_out.bias"], g)
    return _conv(jax.nn.silu(h), p["conv_out.weight"], p["conv_out.bias"])


def controlnet_forward(cfg: UNetConfig, p: Params, sample: jnp.ndarray,
                       t: jnp.ndarray, ctx: jnp.ndarray, cond: jnp.ndarray,
                       scale: float = 1.0) -> tuple:
    """diffusers ControlNetModel: a copy of the UNet encoder whose skip
    outputs pass through zero-initialized 1x1 convs, plus a small conv
    tower embedding the PIXEL-SPACE condition image into latent resolution.

    sample [B, h, w, C_lat]; cond [B, 8·h?, 8·w?, 3] in [0, 1] (the control
    image at pixel resolution); returns (down_residuals, mid_residual) for
    unet_forward's ctrl_residuals."""
    g = cfg.norm_num_groups
    temb = get_timestep_embedding(
        t, cfg.block_out_channels[0], cfg.flip_sin_to_cos, cfg.freq_shift
    ).astype(sample.dtype)
    temb = _linear(temb, p, "time_embedding.linear_1")
    temb = _linear(jax.nn.silu(temb), p, "time_embedding.linear_2")

    # Condition embedding tower: stride-2 conv pairs down to latent res,
    # final conv zero-initialized at training start.
    c = _conv(cond.astype(sample.dtype),
              p["controlnet_cond_embedding.conv_in.weight"],
              p["controlnet_cond_embedding.conv_in.bias"])
    c = jax.nn.silu(c)
    nblk = 0
    while f"controlnet_cond_embedding.blocks.{nblk}.weight" in p:
        nblk += 1
    for i in range(nblk):
        stride = 2 if i % 2 == 1 else 1  # diffusers alternates ch-up, down-2
        c = _conv(c, p[f"controlnet_cond_embedding.blocks.{i}.weight"],
                  p[f"controlnet_cond_embedding.blocks.{i}.bias"], stride=stride)
        c = jax.nn.silu(c)
    c = _conv(c, p["controlnet_cond_embedding.conv_out.weight"],
              p["controlnet_cond_embedding.conv_out.bias"])

    h = _conv(sample, p["conv_in.weight"], p["conv_in.bias"]) + c
    skips = [h]
    for bi, btype in enumerate(cfg.down_block_types):
        pre = f"down_blocks.{bi}"
        heads = cfg.heads_for(bi)
        for li in range(cfg.layers_per_block):
            h = _resnet(p, f"{pre}.resnets.{li}", h, temb, g)
            if btype == "CrossAttnDownBlock2D":
                h = _spatial_transformer(
                    p, f"{pre}.attentions.{li}", h, ctx, heads, g,
                    cfg.tx_depth_for(bi),
                )
            skips.append(h)
        if f"{pre}.downsamplers.0.conv.weight" in p:
            h = _conv(h, p[f"{pre}.downsamplers.0.conv.weight"],
                      p[f"{pre}.downsamplers.0.conv.bias"], stride=2)
            skips.append(h)

    last = len(cfg.block_out_channels) - 1
    h = _resnet(p, "mid_block.resnets.0", h, temb, g)
    h = _spatial_transformer(
        p, "mid_block.attentions.0", h, ctx,
        cfg.heads_for(last), g, cfg.tx_depth_for(last),
    )
    h = _resnet(p, "mid_block.resnets.1", h, temb, g)

    down = [
        scale * _conv(s, p[f"controlnet_down_blocks.{i}.weight"],
                      p[f"controlnet_down_blocks.{i}.bias"], pad=0)
        for i, s in enumerate(skips)
    ]
    mid = scale * _conv(h, p["controlnet_mid_block.weight"],
                        p["controlnet_mid_block.bias"], pad=0)
    return down, mid


# --------------------------------------------------------------------------- #
# VAE
# --------------------------------------------------------------------------- #


def _vae_attn(p: Params, pre: str, x: jnp.ndarray, groups: int) -> jnp.ndarray:
    B, H, W, C = x.shape
    h = _group_norm(x, p[f"{pre}.group_norm.weight"], p[f"{pre}.group_norm.bias"], groups)
    h = h.reshape(B, H * W, C)
    q = _linear(h, p, f"{pre}.to_q")
    k = _linear(h, p, f"{pre}.to_k")
    v = _linear(h, p, f"{pre}.to_v")
    h = _attention(q, k, v, heads=1)
    h = _linear(h, p, f"{pre}.to_out.0").reshape(B, H, W, C)
    return x + h


def vae_decode(cfg: VAEConfig, p: Params, latents: jnp.ndarray) -> jnp.ndarray:
    """[B, h, w, C_lat] (already unscaled) → images [B, 8h, 8w, 3] in [0,1]."""
    g = cfg.norm_num_groups
    zero_t = jnp.zeros((latents.shape[0],), latents.dtype)
    h = latents
    if "post_quant_conv.weight" in p:  # Flux-class VAEs omit the quant convs
        h = _conv(h, p["post_quant_conv.weight"], p["post_quant_conv.bias"], pad=0)
    h = _conv(h, p["decoder.conv_in.weight"], p["decoder.conv_in.bias"])
    h = _resnet(p, "decoder.mid_block.resnets.0", h, zero_t, g)
    h = _vae_attn(p, "decoder.mid_block.attentions.0", h, g)
    h = _resnet(p, "decoder.mid_block.resnets.1", h, zero_t, g)
    n_blocks = len(cfg.block_out_channels)
    for bi in range(n_blocks):
        pre = f"decoder.up_blocks.{bi}"
        for li in range(cfg.layers_per_block + 1):
            h = _resnet(p, f"{pre}.resnets.{li}", h, zero_t, g)
        if f"{pre}.upsamplers.0.conv.weight" in p:
            B, H, W, C = h.shape
            h = jax.image.resize(h, (B, H * 2, W * 2, C), "nearest")
            h = _conv(h, p[f"{pre}.upsamplers.0.conv.weight"],
                      p[f"{pre}.upsamplers.0.conv.bias"])
    h = _group_norm(h, p["decoder.conv_norm_out.weight"],
                    p["decoder.conv_norm_out.bias"], g)
    img = _conv(jax.nn.silu(h), p["decoder.conv_out.weight"], p["decoder.conv_out.bias"])
    return jnp.clip(img.astype(jnp.float32) / 2.0 + 0.5, 0.0, 1.0)


def vae_encode(cfg: VAEConfig, p: Params, img: jnp.ndarray,
               key: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """images [B, H, W, 3] in [0,1] → scaled latents [B, H/8, W/8, C_lat].
    Deterministic (mode) unless a key is given."""
    g = cfg.norm_num_groups
    x = img.astype(jnp.float32) * 2.0 - 1.0
    zero_t = jnp.zeros((x.shape[0],), x.dtype)
    h = _conv(x, p["encoder.conv_in.weight"], p["encoder.conv_in.bias"])
    n_blocks = len(cfg.block_out_channels)
    for bi in range(n_blocks):
        pre = f"encoder.down_blocks.{bi}"
        for li in range(cfg.layers_per_block):
            h = _resnet(p, f"{pre}.resnets.{li}", h, zero_t, g)
        if f"{pre}.downsamplers.0.conv.weight" in p:
            # diffusers pads asymmetrically (0,1,0,1) for stride-2 convs
            h = jnp.pad(h, ((0, 0), (0, 1), (0, 1), (0, 0)))
            h = jax.lax.conv_general_dilated(
                h, p[f"{pre}.downsamplers.0.conv.weight"].astype(h.dtype),
                window_strides=(2, 2), padding=[(0, 0), (0, 0)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p[f"{pre}.downsamplers.0.conv.bias"].astype(h.dtype)
    h = _resnet(p, "encoder.mid_block.resnets.0", h, zero_t, g)
    h = _vae_attn(p, "encoder.mid_block.attentions.0", h, g)
    h = _resnet(p, "encoder.mid_block.resnets.1", h, zero_t, g)
    h = _group_norm(h, p["encoder.conv_norm_out.weight"],
                    p["encoder.conv_norm_out.bias"], g)
    moments = _conv(jax.nn.silu(h), p["encoder.conv_out.weight"],
                    p["encoder.conv_out.bias"])
    if "quant_conv.weight" in p:  # Flux-class VAEs omit the quant convs
        moments = _conv(moments, p["quant_conv.weight"], p["quant_conv.bias"], pad=0)
    mean, logvar = jnp.split(moments, 2, axis=-1)
    if key is not None:
        mean = mean + jnp.exp(0.5 * jnp.clip(logvar, -30, 20)) * jax.random.normal(
            key, mean.shape, mean.dtype
        )
    return mean * cfg.scaling_factor


# --------------------------------------------------------------------------- #
# Schedulers
# --------------------------------------------------------------------------- #


def alphas_cumprod(cfg: SDPipelineConfig) -> np.ndarray:
    betas = np.linspace(
        cfg.beta_start ** 0.5, cfg.beta_end ** 0.5, cfg.num_train_timesteps,
        dtype=np.float64,
    ) ** 2  # "scaled_linear"
    return np.cumprod(1.0 - betas).astype(np.float32)


def ddim_timesteps(cfg: SDPipelineConfig, steps: int) -> np.ndarray:
    ratio = cfg.num_train_timesteps // steps
    return (np.arange(steps) * ratio).round()[::-1].astype(np.int32)  # "leading"


def _pred_x0_eps(cfg: SDPipelineConfig, model_out, x, acp_t):
    """(x0, eps) from the model output under the configured prediction type."""
    sq_a, sq_1ma = jnp.sqrt(acp_t), jnp.sqrt(1.0 - acp_t)
    if cfg.prediction_type == "v_prediction":
        x0 = sq_a * x - sq_1ma * model_out
        eps = sq_a * model_out + sq_1ma * x
    else:
        x0 = (x - sq_1ma * model_out) / sq_a
        eps = model_out
    return x0, eps


def ddim_step(cfg: SDPipelineConfig, acp: jnp.ndarray, model_out: jnp.ndarray,
              t: jnp.ndarray, t_prev: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    acp_t = acp[t]
    acp_prev = jnp.where(t_prev >= 0, acp[jnp.maximum(t_prev, 0)], 1.0)
    x0, eps = _pred_x0_eps(cfg, model_out.astype(jnp.float32), x.astype(jnp.float32), acp_t)
    return (jnp.sqrt(acp_prev) * x0 + jnp.sqrt(1.0 - acp_prev) * eps).astype(x.dtype)


def euler_a_sigmas(cfg: SDPipelineConfig, steps: int) -> np.ndarray:
    acp = alphas_cumprod(cfg)
    sig = np.sqrt((1 - acp) / acp)
    ts = ddim_timesteps(cfg, steps).astype(np.float64)
    sigmas = np.interp(ts, np.arange(len(sig)), sig)
    return np.append(sigmas, 0.0).astype(np.float32)


def k_schedule(cfg: SDPipelineConfig, steps: int, karras: bool):
    """(sigmas [steps+1], timesteps [steps]) for the k-diffusion samplers.

    karras=True uses the Karras et al. (2022) rho-7 spacing over the
    model's trained sigma range (diffusers use_karras_sigmas; what the
    *_karras scheduler names select); timesteps come back from inverting
    the training sigma table so the model is queried at the right t."""
    acp = alphas_cumprod(cfg)
    sig = np.sqrt((1 - acp) / acp)
    if not karras:
        ts = ddim_timesteps(cfg, steps).astype(np.float64)
        sigmas = np.interp(ts, np.arange(len(sig)), sig)
        return (np.append(sigmas, 0.0).astype(np.float32),
                ts.astype(np.float32))
    rho = 7.0
    smin, smax = float(sig[0]), float(sig[-1])
    ramp = np.linspace(0.0, 1.0, steps)
    sigmas = (smax ** (1 / rho) + ramp * (smin ** (1 / rho) - smax ** (1 / rho))) ** rho
    # invert the (monotonic) training sigma table: sigma -> fractional t
    ts = np.interp(np.log(sigmas), np.log(sig), np.arange(len(sig)))
    return (np.append(sigmas, 0.0).astype(np.float32), ts.astype(np.float32))


def ancestral_sigmas(sigma, sigma_next):
    """(sigma_down, sigma_up) for an eta-1 ancestral step (k-diffusion
    get_ancestral_step)."""
    s2, sn2 = sigma ** 2, sigma_next ** 2
    sigma_up = jnp.sqrt(jnp.maximum(sn2 * (s2 - sn2) / jnp.maximum(s2, 1e-12), 0.0))
    sigma_down = jnp.sqrt(jnp.maximum(sn2 - sigma_up ** 2, 0.0))
    return sigma_down, sigma_up


def euler_a_step(model_out, x, sigma, sigma_next, noise):
    """k-diffusion Euler-ancestral over eps-prediction in sigma space."""
    mo = model_out.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    x0 = xf - sigma * mo
    sigma_down, sigma_up = ancestral_sigmas(sigma, sigma_next)
    d = (xf - x0) / jnp.maximum(sigma, 1e-12)
    xf = xf + d * (sigma_down - sigma) + noise * sigma_up
    return xf.astype(x.dtype)


def _denoised_sigma(cfg: SDPipelineConfig, model_out, x, sigma):
    """k-diffusion denoiser output D(x, σ) for the configured prediction
    type (eps: D = x − σ·ε; v: D = x/(σ²+1) − σ/√(σ²+1)·v)."""
    mo = model_out.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if cfg.prediction_type == "v_prediction":
        return xf / (sigma**2 + 1.0) - sigma / jnp.sqrt(sigma**2 + 1.0) * mo
    return xf - sigma * mo


def lms_coefficients(sigmas: np.ndarray, order: int = 4) -> np.ndarray:
    """Adams-Bashforth coefficients over the (static) sigma trajectory:
    ∫ over [σ_i, σ_{i+1}] of each Lagrange basis through the last `order`
    sigmas (k-diffusion sample_lms). Host-side, per compile."""
    from scipy.integrate import quad

    steps = len(sigmas) - 1
    co = np.zeros((steps, order), np.float64)
    for i in range(steps):
        cur = min(i + 1, order)
        for j in range(cur):
            def basis(tau, j=j, cur=cur, i=i):
                prod = 1.0
                for k in range(cur):
                    if k != j:
                        prod *= (tau - sigmas[i - k]) / (sigmas[i - j] - sigmas[i - k])
                return prod

            co[i, j] = quad(basis, sigmas[i], sigmas[i + 1], epsrel=1e-5)[0]
    return co.astype(np.float32)


# --------------------------------------------------------------------------- #
# Generation
# --------------------------------------------------------------------------- #


def generate(
    cfg: SDPipelineConfig,
    params: dict[str, Params],  # {"text": ..., "unet": ..., "vae": ...}
    cond_ids: jnp.ndarray,  # [B, 77]
    uncond_ids: jnp.ndarray,
    key: jnp.ndarray,
    steps: int = 20,
    guidance: float = 7.5,
    height: int = 512,
    width: int = 512,
    scheduler: str = "ddim",
    init_noise: Optional[jnp.ndarray] = None,  # [B, h/8, w/8, C] unit normal
    known_latent: Optional[jnp.ndarray] = None,  # scaled latents to keep
    known_mask: Optional[jnp.ndarray] = None,  # [B, h/8, w/8, 1]; 1 = repaint
    cond_ids2: Optional[jnp.ndarray] = None,  # SDXL: tokenizer_2 ids
    uncond_ids2: Optional[jnp.ndarray] = None,
    control_image: Optional[jnp.ndarray] = None,  # [B, H, W, 3] in [0,1]
    control_scale: float = 1.0,
    init_image: Optional[jnp.ndarray] = None,  # img2img source [B, H, W, 3]
    strength: float = 0.8,  # img2img: fraction of the schedule re-noised
) -> jnp.ndarray:
    """Full text→image pipeline; returns [B, H, W, 3] float32 in [0,1].
    jit-able: shapes depend only on (B, steps, H, W, scheduler).

    SDXL checkpoints (cfg.text2 set) condition on the CONCATENATED
    penultimate states of both encoders plus encoder 2's pooled projection
    and size/crop time-ids (StableDiffusionXLPipeline semantics).

    With known_latent/known_mask set, runs SD-style inpainting on a vanilla
    checkpoint: after every step the preserved region is replaced with the
    source latent re-noised to the current timestep (diffusers'
    StableDiffusionInpaintPipelineLegacy behavior)."""
    B = cond_ids.shape[0]
    added = None
    if cfg.is_xl:
        ids2_c = cond_ids if cond_ids2 is None else cond_ids2
        ids2_u = uncond_ids if uncond_ids2 is None else uncond_ids2
        pen1_c, _ = clip_hidden_states(cfg.text, params["text"], cond_ids)
        pen1_u, _ = clip_hidden_states(cfg.text, params["text"], uncond_ids)
        pen2_c, fin2_c = clip_hidden_states(cfg.text2, params["text2"], ids2_c)
        pen2_u, fin2_u = clip_hidden_states(cfg.text2, params["text2"], ids2_u)
        ctx = jnp.concatenate([
            jnp.concatenate([pen1_u, pen2_u], axis=-1),
            jnp.concatenate([pen1_c, pen2_c], axis=-1),
        ], axis=0)
        pooled = jnp.concatenate([
            clip_pooled_projection(cfg.text2, params["text2"], ids2_u, fin2_u),
            clip_pooled_projection(cfg.text2, params["text2"], ids2_c, fin2_c),
        ], axis=0)
        time_ids = jnp.broadcast_to(
            jnp.asarray([height, width, 0, 0, height, width], jnp.float32),
            (2 * B, 6),
        )
        added = (pooled, time_ids)
    else:
        ctx_c = clip_encode(cfg.text, params["text"], cond_ids)
        ctx_u = clip_encode(cfg.text, params["text"], uncond_ids)
        ctx = jnp.concatenate([ctx_u, ctx_c], axis=0)
    vs = cfg.vae.spatial_scale
    lat_h, lat_w = height // vs, width // vs
    acp = jnp.asarray(alphas_cumprod(cfg))
    key, nk = jax.random.split(key)
    lat_c = cfg.unet.in_channels
    x = init_noise if init_noise is not None else jax.random.normal(
        nk, (B, lat_h, lat_w, lat_c), jnp.float32
    )
    # img2img: encode the source, start `strength` of the way up the noise
    # schedule and run only the remaining steps (diffusers
    # StableDiffusionImg2ImgPipeline semantics; reference backend.py:198).
    i0 = 0
    init_lat = None
    if init_image is not None:
        i0 = steps - max(1, min(steps, int(round(steps * strength))))
        init_lat = vae_encode(cfg.vae, params["vae"], init_image)

    use_ctrl = control_image is not None and "controlnet" in params
    ctrl_cond2 = (jnp.concatenate([control_image, control_image], axis=0)
                  if use_ctrl else None)

    def cfg_eps(x_in, t):
        both = jnp.concatenate([x_in, x_in], axis=0)
        tt = jnp.full((2 * B,), t, jnp.float32)
        ctrl = None
        if use_ctrl:
            ctrl = controlnet_forward(
                cfg.unet, params["controlnet"], both, tt, ctx, ctrl_cond2,
                scale=control_scale,
            )
        out = unet_forward(
            cfg.unet, params["unet"], both, tt, ctx,
            added_text=added[0] if added else None,
            added_time_ids=added[1] if added else None,
            ctrl_residuals=ctrl,
        )
        eps_u, eps_c = jnp.split(out, 2, axis=0)
        return eps_u + guidance * (eps_c - eps_u)

    inpainting = known_latent is not None and known_mask is not None
    if inpainting and scheduler != "ddim":
        # The preserved-region replay (blend) is DDIM-space math; silently
        # ignoring the mask under another sampler would "inpaint" nothing.
        raise ValueError("inpainting requires the ddim scheduler")

    def blend(xc, t_prev, k):
        """Replace the preserved region with the source re-noised to t_prev."""
        if not inpainting:
            return xc
        acp_prev = jnp.where(t_prev >= 0, acp[jnp.maximum(t_prev, 0)], 1.0)
        noise = jax.random.normal(k, xc.shape, jnp.float32)
        noised = jnp.sqrt(acp_prev) * known_latent + jnp.sqrt(1.0 - acp_prev) * noise
        return known_mask * xc + (1.0 - known_mask) * noised.astype(xc.dtype)

    scheduler, karras = resolve_scheduler(scheduler)
    if scheduler in K_SCHEDULERS:
        sigmas_np, ts_np = k_schedule(cfg, steps, karras)
        sigmas = jnp.asarray(sigmas_np)
        ts = jnp.asarray(ts_np)
        if init_lat is not None:
            x = init_lat + x * sigmas[i0]
        else:
            x = x * sigmas[0]

        def denoised_at(xc, i):
            sig = sigmas[i]
            x_in = xc.astype(jnp.float32) / jnp.sqrt(sig**2 + 1.0)
            out = cfg_eps(x_in, ts[i])
            return _denoised_sigma(cfg, out, xc, sig)

        # For samplers that query the model at off-grid sigmas (dpm_2* mid-
        # points, dpmpp_sde half-steps): invert the training sigma table to
        # a fractional timestep on device.
        sig_train = jnp.sqrt((1.0 - acp) / acp)
        log_sig_train = jnp.log(sig_train)
        t_grid = jnp.arange(sig_train.shape[0], dtype=jnp.float32)

        def denoised_at_sigma(xc, sig):
            t = jnp.interp(jnp.log(jnp.maximum(sig, 1e-10)),
                           log_sig_train, t_grid)
            x_in = xc.astype(jnp.float32) / jnp.sqrt(sig**2 + 1.0)
            out = cfg_eps(x_in, t)
            return _denoised_sigma(cfg, out, xc, sig)

        ancestral = ancestral_sigmas

        if scheduler == "euler":
            # k-diffusion sample_euler (churn 0): one deterministic slope
            # step per sigma interval.
            def step(xc, i):
                sig, sig_n = sigmas[i], sigmas[i + 1]
                den = denoised_at(xc, i)
                d = (xc.astype(jnp.float32) - den) / sig
                return (xc.astype(jnp.float32) + d * (sig_n - sig)).astype(xc.dtype), None

            x, _ = jax.lax.scan(step, x, jnp.arange(i0, steps))
        elif scheduler in ("dpm_2", "dpm_2_a"):
            # k-diffusion sample_dpm_2(_ancestral): midpoint (log-sigma
            # lerp 0.5) second-order correction; the ancestral variant
            # steps to sigma_down and re-noises by sigma_up.
            anc = scheduler == "dpm_2_a"

            def step(carry, i):
                xc, k = carry
                k, nk2 = jax.random.split(k)
                xcf = xc.astype(jnp.float32)
                sig, sig_n = sigmas[i], sigmas[i + 1]
                den = denoised_at(xc, i)
                d = (xcf - den) / sig
                x_eul = xcf + d * (sig_n - sig)  # final-step fallback
                tgt, su = (ancestral(sig, sig_n) if anc
                           else (sig_n, jnp.float32(0.0)))
                sig_mid = jnp.exp(0.5 * (
                    jnp.log(sig) + jnp.log(jnp.maximum(tgt, 1e-10))))
                x_2 = xcf + d * (sig_mid - sig)
                den2 = denoised_at_sigma(x_2.astype(xc.dtype), sig_mid)
                d2 = (x_2 - den2) / sig_mid
                xn = xcf + d2 * (tgt - sig)
                if anc:
                    xn = xn + jax.random.normal(nk2, xc.shape, jnp.float32) * su
                xn = jnp.where(sig_n == 0.0, x_eul, xn)
                return (xn.astype(xc.dtype), k), None

            (x, _), _ = jax.lax.scan(step, (x, key), jnp.arange(i0, steps))
        elif scheduler == "dpmpp_sde":
            # k-diffusion sample_dpmpp_sde (r=1/2, eta=1): an SDE half-step
            # to the ancestral midpoint, then a full step from the midpoint
            # estimate (fac = 1/(2r) = 1 → the second eval carries it).
            def step(carry, i):
                xc, k = carry
                k, k1, k2 = jax.random.split(k, 3)
                xcf = xc.astype(jnp.float32)
                sig, sig_n = sigmas[i], sigmas[i + 1]
                den = denoised_at(xc, i)
                t_c = -jnp.log(sig)
                s_mid = jnp.exp(-(t_c + 0.5 * (
                    -jnp.log(jnp.maximum(sig_n, 1e-10)) - t_c)))
                sd1, su1 = ancestral(sig, s_mid)
                s_ = -jnp.log(jnp.maximum(sd1, 1e-10))
                x_2 = (sd1 / sig) * xcf - jnp.expm1(t_c - s_) * den
                x_2 = x_2 + jax.random.normal(k1, xc.shape, jnp.float32) * su1
                den2 = denoised_at_sigma(x_2.astype(xc.dtype), s_mid)
                sd2, su2 = ancestral(sig, sig_n)
                t_n_ = -jnp.log(jnp.maximum(sd2, 1e-10))
                xn = (sd2 / sig) * xcf - jnp.expm1(t_c - t_n_) * den2
                xn = xn + jax.random.normal(k2, xc.shape, jnp.float32) * su2
                # k-diffusion falls back to a plain step when σ_next == 0;
                # x − σ·d = denoised exactly there.
                xn = jnp.where(sig_n == 0.0, den, xn)
                return (xn.astype(xc.dtype), k), None

            (x, _), _ = jax.lax.scan(step, (x, key), jnp.arange(i0, steps))
        elif scheduler == "dpmpp_2m_sde":
            # k-diffusion sample_dpmpp_2m_sde (eta=1, midpoint solver):
            # exponential-integrator SDE multistep over λ = -log σ.
            def step(carry, i):
                xc, old_den, k = carry
                k, nk2 = jax.random.split(k)
                xcf = xc.astype(jnp.float32)
                sig, sig_n = sigmas[i], sigmas[i + 1]
                den = denoised_at(xc, i)
                t_c = -jnp.log(sig)
                t_n = -jnp.log(jnp.maximum(sig_n, 1e-10))
                h = t_n - t_c  # eta_h = h (eta = 1)
                xn = (sig_n / sig) * jnp.exp(-h) * xcf \
                    - jnp.expm1(-2.0 * h) * den
                sig_prev = sigmas[jnp.maximum(i - 1, 0)]
                h_last = t_c - (-jnp.log(sig_prev))
                r = h_last / h
                second = -0.5 * jnp.expm1(-2.0 * h) * (1.0 / r) * (den - old_den)
                xn = xn + jnp.where(i == i0, 0.0, second)
                noise = jax.random.normal(nk2, xc.shape, jnp.float32)
                xn = xn + noise * sig_n * jnp.sqrt(
                    jnp.maximum(-jnp.expm1(-2.0 * h), 0.0))
                # Final σ = 0 step: the multistep correction's 1/r blows up
                # (h → ∞); the exact limit of the update is the denoised
                # sample itself.
                xn = jnp.where(sig_n == 0.0, den, xn)
                return (xn.astype(xc.dtype), den, k), None

            (x, _, _), _ = jax.lax.scan(
                step, (x, jnp.zeros_like(x), key), jnp.arange(i0, steps))
        elif scheduler == "euler_a":

            def step(carry, i):
                xc, k = carry
                k, nk2 = jax.random.split(k)
                sig, sig_n = sigmas[i], sigmas[i + 1]
                x_in = xc / jnp.sqrt(sig ** 2 + 1.0)
                eps = cfg_eps(x_in, ts[i])
                noise = jax.random.normal(nk2, xc.shape, jnp.float32)
                return (euler_a_step(eps, xc, sig, sig_n, noise), k), None

            (x, _), _ = jax.lax.scan(step, (x, key), jnp.arange(i0, steps))
        elif scheduler == "dpmpp_2m":
            # DPM-Solver++(2M): deterministic multistep over λ = −log σ
            # (k-diffusion sample_dpmpp_2m; first and last steps are 1st
            # order).
            def step(carry, i):
                xc, old_d = carry
                den = denoised_at(xc, i)
                sig, sig_n = sigmas[i], sigmas[i + 1]
                t_c, t_n = -jnp.log(sig), -jnp.log(jnp.maximum(sig_n, 1e-10))
                h = t_n - t_c
                sig_prev = sigmas[jnp.maximum(i - 1, 0)]
                h_last = t_c - (-jnp.log(sig_prev))
                r = h_last / h
                den_d = (1 + 1 / (2 * r)) * den - (1 / (2 * r)) * old_d
                use_first = (i == i0) | (sig_n == 0.0)
                den_use = jnp.where(use_first, den, den_d)
                xn = (sig_n / sig) * xc.astype(jnp.float32) \
                    - jnp.expm1(-h) * den_use
                return (xn.astype(xc.dtype), den), None

            (x, _), _ = jax.lax.scan(step, (x, jnp.zeros_like(x)),
                                     jnp.arange(i0, steps))
        elif scheduler == "heun":
            # Heun's 2nd order (k-diffusion sample_heun, churn 0): trapezoid
            # correction with a second model eval; plain Euler when the next
            # sigma is 0 (the correction's slope is undefined there).
            def step(carry, i):
                xc, _ = carry
                sig, sig_n = sigmas[i], sigmas[i + 1]
                den = denoised_at(xc, i)
                d = (xc.astype(jnp.float32) - den) / sig
                dt = sig_n - sig
                x_eul = xc.astype(jnp.float32) + d * dt
                den2 = denoised_at(x_eul.astype(xc.dtype),
                                   jnp.minimum(i + 1, steps - 1))
                d2 = (x_eul - den2) / jnp.maximum(sig_n, 1e-10)
                x_heun = xc.astype(jnp.float32) + (d + d2) / 2 * dt
                xn = jnp.where(sig_n == 0.0, x_eul, x_heun)
                return (xn.astype(xc.dtype), 0.0), None

            (x, _), _ = jax.lax.scan(step, (x, 0.0), jnp.arange(i0, steps))
        else:  # lms
            # coefficients over the REMAINING trajectory: starting mid-
            # schedule (img2img) must not weight history that never ran
            order = min(4, steps - i0)
            co = jnp.asarray(lms_coefficients(sigmas_np[i0:], order))

            def step(carry, i):
                xc, hist = carry
                den = denoised_at(xc, i)
                d = (xc.astype(jnp.float32) - den) / sigmas[i]
                hist = jnp.concatenate([d[None], hist[:-1]], axis=0)
                xn = xc.astype(jnp.float32) + jnp.einsum(
                    "j,j...->...", co[i - i0], hist
                )
                return (xn.astype(xc.dtype), hist), None

            hist0 = jnp.zeros((order,) + x.shape, jnp.float32)
            (x, _), _ = jax.lax.scan(step, (x, hist0), jnp.arange(i0, steps))
    else:
        ts = jnp.asarray(ddim_timesteps(cfg, steps))
        ratio = cfg.num_train_timesteps // steps
        if init_lat is not None:
            acp0 = acp[ts[i0]]
            x = jnp.sqrt(acp0) * init_lat + jnp.sqrt(1.0 - acp0) * x

        if scheduler == "pndm":
            # PLMS (Liu et al. 2022): Adams-Bashforth eps history (orders
            # 1→4 warmup) through the pseudo-linear transfer function
            # (diffusers PNDMScheduler._get_prev_sample). Deliberate
            # difference from diffusers' skip_prk warmup: the first
            # timestep runs ONE order-1 step instead of diffusers'
            # duplicated-timestep two-eval average — steps model evals
            # total, converging to the same trajectory as history fills.
            def transfer(xcf, eps, t, t_prev):
                a_t = acp[t]
                a_p = jnp.where(t_prev >= 0, acp[jnp.maximum(t_prev, 0)], 1.0)
                coeff = jnp.sqrt(a_p / a_t)
                denom = a_t * jnp.sqrt(1.0 - a_p) + jnp.sqrt(
                    a_t * (1.0 - a_t) * a_p)
                return coeff * xcf - (a_p - a_t) * eps / denom

            def step(carry, idx):
                xc, e1, e2, e3, cnt = carry  # e1 newest
                t = ts[idx]
                eps = cfg_eps(xc, t.astype(jnp.float32)).astype(jnp.float32)
                if cfg.prediction_type == "v_prediction":
                    # diffusers PNDMScheduler converts v → eps before the
                    # transfer function: eps = √ᾱ·v + √(1−ᾱ)·x
                    a_t = acp[t]
                    eps = (jnp.sqrt(a_t) * eps
                           + jnp.sqrt(1.0 - a_t) * xc.astype(jnp.float32))
                ep = jnp.where(
                    cnt == 0, eps, jnp.where(
                        cnt == 1, (3.0 * eps - e1) / 2.0, jnp.where(
                            cnt == 2, (23.0 * eps - 16.0 * e1 + 5.0 * e2) / 12.0,
                            (55.0 * eps - 59.0 * e1 + 37.0 * e2 - 9.0 * e3) / 24.0,
                        )))
                xn = transfer(xc.astype(jnp.float32), ep, t, t - ratio)
                return (xn.astype(xc.dtype), eps, e1, e2, cnt + 1), None

            z = jnp.zeros_like(x)
            (x, _, _, _, _), _ = jax.lax.scan(
                step, (x, z, z, z, jnp.int32(0)), jnp.arange(i0, steps))
        elif scheduler == "unipc":
            # UniPC (Zhao et al. 2023), bh2 variant: data-prediction
            # multistep over λ = log(α/σ) with a p=2 predictor and a
            # single-order corrector applied to the previous step once this
            # step's model output is known (the predictor-corrector
            # framework of diffusers UniPCMultistepScheduler, order 2).
            alphas = jnp.sqrt(acp)
            sigmas_t = jnp.sqrt(1.0 - acp)

            def at(t):
                a = jnp.where(t >= 0, alphas[jnp.maximum(t, 0)], 1.0)
                s = jnp.where(t >= 0, sigmas_t[jnp.maximum(t, 0)], 0.0)
                lam = jnp.log(a) - jnp.log(jnp.maximum(s, 1e-10))
                return a, jnp.maximum(s, 1e-10), lam

            def x0_of(xc, t):
                eps = cfg_eps(xc, t.astype(jnp.float32)).astype(jnp.float32)
                a_t, s_t, _ = at(t)
                if cfg.prediction_type == "v_prediction":
                    return a_t * xc.astype(jnp.float32) - s_t * eps
                return (xc.astype(jnp.float32) - s_t * eps) / a_t

            def step(carry, idx):
                xc, x_prev, m_prev, t_prev_step, cnt = carry
                t = ts[idx]
                a_t, s_t, lam_t = at(t)
                m_t = x0_of(xc, t)
                # UniC: correct THIS sample using the fresh model output
                # (rhos_c = 1/2, B_h = h_phi_1 for bh2).
                _, s_p, lam_p = at(t_prev_step)
                h_c = lam_t - lam_p
                phi_c = jnp.expm1(-h_c)
                x_corr = (s_t / s_p) * x_prev.astype(jnp.float32) \
                    - a_t * phi_c * m_prev \
                    - a_t * phi_c * 0.5 * (m_t - m_prev)
                xcf = jnp.where(cnt > 0, x_corr, xc.astype(jnp.float32))
                # UniP to the next timestep: p=1 on the first step, p=2 after.
                t_n = t - ratio
                a_n, s_n, lam_n = at(t_n)
                h = lam_n - lam_t
                phi = jnp.expm1(-h)
                x1 = (s_n / s_t) * xcf - a_n * phi * m_t
                r0 = (lam_p - lam_t) / h
                d1 = (m_prev - m_t) / jnp.where(cnt > 0, r0, 1.0)
                x2 = x1 - a_n * phi * 0.5 * d1
                # lower_order_final (diffusers UniPCMultistepScheduler): on
                # the LAST step t_n < 0 clamps sigma to 1e-10, so h ≈ 20+
                # while r0 = (lam_p - lam_t)/h is tiny — the D1 term then
                # amplifies m_prev - m_t ~25x and corrupts the output
                # latent. Order drops to 1 whenever the target time leaves
                # the schedule.
                xn = jnp.where((cnt > 0) & (t_n >= 0), x2, x1)
                return (xn.astype(xc.dtype), xcf.astype(xc.dtype), m_t, t,
                        cnt + 1), None

            (x, _, _, _, _), _ = jax.lax.scan(
                step, (x, x, jnp.zeros_like(x), ts[i0], jnp.int32(0)),
                jnp.arange(i0, steps))
        else:  # ddim

            def step(carry, i):
                xc, k = carry
                k, bk = jax.random.split(k)
                t = ts[i]
                eps = cfg_eps(xc, t.astype(jnp.float32))
                xn = ddim_step(cfg, acp, eps, t, t - ratio, xc)
                return (blend(xn, t - ratio, bk), k), None

            (x, _), _ = jax.lax.scan(step, (x, key), jnp.arange(i0, steps))

    return vae_decode(cfg.vae, params["vae"], x / cfg.vae.scaling_factor)


# --------------------------------------------------------------------------- #
# Checkpoint loading (diffusers layout)
# --------------------------------------------------------------------------- #


def is_diffusers_dir(path: str) -> bool:
    return os.path.isfile(os.path.join(path, "model_index.json"))


def _load_safetensors_dir(subdir: str) -> dict[str, np.ndarray]:
    from safetensors import safe_open

    out: dict[str, np.ndarray] = {}
    files = sorted(
        f for f in os.listdir(subdir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors under {subdir}")
    for fname in files:
        with safe_open(os.path.join(subdir, fname), framework="numpy") as f:
            for name in f.keys():
                out[name] = f.get_tensor(name)
    return out


def _prep(tensors: dict[str, np.ndarray], dtype) -> Params:
    """torch layouts → ours: convs OIHW→HWIO, 2D linears [out,in]→[in,out]."""
    out: Params = {}
    lookup_tables = ("token_embedding", "position_embedding")
    for name, arr in tensors.items():
        if arr.ndim == 4:
            arr = arr.transpose(2, 3, 1, 0)
        elif (arr.ndim == 2 and name.endswith(".weight")
              and not any(t in name for t in lookup_tables)):
            arr = arr.T
        out[name] = jnp.asarray(np.ascontiguousarray(arr), dtype)
    return out


def _cfg_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_diffusion_lora(path: str, params: dict[str, Params],
                        multiplier: float = 1.0) -> int:
    """Merge a kohya-format LoRA safetensors file (the Civitai SD-LoRA
    ecosystem format: `lora_unet_*` / `lora_te_*` layers with
    `lora_down.weight` / `lora_up.weight` / `alpha`) into an already-loaded
    pipeline's params IN PLACE, scaled by `multiplier`. Returns the number
    of base tensors patched.

    Reference: the diffusers backend's load_lora_weights walks the module
    tree merging up@down*alpha/rank*multiplier into each target
    (/root/reference/backend/python/diffusers/backend.py:456-533); here the
    flat name→array dicts make the walk a direct name lookup. SDXL LoRAs
    use lora_te1_/lora_te2_ for the two encoders."""
    from safetensors import safe_open

    tensors: dict[str, np.ndarray] = {}
    with safe_open(path, framework="numpy") as f:
        for name in f.keys():
            tensors[name] = f.get_tensor(name)

    # group "lora_unet_..._to_q.lora_down.weight" by the layer part;
    # Civitai files sometimes bundle extra top-level tensors (textual
    # inversions etc.) — skip anything that isn't layer.elem shaped
    groups: dict[str, dict[str, np.ndarray]] = {}
    for name, arr in tensors.items():
        if "." not in name:
            log.warning("lora: ignoring non-LoRA tensor %r", name)
            continue
        layer, elem = name.split(".", 1)
        groups.setdefault(layer, {})[elem] = arr

    # kohya flattens module paths with "_": undo it by name lookup against
    # the loaded params (keys are the published dotted names).
    lookups: dict[str, dict[str, str]] = {}

    def lookup_for(part: str) -> dict[str, str]:
        if part not in lookups:
            lookups[part] = {
                k[: -len(".weight")].replace(".", "_"): k
                for k in params.get(part, {}) if k.endswith(".weight")
            }
        return lookups[part]

    prefixes = (
        ("lora_unet_", "unet"), ("lora_te1_", "text"),
        ("lora_te2_", "text2"), ("lora_te_", "text"),
    )
    merged = 0
    for layer, elems in groups.items():
        target = None
        for pref, part in prefixes:
            if layer.startswith(pref):
                target, rest = part, layer[len(pref):]
                break
        if target is None or target not in params:
            continue
        key = lookup_for(target).get(rest)
        down = elems.get("lora_down.weight")
        up = elems.get("lora_up.weight")
        if key is None or down is None or up is None:
            if key is None:
                log.warning("lora: no target for %s (skipped)", layer)
            continue
        rank = down.shape[0]
        alpha = float(elems["alpha"]) if "alpha" in elems else float(rank)
        scale = multiplier * alpha / rank
        base = params[target][key]
        if down.ndim == 4:  # conv: up [O,r,1,1] @ down [r,I,kh,kw]
            delta = np.einsum(
                "or,rikl->oikl", up.reshape(up.shape[0], rank),
                down.astype(np.float32),
            ) * scale
            delta = delta.transpose(2, 3, 1, 0)  # OIHW → HWIO (as _prep)
        else:  # linear: [out,r] @ [r,in] → [out,in]; ours is [in,out]
            delta = (up.astype(np.float32) @ down.astype(np.float32)).T * scale
        if delta.shape != base.shape:
            log.warning("lora: %s shape %s != base %s (skipped)",
                        layer, delta.shape, base.shape)
            continue
        params[target][key] = (
            base.astype(jnp.float32) + jnp.asarray(delta)
        ).astype(base.dtype)
        merged += 1
    return merged


def load_pipeline(ckpt_dir: str, dtype=jnp.float32):
    """(SDPipelineConfig, params, tokenizer) from a diffusers checkpoint dir.

    Matches the reference's dynamic pipeline load
    (backend/python/diffusers/backend.py) for the SD-1.5 class; the tokenizer
    is the checkpoint's own CLIPTokenizer(Fast) via transformers.
    """
    tc = _cfg_json(os.path.join(ckpt_dir, "text_encoder", "config.json"))
    uc = _cfg_json(os.path.join(ckpt_dir, "unet", "config.json"))
    vc = _cfg_json(os.path.join(ckpt_dir, "vae", "config.json"))
    sched_path = os.path.join(ckpt_dir, "scheduler", "scheduler_config.json")
    sc = _cfg_json(sched_path) if os.path.isfile(sched_path) else {}

    cfg = SDPipelineConfig(
        text=CLIPTextConfig(
            vocab_size=tc.get("vocab_size", 49408),
            hidden_size=tc.get("hidden_size", 768),
            intermediate_size=tc.get("intermediate_size", 3072),
            num_hidden_layers=tc.get("num_hidden_layers", 12),
            num_attention_heads=tc.get("num_attention_heads", 12),
            max_position_embeddings=tc.get("max_position_embeddings", 77),
            hidden_act=tc.get("hidden_act", "quick_gelu"),
        ),
        unet=UNetConfig(
            in_channels=uc.get("in_channels", 4),
            out_channels=uc.get("out_channels", 4),
            sample_size=uc.get("sample_size", 64),
            block_out_channels=tuple(uc.get("block_out_channels", (320, 640, 1280, 1280))),
            down_block_types=tuple(uc.get("down_block_types", ())),
            up_block_types=tuple(uc.get("up_block_types", ())),
            layers_per_block=uc.get("layers_per_block", 2),
            attention_head_dim=uc.get("attention_head_dim", 8),
            cross_attention_dim=uc.get("cross_attention_dim", 768),
            norm_num_groups=uc.get("norm_num_groups", 32),
            flip_sin_to_cos=uc.get("flip_sin_to_cos", True),
            freq_shift=uc.get("freq_shift", 0),
            transformer_layers_per_block=uc.get("transformer_layers_per_block", 1),
            addition_embed_type=uc.get("addition_embed_type") or "",
            addition_time_embed_dim=uc.get("addition_time_embed_dim", 256),
            projection_class_embeddings_input_dim=uc.get(
                "projection_class_embeddings_input_dim", 0
            ),
        ),
        vae=VAEConfig(
            in_channels=vc.get("in_channels", 3),
            out_channels=vc.get("out_channels", 3),
            latent_channels=vc.get("latent_channels", 4),
            block_out_channels=tuple(vc.get("block_out_channels", (128, 256, 512, 512))),
            layers_per_block=vc.get("layers_per_block", 2),
            norm_num_groups=vc.get("norm_num_groups", 32),
            scaling_factor=vc.get("scaling_factor", 0.18215),
        ),
        num_train_timesteps=sc.get("num_train_timesteps", 1000),
        beta_start=sc.get("beta_start", 0.00085),
        beta_end=sc.get("beta_end", 0.012),
        prediction_type=sc.get("prediction_type", "epsilon"),
    )
    params = {
        "text": _prep(_load_safetensors_dir(os.path.join(ckpt_dir, "text_encoder")), dtype),
        "unet": _prep(_load_safetensors_dir(os.path.join(ckpt_dir, "unet")), dtype),
        "vae": _prep(_load_safetensors_dir(os.path.join(ckpt_dir, "vae")), dtype),
    }
    from transformers import AutoTokenizer, CLIPTokenizer

    def load_tok(sub: str):
        tok_dir = os.path.join(ckpt_dir, sub)
        try:
            return AutoTokenizer.from_pretrained(tok_dir, local_files_only=True)
        except Exception:  # noqa: BLE001 — vocab.json/merges.txt direct load
            return CLIPTokenizer.from_pretrained(tok_dir, local_files_only=True)

    tokenizer = load_tok("tokenizer")

    # ControlNet: a `controlnet/` subdir in the checkpoint (the diffusers
    # StableDiffusionControlNetPipeline save layout). Its encoder copies the
    # UNet's geometry, so cfg.unet describes both.
    ctrl_dir = os.path.join(ckpt_dir, "controlnet")
    if os.path.isdir(ctrl_dir):
        params["controlnet"] = _prep(_load_safetensors_dir(ctrl_dir), dtype)

    # SDXL layout: a second (OpenCLIP-bigG-class) text encoder + tokenizer.
    te2 = os.path.join(ckpt_dir, "text_encoder_2")
    if os.path.isdir(te2):
        t2 = _cfg_json(os.path.join(te2, "config.json"))
        cfg.text2 = CLIPTextConfig(
            vocab_size=t2.get("vocab_size", 49408),
            hidden_size=t2.get("hidden_size", 1280),
            intermediate_size=t2.get("intermediate_size", 5120),
            num_hidden_layers=t2.get("num_hidden_layers", 32),
            num_attention_heads=t2.get("num_attention_heads", 20),
            max_position_embeddings=t2.get("max_position_embeddings", 77),
            hidden_act=t2.get("hidden_act", "gelu"),
            projection_dim=t2.get("projection_dim", 1280),
            eos_token_id=t2.get("eos_token_id", 49407),
        )
        params["text2"] = _prep(_load_safetensors_dir(te2), dtype)
        tok2_dir = os.path.join(ckpt_dir, "tokenizer_2")
        tok2 = load_tok("tokenizer_2") if os.path.isdir(tok2_dir) else tokenizer
        return cfg, params, (tokenizer, tok2)
    return cfg, params, tokenizer
