"""Qwen2-VL: native-resolution vision tower + m-rope multimodal serving.

Reference: the vLLM backend serves Qwen2-VL through multimodal passthrough
(/root/reference/backend/python/vllm/backend.py:211-243); the round-1
target list names "Llava-1.6 / Qwen2-VL". Unlike the llava tower
(models/vision.py: fixed 336px grid, CLS+interp positions), Qwen2-VL
encodes at NATIVE resolution: images resize to the nearest multiple of
patch·merge (28), every 14px patch becomes a token with 2-axis rotary
positions, and a 2x2 patch merger compresses the grid into LLM tokens.
The language side applies M-RoPE — 3D (temporal, height, width) position
streams section-split across the rope frequencies (models/llama.py
`mrope`, ops/rope.mrope_angles).

TPU shape: the tower is one jitted dense program per (n_patches) bucket —
batched matmuls over the patch sequence (MXU), full (non-causal)
attention, fp32 softmax; the merger is a reshape + two matmuls. Position
streams and the decode-time rope delta are host-side numpy (tiny,
per-request).

HF layout (Qwen2VLForConditionalGeneration): visual.patch_embed.proj,
visual.blocks.{i}.{norm1,attn.qkv,attn.proj,norm2,mlp.fc1,mlp.fc2},
visual.merger.{ln_q,mlp.0,mlp.2}; the LLM under model.* (qwen2 names).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]

# CLIP normalization constants (Qwen2VLImageProcessor defaults)
IMAGE_MEAN = (0.48145466, 0.4578275, 0.40821073)
IMAGE_STD = (0.26862954, 0.26130258, 0.27577711)


@dataclasses.dataclass(frozen=True)
class Qwen2VLVisionConfig:
    depth: int = 32
    embed_dim: int = 1280
    num_heads: int = 16
    mlp_ratio: int = 4
    in_channels: int = 3
    patch_size: int = 14
    spatial_merge_size: int = 2
    temporal_patch_size: int = 2
    hidden_size: int = 3584  # LLM dim (merger output)
    # processor pixel budget (Qwen2VLImageProcessor defaults)
    min_pixels: int = 56 * 56
    max_pixels: int = 28 * 28 * 1280

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.temporal_patch_size * self.patch_size ** 2

    @property
    def merge_dim(self) -> int:
        return self.embed_dim * self.spatial_merge_size ** 2


def vision_config_from_hf(ckpt_dir: str) -> Qwen2VLVisionConfig:
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hf = json.load(f)
    vc = hf.get("vision_config") or {}
    return Qwen2VLVisionConfig(
        depth=vc.get("depth", 32),
        embed_dim=vc.get("embed_dim", 1280),
        num_heads=vc.get("num_heads", 16),
        mlp_ratio=vc.get("mlp_ratio", 4),
        in_channels=vc.get("in_channels", 3),
        patch_size=vc.get("patch_size", 14),
        spatial_merge_size=vc.get("spatial_merge_size", 2),
        temporal_patch_size=vc.get("temporal_patch_size", 2),
        hidden_size=vc.get("hidden_size", hf.get("hidden_size", 3584)),
    )


def is_qwen2_vl_dir(ckpt_dir: str) -> bool:
    cfg = os.path.join(ckpt_dir, "config.json")
    if not os.path.isfile(cfg):
        return False
    try:
        with open(cfg) as f:
            return json.load(f).get("model_type") == "qwen2_vl"
    except (OSError, ValueError):
        return False


# --------------------------------------------------------------------------- #
# Image preprocessing (Qwen2VLImageProcessor semantics)
# --------------------------------------------------------------------------- #


def smart_resize(h: int, w: int, factor: int = 28, min_pixels: int = 56 * 56,
                 max_pixels: int = 28 * 28 * 1280) -> tuple[int, int]:
    """Round to multiples of `factor` keeping total pixels inside the
    budget (HF qwen2_vl image_processing smart_resize)."""
    if max(h, w) / max(min(h, w), 1) > 200:
        raise ValueError("absurd aspect ratio")
    hbar = max(factor, round(h / factor) * factor)
    wbar = max(factor, round(w / factor) * factor)
    if hbar * wbar > max_pixels:
        beta = math.sqrt((h * w) / max_pixels)
        hbar = max(factor, math.floor(h / beta / factor) * factor)
        wbar = max(factor, math.floor(w / beta / factor) * factor)
    elif hbar * wbar < min_pixels:
        beta = math.sqrt(min_pixels / (h * w))
        hbar = math.ceil(h * beta / factor) * factor
        wbar = math.ceil(w * beta / factor) * factor
    return hbar, wbar


def preprocess(cfg: Qwen2VLVisionConfig, image: np.ndarray
               ) -> tuple[np.ndarray, tuple[int, int, int]]:
    """uint8 [H, W, 3] → (patches [n, patch_dim] f32, grid (t, gh, gw)).

    Matches the HF processor's flatten order exactly — the 2x2 merge
    groups are CONTIGUOUS in the sequence: (grid_t, gh/m, gw/m, m, m)
    outermost-to-innermost, features ordered (C, tps, ph, pw)."""
    from PIL import Image

    p, m, tps = cfg.patch_size, cfg.spatial_merge_size, cfg.temporal_patch_size
    H, W = image.shape[:2]
    rh, rw = smart_resize(H, W, p * m, cfg.min_pixels, cfg.max_pixels)
    img = np.asarray(
        Image.fromarray(np.asarray(image, np.uint8)).convert("RGB")
        .resize((rw, rh), Image.BICUBIC), np.float32) / 255.0
    img = (img - np.asarray(IMAGE_MEAN, np.float32)) / np.asarray(
        IMAGE_STD, np.float32)
    arr = img.transpose(2, 0, 1)[None]  # [1, C, H, W]
    arr = np.tile(arr, (tps, 1, 1, 1))  # temporal duplicate for still images
    gt, gh, gw = 1, rh // p, rw // p
    patches = arr.reshape(gt, tps, cfg.in_channels, gh // m, m, p, gw // m, m, p)
    patches = patches.transpose(0, 3, 6, 4, 7, 2, 1, 5, 8)
    return (patches.reshape(gt * gh * gw, cfg.patch_dim).astype(np.float32),
            (gt, gh, gw))


# --------------------------------------------------------------------------- #
# Vision tower forward
# --------------------------------------------------------------------------- #


def _vision_rope_angles(cfg: Qwen2VLVisionConfig, grid: tuple,
                        theta: float = 10000.0) -> np.ndarray:
    """[n_patches, head_dim/2] rotation angles: per-patch (row, col) ids in
    the merge-group order, each driving half the frequency ladder
    (Qwen2VisionTransformer rot_pos_emb + VisionRotaryEmbedding)."""
    t, gh, gw = grid
    m = cfg.spatial_merge_size
    hpos = np.broadcast_to(np.arange(gh)[:, None], (gh, gw))
    wpos = np.broadcast_to(np.arange(gw)[None, :], (gh, gw))

    def reorder(x):
        return (x.reshape(gh // m, m, gw // m, m).transpose(0, 2, 1, 3)
                .reshape(-1))

    hpos, wpos = reorder(hpos), reorder(wpos)
    dim = cfg.head_dim // 2  # rope dim per spatial axis pair
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ang = np.concatenate([
        hpos[:, None] * inv[None, :], wpos[:, None] * inv[None, :],
    ], axis=-1)  # [gh*gw, head_dim/2]
    return np.tile(ang, (t, 1)).astype(np.float32)


def vision_forward(cfg: Qwen2VLVisionConfig, p: Params, patches: jnp.ndarray,
                   angles: jnp.ndarray) -> jnp.ndarray:
    """patches [N, patch_dim], angles [N, head_dim/2] →
    merged tokens [N / merge², hidden_size]."""
    from localai_tpu.ops.rope import rope_rotate

    N = patches.shape[0]
    H, Dh = cfg.num_heads, cfg.head_dim
    w0 = p["patch_embed.weight"]
    # conv3d == linear over the flattened patch; cast to the weight dtype so
    # the whole trunk runs bf16 matmuls (norms/softmax stay fp32)
    h = (patches @ w0).astype(w0.dtype)

    def ln(x, pre):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        return y * p[f"{pre}.weight"] + p[f"{pre}.bias"]

    ang = angles[None]  # [1, N, hd/2] — rope_rotate wants [..., seq, h, d]
    for i in range(cfg.depth):
        pre = f"blocks.{i}"
        x = ln(h, f"{pre}.norm1").astype(h.dtype)
        qkv = x @ p[f"{pre}.attn.qkv.weight"] + p[f"{pre}.attn.qkv.bias"]
        q, k, v = jnp.split(qkv.reshape(N, 3, H, Dh), 3, axis=1)
        q = rope_rotate(q.transpose(1, 0, 2, 3), ang)[0]  # [N, H, Dh]
        k = rope_rotate(k.transpose(1, 0, 2, 3), ang)[0]
        v = v[:, 0]
        scores = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) / math.sqrt(Dh)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(N, -1)
        h = h + attn @ p[f"{pre}.attn.proj.weight"] + p[f"{pre}.attn.proj.bias"]
        x = ln(h, f"{pre}.norm2").astype(h.dtype)
        y = x @ p[f"{pre}.mlp.fc1.weight"] + p[f"{pre}.mlp.fc1.bias"]
        y = y * jax.nn.sigmoid(1.702 * y)  # QuickGELU
        h = h + y @ p[f"{pre}.mlp.fc2.weight"] + p[f"{pre}.mlp.fc2.bias"]

    # PatchMerger: ln_q per token, then 2x2 groups (contiguous by patch
    # order) concatenate and pass through a 2-layer MLP into the LLM dim.
    x = ln(h, "merger.ln_q").astype(h.dtype).reshape(-1, cfg.merge_dim)
    x = x @ p["merger.mlp.0.weight"] + p["merger.mlp.0.bias"]
    x = jax.nn.gelu(x, approximate=False)
    return x @ p["merger.mlp.2.weight"] + p["merger.mlp.2.bias"]


# --------------------------------------------------------------------------- #
# M-RoPE position ids (HF Qwen2VLForConditionalGeneration.get_rope_index)
# --------------------------------------------------------------------------- #


def mrope_positions_for_span(total_len: int, offset: int, grid: tuple,
                             merge: int = 2) -> tuple[np.ndarray, int]:
    """3D (t, h, w) position streams for a prompt whose [offset,
    offset+span) token range holds one image's merged patches.

    Text tokens advance all three streams together; image tokens freeze t
    at the preceding text position and spread (h, w) over the merged grid;
    text after the image resumes at max_position + 1. Returns (pos3
    [3, total_len] i32, rope_delta) with rope_delta = (max_pos + 1) -
    total_len — the constant that makes decode positions row_index + delta
    (HF returns the same as mrope_position_deltas)."""
    t, gh, gw = grid
    mh, mw = gh // merge, gw // merge
    span = t * mh * mw
    pos3 = np.zeros((3, total_len), np.int64)
    # text before the image
    pos3[:, :offset] = np.arange(offset)[None, :]
    st = offset
    tt = np.repeat(np.arange(t), mh * mw)
    hh = np.tile(np.repeat(np.arange(mh), mw), t)
    ww = np.tile(np.tile(np.arange(mw), mh), t)
    pos3[0, offset: offset + span] = st + tt
    pos3[1, offset: offset + span] = st + hh
    pos3[2, offset: offset + span] = st + ww
    nxt = st + int(max(t, mh, mw))  # max position inside the span + 1
    n_after = total_len - offset - span
    if n_after > 0:
        pos3[:, offset + span:] = nxt + np.arange(n_after)[None, :]
        max_pos = nxt + n_after - 1
    else:
        max_pos = nxt - 1
    return pos3.astype(np.int32), int(max_pos + 1 - total_len)


# --------------------------------------------------------------------------- #
# Checkpoint loading + encoder wrapper
# --------------------------------------------------------------------------- #


def load_hf_qwen2_vl_vision(cfg: Qwen2VLVisionConfig, ckpt_dir: str) -> Params:
    """visual.* tensors → flat dict with linears pre-transposed [in, out];
    the conv3d patch embed flattens to a [patch_dim, embed_dim] matmul."""
    from localai_tpu.engine.weights import _ShardReader

    # _ShardReader aliases model.visual.* → visual.*, so one spelling
    # addresses both the published and the nested transformers layouts.
    reader = _ShardReader(ckpt_dir)
    prefix = "visual."
    out: Params = {}
    w = reader.get(prefix + "patch_embed.proj.weight")  # [D, C, tps, p, p]
    out["patch_embed.weight"] = jnp.asarray(
        np.ascontiguousarray(w.reshape(w.shape[0], -1).T))
    names = ["merger.ln_q.weight", "merger.ln_q.bias",
             "merger.mlp.0.weight", "merger.mlp.0.bias",
             "merger.mlp.2.weight", "merger.mlp.2.bias"]
    for i in range(cfg.depth):
        for nm in ("norm1.weight", "norm1.bias", "attn.qkv.weight",
                   "attn.qkv.bias", "attn.proj.weight", "attn.proj.bias",
                   "norm2.weight", "norm2.bias", "mlp.fc1.weight",
                   "mlp.fc1.bias", "mlp.fc2.weight", "mlp.fc2.bias"):
            names.append(f"blocks.{i}.{nm}")
    for nm in names:
        arr = reader.get(prefix + nm)
        if arr.ndim == 2 and nm.endswith(".weight"):
            arr = arr.T
        out[nm] = jnp.asarray(np.ascontiguousarray(arr))
    return out


class Qwen2VLVisionEncoder:
    """Host-side wrapper: uint8 image → (merged tokens [n, llm_dim], grid).
    Jit-cached per patch-count bucket (native resolution varies)."""

    kind = "qwen2_vl"

    def __init__(self, cfg: Qwen2VLVisionConfig, params: Params):
        self.cfg = cfg
        self.params = params
        self._jit: dict[int, Any] = {}

    def encode_with_grid(self, image: np.ndarray
                         ) -> tuple[np.ndarray, tuple[int, int, int]]:
        patches, grid = preprocess(self.cfg, image)
        angles = _vision_rope_angles(self.cfg, grid)
        n = patches.shape[0]
        fn = self._jit.get(n)
        if fn is None:
            cfg = self.cfg
            fn = jax.jit(lambda p, x, a: vision_forward(cfg, p, x, a))
            if len(self._jit) >= 8:
                self._jit.pop(next(iter(self._jit)))
            self._jit[n] = fn
        feats = np.asarray(fn(self.params, jnp.asarray(patches),
                              jnp.asarray(angles)))
        return feats, grid

    @property
    def merge(self) -> int:
        return self.cfg.spatial_merge_size

    def encode(self, image: np.ndarray) -> np.ndarray:
        return self.encode_with_grid(image)[0]
