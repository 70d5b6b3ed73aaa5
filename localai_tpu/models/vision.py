"""CLIP-style vision tower + Llava projector: image features for VLM chat.

Reference capability: multimodal chat via llava / Qwen2-VL through the vllm
backend (backend/python/vllm multimodal). TPU shape:
a ViT encoder (patch conv → pre-LN transformer) whose `select_layer` hidden
states (llava uses -2) pass through a 2-layer MLP projector into the LLM's
embedding space; the serving engine injects the projected tokens into the
prompt's embedding sequence at admission (models/llama.py `inject`).

HF weight mapping follows LlavaForConditionalGeneration
(`vision_tower.vision_model.*`, `multi_modal_projector.linear_{1,2}`).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    name: str = "clip-vit"
    image_size: int = 336
    patch: int = 14
    d_model: int = 1024
    layers: int = 24
    n_heads: int = 16
    ffn: int = 4096
    llm_dim: int = 4096  # projector output = LLM hidden size
    select_layer: int = -2  # llava: penultimate encoder layer
    layer_norm_eps: float = 1e-5

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch) ** 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


VISION_PRESETS: dict[str, VisionConfig] = {
    "vit-test": VisionConfig(
        name="vit-test", image_size=16, patch=8, d_model=32, layers=2,
        n_heads=2, ffn=64, llm_dim=64, select_layer=-1,
    ),
    "clip-vit-l-336": VisionConfig(name="clip-vit-l-336"),
}


def init_params(cfg: VisionConfig, key: jnp.ndarray, scale: float = 0.02) -> Params:
    keys = iter(jax.random.split(key, 32))
    D, L = cfg.d_model, cfg.layers

    def rnd(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    return {
        "patch_w": rnd((D, 3, cfg.patch, cfg.patch)),  # HF conv layout [D,C,k,k]
        "cls": rnd((D,)),
        "pos": rnd((cfg.n_patches + 1, D)),
        "pre_ln_w": jnp.ones((D,)), "pre_ln_b": jnp.zeros((D,)),
        "layers": {
            "ln1_w": jnp.ones((L, D)), "ln1_b": jnp.zeros((L, D)),
            "q_w": rnd((L, D, D)), "q_b": jnp.zeros((L, D)),
            "k_w": rnd((L, D, D)), "k_b": jnp.zeros((L, D)),
            "v_w": rnd((L, D, D)), "v_b": jnp.zeros((L, D)),
            "o_w": rnd((L, D, D)), "o_b": jnp.zeros((L, D)),
            "ln2_w": jnp.ones((L, D)), "ln2_b": jnp.zeros((L, D)),
            "fc1_w": rnd((L, D, cfg.ffn)), "fc1_b": jnp.zeros((L, cfg.ffn)),
            "fc2_w": rnd((L, cfg.ffn, D)), "fc2_b": jnp.zeros((L, D)),
        },
        "proj1_w": rnd((D, cfg.llm_dim)), "proj1_b": jnp.zeros((cfg.llm_dim,)),
        "proj2_w": rnd((cfg.llm_dim, cfg.llm_dim)), "proj2_b": jnp.zeros((cfg.llm_dim,)),
    }


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * w + b


def encode_image(cfg: VisionConfig, params: Params, pixels: jnp.ndarray) -> jnp.ndarray:
    """pixels [B, H, W, 3] in [0, 1] → projected patch features
    [B, n_patches, llm_dim] (CLS dropped, llava default)."""
    B = pixels.shape[0]
    x = (pixels.astype(jnp.float32) - 0.5) / 0.5  # CLIP-style normalize
    x = x.transpose(0, 3, 1, 2)  # NCHW
    patches = jax.lax.conv_general_dilated(
        x, params["patch_w"], (cfg.patch, cfg.patch), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # [B, D, H/p, W/p]
    h = patches.reshape(B, cfg.d_model, -1).transpose(0, 2, 1)  # [B, N, D]
    cls = jnp.broadcast_to(params["cls"][None, None], (B, 1, cfg.d_model))
    h = jnp.concatenate([cls, h], axis=1) + params["pos"][None]
    h = _ln(h, params["pre_ln_w"], params["pre_ln_b"], cfg.layer_norm_eps)

    H, Dh = cfg.n_heads, cfg.head_dim
    T = h.shape[1]

    def layer(h, lp):
        x = _ln(h, lp["ln1_w"], lp["ln1_b"], cfg.layer_norm_eps)
        q = (x @ lp["q_w"] + lp["q_b"]).reshape(B, T, H, Dh)
        k = (x @ lp["k_w"] + lp["k_b"]).reshape(B, T, H, Dh)
        v = (x @ lp["v_w"] + lp["v_b"]).reshape(B, T, H, Dh)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * Dh**-0.5
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, cfg.d_model)
        h = h + attn @ lp["o_w"] + lp["o_b"]
        x = _ln(h, lp["ln2_w"], lp["ln2_b"], cfg.layer_norm_eps)
        h = h + jax.nn.gelu(x @ lp["fc1_w"] + lp["fc1_b"], approximate=False) @ lp["fc2_w"] + lp["fc2_b"]
        return h, h

    _, per_layer = jax.lax.scan(layer, h, params["layers"])  # [L, B, T, D]
    feats = per_layer[cfg.select_layer]  # llava select_layer (-2 default)
    feats = feats[:, 1:]  # drop CLS
    proj = jax.nn.gelu(feats @ params["proj1_w"] + params["proj1_b"], approximate=False)
    return proj @ params["proj2_w"] + params["proj2_b"]  # [B, N, llm_dim]


class VisionEncoder:
    """Host-side wrapper: uint8 image → projected features, jit-cached."""

    def __init__(self, cfg: VisionConfig, params: Params):
        self.cfg = cfg
        self.params = params
        self._fn = jax.jit(lambda p, x: encode_image(cfg, p, x))

    @property
    def n_tokens(self) -> int:
        return self.cfg.n_patches

    def encode(self, image: np.ndarray) -> np.ndarray:
        """uint8 [H, W, 3] (any size) → float32 [n_patches, llm_dim]."""
        from PIL import Image

        s = self.cfg.image_size
        if image.shape[:2] != (s, s):
            image = np.asarray(Image.fromarray(image).resize((s, s), Image.BILINEAR))
        x = image.astype(np.float32)[None] / 255.0
        return np.asarray(self._fn(self.params, jnp.asarray(x)))[0]


# --------------------------------------------------------------------------- #
# HF checkpoint I/O (LlavaForConditionalGeneration names)
# --------------------------------------------------------------------------- #

_VT = "vision_tower.vision_model"

_LAYER_MAP = {
    "ln1_w": ("layer_norm1.weight", False), "ln1_b": ("layer_norm1.bias", False),
    "q_w": ("self_attn.q_proj.weight", True), "q_b": ("self_attn.q_proj.bias", False),
    "k_w": ("self_attn.k_proj.weight", True), "k_b": ("self_attn.k_proj.bias", False),
    "v_w": ("self_attn.v_proj.weight", True), "v_b": ("self_attn.v_proj.bias", False),
    "o_w": ("self_attn.out_proj.weight", True), "o_b": ("self_attn.out_proj.bias", False),
    "ln2_w": ("layer_norm2.weight", False), "ln2_b": ("layer_norm2.bias", False),
    "fc1_w": ("mlp.fc1.weight", True), "fc1_b": ("mlp.fc1.bias", False),
    "fc2_w": ("mlp.fc2.weight", True), "fc2_b": ("mlp.fc2.bias", False),
}


def load_hf_vision(cfg: VisionConfig, ckpt_dir: str) -> Params:
    from localai_tpu.engine.weights import _ShardReader

    reader = _ShardReader(ckpt_dir)

    def grab(name: str, transpose: bool = False) -> jnp.ndarray:
        arr = reader.get(name)
        if transpose and arr.ndim == 2:
            arr = arr.T
        return jnp.asarray(np.ascontiguousarray(arr))

    layers: Params = {}
    for our, (suffix, tr) in _LAYER_MAP.items():
        rows = [
            grab(f"{_VT}.encoder.layers.{i}.{suffix}", tr) for i in range(cfg.layers)
        ]
        layers[our] = jnp.stack(rows)
    return {
        "patch_w": grab(f"{_VT}.embeddings.patch_embedding.weight"),
        "cls": grab(f"{_VT}.embeddings.class_embedding").reshape(-1),
        "pos": grab(f"{_VT}.embeddings.position_embedding.weight"),
        "pre_ln_w": grab(f"{_VT}.pre_layrnorm.weight"),
        "pre_ln_b": grab(f"{_VT}.pre_layrnorm.bias"),
        "layers": layers,
        "proj1_w": grab("multi_modal_projector.linear_1.weight", True),
        "proj1_b": grab("multi_modal_projector.linear_1.bias"),
        "proj2_w": grab("multi_modal_projector.linear_2.weight", True),
        "proj2_b": grab("multi_modal_projector.linear_2.bias"),
    }


def save_hf_vision(cfg: VisionConfig, params: Params, ckpt_dir: str) -> None:
    """Inverse of load_hf_vision (fixture fabrication for tests); merges into
    an existing safetensors file when one is present."""
    from safetensors.numpy import save_file

    os.makedirs(ckpt_dir, exist_ok=True)
    tensors: dict[str, np.ndarray] = {}
    path = os.path.join(ckpt_dir, "model.safetensors")
    if os.path.exists(path):
        from safetensors import safe_open

        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)

    def emit(name, arr, transpose=False):
        a = np.asarray(jnp.asarray(arr, jnp.float32))
        if transpose and a.ndim == 2:
            a = a.T
        tensors[name] = np.ascontiguousarray(a)

    emit(f"{_VT}.embeddings.patch_embedding.weight", params["patch_w"])
    emit(f"{_VT}.embeddings.class_embedding", params["cls"])
    emit(f"{_VT}.embeddings.position_embedding.weight", params["pos"])
    emit(f"{_VT}.pre_layrnorm.weight", params["pre_ln_w"])
    emit(f"{_VT}.pre_layrnorm.bias", params["pre_ln_b"])
    for our, (suffix, tr) in _LAYER_MAP.items():
        for i in range(cfg.layers):
            emit(f"{_VT}.encoder.layers.{i}.{suffix}", params["layers"][our][i], tr)
    emit("multi_modal_projector.linear_1.weight", params["proj1_w"], True)
    emit("multi_modal_projector.linear_1.bias", params["proj1_b"])
    emit("multi_modal_projector.linear_2.weight", params["proj2_w"], True)
    emit("multi_modal_projector.linear_2.bias", params["proj2_b"])
    save_file(tensors, path)
    vjson = os.path.join(ckpt_dir, "vision_config.json")
    with open(vjson, "w") as f:
        json.dump(dataclasses.asdict(cfg), f, indent=1)


def vision_config_from_hf(ckpt_dir: str) -> VisionConfig:
    """From our sidecar vision_config.json or an HF llava config.json."""
    side = os.path.join(ckpt_dir, "vision_config.json")
    if os.path.exists(side):
        with open(side) as f:
            return VisionConfig(**json.load(f))
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hf = json.load(f)
    vc = hf.get("vision_config") or {}
    return VisionConfig(
        name=vc.get("model_type", "clip-vit"),
        image_size=vc.get("image_size", 336),
        patch=vc.get("patch_size", 14),
        d_model=vc.get("hidden_size", 1024),
        layers=vc.get("num_hidden_layers", 24),
        n_heads=vc.get("num_attention_heads", 16),
        ffn=vc.get("intermediate_size", 4096),
        llm_dim=(hf.get("text_config") or {}).get("hidden_size", 4096),
        select_layer=hf.get("vision_feature_layer", -2),
    )
