"""BERT-family encoder as pure-functional JAX: sentence embeddings
(bge / sentence-transformers class) and cross-encoder reranking.

Reference: backend/python/transformers/backend.py SentenceTransformer branch
(bge-* embedding models) and the rerankers backend
(cross-encoder scoring). TPU shape: stacked-layer pytree + lax.scan,
post-LN blocks per original BERT, masked mean / CLS pooling, L2-normalized
outputs; an optional classification head turns the same stack into a
cross-encoder reranker.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class BertConfig:
    name: str = "bert"
    vocab_size: int = 30522
    hidden_size: int = 384  # bge-small
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 1536
    max_position: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pooling: str = "cls"  # "cls" | "mean" (sentence-transformers pooling_mode)
    num_labels: int = 0  # >0 adds the cross-encoder classification head

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


BERT_PRESETS: dict[str, BertConfig] = {
    "bert-test": BertConfig(
        name="bert-test", vocab_size=512, hidden_size=32, num_layers=2,
        num_heads=2, intermediate_size=64, max_position=128,
    ),
    "bert-rerank-test": BertConfig(
        name="bert-rerank-test", vocab_size=512, hidden_size=32, num_layers=2,
        num_heads=2, intermediate_size=64, max_position=128, num_labels=1,
    ),
    "bge-small": BertConfig(name="bge-small"),
    "bge-base": BertConfig(
        name="bge-base", hidden_size=768, intermediate_size=3072
    ),
    "bge-large": BertConfig(
        name="bge-large", hidden_size=1024, num_layers=24, num_heads=16,
        intermediate_size=4096,
    ),
}


def init_params(cfg: BertConfig, key: jnp.ndarray, scale: float = 0.02) -> Params:
    keys = iter(jax.random.split(key, 32))
    D, L, F = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size

    def rnd(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    params: Params = {
        "word_embed": rnd((cfg.vocab_size, D)),
        "pos_embed": rnd((cfg.max_position, D)),
        "type_embed": rnd((cfg.type_vocab_size, D)),
        "embed_ln_w": jnp.ones((D,)), "embed_ln_b": jnp.zeros((D,)),
        "layers": {
            "q_w": rnd((L, D, D)), "q_b": jnp.zeros((L, D)),
            "k_w": rnd((L, D, D)), "k_b": jnp.zeros((L, D)),
            "v_w": rnd((L, D, D)), "v_b": jnp.zeros((L, D)),
            "ao_w": rnd((L, D, D)), "ao_b": jnp.zeros((L, D)),
            "attn_ln_w": jnp.ones((L, D)), "attn_ln_b": jnp.zeros((L, D)),
            "fc1_w": rnd((L, D, F)), "fc1_b": jnp.zeros((L, F)),
            "fc2_w": rnd((L, F, D)), "fc2_b": jnp.zeros((L, D)),
            "out_ln_w": jnp.ones((L, D)), "out_ln_b": jnp.zeros((L, D)),
        },
        "pooler_w": rnd((D, D)), "pooler_b": jnp.zeros((D,)),
    }
    if cfg.num_labels > 0:
        params["cls_w"] = rnd((D, cfg.num_labels))
        params["cls_b"] = jnp.zeros((cfg.num_labels,))
    return params


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * w + b


def encode_hidden(
    cfg: BertConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B]
    token_types: Optional[jnp.ndarray] = None,  # [B, S]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full encoder forward → (hidden [B, S, D], mask [B, S])."""
    B, S = tokens.shape
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    tt = token_types if token_types is not None else jnp.zeros((B, S), jnp.int32)
    h = (
        params["word_embed"][tokens]
        + params["pos_embed"][jnp.arange(S)][None]
        + params["type_embed"][tt]
    )
    h = _ln(h, params["embed_ln_w"], params["embed_ln_b"], cfg.layer_norm_eps)
    H, Dh = cfg.num_heads, cfg.head_dim
    attn_bias = jnp.where(mask[:, None, None, :], 0.0, -1e30)  # [B,1,1,S]

    def layer(h, lp):
        q = (h @ lp["q_w"] + lp["q_b"]).reshape(B, S, H, Dh)
        k = (h @ lp["k_w"] + lp["k_b"]).reshape(B, S, H, Dh)
        v = (h @ lp["v_w"] + lp["v_b"]).reshape(B, S, H, Dh)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * Dh**-0.5 + attn_bias
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, cfg.hidden_size)
        # post-LN (original BERT): sublayer → residual add → LayerNorm
        h = _ln(h + attn @ lp["ao_w"] + lp["ao_b"],
                lp["attn_ln_w"], lp["attn_ln_b"], cfg.layer_norm_eps)
        ffn = jax.nn.gelu(h @ lp["fc1_w"] + lp["fc1_b"], approximate=False)
        h = _ln(h + ffn @ lp["fc2_w"] + lp["fc2_b"],
                lp["out_ln_w"], lp["out_ln_b"], cfg.layer_norm_eps)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return h, mask


def embed(
    cfg: BertConfig,
    params: Params,
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
) -> jnp.ndarray:
    """L2-normalized sentence embeddings [B, D] (bge: CLS pooling; mean
    pooling selectable per config — sentence-transformers semantics)."""
    h, mask = encode_hidden(cfg, params, tokens, lengths)
    if cfg.pooling == "mean":
        m = mask[..., None].astype(jnp.float32)
        pooled = (h * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
    else:  # CLS token
        pooled = h[:, 0]
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def score_pairs(
    cfg: BertConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] — [CLS] query [SEP] doc [SEP] rows
    lengths: jnp.ndarray,
    token_types: jnp.ndarray,  # 0 for query segment, 1 for doc segment
) -> jnp.ndarray:
    """Cross-encoder relevance scores [B] (bge-reranker class)."""
    assert cfg.num_labels > 0, "score_pairs needs a classification head"
    h, _ = encode_hidden(cfg, params, tokens, lengths, token_types)
    pooled = jnp.tanh(h[:, 0] @ params["pooler_w"] + params["pooler_b"])
    logits = pooled @ params["cls_w"] + params["cls_b"]  # [B, num_labels]
    return logits[:, 0]


# --------------------------------------------------------------------------- #
# HF checkpoint I/O (BertModel names, with/without "bert." prefix)
# --------------------------------------------------------------------------- #

_TOP_MAP = {
    "word_embed": ("embeddings.word_embeddings.weight", False),
    "pos_embed": ("embeddings.position_embeddings.weight", False),
    "type_embed": ("embeddings.token_type_embeddings.weight", False),
    "embed_ln_w": ("embeddings.LayerNorm.weight", False),
    "embed_ln_b": ("embeddings.LayerNorm.bias", False),
    "pooler_w": ("pooler.dense.weight", True),
    "pooler_b": ("pooler.dense.bias", False),
}

_LAYER_MAP = {
    "q_w": ("attention.self.query.weight", True),
    "q_b": ("attention.self.query.bias", False),
    "k_w": ("attention.self.key.weight", True),
    "k_b": ("attention.self.key.bias", False),
    "v_w": ("attention.self.value.weight", True),
    "v_b": ("attention.self.value.bias", False),
    "ao_w": ("attention.output.dense.weight", True),
    "ao_b": ("attention.output.dense.bias", False),
    "attn_ln_w": ("attention.output.LayerNorm.weight", False),
    "attn_ln_b": ("attention.output.LayerNorm.bias", False),
    "fc1_w": ("intermediate.dense.weight", True),
    "fc1_b": ("intermediate.dense.bias", False),
    "fc2_w": ("output.dense.weight", True),
    "fc2_b": ("output.dense.bias", False),
    "out_ln_w": ("output.LayerNorm.weight", False),
    "out_ln_b": ("output.LayerNorm.bias", False),
}


def load_hf_bert(cfg: BertConfig, ckpt_dir: str) -> Params:
    from localai_tpu.engine.weights import _ShardReader

    reader = _ShardReader(ckpt_dir)
    prefix = "bert." if "bert.embeddings.word_embeddings.weight" in reader else ""

    def grab(name: str, transpose: bool) -> jnp.ndarray:
        arr = reader.get(prefix + name)
        if transpose and arr.ndim == 2:
            arr = arr.T
        return jnp.asarray(np.ascontiguousarray(arr))

    params: Params = {}
    for our, (suffix, tr) in _TOP_MAP.items():
        if prefix + suffix in reader:
            params[our] = grab(suffix, tr)
        elif our.startswith("pooler"):  # some bge exports drop the pooler
            D = cfg.hidden_size
            params[our] = jnp.eye(D) if our.endswith("_w") else jnp.zeros((D,))
    layers: Params = {}
    for our, (suffix, tr) in _LAYER_MAP.items():
        rows = [grab(f"encoder.layer.{i}.{suffix}", tr) for i in range(cfg.num_layers)]
        layers[our] = jnp.stack(rows)
    params["layers"] = layers
    if cfg.num_labels > 0:
        # BertForSequenceClassification keeps the head OUTSIDE the "bert."
        # prefix; handle both layouts.
        if "classifier.weight" in reader:
            w = reader.get("classifier.weight")
            params["cls_w"] = jnp.asarray(np.ascontiguousarray(w.T))
            params["cls_b"] = jnp.asarray(reader.get("classifier.bias"))
        elif prefix + "classifier.weight" in reader:
            params["cls_w"] = grab("classifier.weight", True)
            params["cls_b"] = grab("classifier.bias", False)
        else:
            params["cls_w"] = jnp.zeros((cfg.hidden_size, cfg.num_labels))
            params["cls_b"] = jnp.zeros((cfg.num_labels,))
    return params


def save_hf_bert(cfg: BertConfig, params: Params, ckpt_dir: str) -> None:
    from safetensors.numpy import save_file

    os.makedirs(ckpt_dir, exist_ok=True)
    tensors: dict[str, np.ndarray] = {}

    def emit(name: str, arr, transpose=False):
        a = np.asarray(jnp.asarray(arr, jnp.float32))
        if transpose and a.ndim == 2:
            a = a.T
        tensors[name] = np.ascontiguousarray(a)

    for our, (suffix, tr) in _TOP_MAP.items():
        emit(suffix, params[our], tr)
    for our, (suffix, tr) in _LAYER_MAP.items():
        for i in range(cfg.num_layers):
            emit(f"encoder.layer.{i}.{suffix}", params["layers"][our][i], tr)
    if cfg.num_labels > 0 and "cls_w" in params:
        emit("classifier.weight", params["cls_w"], True)
        emit("classifier.bias", params["cls_b"])
    save_file(tensors, os.path.join(ckpt_dir, "model.safetensors"))
    with open(os.path.join(ckpt_dir, "config.json"), "w") as f:
        json.dump({
            "model_type": "bert",
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_position_embeddings": cfg.max_position,
            "type_vocab_size": cfg.type_vocab_size,
            "layer_norm_eps": cfg.layer_norm_eps,
            **({"num_labels": cfg.num_labels} if cfg.num_labels else {}),
        }, f, indent=1)


def bert_config_from_hf(ckpt_dir: str) -> BertConfig:
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hf = json.load(f)
    return BertConfig(
        name=hf.get("_name_or_path", "bert"),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        max_position=hf.get("max_position_embeddings", 512),
        type_vocab_size=hf.get("type_vocab_size", 2),
        layer_norm_eps=hf.get("layer_norm_eps", 1e-12),
        num_labels=hf.get("num_labels", 0) if hf.get("architectures", [""])[0].endswith("SequenceClassification") else hf.get("num_labels", 0),
    )
