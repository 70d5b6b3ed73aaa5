"""Weight-only quantization for serving: per-channel int8 and grouped int4/int8.

Reference capability: quantized GGUFs are llama.cpp's bread and butter (the
reference serves Q4/Q8 checkpoints everywhere). TPU-native shape: weight-only
quantization with dequant fused INSIDE the matmul — XLA folds the int→bf16
convert into the dot's operand load, so HBM streams 1 byte (int8) or 0.5+ε
bytes (packed int4) per weight instead of two. Measured on v5e
(llama-3.2-1b bs8 decode): ~17% faster steps at int8 and half the weight
footprint; int4 halves it again (llama.cpp Q4-class memory envelope).

XLA folds that convert reliably only for the flat int8 form. The grouped
int8 and packed int4 forms (reshape → unpack → concat → scale → dot) get a
materialized dequantized copy in HBM instead, so decode streamed ~2.5
bytes/weight at int4. ISSUE 9: decode-shape calls now dispatch to fused
Pallas dequant-matmul kernels (ops/quant_matmul.py, `quant_kernel` /
LOCALAI_QUANT_KERNEL — auto: Pallas on TPU) that unpack + scale in VMEM
registers; the XLA forms in this file remain the numeric oracle and the
prefill/compute-bound path.

Representations consumed by `matmul` / `unembed_matmul`:
- {"q": int8 [..., in, out], "s": f32 [..., 1, out]} — per-output-channel
  symmetric int8 (mode "int8").
- {"gq": int8 [..., G, gs, out], "gs": f32 [..., G, 1, out]} — group-wise
  symmetric int8 (GGUF q8_0 repacks losslessly; q5/q6_K regrid here).
- {"g4": uint8 [..., G, gs//2, out], "gs": ..., "gz": f32 [..., G, 1, out]}
  — group-wise affine 4-bit, two nibbles per byte along the in-group axis
  (low nibbles = first gs/2 elements). value = nibble * gs - gz. GGUF
  q4_0/q4_K blocks repack losslessly (mode "int4" for our own weights).

Quantization happens on device AFTER sharded placement, so the q/s arrays
inherit the weight's sharding (parallel/sharding.py aligns specs to either
form).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

# The stacked-operand convention (a stack and its layer index) lives with the
# kernels that read it; `quant.StackedLayer` is how the models spell it.
from localai_tpu.ops.stacked import StackedLayer, layer_of, layer_slice

Params = dict[str, Any]

# 2D-matmul weights that benefit; embeddings stay bf16 (gather path).
# w_kb/w_vb (MLA latent up-projections) stay unquantized: they ride
# einsum paths with no grouped-int kernel and are small next to the MoE.
QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down",
                    "wq_a", "wq_b", "wkv_a", "w_in", "w_z", "w_xbc", "w_dt",
                    "w_x", "shared_gate", "shared_up", "shared_down")


# The per-layer stacks of a param tree: the model's layers, DeepSeek's dense
# prefix, and a hybrid model's two attention kinds: its recurrent layers (KDA,
# the gated short conv, SSD or window attention) and its cache layers, MLA or
# GQA (models/llama.py,
# `ArchConfig.recurrent_stack`, `.cache_stack`).
LAYER_STACKS = ("layers", "dense_layers", "kda_layers", "conv_layers",
                "ssd_layers", "swa_layers", "s6_layers", "mla_layers",
                "gqa_layers")


def quantize_tensor(w: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Per-output-channel symmetric int8 over the reduction (-2) axis."""
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-9)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


GROUP_SIZE = 32  # matches GGUF q4_0/q8_0 blocks → lossless repack


def quantize_tensor_g4(w: jnp.ndarray, group: int = GROUP_SIZE) -> dict[str, jnp.ndarray]:
    """Group-wise affine 4-bit over the reduction (-2) axis; value =
    nibble * gs - gz, nibbles packed two per byte (low = first half of the
    group). jit-friendly."""
    *lead, n_in, n_out = w.shape
    if n_in % group:
        raise ValueError(f"in dim {n_in} not divisible by group {group}")
    g = n_in // group
    wf = w.astype(jnp.float32).reshape(*lead, g, group, n_out)
    mn = wf.min(axis=-2, keepdims=True)
    mx = wf.max(axis=-2, keepdims=True)
    s = jnp.maximum((mx - mn) / 15.0, 1e-9)
    nib = jnp.clip(jnp.round((wf - mn) / s), 0, 15).astype(jnp.uint8)
    half = group // 2
    packed = nib[..., :half, :] | (nib[..., half:, :] << 4)
    return {"g4": packed, "gs": s, "gz": -mn}


def _grouped_values(w, dtype) -> jnp.ndarray:
    """[..., G, gs, out] values (still un-scaled) from a grouped dict."""
    if "g4" in w:
        qp = w["g4"]
        lo = qp & jnp.uint8(0xF)
        hi = qp >> jnp.uint8(4)
        return jnp.concatenate([lo, hi], axis=-2).astype(dtype)
    return w["gq"].astype(dtype)


def grouped_matmul(x: jnp.ndarray, w: dict) -> jnp.ndarray:
    """x [..., in] @ grouped-quantized w [G, gs(, packed), out] → [..., out].

    One batched dot per group with the scale applied on the group partials —
    XLA fuses the unpack/convert into the dot's operand load, so HBM streams
    the packed bytes. The affine zero-point contributes Σ_i x_i · z per
    group, a cheap rank-1 correction."""
    qv = _grouped_values(w, x.dtype)  # [G, gs, out]
    g, gs, n_out = qv.shape
    xg = x.reshape(*x.shape[:-1], g, gs)
    y = jnp.einsum("...gi,gin->...gn", xg, qv)
    y = y * w["gs"].astype(x.dtype)[..., 0, :]
    out = y.sum(axis=-2)
    if "gz" in w:
        xsum = xg.sum(axis=-1)  # [..., G]
        out = out - jnp.einsum(
            "...g,gn->...n", xsum, w["gz"].astype(x.dtype)[..., 0, :]
        )
    return out


def matmul(x: jnp.ndarray, w, impl: str = "auto", mesh=None,
           part=None) -> jnp.ndarray:
    """x @ w for plain or quantized w.

    Quantized dispatch (ISSUE 9): decode-shape calls route to the fused
    Pallas dequant-matmul kernels (ops/quant_matmul — nibble unpack +
    affine scale in VMEM registers, f32 MXU accumulation; each packed byte
    crosses HBM once) per `impl` — "auto" is Pallas on TPU. Everything the
    kernels don't serve (prefill-scale rows, XLA impl, exotic shapes) falls
    through to the XLA forms below, which double as the kernels' numeric
    oracle. XLA folds the flat int8 convert into the dot's operand load;
    the grouped/packed forms are the ones it materializes — the kernels'
    whole reason to exist.

    mesh/part: under a tp>1 mesh the kernel runs in shard_map with the
    weight's own partitioning ("col" = out axis sharded, "row" = group/in
    axis sharded + psum at the declared boundary) — pallas_call is opaque
    to GSPMD, so unwrapped it would all-gather the sharded weight per call.
    """
    if is_quantized(w):
        from localai_tpu.ops.quant_matmul import dispatch_matmul

        y = dispatch_matmul(x, dict(w), impl=impl, mesh=mesh, part=part,
                            layer=layer_of(w))
        if y is not None:
            return y
        w = layer_slice(w)
        if "q" in w:
            return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)[..., 0, :]
        return grouped_matmul(x, w)
    return x @ w


def is_quantized(w) -> bool:
    """A quantized leaf: its dict, or a StackedLayer that holds one."""
    if isinstance(w, StackedLayer):
        w = w.stack
    return isinstance(w, dict) and ("q" in w or "gq" in w or "g4" in w)


def is_grouped(w) -> bool:
    return isinstance(w, dict) and ("gq" in w or "g4" in w)


def quantize_tensor_np(arr, axis: int = -2) -> dict:
    """numpy variant for host-side load-time quantization (streaming a
    checkpoint too big for HBM in bf16 — e.g. 8B on one v5e chip)."""
    import numpy as np

    wf = np.asarray(arr, np.float32)
    s = np.max(np.abs(wf), axis=axis, keepdims=True) / 127.0
    s = np.maximum(s, 1e-9)
    q = np.clip(np.round(wf / s), -127, 127).astype(np.int8)
    return {"q": q, "s": s.astype(np.float32)}


def quantize_tensor_np_g4(arr, group: int = GROUP_SIZE) -> dict:
    """numpy variant of `quantize_tensor_g4` (host-side int4 load path).
    arr [..., in, out] → grouped affine 4-bit over the in axis."""
    import numpy as np

    wf = np.asarray(arr, np.float32)
    *lead, n_in, n_out = wf.shape
    if n_in % group:
        raise ValueError(f"in dim {n_in} not divisible by group {group}")
    g = n_in // group
    wf = wf.reshape(*lead, g, group, n_out)
    mn = wf.min(axis=-2, keepdims=True)
    mx = wf.max(axis=-2, keepdims=True)
    s = np.maximum((mx - mn) / 15.0, 1e-9)
    nib = np.clip(np.round((wf - mn) / s), 0, 15).astype(np.uint8)
    half = group // 2
    packed = nib[..., :half, :] | (nib[..., half:, :] << 4)
    return {"g4": packed, "gs": s.astype(np.float32), "gz": (-mn).astype(np.float32)}


def is_prequantized(params: Params) -> bool:
    layers = params.get("layers") or {}
    return any(isinstance(layers.get(k), dict) for k in QUANT_LAYER_KEYS)


def dequantize_tensor(w) -> jnp.ndarray:
    """Back to a dense float tensor (tests / debugging)."""
    if not isinstance(w, dict):
        return w
    if "q" in w:
        return w["q"].astype(jnp.float32) * w["s"]
    qv = _grouped_values(w, jnp.float32)  # [..., G, gs, out]
    vals = qv * w["gs"]
    if "gz" in w:
        vals = vals - w["gz"]
    *lead, g, gs, n_out = vals.shape
    return vals.reshape(*lead, g * gs, n_out)


def quantize_params(cfg, params: Params, mode: str = "int8") -> Params:
    """Quantize a llama-family param tree's matmul weights (jit-friendly;
    run AFTER device_put so outputs inherit shardings)."""
    if mode in ("", "none", None):
        return params
    if mode == "int8":
        qfn = quantize_tensor
    elif mode == "int4":
        qfn = quantize_tensor_g4
    else:
        raise ValueError(f"unsupported quantization mode {mode!r}")
    out = dict(params)
    for stack in LAYER_STACKS:
        if stack not in params:
            continue
        layers = dict(params[stack])
        for key in QUANT_LAYER_KEYS:
            if key in layers:
                layers[key] = qfn(layers[key])
        out[stack] = layers
    # lm_head [V, D] is used transposed (h @ W.T): quantize over D so the
    # scale lands on the output (vocab) axis of the transposed matmul.
    if "lm_head" in params and not cfg.tie_embeddings:
        w = params["lm_head"].astype(jnp.float32)  # [V, D]
        s = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0  # [V, 1]
        s = jnp.maximum(s, 1e-9)
        q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        out["lm_head"] = {"q": q, "s": s}
    return out


def init_params_quantized(
    cfg, key: jnp.ndarray, scale: float = 0.02, mode: str = "int8"
) -> Params:
    """Random init that lands directly in the quantized representation.

    Builds the same tree `quantize_params(mode=...)` would produce, but leaf
    by leaf (expert stacks layer by layer): the float tensor only ever
    exists as a transient inside one jit, so peak HBM ≈ the quantized tree +
    the largest single dense weight. This is how
    a synthetic llama-3-8b serves from a single 16 GB chip (a whole-tree bf16
    init is 2x HBM and OOMs before quantization could run).
    """
    from jax import tree_util as jtu

    from localai_tpu.models.llama import (
        SSD_DT, init_gain, init_params, init_special)

    if mode == "int8":
        qfn = quantize_tensor
    elif mode == "int4":
        qfn = quantize_tensor_g4
    else:
        raise ValueError(f"unsupported quantization mode {mode!r}")
    structure = jax.eval_shape(lambda k: init_params(cfg, k, scale), key)
    flat, treedef = jtu.tree_flatten_with_path(structure)
    keys = iter(jax.random.split(key, len(flat)))

    def leaf_name(path) -> str:
        last = path[-1]
        return getattr(last, "key", str(last))

    def build(path, sd):
        name = leaf_name(path)
        if "norm" in name:
            return jnp.ones(sd.shape, sd.dtype)
        if name in ("bq", "bk", "bv"):
            return jnp.zeros(sd.shape, sd.dtype)
        k = next(keys)
        special = init_special(
            name, k, sd.shape,
            SSD_DT if cfg.recurrent_kind in ("ssd", "s6") else cfg.kda_init_dt)
        if special is not None:
            return special.astype(sd.dtype)
        if name in QUANT_LAYER_KEYS and len(sd.shape) == 4:
            # An expert stack [L, E, in, out] is E times a dense leaf (OLMoE:
            # 2**31 elements, 8.6 GB as one float32 transient beside a tree
            # that is already half there): drawn and quantized a layer at a
            # time, so the transient is one layer's.
            std = scale * init_gain(cfg, name, sd.shape)
            return jax.jit(lambda kk: jax.lax.map(
                lambda k1: qfn(
                    jax.random.normal(k1, sd.shape[1:], jnp.float32) * std),
                jax.random.split(kk, sd.shape[0])))(k)
        if name in QUANT_LAYER_KEYS:
            std = scale * init_gain(cfg, name, sd.shape)
            return jax.jit(lambda kk: qfn(
                jax.random.normal(kk, sd.shape, jnp.float32) * std
            ))(k)
        if name == "lm_head" and not cfg.tie_embeddings:
            def head(kk):
                w = jax.random.normal(kk, sd.shape, jnp.float32) * scale
                s = jnp.maximum(
                    jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0, 1e-9
                )
                q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
                return {"q": q, "s": s}

            return jax.jit(head)(k)
        std = scale * init_gain(cfg, name, sd.shape)
        return jax.jit(lambda kk: (
            jax.random.normal(kk, sd.shape, jnp.float32) * std
        ).astype(sd.dtype))(k)

    leaves = [build(path, sd) for path, sd in flat]
    return jtu.tree_unflatten(treedef, leaves)


def unembed_matmul(h: jnp.ndarray, w, impl: str = "auto",
                   mesh=None) -> jnp.ndarray:
    """h @ W.T for the (possibly quantized) lm_head/embed matrix → f32.

    Quantized heads dispatch to the fused Pallas kernel at decode row
    counts (ops/quant_matmul.dispatch_unembed — out tiles stream contiguous
    weight rows, so the transpose never materializes); the XLA form below
    stays the oracle/fallback. Under tp>1 the kernel shard_maps over the
    vocab-parallel axis."""
    if isinstance(w, dict):
        from localai_tpu.ops.quant_matmul import dispatch_unembed

        y = dispatch_unembed(h, w, impl=impl, mesh=mesh)
        if y is not None:
            return y
        logits = jnp.dot(
            h, w["q"].T.astype(h.dtype), preferred_element_type=jnp.float32
        )
        return logits * w["s"][:, 0].astype(jnp.float32)  # [V] broadcasts
    return jnp.dot(h.astype(w.dtype), w.T, preferred_element_type=jnp.float32)
