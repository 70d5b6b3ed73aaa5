"""Checkpoint loading: HF safetensors → stacked-layer JAX param tree.

The reference consumes GGUF via llama.cpp (backend/cpp/llama-cpp) or HF
checkpoints via torch backends (backend/python/transformers/backend.py). Here
the canonical on-disk format is HF safetensors, mapped into the stacked
[L, ...] layout that `localai_tpu.models.llama` scans over, and placed shard-
by-shard onto the mesh so a 70B never materializes unsharded in host RAM.
"""

from __future__ import annotations

import json
import logging
import math
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models.config import ArchConfig

log = logging.getLogger("localai_tpu.weights")

Params = dict[str, Any]

# Our layer-param name -> HF per-layer tensor name (weights transposed: HF
# linear stores [out, in]; our matmuls are x @ W with W [in, out]).
_LAYER_MAP = {
    "attn_norm": ("input_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    # gated attention's output gate (ArchConfig.attn_gate; absent elsewhere)
    "wg": ("self_attn.g_proj.weight", True),
    "bq": ("self_attn.q_proj.bias", False),
    "bk": ("self_attn.k_proj.bias", False),
    "bv": ("self_attn.v_proj.bias", False),
    "mlp_norm": ("post_attention_layernorm.weight", False),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
}

_MOE_LAYER_MAP = {
    "router": ("block_sparse_moe.gate.weight", True),
    "w_gate": ("block_sparse_moe.experts.{e}.w1.weight", True),
    "w_up": ("block_sparse_moe.experts.{e}.w3.weight", True),
    "w_down": ("block_sparse_moe.experts.{e}.w2.weight", True),
}

# OLMoE keeps its MoE block under `mlp.` (as the deepseek loader's names do).
_OLMOE_LAYER_MAP = {
    "router": ("mlp.gate.weight", True),
    "w_gate": ("mlp.experts.{e}.gate_proj.weight", True),
    "w_up": ("mlp.experts.{e}.up_proj.weight", True),
    "w_down": ("mlp.experts.{e}.down_proj.weight", True),
}


def _moe_layer_map(cfg: ArchConfig) -> dict:
    """HF tensor names of a non-MLA MoE block: Mixtral's, or OLMoE's for the
    family that scores all experts before it selects."""
    return _OLMOE_LAYER_MAP if cfg.moe_family == "deepseek" else _MOE_LAYER_MAP


def _index(ckpt_dir: str) -> dict[str, str]:
    """tensor name -> safetensors shard filename."""
    idx_path = os.path.join(ckpt_dir, "model.safetensors.index.json")
    if os.path.exists(idx_path):
        with open(idx_path) as f:
            return json.load(f)["weight_map"]
    single = os.path.join(ckpt_dir, "model.safetensors")
    if not os.path.exists(single):
        raise FileNotFoundError(f"no safetensors checkpoint under {ckpt_dir}")
    from safetensors import safe_open

    with safe_open(single, framework="numpy") as f:
        return {name: "model.safetensors" for name in f.keys()}


class _ShardReader:
    """Lazily-opened safetensors shards with a tensor-name index."""

    def __init__(self, ckpt_dir: str):
        self.dir = ckpt_dir
        self.weight_map = _index(ckpt_dir)
        # Multimodal wrappers (Qwen2-VL et al.): newer transformers nests
        # the decoder under model.language_model.* and the tower under
        # model.visual.*, while published checkpoints use model.* /
        # visual.*. Alias both spellings so every loader addresses either
        # layout; real names win on collision.
        self._alias: dict[str, str] = {}
        for name in list(self.weight_map):
            if name.startswith("model.language_model."):
                short = "model." + name[len("model.language_model."):]
            elif name.startswith("model.visual."):
                short = name[len("model."):]
            else:
                continue
            if short not in self.weight_map:
                self._alias[short] = name
                self.weight_map[short] = self.weight_map[name]
        self._open: dict[str, Any] = {}

    def __contains__(self, name: str) -> bool:
        return name in self.weight_map

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        fname = self.weight_map[name]
        if fname not in self._open:
            self._open[fname] = safe_open(os.path.join(self.dir, fname), framework="numpy")
        return self._open[fname].get_tensor(self._alias.get(name, name))


def sharded_put(cfg: ArchConfig, mesh) -> Callable[[str, np.ndarray], jnp.ndarray]:
    """A `put` callback for load_hf_checkpoint that places each stacked
    tensor DIRECTLY with its NamedSharding from parallel/sharding.param_specs
    (ISSUE 7): jax.device_put from a host array with a sharding ships each
    device exactly its shard, so a tp-sharded checkpoint never materializes
    a full replicated copy in any chip's HBM — the point where an 8B-in-bf16
    load on a v5e-8 stops needing a whole chip's worth of slack.

    Loader paths look like "embed", "final_norm", "lm_head", "layers/<name>"
    (and "layers/<name>@<lo>" for DeepSeek's split stacks, whose dense-prefix
    MLP specs differ from the MoE stack's — disambiguated by rank). Tensors
    without a spec (or whose spec rank mismatches) place replicated."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from localai_tpu.parallel.sharding import param_specs

    specs = param_specs(cfg)
    dt = jnp.dtype(cfg.dtype)

    def lookup(path: str, ndim: int):
        name = path.split("@")[0]
        parts = name.split("/")
        cands = []
        if len(parts) == 2 and parts[0] == "layers":
            for stack in ("layers", "dense_layers"):
                spec = specs.get(stack, {}).get(parts[1])
                if spec is not None:
                    cands.append(spec)
        else:
            spec = specs.get(parts[0])
            if spec is not None:
                cands.append(spec)
        for spec in cands:
            if len(tuple(spec)) <= ndim:
                return spec
        return None

    multiprocess = jax.process_count() > 1

    def put(path: str, arr: np.ndarray) -> jnp.ndarray:
        host = np.asarray(arr)
        if host.dtype != dt and np.issubdtype(host.dtype, np.floating):
            host = host.astype(dt)
        spec = lookup(path, host.ndim)
        if spec is None:
            spec = P()
        sharding = NamedSharding(mesh, spec)
        if multiprocess:
            # Multi-host serving (ISSUE 13): the mesh spans processes, so
            # device_put of a host array would touch non-addressable
            # devices. make_array_from_callback materializes ONLY this
            # process's shards of the global array — every host reads the
            # checkpoint but ships its own slice, which is exactly the
            # per-process shard-load the dp-across-hosts plan needs.
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        return jax.device_put(host, sharding)

    return put


def load_hf_checkpoint(
    cfg: ArchConfig,
    ckpt_dir: str,
    put: Callable[[str, np.ndarray], jnp.ndarray] | None = None,
    quantize: str = "",
    lora: list[tuple[str, float]] | None = None,
) -> Params:
    """Load an HF-format Llama-family checkpoint into the stacked param tree.

    `put(path, np_array) -> device array` lets the caller place each tensor
    with its target sharding as it is read (engine passes a mesh-aware
    device_put); default is plain jnp.asarray in cfg.dtype.

    `quantize="int8"` quantizes the matmul weights ON THE HOST as they are
    read (models/quant.py layout) — the bf16 tree never materializes on
    device, so checkpoints up to ~2x HBM serve from one chip.

    `lora=[(adapter_dir, weight), ...]` merges PEFT adapters into each
    stacked tensor ON THE HOST before placement/quantization — LoRA and the
    int8/int4 HBM envelope compose (merge first, then quantize, one pass).
    """
    if cfg.recurrent_kind == "swa":
        # The config keys are read (`_arch_from_laguna`); the tensors have no
        # name list here yet, and a guessed one would load a wrong model.
        raise ValueError(
            f"{cfg.name}: a `laguna` checkpoint's tensors are not loaded yet "
            "(no tensor-name list is in this repository: the per-kind "
            "q_proj / o_proj / gate shapes, the router's bias); serve the "
            "preset with synthetic weights")
    if cfg.recurrent_kind == "s6":
        # As for `laguna`: the config keys are read (`_arch_from_jamba`), the
        # tensors wait for a name list.
        raise ValueError(
            f"{cfg.name}: a `jamba` checkpoint's tensors are not loaded yet "
            "(no tensor-name list is in this repository: the mixer's "
            "in_proj / x_proj / dt_proj, its three inner norms, A_log [E, N] "
            "to be transposed); serve the preset with synthetic weights")
    dt = jnp.dtype(cfg.dtype)
    reader = _ShardReader(ckpt_dir)
    if put is None:
        put = lambda path, arr: jnp.asarray(arr, dt)
    if quantize not in ("", "none", None, "int8", "int4"):
        raise ValueError(f"unsupported quantization mode {quantize!r}")
    do_quant = quantize in ("int8", "int4")
    lora_deltas: dict[str, dict[int, np.ndarray]] = {}
    for adir, w in lora or []:
        for our, per_layer in load_lora_deltas(adir, w, cfg).items():
            tgt = lora_deltas.setdefault(our, {})
            for li, d in per_layer.items():
                layer_i = li[0] if isinstance(li, tuple) else li
                if layer_i >= cfg.num_layers:
                    raise ValueError(
                        f"lora delta for {our!r} targets layer {layer_i}, "
                        f"model has {cfg.num_layers}"
                    )
                tgt[li] = tgt[li] + d if li in tgt else d

    def merge_lora(our: str, stacked: np.ndarray) -> np.ndarray:
        # Per-layer f32 add — never a full-model-shaped f32 buffer.
        # Index is the layer int, or (layer, expert) for MoE projections.
        for li, d in lora_deltas.get(our, {}).items():
            _check_lora_index(our, li, stacked.shape)
            if d.shape != stacked[li].shape:
                raise ValueError(
                    f"lora delta for {our!r} index {li} has shape {d.shape}, "
                    f"model expects {stacked[li].shape}"
                )
            stacked[li] = (stacked[li].astype(np.float32) + d).astype(stacked.dtype)
        return stacked

    def place(path: str, arr: np.ndarray, can_quant: bool, qaxis: int = -2):
        if do_quant and can_quant:
            from localai_tpu.models.quant import (
                quantize_tensor_np,
                quantize_tensor_np_g4,
            )

            # lm_head (qaxis=-1) always goes per-channel int8 — the unembed
            # path's form; int4 applies to the grouped matmul weights.
            if quantize == "int4" and qaxis == -2:
                qt = quantize_tensor_np_g4(arr)
            else:
                qt = quantize_tensor_np(arr, qaxis)
            # payload stays int, scales stay f32 — never `put`'s cast.
            return {k: jnp.asarray(v) for k, v in qt.items()}
        return put(path, arr)

    _QUANT_KEYS = {"wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down"}

    # Phi-3 fuses qkv and gate/up into single tensors; serve the per-head
    # names by row-block slicing so the rest of the loader stays uniform.
    H, Kh, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    F = cfg.intermediate_size
    _FUSED = {
        "self_attn.q_proj.weight": ("self_attn.qkv_proj.weight",
                                    [H * Hd, Kh * Hd, Kh * Hd], 0),
        "self_attn.k_proj.weight": ("self_attn.qkv_proj.weight",
                                    [H * Hd, Kh * Hd, Kh * Hd], 1),
        "self_attn.v_proj.weight": ("self_attn.qkv_proj.weight",
                                    [H * Hd, Kh * Hd, Kh * Hd], 2),
        "mlp.gate_proj.weight": ("mlp.gate_up_proj.weight", [F, F], 0),
        "mlp.up_proj.weight": ("mlp.gate_up_proj.weight", [F, F], 1),
    }

    def _fused_source(name: str):
        for suf, (fused_suf, sizes, idx) in _FUSED.items():
            if name.endswith(suf):
                fused = name[: -len(suf)] + fused_suf
                if fused in reader:
                    return fused, sizes, idx
        return None

    def has_tensor(name: str) -> bool:
        return name in reader or _fused_source(name) is not None

    _fused_slices: dict[str, np.ndarray] = {}

    def read_tensor(name: str) -> np.ndarray:
        if name in reader:
            return reader.get(name)
        hit = _fused_slices.pop(name, None)
        if hit is not None:
            return hit
        src = _fused_source(name)
        if src is None:
            raise KeyError(name)
        fused, sizes, idx = src
        # The loader walks key-major (all layers' q, then all k, ...), so a
        # fused tensor's sibling slices are wanted much later — split once
        # and stash the siblings under their virtual names (they would be
        # materialized in the tree anyway) instead of re-reading the fused
        # tensor once per slice.
        arr = reader.get(fused)
        offs = np.cumsum([0] + sizes)
        want = None
        for suf, (fsuf, _sizes, fidx) in _FUSED.items():
            if not fused.endswith(fsuf):
                continue
            part = arr[offs[fidx]: offs[fidx + 1]]
            if fidx == idx:
                want = part
            else:
                _fused_slices[fused[: -len(fsuf)] + suf] = part
        return want

    def grab(name: str, transpose: bool) -> np.ndarray:
        arr = read_tensor(name)
        if transpose and arr.ndim == 2:
            arr = arr.T
        if cfg.norm_plus_one and name.endswith("norm.weight"):
            # Gemma stores RMSNorm weights as w with (1+w) applied at run
            # time; fold the +1 here so ops/norm.py stays family-agnostic.
            arr = (arr.astype(np.float32) + 1.0).astype(arr.dtype)
        return np.ascontiguousarray(arr)

    def stack_layers(our: str, hf_suffix: str, transpose: bool) -> np.ndarray:
        rows = [
            grab(f"model.layers.{i}.{hf_suffix}", transpose) for i in range(cfg.num_layers)
        ]
        return np.stack(rows)

    if cfg.is_mla:
        if lora:
            raise ValueError(
                "LoRA merge into DeepSeek checkpoints is not supported yet"
            )
        return _load_deepseek(cfg, grab, place, put, reader)

    layers: Params = {}
    layer_map = dict(_LAYER_MAP)
    if cfg.is_moe:
        for k in ("w_gate", "w_up", "w_down"):
            layer_map.pop(k)
    if cfg.post_norms:
        # Gemma-2 sandwich norms: our mlp_norm is the PRE-feedforward norm
        # (post_attention_layernorm plays a different role there).
        layer_map["mlp_norm"] = ("pre_feedforward_layernorm.weight", False)
        layer_map["post_attn_norm"] = ("post_attention_layernorm.weight", False)
        layer_map["post_ffw_norm"] = ("post_feedforward_layernorm.weight", False)
    if cfg.qk_norm or cfg.qk_norm_full:
        # Gemma-3 per-head q/k norms ((1+w) fold applies — they end in
        # "norm.weight"); OLMoE's span the whole projection, same names.
        layer_map["q_norm"] = ("self_attn.q_norm.weight", False)
        layer_map["k_norm"] = ("self_attn.k_norm.weight", False)
    for our, (suffix, transpose) in layer_map.items():
        probe = f"model.layers.0.{suffix}"
        if not has_tensor(probe):
            continue  # optional tensors (qkv bias)
        layers[our] = place(
            f"layers/{our}", merge_lora(our, stack_layers(our, suffix, transpose)),
            can_quant=our in _QUANT_KEYS,
        )

    if cfg.is_moe:
        moe_map = _moe_layer_map(cfg)
        layers["router"] = put(
            "layers/router", stack_layers("router", moe_map["router"][0], True)
        )
        for our in ("w_gate", "w_up", "w_down"):
            suffix, transpose = moe_map[our]
            per_layer = []
            for i in range(cfg.num_layers):
                experts = [
                    grab(f"model.layers.{i}.{suffix.format(e=e)}", transpose)
                    for e in range(cfg.num_experts)
                ]
                per_layer.append(np.stack(experts))
            layers[our] = place(
                f"layers/{our}", merge_lora(our, np.stack(per_layer)), can_quant=True
            )

    params: Params = {
        "embed": put("embed", grab("model.embed_tokens.weight", False)),
        "layers": layers,
        "final_norm": put("final_norm", grab("model.norm.weight", False)),
    }
    if not cfg.tie_embeddings:
        name = "lm_head.weight"
        if name in reader:
            params["lm_head"] = place(
                "lm_head", grab(name, False), can_quant=True, qaxis=-1
            )
        else:  # some checkpoints tie without declaring it
            params["lm_head"] = params["embed"]
    return params


def _deinterleave(arr: np.ndarray, rot: int, block: int) -> np.ndarray:
    """De-interleave rope columns of a [in, out] weight whose output axis is
    per-head blocks of `block` cols with the LAST `rot` cols rotary. HF
    deepseek applies complex/interleaved rope (pairs (2i, 2i+1)); permuting
    those columns to half-split order here makes the runtime's single neox
    rope implementation exact (the inverse of DeepseekV3's
    apply_rotary_pos_emb_interleave view-transpose)."""
    out = arr.reshape(arr.shape[0], -1, block).copy()
    rope = out[..., block - rot:]
    out[..., block - rot:] = np.concatenate([rope[..., 0::2], rope[..., 1::2]], -1)
    return out.reshape(arr.shape[0], -1)


def _interleave(arr: np.ndarray, rot: int, block: int) -> np.ndarray:
    """Inverse of _deinterleave: back to HF pair-interleaved rope columns
    (deepseek_v2 exports — the V2 modeling code applies complex rope
    unconditionally, so V2 checkpoints MUST ship interleaved)."""
    out = arr.reshape(arr.shape[0], -1, block).copy()
    rope = out[..., block - rot:]
    half = rot // 2
    inter = np.empty_like(rope)
    inter[..., 0::2] = rope[..., :half]
    inter[..., 1::2] = rope[..., half:]
    out[..., block - rot:] = inter
    return out.reshape(arr.shape[0], -1)


def _load_deepseek(cfg: ArchConfig, grab, place, put, reader) -> Params:
    """DeepSeek-V2/V3 checkpoint → the two-stack MLA/MoE param tree.

    HF layout (transformers modeling_deepseek_v3.py): q through an optional
    lora bottleneck (q_a/q_b) or direct q_proj; kv_a_proj_with_mqa emits the
    [kv_lora_rank | k_pe] latent; kv_b_proj [H·(nope+v), r] splits per head
    into w_kb/w_vb (kept in HF [out, in] orientation — the absorbed einsums
    contract the shared r axis); mlp.gate(.e_score_correction_bias) routes
    mlp.experts.N.* with always-on mlp.shared_experts.*; the first
    first_k_dense layers carry a plain mlp. Reference serves this family via
    vLLM passthrough (backend/python/vllm/backend.py:92-141)."""
    H = cfg.num_heads
    n, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    kd = cfg.first_k_dense if cfg.is_moe else 0
    L = cfg.num_layers

    def stack(suffix: str, lo: int, hi: int, transpose: bool,
              rope_block: int = 0) -> np.ndarray:
        rows = []
        for i in range(lo, hi):
            a = grab(f"model.layers.{i}.{suffix}", transpose)
            if rope_block and cfg.rope_interleave:
                a = _deinterleave(a, rot, rope_block)
            rows.append(a)
        return np.stack(rows)

    def attn_stack(lo: int, hi: int) -> Params:
        out: Params = {
            "attn_norm": stack("input_layernorm.weight", lo, hi, False),
            "mlp_norm": stack("post_attention_layernorm.weight", lo, hi, False),
            "kv_norm": stack("self_attn.kv_a_layernorm.weight", lo, hi, False),
            "wo": place(f"layers/wo@{lo}", stack("self_attn.o_proj.weight", lo, hi, True), True),
        }
        if cfg.q_lora_rank:
            out["wq_a"] = place(
                f"layers/wq_a@{lo}", stack("self_attn.q_a_proj.weight", lo, hi, True), True
            )
            out["q_norm_a"] = put(
                f"layers/q_norm_a@{lo}",
                stack("self_attn.q_a_layernorm.weight", lo, hi, False),
            )
            out["wq_b"] = place(
                f"layers/wq_b@{lo}",
                stack("self_attn.q_b_proj.weight", lo, hi, True, rope_block=n + rot),
                True,
            )
        else:
            out["wq"] = place(
                f"layers/wq@{lo}",
                stack("self_attn.q_proj.weight", lo, hi, True, rope_block=n + rot),
                True,
            )
        out["wkv_a"] = place(
            f"layers/wkv_a@{lo}",
            stack("self_attn.kv_a_proj_with_mqa.weight", lo, hi, True,
                  rope_block=r + rot),
            True,
        )
        out["attn_norm"] = put(f"layers/attn_norm@{lo}", out["attn_norm"])
        out["mlp_norm"] = put(f"layers/mlp_norm@{lo}", out["mlp_norm"])
        out["kv_norm"] = put(f"layers/kv_norm@{lo}", out["kv_norm"])
        # kv_b_proj [H·(n+v), r] → per-head k/v up-projections (never
        # quantized: they ride einsum paths with no grouped-int kernel).
        kbs, vbs = [], []
        for i in range(lo, hi):
            kb = grab(f"model.layers.{i}.self_attn.kv_b_proj.weight", False)
            kb = kb.reshape(H, n + vd, r)
            kbs.append(kb[:, :n])
            vbs.append(kb[:, n:])
        out["w_kb"] = put(f"layers/w_kb@{lo}", np.stack(kbs))
        out["w_vb"] = put(f"layers/w_vb@{lo}", np.stack(vbs))
        return out

    # wkv_a's rope permute operates on the whole [D, r+rot] output (one
    # pseudo-head of block r+rot with the last rot cols rotary) — matches
    # rope_block=r + rot above. wq(_b) blocks are per head (n+rot).
    layers = attn_stack(kd, L)
    if cfg.is_moe:
        E = cfg.num_experts
        layers["router"] = put(
            "layers/router", stack("mlp.gate.weight", kd, L, True)
        )
        probe = f"model.layers.{kd}.mlp.gate.e_score_correction_bias"
        if probe in reader:
            layers["router_bias"] = jnp.asarray(
                stack("mlp.gate.e_score_correction_bias", kd, L, False),
                jnp.float32,
            )
        for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                            ("w_down", "down_proj")):
            per_layer = []
            for i in range(kd, L):
                experts = [
                    grab(f"model.layers.{i}.mlp.experts.{e}.{suffix}.weight", True)
                    for e in range(E)
                ]
                per_layer.append(np.stack(experts))
            layers[our] = place(f"layers/{our}", np.stack(per_layer), True)
        if cfg.n_shared_experts:
            for our, suffix in (("shared_gate", "gate_proj"),
                                ("shared_up", "up_proj"),
                                ("shared_down", "down_proj")):
                layers[our] = place(
                    f"layers/{our}",
                    stack(f"mlp.shared_experts.{suffix}.weight", kd, L, True),
                    True,
                )
    else:
        for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                            ("w_down", "down_proj")):
            layers[our] = place(
                f"layers/{our}", stack(f"mlp.{suffix}.weight", 0, L, True), True
            )

    params: Params = {
        "embed": put("embed", grab("model.embed_tokens.weight", False)),
        "layers": layers,
        "final_norm": put("final_norm", grab("model.norm.weight", False)),
    }
    if kd:
        dense = attn_stack(0, kd)
        for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                            ("w_down", "down_proj")):
            dense[our] = place(
                f"dense_layers/{our}", stack(f"mlp.{suffix}.weight", 0, kd, True), True
            )
        params["dense_layers"] = dense
    if not cfg.tie_embeddings:
        if "lm_head.weight" in reader:
            params["lm_head"] = place(
                "lm_head", grab("lm_head.weight", False), True, qaxis=-1
            )
        else:
            params["lm_head"] = params["embed"]
    return params


# PEFT target-module suffix -> our stacked layer key.
_LORA_TARGETS = {
    "self_attn.q_proj": "wq",
    "self_attn.k_proj": "wk",
    "self_attn.v_proj": "wv",
    "self_attn.o_proj": "wo",
    "mlp.gate_proj": "w_gate",
    "mlp.up_proj": "w_up",
    "mlp.down_proj": "w_down",
    # short names PEFT configs commonly use
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
    "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
}


# PEFT fused-module targets (phi-3 layout): delta columns split into the same
# row blocks _FUSED uses at checkpoint load, so adapters trained against the
# fused projections land on the per-head tensors we actually serve.
_LORA_FUSED = {
    "qkv_proj": ("wq", "wk", "wv"),
    "gate_up_proj": ("w_gate", "w_up"),
}
# Mixtral-style per-expert projections: w1/w3/w2 -> (key, expert) slices of
# the stacked [L, E, in, out] expert tensors.
_LORA_EXPERT = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}
# Targets that genuinely have no served matmul (skip quietly, not an error).
_LORA_IGNORED = ("embed_tokens", "lm_head", "norm")


def _check_lora_index(our: str, idx: Any, shape: tuple) -> None:
    """Every leading index (layer, and expert for MoE keys) must be in
    range — jnp's clamped gather would otherwise merge a mis-indexed delta
    into the wrong expert silently."""
    parts = idx if isinstance(idx, tuple) else (idx,)
    for ax, j in enumerate(parts):
        if not 0 <= j < shape[ax]:
            raise ValueError(
                f"lora delta for {our!r} index {idx} is out of range for "
                f"model shape {shape}"
            )


def load_lora_deltas(
    adapter_dir: str, weight: float = 1.0, cfg: ArchConfig | None = None
) -> dict[str, dict[Any, np.ndarray]]:
    """Read a PEFT-format adapter into per-key per-layer f32 weight deltas.

    Returns {our_key: {index: [in, out] f32 delta}} where each delta is
    weight · (alpha/r) · (B@A)^T (PEFT stores A [r, in], B [out, r]; our
    weights are [in, out]). `index` is the layer int for dense keys, or a
    (layer, expert) tuple for MoE expert projections. Reads
    `adapter_config.json` + `adapter_model.safetensors` (names like
    `base_model.model.model.layers.N.self_attn.q_proj.lora_A.weight`).

    Fused phi-3 targets (`qkv_proj`, `gate_up_proj`) are split into the
    per-head deltas by the same row blocks the checkpoint loader's _FUSED
    table uses — `cfg` is required for the qkv split (head sizes). Adapters
    whose targets include no served matmul raise instead of silently
    applying nothing (the server must not claim "merged" for a no-op).
    Only the small rank-r factors and one [in, out] delta per targeted
    (key, layer) ever materialize.
    """
    import re

    from safetensors import safe_open

    with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
        acfg = json.load(f)
    r = int(acfg.get("r", 8))
    alpha = float(acfg.get("lora_alpha", r))
    scale = weight * alpha / max(r, 1)

    path = os.path.join(adapter_dir, "adapter_model.safetensors")
    tensors: dict[str, np.ndarray] = {}
    with safe_open(path, framework="numpy") as f:
        for name in f.keys():
            tensors[name] = np.asarray(f.get_tensor(name), np.float32)

    pat = re.compile(r"layers\.(\d+)\.(.+)\.lora_A\.weight$")
    expert_pat = re.compile(r"experts\.(\d+)\.(w[123])$")
    per_key: dict[str, dict[Any, np.ndarray]] = {}
    unmatched: list[str] = []

    def add(our: str, idx: Any, delta: np.ndarray) -> None:
        tgt = per_key.setdefault(our, {})
        tgt[idx] = tgt[idx] + delta if idx in tgt else delta

    ignored: list[str] = []
    for name, a in tensors.items():
        if not name.endswith("lora_A.weight"):
            continue
        m = pat.search(name)
        if m is None:
            # Non-layer targets (embed_tokens / lm_head / final norm) have
            # no served per-layer matmul — recognized but skipped.
            if any(tag in name for tag in _LORA_IGNORED):
                ignored.append(name)
            else:
                unmatched.append(name)
            continue
        layer, module = int(m.group(1)), m.group(2)
        b = tensors.get(name[: -len("lora_A.weight")] + "lora_B.weight")
        if b is None:
            unmatched.append(f"{module} (no lora_B)")
            continue
        short = module.split(".")[-1]
        our = _LORA_TARGETS.get(module) or _LORA_TARGETS.get(short)
        if our is not None:
            add(our, layer, (b @ a).T * scale)
            continue
        em = expert_pat.search(module)
        if em is not None:
            add(_LORA_EXPERT[em.group(2)], (layer, int(em.group(1))),
                (b @ a).T * scale)
            continue
        if short in _LORA_FUSED:
            delta = (b @ a).T * scale  # [in, out_total]
            if short == "qkv_proj":
                if cfg is None:
                    raise ValueError(
                        f"adapter {adapter_dir!r} targets fused {short!r}; "
                        "splitting it needs the model's head sizes (cfg)"
                    )
                sizes = [cfg.num_heads * cfg.head_dim_,
                         cfg.num_kv_heads * cfg.head_dim_,
                         cfg.num_kv_heads * cfg.head_dim_]
            else:  # gate_up_proj: two equal halves
                sizes = [delta.shape[1] // 2] * 2
            if delta.shape[1] != sum(sizes):
                raise ValueError(
                    f"lora delta for fused {short!r} layer {layer} has "
                    f"{delta.shape[1]} output cols, expected {sum(sizes)}"
                )
            off = 0
            for part_key, size in zip(_LORA_FUSED[short], sizes):
                add(part_key, layer, delta[:, off: off + size])
                off += size
            continue
        if any(tag in module for tag in _LORA_IGNORED):
            ignored.append(module)  # per-layer norms are not served matmuls
            continue
        unmatched.append(module)

    if unmatched:
        log.warning(
            "lora adapter %s: unrecognized target modules skipped: %s",
            adapter_dir, sorted(set(unmatched)),
        )
    if not per_key:
        detail = []
        if unmatched:
            detail.append(f"unrecognized targets: {sorted(set(unmatched))}")
        if ignored:
            detail.append(
                f"targets with no served matmul (embed/lm_head/norm): "
                f"{sorted(set(ignored))}"
            )
        raise ValueError(
            f"lora adapter {adapter_dir!r} matched no served weight — "
            + ("; ".join(detail) or "no lora_A tensors found")
        )
    return per_key


def lora_target_dims(cfg: ArchConfig) -> dict[str, tuple[int, int]]:
    """(in, out) of every runtime-servable LoRA target projection, derived
    from the architecture (the engine's param leaves may be quantized dicts
    whose shapes no longer spell the matmul dims)."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    H = cfg.num_heads * cfg.head_dim_
    K = cfg.num_kv_heads * cfg.head_dim_
    return {
        "wq": (D, H), "wk": (D, K), "wv": (D, K), "wo": (H, D),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
    }


def load_lora_factors(
    adapter_dir: str, weight: float = 1.0, cfg: ArchConfig | None = None
) -> tuple[int, dict[str, dict[int, tuple[np.ndarray, np.ndarray]]]]:
    """Read a PEFT-format adapter into UNMERGED per-layer rank factors for
    runtime multi-tenant serving (ISSUE 10, docs/LORA_SERVING.md).

    Returns (rank, {our_key: {layer: (A [in, r] f32, B [r, out] f32)}})
    with weight·(alpha/r) folded into B, so the served delta is exactly the
    B·(A·x) the merge path would have added — byte-layout aside, the same
    math as load_lora_deltas, kept factorized. Fused phi-3 targets
    (`qkv_proj`, `gate_up_proj`) split by B's output columns (A is shared).
    MoE expert targets are rejected — the runtime path serves the dense
    llama-family projections only; merge those at load instead."""
    import re

    from safetensors import safe_open

    with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
        acfg = json.load(f)
    r_cfg = int(acfg.get("r", 8))
    alpha = float(acfg.get("lora_alpha", r_cfg))
    scale = weight * alpha / max(r_cfg, 1)

    path = os.path.join(adapter_dir, "adapter_model.safetensors")
    tensors: dict[str, np.ndarray] = {}
    with safe_open(path, framework="numpy") as f:
        for name in f.keys():
            tensors[name] = np.asarray(f.get_tensor(name), np.float32)

    pat = re.compile(r"layers\.(\d+)\.(.+)\.lora_A\.weight$")
    expert_pat = re.compile(r"experts\.(\d+)\.(w[123])$")
    per_key: dict[str, dict[int, tuple[np.ndarray, np.ndarray]]] = {}
    rank = 0

    def add(our: str, layer: int, a_t: np.ndarray, b_t: np.ndarray) -> None:
        # A [in, r] (PEFT stores [r, in]); B [r, out] with the scale folded.
        nonlocal rank
        tgt = per_key.setdefault(our, {})
        if layer in tgt:
            raise ValueError(
                f"lora adapter {adapter_dir!r}: duplicate runtime target "
                f"{our!r} layer {layer}"
            )
        tgt[layer] = (np.ascontiguousarray(a_t), np.ascontiguousarray(b_t))
        rank = max(rank, a_t.shape[1])

    unmatched: list[str] = []
    for name, a in tensors.items():
        if not name.endswith("lora_A.weight"):
            continue
        m = pat.search(name)
        if m is None:
            if not any(tag in name for tag in _LORA_IGNORED):
                unmatched.append(name)
            continue
        layer, module = int(m.group(1)), m.group(2)
        b = tensors.get(name[: -len("lora_A.weight")] + "lora_B.weight")
        if b is None:
            unmatched.append(f"{module} (no lora_B)")
            continue
        short = module.split(".")[-1]
        if expert_pat.search(module) is not None:
            raise ValueError(
                f"lora adapter {adapter_dir!r} targets MoE expert "
                f"projections ({module!r}) — the runtime multi-tenant path "
                "serves dense llama-family targets only; merge at load via "
                "`lora_adapters` instead"
            )
        our = _LORA_TARGETS.get(module) or _LORA_TARGETS.get(short)
        if our is not None:
            add(our, layer, a.T, b.T * scale)
            continue
        if short in _LORA_FUSED:
            if short == "qkv_proj" and cfg is None:
                raise ValueError(
                    f"adapter {adapter_dir!r} targets fused {short!r}; "
                    "splitting it needs the model's head sizes (cfg)"
                )
            bt = b.T * scale  # [r, out_total]
            if short == "qkv_proj":
                sizes = [cfg.num_heads * cfg.head_dim_,
                         cfg.num_kv_heads * cfg.head_dim_,
                         cfg.num_kv_heads * cfg.head_dim_]
            else:
                sizes = [bt.shape[1] // 2] * 2
            if bt.shape[1] != sum(sizes):
                raise ValueError(
                    f"lora delta for fused {short!r} layer {layer} has "
                    f"{bt.shape[1]} output cols, expected {sum(sizes)}"
                )
            off = 0
            for part_key, size in zip(_LORA_FUSED[short], sizes):
                add(part_key, layer, a.T, bt[:, off: off + size])
                off += size
            continue
        if not any(tag in module for tag in _LORA_IGNORED):
            unmatched.append(module)

    if unmatched:
        log.warning(
            "lora adapter %s: unrecognized target modules skipped: %s",
            adapter_dir, sorted(set(unmatched)),
        )
    if not per_key:
        raise ValueError(
            f"lora adapter {adapter_dir!r} matched no served weight — "
            "no runtime-servable lora_A/lora_B pairs found"
        )
    if cfg is not None:
        dims = lora_target_dims(cfg)
        for our, layers_d in per_key.items():
            d_in, d_out = dims[our]
            for li, (a_t, b_t) in layers_d.items():
                if li >= cfg.num_layers:
                    raise ValueError(
                        f"lora factors for {our!r} target layer {li}, "
                        f"model has {cfg.num_layers}"
                    )
                if a_t.shape[0] != d_in or b_t.shape[1] != d_out:
                    raise ValueError(
                        f"lora factors for {our!r} layer {li} map "
                        f"{a_t.shape[0]}->{b_t.shape[1]}, model expects "
                        f"{d_in}->{d_out}"
                    )
    return rank, per_key


def apply_lora(
    cfg: ArchConfig, params: Params, adapter_dir: str, weight: float = 1.0
) -> Params:
    """Merge a PEFT-format LoRA adapter into the stacked param tree.

    W += weight · (alpha/r) · B@A per targeted module, exactly what the
    reference does at load time (grpc-server.cpp params_parse lora adapters;
    backend.proto LoraAdapter/LoraScale). Quantized trees are rejected —
    merge before quantizing (`load_hf_checkpoint(lora=...)` does both in one
    host pass). Updates are per-layer `at[].add`s, so no full-model-shaped
    f32 buffer ever materializes. Returns the updated tree.
    """
    per_key = load_lora_deltas(adapter_dir, weight, cfg)
    layers = dict(params["layers"])
    for our, deltas in per_key.items():
        leaf = layers.get(our)
        if leaf is None:
            raise KeyError(f"lora targets {our!r} absent from the model tree")
        if isinstance(leaf, dict):
            raise ValueError(
                "cannot merge a LoRA adapter into quantized weights — either "
                "load the checkpoint unquantized and quantize after merging "
                "(load_hf_checkpoint(lora=...)), or serve the adapter "
                "UNMERGED through the runtime path (a virtual model with "
                "`base_model` + `adapter`, docs/LORA_SERVING.md), which DOES "
                "compose with a quantized base: the delta runs bf16 beside "
                "the int8/int4 matmul"
            )
        for idx, delta in deltas.items():
            _check_lora_index(our, idx, leaf.shape)
            if delta.shape != leaf[idx].shape:
                raise ValueError(
                    f"lora delta for {our!r} index {idx} has shape "
                    f"{delta.shape}, model expects {leaf[idx].shape}"
                )
            leaf = leaf.at[idx].add(jnp.asarray(delta, leaf.dtype))
        layers[our] = leaf
    out = dict(params)
    out["layers"] = layers
    return out


def save_hf_checkpoint(cfg: ArchConfig, params: Params, ckpt_dir: str) -> None:
    """Write a stacked param tree as an HF-format safetensors checkpoint.

    Inverse of `load_hf_checkpoint` (same name/transpose maps) plus a
    matching `config.json`, so converted or trained weights round-trip into
    anything that reads HF checkpoints — and so tests can fabricate real
    on-disk checkpoints. Reference analogue: the transformers backend's
    save-side is torch's save_pretrained (backend/python/transformers)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    tensors: dict[str, np.ndarray] = {}

    def emit(name: str, arr: Any, transpose: bool) -> None:
        a = np.asarray(jnp.asarray(arr, jnp.float32))
        if transpose and a.ndim == 2:
            a = a.T
        if cfg.norm_plus_one and name.endswith("norm.weight"):
            a = a - 1.0  # inverse of the load-time (1+w) fold — gemma layout
        tensors[name] = np.ascontiguousarray(a)

    if cfg.is_mla:
        _save_deepseek(cfg, params, ckpt_dir, tensors, emit)
        return

    layers = params["layers"]
    layer_map = dict(_LAYER_MAP)
    if cfg.is_moe:
        for k in ("w_gate", "w_up", "w_down"):
            layer_map.pop(k)
    if cfg.post_norms:
        layer_map["mlp_norm"] = ("pre_feedforward_layernorm.weight", False)
        layer_map["post_attn_norm"] = ("post_attention_layernorm.weight", False)
        layer_map["post_ffw_norm"] = ("post_feedforward_layernorm.weight", False)
    if cfg.qk_norm or cfg.qk_norm_full:
        layer_map["q_norm"] = ("self_attn.q_norm.weight", False)
        layer_map["k_norm"] = ("self_attn.k_norm.weight", False)
    for our, (suffix, transpose) in layer_map.items():
        if our not in layers:
            continue
        for i in range(cfg.num_layers):
            emit(f"model.layers.{i}.{suffix}", layers[our][i], transpose)
    if cfg.is_moe:
        moe_map = _moe_layer_map(cfg)
        for i in range(cfg.num_layers):
            emit(f"model.layers.{i}.{moe_map['router'][0]}", layers["router"][i], True)
            for our in ("w_gate", "w_up", "w_down"):
                suffix, transpose = moe_map[our]
                for e in range(cfg.num_experts):
                    emit(f"model.layers.{i}.{suffix.format(e=e)}", layers[our][i, e], transpose)

    emit("model.embed_tokens.weight", params["embed"], False)
    emit("model.norm.weight", params["final_norm"], False)
    if not cfg.tie_embeddings and "lm_head" in params:
        emit("lm_head.weight", params["lm_head"], False)

    from safetensors.numpy import save_file

    save_file(tensors, os.path.join(ckpt_dir, "model.safetensors"))

    olmoe = cfg.is_moe and cfg.moe_family == "deepseek"
    if olmoe:
        model_type = "olmoe"
    elif cfg.is_moe:
        model_type = "mixtral"
    elif cfg.post_norms:
        model_type = "gemma2"
    elif cfg.embed_scale or cfg.norm_plus_one:
        model_type = "gemma"
    elif cfg.attn_qkv_bias:
        model_type = "qwen2"
    else:
        model_type = "llama"
    hf_config = {
        "model_type": model_type,
        "hidden_act": ("gelu_pytorch_tanh" if cfg.activation == "gelu_tanh"
                       else "silu"),
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_position,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
    if not cfg.attn_rope or cfg.attn_gate:
        hf_config["use_rope"] = cfg.attn_rope
        hf_config["use_gqa_gate"] = cfg.attn_gate
    if cfg.is_moe:
        hf_config["num_experts" if olmoe else "num_local_experts"] = cfg.num_experts
        hf_config["num_experts_per_tok"] = cfg.num_experts_per_token
    if olmoe:
        hf_config["norm_topk_prob"] = cfg.norm_topk_prob
        hf_config["clip_qkv"] = None
    if cfg.post_norms:
        hf_config["attn_logit_softcapping"] = cfg.attn_softcap or None
        hf_config["final_logit_softcapping"] = cfg.final_softcap or None
        hf_config["query_pre_attn_scalar"] = cfg.query_scale or cfg.head_dim_
        hf_config["sliding_window"] = cfg.sliding_window or None
    if cfg.rope_scaling:
        hf_config["rope_scaling"] = {
            "rope_type": cfg.rope_scaling,
            "factor": cfg.rope_scaling_factor,
            "low_freq_factor": cfg.rope_low_freq_factor,
            "high_freq_factor": cfg.rope_high_freq_factor,
            "original_max_position_embeddings": cfg.rope_original_max_position,
        }
    with open(os.path.join(ckpt_dir, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)


def _save_deepseek(cfg: ArchConfig, params: Params, ckpt_dir: str,
                   tensors: dict, emit) -> None:
    """Emit the two-stack deepseek tree as an HF deepseek_v2/v3 checkpoint
    (inverse of _load_deepseek). V3 exports keep our half-split rope
    columns and declare rope_interleave=false; V2 exports RE-interleave
    them, because the V2 modeling code (HF and vLLM) applies complex
    pair-interleaved rope unconditionally."""
    kd = cfg.first_k_dense if cfg.is_moe else 0
    v3 = cfg.scoring_func == "sigmoid"
    rot = cfg.qk_rope_head_dim

    def rope_cols(arr, block):
        a = np.asarray(jnp.asarray(arr, jnp.float32))  # [in, out]
        return _interleave(a, rot, block) if not v3 else a

    def emit_attn(stack: Params, lo: int) -> None:
        n = stack["attn_norm"].shape[0]
        for j in range(n):
            i = lo + j
            pre = f"model.layers.{i}."
            emit(pre + "input_layernorm.weight", stack["attn_norm"][j], False)
            emit(pre + "post_attention_layernorm.weight", stack["mlp_norm"][j], False)
            emit(pre + "self_attn.kv_a_layernorm.weight", stack["kv_norm"][j], False)
            emit(pre + "self_attn.o_proj.weight", stack["wo"][j], True)
            emit(pre + "self_attn.kv_a_proj_with_mqa.weight",
                 rope_cols(stack["wkv_a"][j], cfg.kv_lora_rank + rot), True)
            if cfg.q_lora_rank:
                emit(pre + "self_attn.q_a_proj.weight", stack["wq_a"][j], True)
                emit(pre + "self_attn.q_a_layernorm.weight", stack["q_norm_a"][j], False)
                emit(pre + "self_attn.q_b_proj.weight",
                     rope_cols(stack["wq_b"][j], cfg.qk_head_dim), True)
            else:
                emit(pre + "self_attn.q_proj.weight",
                     rope_cols(stack["wq"][j], cfg.qk_head_dim), True)
            kb = np.concatenate(
                [np.asarray(jnp.asarray(stack["w_kb"][j], jnp.float32)),
                 np.asarray(jnp.asarray(stack["w_vb"][j], jnp.float32))], axis=1
            )  # [H, n+v, r]
            tensors[f"{pre}self_attn.kv_b_proj.weight"] = np.ascontiguousarray(
                kb.reshape(-1, cfg.kv_lora_rank)
            )

    layers = params["layers"]
    emit_attn(layers, kd)
    if kd:
        dense = params["dense_layers"]
        emit_attn(dense, 0)
        for j in range(kd):
            for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                ("w_down", "down_proj")):
                emit(f"model.layers.{j}.mlp.{suffix}.weight", dense[our][j], True)
    if cfg.is_moe:
        for j in range(cfg.num_layers - kd):
            i = kd + j
            emit(f"model.layers.{i}.mlp.gate.weight", layers["router"][j], True)
            if "router_bias" in layers:
                emit(f"model.layers.{i}.mlp.gate.e_score_correction_bias",
                     layers["router_bias"][j], False)
            for e in range(cfg.num_experts):
                for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                    ("w_down", "down_proj")):
                    emit(f"model.layers.{i}.mlp.experts.{e}.{suffix}.weight",
                         layers[our][j, e], True)
            if cfg.n_shared_experts:
                for our, suffix in (("shared_gate", "gate_proj"),
                                    ("shared_up", "up_proj"),
                                    ("shared_down", "down_proj")):
                    emit(f"model.layers.{i}.mlp.shared_experts.{suffix}.weight",
                         layers[our][j], True)
    else:
        for j in range(cfg.num_layers):
            for our, suffix in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                ("w_down", "down_proj")):
                emit(f"model.layers.{j}.mlp.{suffix}.weight", layers[our][j], True)

    emit("model.embed_tokens.weight", params["embed"], False)
    emit("model.norm.weight", params["final_norm"], False)
    if not cfg.tie_embeddings and "lm_head" in params:
        emit("lm_head.weight", params["lm_head"], False)

    from safetensors.numpy import save_file

    save_file(tensors, os.path.join(ckpt_dir, "model.safetensors"))
    hf_config = {
        "model_type": "deepseek_v3" if v3 else "deepseek_v2",
        "hidden_act": "silu",
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "rope_theta": cfg.rope_theta,
        "max_position_embeddings": cfg.max_position,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "head_dim": cfg.qk_rope_head_dim,
        "rope_interleave": not v3,  # V3: half-split as stored; V2: re-interleaved
        "n_routed_experts": cfg.num_experts or None,
        "num_experts_per_tok": cfg.num_experts_per_token if cfg.is_moe else None,
        "first_k_dense_replace": cfg.first_k_dense,
        "n_shared_experts": cfg.n_shared_experts or None,
        "moe_intermediate_size": cfg.moe_inter_size,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "n_group": cfg.n_group,
        "topk_group": cfg.topk_group,
    }
    if not v3:
        hf_config["scoring_func"] = cfg.scoring_func
        hf_config["topk_method"] = (
            "group_limited_greedy" if cfg.n_group > 1 else "greedy"
        )
    if cfg.rope_scaling:
        hf_config["rope_scaling"] = {
            "rope_type": cfg.rope_scaling,
            "factor": cfg.rope_scaling_factor,
            "original_max_position_embeddings": cfg.rope_original_max_position,
            "beta_fast": cfg.rope_beta_fast,
            "beta_slow": cfg.rope_beta_slow,
            # rope_attn_factor already folds the deepseek mscale product
            # (see arch_from_hf_config); round-trips through the
            # attention_factor branch exactly.
            **({"attention_factor": cfg.rope_attn_factor}
               if cfg.rope_attn_factor is not None else {}),
        }
    with open(os.path.join(ckpt_dir, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)


def _arch_from_laguna(hf: dict) -> ArchConfig:
    """poolside's `laguna` config keys: `layer_types` in periods that begin
    with their `full_attention` layer, `num_attention_heads_per_layer` (one
    count a kind), `rope_parameters` by layer type, `mlp_layer_types` (a
    dense prefix, then sparse), `num_experts`, `moe_routed_scaling_factor`,
    `shared_expert_intermediate_size`, `gating`. What the keys name and do
    not define (the gate's operand, the router's scoring) is this
    repository's reading: benchmark/configs/laguna-xs.2-int8-ep8.json,
    `assumed`."""
    lt = list(hf["layer_types"])
    heads = list(hf["num_attention_heads_per_layer"])
    kinds = tuple({"full_attention": "gqa", "sliding_attention": "swa"}[t]
                  for t in lt)
    by_kind = {k: {h for h, kk in zip(heads, kinds) if kk == k}
               for k in ("gqa", "swa")}
    if any(len(v) != 1 for v in by_kind.values()):
        raise ValueError(f"laguna: one head count a layer type, got {by_kind}")
    mlp = list(hf["mlp_layer_types"])
    dense = mlp.index("sparse") if "sparse" in mlp else len(mlp)
    if any(t != "sparse" for t in mlp[dense:]):
        raise ValueError("laguna: dense MLPs after the first sparse one")
    rp = hf["rope_parameters"]
    full, local = rp["full_attention"], rp["sliding_attention"]
    if (local.get("rope_type", "default") != "default"
            or float(local.get("partial_rotary_factor", 1)) != 1.0):
        raise ValueError("laguna: window layers rotate the whole head, unscaled")
    if hf.get("moe_apply_router_weight_on_input"):
        raise ValueError("laguna: router weights on the expert's input")
    Fm = hf["moe_intermediate_size"]
    shared = int(hf.get("shared_expert_intermediate_size") or 0)
    if shared % Fm:
        raise ValueError("laguna: a shared expert of another width")
    yarn = full.get("rope_type") == "yarn"
    return ArchConfig(
        name=hf.get("_name_or_path", "laguna") or "laguna",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=by_kind["gqa"].pop(),
        swa_heads=by_kind["swa"].pop(),
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim"),
        max_position=hf.get("max_position_embeddings", 8192),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        rope_theta=float(full["rope_theta"]),
        rope_scaling="yarn" if yarn else None,
        rope_scaling_factor=float(full.get("factor", 1.0)),
        rope_original_max_position=int(
            full.get("original_max_position_embeddings")
            or rp.get("original_max_position_embeddings") or 4096),
        rope_beta_fast=float(full.get("beta_fast", 32.0)),
        rope_beta_slow=float(full.get("beta_slow", 1.0)),
        rope_attn_factor=(float(full["attention_factor"])
                          if full.get("attention_factor") is not None else None),
        partial_rotary=float(full.get("partial_rotary_factor", 1.0)),
        rope_local_theta=float(local["rope_theta"]),
        sliding_window=int(hf["sliding_window"]),
        attn_gate="head" if hf.get("gating") else False,
        layer_kinds=kinds,
        moe_family="deepseek",
        num_experts=hf["num_experts"],
        num_experts_per_token=hf["num_experts_per_tok"],
        first_k_dense=dense,
        n_shared_experts=shared // Fm,
        moe_intermediate_size=Fm,
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
    )


def _arch_from_jamba(hf: dict) -> ArchConfig:
    """AI21's `jamba` config keys: layer l is an attention layer iff l mod
    `attn_layer_period` == `attn_layer_offset`, every other a Mamba-1 layer
    (`mamba_expand`, `mamba_d_state`, `mamba_d_conv`, `mamba_dt_rank`); layer
    l has experts iff `num_experts` > 1 and l mod `expert_layer_period` ==
    `expert_layer_offset` (Jamba2-3B: `num_experts` 1, every MLP dense). No
    rope key: the attention layers apply no position term. What the keys do
    not define (the head width, the inner norms) is this repository's
    reading: benchmark/configs/ai21-jamba2-3b-int8.json, `assumed`."""
    if int(hf.get("num_experts") or 1) > 1:
        raise ValueError(
            "jamba: num_experts > 1 (a dense MLP in some layers and experts "
            "in others inside one recurrent stack) is not served yet")
    if not hf.get("mamba_conv_bias", True) or hf.get("mamba_proj_bias"):
        raise ValueError("jamba: the conv has a bias, the projections none")
    if hf.get("sliding_window"):
        raise ValueError("jamba: a sliding window on the attention layers")
    n = hf["num_hidden_layers"]
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    rank = hf.get("mamba_dt_rank", "auto")
    return ArchConfig(
        name=hf.get("_name_or_path", "jamba") or "jamba",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=n,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim"),
        max_position=hf.get("max_position_embeddings", 262144),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        attn_rope=False,
        layer_kinds=tuple("gqa" if i % period == offset else "s6"
                          for i in range(n)),
        mamba_d_state=hf.get("mamba_d_state", 16),
        mamba_conv=hf.get("mamba_d_conv", 4),
        mamba_expand=hf.get("mamba_expand", 2),
        mamba_dt_rank=(-(-hf["hidden_size"] // 16) if rank == "auto"
                       else int(rank)),
    )


def arch_from_hf_config(ckpt_dir: str) -> ArchConfig:
    """Build an ArchConfig from an HF config.json
    (llama/mistral/qwen2/mixtral/gemma/gemma-2/gemma-3/phi3), including every
    rope-scaling family the reference forwards to its engines
    (model_config.go:231-237): linear, llama3, yarn, longrope."""
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        hf = json.load(f)
    if isinstance(hf.get("text_config"), dict):
        # Multimodal wrappers (gemma-3 vision+text) nest the decoder config.
        hf = {**hf, **hf["text_config"]}
    rope_scaling = hf.get("rope_scaling") or {}
    scaling_type = rope_scaling.get("rope_type") or rope_scaling.get("type")
    if scaling_type == "su":
        scaling_type = "longrope"  # phi-3's original name for the same math
    if scaling_type == "default":
        scaling_type = None
    # Qwen2-VL: "mrope" is a position-id SHAPE (3 streams), not a frequency
    # rescale — frequencies stay unscaled; the section split rides on
    # ArchConfig.mrope_section (vllm passthrough in the reference,
    # backend/python/vllm/backend.py:211-243). Newer transformers
    # serializes it as rope_type "default" + an mrope_section key, so
    # detect by the key, not the type name.
    mrope_section: tuple = ()
    if scaling_type == "mrope" or rope_scaling.get("mrope_section"):
        mrope_section = tuple(rope_scaling.get("mrope_section") or ())
        scaling_type = None
    max_position = hf.get("max_position_embeddings", 8192)
    if scaling_type not in (None, "linear", "llama3", "yarn", "longrope"):
        raise ValueError(f"rope_scaling type {scaling_type!r} is not supported")
    orig_pos = int(
        rope_scaling.get("original_max_position_embeddings")
        or hf.get("original_max_position_embeddings")  # phi-3 keeps it top-level
        or max_position
    )
    long_factor = rope_scaling.get("long_factor")
    short_factor = rope_scaling.get("short_factor")
    attn_factor = rope_scaling.get("attention_factor")
    if attn_factor is None:
        attn_factor = rope_scaling.get("mscale")
    model_type = hf.get("model_type", "llama")
    if model_type == "laguna":
        return _arch_from_laguna(hf)
    if model_type == "jamba":
        return _arch_from_jamba(hf)
    gemma3 = model_type in ("gemma3", "gemma3_text")
    gemma = model_type in ("gemma", "gemma2") or gemma3
    gemma2 = model_type == "gemma2"
    # Gemma-3 sliding layout: 5 local : 1 global. Newer HF configs publish a
    # layer_types list; older ones a sliding_window_pattern int.
    sliding_pattern = 2
    if gemma3:
        lt = hf.get("layer_types")
        if isinstance(lt, list) and "full_attention" in lt:
            sliding_pattern = lt.index("full_attention") + 1
        else:
            sliding_pattern = int(
                hf.get("sliding_window_pattern")
                or hf.get("_sliding_window_pattern") or 6
            )
    act = hf.get("hidden_activation") or hf.get("hidden_act") or "silu"
    softcaps = gemma2 or gemma3  # gemma-3 configs carry the keys but None
    if model_type in ("deepseek_v2", "deepseek_v3", "glm4_moe_lite"):
        # `glm4_moe_lite` (GLM-4.7-Flash) is the V3 block under other sizes:
        # MLA with a q-lora, `noaux_tc` routing (sigmoid, correction bias),
        # the same tensor names. Its `num_nextn_predict_layers` MTP block
        # lies at `model.layers.<num_hidden_layers>.*` and is never read:
        # `_load_deepseek` asks for layers below `num_layers` by name.
        glm = model_type == "glm4_moe_lite"
        v3 = model_type == "deepseek_v3" or glm
        if glm and "rope_interleave" not in hf:
            # The published config.json carries no such key, and this
            # repository holds no source (modeling code or config class)
            # that fixes the family's default: a wrong guess rotates the
            # wrong pairs of a real checkpoint's dims and nothing flags it.
            raise ValueError(
                "glm4_moe_lite: config.json has no `rope_interleave`; add "
                "it (true: the rope columns of q_b_proj / kv_a_proj are "
                "stored pair-interleaved and are permuted at load; false: "
                "half-split as stored) from the checkpoint's modeling code")
        if scaling_type == "yarn":
            # DeepSeek yarn: the cos/sin attention_factor (mscale /
            # mscale_all_dim ratio) COMBINES with the extra softmax-scale
            # term yarn_get_mscale(factor, mscale_all_dim)² applied in
            # DeepseekV3Attention.__init__ — the product collapses to
            # yarn_get_mscale(factor, mscale), which rope_query_amp squares.
            factor = float(rope_scaling.get("factor", 1.0))

            def _gm(m):
                return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

            af = rope_scaling.get("attention_factor")
            msad = rope_scaling.get("mscale_all_dim")
            if af is not None:
                attn_factor = float(af) * (_gm(float(msad)) if msad else 1.0)
            elif rope_scaling.get("mscale") is not None and msad:
                attn_factor = _gm(float(rope_scaling["mscale"]))
            else:
                attn_factor = None  # default 0.1·ln(factor)+1 in rope_query_amp
        return ArchConfig(
            name=hf.get("_name_or_path", model_type) or model_type,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("qk_rope_head_dim", 64),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling=scaling_type,
            rope_scaling_factor=rope_scaling.get("factor", 1.0),
            rope_original_max_position=orig_pos,
            rope_beta_fast=float(rope_scaling.get("beta_fast", 32.0)),
            rope_beta_slow=float(rope_scaling.get("beta_slow", 1.0)),
            rope_attn_factor=float(attn_factor) if attn_factor is not None else None,
            max_position=max_position,
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            num_experts=hf.get("n_routed_experts") or 0,
            num_experts_per_token=hf.get("num_experts_per_tok") or 2,
            moe_family="deepseek",
            first_k_dense=(hf.get("first_k_dense_replace", 0)
                           if hf.get("n_routed_experts") else 0),
            n_shared_experts=hf.get("n_shared_experts") or 0,
            moe_intermediate_size=hf.get("moe_intermediate_size"),
            routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
            scoring_func="sigmoid" if v3 else hf.get("scoring_func", "softmax"),
            router_bias=v3,
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            n_group=hf.get("n_group") or 1,
            topk_group=hf.get("topk_group") or 1,
            kv_lora_rank=hf["kv_lora_rank"],
            q_lora_rank=hf.get("q_lora_rank"),
            qk_nope_head_dim=hf.get("qk_nope_head_dim", 128),
            qk_rope_head_dim=hf.get("qk_rope_head_dim", 64),
            v_head_dim=hf.get("v_head_dim", 128),
            # V2 applies complex (pair-interleaved) rope unconditionally
            # (the modeling code ignores any flag); V3 checkpoints carry
            # the flag (default true); a GLM config has to state it (above).
            rope_interleave=(True if not v3
                             else bool(hf.get("rope_interleave", True))),
        )
    # HF OlmoeAttention / OlmoeSparseMoeBlock: q/k RMS norms over the whole
    # projection; softmax over all experts, then top-k, weights renormalised
    # only when norm_topk_prob. intermediate_size is the expert width (there
    # is no dense MLP).
    if hf.get("linear_attn_config"):
        # A hybrid's two attention stacks (models/llama.py) have no loader
        # yet: its presets serve synthetic weights (models/config.py).
        raise ValueError(
            f"{model_type}: checkpoints with linear-attention layers are not "
            "loaded yet; serve the preset with synthetic weights")
    olmoe = model_type == "olmoe"
    if olmoe and hf.get("clip_qkv") is not None:
        raise ValueError("olmoe clip_qkv is not supported (published OLMoE "
                         "checkpoints leave it null)")
    return ArchConfig(
        name=hf.get("_name_or_path", model_type) or model_type,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim"),
        rope_theta=hf.get("rope_theta", 10000.0),
        rope_scaling=scaling_type,
        rope_scaling_factor=rope_scaling.get("factor", 1.0),
        rope_low_freq_factor=rope_scaling.get("low_freq_factor", 1.0),
        rope_high_freq_factor=rope_scaling.get("high_freq_factor", 4.0),
        rope_original_max_position=orig_pos,
        rope_beta_fast=float(rope_scaling.get("beta_fast", 32.0)),
        rope_beta_slow=float(rope_scaling.get("beta_slow", 1.0)),
        rope_long_factor=tuple(long_factor) if long_factor else None,
        rope_short_factor=tuple(short_factor) if short_factor else None,
        rope_attn_factor=float(attn_factor) if attn_factor is not None else None,
        rope_local_theta=float(hf.get("rope_local_base_freq") or 0.0) if gemma3 else 0.0,
        max_position=max_position,
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        # Gemma ties embeddings but its configs often omit the flag.
        tie_embeddings=hf.get("tie_word_embeddings", gemma),
        attn_qkv_bias=(model_type in ("qwen2", "qwen2_vl", "qwen2_vl_text")),
        # gated NoPE attention (Solar-Open2's key names)
        attn_rope=bool(hf.get("use_rope", True)),
        attn_gate=bool(hf.get("use_gqa_gate", False)),
        mrope_section=mrope_section,
        activation=("gelu_tanh" if "gelu" in act else "silu"),
        embed_scale=gemma,
        norm_plus_one=gemma,
        post_norms=gemma2 or gemma3,
        qk_norm=gemma3,
        attn_softcap=float(hf.get("attn_logit_softcapping") or 0.0) if softcaps else 0.0,
        final_softcap=float(hf.get("final_logit_softcapping") or 0.0) if softcaps else 0.0,
        query_scale=float(hf.get("query_pre_attn_scalar") or 0.0) if softcaps else 0.0,
        sliding_window=int(hf.get("sliding_window") or 0) if softcaps else 0,
        sliding_pattern=sliding_pattern,
        num_experts=hf.get("num_experts" if olmoe else "num_local_experts", 0),
        num_experts_per_token=hf.get("num_experts_per_tok", 2),
        qk_norm_full=olmoe,
        moe_family="deepseek" if olmoe else "mixtral",
        norm_topk_prob=olmoe and bool(hf.get("norm_topk_prob", False)),
    )
