"""Sharding plans for Llama-family parameters, KV caches, and activations.

Megatron-style tensor parallel mapped onto a named mesh:
- attention q/k/v projections: column-parallel (heads split over "tp")
- attention output projection: row-parallel
- MLP gate/up: column-parallel; down: row-parallel
- embeddings / lm_head: vocab-parallel (logits all-gathered by XLA only at
  the sampling boundary)
- MoE expert weights: expert axis over "ep" (falls back to "tp" when ep==1
  so Mixtral still tensor-parallelizes inside each expert)
- KV cache: kv-heads over "tp", slots over "dp"

The reference reaches the same goals by passing `tensor_split` to llama.cpp
(grpc-server.cpp:493-496) or `tensor_parallel_size` to vLLM
(backend/python/vllm/backend.py:106-107); here the plan is explicit
PartitionSpecs and XLA compiles the collectives.

Runtime LoRA factor stacks (ISSUE 10) are NOT part of the param tree and
keep their specs next to their kernel in ops/lora_matmul.lora_factor_specs:
column-parallel targets replicate A and shard B on the out axis, row-parallel
targets shard A on the in axis (mirroring the roles _layer_specs assigns the
base weights below) — the sharding-consistency lint pins THIS file's spec
names 1:1 against the llama param tree, so tenant state that lives outside
the tree must not add names here.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from localai_tpu.models.config import ArchConfig

Params = dict[str, Any]


class ShardingPlanError(ValueError):
    """A mesh plan cannot shard this architecture evenly (ISSUE 7).

    Subclasses ValueError so existing `except ValueError` probes keep
    working, but carries structure the engine uses to DEGRADE instead of
    crash at load: `max_tp` is the largest tp <= the requested one that the
    architecture supports (via max_valid_tp), or 0 when the failure is not
    a tp-divisibility problem (e.g. an ep mismatch)."""

    def __init__(self, message: str, *, axis: str = "tp", requested: int = 0,
                 max_tp: int = 0) -> None:
        super().__init__(message)
        self.axis = axis
        self.requested = requested
        self.max_tp = max_tp


def _attn_specs(cfg: ArchConfig, cache_stack: bool = False) -> dict[str, P]:
    """Attention-side specs shared by both layer stacks. MLA shards the
    per-head tensors over "tp" on the HEAD axis (q_b columns, w_kb/w_vb
    leading head dim, wo rows); the low-rank a-projections and the latent
    cache are replicated — they are the whole point of MLA (tiny)."""
    specs: dict[str, P] = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    if cfg.is_hybrid and not cache_stack:
        return specs  # the attention weights live in their kinds' stacks
    if cache_stack:
        specs = {}  # a hybrid model's cache layers' attention, no norms
    if cfg.is_mla:
        if cfg.q_lora_rank:
            specs["wq_a"] = P(None, None, None)
            specs["q_norm_a"] = P(None, None)
            specs["wq_b"] = P(None, None, "tp")
        else:
            specs["wq"] = P(None, None, "tp")
        specs["wkv_a"] = P(None, None, None)
        specs["kv_norm"] = P(None, None)
        specs["w_kb"] = P(None, "tp", None, None)
        specs["w_vb"] = P(None, "tp", None, None)
        specs["wo"] = P(None, "tp", None)
        return specs
    specs.update({
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
    })
    if cfg.attn_gate == "head":
        specs["wg_head"] = P(None, None, "tp")
    elif cfg.attn_gate:
        specs["wg"] = P(None, None, "tp")
    if cfg.post_norms:  # gemma-2 sandwich norms — replicated like the rest
        specs["post_attn_norm"] = P(None, None)
        specs["post_ffw_norm"] = P(None, None)
    if cfg.qk_norm or cfg.qk_norm_full:
        specs["q_norm"] = P(None, None)
        specs["k_norm"] = P(None, None)
    if cfg.attn_qkv_bias:
        specs["bq"] = P(None, "tp")
        specs["bk"] = P(None, "tp")
        specs["bv"] = P(None, "tp")
    return specs


def _layer_specs(cfg: ArchConfig) -> dict[str, P]:
    # Leading axis of every layer param is the stacked layer dim (never sharded:
    # lax.scan iterates over it).
    specs = _attn_specs(cfg)
    if cfg.is_moe:
        specs["router"] = P(None, None, None)
        if cfg.router_bias:
            specs["router_bias"] = P(None, None)
        specs["w_gate"] = P(None, "ep", None, "tp")
        specs["w_up"] = P(None, "ep", None, "tp")
        specs["w_down"] = P(None, "ep", "tp", None)
        if cfg.n_shared_experts:
            specs["shared_gate"] = P(None, None, "tp")
            specs["shared_up"] = P(None, None, "tp")
            specs["shared_down"] = P(None, "tp", None)
    else:
        specs["w_gate"] = P(None, None, "tp")
        specs["w_up"] = P(None, None, "tp")
        specs["w_down"] = P(None, "tp", None)
    return specs


def _dense_layer_specs(cfg: ArchConfig) -> dict[str, P]:
    """DeepSeek dense-prefix stack: attention like the MoE stack, plain MLP."""
    specs = _attn_specs(cfg)
    specs["w_gate"] = P(None, None, "tp")
    specs["w_up"] = P(None, None, "tp")
    specs["w_down"] = P(None, "tp", None)
    return specs


# A recurrent stack's leaves by kind: ([L, a, b] matrices, [L, a] vectors).
_RECURRENT_LEAVES = {
    "kda": (("wq", "wk", "wv", "wo", "conv_w", "f_down", "f_up", "w_beta",
             "g_down", "g_up"), ("dt_bias", "A_log", "o_norm")),
    "conv": (("w_in", "conv_w", "wo"), ()),
    "ssd": (("w_z", "w_xbc", "w_dt", "conv_w", "wo"),
            ("conv_b", "dt_bias", "A_log", "ssm_D", "o_norm")),
    "s6": (("w_in", "conv_w", "w_x", "w_dt", "A_logT", "wo"),
           ("conv_b", "dt_norm", "b_norm", "c_norm", "dt_bias", "ssm_D")),
    # the window layers' attention stack: the GQA stack's leaves
    "swa": (("wq", "wk", "wv", "wo", "wg_head"), ()),
}


def param_specs(cfg: ArchConfig) -> Params:
    specs: Params = {
        "embed": P("tp", None),
        "layers": _layer_specs(cfg),
        "final_norm": P(None),
    }
    if cfg.is_moe and cfg.first_k_dense:
        specs["dense_layers"] = _dense_layer_specs(cfg)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("tp", None)
    if cfg.is_hybrid:
        # A hybrid model serves at tp = 1 (the engine refuses more): its
        # recurrent stack is replicated, its cache layers' stack sharded as
        # any model's of their kind.
        mats, vecs = _RECURRENT_LEAVES[cfg.recurrent_kind]
        specs[cfg.recurrent_stack] = {
            **{n: P(None, None, None) for n in mats},
            **{n: P(None, None) for n in vecs}}
        specs[cfg.cache_stack] = _attn_specs(cfg, cache_stack=True)
    return specs


def param_shardings(cfg: ArchConfig, mesh: Mesh) -> Params:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(cfg),
        is_leaf=lambda x: isinstance(x, P),
    )


def param_shardings_for(cfg: ArchConfig, mesh: Mesh, params: Params) -> Params:
    """Sharding tree structurally aligned to `params`, which may contain
    quantized {"q", "s"} or grouped {"g4"/"gq", "gs"[, "gz"]} leaves
    (models/quant.py). The quantized payload keeps the weight's spec (grouped
    forms shard the group axis the way the in axis was sharded; the
    within-group axis never shards); scales drop spec axes where their
    dimension is 1.

    The fused dequant-matmul kernels consume EXACTLY this partitioning under
    their tp shard_map (ops/quant_matmul._w_specs rebuilds it per call from
    the col/row role — out axis for column-parallel, group/in axis for
    row-parallel). Keep the two in sync: a spec change here that _w_specs
    does not mirror makes the sharded Pallas path reshard every weight per
    decode step (ISSUE 9)."""
    specs = param_specs(cfg)

    def scale_spec(base: tuple, shape: tuple) -> P:
        spec_t = tuple(base) + (None,) * (len(shape) - len(tuple(base)))
        return P(*[
            None if shape[i] == 1 else spec_t[i] for i in range(len(shape))
        ])

    def align(spec, leaf):
        if isinstance(leaf, dict) and "q" in leaf:
            return {
                "q": NamedSharding(mesh, spec),
                "s": NamedSharding(mesh, scale_spec(spec, leaf["s"].shape)),
            }
        if isinstance(leaf, dict):  # grouped quantized tensor
            gspec = tuple(spec)[:-1] + (None, tuple(spec)[-1])
            out = {
                k: NamedSharding(mesh, P(*gspec))
                for k in ("g4", "gq") if k in leaf
            }
            for k in ("gs", "gz"):
                if k in leaf:
                    out[k] = NamedSharding(
                        mesh, scale_spec(gspec, leaf[k].shape)
                    )
            return out
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        align, specs, params,
        is_leaf=lambda x: isinstance(x, P),
    )


def cache_specs(sp: int = 1, mla: bool = False) -> tuple[P, P]:
    # [L, B_slots, S_max, K, Hd]: slots over dp, kv heads over tp. With sp>1
    # the sequence axis shards over "sp" so per-chip KV residency is S/sp —
    # the serving-side guarantee behind ring prefill (parallel/ring.py) and
    # sp decode attention (ops/attention.py decode_attention_*_sp): servable
    # context scales with the sp degree, not just prefill compute.
    # MLA caches hold ONE latent pseudo-head — replicated over tp (every
    # chip's head shard scores against the full latent; it is 1/2·H·Hd/576
    # the size of a dense cache, so replication is the cheap choice).
    spec = P(None, "dp", "sp" if sp > 1 else None, None if mla else "tp", None)
    return spec, spec


def cache_shardings(mesh: Mesh, sp: int = 1,
                    mla: bool = False) -> tuple[NamedSharding, NamedSharding]:
    ks, vs = cache_specs(sp, mla)
    return NamedSharding(mesh, ks), NamedSharding(mesh, vs)


def _tp_violation(cfg: ArchConfig, tp: int) -> Optional[str]:
    """First tp-divisibility violation, or None. Shared by validate_plan
    (raises) and max_valid_tp (probes) so probing never constructs
    exceptions n² deep."""
    if cfg.is_hybrid and tp > 1:
        # So an auto plan degrades to 1 (max_valid_tp) and only an engine
        # handed tp > 1 outright is refused (engine/state.py).
        return (f"{cfg.name} keeps a per-slot recurrent state "
                f"({cfg.recurrent_kind} layers) "
                f"that is not sharded: tp={tp} > 1")
    if not cfg.is_mla and cfg.num_kv_heads % tp != 0:
        # MLA has no per-head kv cache to shard — the latent replicates and
        # only the H-axis tensors (q_b, w_kb/w_vb, wo) split over tp.
        return (
            f"num_kv_heads={cfg.num_kv_heads} not divisible by tp={tp}; "
            f"choose tp in divisors of kv heads for {cfg.name}"
        )
    if cfg.num_heads % tp != 0:
        return f"num_heads={cfg.num_heads} not divisible by tp={tp}"
    if cfg.intermediate_size % tp != 0:
        return f"intermediate_size={cfg.intermediate_size} not divisible by tp={tp}"
    if cfg.vocab_size % tp != 0:
        return (
            f"vocab_size={cfg.vocab_size} not divisible by tp={tp} "
            "(embed/lm_head are vocab-parallel)"
        )
    if cfg.is_moe:
        if cfg.moe_inter_size % tp != 0:
            return (
                f"moe_intermediate_size={cfg.moe_inter_size} not divisible by tp={tp}"
            )
        if cfg.n_shared_experts and (cfg.n_shared_experts * cfg.moe_inter_size) % tp != 0:
            return (
                f"shared-expert width {cfg.n_shared_experts * cfg.moe_inter_size} "
                f"not divisible by tp={tp}"
            )
    return None


def max_valid_tp(cfg: ArchConfig, n_devices: int) -> int:
    """Largest tp ≤ n_devices that divides every sharded dimension.

    Any tp ≤ n_devices is legal (build_mesh truncates unused devices), so all
    integers are probed — e.g. 6 kv-heads on 8 devices serves at tp=6.
    """
    for tp in range(n_devices, 1, -1):
        if _tp_violation(cfg, tp) is None:
            return tp
    return 1


def validate_plan(cfg: ArchConfig, tp: int, ep: int = 1) -> None:
    """Fail fast on shapes that cannot shard evenly (XLA would pad silently).

    tp failures raise ShardingPlanError with `max_tp` naming the largest tp
    this architecture supports at or below the requested one — the engine
    auto-degrades to it instead of crashing at load (ISSUE 7)."""
    msg = _tp_violation(cfg, tp)
    if msg is not None:
        max_tp = max_valid_tp(cfg, tp)
        raise ShardingPlanError(
            f"{msg} (max valid tp for {cfg.name}: {max_tp})",
            axis="tp", requested=tp, max_tp=max_tp,
        )
    if cfg.is_moe and cfg.num_experts % ep != 0:
        raise ShardingPlanError(
            f"num_experts={cfg.num_experts} not divisible by ep={ep}",
            axis="ep", requested=ep, max_tp=0,
        )
