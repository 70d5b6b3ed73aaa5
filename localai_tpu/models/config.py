"""Architecture configs for decoder-only transformer families.

The reference delegates architecture to llama.cpp GGUF metadata
(/root/reference/core/config/gguf.go:15-60 introspects a GGUF to guess context
size and layout). Here architectures are first-class dataclasses so the JAX
model builders, the sharding planner (localai_tpu.parallel.sharding), and the
engine all agree on shapes statically — XLA requires static shapes to tile
matmuls onto the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


# The layer kinds that keep a per-slot state of fixed size and write no row to
# the paged cache: the four recurrent ones and "swa", a sliding-window
# attention layer, whose state is a ring of its last `sliding_window` keys
# and values (engine/state.py).
RECURRENT_KINDS = ("kda", "conv", "ssd", "swa", "s6")


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Shape/hyperparameter description of a Llama-family decoder.

    Covers Llama 2/3, Mistral, Qwen2 (qkv biases), TinyLlama and friends —
    the same families the reference serves through llama.cpp GGUFs.
    """

    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    # None | "linear" | "llama3" | "yarn" | "longrope" — the reference
    # forwards the same knob set to llama.cpp (model_config.go:231-237).
    rope_scaling: Optional[str] = None
    rope_scaling_factor: float = 1.0
    # llama3-style rope scaling extras
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # yarn extras (NTK-by-parts ramp bounds, HF defaults)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    # longrope (phi-3 "su") per-frequency rescale tables [head_dim/2]
    rope_long_factor: Optional[tuple] = None
    rope_short_factor: Optional[tuple] = None
    # Explicit attention-amplitude factor (yarn mscale / longrope scaling);
    # None = derive from the scaling type's published formula.
    rope_attn_factor: Optional[float] = None
    # Gemma-3: local (sliding) layers run their own unscaled rope base while
    # global layers use rope_theta (+ scaling). 0 = single schedule.
    rope_local_theta: float = 0.0
    max_position: int = 8192
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    attn_qkv_bias: bool = False  # Qwen2-style
    # Gemma-family: GeGLU MLP ("gelu_tanh"), embeddings scaled by sqrt(D)
    # at lookup (the tied unembed reads the raw matrix), and (1+w) RMSNorm
    # weights — the +1 is folded into the tree at load, so only the first
    # two need runtime branches.
    activation: str = "silu"  # "silu" | "gelu_tanh"
    embed_scale: bool = False
    # Granite's four scalars (HF `GraniteMoeHybrid`): the embedding row times
    # `embedding_multiplier`, every residual branch times
    # `residual_multiplier` before it is added, the logits divided by
    # `logits_scaling`. 1 = no op is emitted. The fourth, the published
    # `attention_multiplier` m in place of head_dim^-0.5, is `query_scale`
    # = m^-2 below.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_plus_one: bool = False  # load-time fold (engine/weights.py)
    # Gemma-2: sandwich norms (post-attention and post-feedforward RMSNorms
    # inside the residual adds), tanh softcapping on attention scores and
    # final logits, q scaled by query_pre_attn_scalar^-0.5 instead of
    # head_dim^-0.5, and sliding-window attention on even layers.
    post_norms: bool = False
    attn_softcap: float = 0.0  # 0 = off
    final_softcap: float = 0.0
    query_scale: float = 0.0  # 0 = default head_dim^-0.5
    sliding_window: int = 0  # 0 = full attention on every layer
    # Which layers slide: layer li attends globally iff li % pattern ==
    # `sliding_phase`, and slides otherwise. Gemma-2 alternates (2); gemma-3
    # runs 5 local : 1 global (6); both END a period with its global layer,
    # which is what a phase of None means (pattern - 1). A pattern of 1 with
    # a phase no layer has (`kind_view("swa")`): every layer slides.
    sliding_pattern: int = 2
    sliding_phase: Optional[int] = None
    # The share of each head that is rotated: its leading
    # head_dim x partial_rotary lanes, in half-split pairs over those lanes
    # alone; the rest pass through (and carry no yarn amplitude).
    partial_rotary: float = 1.0
    # Gemma-3: per-head RMS norms on q and k (after projection, before rope).
    qk_norm: bool = False
    # OLMoE: ONE RMS norm over the whole q projection (all heads, weight
    # [H·Hd]) and one over the whole k projection ([K·Hd]), before the split
    # into heads — a different reduction from the per-head form above.
    qk_norm_full: bool = False
    # Mixture-of-experts (Mixtral/DeepSeek-style); 0 experts = dense MLP
    num_experts: int = 0
    num_experts_per_token: int = 2
    # Capacity factor for the expert-parallel (ep>1) GShard dispatch path:
    # each expert processes at most ceil(top_k·N/E·cf) tokens per block.
    moe_capacity_factor: float = 2.0
    # DeepSeek-V2/V3 MoE layout (HF DeepseekV2Config/DeepseekV3Config;
    # reference serves these via vLLM passthrough, vllm/backend.py:92-141):
    # the first `first_k_dense` layers run a dense MLP, the rest route
    # `num_experts_per_token` of `num_experts` routed experts (intermediate
    # size `moe_intermediate_size`) plus an always-on shared-expert MLP of
    # size n_shared_experts·moe_intermediate_size.
    first_k_dense: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: Optional[int] = None
    routed_scaling_factor: float = 1.0
    # Router family: "mixtral" softmaxes the top-k logits; "deepseek"
    # scores ALL experts (softmax/sigmoid per scoring_func) and then
    # selects — the two orders give different weights, so this is explicit.
    moe_family: str = "mixtral"
    # Router scoring: "softmax" (Mixtral/DeepSeek-V2) or "sigmoid"
    # (DeepSeek-V3/R1, selection biased by a learned per-expert correction).
    scoring_func: str = "softmax"
    router_bias: bool = False  # V3 e_score_correction_bias
    norm_topk_prob: bool = False  # V3: renormalize the selected weights
    # What the renormalisation adds to the picks' sum (DeepSeek-V3, Kimi-Linear
    # and Solar-Open2 1e-20; LFM2 1e-6).
    norm_topk_eps: float = 1e-20
    # Group-limited routing (device-limited in the paper): experts are split
    # into n_group groups; selection is restricted to the topk_group
    # best-scoring groups (V2 scores a group by its max, V3 by the sum of
    # its top-2 biased scores).
    n_group: int = 1
    topk_group: int = 1
    # Multi-head Latent Attention (DeepSeek-V2/V3): q/kv project through
    # low-rank bottlenecks and the KV cache stores ONE latent row per token
    # ([kv_lora_rank | roped qk_rope_head_dim]) instead of per-head k/v.
    # kv_lora_rank > 0 switches the whole attention stack to MLA.
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None  # None = direct q projection (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # HF deepseek checkpoints store the rope dims pair-interleaved (V2
    # always — complex rope; V3 per config.rope_interleave). The loader
    # de-interleaves the affected projection columns so runtime rope stays
    # the one half-split (neox) implementation.
    rope_interleave: bool = False
    # Qwen2-VL multimodal rope: (t, h, w) section split of head_dim/2.
    # Non-empty → image-bearing prompts prefill with 3D position streams
    # (ops/rope.mrope_angles); text-only paths reduce to plain rope.
    mrope_section: tuple = ()
    dtype: str = "bfloat16"
    # Quantized-matmul kernel choice threaded to every model-side matmul
    # (ISSUE 9): "auto" (fused Pallas dequant-matmul on TPU, XLA dequant
    # elsewhere) | "pallas" | "xla". Lives on ArchConfig — not a shape, but
    # cfg is the one static object every layer helper already receives, so
    # the engine's EngineConfig.quant_kernel knob reaches models/quant.py
    # through `dataclasses.replace(cfg, quant_kernel=...)` without
    # re-plumbing ~30 call sites (the paged_impl treatment at entry-point
    # granularity; quant matmuls live one level deeper).
    quant_kernel: str = "auto"
    # Ragged per-slot LoRA delta kernel choice (ISSUE 10,
    # docs/LORA_SERVING.md): "auto" (Pallas segmented matmul on TPU, XLA
    # gather elsewhere) | "pallas" | "xla". Threaded exactly like
    # quant_kernel — EngineConfig.lora_kernel reaches ops/lora_matmul.py
    # through `dataclasses.replace(cfg, lora_kernel=...)`.
    lora_kernel: str = "auto"
    # Self-draft early-exit prefix (ISSUE 12, docs/SPECULATIVE.md): > 0
    # means `spec_mode=self_draft` drafts with the target's OWN first k
    # layers + final norm + unembed — `llama.self_draft_view` slices the
    # stacked layer tensors to [:k] inside the traced program, so the
    # draft shares the sharded weight buffers (no second checkpoint in
    # HBM). Lives on ArchConfig like quant_kernel/lora_kernel: the engine's
    # EngineConfig.self_draft_layers knob reaches the layer-scan helpers
    # through `dataclasses.replace(cfg, self_draft_layers=...)`.
    self_draft_layers: int = 0
    # Windowed+sink long-context serving (ISSUE 14, docs/LONG_CONTEXT.md):
    # when attention_window > 0, decode (and the chunked-prefill prefix
    # walk under the paged pool) attends only rows with position < sink or
    # within `attention_window` of the query — StreamingLLM-style, with
    # ABSOLUTE rope positions (rows keep their original positions; no
    # re-rope). Lives on ArchConfig like quant_kernel: the engine's
    # EngineConfig knobs reach every attention call through
    # `dataclasses.replace(cfg, ...)`. 0/0 = full attention (default).
    attention_sink: int = 0
    attention_window: int = 0
    # GQA whose q and k are never rotated (Solar-Open2's `use_rope` false):
    # `rope_theta` then has nothing to act on.
    attn_rope: bool = True
    # GQA output gate: the attention output is multiplied by sigmoid(x W_g)
    # of the layer's normed input before W_o. Two forms: True (or
    # "element"; Solar-Open2's `use_gqa_gate`), element by element over heads
    # x head width ("wg" [D, H·Hd]); "head" (Laguna's `gating`), one scalar
    # a head ("wg_head" [D, H], held in the model's dtype).
    attn_gate: "bool | str" = False
    # Hybrid linear attention (Kimi-Linear, arXiv:2510.26692): one kind per
    # layer, "kda" (Kimi Delta Attention: a per-slot recurrent state
    # [kda_heads, kda_head_dim, kda_head_dim] f32 plus the short conv's last
    # kda_conv-1 inputs, no cache rows) or a kind that writes cache rows:
    # "mla" (latent rows) or "gqa" (the model's ordinary K/V rows), whichever
    # `kv_lora_rank` says. Empty = every layer is that attention.
    # models/llama._scan_hybrid needs every cache layer to stand beside a
    # "kda" layer, all of them behind theirs or all of them in front, and the
    # dense-prefix layers to be "kda".
    # "conv" (LFM2's gated short convolution) is the other recurrent kind: in
    # "kda"'s place everywhere above, its per-slot state the operator's last
    # conv_cache-1 inputs [conv_cache-1, hidden_size] and nothing else.
    # "ssd" (Mamba-2's state-space duality layer, Granite-4.0-H) is the third:
    # its per-slot state a [mamba_heads, mamba_head_dim, mamba_d_state] f32
    # matrix a layer with a SCALAR decay a head, and the conv's last
    # mamba_conv-1 inputs [mamba_conv-1, d_inner + 2·groups·d_state].
    # "swa" (Laguna's `sliding_attention`) is the fourth, and no recurrence:
    # a GQA layer of `swa_heads` query heads over the model's KV heads that
    # attends the last `sliding_window` positions, rotated at
    # `rope_local_theta` unscaled over the whole head; its per-slot state is
    # the ring of those positions' keys and values (`ring_pages` pages of
    # `ring_page` rows), and its weights have their own stack because its
    # head count is not the cache layers'.
    # "s6" (Mamba-1's selective scan, the `jamba` mixer: AI21-Jamba2) is the
    # fifth: its per-slot state a [mamba_d_state, d_inner] f32 matrix a layer
    # whose every element decays by its own exp(dt[c] A[c, n]), and the
    # conv's last mamba_conv-1 inputs [mamba_conv-1, d_inner]; no heads and
    # no groups. One model has one such kind (`recurrent_kind`).
    layer_kinds: tuple = ()
    swa_heads: int = 0
    # LFM2's conv_L_cache: the taps of the short conv. Neither the taps nor
    # the two projections have a bias (the published `conv_bias` is false in
    # every LFM2 config; there is no field for a value nothing here computes)
    conv_cache: int = 3
    # The two Mamba forms' widths under their published names. Both read
    # `mamba_d_state` and `mamba_conv` (`mamba_d_conv`), and both convs have a
    # bias (`mamba_conv_bias` true in every published Granite-4.0-H and Jamba
    # config) where the projections have none (`mamba_proj_bias` false).
    # Mamba-2 / SSD ("ssd") alone: `mamba_heads` (`mamba_n_heads`),
    # `mamba_head_dim` (`mamba_d_head`), `mamba_groups` (`mamba_n_groups`),
    # `mamba_chunk` (`mamba_chunk_size`: the chunk the model was trained in;
    # the serving prefill blocks by at most ops/ssd.CHUNK, an exact
    # sub-blocking); d_inner = heads x head_dim.
    # Mamba-1 / the selective scan ("s6") alone: `mamba_expand` (d_inner =
    # expand x hidden_size; it has no heads) and `mamba_dt_rank`, the
    # bottleneck the step comes through.
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4  # short_conv_kernel_size
    kda_gate_rank: int = 0  # low-rank width of the decay and output gates
    # beta = 2·sigmoid(.) in (0, 2) instead of (0, 1): I - beta k k^T then has
    # the eigenvalue 1 - beta in (-1, 1) (Solar-Open2's `kda_allow_neg_eigval`).
    kda_neg_eigval: bool = False
    # Synthetic init only (llama.init_special): the range the KDA decay's
    # step is drawn from, log-uniform. The default is a hundredth of fla's
    # [1e-3, 1e-1] (why: llama.KDA_DT); a preset may ask for another.
    kda_init_dt: tuple = (1e-5, 1e-3)
    # Synthetic init only (llama.init_gain): what the routed experts'
    # down-projection is drawn at besides the scale. The hybrid presets and
    # GLM-4.7-Flash's say a tenth: a preset whose check needs it asks here.
    routed_down_gain: float = 1.0
    # MLA whose rope dims are never rotated (Kimi-Linear's `mla_use_nope`).
    mla_rope: bool = True
    # Latent cache rows are stored padded to a multiple of this many values
    # (0 = as they are): the paged kernel DMAs whole lane tiles.
    latent_pad: int = 0
    # The expert share this process holds, (index, of): the router scores
    # all `num_experts`, the expert stacks hold experts
    # [index·E/of, (index+1)·E/of) and the layer returns their part of the
    # sum (plus the shared expert). None = every expert (models' YAML key
    # `expert_share`, the deployment's, not the model's).
    expert_share: Optional[tuple] = None

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_kinds)

    @property
    def recurrent_kind(self) -> str:
        """A hybrid model's recurrent kind, one of `RECURRENT_KINDS` ("" =
        none). A stack that mixes two is refused here, by name."""
        kinds = {k for k in self.layer_kinds if k in RECURRENT_KINDS}
        if len(kinds) > 1:
            raise NotImplementedError(
                f"{self.name}: layer_kinds mixes the recurrent kinds "
                f"{sorted(kinds)}; one model has one (a stack of two "
                "would need two per-slot states and two scans)")
        return next(iter(kinds), "")

    def kind_view(self, kind: str) -> "ArchConfig":
        """The config as the ONE decoder layer body reads it for the layers
        of `kind` in a model whose attention layers differ by kind (a "swa"
        model): the kind's head count, rope schedule and window, nothing
        else changed. Any other model, and any other kind, reads itself."""
        if self.recurrent_kind != "swa":
            return self
        if kind == "swa":  # every layer slides, rotated whole at the local base
            return dataclasses.replace(
                self, num_heads=self.swa_heads, sliding_pattern=1,
                sliding_phase=1, rope_theta=self.rope_local_theta,
                rope_local_theta=0.0, rope_scaling=None, partial_rotary=1.0)
        return dataclasses.replace(  # the full layers: no window at all
            self, sliding_window=0, rope_local_theta=0.0)

    @property
    def ring_page(self) -> int:
        """Rows of one page of a "swa" layer's per-slot ring."""
        return min(128, self.sliding_window)

    @property
    def ring_pages(self) -> int:
        """Pages a slot's ring holds: `sliding_window` rows, rounded up."""
        return -(-self.sliding_window // self.ring_page)

    @property
    def ring_rows(self) -> int:
        """Rows a slot's ring holds in each window layer."""
        return self.ring_pages * self.ring_page

    @property
    def rotary_dim(self) -> int:
        """The leading lanes of a GQA head that rope rotates."""
        return int(self.head_dim_ * self.partial_rotary)

    @property
    def recurrent_layers(self) -> tuple:
        """Model layer numbers of the layers of the recurrent kind."""
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k in RECURRENT_KINDS)

    @property
    def recurrent_stack(self) -> str:
        """The key of the recurrent layers' weight stack in the param tree."""
        return f"{self.recurrent_kind}_layers"

    @property
    def mamba_d_inner(self) -> int:
        """Mamba-2 states it as heads x head width, Mamba-1 as expand x D."""
        if self.mamba_heads:
            return self.mamba_heads * self.mamba_head_dim
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the SSD layer's short conv: [x | B | C]."""
        return self.mamba_d_inner + 2 * self.mamba_groups * self.mamba_d_state

    @property
    def cache_layer_ids(self) -> tuple:
        """Model layer numbers of the layers that write cache rows."""
        if not self.layer_kinds:
            return tuple(range(self.num_layers))
        return tuple(i for i, k in enumerate(self.layer_kinds)
                     if k not in RECURRENT_KINDS)

    @property
    def cache_stack(self) -> str:
        """The key of a hybrid model's second weight stack in its param tree:
        the attention weights of its cache layers, whichever kind they are."""
        return "mla_layers" if self.is_mla else "gqa_layers"

    @property
    def cache_layers(self) -> int:
        """Layers the KV cache (dense or paged) holds rows for."""
        return len(self.cache_layer_ids)

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this process holds, per layer."""
        if self.expert_share is None:
            return self.num_experts
        return self.num_experts // int(self.expert_share[1])

    @property
    def expert_lo(self) -> int:
        """Id of the first routed expert held here."""
        if self.expert_share is None:
            return 0
        return int(self.expert_share[0]) * self.experts_here

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Per-head q/k width under MLA (nope ⊕ rope)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # Cache layout: the engine, pool allocator, and sharding planner size the
    # KV cache from these three, so MLA's latent layout (one pseudo-head of
    # [kv_lora_rank + rope] per token, no separate V — values are read back
    # out of the same latent) threads through every cache variant (dense /
    # windowed / paged / fp8) without per-call-site branches; so does a
    # narrow-head pool's two heads a row (`cache_pack`).
    @property
    def cache_pack(self) -> int:
        """KV heads a cache row holds side by side. 2 for a hybrid model's
        64-wide GQA heads: a `[page, K, 64]` tile of a 16-bit pool has no
        128-lane row and Mosaic refuses it as stored (PERF.md section 7 item
        6b), so the pool is `[page, K/2, 128]`, head 2i in lanes 0-63 and
        2i + 1 in 64-127 of row i: the same bytes, the reshape of the rows a
        layer emits free. Only where the cache layers' rows are read by the
        decode step's page walk alone (what a hybrid model is held to,
        engine/state.refuse); every other model keeps a head a row, which its
        dense cache and its prefix, chunk and verify readers assume."""
        narrow = (self.is_hybrid and not self.is_mla and self.head_dim_ == 64
                  and self.num_kv_heads % 2 == 0)
        return 2 if narrow else 1

    @property
    def cache_kv_heads(self) -> int:
        return 1 if self.is_mla else self.num_kv_heads // self.cache_pack

    @property
    def cache_k_dim(self) -> int:
        if not self.is_mla:
            return self.head_dim_ * self.cache_pack
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // self.latent_pad) * self.latent_pad if self.latent_pad else w

    @property
    def cache_v_dim(self) -> int:
        return 0 if self.is_mla else self.head_dim_ * self.cache_pack

    @property
    def moe_inter_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size


# ---------------------------------------------------------------------------
# Presets. Shapes match the public model cards; weights are loaded from local safetensors when
# available or randomly initialized for benchmarking.
# ---------------------------------------------------------------------------

PRESETS: dict[str, ArchConfig] = {
    # Tiny configs for tests / CI on the virtual CPU mesh.
    "tiny": ArchConfig(
        name="tiny",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        max_position=256,
        rope_theta=10000.0,
    ),
    "tiny-moe": ArchConfig(
        name="tiny-moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        max_position=256,
        num_experts=4,
        num_experts_per_token=2,
    ),
    "tiny-olmoe": ArchConfig(
        # OLMoE-shaped tiny: full-width q/k norm, softmax over ALL experts
        # then top-k with the weights used as they are (no renormalisation),
        # no shared expert, no dense prefix, untied head.
        name="tiny-olmoe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        max_position=512,
        qk_norm_full=True,
        moe_family="deepseek",
        num_experts=8,
        num_experts_per_token=2,
    ),
    "tiny-mla": ArchConfig(
        # DeepSeek-V3-shaped tiny: MLA with q-lora, sigmoid router with
        # correction bias, group-limited top-k, shared expert, dense-first
        # layer — every R1 mechanism at test scale.
        name="tiny-mla",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,  # rope table width = qk_rope_head_dim
        max_position=256,
        moe_family="deepseek",
        num_experts=8,
        num_experts_per_token=3,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=48,
        routed_scaling_factor=2.5,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=4,
        topk_group=2,
        kv_lora_rank=32,
        q_lora_rank=24,
        qk_nope_head_dim=24,
        qk_rope_head_dim=16,
        v_head_dim=24,
    ),
    "tiny-kimi-linear": ArchConfig(
        # Kimi-Linear-shaped tiny: 3 KDA : 1 MLA with the pattern's ragged
        # end (MLA at layers 3 and 6 of 0..6), NoPE MLA without q-lora, one
        # dense layer, sigmoid router with correction bias in one group,
        # renormalised and scaled, a shared expert, untied head, padded
        # latent rows.
        name="tiny-kimi-linear",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=7,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_position=512,
        routed_down_gain=0.1,
        layer_kinds=("kda", "kda", "kda", "mla", "kda", "kda", "mla"),
        kda_heads=4,
        kda_head_dim=16,
        kda_conv=4,
        kda_gate_rank=16,
        mla_rope=False,
        latent_pad=64,
        moe_family="deepseek",
        num_experts=16,
        num_experts_per_token=4,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=32,
        routed_scaling_factor=2.446,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        kv_lora_rank=32,
        q_lora_rank=None,
        qk_nope_head_dim=16,
        qk_rope_head_dim=16,
        v_head_dim=16,
    ),
    "tiny-glm-4.7-flash": ArchConfig(
        # GLM-4.7-Flash-shaped tiny: MLA in every layer through the plain
        # scan, rotated, a q-lora bottleneck, value heads WIDER than the nope
        # heads (so v is not padded up to the q/k width: 24 + 8 = 32), 5
        # heads (no multiple of 8), one dense layer, 8 experts top-2 (sigmoid,
        # correction bias, one group, renormalised, x1.8) beside a shared one,
        # latent rows padded to one whole lane tile, [c 32 | k_pe 8 | 0 x
        # 88], so that the pool's block write is the staged kernel's.
        name="tiny-glm-4.7-flash",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=5,
        num_kv_heads=5,
        head_dim=8,  # rope table width = qk_rope_head_dim
        rope_theta=1000000.0,
        max_position=512,
        rms_eps=1e-5,
        latent_pad=128,
        moe_family="deepseek",
        num_experts=8,
        num_experts_per_token=2,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=32,
        routed_scaling_factor=1.8,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        routed_down_gain=0.1,
        kv_lora_rank=32,
        q_lora_rank=24,
        qk_nope_head_dim=24,
        qk_rope_head_dim=8,
        v_head_dim=32,
    ),
    "tiny-solar-open2": ArchConfig(
        # Solar-Open2-shaped tiny: two periods of 1 gated NoPE GQA : 3 KDA
        # with the cache layer LEADING its period, beta in (0, 2), every
        # layer MoE (no dense prefix), sigmoid router with correction bias
        # in one group, renormalised, unscaled, a shared expert, an expert
        # count (24) that is no power of two, untied head.
        name="tiny-solar-open2",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=8,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        max_position=512,
        attn_rope=False,
        attn_gate=True,
        routed_down_gain=0.1,
        layer_kinds=("gqa", "kda", "kda", "kda") * 2,
        kda_heads=4,
        kda_head_dim=16,
        kda_conv=4,
        kda_gate_rank=16,
        kda_neg_eigval=True,
        moe_family="deepseek",
        num_experts=24,
        num_experts_per_token=4,
        n_shared_experts=1,
        moe_intermediate_size=40,
        routed_scaling_factor=1.0,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
    ),
    "tiny-laguna-xs.2": ArchConfig(
        # Laguna-XS.2-shaped tiny: two periods of 1 full : 3 window layers
        # with the full layer LEADING its period, 6 query heads in the full
        # layers and 8 in the window ones over 2 KV heads, a window of 16
        # (one ring page a slot), the full layers rotating half a head under
        # YaRN and the window layers the whole head at their own base, a
        # per-head output gate, layer 0 dense AND a cache layer, 16 experts
        # top-4 (sigmoid, selection bias, one group, renormalised, x2.5)
        # beside a shared one, untied head.
        name="tiny-laguna-xs.2",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=8,
        num_heads=6,
        swa_heads=8,
        num_kv_heads=2,
        head_dim=16,
        max_position=512,
        rms_eps=1e-6,
        rope_theta=500000.0,
        rope_scaling="yarn",
        rope_scaling_factor=64.0,
        rope_original_max_position=4096,
        rope_beta_fast=64.0,
        rope_beta_slow=1.0,
        rope_attn_factor=1.4158883083359672,
        partial_rotary=0.5,
        rope_local_theta=10000.0,
        sliding_window=16,
        attn_gate="head",
        routed_down_gain=0.1,
        layer_kinds=("gqa", "swa", "swa", "swa") * 2,
        moe_family="deepseek",
        num_experts=16,
        num_experts_per_token=4,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=32,
        routed_scaling_factor=2.5,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
    ),
    "tiny-lfm2": ArchConfig(
        # LFM2-MoE-shaped tiny: the published pattern cut to its 2 dense conv
        # layers and one whole period (attention, conv, conv, conv), heads of
        # the published 64 (so the pool holds two a row, `cache_pack`),
        # per-head q/k norms, rope, a tied head, sigmoid router with a
        # selection bias, renormalised with 1e-6 and unscaled, no shared
        # expert.
        name="tiny-lfm2",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=6,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        max_position=512,
        rope_theta=1000000.0,
        tie_embeddings=True,
        qk_norm=True,
        routed_down_gain=0.1,
        layer_kinds=("conv", "conv", "gqa", "conv", "conv", "conv"),
        conv_cache=3,
        moe_family="deepseek",
        num_experts=8,
        num_experts_per_token=2,
        first_k_dense=2,
        moe_intermediate_size=32,
        routed_scaling_factor=1.0,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        norm_topk_eps=1e-6,
    ),
    "tiny-granite-h": ArchConfig(
        # Granite-4.0-H-shaped tiny: two periods of 4 SSD (Mamba-2) layers
        # and one NoPE GQA layer BEHIND a Mamba layer of its own (the
        # published pattern has its attention layers at 5, 15, 25, 35), every
        # layer 8 experts top-3 routed the "mixtral" way (softmax over the
        # picks' logits) beside a shared MLP, a tied head, and all four
        # scalar multipliers away from 1.
        name="tiny-granite-h",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_layers=10,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_position=512,
        tie_embeddings=True,
        attn_rope=False,
        query_scale=64.0,  # scores x 1/8, not 16^-0.5
        embedding_multiplier=6.0,
        residual_multiplier=0.35,
        logits_scaling=4.0,
        routed_down_gain=0.1,
        layer_kinds=("ssd", "ssd", "ssd", "ssd", "gqa") * 2,
        mamba_heads=8,
        mamba_head_dim=16,
        mamba_d_state=32,
        mamba_groups=1,
        mamba_conv=4,
        mamba_chunk=32,
        moe_family="mixtral",
        num_experts=8,
        num_experts_per_token=3,
        n_shared_experts=2,
        moe_intermediate_size=32,
    ),
    "tiny-jamba2": ArchConfig(
        # AI21-Jamba2-shaped tiny: two periods of 3 with the NoPE multi-query
        # layer at offset 1 (the published period is 14, offset 7), each
        # BEHIND a Mamba-1 layer of its own; inner width 2 x hidden, 8 states,
        # a step through a rank-8 bottleneck, 4 query heads over ONE K/V
        # head, a dense SwiGLU in every layer, a tied head.
        name="tiny-jamba2",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=6,
        num_heads=4,
        num_kv_heads=1,
        head_dim=16,
        max_position=512,
        rms_eps=1e-6,
        tie_embeddings=True,
        attn_rope=False,
        layer_kinds=("s6", "gqa", "s6") * 2,
        mamba_d_state=8,
        mamba_conv=4,
        mamba_expand=2,
        mamba_dt_rank=8,
    ),
    "llama-3.2-1b": ArchConfig(
        name="llama-3.2-1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=500000.0,
        rope_scaling="llama3",
        rope_scaling_factor=32.0,
        max_position=131072,
        tie_embeddings=True,
    ),
    "llama-3-8b": ArchConfig(
        name="llama-3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500000.0,
        max_position=8192,
    ),
    "mistral-7b": ArchConfig(
        name="mistral-7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=10000.0,
        max_position=32768,
    ),
    "qwen2-7b": ArchConfig(
        name="qwen2-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        rope_theta=1000000.0,
        max_position=32768,
        attn_qkv_bias=True,
    ),
    "mixtral-8x7b": ArchConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=1000000.0,
        max_position=32768,
        num_experts=8,
        num_experts_per_token=2,
    ),
    "olmoe-1b-7b": ArchConfig(
        # allenai/OLMoE-1B-7B-0125-Instruct config.json: 6.9B total / 1.3B
        # active; intermediate_size IS the expert width (no dense MLP, no
        # shared expert); head_dim is not published (2048 / 16). The router
        # is `_deepseek_route`'s order (softmax over all 64, then top-8)
        # with norm_topk_prob false and no scaling.
        name="olmoe-1b-7b",
        vocab_size=50304,
        hidden_size=2048,
        intermediate_size=1024,
        num_layers=16,
        num_heads=16,
        num_kv_heads=16,
        rope_theta=10000.0,
        max_position=4096,
        rms_eps=1e-5,
        qk_norm_full=True,
        moe_family="deepseek",
        num_experts=64,
        num_experts_per_token=8,
        scoring_func="softmax",
        norm_topk_prob=False,
    ),
    "deepseek-v2-lite": ArchConfig(
        # Published card: 27 layers, 16B total / 2.4B active, MLA without
        # q-lora, 64 routed + 2 shared experts, first layer dense.
        name="deepseek-v2-lite",
        vocab_size=102400,
        hidden_size=2048,
        intermediate_size=10944,
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        head_dim=64,
        rope_theta=10000.0,
        max_position=163840,
        moe_family="deepseek",
        num_experts=64,
        num_experts_per_token=6,
        first_k_dense=1,
        n_shared_experts=2,
        moe_intermediate_size=1408,
        routed_scaling_factor=1.0,
        scoring_func="softmax",
        rope_interleave=True,
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    "deepseek-r1": ArchConfig(
        # DeepSeek-V3/R1 (the round-1 flagship target): 61 layers (3 dense),
        # 256 routed experts top-8 in 8 groups, sigmoid router with
        # correction bias, MLA with q-lora. Serving shapes for the EP mesh
        # dryrun and decode benchmarks; full weights need a multi-host pod.
        name="deepseek-r1",
        vocab_size=129280,
        hidden_size=7168,
        intermediate_size=18432,
        num_layers=61,
        num_heads=128,
        num_kv_heads=128,
        head_dim=64,
        rope_theta=10000.0,
        max_position=163840,
        moe_family="deepseek",
        num_experts=256,
        num_experts_per_token=8,
        first_k_dense=3,
        n_shared_experts=1,
        moe_intermediate_size=2048,
        routed_scaling_factor=2.5,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=8,
        topk_group=4,
        rope_interleave=True,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    "kimi-linear-48b-a3b": ArchConfig(
        # moonshotai/Kimi-Linear-48B-A3B-Instruct config.json (arXiv:
        # 2510.26692): 27 layers, 20 KDA : 7 MLA (MLA at layers 4, 8, ...,
        # 24, 27 counted from 1), 32 heads of 128 in both kinds, MLA without
        # q-lora and without rotation (mla_use_nope), layer 1 a dense SwiGLU
        # of 9216, layers 2-27 256 routed experts of 1024 top-8 (sigmoid,
        # correction bias, one group, renormalised, x2.446) plus one shared
        # expert. `head_dim` 72 is the published key (2304 / 32); neither
        # attention kind uses it.
        name="kimi-linear-48b-a3b",
        vocab_size=163840,
        hidden_size=2304,
        intermediate_size=9216,
        num_layers=27,
        num_heads=32,
        num_kv_heads=32,
        head_dim=72,
        rope_theta=10000.0,
        max_position=1048576,
        rms_eps=1e-5,
        routed_down_gain=0.1,
        layer_kinds=tuple(
            "mla" if (i + 1) % 4 == 0 or i == 26 else "kda" for i in range(27)),
        kda_heads=32,
        kda_head_dim=128,
        kda_conv=4,
        kda_gate_rank=128,
        mla_rope=False,
        latent_pad=128,
        moe_family="deepseek",
        num_experts=256,
        num_experts_per_token=8,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=1024,
        routed_scaling_factor=2.446,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=1,
        topk_group=1,
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    "glm-4.7-flash": ArchConfig(
        # zai-org/GLM-4.7-Flash config.json (`glm4_moe_lite`, 30B-A3B): 47
        # layers, every one MLA: 20 heads, a 768-wide q-lora bottleneck, a
        # 512-wide latent, 192 nope + 64 rope dims a q/k head (all 64
        # rotated, theta 1e6, no scaling) and 256-wide value heads; layer 1 a
        # dense SwiGLU of 10240, layers 2-47 64 routed experts of 1536 top-4
        # (`noaux_tc`: sigmoid, correction bias, one group, renormalised,
        # x1.8) plus one shared expert; untied head. The config's one MTP
        # block (`num_nextn_predict_layers` 1, checkpoint layer 47) is a
        # draft source and no part of the language model: not built, its
        # names never read (engine/weights._load_deepseek stops at
        # num_layers). Latent rows [c 512 | k_pe 64 | 0 x 64].
        name="glm-4.7-flash",
        vocab_size=154880,
        hidden_size=2048,
        intermediate_size=10240,
        num_layers=47,
        num_heads=20,
        num_kv_heads=20,
        head_dim=64,  # rope table width = qk_rope_head_dim
        rope_theta=1000000.0,
        max_position=202752,
        rms_eps=1e-5,
        latent_pad=128,
        moe_family="deepseek",
        num_experts=64,
        num_experts_per_token=4,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=1536,
        routed_scaling_factor=1.8,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=1,
        topk_group=1,
        routed_down_gain=0.1,
        kv_lora_rank=512,
        q_lora_rank=768,
        qk_nope_head_dim=192,
        qk_rope_head_dim=64,
        v_head_dim=256,
    ),
    "solar-open2-250b": ArchConfig(
        # upstage/Solar-Open2-250B config.json (`solar_open2`, 250B-A15B):
        # 48 layers in periods of one gated NoPE GQA layer (`gqa_layers` 0,
        # 4, ..., 44: 64 query / 8 KV heads of 128, `use_rope` false,
        # `use_gqa_gate`) and three KDA layers (64 heads of 128, conv 4,
        # `kda_allow_neg_eigval`, low-rank gates: `kda_use_full_proj` false);
        # every layer 320 routed experts of 1280 top-8 (sigmoid, correction
        # bias, one group, renormalised, x1) plus one shared expert.
        # `intermediate_size` 10240 is published and has no layer to live in
        # (`first_k_dense_replace` 0).
        name="solar-open2-250b",
        vocab_size=196608,
        hidden_size=4096,
        intermediate_size=10240,
        num_layers=48,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=10000.0,
        max_position=1048576,
        rms_eps=1e-5,
        attn_rope=False,
        attn_gate=True,
        routed_down_gain=0.1,
        layer_kinds=tuple("gqa" if i % 4 == 0 else "kda" for i in range(48)),
        kda_heads=64,
        kda_head_dim=128,
        kda_conv=4,
        kda_gate_rank=128,
        kda_neg_eigval=True,
        # fla's own step. At a hundredth of it (the default) a KDA layer of
        # this model doubles a perturbation of its input at 1,000 tokens of
        # context, and honest bfloat16 compute ends 14% off the float32
        # hidden state after eight layers: as much, in log-probabilities, as
        # separates the 20 best ids, so the benchmark's check lost the
        # reference's best id from the top 20 in 1 run of 12. At fla's step
        # it is 4.6% against 25% for a cache held one precision lower
        # (PERF.md section 6, PR 34).
        kda_init_dt=(1e-3, 1e-1),
        moe_family="deepseek",
        num_experts=320,
        num_experts_per_token=8,
        first_k_dense=0,
        n_shared_experts=1,
        moe_intermediate_size=1280,
        routed_scaling_factor=1.0,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=1,
        topk_group=1,
    ),
    "laguna-xs.2": ArchConfig(
        # poolside/Laguna-XS.2 config.json (`laguna`, 33.4B-A3B): 40 layers
        # in periods of one full-attention layer (48 query heads, half of
        # each head rotated under YaRN: theta 5e5, factor 64 from 4,096,
        # beta 64 / 1, amplitude 1.4158883) and three sliding-window layers
        # (64 query heads, a window of 512, the whole head rotated at theta
        # 1e4 unscaled), 8 KV heads of 128 everywhere, a per-head sigmoid
        # output gate (`gating`); layer 0 a dense SwiGLU of 8192, layers
        # 1-39 256 experts of 512 top-8 (sigmoid, selection bias, one group,
        # renormalised, x2.5 on the output) plus one shared expert of 512;
        # untied head. The window layers hold 512 rows a slot in a ring
        # (engine/state.py), the full layers pages.
        name="laguna-xs.2",
        vocab_size=100352,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=40,
        num_heads=48,
        swa_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_position=262144,
        rms_eps=1e-6,
        rope_theta=500000.0,
        rope_scaling="yarn",
        rope_scaling_factor=64.0,
        rope_original_max_position=4096,
        rope_beta_fast=64.0,
        rope_beta_slow=1.0,
        rope_attn_factor=1.4158883083359672,
        partial_rotary=0.5,
        rope_local_theta=10000.0,
        sliding_window=512,
        attn_gate="head",
        routed_down_gain=0.1,
        layer_kinds=("gqa", "swa", "swa", "swa") * 10,
        moe_family="deepseek",
        num_experts=256,
        num_experts_per_token=8,
        first_k_dense=1,
        n_shared_experts=1,
        moe_intermediate_size=512,
        routed_scaling_factor=2.5,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        n_group=1,
        topk_group=1,
    ),
    "lfm2-8b-a1b": ArchConfig(
        # LiquidAI/LFM2-8B-A1B config.json (`lfm2_moe`, 8.3B-A1.5B): 24
        # layers, 18 gated short convolutions (`conv_L_cache` 3, no bias) and
        # 6 GQA layers (32 query / 8 KV heads of 64, per-head q/k norms, rope
        # 1e6) at 2, 6, 10, 14, 18, 21; layers 0-1 a dense SwiGLU of 7168,
        # the other 22 with 32 experts of 1792 top-4 (sigmoid, selection
        # bias, renormalised with 1e-6, x1), no shared expert; tied head.
        name="lfm2-8b-a1b",
        vocab_size=65536,
        hidden_size=2048,
        intermediate_size=7168,
        num_layers=24,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=1000000.0,
        max_position=128000,
        rms_eps=1e-5,
        tie_embeddings=True,
        qk_norm=True,
        routed_down_gain=0.1,
        layer_kinds=tuple(
            "gqa" if i in (2, 6, 10, 14, 18, 21) else "conv"
            for i in range(24)),
        conv_cache=3,
        moe_family="deepseek",
        num_experts=32,
        num_experts_per_token=4,
        first_k_dense=2,
        moe_intermediate_size=1792,
        routed_scaling_factor=1.0,
        scoring_func="sigmoid",
        router_bias=True,
        norm_topk_prob=True,
        norm_topk_eps=1e-6,
    ),
    "granite-4.0-h-small": ArchConfig(
        # ibm-granite/granite-4.0-h-small config.json (`granitemoehybrid`,
        # 32B-A9B): 40 layers, 36 Mamba-2 (SSD) layers (128 heads of 64,
        # d_state 128, one group, conv 4 with a bias, chunk 256) and 4 NoPE
        # GQA layers (32 query / 8 KV heads of 128, scores x 1/128) at 5, 15,
        # 25, 35; every layer 72 experts of 768 top-10 (the 10 largest
        # logits, softmax over those 10) plus a shared MLP of 1536 (2 x 768
        # in this repo's fields); embeddings x 12, residual branches x 0.22,
        # logits / 16; tied head.
        name="granite-4.0-h-small",
        vocab_size=100352,
        hidden_size=4096,
        intermediate_size=768,
        num_layers=40,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=10000.0,
        max_position=131072,
        rms_eps=1e-5,
        tie_embeddings=True,
        attn_rope=False,
        query_scale=16384.0,  # attention_multiplier 0.0078125 = 16384^-0.5
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        routed_down_gain=0.1,
        layer_kinds=tuple(
            "gqa" if i in (5, 15, 25, 35) else "ssd" for i in range(40)),
        mamba_heads=128,
        mamba_head_dim=64,
        mamba_d_state=128,
        mamba_groups=1,
        mamba_conv=4,
        mamba_chunk=256,
        moe_family="mixtral",
        num_experts=72,
        num_experts_per_token=10,
        n_shared_experts=2,
        moe_intermediate_size=768,
    ),
    "ai21-jamba2-3b": ArchConfig(
        # ai21labs/AI21-Jamba2-3B config.json (`jamba`, 3B dense): 28 layers,
        # layer l a NoPE multi-query attention layer (20 query heads over ONE
        # K/V head of 128) iff l mod 14 == 7, so layers 7 and 21; the other 26
        # Mamba-1 layers (inner width 2 x 2560, 16 states, the step through a
        # rank-160 bottleneck, conv 4 with a bias, dt / B / C normed);
        # `num_experts` 1: every layer's MLP the dense SwiGLU of 8192; a
        # tied head.
        name="ai21-jamba2-3b",
        vocab_size=65536,
        hidden_size=2560,
        intermediate_size=8192,
        num_layers=28,
        num_heads=20,
        num_kv_heads=1,
        head_dim=128,
        max_position=262144,
        rms_eps=1e-6,
        tie_embeddings=True,
        attn_rope=False,
        layer_kinds=tuple(
            "gqa" if i % 14 == 7 else "s6" for i in range(28)),
        mamba_d_state=16,
        mamba_conv=4,
        mamba_expand=2,
        mamba_dt_rank=160,
    ),
}


def get_arch(name: str) -> ArchConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown architecture preset {name!r}; known: {sorted(PRESETS)}") from None
