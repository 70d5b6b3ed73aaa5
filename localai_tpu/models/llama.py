"""Llama-family decoder (Llama 2/3, Mistral, Qwen2, TinyLlama; MoE variant for
Mixtral) as pure-functional JAX.

Design (TPU-first, not a llama.cpp translation):
- Parameters are a pytree of stacked per-layer weights ([L, ...] leading axis)
  and the forward pass is a single `lax.scan` over layers — one traced layer
  body regardless of depth, which keeps compile time flat for 80-layer models
  and lets XLA pipeline HBM weight streaming against MXU compute.
- Two entry points: `prefill` (dense causal attention over a bucketed prompt)
  and `decode_step` (one token per active slot against the slot KV cache).
  These are the programs the engine jits with shardings; the reference's
  equivalent split is llama.cpp's prompt-processing vs token-generation phases
  (timings surfaced at backend/backend.proto:169-170).
- GQA, RoPE (linear/llama3 scaling), RMSNorm, SwiGLU; optional qkv bias
  (Qwen2) and sparse-MoE MLP (Mixtral) chosen statically from ArchConfig.

Weight-name parity with HF checkpoints is handled in io.py (safetensors
loader), not here.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from localai_tpu.models import quant
from localai_tpu.models.config import ArchConfig
from localai_tpu.models.quant import matmul, unembed_matmul
from localai_tpu.observe.scopes import (
    CONV_MIX, RING_WRITE, S6_MIX, SSD_MIX, WINDOW_MIX, scope)
from localai_tpu.ops.attention import (
    _merge_partials_mq,
    decode_attention,  # noqa: F401 — public, used by tests/benchmarks
    decode_attention_appended,
    decode_attention_appended_sp,
    decode_attention_windowed,
    decode_attention_windowed_paged,
    decode_attention_windowed_sp,
    paged_partials_mq,
    paged_prefill_partials,
    prefill_attention,
    prefix_window_attention,
)
from localai_tpu.ops.norm import rms_norm
from localai_tpu.ops.quant_matmul import (
    MOE_ALL_EXPERTS_MAX_ROWS,
    QUANT_PALLAS_MAX_ROWS,
    group_visits,
    grouped_engaged,
    grouped_moe_mm,
)
from localai_tpu.ops.rope import (
    apply_rope,
    mrope_angles,
    rope_frequencies,
    rope_frequencies_local,
    rope_query_amp,
    rope_rotate,
)

Params = dict[str, Any]


class KVCache(NamedTuple):
    """Slot KV cache: one contiguous region per batch slot.

    k, v: [L, B_slots, S_max, K_heads, head_dim]. Slot occupancy/lengths are
    tracked by the engine; shapes stay static under jit.

    Under MLA (DeepSeek-V2/V3, cfg.is_mla) the cache holds ONE latent row
    per token instead of per-head k/v: k is [L, B, S, 1, kv_lora_rank+rope]
    = [RMSNorm(c_kv) | RoPE(k_pe)] and v is zero-width ([..., 1, 0]) — the
    value read is served out of the same latent (absorbed-weight attention),
    so HBM per token is the published MLA number, not 2x it. Every write
    helper below is shape-generic, so the paged/windowed/fp8 machinery
    serves both layouts.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    # A hybrid model's (cfg.is_hybrid) per-slot recurrent state rides with
    # the cache through every program that carries it, donated with it:
    # state [Lk, B_slots, H, dk, dv] f32 and conv [Lk, B_slots, conv-1,
    # 3·H·dk] (the short conv's last inputs), Lk the KDA layers; a "conv"
    # model's row is conv [Lc, B_slots, conv_cache-1, D] alone, its state
    # None; k/v then hold rows for the cache_layers only (latent rows under
    # MLA, the ordinary [K, Hd] keys and values otherwise). None everywhere
    # else.
    state: Any = None
    conv: Any = None

    @staticmethod
    def zeros(cfg: ArchConfig, num_slots: int, max_seq: int, dtype=None) -> "KVCache":
        dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
        base = (cfg.cache_layers, num_slots, max_seq, cfg.cache_kv_heads)
        return KVCache(
            k=jnp.zeros(base + (cfg.cache_k_dim,), dtype),
            v=jnp.zeros(base + (cfg.cache_v_dim,), dtype),
        )


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _init_attn_layers(cfg: ArchConfig, rnd, keys, L: int,
                      cache_stack: bool = False) -> Params:
    """Attention + norm keys for a stack of L layers (standard or MLA). A
    hybrid model's layer stacks keep the two norms only; `cache_stack` builds
    its `cfg.cache_stack` (the cache layers' attention weights, no norms)."""
    dt = _dtype(cfg)
    D = cfg.hidden_size
    H, K, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    layers: Params = {"attn_norm": jnp.ones((L, D), dt),
                      "mlp_norm": jnp.ones((L, D), dt)}
    if cfg.is_hybrid and not cache_stack:
        return layers  # the attention weights live in their kinds' stacks
    if cfg.is_mla:
        r, rot = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        n, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank:
            layers["wq_a"] = rnd(next(keys), (L, D, cfg.q_lora_rank))
            layers["q_norm_a"] = jnp.ones((L, cfg.q_lora_rank), dt)
            layers["wq_b"] = rnd(next(keys), (L, cfg.q_lora_rank, H * (n + rot)))
        else:
            layers["wq"] = rnd(next(keys), (L, D, H * (n + rot)))
        layers["wkv_a"] = rnd(next(keys), (L, D, r + rot))
        layers["kv_norm"] = jnp.ones((L, r), dt)
        # HF kv_b_proj [H·(n+v), r] split per head: w_kb maps latent→k_nope,
        # w_vb maps latent→v. Stored in HF's [out, in] orientation so the
        # absorbed einsums contract the shared r axis directly.
        layers["w_kb"] = rnd(next(keys), (L, H, n, r))
        layers["w_vb"] = rnd(next(keys), (L, H, vd, r))
        layers["wo"] = rnd(next(keys), (L, H * vd, D))
        if cache_stack:
            del layers["attn_norm"], layers["mlp_norm"]
        return layers
    if cache_stack:
        layers = {}
    layers["wq"] = rnd(next(keys), (L, D, H * Hd))
    layers["wk"] = rnd(next(keys), (L, D, K * Hd))
    layers["wv"] = rnd(next(keys), (L, D, K * Hd))
    layers["wo"] = rnd(next(keys), (L, H * Hd, D),
                       init_gain(cfg, "wo", (L, H * Hd, D)))
    if cfg.attn_gate == "head":  # a scalar a head: small, never quantized
        layers["wg_head"] = rnd(next(keys), (L, D, H))
    elif cfg.attn_gate:  # element by element
        layers["wg"] = rnd(next(keys), (L, D, H * Hd))
    if cfg.post_norms:
        layers["post_attn_norm"] = jnp.ones((L, D), dt)
        layers["post_ffw_norm"] = jnp.ones((L, D), dt)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Hd), dt)
        layers["k_norm"] = jnp.ones((L, Hd), dt)
    elif cfg.qk_norm_full:
        layers["q_norm"] = jnp.ones((L, H * Hd), dt)
        layers["k_norm"] = jnp.ones((L, K * Hd), dt)
    if cfg.attn_qkv_bias:
        layers["bq"] = jnp.zeros((L, H * Hd), dt)
        layers["bk"] = jnp.zeros((L, K * Hd), dt)
        layers["bv"] = jnp.zeros((L, K * Hd), dt)
    return layers


def init_special(name: str, key, shape, step=None):
    """The leaves a normal draw at 0.02 would make degenerate (KDA's decay:
    A as fla's KimiDeltaAttention draws it, the step from `step`, the model's
    `kda_init_dt`, `KDA_DT` by default; the short conv; SSD's A and step drawn
    the same way, as Mamba-2 does, from `SSD_DT`, and its skip D at 1; S6's
    step from `SSD_DT` too, its skip at 1 and its A, held transposed [N, E],
    at 1..N a channel, Mamba-1's published init), float32. None for any
    other leaf."""
    if name == "ssm_D":  # the skip passes x on whole
        return jnp.ones(shape, jnp.float32)
    if name == "A_logT":  # A[c, n] = n + 1, every channel alike
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    if name == "conv_w":  # four taps that pass their input on at its size
        return jax.random.normal(key, shape, jnp.float32) * 0.5
    if name == "A_log":  # A in U(1, 16)
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":  # softplus^-1 of dt, log-uniform in `step`
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, *(jnp.log(x) for x in step or KDA_DT)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return None


# The decay step of a synthetic KDA layer: a hundredth of fla's [1e-3, 1e-1].
# With A in U(1, 16) a channel then forgets over some 10^3 to 10^5 tokens, as
# a long-context model's slow channels do: the regime the float32 state is
# kept for (held in bfloat16 it drifts by the root of the tokens remembered).
KDA_DT = (1e-5, 1e-3)
# The step of a synthetic SSD ("ssd") or S6 ("s6") layer: dt_min and dt_max
# as Mamba-2 and Mamba-1 both publish them. A KDA layer draws from `KDA_DT`
# (or its preset's `kda_init_dt`), a conv layer has no step.
SSD_DT = (1e-3, 1e-1)


def init_gain(cfg: ArchConfig, name: str, shape) -> float:
    """What the normal draw of a leaf is multiplied by besides `scale`. The
    routed experts' down-projection ([L, E, F, D]) is drawn at
    `cfg.routed_down_gain`, a tenth in the hybrid presets and
    GLM-4.7-Flash's: with random experts a pick that flips at a near-tie
    moves the stream by a quarter of a layer, and every rounding anywhere
    reads as routing noise.

    A model with Granite's scalar multipliers is drawn so that they are
    undone, and the random model is the random model of every other family:
    the embedding `logits_scaling` times as large (a random head then
    spreads its logits by scale x sqrt(D), where a sixteenth of it leaves
    every log-probability at log(1/V) and no rounding, honest or not, can be
    told from another), and every residual branch's out-projection by
    `embedding_multiplier` x `logits_scaling` / `residual_multiplier`, so
    that the stream is its layers' as much as in a model without them. With
    the embedding alone enlarged a tied head reads the token's own row out
    of the stream: every position predicted its own token with probability
    1 on the chip, every slot decoded the chat template's last token, all
    rows routed alike (19% of the held experts active) and a cache held one
    precision lower read like the honest one (PERF.md section 6, PR 46).
    All three are 1.0 exactly where the multipliers are 1."""
    if name == "embed":
        return float(cfg.logits_scaling)
    gain = 1.0
    if name in ("wo", "w_down", "shared_down"):
        gain = float(cfg.embedding_multiplier * cfg.logits_scaling
                     / cfg.residual_multiplier)
    if name == "w_down" and len(shape) == 4:
        gain *= cfg.routed_down_gain
    return gain


def _init_kda_layers(cfg: ArchConfig, rnd, keys, L: int) -> Params:
    """The KDA stack of a hybrid model: the int8-able matrices under the
    names the GQA stack gives its projections, the small leaves in the model
    dtype, the decay's two vectors in float32."""
    D, H, dk = cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim
    r, c = cfg.kda_gate_rank, cfg.kda_conv
    return {
        "wq": rnd(next(keys), (L, D, H * dk)),
        "wk": rnd(next(keys), (L, D, H * dk)),
        "wv": rnd(next(keys), (L, D, H * dk)),
        "wo": rnd(next(keys), (L, H * dk, D)),
        # depthwise causal conv over time of [q~ | k~ | v~], no bias
        "conv_w": init_special(
            "conv_w", next(keys), (L, c, 3 * H * dk)).astype(_dtype(cfg)),
        "f_down": rnd(next(keys), (L, D, r)),
        "f_up": rnd(next(keys), (L, r, H * dk)),
        "dt_bias": init_special("dt_bias", next(keys), (L, H * dk),
                                cfg.kda_init_dt),
        "A_log": init_special("A_log", next(keys), (L, H)),
        "w_beta": rnd(next(keys), (L, D, H)),
        "g_down": rnd(next(keys), (L, D, r)),
        "g_up": rnd(next(keys), (L, r, H * dk)),
        "o_norm": jnp.ones((L, dk), _dtype(cfg)),
    }


def _init_conv_layers(cfg: ArchConfig, rnd, keys, L: int) -> Params:
    """The stack of a hybrid model's gated short convolutions (LFM2): the
    in-projection to [b | c | z] and the out-projection int8-able, the
    depthwise taps in the model dtype."""
    D = cfg.hidden_size
    return {
        "w_in": rnd(next(keys), (L, D, 3 * D)),
        # tap conv_cache-1 on the current token, no bias
        "conv_w": init_special(
            "conv_w", next(keys), (L, cfg.conv_cache, D)).astype(_dtype(cfg)),
        "wo": rnd(next(keys), (L, D, D)),
    }


def _init_ssd_layers(cfg: ArchConfig, rnd, keys, L: int) -> Params:
    """The stack of a hybrid model's SSD (Mamba-2) layers: the in-projection
    to [z | xBC | dt], held as its three column blocks, and the
    out-projection int8-able, the depthwise taps, their bias and the gated
    norm's weight in the model dtype, the decay's two vectors and the skip
    in float32. Three leaves and not one [D, 2 d_inner + 2 G N + H]: at
    Granite-4.0-H's widths that is 16,768 = 131 x 128 columns, no multiple
    of 128 under it divides them, and the dequant-matmul walks such a block
    in 131 dots of 128 columns (19% of the HBM's rate on the chip, PERF.md
    section 6, PR 46); 8,192 | 8,448 | 128 each take whole-row blocks."""
    D, H = cfg.hidden_size, cfg.mamba_heads
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    gain = init_gain(cfg, "wo", (L, di, D))
    return {
        "w_z": rnd(next(keys), (L, D, di)),
        "w_xbc": rnd(next(keys), (L, D, cd)),
        "w_dt": rnd(next(keys), (L, D, H)),
        # depthwise causal conv over time of [x | B | C], tap mamba_conv-1 on
        # the current token, with a bias
        "conv_w": init_special(
            "conv_w", next(keys), (L, cfg.mamba_conv, cd)).astype(_dtype(cfg)),
        "conv_b": rnd(next(keys), (L, cd)),
        "dt_bias": init_special("dt_bias", next(keys), (L, H), SSD_DT),
        "A_log": init_special("A_log", next(keys), (L, H)),
        "ssm_D": init_special("ssm_D", next(keys), (L, H)),
        "o_norm": jnp.ones((L, di), _dtype(cfg)),
        "wo": rnd(next(keys), (L, di, D), gain),
    }


def _init_s6_layers(cfg: ArchConfig, rnd, keys, L: int) -> Params:
    """The stack of a hybrid model's S6 (Mamba-1, `jamba`) layers: the
    in-projection to [x | z], the projection to [dt | B | C], the step's
    up-projection and the out-projection int8-able; the depthwise taps, their
    bias and the three inner norms' weights in the model dtype; the step's
    bias, A and the skip in float32. A is held as log(-A) TRANSPOSED, [N, E]:
    the layout the state has and the decode kernel reads (a checkpoint's
    `A_log` [E, N] is transposed once, at load). `w_in` is one leaf:
    [D, 2 E] = 10,240 columns at the published widths take whole-row blocks
    of the dequant-matmul, and the split at E is a lane-tile boundary."""
    D, E, N = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_d_state
    R, dt = cfg.mamba_dt_rank, _dtype(cfg)
    return {
        "w_in": rnd(next(keys), (L, D, 2 * E)),
        # depthwise causal conv over time of x, tap mamba_conv-1 on the
        # current token, with a bias
        "conv_w": init_special(
            "conv_w", next(keys), (L, cfg.mamba_conv, E)).astype(dt),
        "conv_b": rnd(next(keys), (L, E)),
        "w_x": rnd(next(keys), (L, E, R + 2 * N)),
        "dt_norm": jnp.ones((L, R), dt),
        "b_norm": jnp.ones((L, N), dt),
        "c_norm": jnp.ones((L, N), dt),
        "w_dt": rnd(next(keys), (L, R, E)),
        "dt_bias": init_special("dt_bias", next(keys), (L, E), SSD_DT),
        "A_logT": init_special("A_logT", next(keys), (L, N, E)),
        "ssm_D": init_special("ssm_D", next(keys), (L, E)),
        "wo": rnd(next(keys), (L, E, D), init_gain(cfg, "wo", (L, E, D))),
    }


def init_params(cfg: ArchConfig, key: jnp.ndarray, scale: float = 0.02) -> Params:
    """Random init with HF-compatible tree structure (stacked layers).

    DeepSeek-style models (first_k_dense > 0) split into two stacks:
    params["dense_layers"] holds the leading dense-MLP layers and
    params["layers"] the MoE layers (+ shared experts) — `_scan_layers`
    runs them as two scans with a shared layer body.
    """
    dt = _dtype(cfg)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = iter(jax.random.split(key, 32))

    def rnd(k, shape, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * (scale * gain)).astype(dt)

    kd = cfg.first_k_dense if cfg.is_moe else 0
    Lm = L - kd
    layers = _init_attn_layers(cfg, rnd, keys, Lm)
    if cfg.is_moe:
        E, Fm = cfg.num_experts, cfg.moe_inter_size
        Eh = cfg.experts_here  # the router scores all E, the stacks hold Eh
        layers["router"] = rnd(next(keys), (Lm, D, E))
        if cfg.router_bias:
            layers["router_bias"] = jnp.zeros((Lm, E), jnp.float32)
        layers["w_gate"] = rnd(next(keys), (Lm, Eh, D, Fm))
        layers["w_up"] = rnd(next(keys), (Lm, Eh, D, Fm))
        layers["w_down"] = rnd(next(keys), (Lm, Eh, Fm, D),
                               init_gain(cfg, "w_down", (Lm, Eh, Fm, D)))
        if cfg.n_shared_experts:
            Fs = cfg.n_shared_experts * Fm
            layers["shared_gate"] = rnd(next(keys), (Lm, D, Fs))
            layers["shared_up"] = rnd(next(keys), (Lm, D, Fs))
            layers["shared_down"] = rnd(next(keys), (Lm, Fs, D),
                                        init_gain(cfg, "shared_down", (Lm, Fs, D)))
    else:
        layers["w_gate"] = rnd(next(keys), (L, D, F))
        layers["w_up"] = rnd(next(keys), (L, D, F))
        layers["w_down"] = rnd(next(keys), (L, F, D))

    params: Params = {
        "embed": rnd(next(keys), (cfg.vocab_size, D),
                     init_gain(cfg, "embed", (cfg.vocab_size, D))),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if kd:
        dense = _init_attn_layers(cfg, rnd, keys, kd)
        dense["w_gate"] = rnd(next(keys), (kd, D, F))
        dense["w_up"] = rnd(next(keys), (kd, D, F))
        dense["w_down"] = rnd(next(keys), (kd, F, D))
        params["dense_layers"] = dense
    if not cfg.tie_embeddings:
        params["lm_head"] = rnd(next(keys), (cfg.vocab_size, D))
    if cfg.is_hybrid:
        hk = iter(jax.random.split(jax.random.fold_in(key, 1), 32))
        Lr = len(cfg.recurrent_layers)
        stack = cfg.recurrent_stack  # "<kind>_layers"
        params[stack] = RECURRENT[cfg.recurrent_kind].init(cfg, rnd, hk, Lr)
        cache_stack = cfg.cache_stack  # "mla_layers" | "gqa_layers"
        params[cache_stack] = _init_attn_layers(
            cfg, rnd, hk, cfg.cache_layers, cache_stack=True)
    return params


def _scan_layers(cfg: ArchConfig, params: Params, h, layer_fn, extras=()):
    """Scan the layer stack with a shared body. Homogeneous models run one
    scan; DeepSeek layouts run the dense-prefix stack then the MoE stack
    (the body's MLP branch keys statically on each stack's param tree), and
    per-layer outputs are re-concatenated to one [L, ...] stack. `extras`
    are per-layer arrays (caches) with a leading axis over ALL L layers:
    each stack's scan reads them whole at its global layer index, so a
    two-stack model cuts no `[:kd]` copy out of the K/V pool."""
    L = cfg.num_layers
    kd = cfg.first_k_dense if ("dense_layers" in params) else 0
    if kd == 0:
        return _scan_stack(layer_fn, h, params["layers"], 0, L, extras)
    h, out_d = _scan_stack(layer_fn, h, params["dense_layers"], 0, kd, extras)
    h, out_m = _scan_stack(layer_fn, h, params["layers"], kd, L, extras)
    with scope("attention/cache_write"):  # the two stacks' rows, one stack
        out = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), out_d, out_m)
    return h, out


def _rides_stacked(a) -> bool:
    """The scan's one leaf test: an operand its consumer reads out of the
    stack itself (ops/stacked.py) — a quantized weight leaf, or an extra
    the caller marked (`quant.StackedLayer(pool)`: the paged K/V pool)."""
    return isinstance(a, quant.StackedLayer) or quant.is_quantized(a)


def _take_layer(tree, i):
    """Layer i of a tree of per-layer stacks: a leaf that rides stacked as
    the stack and the index (`quant.StackedLayer`), any other sliced."""
    def take(a):
        if _rides_stacked(a):
            return quant.StackedLayer(a, i)
        return jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False, allow_negative_indices=False)

    return jax.tree.map(take, tree, is_leaf=_rides_stacked)


def _scan_stack(layer_fn, h, stack, lo: int, hi: int, extras):
    """`lax.scan` of `layer_fn` over layers lo..hi: `stack` holds just those
    layers' weights, each extra all the model's. The body takes its own
    slice of the stacked weights and of each per-layer extra (what scan does
    with `xs`), so that the two slices carry a name into the compiled
    program: `layer_weights` and `layer_kv_pool` are what a profile shows as
    per-layer copies out of the stacked arrays (PERF.md §5).

    What `_rides_stacked` is not sliced here: the body gets a
    `quant.StackedLayer`, the whole stack plus the index, and the consumer
    (`quant.matmul`, `_moe_mm`, ops/attention's paged dispatchers) either
    hands both to its Pallas kernel, which then reads the layer in place, or
    slices at its own call site (`quant.layer_slice`) in front of the XLA
    form."""

    def body(carry, _):
        # `i` is carried beside h, not sliced out of an arange: the index of
        # every slice below is then a loop counter, as scan's own is.
        h, i = carry
        li = i + lo if lo else i  # the model's layer number
        with jax.named_scope("layer_weights"):
            lp = _take_layer(stack, i)
        with jax.named_scope("layer_kv_pool"):
            ex = _take_layer(tuple(extras), li)
        h, out = layer_fn(h, (lp, li) + ex)
        return (h, i + 1), out

    # `layer` owns what no scope inside it claims: norms, residual adds, the
    # loop itself (observe/scopes.py).
    with scope("layer"):
        (h, _), out = jax.lax.scan(
            body, (h, jnp.int32(0)), None, length=hi - lo)
    return h, out


def _paged_pool(cache: KVCache):
    """A paged K/V pool as scan extras: marked to ride stacked, so the
    paged-attention kernel reads its layer's pages out of the whole
    [L, P, page, K, D] pool and no per-layer copy of it is made."""
    return quant.StackedLayer(cache.k), quant.StackedLayer(cache.v)


def _moe_mm(x: jnp.ndarray, w, sub: str, impl: str = "auto",
            mesh=None) -> jnp.ndarray:
    """Per-expert matmul for plain or quantized expert weights. Quantized
    decode-shape calls dispatch to the fused Pallas dequant-matmul kernels
    (ops/quant_matmul, ISSUE 9); the einsum forms below stay the oracle."""
    if quant.is_quantized(w):
        from localai_tpu.ops.quant_matmul import dispatch_moe_mm

        y = dispatch_moe_mm(x, dict(w), sub, impl=impl, mesh=mesh,
                            layer=quant.layer_of(w))
        if y is not None:
            return y
        w = quant.layer_slice(w)
        if "q" in w:
            out = jnp.einsum(sub, x, w["q"].astype(x.dtype))
            return out * w["s"].astype(x.dtype)[..., 0, :]
        return _moe_grouped_mm(x, w, sub)
    return jnp.einsum(sub, x, w)


def _moe_grouped_mm(x: jnp.ndarray, w: dict, sub: str) -> jnp.ndarray:
    """Grouped int4/int8 expert weights [E, G, gs(, packed), out] for the two
    MoE einsum shapes (see quant.grouped_matmul for the dequant math)."""
    from localai_tpu.models.quant import _grouped_values

    qv = _grouped_values(w, x.dtype)  # [E, G, gs, out]
    s = w["gs"].astype(x.dtype)[..., 0, :]  # [E, G, out]
    z = w["gz"].astype(x.dtype)[..., 0, :] if "gz" in w else None
    e, g, gs, n_out = qv.shape
    if sub == "...d,edf->...ef":  # x [..., D] shared across experts
        xg = x.reshape(*x.shape[:-1], g, gs)
        y = jnp.einsum("...gi,egin->...egn", xg, qv)
        out = (y * s).sum(axis=-2)
        if z is not None:
            out = out - jnp.einsum("...g,egn->...en", xg.sum(-1), z)
        return out
    if sub == "...ef,efd->...ed":  # x already per-expert [..., E, F]
        xg = x.reshape(*x.shape[:-2], e, g, gs)
        y = jnp.einsum("...egi,egin->...egn", xg, qv)
        out = (y * s).sum(axis=-2)
        if z is not None:
            out = out - jnp.einsum("...eg,egn->...en", xg.sum(-1), z)
        return out
    raise ValueError(f"unsupported MoE einsum {sub!r} for grouped weights")


def _moe_route(cfg: ArchConfig, lp: Params, x: jnp.ndarray):
    """Top-k router dispatch: returns (weights [..., k] f32, sel [..., k])."""
    if cfg.moe_family == "deepseek":
        return _deepseek_route(cfg, lp, x)
    router_logits = (x @ lp["router"]).astype(jnp.float32)  # [..., E]
    weights, sel = jax.lax.top_k(router_logits, cfg.num_experts_per_token)
    return jax.nn.softmax(weights, axis=-1), sel


def _deepseek_route(cfg: ArchConfig, lp: Params, x: jnp.ndarray):
    """DeepSeek-V2/V3 router (HF DeepseekV2MoEGate / DeepseekV3TopkRouter
    semantics): score ALL experts in f32 — softmax (V2) or sigmoid (V3) —
    then select top-k, optionally restricted to the topk_group best of
    n_group expert groups. V3 biases SELECTION by a learned per-expert
    correction (e_score_correction_bias) but weights by the unbiased scores,
    renormalized when norm_topk_prob. Returns weights already scaled by
    routed_scaling_factor."""
    E, k = cfg.num_experts, cfg.num_experts_per_token
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), lp["router"].astype(jnp.float32)
    )
    sigmoid = cfg.scoring_func == "sigmoid"
    scores = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    choice = scores + lp["router_bias"] if "router_bias" in lp else scores
    if cfg.n_group > 1:
        g = cfg.n_group
        cg = choice.reshape(*choice.shape[:-1], g, E // g)
        if sigmoid:  # V3: a group's score is the sum of its top-2 biased scores
            gscore = jax.lax.top_k(cg, 2)[0].sum(axis=-1)
        else:  # V2 group_limited_greedy: group max
            gscore = cg.max(axis=-1)
        _, gidx = jax.lax.top_k(gscore, cfg.topk_group)  # [..., topk_group]
        gmask = jax.nn.one_hot(gidx, g, dtype=jnp.float32).sum(axis=-2)  # [..., g]
        keep = jnp.repeat(gmask, E // g, axis=-1) > 0
        choice = jnp.where(keep, choice, 0.0)
    _, sel = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.norm_topk_prob and k > 1:
        weights = weights / (weights.sum(axis=-1, keepdims=True)
                             + cfg.norm_topk_eps)
        # Original DeepseekV2MoEGate: normalization REPLACES the scaling
        # factor on the softmax path; V3 (sigmoid) normalizes AND scales.
        if sigmoid:
            weights = weights * cfg.routed_scaling_factor
    else:
        weights = weights * cfg.routed_scaling_factor
    return weights, sel


def _held_route(cfg: ArchConfig, route):
    """The router's choice as the expert stacks held here see it
    (cfg.expert_share): ids relative to the first held expert, a pick that
    landed elsewhere as the id `experts_here` (one past the stack: no row of
    a one-hot, the last group of a sort) with weight 0. The weights were
    normalised over all the picks before, so what the held experts add is
    their part of the whole layer's sum."""
    weights, sel = route
    n = cfg.experts_here
    local = sel - cfg.expert_lo
    here = (local >= 0) & (local < n)
    return jnp.where(here, weights, 0.0), jnp.where(here, local, n)


def _moe_dense(cfg: ArchConfig, lp: Params, x: jnp.ndarray,
               mesh=None, route=None) -> jnp.ndarray:
    """All-experts MoE: every expert runs on every token, outputs combined by
    routing weight. FLOPs and temporaries ∝ rows × E, but quantized expert
    stacks go through the fused Pallas dequant-matmul without any
    dequantized copy, and it is trivially shardable over "ep". Decode
    batches are tiny and weight-HBM-bound (every expert's weights are read
    regardless), so for quantized decode this is near-optimal; wide row
    counts take `_moe_ragged` (see `_mlp`). `route` = (weights, sel) when
    the caller already ran the router."""
    E = cfg.experts_here
    qk = cfg.quant_kernel
    weights, sel = route or _moe_route(cfg, lp, x)
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.float32)  # [..., topk, E]
    combine = jnp.einsum("...te,...t->...e", onehot, weights)
    gate = _act(cfg, _moe_mm(x, lp["w_gate"], "...d,edf->...ef", qk, mesh))
    up = _moe_mm(x, lp["w_up"], "...d,edf->...ef", qk, mesh)
    expert_out = _moe_mm(gate * up, lp["w_down"], "...ef,efd->...ed", qk, mesh)  # [..., E, D]
    return jnp.einsum("...ed,...e->...d", expert_out.astype(jnp.float32), combine).astype(x.dtype)


def _ragged_mm(xg: jnp.ndarray, w, sizes: jnp.ndarray,
               group: jnp.ndarray) -> jnp.ndarray:
    """`lax.ragged_dot` of expert-sorted rows xg [M, in] with an expert stack
    w [G, in, out], plain or quantized; `group` [M] is each row's group. The
    XLA form of `_moe_ragged`'s grouped matmul, and the oracle of the Pallas
    kernel that serves a quantized stack on the chip
    (ops/quant_matmul.grouped_moe_mm).
    Flat int8 converts in the operand and scales each ROW afterwards by its
    own expert's per-channel scale (exactly x @ (q·s): the scale does not
    depend on the contracted axis). The grouped forms scale per in-group, so
    their layer is dequantized in front of the dot: a temporary of one
    layer's stack in x's dtype, whatever the rows."""
    if not isinstance(w, dict):
        return jax.lax.ragged_dot(xg, w, sizes)
    if "q" in w:
        y = jax.lax.ragged_dot(xg, w["q"].astype(xg.dtype), sizes)
        return y * jnp.take(w["s"][..., 0, :], group, axis=0).astype(y.dtype)
    return jax.lax.ragged_dot(
        xg, quant.dequantize_tensor(w).astype(xg.dtype), sizes)


def _expert_stack(w):
    """One layer's expert weights [E, ...] for the XLA forms: a quantized
    leaf still stacked over layers is sliced here (and counted as a sliced
    site, like every other layer matmul that does not take the stack)."""
    if not quant.is_quantized(w):
        return w
    from localai_tpu.ops.stacked import note_site

    note_site(stacked=False)
    return quant.layer_slice(w)


def _moe_ragged(cfg: ArchConfig, lp: Params, x: jnp.ndarray, route=None,
                grouped: bool = False, rows=None) -> jnp.ndarray:
    """Exact top-k MoE via a sort and one grouped matmul a projection:
    per-token FLOPs ∝ top_k, not E (4× fewer than dense for Mixtral
    top-2-of-8), and temporaries ∝ rows × top_k.

    The (token, choice) pairs are stably sorted by expert id so each expert's
    rows are contiguous, then one grouped matmul per projection runs all
    experts without any capacity factor — no token is ever dropped, so the
    output is bit-comparable to the dense branch (up to f32 reduction order).
    The reference gets this for free from llama.cpp's per-expert CPU loops
    (ggml MoE graph). Two forms of the grouped matmul, `_mlp`'s to choose:
    - `grouped` (a quantized stack on one TPU chip) → the Pallas kernel
      `int8_grouped_matmul` / `int4_grouped_matmul` walks the sorted rows'
      groups over the stack as it is stored, still stacked over layers
      (ops/quant_matmul `grouped_moe_mm`): each visited expert's int8 bytes
      cross the HBM once, no dequantized copy, no per-row scale gather; an
      expert no row chose is not read, and under an expert share the picks
      held elsewhere (they sort last, `_held_route`) are not visited at all;
    - else (plain experts, tp > 1, off the TPU, impl xla) → `lax.ragged_dot`
      on the layer's slice (`_ragged_mms`), the oracle.
    Either way each token's k rows then come back beside each other by the
    inverse permutation and are summed under their weights (a scatter-add
    by token was 27-45% of this path at 8,192 sorted rows on the v5e: PR 38).
    `route` = (weights, sel) when the caller already ran the router; `rows`,
    a list, receives [2] int32 of the grouped kernel's walk: the sorted rows
    it was compiled for and those of them in a held group.
    """
    E, k = cfg.experts_here, cfg.num_experts_per_token
    lead, D = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    weights, sel = route or _moe_route(cfg, lp, xf)
    M = N * k
    e_flat = sel.reshape(M)
    order = jnp.argsort(e_flat, stable=True)  # expert-major, token-minor
    # source token of each sorted row; every index is a row's own, so no
    # fill pass behind the gather
    xg = jnp.take(xf, order // k, axis=0, mode="clip")  # [M, D]
    if grouped:
        # rows per held expert (a pick held elsewhere, id E, counts nowhere)
        walk = group_visits(
            jax.nn.one_hot(e_flat, E, dtype=jnp.int32).sum(axis=0), M)
        if rows is not None:
            rows.append(jnp.stack([jnp.int32(M), walk.off[-1]]))

        def mm(a, name):
            w = lp[name]
            return grouped_moe_mm(a, dict(w), walk, layer=quant.layer_of(w))
    else:
        mm = _ragged_mms(cfg, lp, e_flat, order)
    gate = _act(cfg, mm(xg, "w_gate"))
    up = mm(xg, "w_up")
    dn = mm((gate * up).astype(xg.dtype), "w_down")  # [M, D]
    back = jnp.take(dn, jnp.argsort(order), axis=0, mode="clip")
    back = back.reshape(N, k, D).astype(jnp.float32)
    if cfg.expert_share is not None:
        # The kernel wrote no row of a pick held elsewhere: what stands
        # there is not a number, and 0 x NaN is NaN.
        back = jnp.where((sel.reshape(N, k) < E)[..., None], back, 0)
    y = (back * weights.reshape(N, k, 1)).sum(axis=1)
    return y.reshape(*lead, D).astype(x.dtype)


def _ragged_mms(cfg: ArchConfig, lp: Params, e_flat: jnp.ndarray,
                order: jnp.ndarray):
    """The grouped matmul of `_moe_ragged` as `lax.ragged_dot` on the layer's
    expert stack, plain or quantized (`_ragged_mm`); on TPU ragged_dot maps
    the grouped contraction onto the MXU with static shapes. e_flat [M] each
    (token, choice)'s expert, `order` the sort. Returns mm(rows [M, in],
    name) -> [M, out], name one of the three projections' in lp."""
    E, M = cfg.experts_here, e_flat.shape[0]
    group = jnp.take(e_flat, order)  # [M] expert of each sorted row
    ws = {n: _expert_stack(lp[n]) for n in ("w_gate", "w_up", "w_down")}
    if M < E:
        # Decode-scale batches can touch at most M of E experts. Gathering
        # just the active experts' weights bounds HBM weight traffic by
        # 2·M/E of the dense read — the mechanism that makes top-8-of-256
        # (DeepSeek-R1 class) MoE decode genuinely sparse, where
        # top-2-of-8 at batch ≥ 8 touches every expert anyway. `uniq` is
        # sorted, so the expert-major row order maps 1:1 onto gathered
        # group slots; pad slots (fill E, clipped for the gather) count
        # zero rows and contribute nothing.
        uniq = jnp.unique(e_flat, size=M, fill_value=E)  # [M] sorted ids
        group = jnp.searchsorted(uniq, group)  # slot among the gathered
        gs = jnp.bincount(group, length=M)
        gidx = jnp.minimum(uniq, E - 1)
        ws = jax.tree.map(lambda a: jnp.take(a, gidx, axis=0), ws)
    else:
        gs = jnp.bincount(e_flat, length=E)  # rows per expert (sums to M)
    if cfg.expert_share is not None:
        # Picks of experts held elsewhere sort last (`_held_route`). What
        # `ragged_dot` does with rows that lie in no group is not specified,
        # so they ride in the last one; the combine leaves them out.
        group = jnp.minimum(group, gs.shape[0] - 1)
        gs = gs.at[-1].add(M - gs.sum())
    return lambda a, name: _ragged_mm(a, ws[name], gs, group)


def _moe_capacity(cfg: ArchConfig, lp: Params, x: jnp.ndarray, block: int = 1024) -> jnp.ndarray:
    """GShard-style capacity-bucketed dispatch for expert-parallel meshes.

    Tokens are chunked into blocks; each block builds one-hot dispatch/combine
    tensors [Nb, E, C] with C = ceil(k·Nb/E · capacity_factor), so the expert
    contraction 'ecd,edf->ecf' has a static [E, C, D] operand whose E axis the
    SPMD partitioner places on the chips holding the "ep"-sharded weights —
    each chip computes only its local experts' rows and the combine einsum
    psums the outputs back. Total expert FLOPs ∝ k·cf, not E. Tokens past an
    expert's capacity are dropped (their routing weight renormalizes over the
    kept choices; if every choice drops, the residual passes through) — the
    standard GShard trade; capacity_factor=2 makes drops rare at inference.
    """
    E, k = cfg.num_experts, cfg.num_experts_per_token
    lead, D = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    Nb = min(N, block)
    nblk = -(-N // Nb)
    pad = nblk * Nb - N
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, D), xf.dtype)], axis=0)
    C = max(k, int(-(-k * Nb * cfg.moe_capacity_factor // E)))
    C = min(C, Nb)

    def blk(xb):  # [Nb, D]
        w, sel = _moe_route(cfg, lp, xb)  # [Nb, k] f32 / int
        oh = jax.nn.one_hot(sel, E, dtype=jnp.int32)  # [Nb, k, E]
        # Position of each (token, choice) in its expert's queue, in
        # flattened token-major order (earlier tokens win capacity).
        pos = jnp.cumsum(oh.reshape(Nb * k, E), axis=0).reshape(Nb, k, E) * oh - 1
        keep = (pos >= 0) & (pos < C)  # [Nb, k, E]
        slot = jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=xb.dtype)
        slot = slot * keep[..., None].astype(xb.dtype)  # [Nb, k, E, C]
        disp = slot.sum(axis=1)  # [Nb, E, C] 0/1
        kept_k = keep.sum(axis=-1).astype(jnp.float32)  # [Nb, k] 0/1
        # Drop handling preserves each token's ORIGINAL routing-weight mass
        # (kept weights scale by total/kept): for Mixtral (softmaxed, total
        # = 1) this is the classic renormalization; for DeepSeek the
        # weights deliberately do NOT sum to 1 (sigmoid + routed_scaling),
        # so normalizing to 1 would corrupt every MoE output even with
        # nothing dropped.
        total = w.sum(axis=-1, keepdims=True)
        denom = jnp.maximum((w * kept_k).sum(axis=-1, keepdims=True), 1e-9)
        wr = w * kept_k * (total / denom)  # mass-preserving over kept choices
        comb = jnp.einsum("nk,nkec->nec", wr, slot.astype(jnp.float32))
        xe = jnp.einsum("nec,nd->ecd", disp, xb)  # [E, C, D]
        gate = _act(cfg, jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"]))
        up = jnp.einsum("ecd,edf->ecf", xe, lp["w_up"])
        dn = jnp.einsum("ecf,efd->ecd", gate * up, lp["w_down"])
        return jnp.einsum("nec,ecd->nd", comb, dn.astype(jnp.float32))

    y = jax.lax.map(blk, xf.reshape(nblk, Nb, D)).reshape(nblk * Nb, D)[:N]
    return y.reshape(*lead, D).astype(x.dtype)


def _lora_add(cfg: ArchConfig, lora, key: str, x: jnp.ndarray,
              y: jnp.ndarray, part: str, mesh=None) -> jnp.ndarray:
    """y + the per-row ragged adapter delta for one target projection
    (multi-tenant runtime LoRA, ISSUE 10 / docs/LORA_SERVING.md): unmerged
    B·(A·x) beside the base matmul, so the base weights stay shared (and
    possibly int8/int4-quantized) while each row's tenant rides its own
    rank-r factors. lora = (per-layer stacks, ids) or None; stacks is the
    layer-scan slice {key: {"a": [NA, in, R], "b": [NA, R, out]}}; id 0 is
    the all-zero null adapter (exact no-op for adapter-less rows)."""
    if lora is None:
        return y
    la, ids = lora
    entry = la.get(key)
    if entry is None:
        return y
    from localai_tpu.ops.lora_matmul import lora_delta

    return y + lora_delta(
        x, entry, ids, impl=cfg.lora_kernel, mesh=mesh, part=part
    )


def _mlp(cfg: ArchConfig, lp: Params, x: jnp.ndarray, ep: int = 1,
         mesh=None, lora=None, picks=None, admit=None) -> jnp.ndarray:
    """SwiGLU MLP; dense or sparse-MoE (Mixtral/DeepSeek/OLMoE top-k routing).

    x: [..., D]. MoE is detected per-stack ("router" in lp) so DeepSeek's
    dense-prefix layers run the plain branch under the same body. MoE picks
    its implementation HERE and nowhere else, statically, from what the
    trace sees (the expert leaf, the rows, the mesh, the entry point):
    - quantized experts at few rows → all-experts `_moe_dense`: every
      expert's bytes are read whatever the routing, the stacked Pallas kernel
      reads its layer in place, and rows × E is small. "Few" is the stacked
      kernel's row limit QUANT_PALLAS_MAX_ROWS for a decode block and a
      verify chunk (B·(k+1) rows) whatever serves the rows above it, and
      for an admission program too where only `ragged_dot` does; an
      admission program (`admit`) whose wider rows the grouped Pallas kernel
      serves (`grouped_engaged`) leaves all-experts at the measured
      crossover, MOE_ALL_EXPERTS_MAX_ROWS. Also under ep > 1 (shards over
      "ep" as it is);
    - quantized experts at wider rows (admission groups, long prompts; a
      decode block or verify chunk above 256 rows) → sort + grouped matmul
      on the quantized stack (`_moe_ragged`: the grouped Pallas kernel, or
      `ragged_dot` where it does not engage): FLOPs ∝ top_k and temporaries
      ∝ rows × top_k, where all-experts builds rows × E;
    - plain experts, ep > 1 → GShard capacity dispatch (shards over "ep");
    - plain experts otherwise → the same sort + ragged_dot (at decode batch
      sizes only the ACTIVE experts' weights are gathered, which is where
      top-8-of-256 models win — see _ragged_mms).
    DeepSeek MoE layers add an always-on shared-expert MLP (HF
    DeepseekV3MoE.shared_experts). `picks`, a list, receives the router's
    chosen expert ids [..., k] (the engine's routing counters). `admit`, a
    list, is given by the admission entry points (prefill, a cached tail, a
    prefill chunk) and by no decode entry point; it receives the grouped
    kernel's rows (`_moe_ragged`), nothing where that kernel did not run.
    """
    qk = cfg.quant_kernel
    if "router" not in lp:
        with scope("mlp/dense"):
            gate = _act(cfg, _lora_add(
                cfg, lora, "w_gate", x,
                matmul(x, lp["w_gate"], qk, mesh, "col"), "col", mesh,
            ))
            up = _lora_add(
                cfg, lora, "w_up", x, matmul(x, lp["w_up"], qk, mesh, "col"),
                "col", mesh,
            )
            gu = gate * up
            return _lora_add(
                cfg, lora, "w_down", gu,
                matmul(gu, lp["w_down"], qk, mesh, "row"), "row", mesh,
            ).astype(x.dtype)
    quantized = quant.is_quantized(lp["w_gate"])
    if ep > 1 and not quantized:
        with scope("mlp/experts"):  # routes block by block, inside
            y = _moe_capacity(cfg, lp, x)
    else:
        with scope("mlp/router"):
            route = _moe_route(cfg, lp, x)
            if cfg.expert_share is not None:
                route = _held_route(cfg, route)
        if picks is not None:
            picks.append(route[1])
        rows = x.size // x.shape[-1]
        w = lp["w_gate"]
        grouped = quantized and grouped_engaged(
            x, dict(w), qk, mesh, quant.layer_of(w))
        few = (MOE_ALL_EXPERTS_MAX_ROWS if grouped and admit is not None
               else QUANT_PALLAS_MAX_ROWS)
        with scope("mlp/experts"):
            if quantized and (ep > 1 or rows <= few):
                y = _moe_dense(cfg, lp, x, mesh=mesh, route=route)
            else:
                y = _moe_ragged(cfg, lp, x, route=route, grouped=grouped,
                                rows=admit)
    if "shared_gate" in lp:
        with scope("mlp/shared"):
            sg = _act(cfg, matmul(x, lp["shared_gate"], qk, mesh, "col"))
            y = y + matmul(sg * matmul(x, lp["shared_up"], qk, mesh, "col"),
                           lp["shared_down"], qk, mesh, "row").astype(x.dtype)
    return y


@scope("attention/out")
def _attn_out(cfg: ArchConfig, lp: Params, attn_flat: jnp.ndarray,
              mesh=None, lora=None) -> jnp.ndarray:
    """Output projection + optional gemma-2 post-attention sandwich norm.
    Shared by every layer body so per-arch structure changes in ONE place."""
    a = _lora_add(
        cfg, lora, "wo", attn_flat,
        matmul(attn_flat, lp["wo"], cfg.quant_kernel, mesh, "row"),
        "row", mesh,
    )
    if cfg.post_norms:
        a = rms_norm(a, lp["post_attn_norm"], cfg.rms_eps)
    return a


@scope("attention/out")
def _attn_gated(cfg: ArchConfig, lp: Params, x: jnp.ndarray,
                attn_flat: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """The output gate of gated attention (`cfg.attn_gate`): the attention
    output times sigmoid(x W_g), element by element over heads x head width
    or ("head") one scalar a head, x the layer's normed input; what
    `_attn_out` then projects."""
    head = cfg.attn_gate == "head"
    g = jax.nn.sigmoid(matmul(x, lp["wg_head" if head else "wg"],
                              cfg.quant_kernel, mesh, "col").astype(jnp.float32))
    if head:  # [..., H] over [..., H·Hd]
        g = jnp.repeat(g, cfg.head_dim_, axis=-1)
    return (attn_flat.astype(jnp.float32) * g).astype(attn_flat.dtype)


def _mlp_out(cfg: ArchConfig, lp: Params, x: jnp.ndarray, ep: int = 1,
             mesh=None, lora=None, picks=None, admit=None) -> jnp.ndarray:
    """MLP (scoped `mlp/...` where `_mlp` picks its form) + optional gemma-2
    post-feedforward sandwich norm (the enclosing `layer`'s, as every norm)."""
    m = _mlp(cfg, lp, x, ep, mesh=mesh, lora=lora, picks=picks, admit=admit)
    if cfg.post_norms:
        m = rms_norm(m, lp["post_ffw_norm"], cfg.rms_eps)
    return m


def _layer_sliding(cfg: ArchConfig, li: jnp.ndarray):
    """Which layers slide: all but those with li % pattern == phase, the
    config's `sliding_phase` (None: pattern - 1, the period ENDS with its
    global layer. Gemma-2 alternates, pattern 2: even layers slide, odd
    attend globally; gemma-3 runs 5 local : 1 global, pattern 6). Returns a
    traced bool scalar, None when the arch has no sliding windows, and a
    static `np.True_` where every layer slides (pattern 1: a window kind's
    view of its model, `ArchConfig.kind_view`), which an op may choose its
    kernel by."""
    if not cfg.sliding_window:
        return None
    p = cfg.sliding_pattern
    phase = p - 1 if cfg.sliding_phase is None else cfg.sliding_phase
    if p == 1 and phase != 0:
        import numpy as np

        return np.True_
    return (li % p) != phase


def _layer_inv_freq(cfg: ArchConfig, inv_global, inv_local, li):
    """Per-layer rope schedule: gemma-3's sliding layers run their own
    unscaled local base while global layers use rope_theta (+ scaling)."""
    if inv_local is None:
        return inv_global
    sliding = _layer_sliding(cfg, li)
    return jnp.where(sliding, inv_local, inv_global)


@scope("attention/proj")
def _attn_proj_qkv(cfg: ArchConfig, lp: Params, x: jnp.ndarray, mesh=None,
                   lora=None):
    """x: [..., D] -> q [..., H, Hd], k/v [..., K, Hd]."""
    H, K, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    qk = cfg.quant_kernel
    q = _lora_add(cfg, lora, "wq", x, matmul(x, lp["wq"], qk, mesh, "col"),
                  "col", mesh)
    k = _lora_add(cfg, lora, "wk", x, matmul(x, lp["wk"], qk, mesh, "col"),
                  "col", mesh)
    v = _lora_add(cfg, lora, "wv", x, matmul(x, lp["wv"], qk, mesh, "col"),
                  "col", mesh)
    if cfg.attn_qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm_full:
        # OLMoE: one RMS norm across the whole projection, all heads at once.
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    q = q.reshape(*x.shape[:-1], H, Hd)
    k = k.reshape(*x.shape[:-1], K, Hd)
    v = v.reshape(*x.shape[:-1], K, Hd)
    if cfg.qk_norm:
        # Gemma-3: per-head RMS norms on q/k before rope.
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    if cfg.query_scale:
        # Gemma-2 scales attention by query_pre_attn_scalar^-0.5; the
        # attention kernels divide by sqrt(head_dim), so pre-multiply q by
        # the ratio (commutes with RoPE — a rotation).
        q = q * float((cfg.head_dim_ / cfg.query_scale) ** 0.5)
    amp = rope_query_amp(cfg)
    if amp != 1.0 and cfg.rotary_dim < Hd:
        # ... and where part of a head is rotated the tables reach that part
        # alone: m² on q's rotated lanes, 1 on those that pass through.
        import numpy as np

        q = q * jnp.asarray(np.where(
            np.arange(Hd) < cfg.rotary_dim, amp, 1.0), q.dtype)
    elif amp != 1.0:
        # yarn/longrope attention-amplitude correction (m on both cos/sin
        # tables ≡ m² on q alone; K stays unmodified in the cache).
        q = q * float(amp)
    return q, k, v


# --------------------------------------------------------------------------- #
# Multi-head Latent Attention (DeepSeek-V2/V3; HF DeepseekV3Attention parity)
#
# Prefill runs full-rank: per-head k = [W_kb·c_kv | rope(k_pe)] and
# v = W_vb·c_kv are materialized (compute-bound phase, standard MHA shapes).
# Decode runs the absorbed-weight identity: q·k = [W_kbᵀq_nope | q_pe] ·
# [c_kv | k_pe], so attention is MQA against the cached LATENT rows, and the
# value read is served by passing the same latent array as the v operand —
# the output's first kv_lora_rank dims equal probs·c_kv, which W_vb lifts
# back to per-head values. One latent row per token is all HBM ever holds.
# --------------------------------------------------------------------------- #


def _mla_q(cfg: ArchConfig, lp: Params, x: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """Query projection [..., H, qk_head_dim] (nope|rope concat, pre-rope);
    through the q-lora bottleneck when configured (V3) or direct (V2-Lite)."""
    qk = cfg.quant_kernel
    if cfg.q_lora_rank:
        # wq_a is replicated (the MLA bottleneck is tiny) — no shard part.
        ql = rms_norm(matmul(x, lp["wq_a"], qk), lp["q_norm_a"], cfg.rms_eps)
        q = matmul(ql, lp["wq_b"], qk, mesh, "col")
    else:
        q = matmul(x, lp["wq"], qk, mesh, "col")
    return q.reshape(*x.shape[:-1], cfg.num_heads, cfg.qk_head_dim)


def _mla_rope(cfg: ArchConfig, x, positions, inv):
    """MLA's rotation of its rope dims; none at all under `mla_rope` off
    (Kimi-Linear's NoPE MLA: the KDA layers carry the order)."""
    return apply_rope(x, positions, inv) if cfg.mla_rope else x


def _latent_pad(cfg: ArchConfig, a: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the last axis from r+rot to the cache's row width
    (cfg.latent_pad): zeros add nothing to a score and are never read back
    as values."""
    pad = cfg.cache_k_dim - a.shape[-1]
    if not pad:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


@scope("attention/proj")
def _mla_rows(cfg: ArchConfig, lp: Params, x: jnp.ndarray,
              positions: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """Latent cache rows [B, T, 1, r+rot] = [RMSNorm(c_kv) | RoPE(k_pe)] for
    tokens x [B, T, D] at `positions` [B, T]. This is the ONLY thing MLA
    writes to the KV cache."""
    r = cfg.kv_lora_rank
    ckv = matmul(x, lp["wkv_a"], cfg.quant_kernel)  # [B, T, r+rot] (replicated weight)
    c = rms_norm(ckv[..., :r], lp["kv_norm"], cfg.rms_eps)
    k_pe = _mla_rope(cfg, ckv[..., None, r:], positions, inv)  # [B, T, 1, rot]
    rows = jnp.concatenate([c[..., None, :], k_pe], axis=-1)
    return _latent_pad(cfg, rows)


@scope("attention/proj")
def _mla_full_qkv(cfg: ArchConfig, lp: Params, x: jnp.ndarray,
                  positions: jnp.ndarray, inv: jnp.ndarray, mesh=None):
    """Full-rank MLA projections for prefill. x [B, T, D] →
    (q [B,T,H,Dq], k [B,T,H,Dq], v [B,T,H,Dq] zero-padded from v_head_dim,
    rows [B,T,1,r+rot]). The ops reshape outputs to q's head dim, so v rides
    zero-padded and the caller slices [..., :v_head_dim]."""
    H = cfg.num_heads
    n, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q = _mla_q(cfg, lp, x, mesh)
    q = jnp.concatenate([q[..., :n], _mla_rope(cfg, q[..., n:], positions, inv)], axis=-1)
    amp = rope_query_amp(cfg)
    if amp != 1.0:
        q = q * float(amp)
    rows = _mla_rows(cfg, lp, x, positions, inv)
    c, k_pe = rows[..., 0, :r], rows[..., :, r:r + rot]  # [B,T,r], [B,T,1,rot]
    k_nope = jnp.einsum("btr,hnr->bthn", c, lp["w_kb"]).astype(x.dtype)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_pe.shape[:2], H, rot)).astype(x.dtype)],
        axis=-1,
    )
    v = jnp.einsum("btr,hvr->bthv", c, lp["w_vb"]).astype(x.dtype)
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, cfg.qk_head_dim - vd)))
    return q, k, v, rows


@scope("attention/proj")
def _mla_absorbed_q(cfg: ArchConfig, lp: Params, x: jnp.ndarray,
                    positions: jnp.ndarray, inv: jnp.ndarray,
                    mesh=None) -> jnp.ndarray:
    """Absorbed decode query [B, T, H, r+rot] scoring directly against the
    latent cache. The attention ops scale by the OPERAND width (r+rot), so
    the sqrt((r+rot)/qk_head_dim) ratio is folded in here to restore the
    true 1/sqrt(qk_head_dim) softmax scale (same trick as query_scale)."""
    n = cfg.qk_nope_head_dim
    q = _mla_q(cfg, lp, x, mesh)
    q_pe = _mla_rope(cfg, q[..., n:], positions, inv)
    q_lat = jnp.einsum("bthn,hnr->bthr", q[..., :n], lp["w_kb"]).astype(x.dtype)
    q_eff = _latent_pad(
        cfg, jnp.concatenate([q_lat, q_pe.astype(x.dtype)], axis=-1))
    scale = (cfg.cache_k_dim / cfg.qk_head_dim) ** 0.5
    return q_eff * jnp.asarray(scale * rope_query_amp(cfg), x.dtype)


@scope("attention/out")
def _mla_unlatent(cfg: ArchConfig, lp: Params, attn: jnp.ndarray) -> jnp.ndarray:
    """Absorbed attention output [..., H, r+rot] → flat per-head values
    [..., H·v_head_dim] via W_vb (the deferred value up-projection)."""
    lat = attn[..., : cfg.kv_lora_rank]
    out = jnp.einsum("...hr,hvr->...hv", lat, lp["w_vb"].astype(lat.dtype))
    return out.reshape(*attn.shape[:-2], -1)


@scope("embed")
def _embed(cfg: ArchConfig, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup; Gemma scales hidden states by sqrt(D) here
    while the tied unembed reads the raw matrix."""
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = (h.astype(jnp.float32) * (cfg.hidden_size**0.5)).astype(h.dtype)
    if cfg.embedding_multiplier != 1.0:  # Granite
        h = (h.astype(jnp.float32) * cfg.embedding_multiplier).astype(h.dtype)
    return h


def _residual(cfg: ArchConfig, h: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """h + y, the branch first scaled by Granite's `residual_multiplier`
    (in float32, rounded once; a multiplier of 1 emits the plain add)."""
    if cfg.residual_multiplier == 1.0:
        return h + y
    return (h.astype(jnp.float32) + y.astype(jnp.float32)
            * cfg.residual_multiplier).astype(h.dtype)


def _act(cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Gated-MLP activation: SwiGLU (llama family) or GeGLU (gemma)."""
    if cfg.activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


@scope("lm_head")
def _final_norm(cfg: ArchConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    """The norm in front of the head (booked with it: XLA fuses the two)."""
    return rms_norm(h, params["final_norm"], cfg.rms_eps)


@scope("lm_head")
def _last_row(h: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """h [B, T, D] -> each row's last valid position [B, D]; an empty prompt
    reads position 0, not wrap to T-1."""
    last_idx = jnp.maximum(lengths - 1, 0)
    return jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]


@scope("lm_head")
def _unembed(cfg: ArchConfig, params: Params, h: jnp.ndarray,
             mesh=None) -> jnp.ndarray:
    # bf16 (or int8-dequant) operands with f32 MXU accumulation: casting the
    # [V, D] matrix to f32 would double its HBM traffic on every decode step
    # (the unembed is the single largest weight read at 128k vocabs).
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed_matmul(h, w, cfg.quant_kernel, mesh)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.logits_scaling != 1.0:  # Granite
        logits = logits / cfg.logits_scaling
    return logits


def _softcap(cfg: ArchConfig) -> float:
    """gemma-2's attention softcap; MLA's calls run without (ROADMAP D15)."""
    return 0.0 if cfg.is_mla else cfg.attn_softcap


def _mask_opts(cfg: ArchConfig, sliding, **more):
    """The mask options of one layer's GQA attention call: gemma-2's softcap
    and the layer's sliding window, plus what the entry point adds (`mesh`,
    `sink`/`swin`). MLA passes none of them: its ops run with their defaults
    (the matrix under `_decoder_layer`; ROADMAP D15)."""
    if cfg.is_mla:
        return {}
    return dict(softcap=_softcap(cfg), window=cfg.sliding_window,
                sliding=sliding, **more)


def _slid(cfg: ArchConfig, sliding, mask, dist):
    """`mask` cut to the sliding window on the layers that slide: rows at
    `dist` (query position − row position) >= sliding_window go."""
    if cfg.sliding_window and sliding is not None:
        return mask & (~sliding | (dist < cfg.sliding_window))
    return mask


def _decoder_layer(cfg: ArchConfig, h, xs, *, pos, inv, attend,
                   mla_full: bool = False, mrope_ang=None, ep: int = 1,
                   mesh=None, lora=None, picks=None, admit=None):
    """THE decoder layer, the body of every entry point's layer scan: input
    norm → q/k/v (GQA: `_attn_proj_qkv` + rope; MLA: the full-rank or the
    absorbed projections, which rotate inside) → `attend` → output projection
    → post norm → MLP. Returns (h, the rows the layer emits for the cache):
    (k, v), or MLA's (latent rows, a zero-width v).

    h: [B, D] (one token per slot) or [B, T, D]; `pos` [B] / [B, T] the
    positions rope rotates at (and MLA's rows are written for).
    xs = (lp, li, *cache[, la]) as `_scan_stack` hands it on: this layer's
    weights, its number, the entry point's per-layer cache operands in
    (k, v) pairs and, with `lora` (the entry point's (stacks, ids)), this
    layer's slice of the adapter stacks.
    inv = (global, local | None) rope frequencies; `_layer_inv_freq` and
    `_layer_sliding` pick this layer's kind.

    `attend(q, k, v, sliding, *cache)` is the ONE thing an entry point
    supplies: how it attends over its cache operand ⊕ the fresh rows (dense
    cache ⊕ current row, ⊕ block window, paged pool + table, sp ring,
    prefix ⊕ tail). Which implementation runs is decided where that closure
    is built, never here. Under MLA's absorbed form the latent rows ride as
    both the k and the v operand, fresh and cached alike.

    What each entry point supports, as the code stands (a blank is a hole,
    not a promise: the entry point lacks the parameter; ROADMAP D15):

    | entry point            | LoRA | rope extra   | sink+window | sp        | expert_rows | MLA form |
    | ---------------------- | ---- | ------------ | ----------- | --------- | ----------- | -------- |
    | `_forward_hidden` (1)  | GQA  | m-rope (GQA) |             | ring, GQA |             | full     |
    | `decode_step`          |      |              |             | GQA       |             | absorbed |
    | `decode_step_windowed` | GQA  | `rope_delta` (GQA) | GQA (dense, sp, paged) | GQA | yes  | absorbed |
    | `decode_chunk`         | GQA  |              |             |           |             | absorbed |
    | `prefill_tail`         |      |              |             |           |             | absorbed |
    | `prefill_chunk_paged`  |      |              | GQA (paged walk, ring) | ring (`sp_mesh`), GQA | | absorbed |

    (1) `prefill`, `encode`, `sequence_logprob`; only `prefill` passes LoRA
    and m-rope on. "GQA" = ignored under MLA (sp: refused); softcap and the
    per-layer sliding window reach every GQA call and no MLA call. (1),
    `prefill_tail` and `prefill_chunk_paged` are the admission entry points:
    they pass `admit` on to `_mlp`, the decode entry points never do."""
    lp, li, *cache = xs
    if lora is not None:
        *cache, la = cache
        lora = None if cfg.is_mla else (la, lora[1])
    one = h.ndim == 2  # one token per slot: rope and MLA want a [B, 1] axis
    sliding = _layer_sliding(cfg, li)
    x = rms_norm(h, lp["attn_norm"], cfg.rms_eps)
    inv = _layer_inv_freq(cfg, *inv, li)
    if cfg.is_mla:
        xm, at = (x[:, None], pos[:, None]) if one else (x, pos)
        if mla_full:
            q, k, v, rows = _mla_full_qkv(cfg, lp, xm, at, inv, mesh)
            with scope("attention/mix"):
                attn = attend(q, k, v, sliding, *cache)[..., : cfg.v_head_dim]
            attn = attn.reshape(*h.shape[:-1], -1)
        else:
            q = _mla_absorbed_q(cfg, lp, xm, at, inv, mesh)
            rows = _mla_rows(cfg, lp, xm, at, inv)
            if one:
                q, rows = q[:, 0], rows[:, 0]
            latent = [c for kc in cache[::2] for c in (kc, kc)]
            with scope("attention/mix"):
                attn = attend(q, rows, rows, sliding, *latent)
            attn = _mla_unlatent(cfg, lp, attn)
        emit = (rows, rows[..., :0])
    else:
        q, k, v = _attn_proj_qkv(cfg, lp, x, mesh, lora=lora)
        with scope("attention/rope"):
            if not cfg.attn_rope:
                pass  # NoPE: the causal mask is all the order there is
            elif mrope_ang is not None:
                q, k = rope_rotate(q, mrope_ang), rope_rotate(k, mrope_ang)
            elif one:
                q = apply_rope(q[:, None], pos[:, None], inv)[:, 0]
                k = apply_rope(k[:, None], pos[:, None], inv)[:, 0]
            else:
                q, k = apply_rope(q, pos, inv), apply_rope(k, pos, inv)
        with scope("attention/mix"):
            attn = attend(q, k, v, sliding, *cache)
        attn = attn.reshape(*h.shape[:-1], -1)
        if cfg.attn_gate:
            attn = _attn_gated(cfg, lp, x, attn, mesh)
        # as the cache holds them: `cache_pack` heads a row (a free reshape)
        emit = tuple(a.reshape(*a.shape[:-2], cfg.cache_kv_heads, -1)
                     for a in (k, v)) if cfg.cache_pack > 1 else (k, v)
    h = _residual(cfg, h, _attn_out(cfg, lp, attn, mesh, lora=lora))
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
    return _residual(cfg, h, _mlp_out(cfg, lp, x, ep, mesh, lora=lora,
                                      picks=picks, admit=admit)), emit


# --------------------------------------------------------------------------- #
# Hybrid models (Kimi-Linear, Solar-Open2, LFM2, Granite-4.0-H, AI21-Jamba2): recurrent
# layers with a per-slot state beside layers that write cache rows, MLA's
# latent ones or GQA's ordinary keys and values (cfg.layer_kinds).
#
# A recurrent layer writes no cache row. Of the model's one recurrent kind
# (`cfg.recurrent_kind`) a KDA layer keeps, per slot, a [H, dk, dv] float32
# state and the short conv's last inputs; a gated short convolution ("conv")
# its last conv_cache-1 inputs and nothing else; an SSD (Mamba-2) layer a
# [H, P, N] float32 state and its conv's last inputs; an S6 (Mamba-1) layer a
# [N, E] float32 state, every element with a decay of its own, and its conv's
# last inputs. What a kind brings
# (its stack's init, its decode and prefill mixers) is one entry of
# `RECURRENT`. The two kinds' weights live
# in their own stacks (`cfg.recurrent_stack`, `cfg.cache_stack`), the norms
# and the MLPs in the model's layer stacks as ever. `_scan_hybrid` scans the
# recurrent layers and runs the cache layer that stands beside one under a
# `lax.cond`: the recurrent state is carried by the scan and never enters
# the conditional.
# --------------------------------------------------------------------------- #


@scope("attention/proj")
def _kda_inputs(cfg: ArchConfig, ap: Params, x: jnp.ndarray, conv_prev):
    """x [B, T, D] (normed) -> the recurrence's operands and what the layer
    needs after it. conv_prev [B, c-1, 3·H·dk]: the conv's inputs before
    x's first token (zeros at a prompt's start).

    Returns (q, k, v, g [B, T, H, dk] f32, beta [B, T, H] f32, gate
    [B, T, H, dk], window [B, c-1+T, 3·H·dk]: the conv's inputs, from which
    the caller cuts the rows the next token will need)."""
    f32 = jnp.float32
    B, T, _ = x.shape
    H, dk, c = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    qk = cfg.quant_kernel
    pre = jnp.concatenate(
        [matmul(x, ap[n], qk) for n in ("wq", "wk", "wv")], axis=-1)
    window = jnp.concatenate([conv_prev.astype(pre.dtype), pre], axis=1)
    w = ap["conv_w"].astype(f32)  # [c, 3·H·dk]
    y = sum(window[:, i:i + T].astype(f32) * w[i] for i in range(c))
    y = jax.nn.silu(y).reshape(B, T, 3, H, dk)
    q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = matmul(matmul(x, ap["f_down"], qk), ap["f_up"], qk).astype(f32)
    g = -jnp.exp(ap["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        f + ap["dt_bias"].astype(f32)).reshape(B, T, H, dk)
    beta = jax.nn.sigmoid(matmul(x, ap["w_beta"], qk).astype(f32))
    if cfg.kda_neg_eigval:  # beta in (0, 2): I - beta k k^T reflects
        beta = 2.0 * beta
    gate = jax.nn.sigmoid(matmul(
        matmul(x, ap["g_down"], qk), ap["g_up"], qk).astype(f32))
    return q, k, v, g, beta, gate.reshape(B, T, H, dk), window


@scope("attention/out")
def _kda_out(cfg: ArchConfig, ap: Params, o, gate, dtype, mesh=None):
    """o [..., H, dv] f32 -> per-head RMSNorm, the sigmoid gate, W_o."""
    o = rms_norm(o, ap["o_norm"], cfg.rms_eps) * gate
    o = o.reshape(*o.shape[:-2], -1).astype(dtype)
    return matmul(o, ap["wo"], cfg.quant_kernel, mesh, "row")


@scope("attention/cache_write")
def _claim_rows(rec, j, slots, S, window, lengths, n: int):
    """An admission's write of a recurrent kind's rows: `S` (the state after
    each prompt's last token; None for a kind without one) and the conv's
    inputs at each prompt's last n tokens (cut out of `window`, whose first n
    rows are the zeros before token 0) into rows `slots` [B] of layer j."""
    state, conv = rec
    rows = jnp.take_along_axis(
        window, (lengths[:, None] + jnp.arange(n)[None, :])[..., None], axis=1)
    if S is not None:
        state = state.at[j, slots].set(S)
    return state, conv.at[j, slots].set(rows.astype(conv.dtype))


def _kda_decode_mix(cfg: ArchConfig, ap: Params, x, rec, j, impl="auto"):
    """One token per slot: x [B, D], rec = (state, conv) stacked over the KDA
    layers, j this layer's index in them. Returns (y [B, D], rec)."""
    from localai_tpu.ops.kda import kda_decode

    state, conv = rec
    with scope("attention/proj"), jax.named_scope("layer_conv_rows"):
        prev = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    q, k, v, g, beta, gate, window = _kda_inputs(cfg, ap, x[:, None], prev)
    with scope("attention/cache_write"):
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, window[:, 1:].astype(conv.dtype), j, 0)
    with scope("attention/mix"):  # the kernel writes the state's rows in place
        o, state = kda_decode(state, j, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], impl=impl)
    return _kda_out(cfg, ap, o, gate[:, 0], x.dtype), (state, conv)


def _kda_prefill_mix(cfg: ArchConfig, ap: Params, x, lengths, rec, j, slots):
    """Whole prompts from an empty state: x [B, T, D] right-padded to
    `lengths`. With rec = (state, conv) the state after each prompt's last
    token and the conv's last inputs are written to rows `slots` [B] of
    layer j. Returns (y [B, T, D], rec)."""
    from localai_tpu.ops.kda import CHUNK, SUB, kda_chunk_prefill

    B, T, _ = x.shape
    c = cfg.kda_conv
    with scope("attention/proj"):
        zeros = jnp.zeros(
            (B, c - 1, 3 * cfg.kda_heads * cfg.kda_head_dim), x.dtype)
    q, k, v, g, beta, gate, window = _kda_inputs(cfg, ap, x, zeros)
    with scope("attention/mix"):
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        pad = -T % (CHUNK if T >= CHUNK else SUB)
        if pad:  # a bucket that is no multiple of the chunk: rows that do nothing
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        o, S = kda_chunk_prefill(q, k, v, g, beta, valid)
        o = o[:, :T]
    y = _kda_out(cfg, ap, o, gate, x.dtype)
    if rec is not None:
        rec = _claim_rows(rec, j, slots, S, window, lengths, c - 1)
    return y, rec


@scope("attention/proj")
def _conv_inputs(cfg: ArchConfig, ap: Params, x: jnp.ndarray, conv_prev):
    """x [B, T, D] (normed) -> (c [B, T, D], window [B, conv_cache-1+T, D]):
    [b | c | z] = x W_in, the conv's inputs u = b ⊙ z behind those before x's
    first token (`conv_prev`, zeros at a prompt's start). u is rounded to the
    rows' type here, so that a token reads the same inputs whether they come
    from this window or, a step later, from its slot's rows."""
    f32 = jnp.float32
    b, c, z = jnp.split(matmul(x, ap["w_in"], cfg.quant_kernel), 3, axis=-1)
    u = (b.astype(f32) * z.astype(f32)).astype(x.dtype)
    return c, jnp.concatenate([conv_prev.astype(u.dtype), u], axis=1)


def _conv_out(cfg: ArchConfig, ap: Params, c, window):
    """The gated short convolution itself and its out-projection: v_t = the
    conv_cache taps over u_{t-conv_cache+1..t} (depthwise, causal, no
    activation), y = (c ⊙ v) W_out."""
    f32 = jnp.float32
    T = c.shape[1]
    with scope("attention/mix"):
        w = ap["conv_w"].astype(f32)  # [conv_cache, D]
        v = sum(window[:, i:i + T].astype(f32) * w[i]
                for i in range(cfg.conv_cache))
        y = (c.astype(f32) * v).astype(c.dtype)
    with scope("attention/out"):
        return matmul(y, ap["wo"], cfg.quant_kernel)


@jax.named_scope(CONV_MIX)
def _conv_decode_mix(cfg: ArchConfig, ap: Params, x, rec, j, impl="auto"):
    """One token per slot: x [B, D], rec = (None, conv) with conv the rows
    stacked over the conv layers, j this layer's index in them (`impl`: the
    operator has no kernel to choose). Returns
    (y [B, D], rec). The operator whole is written under `CONV_MIX`, around
    its leaves: XLA names a fusion after any op in it (on the chip the taps
    and the gate after W_out's reshape), so only a word every op of the
    operator carries is still there to read."""
    _, conv = rec
    with scope("attention/proj"), jax.named_scope("layer_conv_rows"):
        prev = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    c, window = _conv_inputs(cfg, ap, x[:, None], prev)
    with scope("attention/cache_write"):
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, window[:, 1:].astype(conv.dtype), j, 0)
    return _conv_out(cfg, ap, c, window)[:, 0], (None, conv)


@jax.named_scope(CONV_MIX)
def _conv_prefill_mix(cfg: ArchConfig, ap: Params, x, lengths, rec, j, slots):
    """Whole prompts from an empty row: x [B, T, D] right-padded to
    `lengths`. With rec = (None, conv) the conv's inputs at each prompt's
    last conv_cache-1 tokens are written to rows `slots` [B] of layer j.
    Returns (y [B, T, D], rec)."""
    n = cfg.conv_cache - 1
    with scope("attention/proj"):
        zeros = jnp.zeros((x.shape[0], n, cfg.hidden_size), x.dtype)
    c, window = _conv_inputs(cfg, ap, x, zeros)
    if rec is not None:
        rec = _claim_rows(rec, j, slots, None, window, lengths, n)
    return _conv_out(cfg, ap, c, window), rec


@scope("attention/proj")
def _ssd_inputs(cfg: ArchConfig, ap: Params, x: jnp.ndarray, conv_prev):
    """x [B, T, D] (normed) -> the recurrence's operands and what the layer
    needs after it: [z | xBC | dt] = x W_in (its three column blocks, a leaf
    each), xBC through the short conv (its
    inputs before x's first token in `conv_prev` [B, c-1, conv_dim], zeros at
    a prompt's start), its bias and silu, then split [x | B | C].

    Returns (xs [B, T, H, P] f32, dt [B, T, H] f32 after the softplus, Bm, Cm
    [B, T, G, N] f32, z [B, T, d_inner], window [B, c-1+T, conv_dim]: the
    conv's inputs, from which the caller cuts the rows the next token will
    need)."""
    f32 = jnp.float32
    B, T, _ = x.shape
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
                  cfg.mamba_groups)
    di, c = cfg.mamba_d_inner, cfg.mamba_conv
    z, pre, dt = (matmul(x, ap[n], cfg.quant_kernel)
                  for n in ("w_z", "w_xbc", "w_dt"))
    window = jnp.concatenate([conv_prev.astype(pre.dtype), pre], axis=1)
    w = ap["conv_w"].astype(f32)  # [c, conv_dim]
    y = sum(window[:, i:i + T].astype(f32) * w[i] for i in range(c))
    y = jax.nn.silu(y + ap["conv_b"].astype(f32))
    xs = y[..., :di].reshape(B, T, H, P)
    Bm = y[..., di:di + G * N].reshape(B, T, G, N)
    Cm = y[..., di + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + ap["dt_bias"].astype(f32))
    return xs, dt, Bm, Cm, z, window


@scope("attention/out")
def _ssd_out(cfg: ArchConfig, ap: Params, y, z, dtype, mesh=None):
    """y [..., H, P] f32 -> the gate silu(z), THEN one RMSNorm over all of
    d_inner (Mamba-2's gated norm, `norm_before_gate` false, one group), W_o."""
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y, ap["o_norm"], cfg.rms_eps).astype(dtype)
    return matmul(y, ap["wo"], cfg.quant_kernel, mesh, "row")


@jax.named_scope(SSD_MIX)
def _ssd_decode_mix(cfg: ArchConfig, ap: Params, x, rec, j, impl="auto"):
    """One token per slot: x [B, D], rec = (state, conv) stacked over the SSD
    layers, j this layer's index in them. Returns (y [B, D], rec). The
    operator whole is written under `SSD_MIX`, around its leaves, as
    `CONV_MIX` is."""
    from localai_tpu.ops.ssd import ssd_decode

    state, conv = rec
    with scope("attention/proj"), jax.named_scope("layer_conv_rows"):
        prev = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    xs, dt, Bm, Cm, z, window = _ssd_inputs(cfg, ap, x[:, None], prev)
    with scope("attention/cache_write"):
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, window[:, 1:].astype(conv.dtype), j, 0)
    with scope("attention/mix"):  # the kernel writes the state's rows in place
        A = -jnp.exp(ap["A_log"].astype(jnp.float32))
        y, state = ssd_decode(state, j, xs[:, 0], dt[:, 0], A, Bm[:, 0],
                              Cm[:, 0], ap["ssm_D"], impl=impl)
    return _ssd_out(cfg, ap, y, z[:, 0], x.dtype), (state, conv)


@jax.named_scope(SSD_MIX)
def _ssd_prefill_mix(cfg: ArchConfig, ap: Params, x, lengths, rec, j, slots):
    """Whole prompts from an empty state: x [B, T, D] right-padded to
    `lengths`. With rec = (state, conv) the state after each prompt's last
    token and the conv's last inputs are written to rows `slots` [B] of
    layer j. Returns (y [B, T, D], rec). Chunks of ops/ssd.CHUNK = 128 (an
    exact sub-blocking of the published `mamba_chunk` 256, or a smaller
    model's own chunk), a shorter bucket as one chunk."""
    from localai_tpu.ops.ssd import CHUNK, ssd_chunk_prefill

    B, T, _ = x.shape
    c = cfg.mamba_conv
    with scope("attention/proj"):
        zeros = jnp.zeros((B, c - 1, cfg.mamba_conv_dim), x.dtype)
    xs, dt, Bm, Cm, z, window = _ssd_inputs(cfg, ap, x, zeros)
    with scope("attention/mix"):
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        chunk = min(cfg.mamba_chunk, CHUNK)
        pad = -T % min(chunk, T)
        if pad:  # a bucket that is no multiple of the chunk: rows that do nothing
            xs, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (xs, Bm, Cm))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        A = -jnp.exp(ap["A_log"].astype(jnp.float32))
        y, S = ssd_chunk_prefill(xs, dt, A, Bm, Cm, ap["ssm_D"], valid, chunk)
        y = y[:, :T]
    out = _ssd_out(cfg, ap, y, z, x.dtype)
    if rec is not None:
        rec = _claim_rows(rec, j, slots, S, window, lengths, c - 1)
    return out, rec


@scope("attention/proj")
def _s6_inputs(cfg: ArchConfig, ap: Params, x: jnp.ndarray, conv_prev):
    """x [B, T, D] (normed) -> the recurrence's operands and what the layer
    needs after it: [x | z] = x W_in, x through the short conv (its inputs
    before x's first token in `conv_prev` [B, c-1, E], zeros at a prompt's
    start), its bias and silu; [r | B | C] = x W_x, each of the three under
    an RMSNorm of its own (the `jamba` mixer's; plain Mamba-1 has none);
    dt = softplus(r W_dt + b_dt).

    Returns (xs [B, T, E] f32, dt [B, T, E] f32, Bm, Cm [B, T, N] f32, z
    [B, T, E], window [B, c-1+T, E]: the conv's inputs, from which the caller
    cuts the rows the next token will need)."""
    f32 = jnp.float32
    T = x.shape[1]
    N, R, c = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_conv
    qk, eps = cfg.quant_kernel, cfg.rms_eps
    pre, z = jnp.split(matmul(x, ap["w_in"], qk), 2, axis=-1)
    window = jnp.concatenate([conv_prev.astype(pre.dtype), pre], axis=1)
    w = ap["conv_w"].astype(f32)  # [c, E]
    y = sum(window[:, i:i + T].astype(f32) * w[i] for i in range(c))
    xs = jax.nn.silu(y + ap["conv_b"].astype(f32))
    rbc = matmul(xs.astype(x.dtype), ap["w_x"], qk)
    r = rms_norm(rbc[..., :R], ap["dt_norm"], eps)
    Bm = rms_norm(rbc[..., R:R + N].astype(f32), ap["b_norm"], eps)
    Cm = rms_norm(rbc[..., R + N:].astype(f32), ap["c_norm"], eps)
    dt = jax.nn.softplus(
        matmul(r, ap["w_dt"], qk).astype(f32) + ap["dt_bias"].astype(f32))
    return xs, dt, Bm, Cm, z, window


@scope("attention/out")
def _s6_out(cfg: ArchConfig, ap: Params, y, z, dtype, mesh=None):
    """y [..., E] f32 -> the gate silu(z), W_out (no norm: Mamba-1's)."""
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return matmul(y, ap["wo"], cfg.quant_kernel, mesh, "row")


@jax.named_scope(S6_MIX)
def _s6_decode_mix(cfg: ArchConfig, ap: Params, x, rec, j, impl="auto"):
    """One token per slot: x [B, D], rec = (state, conv) stacked over the S6
    layers, j this layer's index in them. Returns (y [B, D], rec). The
    operator whole is written under `S6_MIX`, around its leaves, as
    `CONV_MIX` is."""
    from localai_tpu.ops.s6 import s6_decode

    state, conv = rec
    with scope("attention/proj"), jax.named_scope("layer_conv_rows"):
        prev = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    xs, dt, Bm, Cm, z, window = _s6_inputs(cfg, ap, x[:, None], prev)
    with scope("attention/cache_write"):
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, window[:, 1:].astype(conv.dtype), j, 0)
    with scope("attention/mix"):  # the kernel writes the state's rows in place
        At = -jnp.exp(ap["A_logT"].astype(jnp.float32))
        y, state = s6_decode(state, j, xs[:, 0], dt[:, 0], At, Bm[:, 0],
                             Cm[:, 0], ap["ssm_D"], impl=impl)
    return _s6_out(cfg, ap, y, z[:, 0], x.dtype), (state, conv)


@jax.named_scope(S6_MIX)
def _s6_prefill_mix(cfg: ArchConfig, ap: Params, x, lengths, rec, j, slots):
    """Whole prompts from an empty state: x [B, T, D] right-padded to
    `lengths`. With rec = (state, conv) the state after each prompt's last
    token and the conv's last inputs are written to rows `slots` [B] of
    layer j. Returns (y [B, T, D], rec). The recurrence is the decode step
    over the prompt's positions (ops/s6.s6_prefill): Mamba-1 has no chunked
    matmul form."""
    from localai_tpu.ops.s6 import s6_prefill

    B, T, _ = x.shape
    c = cfg.mamba_conv
    with scope("attention/proj"):
        zeros = jnp.zeros((B, c - 1, cfg.mamba_d_inner), x.dtype)
    xs, dt, Bm, Cm, z, window = _s6_inputs(cfg, ap, x, zeros)
    with scope("attention/mix"):
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        At = -jnp.exp(ap["A_logT"].astype(jnp.float32))
        y, S = s6_prefill(xs, dt, At, Bm, Cm, ap["ssm_D"], valid)
    out = _s6_out(cfg, ap, y, z, x.dtype)
    if rec is not None:
        rec = _claim_rows(rec, j, slots, S, window, lengths, c - 1)
    return out, rec


class RecurrentKind(NamedTuple):
    """What a recurrent kind brings to the hybrid scan: its weight stack's
    init, its one-token mixer `(cfg, ap, x, rec, j, impl)` and its
    whole-prompt mixer `(cfg, ap, x, lengths, rec, j, slots)`. A window kind
    ("swa") brings its stack alone: its layer is `_decoder_layer` itself, and
    an entry point says where that layer's cache operands come from and
    where its rows go (`SwaMix`)."""

    init: Any
    decode_mix: Any = None
    prefill_mix: Any = None


class SwaMix(NamedTuple):
    """What an entry point brings for its window layers in the recurrent
    mixer's place: `attend` as `_decoder_layer` takes it, `cache(rec, j)` the
    cache operands of window layer j that `attend` is handed (a decode
    step: its ring and its block window; an admission: none), and
    `keep(rec, j, rows) -> (rec, out)` where the (k, v) rows the layer emits
    go (a decode step writes them into the block's window, which it carries;
    an admission writes the prompt's last ones into the slots' rings)."""

    attend: Any
    cache: Any
    keep: Any


def _init_swa_layers(cfg: ArchConfig, rnd, keys, L: int) -> Params:
    """The window layers' attention stack: the GQA stack's leaves under its
    names, at the window layers' own head count (`cfg.swa_heads`)."""
    return _init_attn_layers(cfg.kind_view("swa"), rnd, keys, L,
                             cache_stack=True)


RECURRENT = {
    "kda": RecurrentKind(_init_kda_layers, _kda_decode_mix, _kda_prefill_mix),
    "conv": RecurrentKind(_init_conv_layers, _conv_decode_mix,
                          _conv_prefill_mix),
    "ssd": RecurrentKind(_init_ssd_layers, _ssd_decode_mix, _ssd_prefill_mix),
    "swa": RecurrentKind(_init_swa_layers),
    "s6": RecurrentKind(_init_s6_layers, _s6_decode_mix, _s6_prefill_mix),
}


def _hybrid_tables(cfg: ArchConfig):
    """Static layout of a hybrid stack: the recurrent layers' model layer
    numbers, for each the index (among the cache layers) of the cache layer
    that stands beside it (or -1), how many recurrent layers carry the
    dense-prefix MLPs, the dense prefix's length, whether a cache layer
    stands IN FRONT of its recurrent layer (a period that begins with it:
    Solar-Open2, LFM2, Laguna) or behind it (one that ends with it:
    Kimi-Linear). Cache layers that carry dense-prefix MLPs themselves
    (Laguna's layer 0; then the whole prefix is such layers) run ahead of
    the scan and stand beside no recurrent layer in it."""
    import numpy as np

    rk = cfg.recurrent_kind  # refuses a stack of two recurrent kinds
    kl = list(cfg.recurrent_layers)
    ml = list(cfg.cache_layer_ids)
    kd = cfg.first_k_dense if cfg.is_moe else 0
    kind = "mla" if cfg.is_mla else "gqa"
    ahead = [l for l in ml if l < kd]  # cache layers of the dense prefix
    ok = (len(cfg.layer_kinds) == cfg.num_layers and bool(kl)
          and all(k in (rk, kind) for k in cfg.layer_kinds)
          and ahead in ([], list(range(kd))))
    for lead in (False, True) if ok else ():
        step = -1 if lead else 1  # where a recurrent layer's cache layer stands
        beside = [ml.index(l + step) if l + step in ml
                  and l + step not in ahead else -1 for l in kl]
        covered = (set(kl) | set(ahead)
                   | {l + step for l, m in zip(kl, beside) if m >= 0})
        if (covered == set(range(cfg.num_layers))
                and not any(m >= 0 and l < kd for l, m in zip(kl, beside))):
            nd = sum(1 for l in kl if l < kd)
            return (np.asarray(kl, np.int32), np.asarray(beside, np.int32),
                    nd, kd, lead)
    raise NotImplementedError(
        f"{cfg.name}: layer_kinds {cfg.layer_kinds} — every {kind!r} layer "
        f"has to stand beside a {rk or 'kda'!r} layer of its own, all of "
        "them behind theirs or all of them in front, and the dense-prefix "
        f"layers have to be all {rk or 'kda'!r} or all {kind!r} "
        "(models/llama._scan_hybrid)")


def _scan_hybrid(cfg: ArchConfig, params: Params, h, rec, rec_fn, cache_fn,
                 cache_zero, extras=()):
    """The layer stack of a hybrid model: ONE scan over its recurrent layers
    (of `cfg.recurrent_kind`: one entry of `RECURRENT`), each with the cache layer that
    stands beside it (`lax.cond`) where there is one: behind it, or in front
    of it where the model's periods begin with their cache layer
    (`_hybrid_tables`).

    rec_fn(h, rec, lp, j) -> (h, rec, out): a recurrent layer and its MLP; lp
    its weights (the recurrent stack's and the layer stack's, one dict), j
    its index among the recurrent layers. rec is whatever the entry point
    carries through them (the recurrent state), never passed into the
    conditional.
    cache_fn(h, lp, m, ex) -> (h, out): a cache layer (MLA or GQA) and its
    MLP; m its index among the cache layers, ex the `extras` (arrays stacked
    over the cache layers: the cache) as `_scan_stack` would hand them on.
    cache_zero(h) is `out` of a layer that is not there.
    Returns (h, rec, the recurrent layers' outs stacked over them,
    cache-layer outs stacked over the cache layers)."""
    kl, beside, nd, kd, lead = _hybrid_tables(cfg)
    kl_t, beside_t = jnp.asarray(kl), jnp.asarray(beside)
    ahead = sum(1 for l in cfg.cache_layer_ids if l < kd)  # run before the scan

    def run(h, rec, lo, hi, stack, off, with_cache):
        def cache_layer(h, j, li):
            """The cache layer beside recurrent layer j (model layer li), if any:
            model layer li - 1 where it leads, li + 1 where it follows."""
            m = beside_t[j]

            def there(h):
                mi = jnp.maximum(m, 0)
                with jax.named_scope("layer_weights"):
                    lp = {**_take_layer(params[cfg.cache_stack], mi),
                          **_take_layer(stack, li + (-1 if lead else 1) - off)}
                with jax.named_scope("layer_kv_pool"):
                    ex = _take_layer(tuple(extras), mi)
                return cache_fn(h, lp, mi, ex)

            return jax.lax.cond(
                m >= 0, there, lambda h: (h, cache_zero(h)), h)

        def body(carry, _):
            h, rec, j = carry
            li = kl_t[j]
            if with_cache and lead:
                h, out_m = cache_layer(h, j, li)
            with jax.named_scope("layer_weights"):
                lp = {**_take_layer(params[cfg.recurrent_stack], j),
                      **_take_layer(stack, li - off)}
            h, rec, out_k = rec_fn(h, rec, lp, j)
            if not with_cache:
                return (h, rec, j + 1), (out_k, None)
            if not lead:
                h, out_m = cache_layer(h, j, li)
            return (h, rec, j + 1), (out_k, out_m)

        with scope("layer"):  # as `_scan_stack`'s
            (h, rec, _), outs = jax.lax.scan(
                body, (h, rec, jnp.int32(lo)), None, length=hi - lo)
        return h, rec, outs

    outs_k, outs_m = [], []
    for m in range(ahead):  # a cache layer with a dense-prefix MLP: its own trace
        with scope("layer"):
            mi = jnp.int32(m)
            with jax.named_scope("layer_weights"):
                lp = {**_take_layer(params[cfg.cache_stack], mi),
                      **_take_layer(params["dense_layers"], mi)}
            with jax.named_scope("layer_kv_pool"):
                ex = _take_layer(tuple(extras), mi)
            h, om = cache_fn(h, lp, mi, ex)
        outs_m.append(jax.tree.map(lambda a: a[None], om))
    if nd:
        h, rec, (ok, _) = run(h, rec, 0, nd, params["dense_layers"], 0, False)
        outs_k.append(ok)
    h, rec, (ok, om) = run(h, rec, nd, len(kl), params["layers"], kd, True)
    outs_k.append(ok)
    with scope("attention/cache_write"):  # the rows the cache layers emitted
        out_k = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs_k)
        there = [j - nd for j in range(nd, len(kl)) if beside[j] >= 0]
        outs_m.append(jax.tree.map(lambda a: a[jnp.asarray(there)], om))
        out_m = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs_m)
    return h, rec, out_k, out_m


@scope("mlp/router")
def _expert_counts(cfg: ArchConfig, picks) -> jnp.ndarray:
    """[E] int32: rows per held expert of one MLP's router choice (zeros for
    a dense MLP, whose `picks` stayed empty)."""
    E = max(cfg.experts_here, 1)
    if not picks:
        return jnp.zeros((E,), jnp.int32)
    lead = tuple(range(picks[0].ndim))
    return jax.nn.one_hot(picks[0], E, dtype=jnp.int32).sum(lead)


def _walk_rows(admit) -> jnp.ndarray:
    """[2] int32: what one MLP's grouped kernel walked (`_moe_ragged`: the
    sorted rows it was compiled for, those in a held group); zeros where the
    kernel did not run and `admit` stayed empty."""
    return admit[0] if admit else jnp.zeros((2,), jnp.int32)


def _hybrid_layer_fns(cfg: ArchConfig, rec_mix, *, pos, inv, attend,
                      mla_full: bool, ep: int, mesh, count: bool,
                      admit: bool = False):
    """(rec_fn, cache_fn, cache_zero) for `_scan_hybrid` from an entry point's
    `rec_mix(lp, x, rec, j) -> (y, rec)` (its recurrent kind's mixer, from
    `RECURRENT`) and its cache layers' `attend`. The
    cache layer, MLA or GQA, is `_decoder_layer` itself; so is a window
    layer, for which `rec_mix` is the entry point's `SwaMix`: each kind of a
    model whose attention layers differ by kind reads the config through its
    own view (`ArchConfig.kind_view`: head count, rope schedule, window).
    With `count` each layer's out ends with its rows per held expert or,
    from an admission entry point (`admit`), with its grouped kernel's rows
    (`_walk_rows`)."""
    cview = cfg.kind_view("mla" if cfg.is_mla else "gqa")
    cinv = inv if cview is cfg else _rope_inv(cview)

    def receiver():  # what `_mlp` fills, as `_mlp_out` takes it
        if admit:
            return {"admit": []}
        return {"picks": [] if count else None}

    def reading(kw):
        if admit:
            return _walk_rows(kw["admit"])
        return _expert_counts(cfg, kw["picks"])

    def rec_fn(h, rec, lp, j):
        kw = receiver()
        y, rec = rec_mix(lp, rms_norm(h, lp["attn_norm"], cfg.rms_eps), rec, j)
        h = _residual(cfg, h, y)
        x = rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
        h = _residual(cfg, h, _mlp_out(cfg, lp, x, ep, mesh, **kw))
        return h, rec, (reading(kw) if count else None)

    if cfg.recurrent_kind == "swa":
        sview = cfg.kind_view("swa")
        sinv = _rope_inv(sview)

        def rec_fn(h, rec, lp, j):  # noqa: F811 — the window kind's
            kw = receiver()
            with jax.named_scope(WINDOW_MIX):
                h, rows = _decoder_layer(
                    sview, h, (lp, j) + tuple(rec_mix.cache(rec, j)), pos=pos,
                    inv=sinv, attend=rec_mix.attend, ep=ep, mesh=mesh, **kw)
                rec, out = rec_mix.keep(rec, j, rows)
            if count:
                out = tuple(out or ()) + (reading(kw),)
            return h, rec, out

    def cache_fn(h, lp, m, ex):
        kw = receiver()
        h, rows = _decoder_layer(
            cview, h, (lp, m) + tuple(ex), pos=pos, inv=cinv, attend=attend,
            mla_full=mla_full, ep=ep, mesh=mesh, **kw)
        return h, rows + ((reading(kw),) if count else ())

    @scope("attention/cache_write")
    def cache_zero(h):
        lead = h.shape[:-1]
        rows = jnp.zeros(lead + (cfg.cache_kv_heads, cfg.cache_k_dim), h.dtype)
        out = (rows, rows[..., :cfg.cache_v_dim])
        if count:
            n = 2 if admit else max(cfg.experts_here, 1)
            out = out + (jnp.zeros((n,), jnp.int32),)
        return out

    return rec_fn, cache_fn, cache_zero


@scope("attention/rope")
def _rope_inv(cfg: ArchConfig):
    """(global, local | None) rope frequencies, as `_decoder_layer` takes them."""
    return rope_frequencies(cfg), rope_frequencies_local(cfg)


def _forward_hidden(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B] int32 valid lengths
    collect_kv: bool,
    mesh=None,  # jax.sharding.Mesh with an "sp" axis > 1 → ring attention
    inject=None,  # (embeds [B, N, D], offsets [B]) — VLM image features
    ep: int = 1,  # expert-parallel degree (MoE implementation choice)
    mrope=None,  # [B, 3, S] (t, h, w) position streams — Qwen2-VL m-rope
    lora=None,  # (stacked adapter factors {key: {"a": [L,NA,in,R], "b":
    # [L,NA,R,out]}}, ids [B]) — per-row runtime LoRA (ISSUE 10)
    recurrent=None,  # hybrid models: (state, conv, slots [B]) — each prompt's
    # final recurrent state is written to its slot's rows, layer by layer
    expert_rows: bool = False,  # MoE: also return what the grouped expert
    # kernel walked, summed over the layers ([2] int32, `_walk_rows`)
):
    """Shared full-sequence forward. Returns (h [B,S,D] after final norm,
    length_mask [B,S], (ks, vs) or None; with `expert_rows`, the grouped
    kernel's rows follow; with `recurrent`, the (state, conv) written comes
    last). An admission entry point: its MLPs are given `admit` (`_mlp`).
    Single source of truth for the layer
    body used by both `prefill` and `encode`.

    With a mesh whose "sp" axis is > 1, attention runs as ring attention
    (localai_tpu.parallel.ring): the sequence axis shards over "sp" and KV
    blocks rotate neighbor-to-neighbor over ICI, so per-chip KV residency is
    S/sp — the long-context serving path (the reference has no sequence
    parallelism; SURVEY.md §5)."""
    B, S = tokens.shape
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_ring and S % mesh.shape["sp"] != 0:
        raise ValueError(f"sequence bucket {S} not divisible by sp={mesh.shape['sp']}")
    inv_freq, inv_local = _rope_inv(cfg)
    with scope("attention/mix"):  # the masks every layer's mixer reads
        positions = jnp.arange(S)[None, :].repeat(B, axis=0)  # [B, S]
        length_mask = jnp.arange(S)[None, :] < lengths[:, None]
    mrope_ang = None
    if mrope is not None:
        # Qwen2-VL m-rope (HF get_rope_index semantics): section-selected
        # per-frequency position streams; same split-half rotation.
        if not cfg.mrope_section:
            raise ValueError("mrope positions passed but cfg.mrope_section empty")
        if inv_local is not None:
            raise ValueError("mrope + per-layer local rope is unsupported")
        with scope("attention/rope"):
            mrope_ang = mrope_angles(mrope, inv_freq, tuple(cfg.mrope_section))

    h = _embed(cfg, params, tokens)  # [B, S, D]
    if inject is not None:
        # Multimodal: overwrite the placeholder span with projected image
        # features (models/vision.py) — the llava injection point.
        embeds, offsets = inject
        with scope("embed"):
            h = jax.vmap(
                lambda hb, eb, ob: jax.lax.dynamic_update_slice(
                    hb, eb.astype(hb.dtype), (ob, 0)
                )
            )(h, embeds, offsets)

    if use_ring:
        if cfg.is_mla:
            raise NotImplementedError(
                "MLA + sequence parallelism is excluded this round "
                "(PARITY.md: ring rotation of latent rows needs its own "
                "kernel); shard MLA models over tp/ep instead"
            )
        from localai_tpu.parallel.ring import ring_prefill_attention

        def attend(q, k, v, sliding):
            return ring_prefill_attention(q, k, v, lengths, mesh,
                                          **_mask_opts(cfg, sliding))
    else:
        # The flash kernel tiles head_dim in 128-lane blocks: MLA at a q/k
        # width that is no multiple (192: Kimi-Linear, the DeepSeek presets)
        # takes the dense path (no `lengths`); at 256 (GLM-4.7-Flash, whose
        # values are as wide, so `_mla_full_qkv` pads nothing) the kernel.
        flash = not cfg.is_mla or cfg.qk_head_dim % 128 == 0

        def attend(q, k, v, sliding):
            return prefill_attention(
                q, k, v, length_mask, lengths if flash else None,
                mesh=mesh, **_mask_opts(cfg, sliding))

    def body(h, xs):  # lora: la, this layer's adapter factors, rides last
        admit = []
        h, kv = _decoder_layer(
            cfg, h, xs, pos=positions, inv=(inv_freq, inv_local),
            attend=attend, mla_full=True, mrope_ang=mrope_ang, ep=ep,
            mesh=mesh, lora=lora, admit=admit)
        kv = kv if collect_kv else None
        return h, ((kv, _walk_rows(admit)) if expert_rows else kv)

    rec = None
    if cfg.is_hybrid:
        if use_ring or lora is not None or mrope is not None:
            raise NotImplementedError(
                f"{cfg.name}: a hybrid ({cfg.recurrent_kind}) model prefills "
                "without sequence parallelism, runtime LoRA and m-rope")
        slots = None
        if recurrent is not None:
            *rec, slots = recurrent
            rec = tuple(rec)

        mix = RECURRENT[cfg.recurrent_kind].prefill_mix

        def rec_mix(lp, x, rec, j):
            return mix(cfg, lp, x, lengths, rec, j, slots)

        if cfg.recurrent_kind == "swa":
            # a window layer attends as any layer of a prompt does, under
            # its window; the prompt's last rows go into its slot's ring
            rec_mix = SwaMix(
                attend, lambda rec, j: (),
                lambda rec, j, rows: (
                    _ring_admit(cfg, rec, j, rows, lengths, slots), None))

        h, rec, walked, kv = _scan_hybrid(
            cfg, params, h, rec, *_hybrid_layer_fns(
                cfg, rec_mix, pos=positions, inv=(inv_freq, inv_local),
                attend=attend, mla_full=True, ep=ep, mesh=mesh,
                count=expert_rows, admit=True))
        if expert_rows:  # the recurrent layers' MLPs, then the cache layers'
            *kv, walked_m = kv
            walked = (walked, walked_m)
        kv = tuple(kv) if collect_kv else None
    else:
        extras = () if lora is None else (lora[0],)
        h, kv = _scan_layers(cfg, params, h, body, extras)
        if expert_rows:
            kv, walked = kv
    h = _final_norm(cfg, params, h)
    out = (h, length_mask, kv)
    if expert_rows:  # [layers, 2] a stack: their sum over the layers
        with scope("mlp/experts"):
            out = out + (sum(a.sum(axis=0) for a in jax.tree.leaves(walked)),)
    if recurrent is not None:
        out = out + (rec,)
    return out


def prefill(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B] int32 valid lengths
    mesh=None,  # Mesh with sp>1 → ring attention (sequence parallel)
    inject=None,  # (embeds [B, N, D], offsets [B]) — VLM image features
    ep: int = 1,
    mrope=None,  # [B, 3, S] m-rope position streams (Qwen2-VL)
    lora=None,  # (stacked adapter factors, ids [B]) — runtime LoRA
    recurrent=None,  # hybrid models: (state, conv, slots [B]), see
    # `_forward_hidden`; the written (state, conv) is then returned last
    expert_rows: bool = False,  # MoE: what the grouped expert kernel walked
    # over all layers ([2] int32) follows v, see `_forward_hidden`
):
    """Prompt processing. Returns (last_logits [B, V] f32, k [L,B,S,K,Hd], v),
    L the layers that write cache rows (cfg.cache_layers)."""
    h, _, (ks, vs), *rec = _forward_hidden(
        cfg, params, tokens, lengths, collect_kv=True, mesh=mesh, inject=inject,
        ep=ep, mrope=mrope, lora=lora, recurrent=recurrent,
        expert_rows=expert_rows,
    )
    logits = _unembed(cfg, params, _last_row(h, lengths), mesh)
    return (logits, ks, vs, *rec)


def encode(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B] int32
    mesh=None,
    ep: int = 1,
) -> jnp.ndarray:
    """Sentence embedding: masked mean-pool of final hidden states, L2-normed.

    Serves the Embedding RPC capability (reference: backend/backend.proto
    Embedding; backend/python/transformers SentenceTransformer branch) from the
    same decoder weights.
    """
    h, length_mask, _ = _forward_hidden(cfg, params, tokens, lengths, collect_kv=False, mesh=mesh, ep=ep)
    with scope("lm_head"):  # this program's head: the pooling
        h = h.astype(jnp.float32)
        mask = length_mask[..., None].astype(jnp.float32)
        pooled = (h * mask).sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1.0)
        return pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def sequence_logprob(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32, right-padded
    lengths: jnp.ndarray,  # [B] int32 total valid length
    cond_lengths: jnp.ndarray,  # [B] int32 — score only positions >= cond_len
    mesh=None,
    ep: int = 1,
) -> jnp.ndarray:
    """Mean log P(tokens[cond_len:len] | tokens[:cond_len]) per row — the
    scoring primitive behind reranking (reference capability: core/backend/
    rerank.go RPC to a cross-encoder; here relevance is measured as the
    document's conditional likelihood under the LLM given the query)."""
    h, _, _ = _forward_hidden(cfg, params, tokens, lengths, collect_kv=False, mesh=mesh, ep=ep)
    logits = _unembed(cfg, params, h[:, :-1], mesh)  # [B, S-1, V] predicts tokens[1:]
    with scope("sample"):  # after the logits: the targets' logprobs
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = tokens[:, 1:]  # [B, S-1]
        tok_lp = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        pos = jnp.arange(tgt.shape[1])[None, :] + 1  # position of the target token
        valid = (pos >= cond_lengths[:, None]) & (pos < lengths[:, None])
        n = jnp.maximum(valid.sum(axis=-1), 1)
        return (tok_lp * valid).sum(axis=-1) / n  # [B]


def decode_step(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B] int32 current token per slot
    positions: jnp.ndarray,  # [B] int32 position of `tokens` in each sequence
    cache: KVCache,
    ep: int = 1,
    mesh=None,  # Mesh with sp>1 → the cache's sequence axis is sp-sharded
):
    """One decode step for the whole slot batch.

    Writes the new k/v at `positions` and attends over [0, positions]. Returns
    (logits [B, V] f32, new_cache). The engine jits this with the cache donated
    so XLA updates it in place in HBM.

    HBM-traffic design (found by profiling the serving engine on a v5e): the
    layer scan must NOT carry or re-emit the cache — stacking per-layer cache
    outputs rewrites the entire [L,B,S,K,Hd] buffer every token (hundreds of
    MB of pure waste). Instead each layer reads its cache slice (scan `xs`,
    a view), attends over `cache ⊕ current token` with the current k/v kept
    separate, and emits only the new [B,K,Hd] row; ONE scatter after the scan
    writes all L rows into the stacked cache in place.
    """
    B = tokens.shape[0]
    use_sp = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_sp and cfg.is_mla:
        raise NotImplementedError("MLA + sp is excluded (PARITY.md)")
    h = _embed(cfg, params, tokens)  # [B, D]

    if use_sp:
        def attend(q, k, v, sliding, kc, vc):
            return decode_attention_appended_sp(
                q, kc, vc, k, v, positions, mesh, **_mask_opts(cfg, sliding))
    else:
        def attend(q, k, v, sliding, kc, vc):
            return decode_attention_appended(
                q, kc, vc, k, v, positions, **_mask_opts(cfg, sliding))

    layer = functools.partial(
        _decoder_layer, cfg, pos=positions, inv=_rope_inv(cfg), attend=attend,
        ep=ep, mesh=mesh)
    h, (new_k, new_v) = _scan_layers(
        cfg, params, h, layer, (cache.k, cache.v)
    )
    # One scatter: cache[l, b, positions[b]] = new row, all layers at once.
    with scope("attention/cache_write"):
        batch_idx = jnp.arange(B)
        k = cache.k.at[:, batch_idx, positions].set(new_k.astype(cache.k.dtype))
        v = cache.v.at[:, batch_idx, positions].set(new_v.astype(cache.v.dtype))
    logits = _unembed(cfg, params, _final_norm(cfg, params, h), mesh)
    return logits, KVCache(k=k, v=v)


def self_draft_view(cfg: ArchConfig, params: Params):
    """Early-exit draft view for `spec_mode=self_draft` (ISSUE 12,
    docs/SPECULATIVE.md): the target's first `cfg.self_draft_layers` layers
    plus the SHARED embed/final-norm/unembed act as the draft model, so one
    set of sharded weights serves both roles.

    Called INSIDE the traced spec program: the [:k] slices of the stacked
    [L, ...] layer tensors are views XLA fuses into the draft scan's operand
    reads — no second parameter tree is ever materialized in HBM (the whole
    point vs a separate draft checkpoint). Works for plain and quantized
    stacks alike (every leaf, scale tensors included, carries the leading L
    axis). Heterogeneous stacks (MoE / DeepSeek dense-prefix / MLA) are
    rejected at engine construction, not here.

    Returns (draft_cfg, draft_params): cfg with num_layers=k, params with
    only the sliced homogeneous "layers" stack swapped.
    """
    k = cfg.self_draft_layers
    assert 0 < k < cfg.num_layers, "engine validates self_draft_layers"
    view = {name: leaf for name, leaf in params.items() if name != "layers"}
    view["layers"] = jax.tree.map(lambda a: a[:k], params["layers"])
    import dataclasses as _dc

    return _dc.replace(cfg, num_layers=k), view


def decode_step_windowed(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B] current token per slot
    positions: jnp.ndarray,  # [B] its position
    cache: KVCache,  # READ-ONLY within a decode block
    local_k: jnp.ndarray,  # [L, B, n, K, Hd] — block-local KV window
    local_v: jnp.ndarray,
    step: jnp.ndarray,  # scalar index within the block
    ep: int = 1,
    mesh=None,  # Mesh: sp>1 → sp-sharded cache; tp>1 → head-sharded Pallas
    ptable=None,  # [B, MP] int32 → `cache` is a page pool (paged KV mode)
    paged_impl: str = "auto",  # paged attention kernel: auto|pallas|xla
    kv_scale=None,  # [2, K] f32 per-head (k, v) pool dequant scales (fp8 KV)
    rope_delta=None,  # [B] int32 — m-rope: rope at positions+delta (cache
    # rows stay at positions). After a Qwen2-VL image prefill the 3D
    # position streams are all equal and offset from the row index by a
    # per-request constant, so plain rope at the shifted position is exact.
    lora=None,  # (stacked adapter factors, ids [B]) — per-slot runtime
    # LoRA deltas applied unmerged beside the base matmuls (ISSUE 10)
    expert_rows: bool = False,  # also return the router's rows per expert
    recurrent=None,  # hybrid models, as `block_recurrent` gives it: (state,
    # conv), the per-slot recurrent state of the KDA or SSD layers ((None,
    # conv) of conv layers), updated in place; window layers' (ring_k,
    # ring_v, win_k, win_v), the rings read-only and this step's rows
    # written into the block's windows; returned LAST
    kda_impl: str = "auto",  # the recurrent kind's decode kernel (KDA's or
    # SSD's): auto|pallas|xla
):
    """One step of a fused decode block with a block-local KV window.

    The cache is never written here — each layer emits its new row, which is
    appended to the local window; the engine scatters the whole window into
    the cache once per block. Returns (logits [B, V] f32, local_k, local_v).
    One layer body serves all three cache layouts (dense / sp-sharded /
    paged) — only the attention call differs.

    With `expert_rows` a fourth value follows: [L, E] int32, how many of the
    B rows the router sent to each expert in each layer (zeros for a
    dense-prefix layer), or None where no layer ran a router that `_mlp`
    sees (dense models, the ep > 1 capacity dispatch).
    """
    use_sp = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_sp and cfg.is_mla:
        raise NotImplementedError("MLA + sp is excluded (PARITY.md)")
    # MLA rotates at the row index: `rope_delta` reaches GQA alone.
    with scope("attention/rope"):
        rope_pos = (positions if rope_delta is None or cfg.is_mla
                    else positions + rope_delta)
    h = _embed(cfg, params, tokens)
    sink = dict(sink=cfg.attention_sink, swin=cfg.attention_window)

    if ptable is not None:
        # A latent row padded to lane tiles (`cfg.latent_pad`) is laid out
        # for the latent paged kernel: this step says so, and which of the
        # row's lanes `_mla_unlatent` reads as values; the op checks it.
        latent = cfg.is_mla and bool(cfg.latent_pad)

        def attend(q, k, v, sliding, kc, vc, lk, lv, ring=False):
            # `ring`: a window layer's, whose kc/vc are its slots' rings
            return decode_attention_windowed_paged(
                q, kc, vc, ring_table(cfg, q.shape[0]) if ring else ptable,
                lk, lv, k, v, positions, step,
                impl=paged_impl, kv_scale=None if ring else kv_scale,
                latent=latent, values=cfg.kv_lora_rank if latent else 0,
                ring=cfg.ring_rows if ring else 0,
                **_mask_opts(cfg, sliding, mesh=mesh, **sink))
    elif use_sp:
        def attend(q, k, v, sliding, kc, vc, lk, lv):
            return decode_attention_windowed_sp(
                q, kc, vc, lk, lv, k, v, positions, step, mesh,
                **_mask_opts(cfg, sliding, **sink))
    else:
        def attend(q, k, v, sliding, kc, vc, lk, lv):
            return decode_attention_windowed(
                q, kc, vc, lk, lv, k, v, positions, step,
                **_mask_opts(cfg, sliding, **sink))

    inv = _rope_inv(cfg)
    routed = []  # trace time: did any layer see a router's choice

    def body(h, xs):
        """The layer and, with `expert_rows`, its rows per expert."""
        picks = [] if expert_rows else None
        h, rows = _decoder_layer(
            cfg, h, xs, pos=rope_pos, inv=inv, attend=attend, ep=ep,
            mesh=mesh, lora=lora, picks=picks)
        if not expert_rows:
            return h, rows
        if picks:
            routed.append(True)
        return h, rows + (_expert_counts(cfg, picks),)

    pool = _paged_pool(cache) if ptable is not None else (cache.k, cache.v)
    extras = pool + (local_k, local_v)
    if lora is not None:
        extras = extras + (lora[0],)
    if cfg.is_hybrid:
        if recurrent is None or lora is not None or use_sp:
            raise NotImplementedError(
                f"{cfg.name}: a hybrid ({cfg.recurrent_kind}) model decodes "
                "with its recurrent state, without runtime LoRA and sequence "
                "parallelism")

        mix = RECURRENT[cfg.recurrent_kind].decode_mix

        def rec_mix(lp, x, rec, j):
            return mix(cfg, lp, x, rec, j, impl=kda_impl)

        swa = cfg.recurrent_kind == "swa"
        if swa:
            # The rings are read-only within a block, as the pool is: a
            # window layer reads its ring as it stood at the block's start
            # beside the block's own rows (`win_k`, `win_v`), which ARE
            # carried through the layer scan: each window layer writes its
            # new row into its own part of them, in place, as a recurrent
            # kind updates its state.
            if ptable is None:
                raise NotImplementedError(
                    f"{cfg.name}: window layers decode beside a paged pool")
            ring_k, ring_v, *wins = recurrent
            rings = (quant.StackedLayer(ring_k), quant.StackedLayer(ring_v))

            def layer_rings(rec, j):
                with jax.named_scope("layer_kv_pool"):
                    return _take_layer(rings, j) + _take_layer(tuple(rec), j)

            @scope("attention/cache_write")
            def keep_row(rec, j, rows):
                return tuple(jax.lax.dynamic_update_slice(
                    win, new.astype(win.dtype)[None, :, None],
                    (j, 0, step, 0, 0)) for win, new in zip(rec, rows)), None

            rec_mix = SwaMix(functools.partial(attend, ring=True),
                             layer_rings, keep_row)
            recurrent = wins

        h, recurrent, rows_k, (new_k, new_v, *rows_e) = _scan_hybrid(
            cfg, params, h, tuple(recurrent), *_hybrid_layer_fns(
                cfg, rec_mix, pos=rope_pos, inv=inv, attend=attend,
                mla_full=False, ep=ep, mesh=mesh, count=expert_rows),
            extras=extras)
        if swa:
            rows_k = rows_k[0] if rows_k else None  # the routers' counts
            recurrent = (ring_k, ring_v) + tuple(recurrent)
        if expert_rows:  # recurrent layers' MLPs, then the cache layers'
            with scope("mlp/router"):
                rows_e = [jnp.concatenate([rows_k, rows_e[0]], axis=0)]
            routed.extend([True] * cfg.is_moe)
    else:
        h, (new_k, new_v, *rows_e) = _scan_layers(cfg, params, h, body, extras)
    with scope("attention/cache_write"):
        local_k = jax.lax.dynamic_update_index_in_dim(
            local_k, new_k.astype(local_k.dtype), step, axis=2
        )
        local_v = jax.lax.dynamic_update_index_in_dim(
            local_v, new_v.astype(local_v.dtype), step, axis=2
        )
    logits = _unembed(cfg, params, _final_norm(cfg, params, h), mesh)
    out = (logits, local_k, local_v)
    if expert_rows:
        out = out + ((rows_e[0] if routed else None),)
    if cfg.is_hybrid:
        out = out + (recurrent,)
    return out


@scope("attention/cache_write")
def write_block_to_cache(
    cache: KVCache,
    local_k: jnp.ndarray,  # [L, B, n, K, Hd]
    local_v: jnp.ndarray,
    start_positions: jnp.ndarray,  # [B] — block start per slot
) -> KVCache:
    """Scatter a decode block's local KV window into the cache (once per
    block). Overshooting rows clamp to S-1 (host discards those tokens)."""
    L, B, n = local_k.shape[:3]
    S = cache.k.shape[2]
    span = jnp.minimum(start_positions[:, None] + jnp.arange(n)[None, :], S - 1)
    bi = jnp.arange(B)[:, None]
    k = cache.k.at[:, bi, span].set(local_k.astype(cache.k.dtype))
    v = cache.v.at[:, bi, span].set(local_v.astype(cache.v.dtype))
    return cache._replace(k=k, v=v)


def decode_chunk(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] — T new tokens per slot (draft window)
    positions: jnp.ndarray,  # [B, T] int32 — their positions (contiguous per slot)
    cache: KVCache,
    ep: int = 1,
    ptable=None,  # [B, MP] int32 → `cache` is a page pool (paged KV mode)
    paged_impl: str = "auto",  # paged attention kernel: auto|pallas|xla
    mesh=None,  # Mesh with tp>1 → paged Pallas kernel head-sharded
    kv_scale=None,  # [2, K] f32 per-head (k, v) pool dequant scales (fp8 KV)
    lora=None,  # (stacked adapter factors, ids [B]) — per-slot runtime LoRA
    # deltas applied unmerged beside the base matmuls, so model-free spec
    # verify composes with multi-tenant adapters (ISSUE 12; the [B, T, in]
    # delta rides the XLA gather oracle, same as prefill)
):
    """Multi-token decode: write T new k/v per slot and return logits for all
    T positions — the verify pass of speculative decoding (the reference
    passes draft tokens to llama.cpp's batch decode; model_config.go:211
    draft_model). Positions must be contiguous per slot. Token t attends to
    the cache prefix (< positions[b, 0]) plus in-window tokens causally; the
    window k/v stay separate operands so — as in decode_step — the layer
    scan never re-emits the cache, and one scatter writes all L×T rows.
    With `ptable`, the prefix read walks the page pool (online-softmax
    partials) and the write routes through the table — speculative decoding
    composes with the paged cache."""
    B, T = tokens.shape
    h = _embed(cfg, params, tokens)  # [B, T, D]
    with scope("attention/mix"):
        causal = jnp.tril(jnp.ones((T, T), bool))
        # In-window distance t-u (positions are contiguous per slot), for the
        # gemma-2 sliding mask.
        win_dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]

    if ptable is not None:
        def attend(q, k, v, sliding, kc, vc):
            acc, m, l = paged_partials_mq(
                q, kc, vc, ptable, positions[:, 0], q_pos=positions,
                impl=paged_impl, kv_scale=kv_scale,
                **_mask_opts(cfg, sliding, mesh=mesh))
            wmask = _slid(cfg, sliding, causal, win_dist)
            return _merge_partials_mq(
                q, acc, m, l, k, v, jnp.broadcast_to(wmask[None], (B, T, T)),
                softcap=_softcap(cfg))
    else:
        S = cache.k.shape[2]

        def attend(q, k, v, sliding, kc, vc):
            # Cache prefix: rows before the window start (later rows stale).
            prefix = _slid(
                cfg, sliding,
                jnp.arange(S)[None, None, :] < positions[:, :1, None],  # [B,1,S]
                positions[:, :, None] - jnp.arange(S)[None, None, :])
            return prefix_window_attention(
                q, kc, vc, k, v, prefix,
                _slid(cfg, sliding, causal, win_dist)[None],
                softcap=_softcap(cfg), latent=cfg.is_mla)

    layer = functools.partial(
        _decoder_layer, cfg, pos=positions, inv=_rope_inv(cfg), attend=attend,
        ep=ep, mesh=mesh, lora=lora)
    extras = _paged_pool(cache) if ptable is not None else (cache.k, cache.v)
    if lora is not None:
        extras = extras + (lora[0],)
    h, (new_k, new_v) = _scan_layers(cfg, params, h, layer, extras)
    if ptable is not None:
        cache = write_chunk_to_pool(cache, ptable, new_k, new_v, positions,
                                    kv_scale=kv_scale)
    else:
        with scope("attention/cache_write"):
            batch_idx = jnp.arange(B)[:, None].repeat(T, axis=1)  # [B, T]
            k = cache.k.at[:, batch_idx, positions].set(
                new_k.astype(cache.k.dtype))
            v = cache.v.at[:, batch_idx, positions].set(
                new_v.astype(cache.v.dtype))
        cache = KVCache(k=k, v=v)
    logits = _unembed(cfg, params, _final_norm(cfg, params, h), mesh)  # [B, T, V]
    return logits, cache


def prefill_tail(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] int32 tail tokens, right-padded
    lengths: jnp.ndarray,  # [B] int32 valid tail lengths
    offsets: jnp.ndarray,  # [B] int32 cached-prefix lengths (tail starts here)
    prefix_k: jnp.ndarray,  # [L, B, P, K, Hd] cached prefix KV; rows >= offsets[b] ignored
    prefix_v: jnp.ndarray,
    ep: int = 1,
    mesh=None,  # Mesh with tp>1 → quantized matmuls shard_map over "tp"
):
    """Prefill a prompt *tail* against cached prefix KV — the compute half of
    the prompt/prefix cache (reference: `cache_prompt`,
    backend/cpp/llama-cpp/grpc-server.cpp:125; `prompt_cache_path`,
    core/config/model_config.go:185-187). Token t of the tail attends to the
    prefix rows [0, offsets) plus the tail causally; RoPE positions are offset
    by the prefix length so the result is identical to prefilling the whole
    prompt. Returns (last_logits [B, V] f32, tail_ks [L, B, T, K, Hd],
    tail_vs) — the engine writes the tail rows after the cached span.
    """
    T = tokens.shape[1]
    P = prefix_k.shape[2]
    with scope("attention/mix"):  # the masks every layer's mixer reads
        positions = offsets[:, None] + jnp.arange(T)[None, :]  # [B, T] global
        length_mask = jnp.arange(T)[None, :] < lengths[:, None]
        causal = jnp.tril(jnp.ones((T, T), bool))
        pvalid = jnp.arange(P)[None, :] < offsets[:, None]  # [B, P]
        win_dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # in-tail t-u
    h = _embed(cfg, params, tokens)  # [B, T, D]

    def attend(q, k, v, sliding, kc, vc):  # kc/vc [B, P, K, Hd]
        pmask = _slid(cfg, sliding, pvalid[:, None, :],
                      positions[:, :, None] - jnp.arange(P)[None, None, :])
        wmask = (_slid(cfg, sliding, causal, win_dist)[None]
                 & length_mask[:, None, :])
        return prefix_window_attention(
            q, kc, vc, k, v, pmask, wmask,
            softcap=_softcap(cfg), latent=cfg.is_mla)

    layer = functools.partial(
        _decoder_layer, cfg, pos=positions, inv=_rope_inv(cfg), attend=attend,
        ep=ep, mesh=mesh, admit=[])
    h, (ks, vs) = _scan_layers(
        cfg, params, h, layer, (prefix_k, prefix_v)
    )
    last = _last_row(_final_norm(cfg, params, h), lengths)  # [B, D]
    logits = _unembed(cfg, params, last, mesh)
    return logits, ks, vs


@scope("attention/cache_write")
def write_prefill_to_cache(
    cache: KVCache,
    ks: jnp.ndarray,  # [L, B_new, S, K, Hd] from prefill
    vs: jnp.ndarray,
    slot: jnp.ndarray,  # scalar int32 — destination slot for batch row 0
) -> KVCache:
    """Copy a prefilled request's k/v into its slot (batch row 0 only).

    jit-friendly: dynamic_update_slice along the slot axis.
    """
    k = jax.lax.dynamic_update_slice(
        cache.k, ks[:, :1].astype(cache.k.dtype), (0, slot, 0, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache.v, vs[:, :1].astype(cache.v.dtype), (0, slot, 0, 0, 0)
    )
    return KVCache(k=k, v=v)


# --------------------------------------------------------------------------- #
# Paged KV cache (page pool + per-slot page tables — ops/attention.py paged)
# --------------------------------------------------------------------------- #


def paged_cache_zeros(cfg: ArchConfig, num_pages: int, page_size: int,
                      dtype=None) -> KVCache:
    """Page pool: k/v [L, P, page, K, Hd]. One pool serves every slot; the
    engine assigns pages to slots and passes per-slot tables to each program.
    HBM scales with pages in use, not slots × max_seq (SURVEY §7 ragged KV).
    MLA pools hold latent rows (see KVCache docstring); L counts the layers
    that write rows (a hybrid model's cache layers, not its KDA layers)."""
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    base = (cfg.cache_layers, num_pages, page_size, cfg.cache_kv_heads)
    return KVCache(
        k=jnp.zeros(base + (cfg.cache_k_dim,), dtype),
        v=jnp.zeros(base + (cfg.cache_v_dim,), dtype),
    )


def _pool_store(rows: jnp.ndarray, pool_dtype, scale_row) -> jnp.ndarray:
    """Cast KV rows [..., K, Hd] to the pool's storage dtype, dividing by
    the per-head kv scale first when the pool is SCALED fp8 (ISSUE 9):
    stored = value / scale, every reader multiplies back in-kernel. The
    division runs in f32 so bf16 rows keep their mantissa until the final
    fp8 cast."""
    if scale_row is None:
        return rows.astype(pool_dtype)
    return (rows.astype(jnp.float32) / scale_row[..., :, None]).astype(pool_dtype)


@scope("attention/cache_write")
def write_block_to_pool(
    pool: KVCache,
    table: jnp.ndarray,  # [B, MP] int32
    local_k: jnp.ndarray,  # [L, B, n, K, Hd]
    local_v: jnp.ndarray,
    start_positions: jnp.ndarray,  # [B]
    kv_scale=None,  # [2, K] f32 → pool rows store value/scale (fp8 KV)
    paged_impl: str = "auto",  # the engine's paged reader (EngineConfig.paged_kernel)
    mesh=None,  # Mesh with tp>1 → the pool is head-sharded
    ring: bool = False,  # the pool is the slots' rings: position p at row
    # p mod (MP·page) of its slot's pages, the block's rows wrap around
) -> KVCache:
    """Write a decode block's window into the page pool (once per block).
    Rows may straddle pages; each (slot, step) row lands at
    (table[b, row // page], row % page). Every slot is written every step —
    idle slots and rows past a slot's reservation resolve through the
    engine's SCRATCH-filled table entries to a page nobody attends, so they
    can never corrupt a live request. How the rows get there
    (`attention.write_window`): XLA's scatter, or, where a token's row of
    the pool is narrower than the native tile and the paged reader is the
    Pallas kernel, a DMA kernel that writes them in place."""
    from localai_tpu.ops import ptable as _pt
    from localai_tpu.ops.attention import write_window

    L, B, n = local_k.shape[:3]
    page = pool.k.shape[2]
    MP = _pt.width(table)
    row = start_positions[:, None] + jnp.arange(n)[None, :]  # [B, n]
    row = row % (MP * page) if ring else jnp.minimum(row, MP * page - 1)
    pid = _pt.gather_cols(table, row // page)  # [B, n]
    off = row % page
    ks = None if kv_scale is None else kv_scale[0]
    vs = None if kv_scale is None else kv_scale[1]
    k = write_window(pool.k, _pool_store(local_k, pool.k.dtype, ks),
                     pid, off, impl=paged_impl, mesh=mesh)
    v = write_window(pool.v, _pool_store(local_v, pool.v.dtype, vs),
                     pid, off, impl=paged_impl, mesh=mesh)
    return pool._replace(k=k, v=v)


def ring_table(cfg: ArchConfig, slots: int) -> jnp.ndarray:
    """[slots, ring_pages] int32: the page table of the window layers' rings,
    a constant: slot i owns pages ring_pages·i .. ring_pages·i + ring_pages-1
    of every window layer (engine/state.py)."""
    P = cfg.ring_pages
    return jnp.arange(slots * P, dtype=jnp.int32).reshape(slots, P)


@scope("attention/cache_write")
def _ring_admit(cfg: ArchConfig, rec, j, rows, lengths, slots):
    """A group of prompts' last rows into window layer j of their slots'
    rings: rec = (ring_k, ring_v) [Ls, slots·ring_pages, ring_page, K, D],
    rows = (k, v) [B, T, K, D] as the layer emitted them (k rotated), right-
    padded to `lengths`. Ring row r takes the last position below the
    prompt's length that is r mod the ring's rows; a row no position maps to
    (a prompt shorter than the ring) holds whatever, past the slot's limit
    where no reader looks. rec None (an entry point that keeps no state)
    stays None."""
    if rec is None:
        return None
    P, page, R = cfg.ring_pages, cfg.ring_page, cfg.ring_rows
    last = lengths[:, None] - 1  # [B, 1]
    t = last - (last - jnp.arange(R)[None, :]) % R  # [B, R] source positions
    t = jnp.clip(t, 0, rows[0].shape[1] - 1)
    pages = slots[:, None] * P + jnp.arange(P)[None, :]  # [B, P]
    with jax.named_scope(RING_WRITE):
        return tuple(
            ring.at[j, pages].set(
                jnp.take_along_axis(a, t[:, :, None, None], axis=1)
                .reshape(a.shape[0], P, page, *a.shape[2:]).astype(ring.dtype))
            for ring, a in zip(rec, rows))


def block_recurrent(cfg: ArchConfig, cache: KVCache, slots: int, n: int):
    """What the steps of an n-step decode block carry of a hybrid model's
    per-slot state (`decode_step_windowed(recurrent=...)`): the recurrent
    kinds' (state, conv), updated in place by every step; a window kind's
    rings, read-only within the block, and the block's own rows of the
    window layers [Ls, slots, n, K, D] beside them."""
    rec = (cache.state, cache.conv)
    if cfg.recurrent_kind == "swa":
        with scope("attention/cache_write"):
            win = jnp.zeros((len(cfg.recurrent_layers), slots, n,
                             cfg.num_kv_heads, cfg.head_dim_), cache.state.dtype)
        rec = rec + (win, win)
    return rec


def block_recurrent_done(cfg: ArchConfig, cache: KVCache, rec, start_positions,
                         paged_impl: str = "auto") -> KVCache:
    """`cache` with the per-slot state as a decode block leaves it: the
    recurrent kinds' as the steps updated it; a window kind's rings with the
    block's rows written at their positions mod the ring (the rows they
    replace lie outside every later query's window)."""
    if cfg.recurrent_kind == "swa":
        ring_k, ring_v, win_k, win_v = rec
        with jax.named_scope(RING_WRITE):
            rings = write_block_to_pool(
                KVCache(k=ring_k, v=ring_v),
                ring_table(cfg, win_k.shape[1]), win_k, win_v,
                start_positions, paged_impl=paged_impl, ring=True)
        rec = (rings.k, rings.v)
    return cache._replace(state=rec[0], conv=rec[1])


@scope("attention/cache_write")
def write_chunk_to_pool(
    pool: KVCache,
    table: jnp.ndarray,  # [B, MP] int32
    new_k: jnp.ndarray,  # [L, B, T, K, Hd]
    new_v: jnp.ndarray,
    positions: jnp.ndarray,  # [B, T] row indices (contiguous per slot)
    kv_scale=None,  # [2, K] f32 → pool rows store value/scale (fp8 KV)
) -> KVCache:
    """Scatter a speculative verify chunk's rows into the page pool (the
    paged counterpart of decode_chunk's dense scatter). Rows resolve through
    the table like write_block_to_pool — rejected-window overshoot rows land
    in later pages of the same slot's reservation and are overwritten by the
    next round's writes at the same positions."""
    from localai_tpu.ops import ptable as _pt

    page = pool.k.shape[2]
    MP = _pt.width(table)
    row = jnp.minimum(positions, MP * page - 1)  # [B, T]
    pid = _pt.gather_cols(table, row // page)  # [B, T]
    off = row % page
    ks = None if kv_scale is None else kv_scale[0]
    vs = None if kv_scale is None else kv_scale[1]
    k = pool.k.at[:, pid, off].set(_pool_store(new_k, pool.k.dtype, ks))
    v = pool.v.at[:, pid, off].set(_pool_store(new_v, pool.v.dtype, vs))
    return KVCache(k=k, v=v)


@scope("attention/cache_write")
def write_rows_to_pool(
    pool: KVCache,
    table_row: jnp.ndarray,  # [MP] int32 — the destination slot's pages
    ks: jnp.ndarray,  # [L, 1, R, K, Hd]
    vs: jnp.ndarray,
    start_row: jnp.ndarray,  # scalar int32 — first destination row
    kv_scale=None,  # [2, K] f32 → pool rows store value/scale (fp8 KV)
) -> KVCache:
    """Scatter R contiguous rows starting at `start_row` into one slot's
    pages (cached-admission tail rows, which start mid-sequence and are not
    page-aligned)."""
    from localai_tpu.ops import ptable as _pt

    R = ks.shape[2]
    page = pool.k.shape[2]
    MP = _pt.width(table_row)
    row = jnp.minimum(start_row + jnp.arange(R), MP * page - 1)  # [R]
    pid = _pt.row_lookup(table_row, row // page)  # [R]
    off = row % page
    ksc = None if kv_scale is None else kv_scale[0]
    vsc = None if kv_scale is None else kv_scale[1]
    k = pool.k.at[:, pid, off].set(_pool_store(ks[:, 0], pool.k.dtype, ksc))
    v = pool.v.at[:, pid, off].set(_pool_store(vs[:, 0], pool.v.dtype, vsc))
    return KVCache(k=k, v=v)


@scope("attention/mix")  # the prefix the mixer reads, made contiguous
def gather_pages(
    pool: KVCache,
    pages: jnp.ndarray,  # [NP] int32 page ids (SCRATCH-padded past the span)
    kv_scale=None,  # [2, K] f32 → rows come back DEQUANTIZED (value·scale)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize a page list as contiguous KV rows [L, 1, NP*page, K, Hd]
    — the read half of prefix-span sharing under the paged cache (the span's
    pages are mapped read-only; prefill_tail consumes a dense prefix
    operand)."""
    k = pool.k[:, pages]  # [L, NP, page, K, Hd]
    v = pool.v[:, pages]
    if kv_scale is not None:
        # A SCALED fp8 pool stores value/scale — the dense prefix operand
        # prefill_tail consumes must be real values again.
        k = k.astype(jnp.float32) * kv_scale[0][..., :, None]
        v = v.astype(jnp.float32) * kv_scale[1][..., :, None]
    L, NP, page, K = k.shape[:4]  # each at its own width: MLA's v has none
    return (
        k.reshape(L, 1, NP * page, K, k.shape[-1]),
        v.reshape(L, 1, NP * page, K, v.shape[-1]),
    )


def prefill_chunk_paged(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,  # [B, T] int32 chunk tokens, right-padded
    lengths: jnp.ndarray,  # [B] int32 valid chunk lengths
    offsets: jnp.ndarray,  # [B] int32 rows already resident (chunk starts here)
    pool: KVCache,
    table,  # [B, MP] int32 page tables (prefix + destination pages), or
    # the hierarchical (l1, l0) pair (ops/ptable)
    ep: int = 1,
    paged_impl: str = "auto",
    with_logits: bool = True,
    mesh=None,  # Mesh with tp>1 → paged Pallas kernel head-sharded
    kv_scale=None,  # [2, K] f32 per-head (k, v) pool dequant scales (fp8 KV)
    sp_mesh=None,  # Mesh with sp>1 → the chunk's attention runs ring-
    # sharded over "sp" (parallel/ring.ring_chunk_paged_attention): each
    # shard holds T/sp chunk tokens, walks the slot's resident pages for
    # its own queries (pool replicated over sp) and rotates the in-chunk
    # K/V blocks neighbor-to-neighbor — per-chip chunk compute is T/sp
    # while the fresh K/V still scatters straight into pool pages
):
    """One chunk of a ragged chunked prefill, direct-to-page (ISSUE 2;
    sequence-parallel leg + windowed+sink prefix walk: ISSUE 14,
    docs/LONG_CONTEXT.md).

    Chunk token t attends the slot's already-written rows [0, offsets[b])
    through the paged-partials walk — the same scalar-prefetch page-table
    kernel as decode (ops/paged_flash, Pallas on TPU; the query-row axis is
    tiled so a whole chunk's online-softmax state fits VMEM) — plus the
    in-chunk causal window, and the chunk's fresh K/V rows scatter STRAIGHT
    into the slot's pages at rows [offsets, offsets+T). Unlike
    `prefill` + `write_prefill_to_pool` there is no dense full-bucket KV
    intermediate and no bucket→page scatter: per-chunk HBM traffic is the
    chunk itself plus one streamed read of the live prefix.

    Padding rows (t >= lengths[b]) write garbage rows past the prompt inside
    the slot's own reservation; decode overwrites each such row before any
    query can attend it (same invariant as the dense bucket's padding).
    Returns (last_logits [B, V] f32 | None, new_pool) — mid chunks skip the
    unembed entirely (with_logits=False).
    """
    T = tokens.shape[1]
    with scope("attention/mix"):  # the masks every layer's mixer reads
        positions = offsets[:, None] + jnp.arange(T)[None, :]  # [B, T] global
        length_mask = jnp.arange(T)[None, :] < lengths[:, None]
        causal = jnp.tril(jnp.ones((T, T), bool))
        win_dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # in-chunk t-u
    h = _embed(cfg, params, tokens)  # [B, T, D]
    sink = dict(sink=cfg.attention_sink, swin=cfg.attention_window)

    # kc/vc below: the [L, P, page, K, Hd] pools at this layer (StackedLayer)
    if sp_mesh is not None and not cfg.is_mla:
        # Sequence-parallel chunk attention (ISSUE 14): ring over "sp".
        from localai_tpu.parallel.ring import ring_chunk_paged_attention

        def attend(q, k, v, sliding, kc, vc):
            return ring_chunk_paged_attention(
                q, k, v, offsets, lengths, kc, vc, table, sp_mesh,
                kv_scale=kv_scale, **_mask_opts(cfg, sliding, **sink))
    else:
        def attend(q, k, v, sliding, kc, vc):
            acc, m, l = paged_prefill_partials(
                q, kc, vc, table, offsets, q_pos=positions, impl=paged_impl,
                kv_scale=kv_scale,
                **_mask_opts(cfg, sliding, mesh=mesh, **sink))
            wmask = _slid(cfg, sliding, causal[None] & length_mask[:, None, :],
                          win_dist[None])  # [B, T, T]
            return _merge_partials_mq(q, acc, m, l, k, v, wmask,
                                      softcap=_softcap(cfg))

    layer = functools.partial(
        _decoder_layer, cfg, pos=positions, inv=_rope_inv(cfg), attend=attend,
        ep=ep, mesh=mesh, admit=[])
    h, (new_k, new_v) = _scan_layers(cfg, params, h, layer, _paged_pool(pool))
    pool = write_chunk_to_pool(pool, table, new_k, new_v, positions,
                               kv_scale=kv_scale)
    if not with_logits:
        return None, pool
    last = _last_row(_final_norm(cfg, params, h), lengths)  # [B, D]
    return _unembed(cfg, params, last, mesh), pool


@scope("attention/cache_write")
def write_rows_to_cache(
    cache: KVCache,
    slot: jnp.ndarray,  # scalar int32 — destination slot
    ks: jnp.ndarray,  # [L, 1, T, K, Hd]
    vs: jnp.ndarray,
    start_row: jnp.ndarray,  # scalar int32 — first destination row
) -> KVCache:
    """Write T contiguous rows starting at `start_row` into one DENSE slot —
    the dense-cache counterpart of write_rows_to_pool (chunked prefill
    writes each chunk's rows mid-sequence)."""
    k = jax.lax.dynamic_update_slice(
        cache.k, ks[:, :1].astype(cache.k.dtype), (0, slot, start_row, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache.v, vs[:, :1].astype(cache.v.dtype), (0, slot, start_row, 0, 0)
    )
    return KVCache(k=k, v=v)


@scope("attention/cache_write")
def write_prefill_to_pool(
    pool: KVCache,
    table_row: jnp.ndarray,  # [MP] int32 — the destination slot's pages
    ks: jnp.ndarray,  # [L, B_new, Sb, K, Hd] from prefill
    vs: jnp.ndarray,
    j: int,  # batch row within ks/vs (static)
    kv_scale=None,  # [2, K] f32 → pool rows store value/scale (fp8 KV)
) -> KVCache:
    """Copy one prefilled request's KV into its pages. The prompt starts at
    row 0, so writes are page-aligned; the (static) trailing partial page
    writes whatever fits. Chunked admission (EngineConfig.prefill_chunk)
    bypasses this dense-bucket scatter entirely — see prefill_chunk_paged."""
    from localai_tpu.ops import ptable as _pt

    Sb = ks.shape[2]
    page = pool.k.shape[2]
    k, v = pool.k, pool.v
    ksc = None if kv_scale is None else kv_scale[0]
    vsc = None if kv_scale is None else kv_scale[1]
    for p in range(-(-Sb // page)):  # static page count for this bucket
        lo = p * page
        chunk_k = ks[:, j, lo: lo + page]  # [L, c, K, Hd], c static
        chunk_v = vs[:, j, lo: lo + page]
        k = jax.lax.dynamic_update_slice(
            k, _pool_store(chunk_k, k.dtype, ksc)[:, None],
            (0, _pt.row_lookup(table_row, p), 0, 0, 0)
        )
        v = jax.lax.dynamic_update_slice(
            v, _pool_store(chunk_v, v.dtype, vsc)[:, None],
            (0, _pt.row_lookup(table_row, p), 0, 0, 0)
        )
    return pool._replace(k=k, v=v)
