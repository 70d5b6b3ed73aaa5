"""Bytes a decode step of an S6 + NoPE multi-query hybrid (AI21-Jamba2:
Mamba-1 layers with a per-slot float32 state beside a few attention layers of
many query heads over ONE key/value head in an ordinary paged pool; a dense
SwiGLU in every layer; a tied bfloat16 head) has to read and write, from
shapes alone. Kept with the benchmark, beside the other `costs_*.py`, for the
same reason: no PR that claims a gain can change the yardstick.

`arch` is the configuration file: the published config.json's keys
(`attn_layer_period` / `_offset`, `mamba_*`, `intermediate_size`, ...) and
`head_dim`. What a step touches, as this program's kernels are built, and
ONLY what moves (rows read, not rows held; nothing a kernel skips):

- every matrix once, whatever the batch: `in_proj` [D, 2 E], `x_proj`
  [E, R + 2 N], `dt_proj` [R, E] and `out_proj` [E, D] of every Mamba layer,
  the four projections of every attention layer, the three of every layer's
  SwiGLU (int8 at `bytes_per_weight`), the conv's taps and bias and the
  inner norms (bfloat16), A_log, D and the step's bias (float32), and the
  head, which is the embedding (tied: bfloat16 as held, 2 bytes a weight
  whatever `bytes_per_weight` says);
- per compiled batch row the whole recurrent state of every Mamba layer,
  read AND written (float32 [N, E]; `s6_decode` updates every row, live or
  not, so every compiled row moves and is counted), its operands (dt, dt x
  in and y out [E], B and C [N], float32) and the conv's held inputs, read
  and written (bfloat16, `mamba_d_conv` - 1 rows of E);
- per live TOKEN the key and the value of the one K/V head in every
  attention layer, read once. Tokens, not pages: the reader's copies are of
  whole pages, but what is counted is what a reader has to move, so the
  share errs low by the last page's unused rows.

Norms' weights of the layer stack, scales, the activations and the embedding
rows gathered are left out (under 0.1% at these shapes): the count errs low.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def layers(arch: dict) -> dict:
    n = arch["num_hidden_layers"]
    mqa = sum(1 for l in range(n)
              if l % arch["attn_layer_period"] == arch["attn_layer_offset"])
    return {"s6": n - mqa, "mqa": mqa, "all": n}


def d_inner(arch: dict) -> int:
    return arch["mamba_expand"] * arch["hidden_size"]


def s6_layer_params(arch: dict) -> dict:
    """One Mamba layer's: {"int8": in_proj + x_proj + dt_proj + out_proj,
    "f32": A_log [E, N], D and the step's bias, "bf16": the taps, their bias
    and the three inner norms' weights}."""
    D, E = arch["hidden_size"], d_inner(arch)
    N, R = arch["mamba_d_state"], arch["mamba_dt_rank"]
    return {"int8": D * 2 * E + E * (R + 2 * N) + R * E + E * D,
            "f32": E * N + 2 * E,
            "bf16": (arch["mamba_d_conv"] + 1) * E + R + 2 * N}


def mqa_layer_params(arch: dict) -> int:
    """One attention layer's W_q, W_k, W_v, W_o, all int8."""
    D, H, K, hd = (arch["hidden_size"], arch["num_attention_heads"],
                   arch["num_key_value_heads"], arch["head_dim"])
    return 2 * D * H * hd + 2 * D * K * hd


def mlp_params(arch: dict) -> int:
    return 3 * arch["hidden_size"] * arch["intermediate_size"]


def param_count(arch: dict) -> int:
    """Every parameter of the published model (what its card calls its
    size): the tied matrix once, the norms of the layer stack too."""
    n = layers(arch)
    D = arch["hidden_size"]
    return (n["s6"] * sum(s6_layer_params(arch).values())
            + n["mqa"] * mqa_layer_params(arch) + n["all"] * mlp_params(arch)
            + arch["vocab_size"] * D + (2 * n["all"] + 1) * D)


def proj_matmul_bytes(arch: dict, bytes_per_weight: float) -> float:
    """The int8 matrices, each read once a step by the dense dequant-matmul
    (`int8_matmul`): every Mamba layer's four, every attention layer's four,
    every layer's SwiGLU. Their scales are left out: the count errs low."""
    n = layers(arch)
    return bytes_per_weight * (
        n["s6"] * s6_layer_params(arch)["int8"]
        + n["mqa"] * mqa_layer_params(arch) + n["all"] * mlp_params(arch))


def weight_bytes(arch: dict, bytes_per_weight: float) -> float:
    """Matrix bytes one decode step has to read."""
    s6 = s6_layer_params(arch)
    return (proj_matmul_bytes(arch, bytes_per_weight)
            + layers(arch)["s6"] * (s6["f32"] * F32 + s6["bf16"] * BF16)
            + arch["vocab_size"] * arch["hidden_size"] * BF16)


def state_bytes_per_row(arch: dict) -> int:
    """One slot's recurrent state over all Mamba layers, as held: the
    float32 [N, E] matrices and the conv's last inputs in bfloat16."""
    E = d_inner(arch)
    return layers(arch)["s6"] * (
        arch["mamba_d_state"] * E * F32
        + (arch["mamba_d_conv"] - 1) * E * BF16)


def s6_state_bytes_per_row(arch: dict) -> int:
    """The float32 state alone that `s6_decode` reads AND writes for one
    slot a step over all Mamba layers (2 x 327,680 B a layer at 16 x 5120):
    the kernel's operands (dt, dt x, y: 60 KB a layer) are left out, so the
    share errs low."""
    return layers(arch)["s6"] * 2 * arch["mamba_d_state"] * d_inner(arch) * F32


def kv_bytes_per_token(arch: dict, bytes_per_kv: float) -> float:
    """One token's key and value over the attention layers."""
    return (layers(arch)["mqa"] * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * bytes_per_kv)


def decode_step_bytes(arch: dict, rows: float, live_tokens: float,
                      bytes_per_weight: float, bytes_per_kv: float) -> float:
    """`rows`: the compiled batch rows; `live_tokens`: the live requests'
    tokens (not rounded up to pages)."""
    E = d_inner(arch)
    n = layers(arch)["s6"]
    operands = n * (3 * E + 2 * arch["mamba_d_state"]) * F32
    conv = 2 * n * (arch["mamba_d_conv"] - 1) * E * BF16
    return (weight_bytes(arch, bytes_per_weight)
            + rows * (s6_state_bytes_per_row(arch) + operands + conv)
            + live_tokens * kv_bytes_per_token(arch, bytes_per_kv))
