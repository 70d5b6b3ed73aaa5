"""Plain reference: the Mistral-7B decoder block (pre-norm, grouped-query
attention with split-half rotary embeddings, SwiGLU) in straightforward
`jax.numpy`, float32, matmul precision "highest". No cache, no kernel, no
batching, and none of the program's forward code: it follows the published
architecture (arXiv:2310.06825 and the HF `MistralForCausalLM` layout), with
one departure, stated in the configuration files: the 4,096-token sliding
window is not applied (contexts here never exceed it).

It reads the served model's parameter arrays as DATA: the engine's tree has
the layers stacked on a leading axis, `[in, out]` projection matrices, and
int8 weights as {"q": int8, "s": f32 per-output-channel scale}. One layer is
dequantised at a time, so the float32 copy fits beside the model.

`compute`, `weight_round` and `kv_round` exist to derive and to test the
tolerance of `correct` (see benchmark/README.md): "bfloat16" compute is the
honest rounding a bf16 server performs; "int4" weights and "fp8" keys and
values are lower precisions the check must catch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _dequant(w) -> jnp.ndarray:
    if isinstance(w, dict):
        return w["q"].astype(F32) * w["s"].astype(F32)
    return w.astype(F32)


def _round_int4(w: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Symmetric per-output-channel 4-bit rounding of a float matrix."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 7.0, 1e-12)
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def _weight(w, weight_round: str, axis: int = -2) -> jnp.ndarray:
    w = _dequant(w)
    if weight_round == "int4":
        w = _round_int4(w, axis)
    elif weight_round:
        raise ValueError(f"unknown weight rounding {weight_round!r}")
    return w


def _rms_norm(x, weight, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _rope(x, positions, theta):
    """x [T, heads, hd]; rotate the two halves of each head (HF layout)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w, dt):
    """x @ w in the compute type `dt`, accumulated in float32."""
    return jnp.dot(x.astype(dt), w.astype(dt), preferred_element_type=F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "compute", "weight_round",
    "kv_round"))
def layer(h, lw, *, heads, kv_heads, theta, eps, compute="float32",
          weight_round="", kv_round=""):
    """One decoder layer over the whole sequence. h: [T, D] in `compute`."""
    dt = jnp.dtype(compute)
    T, D = h.shape
    hd = lw["wq"]["q"].shape[-1] // heads if isinstance(lw["wq"], dict) \
        else lw["wq"].shape[-1] // heads
    W = {k: _weight(lw[k], weight_round)
         for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    pos = jnp.arange(T)
    x = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _mm(x, W["wq"], dt).astype(dt).reshape(T, heads, hd)
    k = _mm(x, W["wk"], dt).astype(dt).reshape(T, kv_heads, hd)
    v = _mm(x, W["wv"], dt).astype(dt).reshape(T, kv_heads, hd)
    q = _rope(q.astype(F32), pos, theta).astype(dt)
    k = _rope(k.astype(F32), pos, theta).astype(dt)
    if kv_round == "fp8":
        k = k.astype(jnp.float8_e4m3fn).astype(dt)
        v = v.astype(jnp.float8_e4m3fn).astype(dt)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    g = heads // kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(hd))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    a = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    h = (h.astype(F32) + _mm(a.reshape(T, heads * hd).astype(dt), W["wo"], dt)
         ).astype(dt)
    x = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    gate = jax.nn.silu(_mm(x, W["w_gate"], dt)).astype(dt)
    up = _mm(x, W["w_up"], dt).astype(dt)
    down = _mm((gate.astype(F32) * up.astype(F32)).astype(dt), W["w_down"], dt)
    return (h.astype(F32) + down).astype(dt)


@functools.partial(jax.jit, static_argnames=("eps", "compute", "weight_round"))
def head(h_rows, final_norm, lm_head, *, eps, compute="float32",
         weight_round=""):
    """log-softmax over the vocabulary for the given rows. lm_head: [V, D]."""
    dt = jnp.dtype(compute)
    w = _weight(lm_head, weight_round, axis=-1)
    x = _rms_norm(h_rows, final_norm, eps).astype(dt)
    logits = jnp.dot(x, w.astype(dt).T, preferred_element_type=F32)
    return jax.nn.log_softmax(logits.astype(F32), axis=-1)


def arch_of(cfg) -> dict:
    """The few sizes the block needs, from the program's ArchConfig or a
    plain dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    return {"heads": int(get("num_heads")), "kv_heads": int(get("num_kv_heads")),
            "theta": float(get("rope_theta")), "eps": float(get("rms_eps")),
            "layers": int(get("num_layers"))}


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids` (a list of token ids); returns
    log-probabilities [len(rows), V] at the positions in `rows`.

    The sequence is right-padded to a multiple of `pad_to` so a handful of
    shapes compile; with causal attention the padding cannot reach an
    earlier position. `hidden_after`, a list, receives the hidden state of
    `rows` after every layer (for bisecting a disagreement).
    """
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        for li in range(a["layers"]):
            lw = jax.tree.map(lambda x: x[li], params["layers"])
            h = layer(h, lw, heads=a["heads"], kv_heads=a["kv_heads"],
                      theta=a["theta"], eps=a["eps"], compute=compute,
                      weight_round=weight_round, kv_round=kv_round)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        lm = params.get("lm_head", params["embed"])
        out = head(h[jnp.asarray(rows)], params["final_norm"], lm,
                   eps=a["eps"], compute=compute, weight_round=weight_round)
        return np.asarray(out)
