"""Plain reference: the OLMoE decoder block (pre-norm; multi-head attention
with ONE RMS norm over the whole q projection and one over the whole k
projection, split-half rotary embeddings; a sparse mixture of SwiGLU experts
whose router softmaxes over ALL experts, takes the top k and uses those
weights as they are) in straightforward `jax.numpy`, float32, matmul
precision "highest". No cache, no kernel, no batching, no sort, and none of
the program's forward code: it follows the published block (HF
`OlmoeAttention`, `OlmoeSparseMoeBlock`; arXiv:2409.02060), per layer, x [T, D]:

    a = rms_norm(x, input_layernorm)
    q = rms_norm(a Wq, q_norm)   k = rms_norm(a Wk, k_norm)   v = a Wv
    heads of head_dim; rotary on q, k; causal softmax(q k^T / sqrt(hd)) v
    x = x + attn Wo
    m = rms_norm(x, post_attention_layernorm)
    p = softmax(m Wg) over all E experts, float32;  (w, e) = top_k(p, k)
    x = x + sum_j w_j * W_down[e_j]( silu(W_gate[e_j] m) * W_up[e_j] m )

Departures from the published block: none (`clip_qkv` is null there, the
weights are random and the tokenizer synthetic, as the configuration file
says). The helpers shared with the dense reference (norm, rotary, matmul in
a compute type, weight rounding, the output head) are that file's.

It reads the served model's parameter arrays as DATA: layers stacked on a
leading axis, experts on the next, `[in, out]` matrices, int8 weights as
{"q", "s"}. The experts run as a plain loop over all E, one expert
dequantised at a time (a whole float32 expert layer would be 1.6 GB), each
token's result weighted by its routing weight for that expert, which is zero
where the token did not choose it: the same sum as over the chosen k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import (
    F32,
    _mm,
    _rms_norm,
    _rope,
    _weight,
    head,
)


def _at(w, *idx):
    """w[idx] of a plain or quantized stacked weight, idx traced or not."""
    def take(a):
        for i in idx:
            a = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        return a

    return {k: take(v) for k, v in w.items()} if isinstance(w, dict) else take(w)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "compute", "weight_round",
    "kv_round"))
def attention(h, lw, *, heads, kv_heads, theta, eps, compute="float32",
              weight_round="", kv_round=""):
    """x + attention(x) of one layer over the whole sequence. h: [T, D]."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    W = {k: _weight(lw[k], weight_round) for k in ("wq", "wk", "wv", "wo")}
    hd = W["wq"].shape[-1] // heads
    pos = jnp.arange(T)
    x = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    # The q/k norms see the whole projection: all heads in one reduction.
    q = _rms_norm(_mm(x, W["wq"], dt).astype(dt), lw["q_norm"], eps).astype(dt)
    k = _rms_norm(_mm(x, W["wk"], dt).astype(dt), lw["k_norm"], eps).astype(dt)
    v = _mm(x, W["wv"], dt).astype(dt)
    q = _rope(q.reshape(T, heads, hd).astype(F32), pos, theta).astype(dt)
    k = _rope(k.reshape(T, kv_heads, hd).astype(F32), pos, theta).astype(dt)
    v = v.reshape(T, kv_heads, hd)
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
    return (h.astype(F32) + _mm(a.reshape(T, heads * hd).astype(dt), W["wo"], dt)
            ).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "compute", "weight_round"))
def experts(h, lw, *, top_k, eps, compute="float32", weight_round=""):
    """x + moe(x) of one layer. h: [T, D]; lw's expert leaves are [E, ...]."""
    dt = jnp.dtype(compute)
    m = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    E = lw["router"].shape[-1]
    p = jax.nn.softmax(_mm(m, lw["router"].astype(F32), dt), axis=-1)  # f32
    w, e = jax.lax.top_k(p, top_k)  # used as they are: no renormalisation
    # each token's weight for every expert: w_j where it chose it, else 0
    share = jnp.sum(w[:, :, None] * (e[:, :, None] == jnp.arange(E)), axis=1)

    def one(i, acc):
        gate = jax.nn.silu(_mm(m, _weight(_at(lw["w_gate"], i), weight_round), dt))
        up = _mm(m, _weight(_at(lw["w_up"], i), weight_round), dt).astype(dt)
        act = (gate.astype(dt).astype(F32) * up.astype(F32)).astype(dt)
        down = _mm(act, _weight(_at(lw["w_down"], i), weight_round), dt)
        return acc + share[:, i, None] * down

    out = jax.lax.fori_loop(0, E, one, jnp.zeros(h.shape, F32))
    return (h.astype(F32) + out).astype(dt)


def arch_of(cfg) -> dict:
    """The few sizes the block needs, from the program's ArchConfig or a
    plain dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    return {"heads": int(get("num_heads")), "kv_heads": int(get("num_kv_heads")),
            "theta": float(get("rope_theta")), "eps": float(get("rms_eps")),
            "layers": int(get("num_layers")),
            "top_k": int(get("num_experts_per_token"))}


_ATTN = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
_MOE = ("mlp_norm", "router", "w_gate", "w_up", "w_down")


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids` (a list of token ids); returns
    log-probabilities [len(rows), V] at the positions in `rows`. Padding and
    `hidden_after` as in `dense_gqa.forward`."""
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    L = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        for li in range(a["layers"]):
            h = attention(h, {k: _at(L[k], li) for k in _ATTN},
                          heads=a["heads"], kv_heads=a["kv_heads"],
                          theta=a["theta"], eps=a["eps"], compute=compute,
                          weight_round=weight_round, kv_round=kv_round)
            h = experts(h, {k: _at(L[k], li) for k in _MOE},
                        top_k=a["top_k"], eps=a["eps"], compute=compute,
                        weight_round=weight_round)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        lm = params.get("lm_head", params["embed"])
        out = head(h[jnp.asarray(rows)], params["final_norm"], lm,
                   eps=a["eps"], compute=compute, weight_round=weight_round)
        return np.asarray(out)
