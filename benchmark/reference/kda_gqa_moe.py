"""Plain reference: the Solar-Open2 decoder (HF `upstage/Solar-Open2-250B`,
`model_type: solar_open2`; its linear layers are KDA, arXiv:2510.26692) in
straightforward `jax.numpy`, float32, matmul precision "highest". No cache, no
recurrent-state array, no kernel, no chunking, no sort, and none of the
program's forward code. Pre-norm residual blocks, x [T, D]; every layer is
`x += mix(norm(x)); x += moe(norm(x))`:

Softmax layer (the layers `cfg.layer_kinds` calls "gqa": 0, 4, 8, ...), H
query heads over K key/value heads of width hd, NO rotation (`use_rope`
false: the causal mask is all the order there is) and an output gate:

    q = a Wq [T, H, hd];  k = a Wk, v = a Wv [T, K, hd]      a = rms_norm(x)
    o_h = causal softmax(q_h k_{h // (H/K)} / sqrt(hd)) v_{h // (H/K)}
    x = x + [o * sigmoid(a Wg)] Wo                  Wg [D, H hd], elementwise

KDA layer ("kda"), Hk heads of dk = dv, each with its own key head:

    q~, k~, v~ = a Wq, a Wk, a Wv
    q, k, v = silu(causal depthwise conv_c over time of q~, k~, v~)
    q_h = l2norm(q_h) dk^-1/2      k_h = l2norm(k_h)
    g_t,h = -exp(A_h) softplus(a Wf_down Wf_up + dt_bias)_h  in R^dk
    beta_t,h = 2 sigmoid(a Wbeta)_h  in (0, 2)     (`kda_allow_neg_eigval`)
    S_t,h = (I - beta k k^T) Diag(exp g) S_t-1,h + beta k v^T     TOKEN BY TOKEN
    o_t,h = S_t,h^T q_t,h
    x = x + [rms_norm_head(o_t,h) * sigmoid(a Wg_down Wg_up)_h] Wo

MoE, every layer (`first_k_dense_replace` 0): s = sigmoid(m Wr) over ALL E
experts, the top k of s + bias picked, w = s[picked] / sum(s[picked]) * 1, and

    x = x + sum over picked e HELD HERE of w_e E_e(m)  +  E_shared(m)

which is `kda_mla_moe.experts`, as is the vocabulary head; both are that
file's. "Held here" is the deployment's expert share (`cfg.expert_share`):
what the other experts would add is left out, as in the program.

Assumed, because the published config.json does not say (each also in the
configuration file's `assumed`):
- the GQA gate multiplies element by element over heads x head width and is
  read from the layer's normed input, as the published gated-attention models
  build it; no q/k norm; no biases;
- `kda_use_full_proj: false` means the decay and output gates are low-rank
  pairs of the head width, as Kimi-Linear's;
- `A_log`, `dt_bias`, the short conv (depthwise, causal, no bias) and the
  per-head output norm as Kimi-Linear's;
- the router scores with a sigmoid and selects with a correction bias in one
  group (the config's DeepSeek-V3-style key names);
- `intermediate_size` 10240 has no layer to live in.

Departures from the published description: none in the layers; of the model,
only what `cfg` says is run (its first `num_layers` layers, `vocab_size` rows
of the embedding and the head, the held experts).

`kv_round="fp8"` rounds what the caches hold one step below what the
configuration states: the K/V rows to an 8-bit float (4 exponent bits, 3
mantissa bits, as float8_e4m3fn has; values under 2^-6 go to zero where
float8_e4m3fn keeps denormals, one row value in a hundred here) AND the
recurrent state to bfloat16 after every token. Both by `lax.reduce_precision`
and not by a cast pair: the TPU compiler is free to drop a cast to a narrower
type and back (excess precision). It does inside the KDA scan (PERF.md
section 6, PR 31), and with the K/V rows cast to float8_e4m3fn and back this
control read on the chip like the float32 reference (0.62-0.81 against an
honest 0.53-0.68, where the CPU reads 2.4: my chip run 2, PR 34).

It reads the served model's parameter arrays as DATA (stacks over layers,
`[in, out]` matrices, int8 as {"q", "s"}).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _weight
from benchmark.reference.kda_mla_moe import _KDA, _MOE, _lin, experts, head
from benchmark.reference.moe_qknorm import _at

_GQA = ("wq", "wk", "wv", "wo", "wg")


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "compute", "weight_round", "kv_round"))
def kda_attention(h, lw, *, heads, eps, compute="float32", weight_round="",
                  kv_round=""):
    """x + KDA(x) of one layer over the whole sequence, beta in (0, 2)."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    pre = jnp.concatenate(
        [_lin(a, lw[n], dt, weight_round) for n in ("wq", "wk", "wv")], -1)
    cw = lw["conv_w"].astype(F32)  # [c, 3 H dk], tap c-1 on the current token
    c = cw.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((c - 1, pre.shape[1]), F32), pre.astype(F32)], 0)
    y = sum(padded[i:i + T] * cw[i] for i in range(c))
    y = jax.nn.silu(y).reshape(T, 3, heads, -1)
    q, k, v = y[:, 0], y[:, 1], y[:, 2]
    dk = q.shape[-1]
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(F32(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = _lin(_lin(a, lw["f_down"], dt), lw["f_up"], dt).astype(F32)
    g = -jnp.exp(lw["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        f + lw["dt_bias"].astype(F32)).reshape(T, heads, dk)
    beta = 2.0 * jax.nn.sigmoid(_lin(a, lw["w_beta"], dt).astype(F32))  # [T, H]
    gate = jax.nn.sigmoid(
        _lin(_lin(a, lw["g_down"], dt), lw["g_up"], dt).astype(F32))
    if kv_round and kv_round != "fp8":
        raise ValueError(f"unknown kv rounding {kv_round!r}")

    def token(S, xs):  # S [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]
        kS = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        if kv_round:  # a state held in bfloat16 (see the module text)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    S0 = jnp.zeros((heads, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(token, S0, (q, k, v, g, beta))
    o = _rms_norm(o, lw["o_norm"], eps) * gate.reshape(T, heads, dk)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "compute", "weight_round", "kv_round"))
def gqa_attention(h, lw, *, heads, kv_heads, eps, compute="float32",
                  weight_round="", kv_round=""):
    """x + gated NoPE GQA(x) of one layer: full causal attention, each KV
    head repeated for its heads/kv_heads query heads, no rotation."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round).reshape(T, heads, -1)
    k = _lin(a, lw["wk"], dt, weight_round).reshape(T, kv_heads, -1)
    v = _lin(a, lw["wv"], dt, weight_round).reshape(T, kv_heads, -1)
    if kv_round == "fp8":  # rows held with 4 exponent and 3 mantissa bits
        k = jax.lax.reduce_precision(k, exponent_bits=4, mantissa_bits=3)
        v = jax.lax.reduce_precision(v, exponent_bits=4, mantissa_bits=3)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(q.shape[-1]))
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    gate = jax.nn.sigmoid(_lin(a, lw["wg"], dt, weight_round).astype(F32))
    o = (o.reshape(T, -1) * gate).astype(dt)
    y = _mm(o, _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    share = get("expert_share")
    E = int(get("num_experts"))
    return {
        "kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
        "kda_heads": int(get("kda_heads")), "heads": int(get("num_heads")),
        "kv_heads": int(get("num_kv_heads")),
        "top_k": int(get("num_experts_per_token")),
        "scaling": float(get("routed_scaling_factor")),
        "lo": 0 if share is None else int(share[0]) * (E // int(share[1])),
    }


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", pad_to: int = 128, hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids`; log-probabilities
    [len(rows), V] at the positions in `rows`. Right-padded to a multiple of
    `pad_to` (causal attention and a forward recurrence: padding cannot
    reach an earlier position). `hidden_after` as in `dense_gqa.forward`."""
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    kw = dict(eps=a["eps"], compute=compute, weight_round=weight_round)
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        nk = ng = 0
        for li, kind in enumerate(a["kinds"]):
            norm = {"attn_norm": _at(lay["attn_norm"], li)}
            if kind == "kda":
                lw = {k: _at(params["kda_layers"][k], nk) for k in _KDA}
                h = kda_attention(h, {**lw, **norm}, heads=a["kda_heads"],
                                  kv_round=kv_round, **kw)
                nk += 1
            else:
                lw = {k: _at(params["gqa_layers"][k], ng) for k in _GQA}
                h = gqa_attention(h, {**lw, **norm}, heads=a["heads"],
                                  kv_heads=a["kv_heads"], kv_round=kv_round,
                                  **kw)
                ng += 1
            h = experts(h, {k: _at(lay[k], li) for k in _MOE},
                        top_k=a["top_k"], scaling=a["scaling"], lo=a["lo"],
                        **kw)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        V = (params["lm_head"]["q"] if isinstance(params["lm_head"], dict)
             else params["lm_head"]).shape[0]
        blocks = next(b for b in (16, 8, 4, 2, 1) if V % b == 0 and V // b >= 64)
        out = head(h[jnp.asarray(rows)], params["final_norm"], params["lm_head"],
                   blocks=blocks, **kw)
        return np.asarray(out)
