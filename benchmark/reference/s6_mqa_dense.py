"""Plain reference: the AI21-Jamba2 decoder (HF `ai21labs/AI21-Jamba2-3B`,
`model_type: jamba`; its recurrent layers are Mamba-1's selective scan,
arXiv:2312.00752, with the `jamba` mixer's three inner norms) in
straightforward `jax.numpy`, float32, matmul precision "highest". No cache, no
recurrent-state array, no kernel, no batching, and none of the program's
forward code. Pre-norm residual blocks, x [T, D], eps = `rms_norm_eps`:

    x = E[ids]
    every layer:  x = x + Mixer(rms_norm(x));  x = x + SwiGLU(rms_norm(x))
    logits = rms_norm(x) E^T                          (tied head)

Layer l is an attention layer iff l mod `attn_layer_period` ==
`attn_layer_offset` (`cfg.layer_kinds` "gqa": 7 and 21 of 28); `num_experts`
1, so every layer's feed-forward is the dense SwiGLU of width F, no biases.

Attention layer: H query heads over ONE key/value head of width hd, NO
rotation and no position term of any kind:

    q = a Wq [T, H, hd];  k = a Wk, v = a Wv [T, 1, hd]
    o_h = causal softmax(q_h k^T / sqrt(hd)) v;   Mixer = concat(o) Wo

Mamba layer ("s6"), inner width E, N states, step rank R:

    [x | z] = a W_in                                  widths E | E, no bias
    x_t = silu(b_c + sum_{i<c} w_c[i] * x_{t-c+1+i})   depthwise, causal, zeros
                                                      before the start
    [r_t | B_t | C_t] = x_t W_x                       widths R | N | N
    r, B, C <- rms_norm(r, w_dt), rms_norm(B, w_B), rms_norm(C, w_C)
                                                      the `jamba` mixer's; plain
                                                      Mamba-1 has none of them
    d_t = softplus(r_t W_dt + b_dt)   [E];   A = -exp(A_log)   [E, N]
    h_t[c, n] = exp(d_t[c] A[c, n]) h_{t-1}[c, n] + d_t[c] x_t[c] B_t[n]
                                                      TOKEN BY TOKEN, h_{-1} = 0
    y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]
    Mixer = (y * silu(z)) W_out                       no bias, no norm

Assumed, because the published config.json does not say (each also in the
configuration file's `assumed`): `head_dim` = D / H; no positional encoding;
the three inner norms and `b_dt`; the conv's tap c-1 on the current token.

Departures from the published description: none.

`inner_norms=False` takes the three inner norms OUT (plain Mamba-1's mixer):
the second control of the configuration's check, which must FAIL, so that a
mixer which forgets what makes this the `jamba` mixer cannot pass.

`kv_round="fp8"` rounds what the caches hold one step below what the
configuration states: the K/V rows and the conv's held inputs (the c-1 BEFORE
the current token) to an 8-bit float (4 exponent bits, 3 mantissa bits) AND
the recurrent state to bfloat16 after every token, all by
`lax.reduce_precision` (kda_gqa_moe.py says why not by a cast pair).

It reads the served model's parameter arrays as DATA (stacks over layers,
`[in, out]` matrices, int8 as {"q", "s"}; `A_logT` is log(-A) held
transposed, [N, E], which is also the layout the state has here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _weight
from benchmark.reference.kda_mla_moe import _lin, dense_mlp
from benchmark.reference.moe_qknorm import _at

_S6 = ("w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm",
       "w_dt", "dt_bias", "A_logT", "ssm_D", "wo")
_GQA = ("wq", "wk", "wv", "wo")
_MLP = ("mlp_norm", "w_gate", "w_up", "w_down")


def _held(x, kv_round: str):
    """What a cache row hands back of `x` under the control `kv_round`."""
    if kv_round not in ("", "fp8"):
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    if kv_round:
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return x


@functools.partial(jax.jit, static_argnames=(
    "eps", "compute", "weight_round", "kv_round", "inner_norms"))
def s6_layer(h, lw, *, eps, compute="float32", weight_round="", kv_round="",
             inner_norms=True):
    """x + Mamba1(x) of one layer over the whole sequence. h: [T, D]."""
    dt_ = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt_)
    pre, z = jnp.split(_lin(a, lw["w_in"], dt_, weight_round).astype(F32), 2, -1)
    held = _held(pre, kv_round)
    w = lw["conv_w"].astype(F32)  # [c, E], tap c-1 on the current token
    c = w.shape[0]
    past = jnp.concatenate([jnp.zeros((c - 1, pre.shape[1]), F32), held], 0)
    x = pre * w[c - 1] + sum(past[i:i + T] * w[i] for i in range(c - 1))
    x = jax.nn.silu(x + lw["conv_b"].astype(F32))
    N, R = lw["b_norm"].shape[0], lw["dt_norm"].shape[0]
    rbc = _lin(x.astype(dt_), lw["w_x"], dt_, weight_round).astype(F32)
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    if inner_norms:
        r = _rms_norm(r, lw["dt_norm"], eps)
        Bm = _rms_norm(Bm, lw["b_norm"], eps)
        Cm = _rms_norm(Cm, lw["c_norm"], eps)
    step = jax.nn.softplus(
        _lin(r.astype(dt_), lw["w_dt"], dt_, weight_round).astype(F32)
        + lw["dt_bias"].astype(F32))  # [T, E]
    At = -jnp.exp(lw["A_logT"].astype(F32))  # [N, E]: A transposed

    def token(S, xs):  # S [N, E]
        x_t, b_t, c_t, d_t = xs
        S = jnp.exp(d_t[None, :] * At) * S + (d_t * x_t)[None, :] * b_t[:, None]
        if kv_round:  # a state held in bfloat16
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(S * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(At.shape, F32), (x, Bm, Cm, step))
    y = (y + lw["ssm_D"].astype(F32) * x) * jax.nn.silu(z)
    out = _mm(y.astype(dt_), _weight(lw["wo"], weight_round), dt_)
    return (h.astype(F32) + out).astype(dt_)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "compute", "weight_round", "kv_round"))
def mqa_attention(h, lw, *, heads, eps, compute="float32", weight_round="",
                  kv_round=""):
    """x + NoPE multi-query attention(x) of one layer: full causal attention
    of `heads` query heads over the ONE key/value head, scores / sqrt(hd)."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round).reshape(T, heads, -1)
    k = _held(_lin(a, lw["wk"], dt, weight_round), kv_round)  # [T, hd]
    v = _held(_lin(a, lw["wv"], dt, weight_round), kv_round)
    s = jnp.einsum("qhd,kd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(q.shape[-1]))
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,kd->qhd", p, v, preferred_element_type=F32)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


@functools.partial(jax.jit, static_argnames=("eps", "compute", "blocks"))
def head(h_rows, final_norm, embed, *, eps, compute="float32",
         blocks: int = 1):
    """log-softmax of rms_norm(x) E^T, the [V, D] matrix a block of rows at
    a time (a whole float32 head is 0.67 GB at 65,536 x 2560)."""
    dt = jnp.dtype(compute)
    x = _rms_norm(h_rows, final_norm, eps).astype(dt)
    V = embed.shape[0]
    n = V // blocks

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(embed, i * n, n, 0)
        return jnp.dot(x, w.astype(dt).T, preferred_element_type=F32)

    logits = jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1)
    return jax.nn.log_softmax(logits.reshape(x.shape[0], V).astype(F32), -1)


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    return {"kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
            "heads": int(get("num_heads"))}


def forward(params, cfg, ids, rows, *, compute="float32", weight_round="",
            kv_round="", inner_norms=True, pad_to: int = 128,
            hidden_after=None) -> np.ndarray:
    """Teacher-forced full forward over `ids`; log-probabilities
    [len(rows), V] at the positions in `rows`. Right-padded to a multiple of
    `pad_to` (causal attention and a forward recurrence: padding cannot
    reach an earlier position). One layer is dequantised at a time and the
    head runs in blocks of rows, so the float32 copies fit beside the served
    model. `hidden_after` as in `dense_gqa.forward`."""
    a = arch_of(cfg)
    T = -(-len(ids) // pad_to) * pad_to
    toks = np.zeros((T,), np.int32)
    toks[: len(ids)] = ids
    dt = jnp.dtype(compute)
    kw = dict(eps=a["eps"], compute=compute, weight_round=weight_round)
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        ns = ng = 0
        for li, kind in enumerate(a["kinds"]):
            norm = {"attn_norm": _at(lay["attn_norm"], li)}
            if kind == "s6":
                lw = {k: _at(params["s6_layers"][k], ns) for k in _S6}
                h = s6_layer(h, {**lw, **norm}, kv_round=kv_round,
                             inner_norms=inner_norms, **kw)
                ns += 1
            else:
                lw = {k: _at(params["gqa_layers"][k], ng) for k in _GQA}
                h = mqa_attention(h, {**lw, **norm}, heads=a["heads"],
                                  kv_round=kv_round, **kw)
                ng += 1
            h = dense_mlp(h, {k: _at(lay[k], li) for k in _MLP}, **kw)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        V = params["embed"].shape[0]
        blocks = next(b for b in (16, 8, 4, 2, 1) if V % b == 0 and V // b >= 64)
        out = head(h[jnp.asarray(rows)], params["final_norm"], params["embed"],
                   eps=a["eps"], compute=compute, blocks=blocks)
        return np.asarray(out)
