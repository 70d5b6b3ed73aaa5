"""Plain reference: the Kimi-Linear decoder (arXiv:2510.26692; HF
`moonshotai/Kimi-Linear-48B-A3B-Instruct`, fla `KimiDeltaAttention`) in
straightforward `jax.numpy`, float32, matmul precision "highest". No cache, no
recurrent-state array, no kernel, no chunking, no absorbed weights, no sort,
and none of the program's forward code. Pre-norm residual blocks, x [T, D]:

KDA layer (the layers `cfg.layer_kinds` calls "kda"), H heads of dk = dv:

    q~, k~, v~ = a Wq, a Wk, a Wv                     a = rms_norm(x)
    q, k, v = silu(causal depthwise conv_c over time of q~, k~, v~)
    q_h = l2norm(q_h) dk^-1/2      k_h = l2norm(k_h)
    g_t,h = -exp(A_h) softplus(a Wf_down Wf_up + dt_bias)_h  in R^dk
    beta_t,h = sigmoid(a Wbeta)_h
    S_t,h = (I - beta k k^T) Diag(exp g) S_t-1,h + beta k v^T     TOKEN BY TOKEN
    o_t,h = S_t,h^T q_t,h
    x = x + [rms_norm_head(o_t,h) * sigmoid(a Wg_down Wg_up)_h] Wo

MLA layer ("mla"), H heads, no q-lora, no rotation anywhere (NoPE):

    q_h = (a Wq)_h in R^{n+rot};  [c | k_pe] = a Wkv_a;  c = rms_norm(c)
    k_h = [W_kb,h c | k_pe]   v_h = W_vb,h c      (explicit, per head)
    x = x + [causal softmax(q_h k_h / sqrt(n+rot)) v_h] Wo

MLP: the first `first_k_dense` layers a dense SwiGLU; the others
s = sigmoid(m Wr) over ALL E experts, the top k of s + bias picked,
w = s[picked] / sum(s[picked]) * routed_scaling_factor, and

    x = x + sum over picked e HELD HERE of w_e E_e(m)  +  E_shared(m)

"Held here" is the deployment's expert share (`cfg.expert_share` = (index,
of), None = all): the expert stacks it is given hold experts
[index E/of, (index+1) E/of) and nothing stands in for the others, as in the
program. The experts run as a plain loop over the held ones, one dequantised
at a time.

`kv_round="fp8"` rounds what the caches hold one step below what the
configuration states: the latent rows [c | k_pe] to float8_e4m3fn AND the
recurrent state to bfloat16 after every token (`lax.reduce_precision`: a
float32 -> bfloat16 -> float32 cast pair is dropped by the TPU compiler).

It reads the served model's parameter arrays as DATA (stacks over layers,
`[in, out]` matrices, int8 as {"q", "s"}); the helpers shared with the dense
reference (norm, matmul in a compute type, weight rounding) are that file's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _weight
from benchmark.reference.moe_qknorm import _at


def _lin(x, w, dt, weight_round=""):
    return _mm(x, _weight(w, weight_round), dt).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "compute", "weight_round", "kv_round"))
def kda_attention(h, lw, *, heads, eps, compute="float32", weight_round="",
                  kv_round=""):
    """x + KDA(x) of one layer over the whole sequence. h: [T, D]."""
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
    beta = jax.nn.sigmoid(_lin(a, lw["w_beta"], dt).astype(F32))  # [T, H]
    gate = jax.nn.sigmoid(
        _lin(_lin(a, lw["g_down"], dt), lw["g_up"], dt).astype(F32))
    if kv_round and kv_round != "fp8":
        raise ValueError(f"unknown kv rounding {kv_round!r}")

    def token(S, xs):  # S [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]
        kS = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        if kv_round:  # a state held in bfloat16. Not `.astype` there and
            # back: XLA:TPU is free to drop that pair (excess precision) and
            # does inside this scan, so the control rounded nothing there.
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    S0 = jnp.zeros((heads, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(token, S0, (q, k, v, g, beta))
    o = _rms_norm(o, lw["o_norm"], eps) * gate.reshape(T, heads, dk)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rot", "eps", "compute", "weight_round",
    "kv_round"))
def mla_attention(h, lw, *, heads, rank, nope, rot, eps, compute="float32",
                  weight_round="", kv_round=""):
    """x + NoPE-MLA(x) of one layer, with explicit per-head keys and values."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round).reshape(T, heads, nope + rot)
    ckv = _lin(a, lw["wkv_a"], dt, weight_round)
    c = _rms_norm(ckv[:, :rank], lw["kv_norm"], eps).astype(dt)
    k_pe = ckv[:, rank:rank + rot]
    if kv_round == "fp8":
        c = c.astype(jnp.float8_e4m3fn).astype(dt)
        k_pe = k_pe.astype(jnp.float8_e4m3fn).astype(dt)
    elif kv_round:
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    k_nope = jnp.einsum("tr,hnr->thn", c.astype(dt), lw["w_kb"].astype(dt),
                        preferred_element_type=F32).astype(dt)
    v = jnp.einsum("tr,hvr->thv", c.astype(dt), lw["w_vb"].astype(dt),
                   preferred_element_type=F32).astype(dt)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, heads, rot))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32)
    s = s / jnp.sqrt(F32(nope + rot))
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + y).astype(dt)


def _swiglu(m, w_gate, w_up, w_down, dt, weight_round):
    gate = jax.nn.silu(_mm(m, _weight(w_gate, weight_round), dt)).astype(dt)
    up = _mm(m, _weight(w_up, weight_round), dt).astype(dt)
    act = (gate.astype(F32) * up.astype(F32)).astype(dt)
    return _mm(act, _weight(w_down, weight_round), dt)


@functools.partial(jax.jit, static_argnames=("eps", "compute", "weight_round"))
def dense_mlp(h, lw, *, eps, compute="float32", weight_round=""):
    dt = jnp.dtype(compute)
    m = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    y = _swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], dt, weight_round)
    return (h.astype(F32) + y).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "lo", "eps", "compute", "weight_round"))
def experts(h, lw, *, top_k, scaling, lo, eps, compute="float32",
            weight_round=""):
    """x + [held experts' part of the routed sum] + shared expert. lw's
    expert leaves are [E_held, ...]: experts lo .. lo + E_held - 1."""
    dt = jnp.dtype(compute)
    m = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    s = jax.nn.sigmoid(_mm(m.astype(F32), lw["router"].astype(F32), F32))
    _, e = jax.lax.top_k(s + lw["router_bias"].astype(F32), top_k)
    w = jnp.take_along_axis(s, e, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scaling
    held = (lw["w_gate"]["q"] if isinstance(lw["w_gate"], dict)
            else lw["w_gate"]).shape[0]

    def one(i, acc):
        mine = jnp.sum(w * (e == lo + i), axis=-1)  # [T]: 0 where not picked
        y = _swiglu(m, _at(lw["w_gate"], i), _at(lw["w_up"], i),
                    _at(lw["w_down"], i), dt, weight_round)
        return acc + mine[:, None] * y

    out = jax.lax.fori_loop(0, held, one, jnp.zeros(h.shape, F32))
    out = out + _swiglu(m, lw["shared_gate"], lw["shared_up"],
                        lw["shared_down"], dt, weight_round)
    return (h.astype(F32) + out).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "eps", "compute", "weight_round", "blocks"))
def head(h_rows, final_norm, lm_head, *, eps, compute="float32",
         weight_round="", blocks: int = 1):
    """log-softmax over the vocabulary, the [V, D] head dequantised a block
    of rows at a time (a whole float32 head is 1.5 GB at 163,840 x 2304)."""
    dt = jnp.dtype(compute)
    x = _rms_norm(h_rows, final_norm, eps).astype(dt)
    V = (lm_head["q"] if isinstance(lm_head, dict) else lm_head).shape[0]
    n = V // blocks

    def block(i):
        w = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i * n, n, 0), lm_head)
        w = _weight(w, weight_round, axis=-1)
        return jnp.dot(x, w.astype(dt).T, preferred_element_type=F32)

    logits = jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1)
    return jax.nn.log_softmax(logits.reshape(x.shape[0], V).astype(F32), -1)


_KDA = ("wq", "wk", "wv", "wo", "conv_w", "f_down", "f_up", "dt_bias", "A_log",
        "w_beta", "g_down", "g_up", "o_norm")
_MLA = ("wq", "wkv_a", "kv_norm", "w_kb", "w_vb", "wo")
_DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")
_MOE = ("mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down")


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    share = get("expert_share")
    E = int(get("num_experts"))
    return {
        "kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
        "kda_heads": int(get("kda_heads")), "heads": int(get("num_heads")),
        "rank": int(get("kv_lora_rank")), "nope": int(get("qk_nope_head_dim")),
        "rot": int(get("qk_rope_head_dim")),
        "dense": int(get("first_k_dense")),
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
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(dt)
        nk = nm = 0
        for li, kind in enumerate(a["kinds"]):
            stack = "dense_layers" if li < a["dense"] else "layers"
            at = li if li < a["dense"] else li - a["dense"]
            norm = {"attn_norm": _at(params[stack]["attn_norm"], at)}
            if kind == "kda":
                lw = {k: _at(params["kda_layers"][k], nk) for k in _KDA}
                h = kda_attention(h, {**lw, **norm}, heads=a["kda_heads"],
                                  kv_round=kv_round, **kw)
                nk += 1
            else:
                lw = {k: _at(params["mla_layers"][k], nm) for k in _MLA}
                h = mla_attention(h, {**lw, **norm}, heads=a["heads"],
                                  rank=a["rank"], nope=a["nope"], rot=a["rot"],
                                  kv_round=kv_round, **kw)
                nm += 1
            if li < a["dense"]:
                h = dense_mlp(h, {k: _at(params[stack][k], at) for k in _DENSE},
                              **kw)
            else:
                h = experts(h, {k: _at(params[stack][k], at) for k in _MOE},
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
