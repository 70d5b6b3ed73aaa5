"""Plain reference: the Granite-4.0-H decoder (HF
`ibm-granite/granite-4.0-h-small`, `model_type: granitemoehybrid`; its
recurrent layers are Mamba-2's state-space duality layer, arXiv:2405.21060) in
straightforward `jax.numpy`, float32, matmul precision "highest". No cache, no
recurrent-state array, no kernel, no chunking, no sort, and none of the
program's forward code. Pre-norm residual blocks, x [T, D], with the family's
four scalars (`embedding_multiplier` e, `residual_multiplier` r,
`attention_multiplier` m, `logits_scaling` s):

    x = E[ids] e
    every layer:  x = x + r Mixer(rms_norm(x));  x = x + r (MoE(a) + Shared(a)),
                  a = rms_norm(x)
    logits = rms_norm(x) E^T / s                     (tied head)

Attention layer (`cfg.layer_kinds` "gqa": 5, 15, 25, 35), H query heads over
K key/value heads of width hd, NO rotation (`position_embedding_type` nope):

    q = a Wq [T, H, hd];  k = a Wk, v = a Wv [T, K, hd]
    o_h = causal softmax(q_h k_{h // (H/K)}^T m) v_{h // (H/K)};   Mixer = o Wo

Mamba-2 layer ("ssd"), Hm heads of width P over a state of width N, G groups
(d_inner = Hm P):

    [z | xBC | dt] = a W_in                 widths d_inner | d_inner + 2 G N | Hm
                                            (the program holds the three column
                                            blocks as three leaves, read here)
    xBC_t = silu(sum_{i<c} w_conv[i] * xBC_{t-c+1+i} + b_conv)   depthwise, causal,
                                            zeros before the start
    [x_t | B_t | C_t] = split d_inner | G N | G N;  x_t as [Hm, P]
    D_t = softplus(dt_t + dt_bias) [Hm];   A = -exp(A_log) [Hm]
    S_t[h] = exp(D_t[h] A[h]) S_t-1[h] + D_t[h] x_t[h] (x) B_t      TOKEN BY TOKEN
    y_t[h] = S_t[h] C_t + D[h] x_t[h]
    Mixer = rms_norm(y * silu(z); w_norm) W_out     gate BEFORE the norm, the
                                            norm over all of d_inner

MoE, every layer: l = a W_r over ALL E experts, the k largest logits picked,
w = softmax over those k (float32), and

    MoE(a) = sum over picked e HELD HERE of w_e E_e(a),   E_e = SwiGLU of width F
    Shared(a) = SwiGLU of width Fs

"Held here" is the deployment's expert share (`cfg.expert_share`): what the
other experts would add is left out, as in the program.

Assumed, because the published config.json does not say (each also in the
configuration file's `assumed`): `head_dim` 128; the split orders z | xBC | dt
and x | B | C; the conv's tap c-1 on the current token; the gated norm's eps =
`rms_norm_eps`; no clamp on the step (`time_step_limit` (0, inf)); the
router's logits in float32.

Departures from the published description: none in the layers; of the model,
only what `cfg` says is run (the held experts).

`kv_round="fp8"` rounds what the caches hold one step below what the
configuration states: the K/V rows and the conv's held inputs (the c-1 BEFORE
the current token; the current one never rests in a row) to an 8-bit float (4
exponent bits, 3 mantissa bits) AND the recurrent state to bfloat16 after
every token, all by `lax.reduce_precision` (kda_gqa_moe.py says why not by a
cast pair). `kv_round="state-bf16"` rounds the recurrent state alone, to
bfloat16 after every token, and leaves the conv's inputs and the K/V rows as
the configuration states them: the precision a deployment may hold the state
in (the configuration's `assumed.precision`), read as a control of its own.

It reads the served model's parameter arrays as DATA (stacks over layers,
`[in, out]` matrices, int8 as {"q", "s"}).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_gqa import F32, _mm, _rms_norm, _weight
from benchmark.reference.kda_mla_moe import _lin, _swiglu
from benchmark.reference.moe_qknorm import _at

_SSD = ("w_z", "w_xbc", "w_dt", "conv_w", "conv_b", "dt_bias", "A_log", "ssm_D",
        "o_norm", "wo")
_GQA = ("wq", "wk", "wv", "wo")
_MOE = ("mlp_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down")


def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _held(x, kv_round: str):
    """What a cache row hands back of `x` under the control `kv_round`."""
    if kv_round not in ("", "fp8", "state-bf16"):
        raise ValueError(f"unknown kv rounding {kv_round!r}")
    return _fp8(x) if kv_round == "fp8" else x


@functools.partial(jax.jit, static_argnames=(
    "heads", "state", "groups", "eps", "res", "compute", "weight_round",
    "kv_round"))
def ssd_layer(h, lw, *, heads, state, groups, eps, res, compute="float32",
              weight_round="", kv_round=""):
    """x + res * Mamba2(x) of one layer over the whole sequence."""
    dt_ = jnp.dtype(compute)
    T = h.shape[0]
    H, N, G = heads, state, groups
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt_)
    # W_in is held as its three column blocks z | xBC | dt
    z, pre, dt = (_lin(a, lw[n], dt_, weight_round).astype(F32)
                  for n in ("w_z", "w_xbc", "w_dt"))
    di = z.shape[-1]
    held = _held(pre, kv_round)
    w = lw["conv_w"].astype(F32)  # [c, conv_dim], tap c-1 on the current token
    c = w.shape[0]
    past = jnp.concatenate([jnp.zeros((c - 1, pre.shape[1]), F32), held], 0)
    u = pre * w[c - 1] + sum(past[i:i + T] * w[i] for i in range(c - 1))
    u = jax.nn.silu(u + lw["conv_b"].astype(F32))
    x = u[:, :di].reshape(T, H, -1)
    Bm = u[:, di:di + G * N].reshape(T, G, N)
    Cm = u[:, di + G * N:].reshape(T, G, N)
    step = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))  # [T, H]
    A = -jnp.exp(lw["A_log"].astype(F32))  # [H]
    per = H // G

    def token(S, xs):  # S [H, P, N]
        x_t, b_t, c_t, d_t = xs
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        S = (jnp.exp(d_t * A)[:, None, None] * S
             + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        if kv_round:  # a state held in bfloat16
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.sum(S * c_h[:, None, :], axis=-1)

    S0 = jnp.zeros((H, x.shape[-1], N), F32)
    _, y = jax.lax.scan(token, S0, (x, Bm, Cm, step))
    y = y + lw["ssm_D"].astype(F32)[:, None] * x
    y = y.reshape(T, di) * jax.nn.silu(z)  # the gate, THEN the norm
    y = _rms_norm(y, lw["o_norm"], eps).astype(dt_)
    out = _mm(y, _weight(lw["wo"], weight_round), dt_)
    return (h.astype(F32) + res * out).astype(dt_)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "mult", "eps", "res", "compute", "weight_round",
    "kv_round"))
def gqa_attention(h, lw, *, heads, kv_heads, mult, eps, res,
                  compute="float32", weight_round="", kv_round=""):
    """x + res * NoPE GQA(x) of one layer: full causal attention, scores
    scaled by `mult`, each KV head repeated for its query heads."""
    dt = jnp.dtype(compute)
    T = h.shape[0]
    a = _rms_norm(h, lw["attn_norm"], eps).astype(dt)
    q = _lin(a, lw["wq"], dt, weight_round).reshape(T, heads, -1)
    k = _lin(a, lw["wk"], dt, weight_round).reshape(T, kv_heads, -1)
    v = _lin(a, lw["wv"], dt, weight_round).reshape(T, kv_heads, -1)
    k, v = _held(k, kv_round), _held(v, kv_round)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=F32) * mult
    pos = jnp.arange(T)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("hqk,khd->qhd", p, v, preferred_element_type=F32)
    y = _mm(o.reshape(T, -1).astype(dt), _weight(lw["wo"], weight_round), dt)
    return (h.astype(F32) + res * y).astype(dt)


def route(m, router, *, top_k):
    """(weights [T, k] float32, expert ids [T, k]): the k largest of the
    logits over ALL experts, softmax over those k."""
    logits = _mm(m.astype(F32), router.astype(F32), F32)
    top, e = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), e


@functools.partial(jax.jit, static_argnames=(
    "top_k", "lo", "eps", "res", "compute", "weight_round"))
def experts(h, lw, *, top_k, lo, eps, res, compute="float32",
            weight_round=""):
    """x + res * ([held experts' part of the routed sum] + shared MLP). lw's
    expert leaves are [E_held, ...]: experts lo .. lo + E_held - 1."""
    dt = jnp.dtype(compute)
    m = _rms_norm(h, lw["mlp_norm"], eps).astype(dt)
    w, e = route(m, lw["router"], top_k=top_k)
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
    return (h.astype(F32) + res * out).astype(dt)


@functools.partial(jax.jit, static_argnames=(
    "eps", "scaling", "compute", "blocks"))
def head(h_rows, final_norm, embed, *, eps, scaling, compute="float32",
         blocks: int = 1):
    """log-softmax of rms_norm(x) E^T / scaling, the [V, D] matrix a block
    of rows at a time (a whole float32 head is 1.6 GB at 100,352 x 4096)."""
    dt = jnp.dtype(compute)
    x = _rms_norm(h_rows, final_norm, eps).astype(dt)
    V = embed.shape[0]
    n = V // blocks

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(embed, i * n, n, 0)
        return jnp.dot(x, w.astype(dt).T, preferred_element_type=F32)

    logits = jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1)
    logits = logits.reshape(x.shape[0], V).astype(F32) / scaling
    return jax.nn.log_softmax(logits, -1)


def arch_of(cfg) -> dict:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    share = get("expert_share")
    E = int(get("num_experts"))
    return {
        "kinds": tuple(get("layer_kinds")), "eps": float(get("rms_eps")),
        "ssd_heads": int(get("mamba_heads")), "state": int(get("mamba_d_state")),
        "groups": int(get("mamba_groups")), "heads": int(get("num_heads")),
        "kv_heads": int(get("num_kv_heads")),
        # `query_scale` q is this repo's spelling of attention_multiplier q^-1/2
        "mult": float(get("query_scale")) ** -0.5,
        "embed": float(get("embedding_multiplier")),
        "res": float(get("residual_multiplier")),
        "scaling": float(get("logits_scaling")),
        "top_k": int(get("num_experts_per_token")),
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
    kw = dict(eps=a["eps"], res=a["res"], compute=compute,
              weight_round=weight_round)
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = (params["embed"][jnp.asarray(toks)].astype(F32)
             * a["embed"]).astype(dt)
        ns = ng = 0
        for li, kind in enumerate(a["kinds"]):
            norm = {"attn_norm": _at(lay["attn_norm"], li)}
            if kind == "ssd":
                lw = {k: _at(params["ssd_layers"][k], ns) for k in _SSD}
                h = ssd_layer(h, {**lw, **norm}, heads=a["ssd_heads"],
                              state=a["state"], groups=a["groups"],
                              kv_round=kv_round, **kw)
                ns += 1
            else:
                lw = {k: _at(params["gqa_layers"][k], ng) for k in _GQA}
                h = gqa_attention(h, {**lw, **norm}, heads=a["heads"],
                                  kv_heads=a["kv_heads"], mult=a["mult"],
                                  kv_round=kv_round, **kw)
                ng += 1
            h = experts(h, {k: _at(lay[k], li) for k in _MOE},
                        top_k=a["top_k"], lo=a["lo"], **kw)
            if hidden_after is not None:
                hidden_after.append(np.asarray(h[jnp.asarray(rows)].astype(F32)))
        V = params["embed"].shape[0]
        blocks = next(b for b in (16, 8, 4, 2, 1) if V % b == 0 and V // b >= 64)
        out = head(h[jnp.asarray(rows)], params["final_norm"], params["embed"],
                   eps=a["eps"], scaling=a["scaling"], compute=compute,
                   blocks=blocks)
        return np.asarray(out)
