"""The second kind of per-slot state: a hybrid model's recurrent state.

A slot of a model with KDA layers (cfg.is_hybrid, models/llama.py) holds two
things of different shape. Its cache rows grow with the context and live in
pages the KV manager hands out. Its recurrent state is fixed in size: per KDA
layer a [H, dk, dv] float32 matrix a head and the short conv's last inputs,
42 MB a slot for Kimi-Linear-48B-A3B whatever the context. It needs no
allocator: row i of the two arrays below belongs to slot index i, always.

The arrays ride in the cache pytree (`llama.KVCache.state`, `.conv`), so every
program that carries the cache carries them, donated with it, and the
device's order of programs is the order of their owners:

- claim:    the admission program writes the whole row from the prompt
            (`llama.prefill(recurrent=...)`: the state after the last prompt
            token, computed from zero). Nothing of an earlier tenant is read.
- decode:   every block updates every row in place, live or not
            (ops/kda.kda_decode, aliased). A row without a tenant decays
            garbage into garbage; it stays bounded (the update is a
            contraction) and is never read by a tenant.
- park:     a tenant whose dispatched blocks cover its budget leaves the index
            (`Engine._park`); its blocks in flight still update the row, and
            the successor's admission, dispatched after them, overwrites it.
- release:  nothing to free.
- preempt:  the row is dropped and recomputed: the victim re-admits its
            prompt and what it generated as one prompt (`kv_preempt` is
            forced to `recompute`); no snapshot is taken.

What needs a snapshot of a row at a point inside a sequence is refused where
the engine is built, by name (`refuse`), or switched off and journalled
(prefix-span reuse): a wrong state cannot be seen in any shape or count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# Most prompt rows (requests x bucket) one admission program of a hybrid model
# takes: the chunkwise KDA prefill holds its operands in float32 for every
# request at once, some 0.5 MB a token at Kimi-Linear's widths (1.1 GB of
# temporaries at 2,048 rows beside 11.7 GB held: compiled for the v5e,
# PERF.md PR 31). A larger group of one bucket is admitted as several
# programs in turn.
ADMIT_ROWS = 2048


def refuse(cfg, ecfg, plan, draft_cfg, spec_mode: str) -> None:
    """Raise ValueError naming each mechanism this engine cannot run for a
    hybrid model as configured. Called once, before anything is allocated."""
    no = []
    if ecfg.kv_pages <= 0:
        no.append("a dense KV cache (set kv_pages > 0: the latent rows live "
                  "in the paged pool)")
    if plan.tp > 1 or plan.sp > 1 or plan.ep > 1 or plan.dp > 1:
        no.append(f"tp/sp/ep/dp > 1 (plan {plan}: the recurrent state and "
                  "the expert share are not sharded)")
    if draft_cfg is not None or spec_mode != "off":
        no.append("speculative decoding (a rejected draft would need the "
                  "state rolled back)")
    if ecfg.prefill_chunk:
        no.append("chunked admission (prefill_chunk > 0: the state is not "
                  "carried across chunks)")
    if ecfg.attention_window or ecfg.kv_spill_bytes:
        no.append("windowed+sink attention and page spill")
    if float(ecfg.kv_scale) != 1.0:
        no.append("a scaled fp8 latent pool (kv_scale != 1)")
    if no:
        raise ValueError(
            f"{cfg.name} keeps a per-slot recurrent state (KDA layers); this "
            "engine does not run it with: " + "; ".join(no))


def allocate(cfg, slots: int, conv_dtype, sharding=None):
    """(state [Lk, slots, H, dk, dv] f32, conv [Lk, slots, c-1, 3·H·dk])."""
    Lk, H, d = len(cfg.kda_layers), cfg.kda_heads, cfg.kda_head_dim
    state = jnp.zeros((Lk, slots, H, d, d), jnp.float32)
    conv = jnp.zeros((Lk, slots, cfg.kda_conv - 1, 3 * H * d), conv_dtype)
    if sharding is not None:
        state, conv = (jax.device_put(a, sharding) for a in (state, conv))
    return state, conv


def row_bytes(cfg, conv_dtype) -> int:
    """Bytes of one slot's row over all KDA layers."""
    Lk, H, d = len(cfg.kda_layers), cfg.kda_heads, cfg.kda_head_dim
    return Lk * (H * d * d * 4 + (cfg.kda_conv - 1) * 3 * H * d
                 * jnp.dtype(conv_dtype).itemsize)
