"""The second kind of per-slot state: a hybrid model's recurrent state.

A slot of a model with recurrent layers (cfg.is_hybrid, models/llama.py)
holds two things of different shape. Its cache rows, of whichever kind the
model's cache layers write (MLA's latent rows, or GQA's keys and values a KV
head), grow with the context and live in pages the KV manager hands out. Its
recurrent state is fixed in size, `row_bytes` a slot whatever the context,
and its shape is the recurrent kind's (`cfg.recurrent_kind`): per KDA layer
a [H, dk, dv] float32 matrix a head and the short conv's last inputs; per
gated short convolution ("conv", LFM2) the operator's last conv_cache-1
inputs [conv_cache-1, D] and no matrix at all (`state` is then None). It
needs no allocator: row i of the arrays below belongs to slot index i,
always.

The arrays ride in the cache pytree (`llama.KVCache.state`, `.conv`), so every
program that carries the cache carries them, donated with it, and the
device's order of programs is the order of their owners:

- claim:    the admission program writes the whole row from the prompt
            (`llama.prefill(recurrent=...)`: the state after the last prompt
            token, computed from zero). Nothing of an earlier tenant is read.
- decode:   every block updates every row in place, live or not
            (ops/kda.kda_decode, aliased; a conv row shifts by one input). A
            row without a tenant decays garbage into garbage; it stays
            bounded (KDA's update is a contraction, a conv row forgets after
            conv_cache-1 steps) and is never read by a tenant.
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

import math

import jax
import jax.numpy as jnp


# What the float32 temporaries of one admission program of a hybrid model may
# take: the chunkwise KDA prefill holds its operands for every request of the
# group at once (1.1 GB compiled for the v5e at this bound beside 11.7 GB
# held, PERF.md PR 31). A larger group of one bucket is admitted as several
# programs in turn (the `admit_split` gauge and journal event).
ADMIT_BYTES = 1 << 30


def admit_rows(cfg) -> int | None:
    """Most prompt rows (requests x bucket) one admission program takes under
    `ADMIT_BYTES`, from the model's own widths: the widest temporaries are
    the diagonal blocks' pairwise exponents and their exponentials,
    [SUB, SUB, dk] float32 a sub-block and head each (ops/kda.py), so
    2·H·SUB·dk·4 bytes a prompt token: 0.5 MB and 2,048 rows at 32 heads of
    128, 1 MB and 1,024 rows at 64. None for a model without KDA layers (a
    conv model's prefill holds a few [T, D] rows a prompt, as any layer's):
    its admission groups are not bounded here."""
    if cfg.recurrent_kind != "kda":
        return None
    from localai_tpu.ops.kda import SUB

    return max(1, ADMIT_BYTES // (2 * cfg.kda_heads * SUB * cfg.kda_head_dim * 4))


def refuse(cfg, ecfg, plan, draft_cfg, spec_mode: str) -> None:
    """Raise ValueError naming each mechanism this engine cannot run for a
    hybrid model as configured. Called once, before anything is allocated."""
    no = []
    if ecfg.kv_pages <= 0:
        no.append("a dense KV cache (set kv_pages > 0: the cache layers' "
                  "rows live in the paged pool)")
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
        no.append("a scaled fp8 pool (kv_scale != 1)")
    if no:
        raise ValueError(
            f"{cfg.name} keeps a per-slot recurrent state "
            f"({cfg.recurrent_kind} layers, "
            f"{row_bytes(cfg, cfg.dtype)} bytes a slot) beside its "
            f"{'latent' if cfg.is_mla else 'K/V'} cache rows; this engine "
            "does not run it with: " + "; ".join(no))


def _shapes(cfg, slots: int):
    """(state shape | None, conv shape) of `slots` rows, by recurrent kind."""
    Lk = len(cfg.recurrent_layers)
    if cfg.recurrent_kind == "conv":
        return None, (Lk, slots, cfg.conv_cache - 1, cfg.hidden_size)
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return (Lk, slots, H, d, d), (Lk, slots, cfg.kda_conv - 1, 3 * H * d)


def allocate(cfg, slots: int, conv_dtype, sharding=None):
    """(state [Lk, slots, H, dk, dv] f32, conv [Lk, slots, c-1, 3·H·dk]) of a
    KDA model; (None, conv [Lc, slots, conv_cache-1, D]) of a conv model."""
    st, cv = _shapes(cfg, slots)
    rows = (None if st is None else jnp.zeros(st, jnp.float32),
            jnp.zeros(cv, conv_dtype))
    if sharding is not None:  # None has no leaf to put
        rows = jax.device_put(rows, sharding)
    return rows


def row_bytes(cfg, conv_dtype) -> int:
    """Bytes of one slot's row over all recurrent layers."""
    st, cv = _shapes(cfg, 1)
    return ((math.prod(st) * 4 if st else 0)
            + math.prod(cv) * jnp.dtype(conv_dtype).itemsize)
