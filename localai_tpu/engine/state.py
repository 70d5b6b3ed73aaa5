"""The second kind of per-slot state: a hybrid model's recurrent state.

A slot of a model with recurrent layers (cfg.is_hybrid, models/llama.py)
holds two things of different shape. Its cache rows, of whichever kind the
model's cache layers write (MLA's latent rows, or GQA's keys and values a KV
head), grow with the context and live in pages the KV manager hands out. Its
recurrent state is fixed in size, `row_bytes` a slot whatever the context,
and its shape is the recurrent kind's (`cfg.recurrent_kind`): per KDA layer
a [H, dk, dv] float32 matrix a head and the short conv's last inputs; per
gated short convolution ("conv", LFM2) the operator's last conv_cache-1
inputs [conv_cache-1, D] and no matrix at all (`state` is then None); per SSD
layer ("ssd", Mamba-2: Granite-4.0-H) a [H, P, N] float32 matrix (4 MiB at
128 heads of 64 x 128) and the conv's last mamba_conv-1 inputs over
[x | B | C]; per S6 layer ("s6", Mamba-1 as the `jamba` mixer runs it:
AI21-Jamba2) a [N, E] float32 matrix, the states on the sublanes and the
channels on the lanes (320 KiB at 16 x 5120), and the conv's last
mamba_conv-1 inputs over x alone. `_ROWS` holds the shape a kind, `_ADMIT_TOKEN_BYTES` what its
chunked prefill holds a prompt token. It needs no allocator: row i of the
arrays below belongs to slot index i,
always.

The fourth kind is no recurrence: a sliding-window attention layer ("swa",
Laguna's `sliding_attention`) attends the last `sliding_window` positions and
nothing before them, so what it keeps a slot is a RING of that many rows of
keys and values, whatever the context: position p lives in row p mod the
ring's rows, page-shaped (`state` the keys, `conv` the values, each
[Ls, slots x ring_pages, ring_page, K, D] in the cache's dtype; slot i owns
pages ring_pages·i .. of every window layer, a table that never changes:
`llama.ring_table`). Keys are stored rotated, so a reader needs to know which
rows are live and at which position each is masked, not their order: the
paged reader walks a ring as it walks a slot's pages (`ops/paged_flash`,
`ring_rows`). The KV manager's pages are the full layers' alone. Its
lifecycle is the recurrent kinds', with one difference in `decode`:

- claim:    the admission program writes the prompt's last min(n, ring) rows
            of every window layer (`llama._ring_admit`).
- decode:   a block READS the ring as it stood at its start beside its own
            rows (`llama.block_recurrent`) and writes those rows at its end,
            at their positions mod the ring (`llama.block_recurrent_done`):
            the n rows they replace are the oldest, outside the window of
            every query after the block. A block is no longer than the ring
            (`refuse`). Idle and parked rows are written too, garbage into
            a ring no tenant reads.
- park, release, preempt: as below.

The arrays ride in the cache pytree (`llama.KVCache.state`, `.conv`), so every
program that carries the cache carries them, donated with it, and the
device's order of programs is the order of their owners:

- claim:    the admission program writes the whole row from the prompt
            (`llama.prefill(recurrent=...)`: the state after the last prompt
            token, computed from zero). Nothing of an earlier tenant is read.
- decode:   every block updates every row in place, live or not
            (ops/kda.kda_decode, ops/ssd.ssd_decode, ops/s6.s6_decode,
            aliased; a conv row shifts by one input). A row without a
            tenant decays garbage into garbage; it stays bounded (KDA's
            update is a contraction, SSD's and S6's a decay < 1 plus a
            bounded input, a conv row forgets after
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


def _kda_token_bytes(cfg) -> int:
    """The chunkwise KDA prefill's widest temporaries a prompt token: the
    diagonal blocks' pairwise exponents and their exponentials,
    [SUB, SUB, dk] float32 a sub-block and head each (ops/kda.py):
    2·H·SUB·dk·4 bytes, 0.5 MB at 32 heads of 128, 1 MB at 64."""
    from localai_tpu.ops.kda import SUB

    return 2 * cfg.kda_heads * SUB * cfg.kda_head_dim * 4


def _ssd_token_bytes(cfg) -> int:
    """The chunkwise SSD prefill's float32 temporaries a prompt token
    (ops/ssd.ssd_chunk_prefill): four rows of [heads, chunk] (the chunk's
    pairwise log-decays, their exponentials, the product with C B^T and its
    copy in the layout the dot reads), a chunk's share of its state
    ([H, P, N] / chunk, held twice: what the chunk adds and what enters it),
    and six of [H, P] (x, dt x, the two halves of y, y, the gated y): 0.5 MB
    at 128 heads of 64 x 128 in chunks of 128. The whole admission program
    holds 0.65 MB a token beside them, most of it the grouped expert path's
    [rows, top-k, D] float32 rows, any MoE model's (tools/cell_program.py
    --program admit for a described v5e: 0.87 GB of temporaries at 4 x 256
    rows, 1.53 GB at 8 x 256)."""
    from localai_tpu.ops.ssd import CHUNK

    H, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    C = min(cfg.mamba_chunk, CHUNK)
    return 4 * (4 * H * C + 2 * H * P * N // C + 6 * H * P)


def _s6_token_bytes(cfg) -> int:
    """What an admission of an S6 (Mamba-1) model holds a prompt token: the
    selective scan (ops/s6.s6_prefill) is a loop over positions whose [N, E]
    state and decay exist once a PROMPT, so a token costs its float32 rows
    alone: eight of [E] (the conv's output, x, dt, dt x, y, the gated y, z
    and the scan's stacked output in the loop's own order) and the dense
    MLP's three [F] rows beside them: 262 KB at 5,120 and 8,192, 4,096 rows a
    program (tools/cell_program.py --program admit for a described v5e)."""
    return 4 * (8 * cfg.mamba_d_inner + 3 * cfg.intermediate_size)


def _mla_token_bytes(cfg) -> int:
    """What an admission of a plain-scan MLA model holds a prompt token: the
    latent rows of EVERY layer until `write_prefill_to_pool` has them (the
    scan's stacked output: 60 KB at 47 layers of 640 bfloat16), the
    full-rank q, k and v of the layer at work (3 x H x the q/k width: 30 KB
    at 20 heads of 256) and, for experts, the grouped path's [rows, top-k,
    D] float32 rows in and out (64 KB at top-4 of 2048): 156 KB, 6,800 rows
    a program (tools/cell_program.py --program admit for a described v5e:
    3.03 GB of temporaries at 32 x 512 rows, 0.81 GB at 4 x 1,024, beside
    12.7 GB held)."""
    size = 2  # the rows' and the activations' 16 bits
    rows = cfg.cache_layers * cfg.cache_k_dim * size
    qkv = 3 * cfg.num_heads * cfg.qk_head_dim * size
    moe = 2 * cfg.num_experts_per_token * cfg.hidden_size * 4 if cfg.is_moe else 0
    return rows + qkv + moe


def _swa_token_bytes(cfg) -> int:
    """What an admission of a window / full attention MoE model holds a
    prompt token: the FULL layers' K/V rows of every such layer until
    `write_prefill_to_pool` has them (40 KB at 10 layers of 8 heads of 128;
    the window layers' rows go into the rings layer by layer), q, k and v
    of the layer at work at the wider kind's head count (20 KB at 64 + 16
    heads), the grouped expert path's [rows, top-k, D] float32 rows in and
    out and its two [rows, top-k, F] intermediates (160 KB at top-8 of 2048
    and 512) and a dozen [rows, D] float32 rows of the layer at work (the
    stream, its norms, the projections' outputs: 96 KB): 323 KB, 3,318 rows
    a program, so 4 prompts of the 512 bucket (tools/cell_program.py
    --program admit for a described v5e: 1.29 GB of temporaries at 8 x 512
    rows, 315 KB a token, beside 14.9 GB held)."""
    size = 2
    rows = cfg.cache_layers * 2 * cfg.num_kv_heads * cfg.head_dim_ * size
    qkv = (max(cfg.num_heads, cfg.swa_heads) + 2 * cfg.num_kv_heads
           ) * cfg.head_dim_ * size
    k = cfg.num_experts_per_token if cfg.is_moe else 0
    moe = 2 * k * (cfg.hidden_size + cfg.moe_inter_size) * 4
    return rows + qkv + moe + 12 * cfg.hidden_size * 4


# Bytes of temporaries one prompt token costs an admission program, by
# recurrent kind, or "mla" for a model of latent attention in every layer; a
# kind that is not here is not bounded (a conv model's prefill holds a few
# [T, D] rows a prompt, as any layer's).
_ADMIT_TOKEN_BYTES = {"kda": _kda_token_bytes, "ssd": _ssd_token_bytes,
                      "mla": _mla_token_bytes, "swa": _swa_token_bytes,
                      "s6": _s6_token_bytes}


def admit_rows(cfg) -> int | None:
    """Most prompt rows (requests x bucket) one admission program takes under
    `ADMIT_BYTES`, from the model's own widths (`_ADMIT_TOKEN_BYTES`): 2,048
    rows at KDA's 32 heads of 128 (Kimi-Linear), 1,024 at 64 (Solar-Open2);
    2,048 at SSD's 128 heads of 64 x 128 (Granite-4.0-H); 6,864 at
    GLM-4.7-Flash's 47 latent layers; 3,318 at Laguna's window and full
    layers; 4,096 at S6's 5,120 channels beside a dense MLP of 8,192
    (AI21-Jamba2). None for a kind without a bound (LFM2's conv)."""
    kind = cfg.recurrent_kind or ("mla" if cfg.is_mla else "")
    per_token = _ADMIT_TOKEN_BYTES.get(kind)
    return max(1, ADMIT_BYTES // per_token(cfg)) if per_token else None


def what(cfg) -> str:
    """What a slot of this model keeps beside its cache rows, in words."""
    return "window rows" if cfg.recurrent_kind == "swa" else "recurrent state"


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
    if cfg.recurrent_kind == "swa":
        ring = cfg.ring_rows
        if ring & (ring - 1):
            no.append(f"a ring of {ring} rows (sliding_window "
                      f"{cfg.sliding_window}: the reader maps a row to its "
                      "position by a power of two)")
        if max(ecfg.block_sizes) > ring:
            no.append(f"decode blocks of {max(ecfg.block_sizes)} steps (a "
                      f"block's rows replace the ring's oldest: at most "
                      f"{ring})")
        if jnp.dtype(ecfg.cache_dtype(cfg.dtype)).itemsize < 2:
            no.append("an 8-bit cache (the rings are held in the model's "
                      "dtype)")
    if no:
        raise ValueError(
            f"{cfg.name} keeps a per-slot {what(cfg)} "
            f"({cfg.recurrent_kind} layers, "
            f"{row_bytes(cfg, cfg.dtype)} bytes a slot) beside its "
            f"{'latent' if cfg.is_mla else 'K/V'} cache rows; this engine "
            "does not run it with: " + "; ".join(no))


def _kda_rows(cfg, Lk: int, slots: int):
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return (Lk, slots, H, d, d), (Lk, slots, cfg.kda_conv - 1, 3 * H * d)


def _conv_rows(cfg, Lk: int, slots: int):
    return None, (Lk, slots, cfg.conv_cache - 1, cfg.hidden_size)


def _ssd_rows(cfg, Lk: int, slots: int):
    return ((Lk, slots, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state),
            (Lk, slots, cfg.mamba_conv - 1, cfg.mamba_conv_dim))


def _s6_rows(cfg, Lk: int, slots: int):
    E = cfg.mamba_d_inner
    return ((Lk, slots, cfg.mamba_d_state, E),
            (Lk, slots, cfg.mamba_conv - 1, E))


def _swa_rows(cfg, Ls: int, slots: int):
    ring = (Ls, slots * cfg.ring_pages, cfg.ring_page, cfg.num_kv_heads,
            cfg.head_dim_)
    return ring, ring  # the keys' rings, the values'


_ROWS = {"kda": _kda_rows, "conv": _conv_rows, "ssd": _ssd_rows,
         "swa": _swa_rows, "s6": _s6_rows}


def _shapes(cfg, slots: int):
    """(state shape | None, conv shape) of `slots` rows, by recurrent kind."""
    return _ROWS[cfg.recurrent_kind](cfg, len(cfg.recurrent_layers), slots)


def allocate(cfg, slots: int, conv_dtype, sharding=None):
    """(state [Lk, slots, H, dk, dv] f32, conv [Lk, slots, c-1, 3·H·dk]) of a
    KDA model; (None, conv [Lc, slots, conv_cache-1, D]) of a conv model;
    (state [Lm, slots, H, P, N] f32, conv [Lm, slots, c-1, d_inner + 2·G·N])
    of an SSD model; (state [Lm, slots, N, E] f32, conv [Lm, slots, c-1, E])
    of an S6 model; a window model's rings, (keys, values)
    [Ls, slots·ring_pages, ring_page, K, D] each, both in `conv_dtype`."""
    st, cv = _shapes(cfg, slots)
    rows = (None if st is None else jnp.zeros(st, _state_dtype(cfg, conv_dtype)),
            jnp.zeros(cv, conv_dtype))
    if sharding is not None:  # None has no leaf to put
        rows = jax.device_put(rows, sharding)
    return rows


def _state_dtype(cfg, conv_dtype):
    """A recurrence's matrix is float32; a ring's keys are rows like its
    values."""
    return conv_dtype if cfg.recurrent_kind == "swa" else jnp.float32


def row_bytes(cfg, conv_dtype) -> int:
    """Bytes of one slot's row over all recurrent layers."""
    st, cv = _shapes(cfg, 1)
    size = jnp.dtype(_state_dtype(cfg, conv_dtype)).itemsize
    return ((math.prod(st) * size if st else 0)
            + math.prod(cv) * jnp.dtype(conv_dtype).itemsize)
