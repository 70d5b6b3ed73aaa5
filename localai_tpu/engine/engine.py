"""Continuous-batching serving engine.

The JAX re-design of llama.cpp's server slot machinery (reference:
backend/cpp/llama-cpp/grpc-server.cpp:679 PredictStream posts server_tasks
into a slot-based queue; vendored server-context start_loop is the hot loop).
Key differences, TPU-first:

- One resident engine owns the devices. Requests are multiplexed onto a fixed
  number of KV-cache *slots*; all shapes are static so each program compiles
  exactly once.
- The entire control state lives on device: KV cache, penalty counts, PRNG
  keys, logit bias, current token and position per slot. The host never sits
  in the per-token critical path — decode runs in fused N-step `lax.scan`
  blocks (one dispatch per N tokens), and sampled tokens feed the next step
  entirely on device.
- Dispatch is pipelined: up to `pipeline_depth` decode blocks are in flight
  while the host does detokenization/stop-scan bookkeeping on earlier
  results, so the device never waits on a dispatch or a device→host copy.
- Admission is fused and batched: one program prefills up to M prompts,
  writes their KV into the cache slots, samples each first token and updates
  all per-slot device state — one dispatch per admission group instead of
  three per request.
- Prompt lengths are bucketed (powers of two) so prefill compiles once per
  (bucket, group-size), never per request.
- Sampling variants compile separately so the common paths stay cheap:
  pure-greedy blocks never pay a categorical, unfiltered sampling never pays
  a sort (Gumbel argmax), and the partial top-k candidate chain only runs
  when a slot actually uses top-k/top-p/min-p.
- Grammar-constrained requests are host-interactive by nature (the pushdown
  machine walks candidate tokens in probability order), so they fall back to
  single-step blocks that also return top-k candidate ids; the host's
  corrected token is fed back as an override input on the next dispatch.
- Streaming is UTF-8-safe incremental detokenization mirroring the byte
  reassembly at core/backend/llm.go:146-166.

Slot-finish detection (EOS / stop sequence / length) happens host-side with
up to one block of lag; the device may decode a handful of tokens past the
finish point, which are discarded. A request that ends on its token budget
(max_new_tokens, or the end of the context) loses the rest of its last block
and nothing more: when the block that covers the budget is dispatched the
slot index is handed on at once and the request is parked until that block
comes back (`Engine._park`). One that ends sooner (EOS, a stop sequence, a
cancel, a deadline), a slot under a host-walk grammar and a speculative
engine's slots are found out only when the block has been processed, so
theirs is bounded by pipeline_depth * block size (3 x 16 = 48 steps at the
defaults), the price of keeping the device saturated.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import logging
import os
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models import llama
from localai_tpu.engine import speclookup
from localai_tpu.engine import state as rstate
from localai_tpu.engine.runtime import (
    SPAN_SLICE_S,
    ControlStager,
    DeadlineIndex,
    LoopPhases,
)
from localai_tpu.models.config import ArchConfig
from localai_tpu.observe import gcwatch
from localai_tpu.observe import postmortem as opostmortem
from localai_tpu.observe import trace as otrace
from localai_tpu.observe.journal import EventJournal
from localai_tpu.observe.scopes import scope
from localai_tpu.ops.sampling import (
    NEG_INF,
    SamplingParams,
    sample,
    sample_greedy,
    sample_simple,
)
from localai_tpu.ops.stacked import SiteCounts
from localai_tpu.parallel.mesh import MeshPlan, build_mesh
from localai_tpu.parallel.sharding import cache_shardings, param_shardings, validate_plan
from localai_tpu.testing import faults

log = logging.getLogger("localai_tpu.engine")


class QueueFullError(RuntimeError):
    """submit() rejected a request because the pending queue is at
    EngineConfig.max_pending (crash-only backpressure, ISSUE 4): the server
    sheds load at admission instead of queueing unboundedly. Carries a
    Retry-After hint derived from the engine's observed admission latency so
    the HTTP layer can map this to 429/503 + Retry-After."""

    def __init__(self, depth: int, limit: int, retry_after_s: float) -> None:
        super().__init__(
            f"engine queue full ({depth} pending, max_pending={limit}) — "
            f"retry in ~{retry_after_s:.0f}s"
        )
        self.depth = depth
        self.limit = limit
        self.retry_after_s = retry_after_s


class AdapterError(RuntimeError):
    """A multi-tenant LoRA adapter operation failed (ISSUE 10,
    docs/LORA_SERVING.md): unknown adapter name, a base the runtime path
    cannot serve (MoE/MLA/speculative engines), or every device adapter
    slot pinned by active requests. Typed so the HTTP layer and the
    admission containment paths can fail ONE tenant's request cleanly
    while the engine keeps serving everyone else."""


_SAMPLING_FIELDS = (
    "temperature",
    "top_k",
    "top_p",
    "min_p",
    "repeat_penalty",
    "presence_penalty",
    "frequency_penalty",
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    max_seq: int = 2048
    min_prefill_bucket: int = 32
    base_seed: int = 0
    # Decode-block sizes the scheduler chooses from (descending); the first
    # is the throughput block, the rest serve the tail (_pick_block_size).
    # A request is live to the end of the block its budget ends in, so it
    # throws away (n - 1) / 2 steps on average, and a parked request's
    # `done` comes back pipeline_depth blocks after its slot was handed on:
    # at 64 steps 13-17% of a saturated batch's rows carried no token, at
    # 16 2.3% (PERF.md section 6, PR 43). What a shorter block costs is one
    # dispatch, one control commit and one pull every n steps; the
    # per-token host work does not depend on n. The block-local K/V window
    # is [cache_layers, B, n, K, D] (_get_block), read whole every step.
    block_sizes: tuple[int, ...] = (16, 4, 1)
    # Decode blocks kept in flight while the host processes earlier results.
    pipeline_depth: int = 3
    # Wall budget in ms for one housekeeping tick of the pipelined loop
    # (docs/ENGINE_RUNTIME.md). The lifecycle-critical sweeps (pending purge +
    # active-deadline enforcement) always run on a due tick; optional work
    # (cold-page spill, deferred prefix-span saves) runs only while the
    # tick is under budget, so housekeeping can never delay a ready
    # dispatch by more than roughly this bound plus one bounded task.
    # LOCALAI_HOUSEKEEPING_BUDGET_MS env var overrides.
    housekeeping_budget_ms: float = 2.0
    # Admission coalescing: when no decode block is in flight yet and a slot
    # was admitted within this window, hold the first block briefly so a
    # burst of simultaneous arrivals lands in the SAME block phase. A
    # block costs the same with 1 active slot as with 8 — one straggler
    # admitted just after dispatch forces a whole extra block (measured
    # with 64-step blocks: 3x260 ms instead of 2x260 ms for 8 parallel
    # requests on llama-3.2-1b, ~30% of the decode wall; GIL scheduling
    # staggers a simultaneous 8-thread burst by several ms, so the window
    # must cover that). Costs at most this many ms of added latency on a
    # lone request.
    admit_coalesce_ms: float = 6.0
    # Prompt/prefix KV cache (reference: cache_prompt, grpc-server.cpp:125):
    # device-resident LRU of prefilled KV spans keyed by token prefixes.
    # Admissions that share a prefix (system prompts, multi-turn chat) copy
    # the cached span and prefill only the tail. 0 disables.
    prefix_cache_entries: int = 8
    # Minimum matched/saved prefix length in tokens — shorter prefixes are
    # cheaper to re-prefill than to manage.
    prefix_cache_min: int = 32
    # First hit of a (prefix-bucket, tail-bucket) shape needs its own XLA
    # program. True (default): compile it on a BACKGROUND thread and serve
    # that request through the ordinary full admission — a prefix hit is an
    # optimization, never worth a multi-second serving stall (observed 6.2 s
    # for the first cached admit on TPU). False: compile synchronously on
    # the loop thread (deterministic hits; used by tests and benches).
    prefix_admit_async_compile: bool = True
    # HBM budget for stored spans. Entry count alone is not a bound: one
    # max_seq span of an 8B model is ~1 GiB of KV, so 8 entries could eat
    # half a chip. Eviction honors whichever limit trips first; a span
    # bigger than the whole budget is simply not saved.
    prefix_cache_bytes: int = 1 << 30
    # Paged KV cache (SURVEY §7 ragged/paged KV; vLLM PagedAttention role):
    # kv_pages > 0 replaces the dense [slots, max_seq] cache with a shared
    # page pool — HBM scales with live context, not slots × max_seq, so many
    # short chats and one long one share a pool neither could afford dense.
    # Admission reserves only the prompt's pages plus kv_page_headroom
    # (ISSUE 3 on-demand growth); the decode loop grows each slot's table
    # host-side as its context crosses page boundaries, and genuine pool
    # exhaustion mid-decode preempts the youngest slot (kv_preempt) instead
    # of deadlocking. 0 = dense cache.
    kv_pages: int = 0
    kv_page_size: int = 128
    # Extra pages allocated beyond the prompt bucket at admission so the
    # first decode blocks never stall on a host-side growth check. The
    # difference between this and the old planner is the whole point of
    # on-demand growth: reservation was ceil((prompt+max_new)/page), which
    # for generous max_tokens gated concurrency on pages that were mostly
    # never written. LOCALAI_KV_PAGE_HEADROOM env var overrides.
    kv_page_headroom: int = 1
    # What to do when on-demand growth finds the pool empty mid-decode
    # (after evicting prefix-cache spans): preempt the youngest live slot.
    #   "swap"      — copy the victim's pages to the bounded host-RAM tier
    #                 (kv_swap_bytes) and restore them on re-admission; the
    #                 victim resumes byte-exactly (RNG chain included).
    #   "recompute" — drop the pages and re-admit prompt+generated through
    #                 the ordinary (chunked) prefill path; byte-exact for
    #                 greedy decoding, chain-preserving otherwise.
    #   "auto"      — swap for short contexts (span fits a quarter of
    #                 kv_swap_bytes), recompute for long ones.
    # Engines with a draft model always recompute (the draft's dense KV has
    # no swap image); grammar-constrained slots are preempted only as a
    # last resort, always via recompute (the host machine is replayed).
    # LOCALAI_KV_PREEMPT env var overrides.
    kv_preempt: str = "auto"
    # Byte budget for the pinned host-RAM tier shared by preempt-swap images
    # and spilled prefix-cache spans (the prefix cache's second level:
    # spans evicted for pool pressure land here and swap back in on a hit
    # instead of being re-prefilled). 0 disables the tier (preempt falls
    # back to recompute). LOCALAI_KV_SWAP_BYTES env var overrides.
    kv_swap_bytes: int = 256 << 20
    # Paged decode attention implementation (ops/paged_flash): "auto" runs
    # the fused ragged paged-attention Pallas kernel on TPU (page-table walk
    # in-kernel, KV pages streamed HBM→VMEM once, per-slot ragged bounds)
    # and the XLA gather walk elsewhere; "pallas"/"xla" force one (pallas
    # off-TPU runs in interpret mode — tests only). LOCALAI_PAGED_KERNEL
    # env var overrides.
    paged_kernel: str = "auto"
    # Quantized-matmul kernel (ISSUE 9, docs/QUANTIZATION.md): "auto" runs
    # the fused Pallas dequant-matmul kernels (ops/quant_matmul — nibble
    # unpack + affine scale in VMEM registers, f32 MXU accumulation; the
    # packed int8/int4 bytes cross HBM exactly once) for decode-shape
    # matmuls on TPU and the XLA dequant path elsewhere; "pallas"/"xla"
    # force one (pallas off-TPU runs in interpret mode — tests only). The
    # XLA path is kept as the numeric oracle, exactly like paged_kernel.
    # LOCALAI_QUANT_KERNEL env var overrides.
    quant_kernel: str = "auto"
    # Per-head KV dequant scale for a SCALED fp8 paged pool (ISSUE 9):
    # stored rows are value/kv_scale and every reader — the Pallas ragged
    # kernel and the XLA page walk alike — multiplies back in-register, so
    # large K/V magnitudes use the fp8 grid instead of clipping at e4m3's
    # ±448. 1.0 = today's cast-only storage (byte-identical, no scale
    # bookkeeping). Requires kv_pages > 0 AND an fp8 kv_cache_dtype; the
    # engine broadcasts it to a [2, K] per-head array threaded through the
    # kernels (per-head calibration can land without another plumbing
    # change). LOCALAI_KV_SCALE env var overrides.
    kv_scale: float = 1.0
    # Ragged per-slot LoRA delta kernel (ISSUE 10, docs/LORA_SERVING.md):
    # "auto" runs the Pallas segmented grouped matmul (ops/lora_matmul —
    # per-slot adapter ids scalar-prefetched, factor blocks gathered out of
    # the stacked HBM tensors by the double-buffered grid pipeline) for
    # decode-shape deltas on TPU and the XLA gather path elsewhere;
    # "pallas"/"xla" force one (pallas off-TPU runs in interpret mode —
    # tests only). The XLA path is kept as the numeric oracle, same
    # contract as paged_kernel/quant_kernel. LOCALAI_LORA_KERNEL env var
    # overrides.
    lora_kernel: str = "auto"
    # Host-RAM byte budget for the adapter tier (ISSUE 10): fetched adapter
    # factor images page through a bounded LRU exactly like the KV swap
    # tier, so thousands of REGISTERED adapters far exceed what is
    # device-resident (the stacked factors hold only the adapters active
    # slots are using; unpinned rows evict LRU and re-fetch through this
    # tier — or from disk on a tier miss). 0 disables host caching (every
    # promote re-reads the adapter from disk).
    # LOCALAI_ADAPTER_CACHE_BYTES env var overrides.
    adapter_cache_bytes: int = 64 << 20
    # Tensor-parallel serving (ISSUE 7, docs/SHARDED_SERVING.md): shard the
    # weights (Megatron column/row splits, parallel/sharding.py), the KV
    # cache / paged pool (kv-head axis — pages live on the head shard that
    # owns them; the allocator, refcounts, and host tier stay global), and
    # the Pallas kernels (head-sharded under shard_map, psum only at the
    # o-projection) over this many devices. 0 = leave the mesh plan alone
    # (the mesh_plan argument, or single chip); N > 0 = replace the plan's
    # tp axis with N (clamped to the devices present); -1 = auto: all
    # available devices. Either way a tp the architecture cannot shard
    # evenly (GQA kv heads etc.) DEGRADES to max_valid_tp with a warning
    # instead of failing the load. LOCALAI_TENSOR_PARALLEL env var
    # overrides ("auto" = -1).
    tensor_parallel: int = 0
    # Chunked ragged prefill (docs/CHUNKED_PREFILL.md, ISSUE 2): prompts
    # whose un-cached tail exceeds this many tokens admit in
    # prefill_chunk-token chunks that the engine loop interleaves with
    # decode blocks — a long prompt no longer monopolizes the device
    # (BENCH_r04: one 32k prefill stalled every running decode for 3.5 s),
    # and under the paged pool each chunk's K/V writes land DIRECTLY in the
    # slot's pages (models/llama.prefill_chunk_paged) instead of routing
    # through a dense full-bucket buffer + scatter. Must be a power of two
    # >= min_prefill_bucket; page-aligned values (multiple of kv_page_size)
    # give the cleanest page DMAs but are not required. 0 disables
    # (single-shot admission). LOCALAI_PREFILL_CHUNK env var overrides.
    prefill_chunk: int = 0
    # Bounded admission (ISSUE 4, docs/ROBUSTNESS.md): submit() raises
    # QueueFullError once this many requests sit in the pending queue —
    # load sheds at the door (HTTP 429 + Retry-After) instead of building
    # an unbounded deque whose tail can never meet any latency target.
    # 0 = unbounded (library/embedded use). LOCALAI_MAX_PENDING overrides.
    max_pending: int = 0
    # A request still PENDING after this many seconds is shed with an error
    # event (it would have been admitted into a saturated engine only to
    # blow its caller's timeout anyway). 0 disables.
    # LOCALAI_QUEUE_TIMEOUT overrides.
    queue_timeout_s: float = 0.0
    # Default end-to-end deadline applied to requests that don't carry
    # their own GenRequest.deadline_s: once exceeded, a pending request is
    # shed and an active one is cancelled (its KV pages/host-tier bytes
    # release on the next processed block). 0 disables.
    # LOCALAI_DEADLINE overrides.
    deadline_s: float = 0.0
    # Request-lifecycle event journal (ISSUE 11, docs/OBSERVABILITY.md):
    # capacity (in events) of the engine loop's preallocated ring-buffer
    # flight recorder — queued/admitted/chunk/decode-block/preempt/swap/
    # resume/prefix-hit/span-transfer/terminal events plus per-iteration
    # dispatch records. Appends are lock-free from the loop thread, O(1),
    # allocation-free, and never touch the device (trace-safety lint
    # covers the module). 0 disables the journal (and with it /debug/
    # timeline and the postmortem journal tail). LOCALAI_TRACE_JOURNAL
    # env var overrides.
    trace_journal_events: int = 4096
    # Flight-recorder output directory (ISSUE 11): where the engine dumps
    # its postmortem JSON (journal tail + state snapshot) when the loop
    # dies. "" = a stable tempdir child (observe/postmortem.default_dir).
    # The ApplicationConfig.postmortem_dir / LOCALAI_POSTMORTEM_DIR knob
    # forwards here through the manager.
    postmortem_dir: str = ""
    # Speculative decoding draft source (ISSUE 12, docs/SPECULATIVE.md):
    #   "off"           — plain decode blocks only.
    #   "draft_model"   — the separate draft checkpoint (draft_cfg/
    #                     draft_params/n_draft engine args; the only mode
    #                     that costs extra HBM).
    #   "prompt_lookup" — model-free: per-slot n-gram suffix matches over
    #                     prompt+output (engine/speclookup.py, host-side)
    #                     feed deterministic drafts into the same verify
    #                     machinery. Greedy output is byte-identical to
    #                     plain decode; composes with paged pools, quantized
    #                     targets, grammar-DFA slots, LoRA tenants and tp>1.
    #   "self_draft"    — model-free: the target's own first
    #                     self_draft_layers layers + unembed draft on the
    #                     SAME sharded params (llama.self_draft_view — no
    #                     second checkpoint resident), with a dense scratch
    #                     KV for the k-layer prefix.
    #   "auto"          — draft_model when a draft checkpoint is configured,
    #                     else off (model-free modes are opt-in: they change
    #                     sampled requests' RNG consumption, so flipping
    #                     them on by default would break seeded streams).
    # LOCALAI_SPEC_MODE env var overrides.
    spec_mode: str = "auto"
    # First-k-layer prefix for spec_mode=self_draft. 0 = auto
    # (num_layers // 4, min 1). Threaded into ArchConfig.self_draft_layers
    # like quant_kernel. LOCALAI_SELF_DRAFT_LAYERS env var overrides.
    self_draft_layers: int = 0
    # Per-slot acceptance EWMA coefficient (ISSUE 12 acceptance-aware
    # scheduling): after each verify round a slot's estimate moves by this
    # fraction toward the round's accepted/drafted ratio. The EWMA chooses
    # each slot's next draft length — hot slots draft long, cold slots
    # decay to draft 0 and ride the plain blocks.
    # LOCALAI_SPEC_ACCEPT_EWMA env var overrides.
    spec_accept_ewma: float = 0.4
    # Draft-length buckets the verify-block programs compile for (the
    # BLOCK's draft window is bucketed up to the smallest covering entry;
    # per-slot draft lengths stay exact and ride the dispatch pack).
    # Bounds the AOT compile family set exactly like block_sizes does for
    # plain blocks. () = auto: {0, n_draft // 2, n_draft}. 0 always counts
    # as a bucket (an all-cold round dispatches a plain block, no spec
    # program at all). LOCALAI_SPEC_DRAFT_BUCKETS env var overrides
    # (comma-separated).
    spec_draft_buckets: tuple[int, ...] = ()
    # --- Million-token context serving (ISSUE 14, docs/LONG_CONTEXT.md) ---
    # Windowed+sink attention: when attention_window > 0, decode (and the
    # chunked-prefill prefix walk under the paged pool) attends only rows
    # with position < attention_sink plus rows within attention_window of
    # the query — StreamingLLM-style, absolute rope positions. This is what
    # makes a 512k–1M context's attention LINEAR in context length, and it
    # is the precondition for cold-page spill (kv_spill_bytes): a page that
    # falls out of the window can never be attended again, so its device
    # bytes can move to host RAM. Requires, under the paged pool, a chunked
    # prefill (prefill_chunk > 0, prefill_chunk <= attention_window) so
    # every long admission runs the one masked numeric path; incompatible
    # with arch sliding windows (gemma-2), draft models, spec modes and
    # mrope. 0 = full attention. LOCALAI_ATTENTION_WINDOW /
    # LOCALAI_ATTENTION_SINK env vars override.
    attention_sink: int = 0
    attention_window: int = 0
    # Host-RAM byte budget for COLD-page spill (ISSUE 14): with windowed+
    # sink decode active, pages wholly behind every live query's window
    # (and past the sink) are copied to host RAM and their device pages
    # returned to the pool — restored byte-exactly when a consumer needs
    # them hot again (prefix save), merged byte-exactly into preempt-swap
    # images otherwise. Shared (CoW prefix-span) pages never spill — they
    # are hot BECAUSE other slots read them. Separate from kv_swap_bytes so
    # spill pressure can't evict preempt images. 0 disables spill (windowed
    # decode still works; everything stays hot). LOCALAI_KV_SPILL_BYTES
    # env var overrides.
    kv_spill_bytes: int = 0
    # Hierarchical page-table geometry (ISSUE 14, ops/ptable): 0 = the flat
    # [max_slots, max_seq/page] table (fine to ~tens of k tokens); N >= 2 =
    # two-level tables with N page ids per L0 table page — each slot ships
    # an ML1 = ceil(max_pages/N)-entry L1 directory instead of one giant
    # row, the Pallas kernel walks L1 in-kernel, and table pages are shared
    # copy-on-write across slots exactly like the KV pages they map (N
    # readers of one 500k-token span pay its directory once). The
    # allocator/refcount/growth/swap machinery is unchanged either way.
    # LOCALAI_KV_L1_SPAN env var overrides.
    kv_l1_span: int = 0
    # Sequence-parallel chunked prefill (ISSUE 14): with an sp>1 mesh AND a
    # paged pool, each prefill chunk's attention runs ring-sharded over
    # "sp" (parallel/ring.ring_chunk_paged_attention — per-chip chunk
    # compute is chunk/sp, in-chunk K/V rotating neighbor-to-neighbor)
    # while the chunk's K/V still scatters straight into pool pages. False
    # = keep sp meshes on the dense single-shot ring path (paged + sp then
    # rejects at load, the pre-ISSUE-14 behavior). LOCALAI_SP_PREFILL env
    # var overrides ("0" disables).
    sp_prefill: bool = True
    # KV-cache storage dtype (reference: CacheTypeKey/CacheTypeValue,
    # backend/backend.proto:261-262, llama.cpp q8 KV). "" = model dtype;
    # "fp8" (e4m3) / "fp8_e5m2" halve KV bytes — the TPU-native equivalent
    # of q8 (cast-only, no scale bookkeeping; XLA fuses the converts into
    # the cache reads/writes). Composes with dense/paged/sp/spec/prefix:
    # every kernel reads via astype(f32) and writes via astype(cache dtype).
    kv_cache_dtype: str = ""
    # Tree-batched parallel sampling (ISSUE 18, docs/TREE_SAMPLING.md):
    # submit_fork() admits a shared prompt ONCE and forks the slot N-1
    # times by addref'ing its KV pages and CoW-mapping its L1 directory
    # chunks — n>1 / best_of pay one prefill instead of N. False (or
    # LOCALAI_FORK_SAMPLING=0) degrades every fork to the N-clone
    # admission path (byte-identical output, N× prefill + KV). Dense
    # (kv_pages=0) engines and draft-model spec always clone.
    fork_sampling: bool = True

    def cache_dtype(self, model_dtype):
        import jax.numpy as _jnp

        table = {
            "": None,
            "fp8": _jnp.float8_e4m3fn,
            "fp8_e4m3": _jnp.float8_e4m3fn,
            "fp8_e5m2": _jnp.float8_e5m2,
        }
        if self.kv_cache_dtype not in table:
            raise ValueError(
                f"kv_cache_dtype {self.kv_cache_dtype!r} not supported — "
                "use 'fp8' (e4m3) or 'fp8_e5m2'"
            )
        dt = table[self.kv_cache_dtype]
        return _jnp.dtype(model_dtype) if dt is None else dt

    def buckets(self) -> list[int]:
        out, b = [], self.min_prefill_bucket
        while b < self.max_seq:
            out.append(b)
            b *= 2
        out.append(self.max_seq)
        return out


@dataclasses.dataclass
class GenRequest:
    prompt_ids: list[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    repeat_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    stop: list[str] = dataclasses.field(default_factory=list)
    seed: Optional[int] = None
    ignore_eos: bool = False
    logit_bias: dict[int, float] = dataclasses.field(default_factory=dict)
    # Grammar-constrained decoding (localai_tpu.functions.jsonschema
    # GrammarConstraint): the engine picks the best valid token from the
    # model's top-k candidates each step and may emit EOS only when the
    # grammar is complete. Penalty counts track sampled (not overridden)
    # tokens for these requests — an accepted approximation.
    grammar: Optional[Any] = None
    # Top-N logprobs per generated token (0 = off). When > 0 every token
    # event carries the sampled token's logprob and the top-N alternatives,
    # computed from log_softmax(logits + bias) — the raw model distribution
    # (with user bias), before penalties/temperature, matching OpenAI
    # semantics (reference: Reply logprobs in backend.proto / chat.go).
    logprobs: int = 0
    # Multimodal (VLM): projected image features [N, hidden] injected over
    # prompt_ids[image_offset : image_offset+N] at prefill (llava semantics;
    # the placeholder ids under the span are ignored).
    image_embeds: Optional[Any] = None
    image_offset: int = 0
    # Qwen2-VL m-rope: [3, len(prompt_ids)] (t, h, w) position streams
    # (models/qwen2_vl.mrope_positions_for_span). None → standard rope.
    mrope_positions: Optional[Any] = None
    # End-to-end deadline in seconds from submit() (ISSUE 4): a request
    # still pending past it is shed with an error event; an active one is
    # cancelled and its slot/KV pages released. 0 = engine default
    # (EngineConfig.deadline_s), which may itself be 0 (no deadline).
    deadline_s: float = 0.0
    # Multi-tenant LoRA (ISSUE 10): name of a registered runtime adapter
    # (Engine.register_adapter) applied UNMERGED to this request — the
    # OpenAI `model` field selects it through a virtual-model config
    # (docs/LORA_SERVING.md). None = serve the shared base weights.
    adapter: Optional[str] = None
    # Request-lifecycle tracing (ISSUE 11, docs/OBSERVABILITY.md): a
    # caller-visible request id (the OpenAI response id at the HTTP layer)
    # keys the span tree at /debug/trace/{request_id}; traceparent is the
    # W3C header value propagated from HTTP through cluster dispatch,
    # federation proxying, and span-transfer frames so a disaggregated
    # prefill→decode request stays ONE trace across replicas. Empty =
    # untraced (library/bench callers pay nothing).
    request_id: str = ""
    traceparent: str = ""
    # INTERNAL — set by the engine when it preempts a slot (ISSUE 3).
    # Carries the victim's host-side continuation state (generated tokens,
    # RNG chain, swap image) so re-admission resumes the original stream
    # instead of starting over. Never set by callers.
    resume: Optional[dict] = None
    # INTERNAL — set by submit_fork() on the group's PRIMARY request
    # (ISSUE 18): [(branch_request, branch_handle), ...] siblings to fork
    # off this request's slot right after its one shared-prompt prefill.
    # Every path that terminates a pending primary must also terminate or
    # requeue these (see _fork_group_detach). Never set by callers.
    fork_group: Optional[list] = None
    # INTERNAL — set by the cluster layer on a mid-stream grammar failover
    # (ISSUE 19): the `grammar` object arrives already advanced past this
    # many emitted tokens (replayed on the survivor). Non-zero keeps the
    # request on the HOST grammar walk — a device-DFA init starts at the
    # grammar's initial state, which is wrong mid-stream (same reason
    # `resume` requests skip the DFA). Never set by callers.
    grammar_pos: int = 0


@dataclasses.dataclass
class TokenEvent:
    kind: str  # "token" | "done" | "error"
    text: str = ""
    token_id: int = -1
    finish_reason: Optional[str] = None  # "stop" | "length"
    error: Optional[str] = None
    # Filled on "done", mirroring Reply timing fields (backend.proto:169-170).
    prompt_tokens: int = 0
    completion_tokens: int = 0
    timing_prompt_processing: float = 0.0  # seconds (TTFT component)
    timing_token_generation: float = 0.0
    # Seconds spent in the pending queue before the admission dispatch
    # (ISSUE 11): ttft = queue wait + prompt processing; the HTTP layer
    # feeds the queue_wait/ttft histograms from these.
    timing_queue_wait: float = 0.0
    # Filled on "token" when the request asked for logprobs.
    logprob: Optional[float] = None
    top_logprobs: Optional[list] = None  # [(token_id, logprob)] descending


class _EventQueue(queue.Queue):
    """Token-event queue that mirrors TERMINAL events into the request's
    trace (ISSUE 11). Every path that ends a stream — _finish, cancel,
    deadline sweeps, loop death, stop() — funnels through put() on this
    queue, so routing the terminal note here guarantees each traced
    request records exactly one terminal (RequestTrace.terminal is
    idempotent; stop()'s deliberate duplicate done events are ignored).
    Untraced requests (trace is None) pay one attribute check per event."""

    def __init__(self) -> None:
        super().__init__()
        self.trace: Optional[otrace.RequestTrace] = None

    def put(self, item, *args, **kwargs):
        tr = self.trace
        if tr is not None and getattr(item, "kind", None) in ("done", "error"):
            tr.terminal(item)
        super().put(item, *args, **kwargs)


class RequestHandle:
    """Streaming consumer side of a submitted request."""

    def __init__(self) -> None:
        self._q: "_EventQueue" = _EventQueue()
        self.cancelled = threading.Event()
        # Stamped by submit(): admission-wait measurement + deadline/queue-
        # timeout enforcement (ISSUE 4). 0.0 / None on handles built outside
        # submit (warmup) — every consumer guards on that.
        self.t_submit: float = 0.0
        self.deadline: Optional[float] = None  # absolute monotonic
        # Lifecycle tracing (ISSUE 11): journal request id (always set by
        # submit) and the request's span-tree recorder (None = untraced).
        self.rid: str = ""
        self.trace: Optional[otrace.RequestTrace] = None
        # Admission-dispatch stamp (_note_admitted): terminal events derive
        # timing_queue_wait from it.
        self.t_admit: float = 0.0
        # Decode blocks in flight when the request was admitted, from its
        # admission until its first token out of a decode block is posted
        # (the `decode_first` journal event): -1 before, -2 after.
        self.join_blocks: int = -1

    def __iter__(self) -> Iterator[TokenEvent]:
        while True:
            ev = self._q.get()
            yield ev
            if ev.kind in ("done", "error"):
                return

    def cancel(self) -> None:
        self.cancelled.set()

    def result(self) -> tuple[str, TokenEvent]:
        """Drain the stream; returns (full text, final event)."""
        parts: list[str] = []
        final = TokenEvent(kind="error", error="empty stream")
        for ev in self:
            if ev.kind == "token":
                parts.append(ev.text)
            final = ev
        if final.kind == "error":
            raise RuntimeError(final.error)
        return "".join(parts), final


@dataclasses.dataclass
class _Slot:
    request: GenRequest
    handle: RequestHandle
    prompt_len: int
    generated: list[int] = dataclasses.field(default_factory=list)
    emitted_len: int = 0  # chars of decoded text already streamed
    # Text of the first `dec_n` generated tokens, settled (Engine._decoded).
    dec_n: int = 0
    dec_text: str = ""
    scheduled: int = 0  # decode steps dispatched (>= len(generated))
    # Upper bound on KV rows dispatched writes may touch (prompt rows +
    # decode steps scheduled) — what on-demand page growth must cover
    # BEFORE the next block dispatch (ISSUE 3). Spec rounds advance it by
    # their whole window, a safe overestimate.
    sched_rows: int = 0
    t_submit: float = 0.0
    t_first: float = 0.0
    # Grammar enforced on device via DFA tables (functions/dfa.py): the host
    # never walks candidates and the slot runs in full-depth fused blocks.
    dfa: bool = False
    # Set once the slot index has been handed on (Engine._park): what the
    # index held for this request until its last block comes back.
    parked: Optional["_Parked"] = None


@dataclasses.dataclass
class _Parked:
    """What a slot index held for a request that left it early: the index
    is another request's now, so the pages, table pages, spill images and
    adapter pin wait here, under (idx, gen) in Engine._parked, until `last`
    has been processed. They are released then and not before: the
    finish-time prefix save needs the generated ids."""

    idx: int
    gen: int  # the index's generation while the request held it
    last: "_Entry"  # the block that covers the request's budget
    pages: list[int]
    tps: list[int]  # hierarchical tables: the directory's table pages
    spill: dict  # cold-page host images, by page column
    adapter_row: int
    # Dense cache with the prefix cache on: (pb, k, v), the row's snapshot
    # taken behind `last`, before the next tenant's prefill overwrites it.
    snap: Optional[tuple] = None


def _parse_tp_env(val: str) -> int:
    """LOCALAI_TENSOR_PARALLEL value: an integer, or "auto" (= -1, all
    available devices with max_valid_tp degrade)."""
    return -1 if val.strip().lower() == "auto" else int(val)


def _parse_flag_env(val: str) -> bool:
    """Boolean env values ("1"/"true"/"yes"/"on"); bool("0") would be True."""
    return val.strip().lower() in ("1", "true", "yes", "on")


def _parse_buckets_env(val: str) -> tuple[int, ...]:
    """LOCALAI_SPEC_DRAFT_BUCKETS value: comma/pipe-separated ints."""
    return tuple(
        int(x) for x in val.replace("|", ",").split(",") if x.strip()
    )


# Every program the engine jits, by kind. The compiled module is named
# `jit_<kind>` (shapes and flags stay in the fingerprint), so a profiler
# trace, a compile log and the benchmark's readers tell the decode block
# from an admission by name (docs/OBSERVABILITY.md).
PROGRAM_NAMES = frozenset({
    "decode_block", "spec_block", "admit", "admit_spec", "admit_cached",
    "admit_cached_paged", "prefill_chunk", "prefill_chunk_final", "chunk_pin",
    "span_copy", "fork", "page_copy", "ctrl_copy", "snapshot", "pages_gather",
    "swap_in", "resume_restore", "rng_set", "sd_sync", "sd_sync_paged",
    "prefill", "embed", "score", "quantize_params",
})


def _named_jit(fn, name: str, sites: Optional[SiteCounts] = None,
               leaf: str = "control", **kw):
    """`jax.jit(fn, **kw)` under a stable program name (compile-time only).
    With `sites`, each trace of the program counts into it the quantized
    matmul and paged-attention call sites it holds (ops/stacked.SiteCounts).

    The body is traced under the scope `leaf`, by default `control`: what a
    program does outside the model's own scopes (models/llama.py) and outside
    the `sample` regions marked below is the engine's glue (host control
    unpacked, slot rows written, positions advanced, the step loop), and a
    capture books it so (observe/scopes.py). The few programs whose glue is
    something else say so: those that only move cache rows (a span, pages, a
    slot's snapshot) pass `attention/cache_write`, the fork, which only
    samples, `sample`."""
    assert name in PROGRAM_NAMES, name
    body = fn

    def fn(*args, **kwargs):
        counting = (sites.tracing(name) if sites is not None
                    else contextlib.nullcontext())
        with scope(leaf), counting:
            return body(*args, **kwargs)

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


def _gather_pages(k, v, pages):
    """`k[:, pages], v[:, pages]` of a page pool, a page at a time. XLA's
    gather over a row wider than one lane tile first cuts the WHOLE pool into
    128-lane slices, a pool-sized temporary (5.9 GB under a 47-layer latent
    pool of 640-value rows: it could not load beside the pool, PERF.md
    section 7 item 15a). A slice of the page axis reads the pages it wants
    whatever the row."""
    def per_page(x):
        got = jax.lax.map(
            lambda p: jax.lax.dynamic_index_in_dim(x, p, axis=1,
                                                   keepdims=False), pages)
        return jnp.moveaxis(got, 0, 1)

    return per_page(k), per_page(v)


@dataclasses.dataclass
class _Entry:
    """One in-flight dispatch whose results the host still has to process."""

    kind: str  # "admit" | "block"
    toks: Any  # device array: admit [M]; block [n, B]
    tk: Any  # top-k candidate ids or None: admit [M, K]; block [n, B, K]
    lp: Any = None  # logprob triple (tok_lp, lp_ids, lp_vals) or None
    gen: list[int] = dataclasses.field(default_factory=list)  # slot-generation snapshot at dispatch
    items: Optional[list] = None  # admit: [(slot_idx, request, handle, plen, t0)]
    active: Optional[np.ndarray] = None  # block: active mask at dispatch
    n: int = 0  # block: tokens per slot in this entry
    # Spec rounds (ISSUE 12): per-slot draft lengths chosen at dispatch —
    # the acceptance-EWMA update needs the denominator per slot.
    dlens: Optional[np.ndarray] = None
    # Decode block of a MoE model: device [2] i32, the block's routing sums
    # (experts that got a row, busiest expert's rows); see _count_routing.
    # Admission under an expert share: device [2] i32, what the grouped
    # expert kernel walked; see _count_admit_routing.
    moe: Any = None
    # Host-side results pulled by the drainer thread (toks, tk, lp, moe as
    # numpy).
    host: Optional[tuple] = None
    host_done: bool = False

    def ready(self) -> bool:
        if self.host_done:
            return True
        return bool(self.toks.is_ready())


@dataclasses.dataclass
class _BlockPlan:
    """One decode block's control state, built ahead of dispatch (ISSUE 17).

    The prepare-ahead path fills this while the previous block is still in
    flight; the post-result path then only commits + dispatches. `epoch`
    stamps the scheduler state the plan was derived from — any mutation
    that could change the plan (slot claim/release, preempt, override
    write, chunk activation) bumps Engine._ctrl_epoch and the stale plan
    is dropped, so a consumed plan is always byte-identical to what
    _plan_block would build at dispatch time."""

    grammar: bool
    variant: str
    n: int
    with_dfa: Any        # False or the dfa mode string (see _dfa_mode)
    with_lp: bool
    kv_win: Optional[int]
    with_lora: bool
    # None, or (smode, (kb, dlens, windows)) — a planned speculative round.
    spec: Optional[tuple]
    active: Optional[np.ndarray]   # active-mask snapshot (plain blocks)
    pack: Optional[np.ndarray]     # sampling/override pack (plain blocks)
    epoch: int = 0


class Engine:
    """Persistent multi-slot generation engine for one loaded model."""

    GRAMMAR_TOPK = 64
    LOGPROB_TOPK = 20  # OpenAI caps top_logprobs at 20
    _KV_WIN_MIN = 256  # smallest read-side KV window bucket (doubles up to max_seq)
    # Acceptance-aware scheduling (ISSUE 12): a slot whose acceptance EWMA
    # falls below the floor drafts 0 (plain decode); every PROBE_EVERY
    # cold rounds it re-tries the smallest nonzero bucket so a stream
    # whose statistics improved (e.g. entered a quoting span) can warm
    # back up.
    _SPEC_EWMA_FLOOR = 0.15
    _SPEC_PROBE_EVERY = 32
    # When a model-free spec round found nothing to draft, the fallback
    # plain block is capped at this many steps: a longer block would
    # forfeit every draft opportunity inside its window — the suffix index
    # / EWMA only get to re-plan between dispatches. The default throughput
    # block is this long already; the cap binds where block_sizes[0] was
    # set larger from code.
    _SPEC_REPLAN_BLOCK = 16

    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        tokenizer,
        mesh_plan: Optional[MeshPlan] = None,
        engine_cfg: Optional[EngineConfig] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        draft_cfg: Optional[ArchConfig] = None,
        draft_params: Any = None,
        n_draft: int = 5,
        quantization: str = "",
    ) -> None:
        self.cfg = cfg
        # Per program kind: traces, and the quantized matmul sites in them
        # that took the layer stack or a slice (metrics(), OBSERVABILITY.md).
        self.quant_sites = SiteCounts()
        # Under an expert share the admission program also returns what its
        # grouped expert kernel walked: the sorted (row, pick) pairs it was
        # compiled for and those of an expert held here (journal
        # `moe_admit_rows`, _count_admit_routing).
        self._held_rows = bool(cfg.is_moe and cfg.expert_share is not None)
        self.tokenizer = tokenizer
        self.ecfg = engine_cfg or EngineConfig()
        env_chunk = os.environ.get("LOCALAI_PREFILL_CHUNK")
        if env_chunk is not None and env_chunk != "":
            self.ecfg = dataclasses.replace(
                self.ecfg, prefill_chunk=int(env_chunk)
            )
        for env, (fname, conv) in {
            "LOCALAI_KV_PAGE_HEADROOM": ("kv_page_headroom", int),
            "LOCALAI_KV_PREEMPT": ("kv_preempt", str),
            "LOCALAI_KV_SWAP_BYTES": ("kv_swap_bytes", int),
            "LOCALAI_MAX_PENDING": ("max_pending", int),
            "LOCALAI_QUEUE_TIMEOUT": ("queue_timeout_s", float),
            "LOCALAI_DEADLINE": ("deadline_s", float),
            "LOCALAI_TENSOR_PARALLEL": ("tensor_parallel", _parse_tp_env),
            "LOCALAI_QUANT_KERNEL": ("quant_kernel", str),
            "LOCALAI_KV_SCALE": ("kv_scale", float),
            "LOCALAI_LORA_KERNEL": ("lora_kernel", str),
            "LOCALAI_ADAPTER_CACHE_BYTES": ("adapter_cache_bytes", int),
            "LOCALAI_TRACE_JOURNAL": ("trace_journal_events", int),
            "LOCALAI_POSTMORTEM_DIR": ("postmortem_dir", str),
            "LOCALAI_SPEC_MODE": ("spec_mode", str),
            "LOCALAI_SELF_DRAFT_LAYERS": ("self_draft_layers", int),
            "LOCALAI_SPEC_ACCEPT_EWMA": ("spec_accept_ewma", float),
            "LOCALAI_SPEC_DRAFT_BUCKETS": ("spec_draft_buckets", _parse_buckets_env),
            "LOCALAI_ATTENTION_SINK": ("attention_sink", int),
            "LOCALAI_ATTENTION_WINDOW": ("attention_window", int),
            "LOCALAI_KV_SPILL_BYTES": ("kv_spill_bytes", int),
            "LOCALAI_KV_L1_SPAN": ("kv_l1_span", int),
            "LOCALAI_SP_PREFILL": ("sp_prefill", _parse_flag_env),
            "LOCALAI_FORK_SAMPLING": ("fork_sampling", _parse_flag_env),
            "LOCALAI_HOUSEKEEPING_BUDGET_MS": ("housekeeping_budget_ms",
                                               float),
        }.items():
            val = os.environ.get(env)
            if val is not None and val != "":
                self.ecfg = dataclasses.replace(self.ecfg, **{fname: conv(val)})
        if self.ecfg.kv_preempt not in ("swap", "recompute", "auto"):
            raise ValueError(
                f"kv_preempt={self.ecfg.kv_preempt!r}: use swap|recompute|auto"
            )
        if self.ecfg.kv_page_headroom < 0:
            raise ValueError("kv_page_headroom must be >= 0")
        if self.ecfg.max_pending < 0:
            raise ValueError("max_pending must be >= 0 (0 = unbounded)")
        if self.ecfg.queue_timeout_s < 0 or self.ecfg.deadline_s < 0:
            raise ValueError("queue_timeout_s / deadline_s must be >= 0")
        if self.ecfg.quant_kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"quant_kernel={self.ecfg.quant_kernel!r}: use auto|pallas|xla"
            )
        if self.ecfg.lora_kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"lora_kernel={self.ecfg.lora_kernel!r}: use auto|pallas|xla"
            )
        if self.ecfg.adapter_cache_bytes < 0:
            raise ValueError("adapter_cache_bytes must be >= 0")
        if self.ecfg.trace_journal_events < 0:
            raise ValueError("trace_journal_events must be >= 0 (0 = off)")
        if self.ecfg.housekeeping_budget_ms <= 0:
            raise ValueError("housekeeping_budget_ms must be > 0")
        if self.ecfg.kv_scale <= 0:
            raise ValueError("kv_scale must be > 0")
        if self.ecfg.kv_scale != 1.0 and not (
            self.ecfg.kv_pages > 0 and self.ecfg.kv_cache_dtype
        ):
            raise ValueError(
                "kv_scale != 1.0 requires a paged pool (kv_pages > 0) with "
                "an fp8 kv_cache_dtype — the dense cache has no scaled path"
            )
        # Windowed+sink long-context serving (ISSUE 14,
        # docs/LONG_CONTEXT.md): validate the knob set, then thread it to
        # every attention call through the (frozen) ArchConfig like
        # quant_kernel below.
        sink_t = self.ecfg.attention_sink
        win_t = self.ecfg.attention_window
        if sink_t < 0 or win_t < 0:
            raise ValueError("attention_sink / attention_window must be >= 0")
        if sink_t and not win_t:
            raise ValueError(
                "attention_sink without attention_window is full attention "
                "— set attention_window > 0 (or drop the sink)"
            )
        if win_t:
            if cfg.sliding_window:
                raise ValueError(
                    f"attention_window composes with full-attention models "
                    f"only — {cfg.name} already has an architectural "
                    f"sliding window"
                )
            if getattr(cfg, "mrope_section", ()):
                raise ValueError(
                    "attention_window excludes m-rope (VLM) models this "
                    "round — text decoders only"
                )
            if self.ecfg.kv_pages > 0:
                C0 = self.ecfg.prefill_chunk
                if not C0:
                    raise ValueError(
                        "attention_window on a paged pool requires chunked "
                        "prefill (prefill_chunk > 0) — long admissions must "
                        "run the one masked prefix-walk path"
                    )
                if C0 > win_t:
                    raise ValueError(
                        f"prefill_chunk={C0} must be <= attention_window="
                        f"{win_t} (the in-chunk causal part must sit inside "
                        "the window for the mask to stay exact)"
                    )
        if self.ecfg.kv_spill_bytes < 0:
            raise ValueError("kv_spill_bytes must be >= 0")
        if self.ecfg.kv_l1_span:
            if self.ecfg.kv_l1_span < 2:
                raise ValueError("kv_l1_span must be >= 2 (0 = flat table)")
            if self.ecfg.kv_pages <= 0:
                raise ValueError(
                    "kv_l1_span (hierarchical page tables) requires a paged "
                    "pool (kv_pages > 0)"
                )
        if (cfg.attention_sink != sink_t or cfg.attention_window != win_t):
            cfg = dataclasses.replace(
                cfg, attention_sink=sink_t, attention_window=win_t
            )
            self.cfg = cfg
        # Thread the quant-kernel choice to every model-side matmul through
        # the (frozen) ArchConfig — cfg is the one static object each layer
        # helper already receives (models/config.py quant_kernel).
        if self.ecfg.quant_kernel != cfg.quant_kernel:
            cfg = dataclasses.replace(cfg, quant_kernel=self.ecfg.quant_kernel)
            self.cfg = cfg
        # Same treatment for the ragged LoRA delta kernel (ISSUE 10).
        if self.ecfg.lora_kernel != cfg.lora_kernel:
            cfg = dataclasses.replace(cfg, lora_kernel=self.ecfg.lora_kernel)
            self.cfg = cfg
        if draft_cfg is not None and (
            self.ecfg.quant_kernel != draft_cfg.quant_kernel
        ):
            draft_cfg = dataclasses.replace(
                draft_cfg, quant_kernel=self.ecfg.quant_kernel
            )
        # Arm LOCALAI_FAULTS (deterministic fault injection — testing/faults)
        # before the loop thread can hit any hook point.
        faults.ensure_env_installed()
        C = self.ecfg.prefill_chunk
        if C:
            if C < self.ecfg.min_prefill_bucket or C & (C - 1):
                raise ValueError(
                    f"prefill_chunk={C} must be a power of two >= "
                    f"min_prefill_bucket={self.ecfg.min_prefill_bucket}"
                )
        self.plan = mesh_plan or MeshPlan(dp=1, tp=1)
        # tensor_parallel knob (ISSUE 7): a nonzero value replaces the
        # plan's tp axis — the explicit EngineConfig/YAML/env route to
        # sharded serving that doesn't require callers to build a MeshPlan.
        tp_req = self.ecfg.tensor_parallel
        if tp_req:
            ndev = len(devices) if devices is not None else len(jax.devices())
            room = max(1, ndev // max(1, self.plan.dp * self.plan.ep * self.plan.sp))
            tp = room if tp_req < 0 else tp_req
            if tp > room:
                log.warning(
                    "tensor_parallel=%d exceeds the %d device(s) available "
                    "(dp=%d ep=%d sp=%d) — clamping to tp=%d",
                    tp_req, ndev, self.plan.dp, self.plan.ep, self.plan.sp,
                    room,
                )
                tp = room
            self.plan = dataclasses.replace(self.plan, tp=max(1, tp))
        # Auto-degrade (ISSUE 7 satellite): a tp the architecture (or the
        # draft's) cannot shard evenly degrades to the largest joint
        # max_valid_tp instead of crashing at load. ep violations (and any
        # other non-tp plan error) still raise the typed ShardingPlanError.
        from localai_tpu.parallel.sharding import ShardingPlanError, max_valid_tp

        tp_cfgs = [cfg] + ([draft_cfg] if draft_cfg is not None else [])
        tp_eff = self.plan.tp
        while tp_eff > 1:
            t2 = min(max_valid_tp(c, tp_eff) for c in tp_cfgs)
            if t2 == tp_eff:
                break
            tp_eff = t2
        if tp_eff != self.plan.tp:
            log.warning(
                "tp=%d cannot shard %s evenly — degrading to tp=%d "
                "(max_valid_tp)", self.plan.tp,
                "/".join(c.name for c in tp_cfgs), tp_eff,
            )
            self.plan = dataclasses.replace(self.plan, tp=tp_eff)
        validate_plan(cfg, self.plan.tp, self.plan.ep)
        self.mesh = build_mesh(self.plan, devices)
        # Mesh handed to model/op code: the sp ring path AND the tp
        # head-sharded Pallas kernel paths key off it; None on single-chip
        # plans so every existing single-device trace stays byte-identical.
        self._op_mesh = (
            self.mesh if (self.plan.sp > 1 or self.plan.tp > 1) else None
        )
        if self.plan.sp > 1:
            if cfg.is_mla:
                raise ValueError(
                    "MLA models exclude sp>1 this round (PARITY.md) — "
                    "shard over tp/ep instead"
                )
            ecfg_ = engine_cfg or EngineConfig()
            if ecfg_.max_seq % self.plan.sp or ecfg_.min_prefill_bucket % self.plan.sp:
                raise ValueError(
                    f"max_seq={ecfg_.max_seq} and min_prefill_bucket="
                    f"{ecfg_.min_prefill_bucket} must divide by sp={self.plan.sp}"
                )
            if draft_cfg is not None:
                raise ValueError(
                    "speculative decoding with a sequence-sharded KV cache "
                    "(sp>1) is not supported yet — drop the draft model or sp"
                )
        # Speculative decoding (reference: draft_model/n_draft,
        # model_config.go:211-212 passed into llama.cpp's batch decode).
        self.draft_cfg = draft_cfg
        self.n_draft = max(1, int(n_draft))
        if draft_cfg is not None and draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft model vocab ({draft_cfg.vocab_size}) must match the "
                f"target vocab ({cfg.vocab_size})"
            )
        # Draft-source selection (ISSUE 12, docs/SPECULATIVE.md): resolve
        # spec_mode before any spec state is sized.
        mode = self.ecfg.spec_mode
        if mode not in ("off", "auto", "draft_model", "prompt_lookup",
                        "self_draft"):
            raise ValueError(
                f"spec_mode={mode!r}: use "
                "off|draft_model|prompt_lookup|self_draft|auto"
            )
        if mode == "auto":
            mode = "draft_model" if draft_cfg is not None else "off"
        if mode == "draft_model" and draft_cfg is None:
            raise ValueError(
                "spec_mode=draft_model needs a draft checkpoint "
                "(draft_model in the model YAML / draft_cfg+draft_params)"
            )
        if mode in ("prompt_lookup", "self_draft") and draft_cfg is not None:
            raise ValueError(
                f"spec_mode={mode} is model-free — the configured draft "
                "model would sit dead in HBM; drop draft_model or use "
                "spec_mode=draft_model"
            )
        if mode in ("prompt_lookup", "self_draft") and self.plan.sp > 1:
            raise ValueError(
                "speculative decoding with a sequence-sharded KV cache "
                "(sp>1) is not supported yet — drop spec_mode or sp"
            )
        self._sd_layers = 0
        if mode == "self_draft":
            if cfg.is_moe or cfg.is_mla or cfg.first_k_dense:
                raise ValueError(
                    "spec_mode=self_draft needs a homogeneous dense layer "
                    f"stack ({cfg.name} is "
                    f"{'MoE' if cfg.is_moe else 'MLA/dense-prefix'}) — use "
                    "prompt_lookup instead"
                )
            kl = self.ecfg.self_draft_layers or max(1, cfg.num_layers // 4)
            if not 1 <= kl < cfg.num_layers:
                raise ValueError(
                    f"self_draft_layers={kl} must be in [1, "
                    f"num_layers={cfg.num_layers})"
                )
            self._sd_layers = kl
            if cfg.self_draft_layers != kl:
                # Threaded like quant_kernel: the one static object the
                # layer helpers already receive (llama.self_draft_view).
                cfg = dataclasses.replace(cfg, self_draft_layers=kl)
                self.cfg = cfg
        self._spec_mode = mode
        if not 0.0 < self.ecfg.spec_accept_ewma <= 1.0:
            raise ValueError("spec_accept_ewma must be in (0, 1]")
        # Draft-length bucket set: the verify BLOCK's draft window is
        # bucketed up to the smallest covering entry (compile families stay
        # bounded, exactly like block_sizes); per-slot lengths stay exact.
        raw_buckets = self.ecfg.spec_draft_buckets
        if raw_buckets:
            bl = sorted({int(b) for b in raw_buckets if int(b) >= 0} | {0})
        else:
            bl = sorted({0, self.n_draft // 2, self.n_draft})
        if mode != "off" and bl[-1] < 1:
            raise ValueError(
                f"spec_draft_buckets={raw_buckets} needs at least one "
                "bucket >= 1"
            )
        self._spec_buckets = tuple(bl)
        if cfg.is_hybrid:
            # The recurrent state is engine/state.py's business: what this
            # engine cannot run with it is refused here, by name.
            rstate.refuse(cfg, self.ecfg, self.plan, draft_cfg, mode)
        if self.ecfg.attention_window and (
            mode != "off" or draft_cfg is not None
        ):
            raise ValueError(
                "attention_window excludes speculative decoding this round "
                "— the verify chunk has no windowed+sink variant; drop "
                "spec_mode/draft_model or the window"
            )

        B, S, V = self.ecfg.max_slots, self.ecfg.max_seq, cfg.vocab_size
        from localai_tpu.models.quant import is_prequantized, quantize_params
        from localai_tpu.parallel.sharding import param_shardings_for

        with self.mesh:
            pshard = param_shardings_for(cfg, self.mesh, params)
            self.params = jax.tree.map(
                lambda a, s: jax.device_put(a, s), params, pshard
            )
            if quantization and not is_prequantized(params):
                # Weight-only int8 AFTER sharded placement so q/s inherit
                # the weight shardings (models/quant.py). Checkpoints too big
                # for HBM in bf16 arrive pre-quantized from the loader
                # instead (load_hf_checkpoint quantize=).
                self.params = self._jit(
                    lambda p: quantize_params(cfg, p, quantization),
                    "quantize_params",
                )(self.params)
            if self.ecfg.kv_pages > 0:
                # Paged pool [L, P, page, K, Hd]: kv-heads shard over tp;
                # pages are shared across slots so dp doesn't apply, and
                # sp>1 serves ONLY the ring-sharded chunked prefill (ISSUE
                # 14, sp_prefill) — the pool itself replicates over sp.
                if self.plan.dp > 1:
                    raise ValueError(
                        "paged KV cache (kv_pages > 0) requires dp == 1"
                    )
                if self.plan.sp > 1:
                    C0 = self.ecfg.prefill_chunk
                    if not (self.ecfg.sp_prefill and C0):
                        raise ValueError(
                            "paged KV cache with sp > 1 requires the "
                            "sequence-parallel chunked prefill (sp_prefill "
                            "on AND prefill_chunk > 0, ISSUE 14)"
                        )
                    if C0 % self.plan.sp:
                        raise ValueError(
                            f"prefill_chunk={C0} must divide by "
                            f"sp={self.plan.sp}"
                        )
                if S % self.ecfg.kv_page_size:
                    raise ValueError(
                        f"max_seq={S} must divide by kv_page_size="
                        f"{self.ecfg.kv_page_size}"
                    )
                if self.ecfg.paged_kernel not in ("auto", "pallas", "xla"):
                    raise ValueError(
                        f"paged_kernel={self.ecfg.paged_kernel!r}: use "
                        "auto|pallas|xla"
                    )
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                pool_shard = NamedSharding(
                    self.mesh,
                    P(None, None, None, None if cfg.is_mla else "tp", None),
                )
                # +1: the last page is SCRATCH — every unassigned/stale page
                # table entry points there, so idle slots and end-of-request
                # overshoot rows (the decode block writes all B slots every
                # step) land in a page nobody attends instead of corrupting
                # a live request's pages.
                pool = llama.paged_cache_zeros(
                    cfg, self.ecfg.kv_pages + 1, self.ecfg.kv_page_size,
                    dtype=self.ecfg.cache_dtype(cfg.dtype),
                )
                self.cache = llama.KVCache(
                    k=jax.device_put(pool.k, pool_shard),
                    v=jax.device_put(pool.v, pool_shard),
                )
                if cfg.is_hybrid:
                    # One row a slot index, beside the pages (engine/state.py)
                    st, cv = rstate.allocate(
                        cfg, B, jnp.dtype(cfg.dtype),
                        NamedSharding(self.mesh, P()))
                    self.cache = self.cache._replace(state=st, conv=cv)
            else:
                kshard, vshard = cache_shardings(
                    self.mesh, self.plan.sp, cfg.is_mla
                )
                cache_dt = self.ecfg.cache_dtype(cfg.dtype)
                base = (cfg.cache_layers, B, S, cfg.cache_kv_heads)
                self.cache = llama.KVCache(
                    k=jax.device_put(
                        jnp.zeros(base + (cfg.cache_k_dim,), cache_dt), kshard
                    ),
                    v=jax.device_put(
                        jnp.zeros(base + (cfg.cache_v_dim,), cache_dt), vshard
                    ),
                )
        self.draft_params = None
        self.d_cache = None
        if draft_cfg is not None:
            validate_plan(draft_cfg, self.plan.tp, self.plan.ep)
            with self.mesh:
                dshard = param_shardings(draft_cfg, self.mesh)
                self.draft_params = jax.tree.map(
                    lambda a, s: jax.device_put(a, s), draft_params, dshard
                )
                dk, dv = cache_shardings(self.mesh, mla=draft_cfg.is_mla)
                dbase = (
                    draft_cfg.num_layers, B, S, draft_cfg.cache_kv_heads,
                )
                ddt = jnp.dtype(draft_cfg.dtype)
                self.d_cache = llama.KVCache(
                    k=jax.device_put(
                        jnp.zeros(dbase + (draft_cfg.cache_k_dim,), ddt), dk
                    ),
                    v=jax.device_put(
                        jnp.zeros(dbase + (draft_cfg.cache_v_dim,), ddt), dv
                    ),
                )
        # Self-draft scratch KV (ISSUE 12): a dense cache for the first-k-
        # layer prefix — sized like a draft model's cache but k layers deep.
        # Rows are resynced FROM the target cache lazily per slot
        # generation (_spec_sd_sync): the target's stored rows for the
        # first k layers are exactly what the early-exit scan would have
        # written, so admission/swap/recompute resume all share one sync
        # path instead of new admit program families.
        self.sd_cache = None
        if self._spec_mode == "self_draft":
            with self.mesh:
                sdk, sdv = cache_shardings(self.mesh, mla=cfg.is_mla)
                sdbase = (self._sd_layers, B, S, cfg.cache_kv_heads)
                sddt = jnp.dtype(cfg.dtype)
                self.sd_cache = llama.KVCache(
                    k=jax.device_put(
                        jnp.zeros(sdbase + (cfg.cache_k_dim,), sddt), sdk
                    ),
                    v=jax.device_put(
                        jnp.zeros(sdbase + (cfg.cache_v_dim,), sddt), sdv
                    ),
                )
        # Acceptance-aware per-slot scheduling state (ISSUE 12): EWMA of
        # accepted/drafted per slot drives each slot's next draft length;
        # optimistic start so fresh slots try a full window first. All
        # host-side numpy — read/written only on the loop thread.
        self.h_accept_ewma = np.ones((B,), np.float32)
        self.h_draft_len = np.zeros((B,), np.int32)
        self._spec_probe = np.zeros((B,), np.int32)
        # Prompt-lookup suffix indexes, (re)built lazily per slot
        # generation from prompt+generated (engine/speclookup.py): entry is
        # (slot_gen, SuffixIndex, tokens_fed) or None.
        self._lookup: list[Optional[tuple]] = [None] * B
        # Self-draft scratch sync generation per slot (-1 = never synced).
        self._sd_gen = [-1] * B
        # Metrics for speculative acceptance (tokens accepted / window).
        self.m_spec_rounds = 0
        self.m_spec_accepted = 0
        self.m_spec_drafted = 0
        self.m_spec_draft_len = 0.0
        # Draft-length histogram {chosen length: dispatch count} over
        # active slots (not a /metrics scalar).
        self.m_spec_dlen_hist: dict[int, int] = {}

        # Per-head (k, v) dequant scales for the SCALED fp8 paged pool
        # (ISSUE 9): None = unscaled storage (every existing byte-exact
        # swap/span/prefix invariant untouched). The [2, K] layout is what
        # ops/paged_flash + the XLA walk consume; uniform today, per-head
        # calibration slots in here.
        self._kv_scales = None
        if self.ecfg.kv_scale != 1.0:
            self._kv_scales = jnp.full(
                (2, cfg.cache_kv_heads), float(self.ecfg.kv_scale),
                jnp.float32,
            )

        # Device-resident per-slot state.
        self.counts = jnp.zeros((B, V), jnp.int32)
        self.rngs = jax.random.split(jax.random.key(self.ecfg.base_seed), B)
        self.bias = jnp.zeros((B, V), jnp.float32)
        self.d_tokens = jnp.zeros((B,), jnp.int32)
        self.d_positions = jnp.zeros((B,), jnp.int32)

        # Host-side control state.
        self.h_active = np.zeros((B,), bool)
        self.h_sampling = {
            "temperature": np.zeros((B,), np.float32),
            "top_k": np.zeros((B,), np.int32),
            "top_p": np.ones((B,), np.float32),
            "min_p": np.zeros((B,), np.float32),
            "repeat_penalty": np.ones((B,), np.float32),
            "presence_penalty": np.zeros((B,), np.float32),
            "frequency_penalty": np.zeros((B,), np.float32),
        }
        self.h_override_tok = np.zeros((B,), np.int32)
        self.h_override_mask = np.zeros((B,), bool)
        # Qwen2-VL m-rope: per-slot decode rope offset (rope position =
        # cache row + delta; models/llama.py decode_step_windowed). Only
        # threaded into block programs when the arch declares mrope.
        self._mrope = bool(getattr(cfg, "mrope_section", ()))
        self.h_rope_delta = np.zeros((B,), np.int32)
        self.slots: list[Optional[_Slot]] = [None] * B
        self._slot_gen = [0] * B
        # Requests whose slot index was handed on before their last block
        # came back (_park), by (slot index, the generation they held).
        self._parked: dict[tuple[int, int], _Slot] = {}
        self._tok_strs: Optional[list[str]] = None  # lazy grammar cache
        self.grammar_topk = self.GRAMMAR_TOPK
        # On-device grammar DFA (functions/dfa.py): per-slot automaton state
        # + one active table set (schemas repeat, so one is usually enough;
        # a second concurrent schema falls back to the host walk).
        self.h_gmask = np.zeros((B,), np.float32)  # 1 = slot DFA-constrained
        self.d_gstate = jnp.zeros((B,), jnp.int32)
        if self.plan.total > 1:
            # Commit the per-slot control state REPLICATED on the mesh.
            # Uncommitted single-device arrays leave placement to each
            # program's inference; an explicit replicated sharding keeps
            # every compiled program's input contract stable — the AOT
            # cached-admit lowering takes shardings straight from these
            # avals (ISSUE 7).
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            for name in ("counts", "rngs", "bias", "d_tokens",
                         "d_positions", "d_gstate"):
                setattr(self, name, jax.device_put(getattr(self, name), rep))
            if self._kv_scales is not None:
                # Tiny [2, K] constant: replicate; the head-sharded kernel
                # wrapper re-slices it per shard via its own in_spec.
                self._kv_scales = jax.device_put(self._kv_scales, rep)
        self._dfa: Optional[dict] = None  # {key, mask_bits, trans, tok_cls, host}
        self._dfa_building: set = set()  # schema keys compiling off-thread
        self._tok_fp: Optional[str] = None
        self.m_dfa_tokens = 0

        self._pending: deque[tuple[GenRequest, RequestHandle]] = deque()
        self._pending_lock = threading.Lock()
        self._inflight: deque[_Entry] = deque()
        self._last_admit_t = 0.0  # admission-coalescing reference (monotonic)
        # Submit-burst coalescing state (_admit_pending): last submit() time
        # and the start of the current idle-engine admission hold. BENCH_r05
        # died (rc=124) because these were read before ever being assigned —
        # the loop thread hit AttributeError on the first idle admission.
        self._last_submit_t = 0.0
        self._admit_hold_start = 0.0
        self._loop_dead: Optional[str] = None  # set by _loop_guard on crash
        # Tree-batched fork sampling (ISSUE 18, docs/TREE_SAMPLING.md).
        # _fork_logits: final-position logits stashed by the primary's
        # admission dispatch (with_logits variants) for the fork-sample
        # program — loop-thread only, consumed and cleared by
        # _fork_after_admit in the same loop step that set it.
        self._fork_logits = None
        # Mid-stream fork requests staged by Engine.fork() (any thread,
        # under _fork_lock); the loop services them at a quiesce point
        # (_service_forks). Each entry: (src_handle, [(req, handle), ...]).
        self._fork_requests: list = []
        self._fork_lock = threading.Lock()
        self.m_forks = 0               # branches admitted via slot fork
        self.m_fork_clone_fallbacks = 0  # branches degraded to clone admission
        # Peak pages simultaneously in use (pool size - free low-water):
        # the allocator-accounted probe behind fork_kv_bytes_ratio.
        self.m_kv_pages_peak = 0
        # Bounded-admission / deadline accounting (ISSUE 4). _admit_wait_ewma
        # tracks observed submit→admission latency (seconds) and feeds the
        # Retry-After hint on QueueFullError.
        self._admit_wait_ewma = 0.0
        self.m_queue_shed = 0
        self.m_queue_timeouts = 0
        self.m_deadline_expired = 0
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_q: "queue.Queue[Optional[_Entry]]" = queue.Queue()
        self._lp_warmed = False  # warmup(logprobs=True) compiled lp kv_win blocks
        self._wake = threading.Event()
        self._shutdown = threading.Event()
        self._thread: Optional[threading.Thread] = None

        # Metrics (reference: GetMetrics RPC, backend/backend.proto:39-47).
        self.m_prompt_tokens = 0
        self.m_generated_tokens = 0
        self._decode_time = 0.0
        self._decode_tokens = 0
        self._charge_last = 0.0
        self._charge_was_active = False

        self._block_cache: dict[tuple, Any] = {}
        self._admit_cache: dict[tuple, Any] = {}
        # Cached-admit programs compiling on background threads (keys), and
        # the lock guarding both structures (prefix_admit_async_compile).
        self._admit_compiling: set = set()
        self._admit_compile_lock = threading.Lock()
        # Prompt/prefix KV cache: list of dicts (most-recent-first), each
        # {"key": np.int32[n] tokens, "valid": int rows valid, "pb": bucket,
        #  "k"/"v": [L, 1, pb, K, Hd] device arrays}. Disabled alongside a
        # draft model (the draft's KV cache would miss the cached span).
        self._prefix_entries: list[dict] = []
        self._snap_cache: dict[int, Any] = {}
        self.m_prefix_hits = 0
        self.m_prefix_tokens = 0
        # Paged KV: host-side page accounting. h_ptable mirrors each slot's
        # page list (shipped to the device with every dispatch — [B, MP] i32
        # is tiny); _free_pages is the allocator.
        self._max_pages = (
            self.ecfg.max_seq // self.ecfg.kv_page_size
            if self.ecfg.kv_pages else 0
        )
        self._scratch_page = self.ecfg.kv_pages  # pool row nobody attends
        self.h_ptable = np.full(
            (B, max(self._max_pages, 1)), self._scratch_page, np.int32
        )
        self._free_pages: list[int] = list(range(self.ecfg.kv_pages))
        self._slot_pages: list[list[int]] = [[] for _ in range(B)]
        # Hierarchical page tables (ISSUE 14, ops/ptable, kv_l1_span > 0):
        # h_l1 [B, ML1] holds per-slot directories of TABLE-PAGE ids; h_l0
        # [NTP+1, SPAN] is the global table-page pool (row 0 = the all-
        # SCRATCH table page every idle directory entry points at). Table
        # pages are refcounted and shared copy-on-write across slots and
        # prefix entries exactly like the KV pages they map — _ptable_set
        # copies a shared table page before writing through it. NTP is
        # sized so claims cannot fail: every slot + every prefix entry can
        # hold a full directory, plus CoW transients.
        self._l1_span = self.ecfg.kv_l1_span if self.ecfg.kv_pages else 0
        self._hier = self._l1_span > 0
        ml1 = (-(-max(self._max_pages, 1) // self._l1_span)
               if self._hier else 0)
        self._ml1 = ml1
        # A parked tenant (_park) keeps its directory until its last block
        # is processed: at most one a row of each block in flight.
        ntp = ((B * (1 + self.ecfg.pipeline_depth)
                + max(self.ecfg.prefix_cache_entries, 0) + 2) * ml1
               if self._hier else 0)
        self._scratch_tp = 0
        self.h_l0 = np.full(
            (ntp + 1, max(self._l1_span, 1)), self._scratch_page, np.int32
        )
        self.h_l1 = np.full((B, max(ml1, 1)), self._scratch_tp, np.int32)
        self._tp_free: list[int] = list(range(1, ntp + 1))
        self._tp_refs = np.zeros((ntp + 1,), np.int32)
        self._slot_tps: list[list[int]] = [[] for _ in range(B)]
        # Cold-page spill (ISSUE 14, docs/LONG_CONTEXT.md): per-slot
        # {page column: (hk [L,1,page,K,Dk], hv)} host images of spilled
        # cold-middle pages; the matching _slot_pages entries hold the
        # SPILLED (-1) sentinel and the directory entries point at SCRATCH.
        # _spill_bytes tracks the images against kv_spill_bytes (its own
        # budget — spill pressure must not evict preempt-swap images).
        self._slot_spill: list[dict] = [{} for _ in range(B)]
        # Next directory column each slot's spill scan resumes from —
        # query positions only grow, so the scan never needs to revisit.
        self._spill_cursor = np.zeros((B,), np.int64)
        self._spill_bytes = 0
        self._spill_on = (
            self._paged and self.ecfg.attention_window > 0
            and self.ecfg.kv_spill_bytes > 0
        )
        self.m_kv_spill_bytes_out = 0
        self.m_kv_spill_bytes_in = 0
        self.m_kv_pages_spilled = 0
        self.m_kv_pages_restored = 0
        self.m_kv_spill_skips = 0
        # Chunked ragged prefill state (EngineConfig.prefill_chunk): each
        # in-progress chunked admission holds a reserved slot (inactive —
        # decode blocks skip it) and, under the paged pool, its page table
        # ROW kept OFF h_ptable until the final chunk activates the slot, so
        # interleaved decode-block writes for the idle slot keep resolving
        # through SCRATCH instead of corrupting freshly-prefilled pages.
        self._chunkings: list[dict] = []
        self.m_prefill_chunks = 0
        self.m_chunked_admits = 0
        # Page refcounts: a page may be referenced by its owning slot AND by
        # prefix-cache entries (copy-on-write sharing — spans live in pool
        # pages mapped read-only into later admissions' tables). A page
        # returns to the free list only at refcount 0.
        self._page_refs = np.zeros((max(self.ecfg.kv_pages, 1),), np.int32)
        # On-demand growth + preemption + host swap tier (ISSUE 3).
        # _growth_blocked: a decode-block dispatch could not grow some
        # slot's table — new admissions pause and, once the in-flight queue
        # drains, the youngest slot is preempted. _prefix_host is the
        # second (host-RAM) level of the prefix cache: spans evicted for
        # pool pressure spill here (bounded by kv_swap_bytes, shared with
        # preempt-swap images tracked in _host_bytes) and swap back into
        # pool pages on a hit instead of being re-prefilled.
        self._growth_blocked = False
        self._prefix_host: list[dict] = []
        self._host_bytes = 0
        self.m_kv_pages_grown = 0
        self.m_kv_preemptions = 0
        self.m_kv_preempt_swaps = 0
        self.m_kv_preempt_recomputes = 0
        self.m_kv_swap_bytes_out = 0
        self.m_kv_swap_bytes_in = 0
        self.m_kv_preempt_recover_ms = 0.0
        self.m_prefix_host_hits = 0
        self.m_peak_active = 0
        # Cluster KV-span transfer (ISSUE 6, docs/CLUSTER.md): spans framed
        # by cluster/transfer.py arrive from a prefill-role replica via
        # import_span_bytes() on ARBITRARY threads; they stage here and the
        # loop thread merges them into _prefix_host (the host tier already
        # serves hits from RAM — an imported span is indistinguishable from
        # a locally-spilled one). Each staged tuple carries a done-Event the
        # importer waits on, so a handoff is visible to the very next
        # admission.
        self._span_inbox: list[tuple[dict, threading.Event]] = []
        self._span_inbox_lock = threading.Lock()
        # Host-tier byte accounting is mutated from the loop (make-room,
        # preempt swap, promote/spill) AND from caller threads (stop /
        # cancel_all discarding queued resumes) — every read-modify-write
        # of _host_bytes holds this leaf lock so no update is lost.
        self._host_lock = threading.Lock()
        self.m_span_exports = 0
        self.m_span_imports = 0
        self.m_span_import_rejects = 0
        # Multi-tenant LoRA serving (ISSUE 10, docs/LORA_SERVING.md).
        # _adapter_registry (name -> {dir, weight}) is the only structure
        # touched off the loop thread (register_adapter / submit) and is
        # guarded by _adapter_lock. Everything else — the host-RAM factor-
        # image LRU (_adapter_host, bounded by adapter_cache_bytes), the
        # device row table (_adapter_rows / _adapter_refs / _adapter_last)
        # and the stacked factor tree (_lora_tree: {key: {"a": [L, NA, in,
        # R], "b": [L, NA, R, out]}}, row 0 = the all-zero null adapter) —
        # is loop-thread-only, like the page allocator. A device row's
        # refcount counts the ACTIVE slots decoding through it; eviction of
        # a row with refs > 0 is forbidden (allocator-primitive discipline,
        # _adapter_acquire/_adapter_unpin only), so a tenant's factors can
        # never be swapped out from under a mid-flight request.
        self._adapter_lock = threading.Lock()
        self._adapter_registry: dict[str, dict] = {}
        self._adapter_host: "OrderedDict[str, dict]" = OrderedDict()
        self._adapter_host_bytes = 0
        self._adapter_rows: list[Optional[str]] = []
        self._adapter_refs = np.zeros((0,), np.int32)
        self._adapter_last: list[float] = []
        self._lora_tree: Optional[dict] = None
        self._lora_keys: tuple = ()
        self._lora_rank = 0
        self.h_adapter = np.zeros((B,), np.int32)
        self.m_adapter_fetches = 0
        self.m_adapter_promotes = 0
        self.m_adapter_evictions = 0
        # Request-lifecycle observability (ISSUE 11, docs/OBSERVABILITY.md):
        # the loop-owned event journal (None = disabled), a submit-side id
        # counter for requests that carry no caller request_id, and the
        # path of the last flight-recorder dump
        # (surfaced via the loop_dead gauge labels + manager log).
        self._journal = (
            EventJournal(self.ecfg.trace_journal_events)
            if self.ecfg.trace_journal_events > 0 else None
        )
        self._postmortem_path = ""
        if cfg.is_hybrid and self.ecfg.prefix_cache_entries > 0:
            self._jstage("prefix_reuse_off",
                         a=float(self.ecfg.prefix_cache_entries))
        if cfg.recurrent_kind == "swa":
            # The two lengths of this model's K/V, once: the window layers'
            # rows a slot (and all slots' bytes), the full layers' pages.
            self._jstage("window_state",
                         a=float(cfg.ring_rows),
                         b=float(self._slot_state_bytes()))
        if cfg.recurrent_kind == "s6":
            # The same for a slot's [N, E] matrix a layer and its row over
            # all S6 layers, beside the few attention layers' one K/V head.
            self._jstage("s6_state",
                         a=float(cfg.mamba_d_state * cfg.mamba_d_inner),
                         b=float(rstate.row_bytes(cfg, self.cache.conv.dtype)))
        if cfg.recurrent_kind in ("swa", "s6"):
            self._jstage("kv_pool", a=float(self.ecfg.kv_pages), b=float(
                (self.ecfg.kv_pages + 1) * self._page_bytes()))
        # Pipelined loop runtime (ISSUE 17, docs/ENGINE_RUNTIME.md).
        # thread: single-writer engine-loop — per-iteration host-phase
        # accumulator feeding the coalesced loop_iter journal emission.
        self._phases = LoopPhases()
        # thread: single-writer engine-loop — the control stager's cache
        # and counters are loop-thread state; bench/tests read the
        # counters best-effort after generation settles.
        self._ctrl = ControlStager(call=self._phases.call)
        # thread: single-writer engine-loop — how far this loop has read
        # gcwatch's ring of collector pauses (journalled as gc_pause).
        self._gc_seen = 0
        self.m_loop_call_ms = 0.0
        self.m_loop_gc_ms = 0.0
        self.m_loop_off_ms = 0.0
        # Deadline min-heap: submit-side threads push (internally locked),
        # the loop's housekeeping gate peeks — O(1) "anything due?" instead
        # of scanning every pending request every iteration.
        self._deadlines = DeadlineIndex()
        # thread: single-writer engine-loop — the prepare-ahead staging
        # slot: the NEXT block's control plan, built while the loop waits
        # on an in-flight block, consumed (or discarded as stale) by the
        # next dispatch. _ctrl_epoch stamps plan validity: every mutation
        # of plan inputs (slot claim/teardown, activation, grammar
        # override) bumps it via _plan_dirty and orphans the staged plan.
        self._staged_plan = None
        self._ctrl_epoch = 0
        # thread: single-writer engine-loop — housekeeping-tick clock and
        # deferred admission-time prefix-span saves [(slot, ids, rows,
        # gen)], flushed on ticks and before the owning slot finishes.
        self._hk_last = 0.0
        self._deferred_saves: list[tuple] = []
        self.m_loop_host_ms = 0.0
        self.m_loop_blocked_ms = 0.0  # the `pull` phase: blocked on the device
        self.m_loop_blocks = 0
        # Decode rows (steps x compiled batch rows), see _count_rows.
        self.m_rows_dispatched = 0
        self.m_rows_posted = 0
        self.m_rows_overshoot = 0
        self.m_rows_empty = 0
        # Requests finished, and of those the ones whose slot index had
        # been handed on before their `done` was posted (_park).
        self.m_slots_released = 0
        self.m_slots_released_early = 0
        # Decode-block routing of a MoE model, see _count_routing.
        self.m_moe_slots = 0
        self.m_moe_picks = 0  # under an expert share: the router's picks,
        self.m_moe_picks_here = 0  # and those of an expert held here
        # and the same of the wide admission programs (_count_admit_routing)
        self.m_moe_admit_rows = 0
        self.m_moe_admit_rows_held = 0
        self.m_state_restores = 0  # recurrent-state rows recomputed (preempt)
        self.m_window_rows_read = 0  # rows a window layer's reader walked
        self.m_window_rows_full = 0  # ... and would have at full length
        self.m_admit_splits = 0  # admission groups cut by state.admit_rows
        # Admission programs dispatched (full, cached tail, chunk), the rows
        # they were compiled for and the prompt tokens in them (_count_admit).
        self.m_admit_programs = 0
        self.m_admit_rows_dispatched = 0
        self.m_admit_rows_prompt = 0
        self.m_moe_slots_hit = 0
        self.m_moe_rows_busiest = 0
        self.m_moe_rows_mean = 0.0
        self._build_programs()

    # ------------------------------------------------------------------ #
    # Lifecycle journal / tracing (ISSUE 11)
    # ------------------------------------------------------------------ #

    @property
    def journal(self) -> Optional[EventJournal]:
        """The engine's event journal (None when trace_journal_events=0);
        /debug/timeline renders it as a Perfetto-loadable trace."""
        return self._journal

    @property
    def postmortem_path(self) -> str:
        """Path of the flight-recorder dump written when the loop died
        ("" while alive) — rides the loop_dead gauge labels."""
        return self._postmortem_path

    def _jnote(self, event: str, rid: str = "", slot: int = -1,
               a: float = 0.0, b: float = 0.0) -> None:
        """Loop-thread journal append (lock-free; no-op when disabled)."""
        j = self._journal
        if j is not None:
            j.append(event, rid=rid, slot=slot, a=a, b=b)

    def _upload(self, host, dtype=None):
        """`jnp.asarray(host)` through the loop's door (LoopPhases.call):
        the time the loop thread sits in a transfer is the call's, not
        Python's own. From another thread it is the span alone."""
        with self._phases.call("call/upload",
                               bytes=getattr(host, "nbytes", 0)):
            return jnp.asarray(host, dtype)

    def _host_copy_async(self, arr: Any) -> None:
        """Start a device→host copy without blocking; np.asarray later is
        then a cheap wait instead of a full round trip."""
        with self._phases.call("call/host_copy"):
            arr.copy_to_host_async()

    def _count_admit(self, rows: int, tokens: int) -> None:
        """One admission program went out: `rows` it was compiled for (group
        size x bucket; a chunk's or a cached tail's own rows), `tokens` of a
        prompt in them. The rest is padding the device computes all the same."""
        self.m_admit_programs += 1
        self.m_admit_rows_dispatched += rows
        self.m_admit_rows_prompt += tokens
        self._phases.note(1, rows)  # what this stretch did (a loop_stall's)
        self._jnote("admit_rows", a=float(rows), b=float(tokens))

    def _jstage(self, event: str, rid: str = "", slot: int = -1,
                a: float = 0.0, b: float = 0.0) -> None:
        """Cross-thread journal emit (submit / span export): staged into
        the journal's sidecar, drained by the loop thread in order."""
        j = self._journal
        if j is not None:
            j.stage(event, rid=rid, slot=slot, a=a, b=b)

    def _jnote_fault(self, e: BaseException) -> None:
        """Journal an injected fault under its per-site event type
        (fault_<site> — cross-checked against faults.SITES by the
        journal-events lint pass). Real failures journal as "error"."""
        if not isinstance(e, faults.InjectedFault):
            return
        msg = str(e)
        for site in faults.SITES:
            if f"at {site} " in msg:
                self._jnote("fault_" + site)
                return

    def _write_postmortem(self, reason: str, live: list,
                          pending_rids: list) -> str:
        """Flight-recorder dump (loop death): journal tail + engine state
        snapshot → one JSON file. Runs on the dying loop thread, after the
        terminal events posted and the allocator was released."""
        j = self._journal
        payload = {
            "reason": reason,
            "engine": self.cfg.name,
            "wall_time": time.time(),
            "slots": [
                {"slot": i, "rid": rid, "generated": gen, "prompt_len": plen}
                for i, rid, gen, plen in live
            ],
            "pending": list(pending_rids),
            "pending_depth": len(pending_rids),
            "pool": {
                "kv_pages": int(self.ecfg.kv_pages),
                "free_pages": len(self._free_pages),
                "host_tier_bytes": int(self._host_bytes),
                "spilled_pages": int(sum(len(d) for d in self._slot_spill)),
                "spill_bytes": int(self._spill_bytes),
                "prefix_entries": len(self._prefix_entries),
                "prefix_host_entries": len(self._prefix_host),
            },
            "config": {
                "max_slots": self.ecfg.max_slots,
                "max_seq": self.ecfg.max_seq,
                "kv_page_size": self.ecfg.kv_page_size,
                "prefill_chunk": self.ecfg.prefill_chunk,
                "tensor_parallel": self.plan.tp,
            },
            "journal": j.snapshot(last=512) if j is not None else [],
        }
        return opostmortem.write(
            self.ecfg.postmortem_dir, self.cfg.name, payload
        )

    @property
    def _paged(self) -> bool:
        return self.ecfg.kv_pages > 0

    # ------------------------------------------------------------------ #
    # Hierarchical page tables (ISSUE 14, ops/ptable — kv_l1_span > 0)
    #
    # The flat h_ptable row is replaced by a per-slot L1 directory of
    # refcounted TABLE PAGES (h_l1 → h_l0 rows of kv_l1_span page ids).
    # Directories share table pages copy-on-write with prefix entries and
    # other slots: mapping a 500k-token span costs ML1 addrefs, not 4k
    # int writes, and the device ships a 64-entry row instead of a 4k one.
    # The KV-page allocator itself (claim/addref/release, _free_pages,
    # _page_refs) is untouched — these helpers only maintain the mapping.
    # ------------------------------------------------------------------ #

    def _tp_claim(self) -> int:
        """Claim a fresh table page (refcount 1, all-SCRATCH content)."""
        if not self._tp_free:
            # Sized so this cannot happen (see __init__); heal like the
            # page allocator's clamp paths rather than corrupting state.
            if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                raise AssertionError("table-page pool exhausted")
            grow = max(self._ml1, 1)
            base = self.h_l0.shape[0]
            self.h_l0 = np.concatenate([
                self.h_l0,
                np.full((grow, self.h_l0.shape[1]), self._scratch_page,
                        np.int32),
            ])
            self._tp_refs = np.concatenate([
                self._tp_refs, np.zeros((grow,), np.int32)
            ])
            self._tp_free.extend(range(base, base + grow))
            log.error("table-page pool exhausted — grew by %d", grow)
        tp = self._tp_free.pop()
        self._tp_refs[tp] = 1
        self.h_l0[tp, :] = self._scratch_page
        return tp

    def _tp_release(self, tps: list[int]) -> None:
        for tp in tps:
            if tp == self._scratch_tp:
                continue
            if self._tp_refs[tp] <= 0:
                if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                    raise AssertionError(f"double release of table page {tp}")
                log.error("double release of table page %d ignored", tp)
                self._tp_refs[tp] = 0
                continue
            self._tp_refs[tp] -= 1
            if self._tp_refs[tp] == 0:
                self._tp_free.append(tp)

    # thread: engine-loop-only
    def _ptable_set(self, slot_idx: int, pos: int, page_id: int) -> None:
        """Write one directory entry (hier mode): point slot column `pos`
        at `page_id`, copy-on-writing the backing table page if shared.
        Declared loop-only: the hierarchical table's COW bookkeeping has no
        lock — a second mutator thread would corrupt refcounts."""
        span = self._l1_span
        c, o = divmod(pos, span)
        tps = self._slot_tps[slot_idx]
        while len(tps) <= c:
            tp_new = self._tp_claim()
            tps.append(tp_new)
            self.h_l1[slot_idx, len(tps) - 1] = tp_new
        tp = tps[c]
        if self._tp_refs[tp] > 1:
            # Shared with a prefix entry / another slot — copy before write.
            tp_new = self._tp_claim()
            self.h_l0[tp_new, :] = self.h_l0[tp]
            self._tp_release([tp])
            tps[c] = tp_new
            self.h_l1[slot_idx, c] = tp_new
            tp = tp_new
        self.h_l0[tp, o] = page_id

    def _ptable_build_slot(self, slot_idx: int, pages: list[int],
                           shared_tps: Optional[list[int]] = None,
                           n_shared: int = 0) -> np.ndarray:
        """Build a slot's L1 directory for `pages` (hier mode). Full
        SPAN-chunks of the leading `n_shared` shared pages reuse the donor
        entry's table pages (addref — the CoW path); everything else writes
        into freshly-claimed private table pages. Returns the slot's L1
        row (the device-shippable analogue of the flat h_ptable row)."""
        span = self._l1_span
        tps = self._slot_tps[slot_idx]
        assert not tps, f"slot {slot_idx} already holds a directory"
        self.h_l1[slot_idx, :] = self._scratch_tp
        start = 0
        if shared_tps and n_shared:
            full = min(n_shared // span, len(shared_tps))
            for c in range(full):
                tp = shared_tps[c]
                self._tp_refs[tp] += 1
                tps.append(tp)
                self.h_l1[slot_idx, c] = tp
            start = full * span
        for pos in range(start, len(pages)):
            self._ptable_set(slot_idx, pos, pages[pos])
        return self.h_l1[slot_idx].copy()

    def _ptable_free_slot(self, slot_idx: int) -> None:
        self._tp_release(self._slot_tps[slot_idx])
        self._slot_tps[slot_idx] = []
        self.h_l1[slot_idx, :] = self._scratch_tp

    def _entry_tps_for_pages(self, pages: list[int]) -> list[int]:
        """Fresh table pages mapping an ENTRY's page list (hier mode) —
        the host-tier promote path, where the pages belong to no slot."""
        span = self._l1_span
        tps = []
        for c in range(-(-len(pages) // span)):
            tp = self._tp_claim()
            chunkp = pages[c * span: (c + 1) * span]
            self.h_l0[tp, : len(chunkp)] = chunkp
            tps.append(tp)
        return tps

    def _entry_tps(self, slot_idx: int, n_pages: int,
                   parked: Optional[_Parked] = None) -> list[int]:
        """Addref'd table pages covering a prefix entry's n_pages leading
        pages (hier mode) — the directory half of copy-on-write span
        sharing. The entry keeps these rows byte-stable: any later slot
        write through a shared table page copies it first (_ptable_set).
        The directory is the slot's, or a parked tenant's own."""
        span = self._l1_span
        n_tp = -(-n_pages // span)
        tps = (parked.tps if parked else self._slot_tps[slot_idx])[:n_tp]
        for tp in tps:
            self._tp_refs[tp] += 1
        return list(tps)

    def _ptable_device(self):
        """The device ptable operand for batched programs: the flat
        [B, MP] row table, or the hierarchical (l1, l0) pair.

        Stager-backed (ISSUE 17): the table barely changes between decode
        blocks (steady decode grows one slot's row occasionally), so the
        dirty-diff cache skips the upload entirely on a byte match and
        ships only the changed rows otherwise. Sound because no block/spec
        program donates its ptable operand."""
        if self._hier:
            return (self._ctrl.commit("ptable_l1", self.h_l1),
                    self._ctrl.commit("ptable_l0", self.h_l0))
        return self._ctrl.commit("ptable", self.h_ptable)

    def _ptable_device_row(self, row: np.ndarray):
        """One slot's table operand from its host row (flat [MP] or hier
        L1 [ML1] — the l0 pool rides along CURRENT, so directory-content
        updates between dispatches are visible)."""
        # Copies, as in _ptable_device: `row` may be a view of the host
        # table, which is rewritten while this dispatch is in flight.
        if self._hier:
            return (self._upload(row.copy()), self._upload(self.h_l0.copy()))
        return self._upload(row.copy())

    def _pages_worst(self, request: GenRequest) -> int:
        """Worst-case pages for a request: the prefill writes a full bucket
        of rows (padding included), and decode may extend to prompt+max_new.
        Used only as the can-this-EVER-be-served gate (submit) and as the
        on-demand headroom cap — admission no longer reserves this."""
        plen = len(request.prompt_ids)
        rows = max(self._bucket_for(plen),
                   min(plen + request.max_new_tokens, self.ecfg.max_seq))
        return -(-rows // self.ecfg.kv_page_size)

    def _pages_needed(self, request: GenRequest) -> int:
        """On-demand admission need (ISSUE 3): pages covering the prompt's
        prefill bucket (the prefill writes the whole bucket, padding
        included) plus kv_page_headroom for the first decode blocks —
        decode growth allocates the rest as the context actually crosses
        page boundaries. Headroom never pushes past the worst case."""
        page = self.ecfg.kv_page_size
        base = -(-self._bucket_for(len(request.prompt_ids)) // page)
        cap = max(base, self._pages_worst(request))
        return min(base + self.ecfg.kv_page_headroom, cap)

    def _pages_needed_cached(self, request: GenRequest, match_len: int,
                             host: bool = False) -> int:
        """Fresh pages for a prefix-hit admission: device-tier spans are
        shared (zero cost) and only the tail bucket + headroom allocate;
        host-tier spans (spilled to RAM) must swap back into fresh pages,
        so the span pages count too."""
        page = self.ecfg.kv_page_size
        plen = len(request.prompt_ids)
        shared = 0 if host else match_len // page
        rows = match_len + self._bucket_for(plen - match_len)
        base = -(-rows // page) - shared
        worst = max(rows, min(plen + request.max_new_tokens, self.ecfg.max_seq))
        cap = max(base, -(-worst // page) - shared)
        return min(base + self.ecfg.kv_page_headroom, cap)

    def _pages_alloc(self, slot_idx: int, n: int,
                     shared: Optional[list[int]] = None,
                     shared_tps: Optional[list[int]] = None,
                     ) -> Optional[np.ndarray]:
        """Build a slot's page table: `shared` read-only prefix pages (a
        prefix-cache span — refcounted, never written by this slot because
        all its writes land at rows past the shared span) followed by `n`
        freshly-allocated pages. Under hierarchical tables, `shared_tps`
        (the donor entry's table pages) lets full directory chunks of the
        shared span map by addref instead of rewrite. A slot that already
        holds a table is a caller bug — overwriting it would leak its
        pages' refcounts into the pool forever, so the stale table is
        released first (and raised under LOCALAI_ALLOC_DEBUG=1 / the test
        suite). Returns the slot's device-shippable table row (flat [MP] or
        hier L1 [ML1]), or None on pool pressure (no mutation)."""
        # Injected allocator failure fires BEFORE any mutation so pool
        # accounting stays exact across the fault (testing/faults).
        faults.fire("page_alloc")
        if self._slot_pages[slot_idx]:
            if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                raise AssertionError(
                    f"_pages_alloc: slot {slot_idx} already holds "
                    f"{len(self._slot_pages[slot_idx])} pages"
                )
            log.error(
                "_pages_alloc: slot %d already held a table (%d pages) — "
                "releasing it to avoid a pool leak", slot_idx,
                len(self._slot_pages[slot_idx]),
            )
            self._pages_free(slot_idx)
        fresh = self._pages_claim(n)
        if fresh is None:
            return None
        shared = shared or []
        self._pages_addref(shared)
        pages = shared + fresh
        self._slot_pages[slot_idx] = pages
        if self._hier:
            return self._ptable_build_slot(
                slot_idx, pages, shared_tps=shared_tps,
                n_shared=len(shared),
            )
        # Unused tail entries point at SCRATCH so any row past the slot's
        # reservation (end-of-request block overshoot) lands harmlessly.
        row = np.full((self._max_pages,), self._scratch_page, np.int32)
        row[: len(pages)] = pages
        self.h_ptable[slot_idx] = row
        return row

    def _pages_claim(self, n: int) -> Optional[list[int]]:
        """Allocator primitive: pop `n` fresh pages from the free list, each
        with refcount 1, or None (no mutation) when the pool cannot cover
        it. Every fresh-page booking flows through here — the paired
        primitive for sharing is _pages_addref — so the randomized
        invariant walk (tests/test_paged_kv.py) and the page-refcount lint
        pass see every reference the pool hands out."""
        if n < 0 or len(self._free_pages) < n:
            return None
        fresh = [self._free_pages.pop() for _ in range(n)]
        for p in fresh:
            self._page_refs[p] = 1
        used = self.ecfg.kv_pages - len(self._free_pages)
        if used > self.m_kv_pages_peak:
            self.m_kv_pages_peak = used
        return fresh

    def _pages_addref(self, pages: list[int]) -> None:
        """Allocator primitive: take one extra reference on already-
        allocated pages (prefix-span copy-on-write sharing). Referencing a
        FREE page would let it alias the next claim — clamp-and-heal like
        _pages_release (raise under LOCALAI_ALLOC_DEBUG=1 / the tests)."""
        for p in pages:
            if self._page_refs[p] <= 0:
                if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                    raise AssertionError(f"addref of free page {p}")
                log.error("addref of free page %d — reclaiming it", p)
                try:
                    self._free_pages.remove(p)
                except ValueError:
                    pass
                self._page_refs[p] = 1
                continue
            self._page_refs[p] += 1

    def _pages_release(self, pages: list[int]) -> None:
        for p in pages:
            if p < 0:
                continue  # SPILLED sentinel — the image owns no device page
            if self._page_refs[p] <= 0:
                # Double release: the page is already free (or never
                # allocated). Appending it to the free list AGAIN would let
                # two slots pop the same page — clamp and flag instead.
                if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                    raise AssertionError(f"double release of page {p}")
                log.error("double release of page %d ignored", p)
                self._page_refs[p] = 0
                continue
            self._page_refs[p] -= 1
            if self._page_refs[p] == 0:
                self._free_pages.append(p)

    def _page_bytes(self) -> int:
        """Host/device bytes of one page's K+V rows across all layers that
        hold pages (a hybrid model's cache layers: a window model's full
        layers alone)."""
        return self._prefix_span_bytes(self.ecfg.kv_page_size)

    def _slot_state_bytes(self) -> int:
        """Bytes of a hybrid model's per-slot state over all slots (the
        recurrent rows, or the window layers' rings): fixed, whatever the
        contexts (engine/state.py)."""
        return self.ecfg.max_slots * rstate.row_bytes(
            self.cfg, self.cache.conv.dtype)

    def _pages_grow_slot(self, slot_idx: int, need_pages: int) -> bool:
        """Extend a live slot's table to `need_pages` total pages — a HOST
        array write (h_ptable ships with every dispatch), no recompile, no
        device traffic. Evicts prefix-cache spans (spilling them to the
        host tier) before reporting failure."""
        need_pages = min(need_pages, self._max_pages)
        have = len(self._slot_pages[slot_idx])
        grow = need_pages - have
        if grow <= 0:
            return True
        if len(self._free_pages) < grow:
            self._prefix_evict_for_pages(grow)
        fresh = self._pages_claim(grow)
        if fresh is None:
            return False
        self._slot_pages[slot_idx].extend(fresh)
        if self._hier:
            for off, p in enumerate(fresh):
                self._ptable_set(slot_idx, have + off, p)
        else:
            self.h_ptable[slot_idx, have:need_pages] = fresh
        self.m_kv_pages_grown += grow
        return True

    def _grow_for_decode(self, steps: int) -> bool:
        """Grow every active slot's table to cover the next `steps` decode
        rows before a block is dispatched — rows written past a slot's last
        allocated page would otherwise resolve through the SCRATCH tail and
        be silently lost. Returns False (dispatch must not proceed) when
        some slot cannot be grown; the loop then drains in-flight work and
        preempts the youngest slot."""
        if not self._paged:
            return True
        page = self.ecfg.kv_page_size
        for i in range(self.ecfg.max_slots):
            s = self.slots[i]
            if s is None or not self.h_active[i]:
                continue
            rows = min(s.sched_rows + steps, self.ecfg.max_seq)
            if not self._pages_grow_slot(i, -(-rows // page)):
                self._growth_blocked = True
                return False
        self._growth_blocked = False
        return True

    def _pages_free(self, slot_idx: int) -> None:
        self._pages_release(self._slot_pages[slot_idx])
        self._slot_pages[slot_idx] = []
        if self._slot_spill[slot_idx]:
            # Spilled cold-page images die with the slot (their device
            # pages were already returned at spill time).
            self._spill_bytes -= (
                len(self._slot_spill[slot_idx]) * self._page_bytes()
            )
            self._slot_spill[slot_idx] = {}
        self._spill_cursor[slot_idx] = 0
        # The slot stays in every decode block's scatter until re-admitted —
        # its stale table must not alias pages handed to the next request.
        if self._hier:
            self._ptable_free_slot(slot_idx)
        else:
            self.h_ptable[slot_idx] = self._scratch_page

    # ------------------------------------------------------------------ #
    # Cold-page spill for live slots (ISSUE 14, docs/LONG_CONTEXT.md)
    #
    # With windowed+sink decode active, a page whose LAST row sits further
    # than attention_window behind every live query (and past the sink)
    # can never be attended again — query positions only grow. Its bytes
    # move to host RAM (bounded by kv_spill_bytes), the device page
    # returns to the pool, and the directory entry points at SCRATCH; any
    # in-flight dispatch that still lists the old page id reads rows its
    # mask zeroes, so recycling under the pipeline is exact. Shared (CoW
    # span) pages never spill — other slots read them hot. Restoration is
    # byte-exact: prefix save swaps the images back into fresh pages;
    # preempt-swap splices them into the swap image host-side.
    # ------------------------------------------------------------------ #

    _SPILL_MAX_PER_TICK = 64  # pages per loop iteration — bounds the D2H
    # gather so spilling a 512k slot amortizes over iterations instead of
    # stalling dispatch for one giant copy

    def _spill_cold_pages(self) -> None:
        """Loop-thread tick: move cold middle pages of live/chunking slots
        to the host tier. Any failure (injected page_spill/host_swap fault,
        allocator oddity) skips that slot's batch — it simply stays hot
        (exact attention), never a hung caller."""
        if not self._spill_on:
            return
        page = self.ecfg.kv_page_size
        swin = self.cfg.attention_window
        sink_cols = (-(-self.cfg.attention_sink // page)
                     if self.cfg.attention_sink else 0)
        # Conservative margin: in-flight chunk queries sit up to one chunk
        # behind st["offset"], in-flight decode queries up to one block
        # behind the processed count.
        margin = self.ecfg.prefill_chunk + max(self.ecfg.block_sizes)
        pb = self._page_bytes()
        by_slot = {st["slot"]: st for st in self._chunkings}
        done = 0
        for i in range(self.ecfg.max_slots):
            if done >= self._SPILL_MAX_PER_TICK:
                return
            st = by_slot.get(i)
            if st is not None:
                floor = st["offset"]
            elif self.h_active[i] and self.slots[i] is not None:
                s = self.slots[i]
                floor = s.prompt_len + len(s.generated)
            else:
                continue
            pages = self._slot_pages[i]
            cand: list[int] = []
            c = max(int(self._spill_cursor[i]), sink_cols)
            while (c < len(pages)
                   and (c + 1) * page <= floor - swin - margin
                   and done + len(cand) < self._SPILL_MAX_PER_TICK):
                p = pages[c]
                if p < 0:
                    self._spill_cursor[i] = c + 1  # already spilled
                elif self._page_refs[p] > 1:
                    # Shared with a prefix span / another slot — hot on
                    # purpose; releasing our ref would save no memory.
                    self.m_kv_spill_skips += 1
                    self._spill_cursor[i] = c + 1
                elif (self._spill_bytes + (len(cand) + 1) * pb
                      > self.ecfg.kv_spill_bytes):
                    break  # budget full — retry once images are freed
                else:
                    cand.append(c)
                c += 1
            if not cand:
                continue
            try:
                faults.fire("page_spill")
                hk, hv = self._swap_out_pages([pages[c] for c in cand])
            except Exception as e:  # noqa: BLE001 — degrade to exact/hot
                self._jnote_fault(e)
                if not isinstance(e, faults.InjectedFault):
                    log.exception("cold-page spill failed (slot %d)", i)
                self.m_kv_spill_skips += len(cand)
                # Cursor moves past the batch: these pages stay hot for
                # the slot's lifetime (exact attention fallback).
                self._spill_cursor[i] = cand[-1] + 1
                continue
            span = self._l1_span
            spilled = 0
            for j, c in enumerate(cand):
                if (self._hier and st is not None
                        and self._tp_refs[self._slot_tps[i][c // span]] > 1):
                    # Chunking slots ship a SAVED L1 row per dispatch — a
                    # CoW would orphan it, and writing a SHARED table page
                    # in place would corrupt the donor entry. Shared table
                    # pages during chunking only back shared KV pages
                    # (skipped above), so this is belt and braces: leave
                    # the page hot.
                    self.m_kv_spill_skips += 1
                    self._spill_cursor[i] = c + 1
                    continue
                self._slot_spill[i][c] = (
                    np.ascontiguousarray(hk[:, j: j + 1]),
                    np.ascontiguousarray(hv[:, j: j + 1]),
                )
                self._pages_release([pages[c]])
                pages[c] = -1
                if self._hier:
                    self._ptable_set(i, c, self._scratch_page)
                elif st is not None:
                    st["table_row"][c] = self._scratch_page
                else:
                    self.h_ptable[i, c] = self._scratch_page
                self._spill_cursor[i] = c + 1
                spilled += 1
            if not spilled:
                continue
            nbytes = spilled * pb
            self._spill_bytes += nbytes
            self.m_kv_pages_spilled += spilled
            self.m_kv_spill_bytes_out += nbytes
            done += spilled
            self._jnote("page_spill", slot=i, a=float(spilled),
                        b=float(nbytes))

    def _restore_spilled(self, slot_idx: int) -> bool:
        """Swap a slot's spilled cold pages back into fresh pool pages —
        byte-exact re-admission to full residency (prefix save needs every
        page hot before it can pin the span). Returns False when the pool
        cannot cover it right now (callers degrade: the span is not
        saved)."""
        images = self._slot_spill[slot_idx]
        if not images:
            return True
        faults.fire("page_spill")
        need = len(images)
        if len(self._free_pages) < need:
            self._prefix_evict_for_pages(need)
        fresh = self._pages_claim(need)
        if fresh is None:
            return False
        cols = sorted(images)
        hk = np.concatenate([images[c][0] for c in cols], axis=1)
        hv = np.concatenate([images[c][1] for c in cols], axis=1)
        self._swap_in_pages(fresh, hk, hv)
        pages = self._slot_pages[slot_idx]
        st = next((s for s in self._chunkings if s["slot"] == slot_idx),
                  None)
        for p, c in zip(fresh, cols):
            pages[c] = p
            if self._hier:
                self._ptable_set(slot_idx, c, p)
            elif st is not None:
                st["table_row"][c] = p
            else:
                self.h_ptable[slot_idx, c] = p
        nbytes = need * self._page_bytes()
        self._spill_bytes -= nbytes
        self._slot_spill[slot_idx] = {}
        self._spill_cursor[slot_idx] = 0
        self.m_kv_pages_restored += need
        self.m_kv_spill_bytes_in += nbytes
        self._jnote("page_restore", slot=slot_idx, a=float(need),
                    b=float(nbytes))
        return True

    def _swap_out_slot_span(self, slot_idx: int,
                            n_live: int) -> tuple[np.ndarray, np.ndarray]:
        """A preempt-swap image of the slot's first n_live pages with any
        spilled cold pages spliced in from their host images — byte-exact
        without re-admitting them to the device first."""
        pages = self._slot_pages[slot_idx][:n_live]
        images = self._slot_spill[slot_idx]
        hot = [(j, p) for j, p in enumerate(pages) if p >= 0]
        if all(p >= 0 for p in pages):
            return self._swap_out_pages(pages)
        hk_hot, hv_hot = (self._swap_out_pages([p for _, p in hot])
                          if hot else (None, None))
        sample_k, sample_v = next(iter(images.values()))
        if hk_hot is not None:
            sample_k, sample_v = hk_hot, hv_hot
        hk = np.zeros((sample_k.shape[0], n_live) + sample_k.shape[2:],
                      sample_k.dtype)
        hv = np.zeros((sample_v.shape[0], n_live) + sample_v.shape[2:],
                      sample_v.dtype)
        for idx, (j, _p) in enumerate(hot):
            hk[:, j] = hk_hot[:, idx]
            hv[:, j] = hv_hot[:, idx]
        for c, (ik, iv) in images.items():
            if c < n_live:
                hk[:, c] = ik[:, 0]
                hv[:, c] = iv[:, 0]
        return hk, hv

    # ------------------------------------------------------------------ #
    # Preemption + host-RAM swap tier (ISSUE 3)
    #
    # When on-demand growth finds the pool empty (after spilling prefix
    # spans), the loop drains all in-flight dispatches — every decode block
    # writes EVERY slot's pages through the table it shipped, so a victim's
    # pages cannot be recycled under an in-flight write — and preempts the
    # youngest non-grammar slot. `swap` copies the victim's live pages to
    # the bounded host tier and restores them (plus the slot's device rows,
    # RNG chain included) on re-admission — byte-exact resume with no
    # re-prefill. `recompute` re-admits prompt+generated through the
    # ordinary (chunked) prefill path — byte-exact for greedy, RNG-chain-
    # preserving otherwise. Either way the original stream continues: the
    # resumed slot keeps its accumulated generated tokens and emitted text.
    # ------------------------------------------------------------------ #

    def _pow2_pages(self, n: int) -> int:
        """Page-count bucket for the swap gather/scatter programs (compile
        once per power of two, pad with SCRATCH/zeros)."""
        b = 1
        while b < n:
            b *= 2
        return min(b, max(self._max_pages, 1))

    def _get_pages_gather(self, npgb: int):
        key = ("pages-gather", npgb)
        fn = self._block_cache.get(key)
        if fn is None:
            fn = self._jit(_gather_pages, "pages_gather",
                           leaf="attention/cache_write")
            self._block_cache[key] = fn
        return fn

    def _get_swap_in(self, npgb: int):
        key = ("swap-in", npgb)
        fn = self._block_cache.get(key)
        if fn is None:
            def swap_in(cache, pages, hk, hv):
                k = cache.k.at[:, pages].set(hk.astype(cache.k.dtype))
                v = cache.v.at[:, pages].set(hv.astype(cache.v.dtype))
                return llama.KVCache(k=k, v=v)

            fn = self._jit(swap_in, "swap_in", leaf="attention/cache_write",
                           donate_argnums=(0,))
            self._block_cache[key] = fn
        return fn

    def _get_resume_restore(self):
        """Reinstall a swapped-out slot's device rows in one dispatch."""
        fn = self._block_cache.get(("resume-restore",))
        if fn is None:
            def restore(counts, rngs, bias, d_tokens, d_positions, slot,
                        crow, brow, rngd, tok, pos):
                counts = counts.at[slot].set(crow)
                rngs = rngs.at[slot].set(jax.random.wrap_key_data(rngd))
                bias = bias.at[slot].set(brow)
                d_tokens = d_tokens.at[slot].set(tok)
                d_positions = d_positions.at[slot].set(pos)
                return counts, rngs, bias, d_tokens, d_positions

            fn = self._jit(restore, "resume_restore",
                            donate_argnums=(0, 1, 2, 3, 4))
            self._block_cache[("resume-restore",)] = fn
        return fn

    def _get_rng_set(self):
        fn = self._block_cache.get(("rng-set",))
        if fn is None:
            def setrng(rngs, slot, rngd):
                return rngs.at[slot].set(jax.random.wrap_key_data(rngd))

            fn = self._jit(setrng, "rng_set", donate_argnums=(0,))
            self._block_cache[("rng-set",)] = fn
        return fn

    def _swap_out_pages(self, pages: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Pull a page span's K/V to host numpy. The gathered arrays are
        device-side snapshots, so the pages themselves can be recycled the
        moment this returns; the D2H copy is started async and awaited."""
        faults.fire("host_swap")
        npg = len(pages)
        npgb = self._pow2_pages(npg)
        idx = np.full((npgb,), self._scratch_page, np.int32)
        idx[:npg] = pages
        with self._phases.call("call/swap_out", pages=npg):
            gk, gv = self._get_pages_gather(npgb)(
                self.cache.k, self.cache.v, self._upload(idx)
            )
            self._host_copy_async(gk)
            self._host_copy_async(gv)
            hk = np.ascontiguousarray(np.asarray(gk)[:, :npg])
            hv = np.ascontiguousarray(np.asarray(gv)[:, :npg])
        return hk, hv

    def _swap_in_pages(self, pages: list[int], hk: np.ndarray,
                       hv: np.ndarray) -> None:
        """Scatter host K/V back into freshly-allocated pool pages."""
        faults.fire("host_swap")
        npg = len(pages)
        npgb = self._pow2_pages(npg)
        idx = np.full((npgb,), self._scratch_page, np.int32)
        idx[:npg] = pages
        if npgb > npg:
            pad = ((0, 0), (0, npgb - npg), (0, 0), (0, 0), (0, 0))
            hk = np.pad(hk, pad)
            hv = np.pad(hv, pad)
        with self._phases.call("call/swap_in", pages=npg):
            self.cache = self._get_swap_in(npgb)(
                self.cache, self._upload(idx), self._upload(hk),
                self._upload(hv)
            )

    def _host_make_room(self, need: int) -> bool:
        """Fit `need` bytes into the host tier by evicting LRU spilled
        prefix spans. Pending swap images are never evicted — they are
        required state, not cache."""
        if need > self.ecfg.kv_swap_bytes:
            return False
        with self._host_lock:
            while (self._host_bytes + need > self.ecfg.kv_swap_bytes
                   and self._prefix_host):
                dead = self._prefix_host.pop()
                self._host_bytes -= dead["bytes"]
            return self._host_bytes + need <= self.ecfg.kv_swap_bytes

    def _host_bias_row(self, request: GenRequest) -> np.ndarray:
        """The bias row the admission program would build — logit_bias plus
        the padded-vocab mask — recomputed host-side for swap resume."""
        from localai_tpu.ops.sampling import NEG_INF

        V = self.cfg.vocab_size
        row = np.zeros((V,), np.float32)
        for tid, bval in request.logit_bias.items():
            if 0 <= int(tid) < V:
                row[int(tid)] = bval
        tok_v = min(getattr(self.tokenizer, "vocab_size", V) or V, V)
        if tok_v < V:
            row[tok_v:] = NEG_INF
        return row

    def _resume_discard(self, request: GenRequest) -> None:
        """Release a queued resume's host-tier bytes (cancellation path)."""
        rec = request.resume
        if rec is not None and "bytes" in rec:
            # Runs on caller threads (stop/cancel_all) concurrently with
            # the loop's host-tier accounting — locked RMW or the budget
            # drifts (shared-state-race).
            with self._host_lock:
                self._host_bytes -= rec["bytes"]
            rec.pop("hk", None)
            rec.pop("hv", None)
            rec["bytes"] = 0

    def _preempt_youngest(self) -> None:
        """Evict the youngest live slot so a growth-blocked older slot can
        proceed. Caller guarantees the in-flight queue is EMPTY (drained by
        the loop), so the victim's host/device state is a consistent
        snapshot and its pages have no pending writes. Grammar-constrained
        slots are preempted only as a last resort (recompute policy; a
        device-DFA victim's host machine is re-seeded by replaying its
        generated tokens) — their state is the most expensive to move."""
        B = self.ecfg.max_slots
        live = [i for i in range(B)
                if self.h_active[i] and self.slots[i] is not None]
        cands = [i for i in live if self.slots[i].request.grammar is None]
        grammar_victim = False
        if not cands:
            cands = live
            grammar_victim = True
        if not cands:
            return
        victim = max(cands, key=lambda i: (self.slots[i].t_submit, i))
        slot = self.slots[victim]
        r = slot.request
        page = self.ecfg.kv_page_size
        ctx_rows = slot.prompt_len + len(slot.generated)
        n_live = min(-(-ctx_rows // page), len(self._slot_pages[victim]))
        span_bytes = n_live * self._page_bytes()
        policy = self.ecfg.kv_preempt
        if self.cfg.is_hybrid:
            # No image of the recurrent state is taken: the row is dropped
            # and the re-admission recomputes it from prompt + generated
            # (engine/state.py: preempt).
            policy = "recompute"
            self.m_state_restores += 1
        elif self.draft_cfg is not None:
            # Only the SEPARATE draft checkpoint forces recompute (its
            # dense KV has no swap image). Model-free spec slots swap
            # byte-exactly: prompt_lookup keeps no device draft state at
            # all, and the self_draft scratch resyncs from the restored
            # target cache on the slot-generation bump (_spec_sd_sync).
            policy = "recompute"
        elif grammar_victim:
            # Swap cannot restore a DFA slot's device automaton row into a
            # possibly-swapped table set; recompute re-admits through the
            # host walk with the machine replayed below.
            policy = "recompute"
        elif policy == "auto":
            policy = ("swap" if span_bytes * 4 <= self.ecfg.kv_swap_bytes
                      else "recompute")
        if policy == "swap" and (self.ecfg.kv_swap_bytes <= 0
                                 or not self._host_make_room(span_bytes)):
            policy = "recompute"
        if grammar_victim and slot.dfa:
            # The device DFA never advanced the host machine; replay the
            # generated tokens so the host walk resumes from the right
            # state (re-admission gates DFA off for resume requests).
            for tok in slot.generated:
                self._grammar_advance(r.grammar, int(tok))
        rec = {
            "mode": policy,
            "orig_prompt_len": slot.prompt_len,
            "generated": list(slot.generated),
            "emitted_len": slot.emitted_len,
            "t_submit": slot.t_submit,
            "t_first": slot.t_first,
            "t_preempt": time.monotonic(),
            "rng": np.asarray(jax.random.key_data(self.rngs))[victim].copy(),
            "rope_delta": int(self.h_rope_delta[victim]),
        }
        if policy == "swap":
            # Spilled cold pages splice in from their host images — the
            # swap image is byte-exact without re-admitting them first.
            hk, hv = self._swap_out_slot_span(victim, n_live)
            rec.update({
                "hk": hk, "hv": hv, "ctx_rows": ctx_rows,
                "d_tok": int(np.asarray(self.d_tokens)[victim]),
                "d_pos": int(np.asarray(self.d_positions)[victim]),
                "bytes": span_bytes,
            })
            with self._host_lock:
                self._host_bytes += span_bytes
            self.m_kv_swap_bytes_out += span_bytes
            self.m_kv_preempt_swaps += 1
        else:
            self.m_kv_preempt_recomputes += 1
        self.m_kv_preemptions += 1
        self._jnote("preempt", rid=slot.handle.rid, slot=victim,
                    a=float(ctx_rows))
        if policy == "swap":
            self._jnote("swap_out", rid=slot.handle.rid, slot=victim,
                        a=float(span_bytes))
        tr = slot.handle.trace
        if tr is not None:
            tr.note("preempt", policy=policy, ctx_rows=ctx_rows)
        resume_req = dataclasses.replace(
            r, prompt_ids=list(r.prompt_ids) + list(slot.generated),
            resume=rec,
        )
        handle = slot.handle
        # Tear the slot down WITHOUT a terminal event — the handle lives on
        # and the resumed slot keeps streaming into it. The generation bump
        # makes any straggler result for this slot index drop on the floor.
        self._plan_dirty()
        self._slot_gen[victim] += 1
        self.slots[victim] = None
        self._chunkings = [st for st in self._chunkings
                           if st["slot"] != victim]
        self.h_active[victim] = False
        self.h_override_mask[victim] = False
        self.h_gmask[victim] = 0.0
        # Spec scheduling state resets with the slot; the resumed request
        # rebuilds its lookup index / EWMA from its restored history.
        self.h_accept_ewma[victim] = 1.0
        self._spec_probe[victim] = 0
        # The resume request still carries .adapter — re-admission re-pins
        # it (possibly into a different row after churn).
        self._slot_release_adapter(victim)
        self._pages_free(victim)
        with self._pending_lock:
            self._pending.appendleft((resume_req, handle))
        # _growth_blocked stays SET: the freed pages belong to the growth-
        # starved survivors first. Clearing it here would let the very next
        # _admit_pending hand them straight back to this victim's resume
        # (it sits at the queue head) and ping-pong the preemption forever;
        # _grow_for_decode clears the flag once growth actually succeeds,
        # and the loop clears it if every active slot drains away.
        log.info("preempted slot %d (%s, ctx=%d rows) for page growth",
                 victim, policy, ctx_rows)

    def _resume_swap_pages(self, request: GenRequest) -> int:
        """Pages a queued swap resume needs: its live span + headroom
        (capped at the request's worst case)."""
        rec = request.resume
        page = self.ecfg.kv_page_size
        n_live = rec["hk"].shape[1]
        worst = -(-min(rec["orig_prompt_len"] + request.max_new_tokens,
                       self.ecfg.max_seq) // page)
        return min(n_live + self.ecfg.kv_page_headroom, max(n_live, worst))

    def _dispatch_resume_swap(self, request: GenRequest,
                              handle: RequestHandle, slot_idx: int) -> bool:
        """Re-admit a swap-preempted request: allocate pages, scatter the
        host image back, reinstall the slot's device rows — no prefill, no
        sampling; the slot resumes decoding exactly where it stopped."""
        rec = request.resume
        row_a = 0
        if request.adapter:
            # Re-pin the tenant's adapter BEFORE pages: its factors may
            # have been evicted while the slot sat swapped out. A failed
            # re-pin consumes the request with a typed error event (the
            # KV image is released) instead of stalling the queue head.
            try:
                row_a = self._adapter_acquire(request.adapter)
            except Exception as e:  # noqa: BLE001 — fail one tenant only
                log.exception("adapter re-pin failed on swap resume")
                self._resume_discard(request)
                handle._q.put(TokenEvent(
                    kind="error", error=f"{type(e).__name__}: {e}"
                ))
                return True
        total = self._resume_swap_pages(request)
        try:
            row = self._pages_alloc(slot_idx, total)
        except BaseException:
            # An allocator raise (page-geometry validation) must not strand
            # the adapter pin taken above.
            if row_a:
                self._adapter_unpin(row_a)
            raise
        if row is None:
            if row_a:
                self._adapter_unpin(row_a)
            return False
        n_live = rec["hk"].shape[1]
        self._swap_in_pages(self._slot_pages[slot_idx][:n_live],
                            rec["hk"], rec["hv"])
        V = self.cfg.vocab_size
        crow = np.bincount(
            np.asarray(request.prompt_ids, np.int64) % V, minlength=V
        )[:V].astype(np.int32)
        brow = self._host_bias_row(request)
        with self._phases.call("call/resume_restore"):
            (
                self.counts, self.rngs, self.bias, self.d_tokens,
                self.d_positions,
            ) = self._get_resume_restore()(
                self.counts, self.rngs, self.bias, self.d_tokens,
                self.d_positions, jnp.int32(slot_idx), self._upload(crow),
                self._upload(brow), self._upload(rec["rng"]),
                jnp.int32(rec["d_tok"]), jnp.int32(rec["d_pos"]),
            )
        for kf in _SAMPLING_FIELDS:
            self.h_sampling[kf][slot_idx] = getattr(request, kf)
        if self._mrope:
            self.h_rope_delta[slot_idx] = rec["rope_delta"]
        orig_req = dataclasses.replace(
            request, prompt_ids=list(request.prompt_ids[: rec["orig_prompt_len"]]),
            resume=None,
        )
        self._slot_gen[slot_idx] += 1
        self.slots[slot_idx] = _Slot(
            request=orig_req, handle=handle,
            prompt_len=rec["orig_prompt_len"],
            generated=list(rec["generated"]),
            emitted_len=rec["emitted_len"],
            scheduled=len(rec["generated"]),
            sched_rows=rec["d_pos"],
            t_submit=rec["t_submit"], t_first=rec["t_first"],
        )
        self.h_active[slot_idx] = True
        self.h_override_mask[slot_idx] = False
        self.h_gmask[slot_idx] = 0.0
        self.h_adapter[slot_idx] = row_a
        with self._host_lock:
            self._host_bytes -= rec["bytes"]
        self.m_kv_swap_bytes_in += rec["bytes"]
        self.m_kv_preempt_recover_ms += (
            (time.monotonic() - rec["t_preempt"]) * 1e3
        )
        self._jnote("swap_in", rid=handle.rid, slot=slot_idx,
                    a=float(rec["bytes"]))
        self._plan_dirty()
        self._last_admit_t = time.monotonic()
        return True

    def _apply_resume(self, slot_idx: int) -> None:
        """Patch a freshly-admitted slot that is actually a recompute
        resume: restore the original request identity, the accumulated
        generated tokens and emitted text (stream continuity — the next
        event continues the original handle mid-stream), and the RNG
        chain."""
        slot = self.slots[slot_idx]
        rec = slot.request.resume if slot is not None else None
        if rec is None:
            return
        self._jnote("resume", rid=slot.handle.rid, slot=slot_idx,
                    a=float(len(rec["generated"])))
        orig = list(slot.request.prompt_ids[: rec["orig_prompt_len"]])
        slot.request = dataclasses.replace(
            slot.request, prompt_ids=orig, resume=None
        )
        slot.prompt_len = rec["orig_prompt_len"]
        slot.generated = list(rec["generated"])
        slot.dec_n, slot.dec_text = 0, ""
        slot.emitted_len = rec["emitted_len"]
        # The admission just sampled the NEXT token (it rides the tracked
        # admit entry and will append to the restored list).
        slot.scheduled = len(slot.generated) + 1
        slot.t_submit = rec["t_submit"]
        slot.t_first = rec["t_first"]
        if self.draft_cfg is None:
            # Continue the RNG chain: the uncontended run draws token g+2
            # from split(k_{g+1}); the admission consumed its own fold_in
            # draw for token g+1, so advance the saved key one split —
            # every draw after the re-admission token then matches the
            # uncontended run (greedy is byte-exact regardless).
            with self._phases.call("call/rng_set"):
                key = jax.random.wrap_key_data(self._upload(rec["rng"]))
                nxt = jax.random.key_data(jax.random.split(key, 2)[0])
                self.rngs = self._get_rng_set()(
                    self.rngs, jnp.int32(slot_idx), nxt
                )
        self.m_kv_preempt_recover_ms += (
            (time.monotonic() - rec["t_preempt"]) * 1e3
        )

    # ------------------------------------------------------------------ #
    # Multi-tenant LoRA adapters (ISSUE 10, docs/LORA_SERVING.md)
    # ------------------------------------------------------------------ #

    def register_adapter(self, name: str, adapter_dir: str,
                         weight: float = 1.0) -> None:
        """Register a PEFT-format adapter as a servable tenant of this
        engine. Registration is metadata-only (no disk I/O): the factor
        image is fetched through the bounded host tier and promoted into
        the stacked device factors lazily, at the first admission that
        names it — thousands of registered adapters cost nothing until
        they serve. Idempotent for an identical (dir, weight); re-binding
        a name to a different source is an error (tenant identity must be
        stable while requests may be in flight)."""
        if self.draft_cfg is not None:
            raise AdapterError(
                "runtime LoRA adapters are not supported with a separate "
                "draft model — the draft would decode without the delta; "
                "model-free speculation (spec_mode=prompt_lookup/"
                "self_draft) serves adapter tenants"
            )
        if self.cfg.is_mla or self.cfg.is_moe or self.cfg.is_hybrid:
            kind = (f"hybrid {self.cfg.recurrent_kind.upper()}/"
                    f"{'MLA' if self.cfg.is_mla else 'GQA'}"
                    if self.cfg.is_hybrid
                    else "MLA" if self.cfg.is_mla else "MoE")
            raise AdapterError(
                f"runtime LoRA adapters serve dense llama-family bases only "
                f"({self.cfg.name} is {kind}) "
                "— merge at load via `lora_adapters` instead"
            )
        with self._adapter_lock:
            prev = self._adapter_registry.get(name)
            if prev is not None:
                if prev["dir"] != adapter_dir or prev["weight"] != float(weight):
                    raise AdapterError(
                        f"adapter {name!r} is already registered from "
                        f"{prev['dir']!r} (weight={prev['weight']}) — "
                        "unregister/rename instead of rebinding"
                    )
                return
            self._adapter_registry[name] = {
                "dir": adapter_dir, "weight": float(weight),
            }

    def adapter_names(self) -> list[str]:
        with self._adapter_lock:
            return sorted(self._adapter_registry)

    def _adapter_image(self, name: str, reg: dict) -> dict:
        """Host-tier factor image for one adapter: {rank, stacks: {key:
        (A [L, in, r], B [L, r, out]) f32}, bytes}. Hits promote within the
        LRU; misses read the PEFT checkpoint from disk (faults site
        `adapter_fetch`) and insert under the adapter_cache_bytes budget —
        LRU entries evict to make room, and an image bigger than the whole
        budget serves this promote but is not retained (loop thread
        only)."""
        entry = self._adapter_host.get(name)
        if entry is not None:
            self._adapter_host.move_to_end(name)
            return entry
        faults.fire("adapter_fetch")
        from localai_tpu.engine.weights import load_lora_factors, lora_target_dims

        rank, per_key = load_lora_factors(reg["dir"], reg["weight"], self.cfg)
        dims = lora_target_dims(self.cfg)
        L = self.cfg.num_layers
        stacks: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        nbytes = 0
        for key, layers_d in per_key.items():
            d_in, d_out = dims[key]
            a = np.zeros((L, d_in, rank), np.float32)
            b = np.zeros((L, rank, d_out), np.float32)
            for li, (a_t, b_t) in layers_d.items():
                r = a_t.shape[1]
                a[li, :, :r] = a_t
                b[li, :r, :] = b_t
            stacks[key] = (a, b)
            nbytes += a.nbytes + b.nbytes
        entry = {"rank": rank, "stacks": stacks, "bytes": nbytes}
        self._adapter_host[name] = entry
        self._adapter_host_bytes += nbytes
        self.m_adapter_fetches += 1
        budget = self.ecfg.adapter_cache_bytes
        while self._adapter_host_bytes > budget and len(self._adapter_host) > 1:
            victim = next(iter(self._adapter_host))
            if victim == name:
                self._adapter_host.move_to_end(name, last=False)
                victim = next(iter(self._adapter_host))
                if victim == name:
                    break
            self._adapter_host_bytes -= self._adapter_host.pop(victim)["bytes"]
        if self._adapter_host_bytes > budget:
            # The image alone exceeds the budget: serve it, don't retain it.
            self._adapter_host_bytes -= self._adapter_host.pop(name)["bytes"]
        return entry

    def _lora_rebuild(self, keys: tuple, na: int, rank: int) -> None:
        """(Re)allocate the stacked device factor tree at (keys, na, rank),
        copying every resident adapter's rows from the old tree. Row 0 is
        the all-zero null adapter. Shapes are static program inputs, so a
        rebuild retraces the lora-enabled programs — growth doubles (capped
        at max_slots + 1 rows: every slot a distinct tenant) to keep
        rebuilds logarithmic. tp>1 places A/B with the factor partitioning
        mirroring the base weight's role (ops/lora_matmul)."""
        from localai_tpu.engine.weights import lora_target_dims
        from localai_tpu.ops.lora_matmul import LORA_PART, lora_factor_specs

        dims = lora_target_dims(self.cfg)
        dt = jnp.dtype(self.cfg.dtype)
        L = self.cfg.num_layers
        old = self._lora_tree or {}
        new_tree: dict = {}
        with self.mesh:
            for key in keys:
                d_in, d_out = dims[key]
                a = jnp.zeros((L, na, d_in, rank), dt)
                b = jnp.zeros((L, na, rank, d_out), dt)
                o = old.get(key)
                if o is not None:
                    ona, orank = o["a"].shape[1], o["a"].shape[3]
                    a = a.at[:, :ona, :, :orank].set(o["a"])
                    b = b.at[:, :ona, :orank, :].set(o["b"])
                if self.plan.total > 1:
                    from jax.sharding import NamedSharding

                    specs = lora_factor_specs(LORA_PART[key])
                    a = jax.device_put(a, NamedSharding(self.mesh, specs["a"]))
                    b = jax.device_put(b, NamedSharding(self.mesh, specs["b"]))
                new_tree[key] = {"a": a, "b": b}
        self._lora_tree = new_tree
        self._lora_keys = keys
        self._lora_rank = rank
        while len(self._adapter_rows) < na:
            self._adapter_rows.append(None)
            self._adapter_last.append(0.0)
        if len(self._adapter_refs) < na:
            refs = np.zeros((na,), np.int32)
            refs[: len(self._adapter_refs)] = self._adapter_refs
            self._adapter_refs = refs

    def _lora_write_row(self, row: int, image: dict) -> None:
        """Install one host factor image into device row `row` (every
        target key: absent keys write zeros so a recycled row never leaks
        the previous tenant's factors)."""
        from localai_tpu.engine.weights import lora_target_dims

        dims = lora_target_dims(self.cfg)
        dt = jnp.dtype(self.cfg.dtype)
        L = self.cfg.num_layers
        rank = self._lora_rank
        for key in self._lora_keys:
            d_in, d_out = dims[key]
            st = image["stacks"].get(key)
            if st is None:
                a_np = np.zeros((L, d_in, rank), np.float32)
                b_np = np.zeros((L, rank, d_out), np.float32)
            else:
                a_np, b_np = st
                r = a_np.shape[-1]
                if r < rank:
                    a_np = np.pad(a_np, ((0, 0), (0, 0), (0, rank - r)))
                    b_np = np.pad(b_np, ((0, 0), (0, rank - r), (0, 0)))
            ent = self._lora_tree[key]
            ent["a"] = ent["a"].at[:, row].set(self._upload(a_np, dt))
            ent["b"] = ent["b"].at[:, row].set(self._upload(b_np, dt))

    def _adapter_acquire(self, name: str) -> int:
        """Pin `name` into a device adapter row and return the row id
        (allocator primitive — the ONLY place a row is claimed; loop thread
        only). Resident adapters just bump their refcount; otherwise the
        factor image is fetched through the host tier and promoted into a
        free row, a grown row, or the LRU UNPINNED row — a row with live
        references is never evicted, so mid-flight tenants keep their
        factors until _adapter_unpin drops the last ref."""
        with self._adapter_lock:
            reg = self._adapter_registry.get(name)
        if reg is None:
            raise AdapterError(
                f"unknown adapter {name!r} — register_adapter() first"
            )
        if name in self._adapter_rows:
            row = self._adapter_rows.index(name)
        else:
            image = self._adapter_image(name, reg)
            faults.fire("adapter_fetch")
            keys = tuple(sorted(set(self._lora_keys) | set(image["stacks"])))
            rank = max(self._lora_rank, image["rank"], 1)
            cap = self.ecfg.max_slots + 1
            na = len(self._adapter_rows)
            row = next(
                (i for i in range(1, na) if self._adapter_rows[i] is None),
                None,
            )
            if row is None and na < cap:
                row = max(1, na)
                na = min(cap, max(2, na * 2))
            if row is None:
                cands = [
                    i for i in range(1, na)
                    if self._adapter_rows[i] is not None
                    and self._adapter_refs[i] == 0
                ]
                if cands:
                    row = min(cands, key=lambda i: self._adapter_last[i])
                    self._adapter_rows[row] = None
                    self.m_adapter_evictions += 1
            if row is None:
                raise AdapterError(
                    "every device adapter slot is pinned by an active "
                    "request — retry when traffic drains or raise max_slots"
                )
            if (keys != self._lora_keys or rank != self._lora_rank
                    or na != len(self._adapter_rows)):
                self._lora_rebuild(keys, na, rank)
            self._lora_write_row(row, image)
            self._adapter_rows[row] = name
            self.m_adapter_promotes += 1
        self._adapter_refs[row] += 1
        self._adapter_last[row] = time.monotonic()
        return row

    def _adapter_unpin(self, row: int) -> None:
        """Drop one reference on a device adapter row (allocator primitive
        — the only decrement; loop thread only). Underflow clamps and logs
        like _pages_release (LOCALAI_ALLOC_DEBUG=1 raises)."""
        if row <= 0 or row >= len(self._adapter_refs):
            return
        v = int(self._adapter_refs[row])
        if v <= 0:
            msg = f"adapter refcount underflow at device row {row}"
            if os.environ.get("LOCALAI_ALLOC_DEBUG", "0") == "1":
                raise AssertionError(msg)
            log.warning("%s — clamped", msg)
            self._adapter_refs[row] = 0
            return
        self._adapter_refs[row] = v - 1

    def _slot_release_adapter(self, slot_idx: int) -> None:
        """Unpin a slot's adapter row on any teardown path (finish, cancel,
        preempt, loop death release runs its own bulk reset)."""
        row = int(self.h_adapter[slot_idx])
        if row:
            self.h_adapter[slot_idx] = 0
            self._adapter_unpin(row)

    # ------------------------------------------------------------------ #
    # Compiled programs
    # ------------------------------------------------------------------ #

    def _build_programs(self) -> None:
        cfg = self.cfg
        # sp>1 routes prefill through ring attention over the mesh's "sp"
        # axis (long-context serving — KV residency per chip is bucket/sp).
        # _ring_mesh stays the sp-only gate (chunking/kv-window policy key
        # off it); the mesh ARGUMENT model code receives is _op_mesh, which
        # is also set on tp>1 plans so the Pallas kernels run head-sharded
        # under shard_map (ISSUE 7).
        ring_mesh = self.mesh if self.plan.sp > 1 else None
        self._ring_mesh = ring_mesh
        # Sequence-parallel chunked prefill (ISSUE 14): with sp>1 AND a
        # paged pool, the chunk programs ring-shard each chunk's attention
        # over "sp" (parallel/ring.ring_chunk_paged_attention) while K/V
        # scatters direct-to-page; the pool itself replicates over sp.
        self._sp_chunk_mesh = (
            self.mesh
            if (self._paged and self.plan.sp > 1 and self.ecfg.sp_prefill)
            else None
        )
        op_mesh = self._op_mesh

        def _prefill(params, tokens, lengths):
            return llama.prefill(cfg, params, tokens, lengths, mesh=op_mesh, ep=self.plan.ep)

        def _embed(params, tokens, lengths):
            return llama.encode(cfg, params, tokens, lengths, mesh=op_mesh, ep=self.plan.ep)

        def _score(params, tokens, lengths, cond_lengths):
            return llama.sequence_logprob(
                cfg, params, tokens, lengths, cond_lengths, mesh=op_mesh,
                ep=self.plan.ep,
            )

        self._prefill_fn = self._jit(_prefill, "prefill")
        self._embed_fn = self._jit(_embed, "embed")
        self._score_fn = self._jit(_score, "score")

    def _get_block(self, variant: str, n: int, with_lp: bool = False,
                   with_dfa: bool = False, kv_win: Optional[int] = None,
                   with_lora: bool = False):
        """Fused n-step decode block program for one sampling variant.

        variant: "greedy" | "simple" | "filtered" | "grammar".
        State flows through the scan entirely on device; only the sampled
        token ids (and, for grammar, top-k candidates) come back to the host.
        All per-dispatch host control (active mask, sampling params, token
        overrides) rides in ONE packed [10, B] f32 array — every separate
        H2D transfer is a dispatch of its own, so the hot path gets exactly
        one.

        with_lp additionally returns, per step, the sampled token's logprob
        and the top-LOGPROB_TOPK (ids, logprobs) from log_softmax(logits +
        bias) — the OpenAI logprobs contract (pre-penalty, pre-temperature).

        with_dfa runs the grammar DFA on device for slots whose pack row 10
        is set: their logits are masked to the legal set of the slot's
        automaton state, and the state advances by walking the sampled
        token's char classes — no host round-trip, so constrained requests
        keep full block depth and pipeline alongside unconstrained slots
        (which run through the FREE state, an all-legal fixed point).

        kv_win (static): attention reads only cache[:, :, :kv_win]. Every
        decode step otherwise streams the FULL padded [S] KV rows from HBM —
        at max_seq 1024 with ~200 live tokens that is ~0.5 ms/step of pure
        waste on a 1B model (measured ~11% of the decode step). The host
        picks the smallest bucket covering every active slot's position;
        writes still target the full cache, so this is read-side only.
        """
        key = (variant, n, with_lp, with_dfa, kv_win, with_lora)
        fn = self._block_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        B, S = self.ecfg.max_slots, self.ecfg.max_seq
        V = cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)

        paged = self._paged

        mrope = self._mrope

        def block(params, cache, counts, rngs, bias, tokens, positions, pack,
                  rope_delta=None, ptable=None, mask_bits=None, gtrans=None,
                  tok_cls=None, gstate=None, lora=None):
            active = pack[0] > 0
            samp = SamplingParams(
                temperature=pack[1], top_k=pack[2].astype(jnp.int32),
                top_p=pack[3], min_p=pack[4], repeat_penalty=pack[5],
                presence_penalty=pack[6], frequency_penalty=pack[7],
            )
            overrides = pack[8].astype(jnp.int32)  # token ids < 2^24: exact in f32
            omask = pack[9] > 0
            tokens = jnp.where(omask, overrides, tokens)
            act_i32 = active.astype(jnp.int32)
            if with_dfa:
                gmask = pack[10] > 0
                gstate = jnp.where(gmask, gstate, 0)  # FREE for unconstrained

            # Block-local KV window: the cache stays READ-ONLY inside the
            # scan (profiling showed a carried cache costs one full cache
            # copy per token); the window scatters into the cache once.
            read_cache = cache
            if kv_win is not None and not paged:
                # Read-side slice: XLA fuses it into the attention consumers,
                # so only the live prefix streams from HBM. Idle rows whose
                # (discarded) positions exceed the window just attend over
                # the whole slice; the final write targets the full cache.
                with scope("attention/mix"):
                    read_cache = type(cache)(
                        k=cache.k[:, :, :kv_win], v=cache.v[:, :, :kv_win]
                    )
            start_pos = positions
            # SCALED fp8 pool: the block-local window stays in MODEL dtype
            # (unscaled) — rows quantize ONCE, at the block's pool write,
            # where the /scale happens. Storing the window pre-quantized
            # (the unscaled-pool layout) would clip exactly the magnitudes
            # the scale exists to keep.
            ldt_k = cache.k.dtype if self._kv_scales is None else jnp.dtype(cfg.dtype)
            ldt_v = cache.v.dtype if self._kv_scales is None else jnp.dtype(cfg.dtype)
            with scope("attention/cache_write"):  # the block-local window
                local_k = jnp.zeros(
                    (cfg.cache_layers, B, n, cfg.cache_kv_heads,
                     cfg.cache_k_dim), ldt_k,
                )
                local_v = jnp.zeros(
                    (cfg.cache_layers, B, n, cfg.cache_kv_heads,
                     cfg.cache_v_dim), ldt_v,
                )
            # A hybrid model's recurrent state is carried by the steps (each
            # updates every row in place) while the pool stays read-only; a
            # window kind's rings stay read-only too, beside the block's rows.
            rec0 = (llama.block_recurrent(cfg, cache, B, n)
                    if cfg.is_hybrid else None)
            if rec0 is not None:
                cache = cache._replace(state=None, conv=None)

            @scope("sample")
            def after_logits(logits, counts, rngs, gs):
                """Everything after a step's logits: the draw, the grammar
                mask, the token, its logprobs, the penalty counts."""
                split = jax.vmap(lambda k: jax.random.split(k, 2))(rngs)
                rngs, draw = split[:, 0], split[:, 1]
                if with_dfa:
                    from localai_tpu.ops.sampling import NEG_INF

                    allowed = self._dfa_allowed(mask_bits, gs, V)
                    slogits = jnp.where(allowed, logits, NEG_INF)
                else:
                    slogits = logits
                if variant == "greedy":
                    nxt = sample_greedy(slogits, samp, counts, bias)
                elif variant == "simple":
                    nxt = sample_simple(slogits, draw, samp, counts, bias)
                else:
                    nxt = sample(slogits, draw, samp, counts, bias)
                counts = counts.at[jnp.arange(B), nxt].add(act_i32)
                if with_dfa:
                    ns = self._dfa_advance(with_dfa, gtrans, tok_cls, gs, nxt)
                    gs = jnp.where(active, ns, gs)  # FREE rows self-loop
                nxt = jnp.where(active, nxt, 0)
                if variant == "grammar":
                    _, tk = jax.lax.top_k(logits + bias, K)
                    out = (nxt, tk)
                else:
                    out = (nxt,)
                if with_lp:
                    # The model's own distribution (pre-grammar-mask), per
                    # the OpenAI logprobs contract.
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32) + bias, axis=-1
                    )
                    lp_vals, lp_ids = jax.lax.top_k(logp, LK)
                    tok_lp = jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]
                    out = out + (tok_lp, lp_ids, lp_vals)
                return out, nxt, counts, rngs, gs

            def body(carry, step):
                tokens, positions, counts, rngs, lk, lv, gs, rec = carry
                hyb = {} if rec is None else {"recurrent": rec}
                if paged:
                    # Idle/released slots' positions keep ratcheting toward
                    # S-1 (the carry advances every slot); left unmasked
                    # they would drive the paged fori_loop bound to the full
                    # table forever. Their compute is discarded anyway, so
                    # pin them to 0 for this step's attention.
                    pos_eff = jnp.where(active, positions, 0)
                    logits, lk, lv, *routed = llama.decode_step_windowed(
                        cfg, params, tokens, pos_eff, cache, lk, lv, step,
                        ep=self.plan.ep, ptable=ptable,
                        paged_impl=self.ecfg.paged_kernel,
                        kv_scale=self._kv_scales,
                        rope_delta=rope_delta, mesh=self._op_mesh,
                        lora=lora, expert_rows=cfg.is_moe, **hyb,
                    )
                else:
                    logits, lk, lv, *routed = llama.decode_step_windowed(
                        cfg, params, tokens, positions, read_cache, lk, lv, step,
                        ep=self.plan.ep, mesh=self._op_mesh,
                        rope_delta=rope_delta, lora=lora,
                        expert_rows=cfg.is_moe,
                    )
                if rec is not None:
                    rec = routed.pop()
                out, nxt, counts, rngs, gs = after_logits(
                    logits, counts, rngs, gs)
                # Routing of this step, [L, E] rows per expert → experts that
                # got a row, and the busiest expert's rows summed over layers.
                per = routed[0] if routed else None
                with scope("mlp/router"):
                    moe = (None if per is None else
                           jnp.stack([(per > 0).sum(), per.max(-1).sum()]
                                     # an expert share: the picks that landed here
                                     + ([per.sum()] if cfg.expert_share else [])))
                # Clamp so idle/overshooting slots keep writing inside their
                # own cache row instead of out-of-bounds.
                positions = jnp.minimum(positions + 1, S - 1)
                return (nxt, positions, counts, rngs, lk, lv, gs, rec), (out, moe)

            gs0 = gstate if with_dfa else jnp.zeros((B,), jnp.int32)
            (tokens, positions, counts, rngs, local_k, local_v, gs, rec), (outs, moe) = jax.lax.scan(
                body,
                (tokens, positions, counts, rngs, local_k, local_v, gs0, rec0),
                jnp.arange(n),
            )
            if paged:
                cache = llama.write_block_to_pool(
                    cache, ptable, local_k, local_v, start_pos,
                    kv_scale=self._kv_scales,
                    paged_impl=self.ecfg.paged_kernel, mesh=self._op_mesh,
                )
            else:
                cache = llama.write_block_to_cache(cache, local_k, local_v, start_pos)
            if rec is not None:
                cache = llama.block_recurrent_done(
                    cfg, cache, rec, start_pos,
                    paged_impl=self.ecfg.paged_kernel)
            toks_block = outs[0]  # [n, B]
            tk_block = outs[1] if variant == "grammar" else None
            lp_block = tuple(outs[-3:]) if with_lp else None  # ([n,B],[n,B,LK],[n,B,LK])
            # [2] i32 over the block's steps ([3] under an expert share), MoE
            # models only (_count_routing)
            with scope("mlp/router"):
                moe_block = None if moe is None else moe.sum(0)
            out = (cache, counts, rngs, tokens, positions, toks_block, tk_block,
                   lp_block, moe_block)
            if with_dfa:
                out = out + (gs,)
            return out

        # Positional wrapper: [8 base] [rope_delta?] [ptable?] [dfa: mask,
        # trans, cls, gstate] [lora: stacks, ids] — mirrors
        # _dispatch_block's argument assembly.
        def program(*args):
            i = 8
            rope_delta = None
            if mrope:
                rope_delta = args[i]
                i += 1
            ptable = None
            if paged:
                ptable = args[i]
                i += 1
            mask_bits = gtrans = tok_cls = gstate = None
            if with_dfa:
                mask_bits, gtrans, tok_cls, gstate = args[i: i + 4]
                i += 4
            lora = (args[i], args[i + 1]) if with_lora else None
            return block(*args[:8], rope_delta=rope_delta, ptable=ptable,
                         mask_bits=mask_bits, gtrans=gtrans, tok_cls=tok_cls,
                         gstate=gstate, lora=lora)

        donate = (1, 2, 3, 5, 6)
        if with_dfa:
            donate = donate + (8 + (1 if mrope else 0) + (1 if paged else 0) + 3,)
        fn = self._jit(program, "decode_block", donate_argnums=donate)
        self._block_cache[key] = fn
        return fn

    def _get_admit(self, m: int, bucket: int, has_bias: bool, with_topk: bool,
                   with_lp: bool = False, n_img: int = 0,
                   with_dfa: bool = False, with_mrope: bool = False,
                   with_lora: bool = False, with_logits: bool = False):
        """Fused admission program: prefill M prompts, write their KV/state
        into their slots, and sample each first token — one dispatch.

        Host control arrives packed: `aux` [3, M] i32 (lens, slot ids, seeds)
        and `samp_pack` [7, M] f32 (sampling params), so an admission costs
        three H2D transfers (prompts, aux, samp) instead of twelve.

        n_img > 0 (multimodal, always m=1): the program takes projected
        image features [m, n_img, D] + offsets [m] injected into the prompt
        embeddings before the layer stack (llava path).

        with_dfa (grammar DFA, m == 1): the first sampled token is masked to
        the start state's legal set (gmask0, additive -inf rows) and the
        slot's device automaton state is initialized by walking that token's
        char classes — so follow-up decode blocks can pipeline immediately
        with no host round-trip.

        with_logits (fork sampling, ISSUE 18): the final-position logits row
        rides the output tuple LAST, so _fork_after_admit can sample each
        sibling branch's first token from the exact same distribution the
        primary's (or a clone's) admission would have produced.
        """
        key = (m, bucket, has_bias, with_topk, with_lp, n_img, with_dfa,
               with_mrope, with_lora, with_logits)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        V = cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)
        held_rows = self._held_rows

        # Logits may cover more ids than the tokenizer can decode (padded
        # embedding rows); permanently mask those out of sampling via the
        # per-slot bias rows written at admission.
        tok_v = min(getattr(self.tokenizer, "vocab_size", V) or V, V)

        def admit(params, cache, counts, rngs, bias, d_tokens, d_positions,
                  prompt_toks, aux, samp_pack, bias_rows, img_embeds=None,
                  img_offsets=None, mrope_pos=None, gmask0=None, gtrans=None,
                  tok_cls=None, ginit=None, d_gstate=None, ptable=None,
                  lora=None):
            lens, slot_ids, seeds = aux[0], aux[1], aux[2]
            samp = SamplingParams(
                temperature=samp_pack[0], top_k=samp_pack[1].astype(jnp.int32),
                top_p=samp_pack[2], min_p=samp_pack[3], repeat_penalty=samp_pack[4],
                presence_penalty=samp_pack[5], frequency_penalty=samp_pack[6],
            )
            inject = (img_embeds, img_offsets) if img_embeds is not None else None
            if cfg.is_hybrid:
                # Each prompt's recurrent state is written to its slot's row
                # layer by layer inside the prefill (engine/state.py: claim).
                logits, ks, vs, *held, (st, cv) = llama.prefill(
                    cfg, params, prompt_toks, lens, ep=self.plan.ep,
                    recurrent=(cache.state, cache.conv, slot_ids),
                    expert_rows=held_rows,
                )
                cache = cache._replace(state=st, conv=cv)
            else:
                logits, ks, vs, *held = llama.prefill(
                    cfg, params, prompt_toks, lens, mesh=self._op_mesh,
                    inject=inject, ep=self.plan.ep, mrope=mrope_pos, lora=lora,
                    expert_rows=held_rows,
                )
            with scope("sample"):  # everything after the logits
                valid = (jnp.arange(bucket)[None, :] < lens[:, None]).astype(jnp.int32)
                rows = jnp.zeros((m, V), jnp.int32)
                rows = rows.at[jnp.arange(m)[:, None], prompt_toks].add(valid)
                brows = bias_rows if has_bias else jnp.zeros((m, V), jnp.float32)
                if tok_v < V:
                    from localai_tpu.ops.sampling import NEG_INF

                    brows = jnp.where(jnp.arange(V)[None, :] >= tok_v, NEG_INF, brows)
                keys0 = jax.vmap(jax.random.key)(seeds.astype(jnp.uint32))
                draws = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys0)
                srows = brows + gmask0 if with_dfa else brows
                toks = sample(logits, draws, samp, rows, srows)  # [m]
                rows = rows.at[jnp.arange(m), toks].add(1)
                tk = jax.lax.top_k(logits + brows, K)[1] if with_topk else None
                lp = None
                if with_lp:
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32) + brows, axis=-1)
                    lp_vals, lp_ids = jax.lax.top_k(logp, LK)
                    tok_lp = jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
                    lp = (tok_lp, lp_ids, lp_vals)
                if with_dfa:
                    gnext = self._dfa_advance(with_dfa, gtrans, tok_cls, ginit, toks)  # [m]
            for j in range(m):  # m is static and small — unrolled
                s = slot_ids[j]
                if ptable is not None:
                    from localai_tpu.ops import ptable as _pt

                    with scope("attention/cache_write"):
                        row = _pt.select_row(ptable, j)
                    cache = llama.write_prefill_to_pool(
                        cache, row, ks, vs, j, kv_scale=self._kv_scales,
                    )
                else:
                    with scope("attention/cache_write"):
                        kj, vj = ks[:, j:j + 1], vs[:, j:j + 1]
                    cache = llama.write_prefill_to_cache(cache, kj, vj, s)
                with scope("sample"):  # the slot's sampling state
                    counts = counts.at[s].set(rows[j])
                    rngs = rngs.at[s].set(keys0[j])
                    bias = bias.at[s].set(brows[j])
                d_tokens = d_tokens.at[s].set(toks[j])
                d_positions = d_positions.at[s].set(lens[j])
                if with_dfa:
                    d_gstate = d_gstate.at[s].set(gnext[j])
            out = (cache, counts, rngs, bias, d_tokens, d_positions, toks, tk, lp)
            out = out + tuple(held)  # under an expert share (_held_rows)
            if with_dfa:
                out = out + (d_gstate,)
            if with_logits:
                out = out + (logits,)
            return out

        paged = self._paged
        if self.draft_cfg is None:
            # Uniform positional wrapper: [7 state] [d_gstate?] [4 request]
            # [img 2?] [mrope?] [dfa 4?] [ptable?] [lora 2?] — mirrors
            # _dispatch_admit's arg assembly so every flag combination
            # shares one code path.
            def program(*args):
                i = 7
                params, cache, counts, rngs, bias, d_tokens, d_positions = args[:7]
                d_gstate = None
                if with_dfa:
                    d_gstate = args[i]
                    i += 1
                prompt_toks, aux, samp_pack, bias_rows = args[i: i + 4]
                i += 4
                img_embeds = img_offsets = None
                if n_img:
                    img_embeds, img_offsets = args[i: i + 2]
                    i += 2
                mrope_pos = None
                if with_mrope:
                    mrope_pos = args[i]
                    i += 1
                gmask0 = gtrans = tok_cls = ginit = None
                if with_dfa:
                    gmask0, gtrans, tok_cls, ginit = args[i: i + 4]
                    i += 4
                ptable = None
                if paged:
                    ptable = args[i]
                    i += 1
                lora = (args[i], args[i + 1]) if with_lora else None
                return admit(params, cache, counts, rngs, bias, d_tokens,
                             d_positions, prompt_toks, aux, samp_pack,
                             bias_rows, img_embeds=img_embeds,
                             img_offsets=img_offsets, mrope_pos=mrope_pos,
                             gmask0=gmask0,
                             gtrans=gtrans, tok_cls=tok_cls, ginit=ginit,
                             d_gstate=d_gstate, ptable=ptable, lora=lora)

            donate = (1, 2, 3, 4, 5, 6) + ((7,) if with_dfa else ())
            fn = self._jit(program, "admit", donate_argnums=donate)
        else:
            dcfg = self.draft_cfg

            def admit_spec(params, cache, counts, rngs, bias, d_tokens,
                           d_positions, dparams, dcache, prompt_toks, aux,
                           samp_pack, bias_rows, *rest):
                # rest mirrors _dispatch_admit's assembly: [dfa 4?]
                # [ptable?] [d_gstate? — appended last].
                i = 0
                gmask0 = gtrans = tok_cls = ginit = d_gstate = None
                if with_dfa:
                    gmask0, gtrans, tok_cls, ginit = rest[i: i + 4]
                    i += 4
                ptable = None
                if paged:
                    ptable = rest[i]
                    i += 1
                if with_dfa:
                    d_gstate = rest[i]
                out = admit(params, cache, counts, rngs, bias, d_tokens,
                            d_positions, prompt_toks, aux, samp_pack,
                            bias_rows, gmask0=gmask0, gtrans=gtrans,
                            tok_cls=tok_cls, ginit=ginit,
                            d_gstate=d_gstate, ptable=ptable)
                # Prefill the draft model too so its KV cache matches the
                # prompt before the first speculative round (the draft's own
                # cache stays dense — it is small).
                _, dks, dvs = llama.prefill(dcfg, dparams, prompt_toks, aux[0], ep=self.plan.ep)
                for j in range(m):
                    dcache = llama.write_prefill_to_cache(
                        dcache, dks[:, j:j + 1], dvs[:, j:j + 1], aux[1][j]
                    )
                return out + (dcache,)

            donate = (1, 2, 3, 4, 5, 6, 8)
            if with_dfa:
                # d_gstate is the LAST positional arg (after the 13 fixed,
                # the 4 dfa tables, and the optional ptable).
                donate = donate + (13 + 4 + (1 if paged else 0),)
            fn = self._jit(admit_spec, "admit_spec", donate_argnums=donate)
        self._admit_cache[key] = fn
        return fn

    def _get_admit_cached(self, pb: int, tb: int, fbp: int, has_bias: bool,
                          with_topk: bool, with_lp: bool,
                          with_dfa: bool = False, draft: bool = False,
                          build_only: bool = False):
        """Cached admission: copy a stored prefix KV span into the slot and
        prefill only the prompt tail (models/llama.py prefill_tail) — the
        prompt cache fast path (reference: cache_prompt, grpc-server.cpp:125).
        Always m=1. `aux` is [4] i32 (tail_len, slot, seed, prefix_len).

        Host→device traffic is deliberately minimal: the penalty count row
        is computed ON DEVICE from the full prompt ids in an fbp-token
        bucket (~16 KB at a 4k prompt) — shipping a precomputed [1, V]
        bincount instead costs ~0.5 MB of H2D per hit at a llama vocab, on
        the very path the cache exists to shorten. bias_rows rides only
        when the request actually has logit bias.

        draft (draft model configured): the program additionally prefills
        the DRAFT model with the same full-prompt bucket — the draft's small
        cache has no span to reuse, and speculative verify needs its KV
        aligned with the target's (llama.cpp serves cache_prompt and a
        draft together; grpc-server.cpp:125 + params_parse). The target
        still skips its own prefix compute, which is where the admission
        time goes."""
        key = ("cached", pb, tb, fbp, has_bias, with_topk, with_lp, with_dfa,
               draft)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        V = cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)
        tok_v = min(getattr(self.tokenizer, "vocab_size", V) or V, V)

        def admit_cached(params, cache, counts, rngs, bias, d_tokens,
                         d_positions, pk, pv, tail_toks, full_toks, aux,
                         samp_pack, bias_rows=None, gmask0=None, gtrans=None,
                         tok_cls=None, ginit=None, d_gstate=None):
            tail_len, slot, seed, plen = aux[0], aux[1], aux[2], aux[3]
            samp = SamplingParams(
                temperature=samp_pack[0], top_k=samp_pack[1].astype(jnp.int32),
                top_p=samp_pack[2], min_p=samp_pack[3], repeat_penalty=samp_pack[4],
                presence_penalty=samp_pack[5], frequency_penalty=samp_pack[6],
            )
            logits, tks, tvs = llama.prefill_tail(
                cfg, params, tail_toks, aux[0:1], aux[3:4], pk, pv,
                ep=self.plan.ep, mesh=self._op_mesh,
            )
            # Penalty counts from the full prompt, on device (_get_admit's
            # exact recipe — the prefix tokens DO reach the device here, as
            # a token bucket two orders of magnitude smaller than a [V] row).
            with scope("sample"):  # everything after the logits
                fvalid = (jnp.arange(fbp)[None, :] < (plen + tail_len)).astype(jnp.int32)
                rows = jnp.zeros((1, V), jnp.int32)
                rows = rows.at[jnp.arange(1)[:, None], full_toks].add(fvalid)
                brows = bias_rows if has_bias else jnp.zeros((1, V), jnp.float32)
                if tok_v < V:
                    from localai_tpu.ops.sampling import NEG_INF

                    brows = jnp.where(jnp.arange(V)[None, :] >= tok_v, NEG_INF, brows)
                keys0 = jax.vmap(jax.random.key)(aux[2:3].astype(jnp.uint32))
                draws = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(keys0)
                srows = brows + gmask0 if with_dfa else brows
                toks = sample(logits, draws, samp, rows, srows)  # [1]
                rows = rows.at[jnp.arange(1), toks].add(1)
                tk = jax.lax.top_k(logits + brows, K)[1] if with_topk else None
                lp = None
                if with_lp:
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32) + brows, axis=-1)
                    lp_vals, lp_ids = jax.lax.top_k(logp, LK)
                    tok_lp = jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
                    lp = (tok_lp, lp_ids, lp_vals)
            with scope("attention/cache_write"):
                k = jax.lax.dynamic_update_slice(cache.k, pk.astype(cache.k.dtype),
                                                 (0, slot, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(cache.v, pv.astype(cache.v.dtype),
                                                 (0, slot, 0, 0, 0))
                k = jax.lax.dynamic_update_slice(k, tks.astype(k.dtype),
                                                 (0, slot, plen, 0, 0))
                v = jax.lax.dynamic_update_slice(v, tvs.astype(v.dtype),
                                                 (0, slot, plen, 0, 0))
                cache = llama.KVCache(k=k, v=v)
            with scope("sample"):  # the slot's sampling state
                counts = counts.at[slot].set(rows[0])
                rngs = rngs.at[slot].set(keys0[0])
                bias = bias.at[slot].set(brows[0])
            d_tokens = d_tokens.at[slot].set(toks[0])
            d_positions = d_positions.at[slot].set(plen + tail_len)
            out = (cache, counts, rngs, bias, d_tokens, d_positions, toks, tk, lp)
            if with_dfa:
                gnext = self._dfa_advance(with_dfa, gtrans, tok_cls, ginit, toks)
                out = out + (d_gstate.at[slot].set(gnext[0]),)
            return out

        dcfg = self.draft_cfg

        def program(*args):
            # Positional assembly mirrors _dispatch_admit_cached: [7 state]
            # [d_gstate?] [dparams, dcache?] [pk, pv] [tail, full, aux,
            # samp] [bias_rows?] [dfa 4?].
            i = 7
            params, cache, counts, rngs, bias, d_tokens, d_positions = args[:7]
            d_gstate = None
            if with_dfa:
                d_gstate = args[i]
                i += 1
            dparams = dcache = None
            if draft:
                dparams, dcache = args[i: i + 2]
                i += 2
            pk, pv, tail_toks, full_toks, aux, samp_pack = args[i: i + 6]
            i += 6
            bias_rows = None
            if has_bias:
                bias_rows = args[i]
                i += 1
            gmask0 = gtrans = tok_cls = ginit = None
            if with_dfa:
                gmask0, gtrans, tok_cls, ginit = args[i: i + 4]
                i += 4
            out = admit_cached(params, cache, counts, rngs, bias, d_tokens,
                               d_positions, pk, pv, tail_toks, full_toks,
                               aux, samp_pack, bias_rows=bias_rows,
                               gmask0=gmask0, gtrans=gtrans, tok_cls=tok_cls,
                               ginit=ginit, d_gstate=d_gstate)
            if draft:
                flen = aux[0:1] + aux[3:4]  # tail + prefix = full prompt
                _, dks, dvs = llama.prefill(dcfg, dparams, full_toks, flen,
                                            ep=self.plan.ep)
                dcache = llama.write_prefill_to_cache(
                    dcache, dks[:, 0:1], dvs[:, 0:1], aux[1]
                )
                out = out + (dcache,)
            return out

        donate = (1, 2, 3, 4, 5, 6)
        if with_dfa:
            donate = donate + (7,)
        if draft:
            donate = donate + (7 + (1 if with_dfa else 0) + 1,)  # dcache
        fn = self._jit(program, "admit_cached", donate_argnums=donate)
        if not build_only:
            self._admit_cache[key] = fn
        return fn

    def _get_admit_cached_paged(self, npg: int, tb: int, fbp: int,
                                has_bias: bool, with_topk: bool,
                                with_lp: bool, with_dfa: bool = False,
                                draft: bool = False,
                                with_logits: bool = False,
                                build_only: bool = False):
        """Cached admission against the PAGE POOL: the span's pages are
        mapped read-only into the slot's table (no copy — copy-on-write
        sharing), gathered once for the tail's attention, and the freshly
        prefilled tail rows scatter into the slot's own fresh pages. Always
        m=1; `aux` is [4] i32 (tail_len, slot, seed, prefix_len) with
        prefix_len page-aligned; `pages` is the [npg] span page list
        (SCRATCH-padded — rows past prefix_len are masked by prefill_tail).
        Penalty counts/bias ride as in _get_admit_cached: full-prompt token
        bucket on device, bias row only when the request has one."""
        key = ("cached-paged", npg, tb, fbp, has_bias, with_topk, with_lp,
               with_dfa, draft, with_logits)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        V = cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)
        tok_v = min(getattr(self.tokenizer, "vocab_size", V) or V, V)

        def admit_cached_paged(params, cache, counts, rngs, bias, d_tokens,
                               d_positions, pages, table_row, tail_toks,
                               full_toks, aux, samp_pack, bias_rows=None,
                               gmask0=None, gtrans=None, tok_cls=None,
                               ginit=None, d_gstate=None):
            tail_len, slot, seed, plen = aux[0], aux[1], aux[2], aux[3]
            samp = SamplingParams(
                temperature=samp_pack[0], top_k=samp_pack[1].astype(jnp.int32),
                top_p=samp_pack[2], min_p=samp_pack[3], repeat_penalty=samp_pack[4],
                presence_penalty=samp_pack[5], frequency_penalty=samp_pack[6],
            )
            pk, pv = llama.gather_pages(
                cache, pages, kv_scale=self._kv_scales
            )  # [L, 1, npg*page, K, Hd] — dequantized when the pool is scaled
            logits, tks, tvs = llama.prefill_tail(
                cfg, params, tail_toks, aux[0:1], aux[3:4], pk, pv,
                ep=self.plan.ep, mesh=self._op_mesh,
            )
            with scope("sample"):  # everything after the logits
                fvalid = (jnp.arange(fbp)[None, :] < (plen + tail_len)).astype(jnp.int32)
                rows = jnp.zeros((1, V), jnp.int32)
                rows = rows.at[jnp.arange(1)[:, None], full_toks].add(fvalid)
                brows = bias_rows if has_bias else jnp.zeros((1, V), jnp.float32)
                if tok_v < V:
                    from localai_tpu.ops.sampling import NEG_INF

                    brows = jnp.where(jnp.arange(V)[None, :] >= tok_v, NEG_INF, brows)
                keys0 = jax.vmap(jax.random.key)(aux[2:3].astype(jnp.uint32))
                draws = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(keys0)
                srows = brows + gmask0 if with_dfa else brows
                toks = sample(logits, draws, samp, rows, srows)  # [1]
                rows = rows.at[jnp.arange(1), toks].add(1)
                tk = jax.lax.top_k(logits + brows, K)[1] if with_topk else None
                lp = None
                if with_lp:
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32) + brows, axis=-1)
                    lp_vals, lp_ids = jax.lax.top_k(logp, LK)
                    tok_lp = jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
                    lp = (tok_lp, lp_ids, lp_vals)
            # Only the tail rows are written — the span's pages stay
            # untouched (they may back other slots and the entry itself).
            cache = llama.write_rows_to_pool(cache, table_row, tks, tvs, plen,
                                             kv_scale=self._kv_scales)
            with scope("sample"):  # the slot's sampling state
                counts = counts.at[slot].set(rows[0])
                rngs = rngs.at[slot].set(keys0[0])
                bias = bias.at[slot].set(brows[0])
            d_tokens = d_tokens.at[slot].set(toks[0])
            d_positions = d_positions.at[slot].set(plen + tail_len)
            out = (cache, counts, rngs, bias, d_tokens, d_positions, toks, tk, lp)
            if with_dfa:
                gnext = self._dfa_advance(with_dfa, gtrans, tok_cls, ginit, toks)
                out = out + (d_gstate.at[slot].set(gnext[0]),)
            if with_logits:
                out = out + (logits,)
            return out

        dcfg = self.draft_cfg

        def program(*args):
            # Same positional assembly as _get_admit_cached, with the span
            # operands (pages, table_row) in place of (pk, pv).
            i = 7
            params, cache, counts, rngs, bias, d_tokens, d_positions = args[:7]
            d_gstate = None
            if with_dfa:
                d_gstate = args[i]
                i += 1
            dparams = dcache = None
            if draft:
                dparams, dcache = args[i: i + 2]
                i += 2
            pages, table_row, tail_toks, full_toks, aux, samp_pack = args[i: i + 6]
            i += 6
            bias_rows = None
            if has_bias:
                bias_rows = args[i]
                i += 1
            gmask0 = gtrans = tok_cls = ginit = None
            if with_dfa:
                gmask0, gtrans, tok_cls, ginit = args[i: i + 4]
                i += 4
            out = admit_cached_paged(params, cache, counts, rngs, bias,
                                     d_tokens, d_positions, pages, table_row,
                                     tail_toks, full_toks, aux, samp_pack,
                                     bias_rows=bias_rows, gmask0=gmask0,
                                     gtrans=gtrans, tok_cls=tok_cls,
                                     ginit=ginit, d_gstate=d_gstate)
            if draft:
                flen = aux[0:1] + aux[3:4]
                _, dks, dvs = llama.prefill(dcfg, dparams, full_toks, flen,
                                            ep=self.plan.ep)
                dcache = llama.write_prefill_to_cache(
                    dcache, dks[:, 0:1], dvs[:, 0:1], aux[1]
                )
                out = out + (dcache,)
            return out

        donate = (1, 2, 3, 4, 5, 6)
        if with_dfa:
            donate = donate + (7,)
        if draft:
            donate = donate + (7 + (1 if with_dfa else 0) + 1,)  # dcache
        fn = self._jit(program, "admit_cached_paged",
                        donate_argnums=donate)
        if not build_only:
            self._admit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # Chunked ragged prefill (EngineConfig.prefill_chunk — ISSUE 2)
    #
    # A long admission runs as a sequence of fixed-size chunk programs the
    # loop interleaves with decode blocks: chunk c attends the rows already
    # written ([0, offset) — the slot's pages under the paged pool, a
    # bucketed read window of the slot's dense rows otherwise) plus itself
    # causally, and writes its K/V straight into the cache. The FINAL chunk
    # additionally samples the first token and installs the slot's device
    # state — after it, the request decodes like any other admission. At
    # most ONE chunk dispatch is in flight at a time, so decode blocks slot
    # between consecutive chunks on the device stream instead of queueing
    # behind a monolithic multi-second prefill program.
    # ------------------------------------------------------------------ #

    @property
    def _chunk_size(self) -> int:
        """Effective chunk size: 0 when chunking is off or prefill runs
        DENSE ring attention (sp>1 without a paged pool — the dense chunk
        path has no ring variant). Paged sp>1 engines chunk as usual: the
        chunk programs themselves ring-shard over sp (ISSUE 14)."""
        if self._ring_mesh is not None and self._sp_chunk_mesh is None:
            return 0
        return self.ecfg.prefill_chunk

    def _chunk_admit_rows(self, total_len: int, match_len: int) -> int:
        """Exact KV rows a chunked admission writes: the matched prefix,
        the whole mid chunks (C tokens each), and the final tail's bucket
        (padding rows included) — what on-demand page allocation must
        cover at _chunk_start."""
        C = self.ecfg.prefill_chunk
        rem = total_len - match_len
        mids = 0
        while rem > C:
            rem -= C
            mids += 1
        return match_len + mids * C + self._bucket_for(max(rem, 1))

    def _chunkable(self, request: GenRequest, match_len: int = 0) -> bool:
        """Whether this request's (un-cached) prompt tail should admit
        through the chunked state machine. Multimodal/mrope prompts keep
        the single-shot path (their injection points assume a whole-prompt
        prefill); draft engines mirror _cached_admit_ok's exclusions (no
        grammar/logprob final-chunk variant composes with the draft)."""
        C = self._chunk_size
        if not C:
            return False
        if request.image_embeds is not None or request.mrope_positions is not None:
            return False
        if request.adapter is not None:
            # Adapter prompts admit single-shot: the chunk mid/final
            # programs carry no per-slot lora operand (ISSUE 10 keeps the
            # runtime-LoRA surface to admission + decode blocks).
            return False
        if (self._paged and self.cfg.attention_window
                and (match_len or len(request.prompt_ids) > C)):
            # Windowed+sink paged serving (ISSUE 14): EVERY admission that
            # attends past one chunk — long prompts and all prefix hits —
            # must run the chunk programs' masked prefix walk, the one
            # numeric path the window semantics are defined on. (The
            # single-shot cached path would gather_pages a possibly-huge
            # span densely AND attend it unmasked.) Short cold prompts
            # (<= prefill_chunk <= attention_window) stay single-shot:
            # every query's window covers the whole prompt, so the mask is
            # a no-op there and the full-attention program is exact.
            return True
        if len(request.prompt_ids) - match_len <= C:
            return False
        if self.draft_cfg is not None and (
            request.grammar is not None or request.logprobs > 0
        ):
            return False
        return True

    def _get_chunk_mid(self, tb: int, pwin: Optional[int]):
        """Mid-chunk program: prefill `tb` chunk tokens against the rows
        already written for the slot and write their K/V directly into the
        cache — no sampling, no unembed (the final chunk does both). pwin
        is the dense prefix read window (None under the paged pool, where
        the chunk walks a page-table operand instead — the slot's real
        table rides here while h_ptable keeps the slot on SCRATCH).

        d_positions rides through so the program can pin the idle slot's
        carried position at S-1: decode blocks write EVERY slot's row each
        step, and a stale carry from the slot's previous tenant could
        otherwise land inside the rows this prefill is writing. (Paged idle
        writes already resolve through SCRATCH; the pin is harmless there.)
        """
        key = ("chunk", tb, pwin)
        fn = self._block_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        S = self.ecfg.max_seq

        if self._paged:
            from localai_tpu.ops import ptable as _pt

            def chunk(params, cache, d_positions, toks, aux, table_row):
                # aux: [chunk_len, slot, offset] i32
                _, cache = llama.prefill_chunk_paged(
                    cfg, params, toks, aux[0:1], aux[2:3], cache,
                    _pt.batch_row(table_row), ep=self.plan.ep,
                    paged_impl=self.ecfg.paged_kernel, with_logits=False,
                    mesh=self._op_mesh, kv_scale=self._kv_scales,
                    sp_mesh=self._sp_chunk_mesh,
                )
                d_positions = d_positions.at[aux[1]].set(S - 1)
                return cache, d_positions, aux
        else:
            L, K = cfg.num_layers, cfg.cache_kv_heads
            kd, vd = cfg.cache_k_dim, cfg.cache_v_dim

            def chunk(params, cache, d_positions, toks, aux):
                slot = aux[1]
                # Read-side slice of the slot's written prefix; rows past
                # aux[2] are garbage and masked inside prefill_tail.
                with scope("attention/mix"):  # the prefix the mixer reads
                    pk = jax.lax.dynamic_slice(
                        cache.k, (0, slot, 0, 0, 0), (L, 1, pwin, K, kd))
                    pv = jax.lax.dynamic_slice(
                        cache.v, (0, slot, 0, 0, 0), (L, 1, pwin, K, vd))
                _, tks, tvs = llama.prefill_tail(
                    cfg, params, toks, aux[0:1], aux[2:3], pk, pv,
                    ep=self.plan.ep, mesh=self._op_mesh,
                )
                cache = llama.write_rows_to_cache(cache, slot, tks, tvs, aux[2])
                d_positions = d_positions.at[slot].set(S - 1)
                return cache, d_positions, aux

        fn = self._jit(chunk, "prefill_chunk", donate_argnums=(1, 2))
        self._block_cache[key] = fn
        return fn

    def _get_chunk_pin(self):
        """Set one slot's carried decode position to S-1. Dispatched at
        dense chunk start so every decode block dispatched afterwards writes
        the idle slot's (discarded) row at S-1 instead of at a stale carry
        from the slot's previous tenant — a stale position inside the copied
        prefix span would corrupt rows no later chunk rewrites."""
        fn = self._block_cache.get(("chunk-pin",))
        if fn is None:
            S = self.ecfg.max_seq

            def pin(d_positions, slot):
                return d_positions.at[slot].set(S - 1)

            fn = self._jit(pin, "chunk_pin", donate_argnums=(0,))
            self._block_cache[("chunk-pin",)] = fn
        return fn

    def _get_span_copy(self, pb: int):
        """Copy a stored dense prefix span into a slot's cache rows [0, pb)
        — seeds a chunked prefix-hit admission (the chunk programs then
        read the prefix from the slot itself)."""
        key = ("span-copy", pb)
        fn = self._block_cache.get(key)
        if fn is None:
            def copy(cache, pk, pv, slot):
                k = jax.lax.dynamic_update_slice(
                    cache.k, pk.astype(cache.k.dtype), (0, slot, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    cache.v, pv.astype(cache.v.dtype), (0, slot, 0, 0, 0))
                return llama.KVCache(k=k, v=v)

            fn = self._jit(copy, "span_copy", leaf="attention/cache_write",
                           donate_argnums=(0,))
            self._block_cache[key] = fn
        return fn

    def _get_chunk_final_paged(self, tb: int, fbp: int, has_bias: bool,
                               with_topk: bool, with_lp: bool,
                               with_dfa=False, draft: bool = False,
                               with_logits: bool = False):
        """Final chunk of a paged chunked admission: prefill the last
        ≤prefill_chunk tokens direct-to-page (prefix attention walks the
        slot's OWN pages — no gather_pages materialization of a 32k
        prefix), sample the first token and install the full per-slot
        device state. _get_admit_cached_paged's contract with
        prefill_chunk_paged in place of gather_pages + prefill_tail; `aux`
        is [4] i32 (tail_len, slot, seed, prefix_len)."""
        key = ("chunk-final", tb, fbp, has_bias, with_topk, with_lp,
               with_dfa, draft, with_logits)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        V = cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)
        tok_v = min(getattr(self.tokenizer, "vocab_size", V) or V, V)

        def admit_chunk(params, cache, counts, rngs, bias, d_tokens,
                        d_positions, table_row, tail_toks, full_toks, aux,
                        samp_pack, bias_rows=None, gmask0=None, gtrans=None,
                        tok_cls=None, ginit=None, d_gstate=None):
            tail_len, slot, seed, plen = aux[0], aux[1], aux[2], aux[3]
            samp = SamplingParams(
                temperature=samp_pack[0], top_k=samp_pack[1].astype(jnp.int32),
                top_p=samp_pack[2], min_p=samp_pack[3], repeat_penalty=samp_pack[4],
                presence_penalty=samp_pack[5], frequency_penalty=samp_pack[6],
            )
            from localai_tpu.ops import ptable as _pt

            logits, cache = llama.prefill_chunk_paged(
                cfg, params, tail_toks, aux[0:1], aux[3:4], cache,
                _pt.batch_row(table_row), ep=self.plan.ep,
                paged_impl=self.ecfg.paged_kernel, mesh=self._op_mesh,
                kv_scale=self._kv_scales, sp_mesh=self._sp_chunk_mesh,
            )
            with scope("sample"):  # everything after the logits
                fvalid = (jnp.arange(fbp)[None, :] < (plen + tail_len)).astype(jnp.int32)
                rows = jnp.zeros((1, V), jnp.int32)
                rows = rows.at[jnp.arange(1)[:, None], full_toks].add(fvalid)
                brows = bias_rows if has_bias else jnp.zeros((1, V), jnp.float32)
                if tok_v < V:
                    from localai_tpu.ops.sampling import NEG_INF

                    brows = jnp.where(jnp.arange(V)[None, :] >= tok_v, NEG_INF, brows)
                keys0 = jax.vmap(jax.random.key)(aux[2:3].astype(jnp.uint32))
                draws = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(keys0)
                srows = brows + gmask0 if with_dfa else brows
                toks = sample(logits, draws, samp, rows, srows)  # [1]
                rows = rows.at[jnp.arange(1), toks].add(1)
                tk = jax.lax.top_k(logits + brows, K)[1] if with_topk else None
                lp = None
                if with_lp:
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32) + brows, axis=-1)
                    lp_vals, lp_ids = jax.lax.top_k(logp, LK)
                    tok_lp = jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
                    lp = (tok_lp, lp_ids, lp_vals)
            with scope("sample"):  # the slot's sampling state
                counts = counts.at[slot].set(rows[0])
                rngs = rngs.at[slot].set(keys0[0])
                bias = bias.at[slot].set(brows[0])
            d_tokens = d_tokens.at[slot].set(toks[0])
            d_positions = d_positions.at[slot].set(plen + tail_len)
            out = (cache, counts, rngs, bias, d_tokens, d_positions, toks, tk, lp)
            if with_dfa:
                gnext = self._dfa_advance(with_dfa, gtrans, tok_cls, ginit, toks)
                out = out + (d_gstate.at[slot].set(gnext[0]),)
            if with_logits:
                out = out + (logits,)
            return out

        dcfg = self.draft_cfg

        def program(*args):
            # Positional assembly mirrors _get_admit_cached_paged with
            # (table_row,) in place of (pages, table_row): [7 state]
            # [d_gstate?] [dparams, dcache?] [table_row, tail, full, aux,
            # samp] [bias_rows?] [dfa 4?].
            i = 7
            params, cache, counts, rngs, bias, d_tokens, d_positions = args[:7]
            d_gstate = None
            if with_dfa:
                d_gstate = args[i]
                i += 1
            dparams = dcache = None
            if draft:
                dparams, dcache = args[i: i + 2]
                i += 2
            table_row, tail_toks, full_toks, aux, samp_pack = args[i: i + 5]
            i += 5
            bias_rows = None
            if has_bias:
                bias_rows = args[i]
                i += 1
            gmask0 = gtrans = tok_cls = ginit = None
            if with_dfa:
                gmask0, gtrans, tok_cls, ginit = args[i: i + 4]
                i += 4
            out = admit_chunk(params, cache, counts, rngs, bias, d_tokens,
                              d_positions, table_row, tail_toks, full_toks,
                              aux, samp_pack, bias_rows=bias_rows,
                              gmask0=gmask0, gtrans=gtrans, tok_cls=tok_cls,
                              ginit=ginit, d_gstate=d_gstate)
            if draft:
                # The draft's small dense cache has no chunked/paged span to
                # reuse — prefill it with the full prompt in one program
                # (same trade as the cached-admit draft branch).
                flen = aux[0:1] + aux[3:4]
                _, dks, dvs = llama.prefill(dcfg, dparams, full_toks, flen,
                                            ep=self.plan.ep)
                dcache = llama.write_prefill_to_cache(
                    dcache, dks[:, 0:1], dvs[:, 0:1], aux[1]
                )
                out = out + (dcache,)
            return out

        donate = (1, 2, 3, 4, 5, 6)
        if with_dfa:
            donate = donate + (7,)
        if draft:
            donate = donate + (7 + (1 if with_dfa else 0) + 1,)  # dcache
        fn = self._jit(program, "prefill_chunk_final",
                        donate_argnums=donate)
        self._admit_cache[key] = fn
        return fn

    def _chunk_start(self, request: GenRequest, handle: RequestHandle,
                     hit: Optional[tuple]) -> bool:
        """Reserve a slot (and pages) for a chunked admission and enqueue
        its state. Returns False on pool pressure (request requeued — the
        caller must stop planning this round, backpressure)."""
        t0 = time.monotonic()
        ids = request.prompt_ids
        slot_idx = next(i for i, s in enumerate(self.slots) if s is None)
        entry, match_len = (hit if hit is not None else (None, 0))
        if entry is not None and self._paged and "hk" in entry:
            # Host-tier span: swap it back into pool pages before mapping.
            # A failed promotion (pool pressure) degrades to a full chunked
            # admission rather than busy-requeueing on the same hit.
            entry = self._prefix_promote(entry)
            if entry is None:
                match_len = 0
        if entry is not None and self._paged and not any(
            e is entry for e in self._prefix_entries
        ):
            entry, match_len = None, 0  # evicted between find and start
        table_row: Optional[np.ndarray] = None
        if self._paged:
            page = self.ecfg.kv_page_size
            shared = entry["pages"][: match_len // page] if entry is not None else []
            # On-demand: pages covering exactly the rows the chunk programs
            # will write (mid chunks are exact C-token writes; only the
            # final tail is bucketed) + headroom; decode growth takes over
            # after activation.
            rows = self._chunk_admit_rows(len(ids), match_len)
            base = -(-rows // page) - len(shared)
            worst = max(rows, min(len(ids) + request.max_new_tokens,
                                  self.ecfg.max_seq))
            cap = max(base, -(-worst // page) - len(shared))
            fresh = min(base + self.ecfg.kv_page_headroom, cap)
            if len(self._free_pages) < fresh:
                self._prefix_evict_for_pages(
                    fresh, protect=[entry] if entry is not None else []
                )
            table_row = self._pages_alloc(
                slot_idx, fresh, shared=shared,
                shared_tps=(entry.get("tps")
                            if (entry is not None and self._hier) else None),
            )
            if table_row is None:
                with self._pending_lock:
                    self._pending.appendleft((request, handle))
                return False
            # Keep the slot on SCRATCH until the final chunk activates it:
            # decode blocks write every slot every step, and the real table
            # must not be reachable while this prefill owns the pages. The
            # SAVED row (flat page row / hier L1 directory row) rides the
            # chunk dispatches instead.
            if self._hier:
                self.h_l1[slot_idx, :] = self._scratch_tp
            else:
                self.h_ptable[slot_idx] = self._scratch_page
        else:
            # Dense cache: pin the idle slot's carried position FIRST (see
            # _get_chunk_pin — blocks dispatched from here on must not stamp
            # stale-position rows into the slot). Paged idle writes resolve
            # through SCRATCH instead, no pin needed.
            with self._phases.call("call/chunk_pin"):
                self.d_positions = self._get_chunk_pin()(
                    self.d_positions, jnp.int32(slot_idx)
                )
            if entry is not None:
                # Seed the slot's rows [0, pb) from the stored span so the
                # chunk programs read the prefix from the slot itself.
                with self._phases.call("call/span_copy"):
                    self.cache = self._get_span_copy(entry["pb"])(
                        self.cache, entry["k"], entry["v"],
                        jnp.int32(slot_idx)
                    )
        if entry is not None:
            for idx, e in enumerate(self._prefix_entries):
                if e is entry:
                    self._prefix_entries.pop(idx)
                    self._prefix_entries.insert(0, entry)
                    break
            self.m_prefix_hits += 1
            self.m_prefix_tokens += match_len
            self._jnote("prefix_hit", rid=handle.rid, slot=slot_idx,
                        a=float(match_len))
            tr = handle.trace
            if tr is not None:
                tr.note("prefix_hit", matched_tokens=match_len)
        self.slots[slot_idx] = _Slot(
            request=request, handle=handle, prompt_len=len(ids), t_submit=t0,
            sched_rows=len(ids),
        )
        self._chunkings.append({
            "request": request, "handle": handle, "slot": slot_idx,
            "ids": ids, "offset": match_len, "t0": t0,
            "table_row": table_row,
        })
        return True

    def _advance_chunked(self) -> bool:
        """Dispatch the next chunk of the oldest in-progress chunked
        admission — at most one chunk in flight engine-wide, so decode
        blocks interleave between chunks on the device stream. Runs on the
        loop thread only."""
        if not self._chunkings:
            return False
        if any(e.kind == "chunk" for e in self._inflight):
            return False
        st = self._chunkings[0]
        slot_idx = st["slot"]
        if st["handle"].cancelled.is_set():
            self._chunkings.pop(0)
            st["handle"]._q.put(TokenEvent(kind="done", finish_reason="stop"))
            self._fork_group_requeue(st["request"])
            self._release(slot_idx)
            return True
        C = self.ecfg.prefill_chunk
        rem = len(st["ids"]) - st["offset"]
        try:
            if rem > C:
                self._dispatch_chunk_mid(st, C)
                st["offset"] += C
            else:
                self._chunkings.pop(0)
                self._dispatch_chunk_final(st)
        except Exception as e:  # noqa: BLE001 — fail the request, keep serving
            log.exception("chunked prefill dispatch failed (slot %d)", slot_idx)
            # Identity scan, not `in`: dict == would compare the numpy
            # table_row arrays elementwise.
            self._chunkings = [s for s in self._chunkings if s is not st]
            st["handle"]._q.put(
                TokenEvent(kind="error", error=f"{type(e).__name__}: {e}")
            )
            self._fork_group_fail(st["request"], TokenEvent(
                kind="error", error=f"{type(e).__name__}: {e}"
            ))
            self._release(slot_idx)
        return True

    def _dispatch_chunk_mid(self, st: dict, n: int) -> None:
        offset, slot_idx = st["offset"], st["slot"]
        toks = np.zeros((1, n), np.int32)
        toks[0] = st["ids"][offset: offset + n]
        aux = np.asarray([n, slot_idx, offset], np.int32)
        with self._phases.call("dispatch/prefill_chunk", m=1, bucket=n,
                               tokens=n):
            if self._paged:
                fn = self._get_chunk_mid(n, None)
                out = fn(self.params, self.cache, self.d_positions,
                         self._upload(toks), self._upload(aux),
                         self._ptable_device_row(st["table_row"]))
            else:
                pwin = self._bucket_for(max(offset, 1))
                fn = self._get_chunk_mid(n, pwin)
                out = fn(self.params, self.cache, self.d_positions,
                         self._upload(toks), self._upload(aux))
        self.cache, self.d_positions, marker = out
        self.m_prefill_chunks += 1
        self._count_admit(n, n)
        self._jnote("chunk", rid=st["handle"].rid, slot=slot_idx, a=float(n))
        self._track(_Entry(kind="chunk", toks=marker, tk=None,
                           gen=list(self._slot_gen)))

    def _dispatch_chunk_final(self, st: dict) -> None:
        """The last ≤prefill_chunk tokens: prefill + first-token sample +
        slot activation, mirroring _dispatch_admit_cached's glue with the
        already-resident rows as the prefix."""
        request, handle, slot_idx = st["request"], st["handle"], st["slot"]
        ids, offset, t0 = st["ids"], st["offset"], st["t0"]
        V = self.cfg.vocab_size
        tail = ids[offset:]
        tb = self._bucket_for(len(tail))
        fbp = self._bucket_for(len(ids))
        draft = self.draft_cfg is not None
        # Fork primaries (ISSUE 18) need the final-position logits so
        # _fork_after_admit can sample each sibling's first token from the
        # same distribution a clone admission would have produced.
        with_logits = (request.fork_group is not None and self._paged
                       and not draft)
        dfa_tables = None
        if (request.grammar is not None and request.resume is None
                and request.grammar_pos == 0):
            dfa_tables = self._dfa_for(request)
        with_dfa = self._dfa_mode_of(dfa_tables)
        with_topk = request.grammar is not None and not with_dfa
        with_lp = request.logprobs > 0
        has_bias = bool(request.logit_bias)
        tail_toks = np.zeros((1, tb), np.int32)
        tail_toks[0, : len(tail)] = tail
        full_toks = np.zeros((1, fbp), np.int32)
        full_toks[0, : len(ids)] = ids
        aux = np.zeros((4,), np.int32)
        aux[0] = len(tail)
        aux[1] = slot_idx
        aux[2] = (
            request.seed & 0x7FFFFFFF if request.seed is not None
            else int.from_bytes(os.urandom(4), "little") & 0x7FFFFFFF
        )
        aux[3] = offset
        samp_pack = np.zeros((7, 1), np.float32)
        for fi, kf in enumerate(_SAMPLING_FIELDS):
            samp_pack[fi, 0] = getattr(request, kf)
        if self._paged:
            fn = self._get_chunk_final_paged(tb, fbp, has_bias, with_topk,
                                             with_lp, with_dfa, draft,
                                             with_logits=with_logits)
            # Publish the real table NOW (loop thread): blocks dispatched
            # from here on — all strictly after this program on the device
            # stream — may read and write the slot's pages.
            if self._hier:
                self.h_l1[slot_idx] = st["table_row"]
            else:
                self.h_ptable[slot_idx] = st["table_row"]
            args = (self._ptable_device_row(st["table_row"]),)
        else:
            pb = self._bucket_for(max(offset, 1))
            with self._phases.call("call/snapshot"):
                pk, pv = self._get_snapshot(pb)(self.cache,
                                                jnp.int32(slot_idx))
            fn = self._get_admit_cached(pb, tb, fbp, has_bias, with_topk,
                                        with_lp, with_dfa, draft)
            args = (pk, pv)
        args = args + (
            self._upload(tail_toks), self._upload(full_toks), self._upload(aux),
            self._upload(samp_pack),
        )
        if has_bias:
            bias_rows = np.zeros((1, V), np.float32)
            for tid, bval in request.logit_bias.items():
                if 0 <= int(tid) < V:
                    bias_rows[0, int(tid)] = bval
            args = args + (self._upload(bias_rows),)
        if with_dfa:
            host = dfa_tables["host"]
            row = np.unpackbits(
                host.mask_bits[host.init_state], bitorder="little"
            )[:V].astype(bool)
            gmask0 = np.where(row, 0.0, -1e30).astype(np.float32)[None, :]
            ginit = np.full((1,), host.init_state, np.int32)
            args = args + (
                self._upload(gmask0), self._dfa_table(dfa_tables, with_dfa),
                dfa_tables["tok_cls"], self._upload(ginit),
            )
        state = (
            self.params, self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions,
        )
        if with_dfa:
            state = state + (self.d_gstate,)
        if draft:
            state = state + (self.draft_params, self.d_cache)
        with self._phases.call(
                "dispatch/prefill_chunk_final" if self._paged
                else "dispatch/admit_cached", m=1, bucket=tb, tokens=len(tail)):
            out = fn(*state, *args)
        self._count_admit(tb, len(tail))
        (
            self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, toks, tk, lp,
        ) = out[:9]
        if with_dfa:
            self.d_gstate = out[9]
        elif draft:
            self.d_cache = out[9]
        if with_logits:
            self._fork_logits = out[-1]
        self._host_copy_async(toks)
        for kf in _SAMPLING_FIELDS:
            self.h_sampling[kf][slot_idx] = getattr(request, kf)
        if self._mrope:
            self.h_rope_delta[slot_idx] = 0  # chunked path is text-only
        self._slot_gen[slot_idx] += 1
        self.slots[slot_idx] = _Slot(
            request=request, handle=handle, prompt_len=len(ids), scheduled=1,
            t_submit=t0, dfa=with_dfa, sched_rows=len(ids),
        )
        self._apply_resume(slot_idx)
        self.h_active[slot_idx] = True
        self.h_override_mask[slot_idx] = False
        self.h_gmask[slot_idx] = 1.0 if with_dfa else 0.0
        self.m_prefill_chunks += 1
        self.m_chunked_admits += 1
        self._jnote("admitted", rid=handle.rid, slot=slot_idx,
                    a=float(len(ids)), b=1.0)
        self._track(_Entry(
            kind="admit", toks=toks, tk=tk, lp=lp, gen=list(self._slot_gen),
            items=[(slot_idx, request, handle, len(ids), t0)],
        ))
        self._plan_dirty()
        self._last_admit_t = time.monotonic()
        self._defer_prefix_save(slot_idx, ids, len(ids))
        if request.fork_group is not None:
            # Fork the freshly-activated slot NOW, before any decode block
            # can touch its control row (the fork program reconstructs the
            # prompt bincount from counts[slot] - the first sampled token).
            self._fork_after_admit(slot_idx, request, dfa_tables)

    # ------------------------------------------------------------------ #
    # Tree-batched parallel sampling: CoW slot forking (ISSUE 18,
    # docs/TREE_SAMPLING.md)
    # ------------------------------------------------------------------ #

    def _get_fork_sample(self, nb: int, with_topk: bool, with_lp: bool,
                         with_dfa):
        """Fork-sample program: give `nb` sibling branches their own control
        rows off a freshly-admitted source slot, sampling each branch's
        first token from the source's stashed final-position logits.

        Byte-identity contract (the fork-vs-clone tests pin this): every
        per-branch op below replays _get_admit's m=1 recipe exactly — the
        prompt bincount is recovered as counts[src] minus the source's first
        sampled token (integer math, bit-exact), the RNG chain is
        key(seed_b) folded at 0, the sampling mask is the source's bias row
        (fork groups share logit_bias by construction) plus the grammar
        start mask — so a greedy or seeded fork emits the same bytes the
        branch's own clone admission would have.

        aux [3, nb] i32: row 0 = dst slots, row 1 = seeds, row 2 = src slot
        (broadcast). samp_pack [7, nb] f32 — per-branch sampling params.
        The branch loop is unrolled (nb is small and static)."""
        key = ("fork", nb, with_topk, with_lp, with_dfa)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn
        V = self.cfg.vocab_size
        K = min(self.GRAMMAR_TOPK, V)
        LK = min(self.LOGPROB_TOPK, V)

        def fork_fn(*args):
            counts, rngs, bias, d_tokens, d_positions = args[:5]
            logits, aux, samp_pack = args[5:8]
            gmask0 = gtrans = tok_cls = ginit = d_gstate = None
            if with_dfa:
                gmask0, gtrans, tok_cls, ginit, d_gstate = args[8:13]
            src = aux[2, 0]
            # counts[src] = prompt bincount + first sampled token (admit
            # added it); subtracting d_tokens[src] recovers the bincount a
            # clone admission would have computed. Integer ops — bit-exact.
            rows0 = counts[src].at[d_tokens[src]].add(-1)
            brow = bias[src]
            pos = d_positions[src]
            if with_topk:
                tk_row = jax.lax.top_k(logits + brow[None], K)[1]
            if with_lp:
                logp = jax.nn.log_softmax(
                    logits.astype(jnp.float32) + brow[None], axis=-1
                )
                lp_vals, lp_ids = jax.lax.top_k(logp, LK)
            toks_l = []
            tk_l: list = []
            lp_tok: list = []
            for b in range(nb):
                samp = SamplingParams(
                    temperature=samp_pack[0, b:b + 1],
                    top_k=samp_pack[1, b:b + 1].astype(jnp.int32),
                    top_p=samp_pack[2, b:b + 1],
                    min_p=samp_pack[3, b:b + 1],
                    repeat_penalty=samp_pack[4, b:b + 1],
                    presence_penalty=samp_pack[5, b:b + 1],
                    frequency_penalty=samp_pack[6, b:b + 1],
                )
                keys0 = jax.vmap(jax.random.key)(
                    aux[1, b:b + 1].astype(jnp.uint32)
                )
                draws = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys0)
                srow = brow[None] + gmask0 if with_dfa else brow[None]
                tok = sample(logits, draws, samp, rows0[None], srow)  # [1]
                dst = aux[0, b]
                counts = counts.at[dst].set(rows0.at[tok[0]].add(1))
                rngs = rngs.at[dst].set(keys0[0])
                bias = bias.at[dst].set(brow)
                d_tokens = d_tokens.at[dst].set(tok[0])
                d_positions = d_positions.at[dst].set(pos)
                toks_l.append(tok[0])
                if with_topk:
                    tk_l.append(tk_row[0])
                if with_lp:
                    lp_tok.append(logp[0, tok[0]])
                if with_dfa:
                    gnext = self._dfa_advance(
                        with_dfa, gtrans, tok_cls, ginit, tok
                    )
                    d_gstate = d_gstate.at[dst].set(gnext[0])
            toks = jnp.stack(toks_l)
            tk = jnp.stack(tk_l) if with_topk else None
            lp = None
            if with_lp:
                lp = (jnp.stack(lp_tok),
                      jnp.broadcast_to(lp_ids, (nb, LK)),
                      jnp.broadcast_to(lp_vals, (nb, LK)))
            out = (counts, rngs, bias, d_tokens, d_positions, toks, tk, lp)
            if with_dfa:
                out = out + (d_gstate,)
            return out

        donate = (0, 1, 2, 3, 4) + ((12,) if with_dfa else ())
        fn = self._jit(fork_fn, "fork", leaf="sample", donate_argnums=donate)
        self._admit_cache[key] = fn
        return fn

    def _get_fork_page_copy(self):
        """One-page KV copy (CoW materialization of a fork's partially-
        filled boundary page): both lineages would write rows of that page,
        so the branch gets a private copy before its first decode write.
        Quantized caches copy the stored bytes verbatim — the KV scales are
        a global per-head constant (self._kv_scales), not per-page state."""
        key = ("fork-page-copy",)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn

        def copy_page(cache, srcp, dstp):
            k = cache.k.at[:, dstp].set(cache.k[:, srcp])
            v = cache.v.at[:, dstp].set(cache.v[:, srcp])
            return llama.KVCache(k=k, v=v)

        fn = self._jit(copy_page, "page_copy", leaf="attention/cache_write",
                       donate_argnums=(0,))
        self._admit_cache[key] = fn
        return fn

    def _get_fork_ctrl_copy(self, with_dfa: bool):
        """Mid-stream fork control copy (Engine.fork): duplicate one slot's
        control row into a free slot, decorrelating the branch's RNG chain
        by folding `salt` into the source's key. aux [3] i32: src, dst,
        salt. Mid-stream forks are deliberately NOT clone-byte-compatible —
        there is no clone equivalent of an in-flight RNG chain."""
        key = ("fork-ctrl-copy", bool(with_dfa))
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn

        def ctrl_copy(*args):
            counts, rngs, bias, d_tokens, d_positions, aux = args[:6]
            src, dst, salt = aux[0], aux[1], aux[2]
            counts = counts.at[dst].set(counts[src])
            rngs = rngs.at[dst].set(jax.random.fold_in(rngs[src], salt))
            bias = bias.at[dst].set(bias[src])
            d_tokens = d_tokens.at[dst].set(d_tokens[src])
            d_positions = d_positions.at[dst].set(d_positions[src])
            out = (counts, rngs, bias, d_tokens, d_positions)
            if with_dfa:
                d_gstate = args[6]
                out = out + (d_gstate.at[dst].set(d_gstate[src]),)
            return out

        donate = (0, 1, 2, 3, 4) + ((6,) if with_dfa else ())
        fn = self._jit(ctrl_copy, "ctrl_copy", donate_argnums=donate)
        self._admit_cache[key] = fn
        return fn

    def _fork_supported(self, requests: list[GenRequest]) -> bool:
        """Whether a request group can admit via slot forking. The shared
        prefill means every branch must agree on everything that shapes the
        prompt's KV and sampling mask: same adapter (KV rows are tenant-
        specific under LoRA), same logit_bias, grammar all-or-none (the
        machines themselves must be equivalent — the HTTP layer builds each
        branch's machine from the same spec). Draft-model engines, dense
        caches, multimodal and resume requests always clone."""
        if not (self._paged and self.ecfg.fork_sampling):
            return False
        if self.draft_cfg is not None or self.cfg.is_hybrid:
            return False  # a hybrid model's branches clone: no state copy
        r0 = requests[0]
        b0 = r0.logit_bias or {}
        g0 = r0.grammar is not None
        for r in requests:
            if r.image_embeds is not None or r.mrope_positions is not None:
                return False
            if r.resume is not None:
                return False
            if r.adapter != r0.adapter:
                return False
            if (r.logit_bias or {}) != b0 or (r.grammar is not None) != g0:
                return False
        return True

    def _pages_fork_need(self, request: GenRequest) -> int:
        """Fresh pages ONE forked branch claims at fork time: the partially-
        filled boundary page (materialized CoW copy) if the prompt doesn't
        end on a page boundary, plus decode headroom — capped so headroom
        never books past what the branch could ever write beyond the shared
        span. Everything else is addref'd from the source."""
        page = self.ecfg.kv_page_size
        plen = len(request.prompt_ids)
        partial = 1 if plen % page else 0
        cap = max(partial, self._pages_worst(request) - plen // page)
        return min(partial + self.ecfg.kv_page_headroom, cap)

    def _branch_handle(self, request: GenRequest) -> RequestHandle:
        """Handle for a fork-group branch: the same rid/trace/deadline
        wiring submit() gives the primary. The branch never sits in
        _pending itself — its lifecycle rides the primary's fork_group
        until fork admission (or detach requeues it as an ordinary
        independent entry)."""
        handle = RequestHandle()
        handle.t_submit = time.monotonic()
        handle.rid = request.request_id or f"h{id(handle):x}"
        if request.request_id or request.traceparent:
            tr = otrace.RequestTrace(
                handle.rid, traceparent=request.traceparent,
                engine=self.cfg.name,
            )
            handle.trace = tr
            handle._q.trace = tr
            otrace.STORE.register(tr)
            tr.note("queued", prompt_tokens=len(request.prompt_ids))
        deadline_s = request.deadline_s or self.ecfg.deadline_s
        if deadline_s > 0:
            handle.deadline = handle.t_submit + deadline_s
            self._deadlines.push(handle.deadline)
        if self.ecfg.queue_timeout_s > 0:
            self._deadlines.push(handle.t_submit + self.ecfg.queue_timeout_s)
        self._jstage("queued", rid=handle.rid,
                     a=float(len(request.prompt_ids)))
        return handle

    def submit_fork(self, requests: list[GenRequest]) -> list[RequestHandle]:
        """Admit a group of same-prompt requests paying ONE prefill
        (ISSUE 18, docs/TREE_SAMPLING.md): the first request is the
        primary — it rides the ordinary admission path (batched, chunked,
        or prefix-cached) — and the rest fork off its slot right after the
        prefill, addref'ing its KV pages. Engines that can't fork (dense
        cache, draft model, fork_sampling off, mixed adapters/bias/grammar)
        degrade to N independent submits — same API, same outputs, N×
        prefill. Returns one handle per request, in order."""
        if not requests:
            return []
        if len(requests) == 1:
            return [self.submit(requests[0])]
        p0 = list(requests[0].prompt_ids)
        for r in requests[1:]:
            if list(r.prompt_ids) != p0:
                raise ValueError(
                    "submit_fork requires identical prompts across the group"
                )
        if not self._fork_supported(requests):
            return [self.submit(r) for r in requests]
        branches = []
        limit = self.ecfg.max_seq - 1
        for r in requests[1:]:
            ids = list(r.prompt_ids)
            if len(ids) > limit:
                # Mirror submit()'s truncation so branch state matches the
                # primary's post-truncation prompt.
                ids = [ids[0]] + ids[-(limit - 1):]
            rr = dataclasses.replace(r, prompt_ids=ids, fork_group=None)
            branches.append((rr, self._branch_handle(rr)))
        primary = dataclasses.replace(requests[0], fork_group=branches)
        try:
            h0 = self.submit(primary)
        except BaseException as e:
            # The branch handles never reach the loop — close them here so
            # no caller (or trace) is left open.
            for _r, bh in branches:
                bh._q.put(TokenEvent(
                    kind="error", error=f"fork submit failed: {e}"
                ))
            raise
        if self._loop_dead is not None:
            # submit() observed (or raced) a dead loop: it errored the
            # primary itself, but the loop will never detach the group.
            # Duplicate terminals on a branch are harmless.
            for _r, bh in branches:
                bh._q.put(TokenEvent(kind="error", error=self._loop_dead))
        return [h0] + [bh for _r, bh in branches]

    def _fork_group_fail(self, request: GenRequest, event: TokenEvent) -> None:
        """Propagate a fork primary's terminal error to every branch handle
        (the branches never reach _pending, so no other path would close
        them)."""
        group = request.fork_group
        if not group:
            return
        request.fork_group = None
        for _r, h in group:
            h._q.put(dataclasses.replace(event))

    def _fork_group_requeue(self, request: GenRequest) -> None:
        """The fork primary was cancelled before admission: its LIVE
        branches requeue as ordinary independent entries (each pays its own
        prefill — correctness over the lost sharing), cancelled ones get
        their terminal now. Takes _pending_lock — callers inside the
        admission scan's locked region defer the call until the lock is
        released."""
        group = request.fork_group
        if not group:
            return
        request.fork_group = None
        live = []
        for r, h in group:
            if h.cancelled.is_set():
                h._q.put(TokenEvent(kind="done", finish_reason="stop"))
            else:
                live.append((r, h))
        if not live:
            return
        with self._pending_lock:
            dead = self._loop_dead
            if dead is None:
                self._pending.extend(live)
        if dead is not None:
            for _r, h in live:
                h._q.put(TokenEvent(kind="error", error=dead))
            return
        self._wake.set()

    # thread: engine-loop-only
    def _fork_after_admit(self, src_slot: int, request: GenRequest,
                          dfa_tables: Optional[dict] = None) -> None:
        """Admit the primary's fork_group branches by forking its freshly-
        admitted slot (the tentpole): each branch addrefs the full prompt
        pages ([0, plen // page) — whole directory chunks share by addref
        under hierarchical tables), gets a private copy of the partially-
        filled boundary page, and samples its own first token from the
        primary's stashed final-position logits — byte-identical to what
        that branch's clone admission would have produced. Branches that
        cannot fork (no free slot, pool pressure, adapter pin failure,
        injected slot_fork fault, or no stashed logits) degrade to ordinary
        clone admission via the pending queue: strictly slower, never
        wrong. Must run before any decode block touches the source's
        control row. Loop thread only."""
        branches = request.fork_group
        request.fork_group = None
        logits = self._fork_logits
        self._fork_logits = None
        if not branches:
            return
        page = self.ecfg.kv_page_size
        plen = len(request.prompt_ids)
        nfull = plen // page
        partial = plen % page
        src_pages = list(self._slot_pages[src_slot]) if self._paged else []
        shared = src_pages[:nfull]
        clones: list[tuple[GenRequest, RequestHandle]] = []
        forked: list[tuple[int, GenRequest, RequestHandle, int]] = []
        copies: list[tuple[int, int]] = []
        taken: set[int] = set()
        for r, h in branches:
            if h.cancelled.is_set():
                h._q.put(TokenEvent(kind="done", finish_reason="stop"))
                continue
            dst = next((i for i, s in enumerate(self.slots)
                        if s is None and i not in taken), None)
            if dst is None or logits is None or not self._paged:
                clones.append((r, h))
                continue
            try:
                # Injected fork failure (testing/faults): the branch
                # degrades to clone admission, the journal records it.
                faults.fire("slot_fork")
            except faults.InjectedFault as e:
                self._jnote_fault(e)
                clones.append((r, h))
                continue
            row = self._pages_alloc(
                dst, self._pages_fork_need(r), shared=shared,
                shared_tps=(self._slot_tps[src_slot] if self._hier else None),
            )
            if row is None:
                clones.append((r, h))
                continue
            arow = 0
            if r.adapter:
                try:
                    arow = self._adapter_acquire(r.adapter)
                except Exception:  # noqa: BLE001 — degrade this branch only
                    self._pages_free(dst)
                    clones.append((r, h))
                    continue
            if partial:
                copies.append((src_pages[nfull],
                               self._slot_pages[dst][nfull]))
            taken.add(dst)
            forked.append((dst, r, h, arow))
        if forked:
            try:
                self._dispatch_fork(src_slot, plen, forked, copies, logits,
                                    dfa_tables)
                self.m_forks += len(forked)
            except Exception as e:  # noqa: BLE001 — degrade, keep serving
                log.exception(
                    "fork dispatch failed — degrading %d branches to clone "
                    "admission", len(forked)
                )
                self._jnote("error", a=float(len(forked)))
                self._jnote_fault(e)
                for dst, r, h, arow in forked:
                    self._pages_free(dst)
                    if arow:
                        self._adapter_unpin(arow)
                    clones.append((r, h))
        if clones:
            self.m_fork_clone_fallbacks += len(clones)
            with self._pending_lock:
                self._pending.extend(clones)
            self._wake.set()

    # thread: engine-loop-only
    def _dispatch_fork(self, src_slot: int, plen: int, forked: list,
                       copies: list, logits, dfa_tables) -> None:
        """Device work + slot installs for _fork_after_admit's fork set.
        Boundary-page copies dispatch FIRST so device-stream order makes
        them visible to every later branch read."""
        nb = len(forked)
        with_dfa = self._dfa_mode_of(dfa_tables)
        with_topk = any(r.grammar is not None
                        for _d, r, _h, _a in forked) and not with_dfa
        with_lp = any(r.logprobs > 0 for _d, r, _h, _a in forked)
        aux = np.zeros((3, nb), np.int32)
        samp_pack = np.zeros((7, nb), np.float32)
        aux[2] = src_slot
        for j, (dst, r, _h, _arow) in enumerate(forked):
            aux[0, j] = dst
            aux[1, j] = (
                r.seed & 0x7FFFFFFF if r.seed is not None
                else int.from_bytes(os.urandom(4), "little") & 0x7FFFFFFF
            )
            for fi, kf in enumerate(_SAMPLING_FIELDS):
                samp_pack[fi, j] = getattr(r, kf)
        if copies:
            cp = self._get_fork_page_copy()
            with self._phases.call("call/page_copy", pages=len(copies)):
                for sp, dp in copies:
                    self.cache = cp(self.cache, jnp.int32(sp), jnp.int32(dp))
        args = (logits, self._upload(aux), self._upload(samp_pack))
        if with_dfa:
            host = dfa_tables["host"]
            V = self.cfg.vocab_size
            rowb = np.unpackbits(
                host.mask_bits[host.init_state], bitorder="little"
            )[:V].astype(bool)
            gmask0 = np.where(rowb, 0.0, -1e30).astype(np.float32)[None, :]
            ginit = np.full((1,), host.init_state, np.int32)
            args = args + (
                self._upload(gmask0), self._dfa_table(dfa_tables, with_dfa),
                dfa_tables["tok_cls"], self._upload(ginit), self.d_gstate,
            )
        fn = self._get_fork_sample(nb, with_topk, with_lp, with_dfa)
        with self._phases.call("call/fork"):
            out = fn(self.counts, self.rngs, self.bias, self.d_tokens,
                     self.d_positions, *args)
        (self.counts, self.rngs, self.bias, self.d_tokens,
         self.d_positions, toks, tk, lp) = out[:8]
        if with_dfa:
            self.d_gstate = out[8]
        self._host_copy_async(toks)
        t0 = time.monotonic()
        items = []
        for j, (dst, r, h, arow) in enumerate(forked):
            for kf in _SAMPLING_FIELDS:
                self.h_sampling[kf][dst] = getattr(r, kf)
            if self._mrope:
                self.h_rope_delta[dst] = 0  # fork groups are text-only
            self._slot_gen[dst] += 1
            self.slots[dst] = _Slot(
                request=r, handle=h, prompt_len=plen, scheduled=1,
                t_submit=(h.t_submit or t0), dfa=with_dfa, sched_rows=plen,
            )
            self.h_active[dst] = True
            self.h_override_mask[dst] = False
            self.h_gmask[dst] = 1.0 if with_dfa else 0.0
            self.h_adapter[dst] = arow
            items.append((dst, r, h, plen, t0))
            self._note_admitted(h)
            self._jnote("forked", rid=h.rid, slot=dst, a=float(plen),
                        b=float(src_slot))
            tr = h.trace
            if tr is not None:
                tr.note("forked", source_slot=src_slot)
        self._track(_Entry(kind="admit", toks=toks, tk=tk, lp=lp,
                           gen=list(self._slot_gen), items=items))
        self._plan_dirty()
        self._last_admit_t = time.monotonic()

    def fork(self, handle: RequestHandle, n: int = 1,
             seeds: Optional[list] = None) -> list[RequestHandle]:
        """Fork a LIVE stream `n` ways at its current position — the agent
        fan-out seam (ISSUE 18): each branch inherits the source's prompt
        and generation so far (KV shared CoW on paged engines, boundary
        page copied) and continues decoding with a decorrelated RNG chain.
        Branch streams emit only continuation tokens. Executes on the
        engine loop at its next quiesce point (nothing in flight); if the
        source finishes or is cancelled first, branch handles get an error
        event. Dense engines degrade to recompute-clone admission (the
        prompt + generation re-prefill as a fresh request). Mid-stream
        forks are NOT clone-byte-compatible by design — there is no clone
        equivalent of an in-flight RNG chain. Thread-safe."""
        if n < 1:
            raise ValueError("fork n must be >= 1")
        if self.cfg.is_hybrid:
            raise ValueError(
                f"{self.cfg.name} keeps a per-slot {rstate.what(self.cfg)} "
                f"({self.cfg.recurrent_kind} layers): forking a live stream "
                "would need a copy of the source's state row, which this "
                "engine does not make")
        if seeds is not None and len(seeds) != n:
            raise ValueError(f"fork got {len(seeds)} seeds for n={n}")
        out = []
        for _ in range(n):
            bh = RequestHandle()
            bh.t_submit = time.monotonic()
            bh.rid = f"h{id(bh):x}"
            out.append(bh)
        entry = (handle, list(seeds) if seeds is not None else [None] * n,
                 out)
        with self._fork_lock:
            self._fork_requests.append(entry)
        # Dead-loop check AFTER the append: the guard drains _fork_requests
        # under _fork_lock after setting _loop_dead, so if we read None
        # here the drain is still ahead of our entry and will error it. If
        # we read dead, the drain may have run either side of our append —
        # unstage if still staged and post the terminals ourselves
        # (duplicate terminals on a handle are harmless).
        dead = self._loop_dead
        if dead is not None:
            with self._fork_lock:
                if entry in self._fork_requests:
                    self._fork_requests.remove(entry)
            for bh in out:
                bh._q.put(TokenEvent(kind="error", error=dead))
            return out
        self._wake.set()
        return out

    # thread: engine-loop-only
    def _service_forks(self) -> None:
        """Execute staged mid-stream forks (Engine.fork) at a quiesce point:
        nothing in flight and no chunked prefill, so every slot's device
        control row exactly matches its host view (scheduled ==
        len(generated)) and copying a row forks the stream at a well-
        defined position. The loop holds new admissions and block
        dispatches while forks are staged, so the wait is bounded by the
        in-flight pipeline draining."""
        if not self._fork_requests:
            return
        if self._inflight or self._chunkings:
            return
        with self._fork_lock:
            staged, self._fork_requests = self._fork_requests, []
        for src_handle, seeds, handles in staged:
            src = next((i for i, s in enumerate(self.slots)
                        if s is not None and s.handle is src_handle), None)
            if src is None:
                for bh in handles:
                    bh._q.put(TokenEvent(
                        kind="error",
                        error="fork source is not an active stream",
                    ))
                continue
            self._fork_midstream(src, seeds, handles)

    # thread: engine-loop-only
    def _fork_midstream(self, src: int, seeds: list, handles: list) -> None:
        """Fork one live slot for _service_forks. Paged: addref the full
        pages of the [0, boundary) span, copy the boundary page, copy the
        control row with a salted RNG fold. Dense: recompute-clone — the
        prompt + generation requeue as a fresh prefill whose stream
        continues from the fork point."""
        slot = self.slots[src]
        req0 = slot.request
        gen = list(slot.generated)
        boundary = slot.prompt_len + max(0, len(gen) - 1)
        page = self.ecfg.kv_page_size
        for j, bh in enumerate(handles):
            seed = seeds[j]
            salt = (int(seed) & 0x7FFFFFFF if seed is not None
                    else int.from_bytes(os.urandom(4), "little") & 0x7FFFFFFF)
            if not self._paged:
                ids = list(req0.prompt_ids) + gen
                r = dataclasses.replace(
                    req0, prompt_ids=ids, fork_group=None, resume=None,
                    seed=(int(seed) if seed is not None else req0.seed),
                    max_new_tokens=max(1, req0.max_new_tokens - len(gen)),
                )
                with self._pending_lock:
                    self._pending.append((r, bh))
                self.m_fork_clone_fallbacks += 1
                self._wake.set()
                continue
            dst = next((i for i, s in enumerate(self.slots) if s is None),
                       None)
            nfull = boundary // page
            partial = boundary % page
            src_pages = list(self._slot_pages[src])
            need = min((1 if partial else 0) + self.ecfg.kv_page_headroom,
                       max(1 if partial else 0,
                           self._pages_worst(req0) - nfull))
            row = None
            if dst is not None:
                row = self._pages_alloc(
                    dst, need, shared=src_pages[:nfull],
                    shared_tps=(self._slot_tps[src] if self._hier else None),
                )
            if row is None:
                bh._q.put(TokenEvent(
                    kind="error", error="fork failed: no slot/page capacity"
                ))
                continue
            arow = 0
            if req0.adapter:
                try:
                    arow = self._adapter_acquire(req0.adapter)
                except Exception:  # noqa: BLE001 — fail this branch only
                    self._pages_free(dst)
                    bh._q.put(TokenEvent(
                        kind="error", error="fork failed: adapter pin"
                    ))
                    continue
            try:
                rg = (copy.deepcopy(req0.grammar)
                      if req0.grammar is not None else None)
            except Exception:  # noqa: BLE001 — fail this branch only
                # Unpin before _pages_free: the free can raise (page
                # geometry validation) and would strand the pin.
                if arow:
                    self._adapter_unpin(arow)
                self._pages_free(dst)
                bh._q.put(TokenEvent(
                    kind="error", error="fork failed: grammar state copy"
                ))
                continue
            if partial:
                sp, dp = src_pages[nfull], self._slot_pages[dst][nfull]
                cp = self._get_fork_page_copy()
                with self._phases.call("call/page_copy", pages=1):
                    self.cache = cp(self.cache, jnp.int32(sp), jnp.int32(dp))
            fn = self._get_fork_ctrl_copy(bool(slot.dfa))
            aux = np.asarray([src, dst, salt], np.int32)
            state = (self.counts, self.rngs, self.bias, self.d_tokens,
                     self.d_positions)
            with self._phases.call("call/ctrl_copy"):
                if slot.dfa:
                    out = fn(*state, self._upload(aux), self.d_gstate)
                    self.d_gstate = out[5]
                else:
                    out = fn(*state, self._upload(aux))
            (self.counts, self.rngs, self.bias, self.d_tokens,
             self.d_positions) = out[:5]
            r = dataclasses.replace(
                req0, prompt_ids=list(req0.prompt_ids), fork_group=None,
                resume=None, grammar=rg,
                seed=(int(seed) if seed is not None else req0.seed),
            )
            for kf in _SAMPLING_FIELDS:
                self.h_sampling[kf][dst] = getattr(r, kf)
            if self._mrope:
                self.h_rope_delta[dst] = self.h_rope_delta[src]
            self._slot_gen[dst] += 1
            ns = _Slot(
                request=r, handle=bh, prompt_len=slot.prompt_len,
                generated=list(gen), emitted_len=slot.emitted_len,
                scheduled=len(gen), t_submit=bh.t_submit, dfa=slot.dfa,
                sched_rows=boundary,
            )
            ns.t_first = time.monotonic()
            self.slots[dst] = ns
            self.h_active[dst] = True
            self.h_override_tok[dst] = self.h_override_tok[src]
            self.h_override_mask[dst] = self.h_override_mask[src]
            self.h_gmask[dst] = self.h_gmask[src]
            self.h_adapter[dst] = arow
            self.m_forks += 1
            self._note_admitted(bh)
            self._jnote("forked", rid=bh.rid, slot=dst, a=float(boundary),
                        b=float(src))
        self._plan_dirty()

    # ------------------------------------------------------------------ #
    # Prompt/prefix KV cache (host side)
    # ------------------------------------------------------------------ #

    @property
    def _prefix_enabled(self) -> bool:
        # Composes with draft models too (r5): the cached-admit program
        # prefills the DRAFT with the full prompt (its small cache has no
        # span to reuse) while the target still skips its prefix compute —
        # llama.cpp serves cache_prompt + draft together (grpc-server.cpp:125).
        # A hybrid model's prefix would need a snapshot of the recurrent
        # state at the span's end: reuse is off for it (engine/state.py).
        return self.ecfg.prefix_cache_entries > 0 and not self.cfg.is_hybrid

    def _cached_admit_ok(self, request: GenRequest) -> bool:
        """Whether this request may admit through the prefix-cache shortcut.
        Grammar/logprob requests on DRAFT engines have no draft-composed
        cached variant — they must be decided at PLANNING time (treated as
        misses) so the paged planner budgets FULL pages; deciding at
        dispatch would leave a tail-only budget for a full admission
        (pool-gate break / requeue livelock). Adapter requests never use
        the prefix cache in either direction — their wk/wv deltas make the
        cached K/V rows tenant-specific (ISSUE 10)."""
        if request.adapter is not None:
            return False
        if self.draft_cfg is None:
            return True
        return request.grammar is None and request.logprobs <= 0

    def _prefix_find(self, prompt_ids: list[int]):
        """Longest-common-prefix match against the stored spans. Returns
        (entry, match_len) or None. A partial match is fine — any prefix of a
        cached span is valid KV for that prefix (causality). Under the paged
        cache the match rounds DOWN to a page boundary: shared pages are
        mapped read-only into the new slot's table, and the tail prefill must
        only ever write fresh pages."""
        if not self._prefix_enabled or len(prompt_ids) < 2:
            return None
        prompt = np.asarray(prompt_ids, np.int32)
        cap = len(prompt_ids) - 1  # always prefill >= 1 tail token for logits
        best, best_len = None, 0
        # Device tier first, then the host tier (spilled spans) — a host
        # hit only wins on a strictly longer match, since it must swap its
        # pages back in before it can be mapped.
        tiers = [self._prefix_entries]
        if self._paged:
            tiers.append(self._prefix_host)
        for tier in tiers:
            for entry in tier:
                n = min(entry["valid"], cap, len(entry["key"]))
                if n <= best_len:
                    continue
                eq = entry["key"][:n] == prompt[:n]
                match = n if eq.all() else int(np.argmin(eq))
                if self._paged:
                    match = (match // self.ecfg.kv_page_size) * self.ecfg.kv_page_size
                if match > best_len:
                    best, best_len = entry, match
        if best is None or best_len < max(self.ecfg.prefix_cache_min, 1):
            return None
        # The tail must fit between the prefix and the cache end.
        tb = self._bucket_for(len(prompt_ids) - best_len)
        if best_len + tb > self.ecfg.max_seq:
            return None
        return best, best_len

    def _get_snapshot(self, pb: int):
        fn = self._snap_cache.get(pb)
        if fn is None:
            L = self.cfg.num_layers
            K = self.cfg.cache_kv_heads
            kd, vd = self.cfg.cache_k_dim, self.cfg.cache_v_dim

            def snap(cache, slot):
                k = jax.lax.dynamic_slice(
                    cache.k, (0, slot, 0, 0, 0), (L, 1, pb, K, kd))
                v = jax.lax.dynamic_slice(
                    cache.v, (0, slot, 0, 0, 0), (L, 1, pb, K, vd))
                return k, v

            fn = self._jit(snap, "snapshot", leaf="attention/cache_write")
            self._snap_cache[pb] = fn
        return fn

    def _snapshot_rows(self, slot_idx: int, rows: int) -> Optional[tuple]:
        """Dense cache: (pb, k, v), a device-to-device copy of the slot's
        first `rows` rows at their bucket, or None over the byte budget."""
        pb = self._bucket_for(rows)
        if self._prefix_span_bytes(pb) > self.ecfg.prefix_cache_bytes:
            return None
        with self._phases.call("call/snapshot"):
            k, v = self._get_snapshot(pb)(self.cache, jnp.int32(slot_idx))
        return pb, k, v

    def _prefix_save(self, slot_idx: int, key_tokens, valid_len: int,
                     min_extend: int = 0,
                     parked: Optional[_Parked] = None) -> None:
        """Store the slot's KV rows [0:valid_len] under `key_tokens`.

        Called right after an admission dispatch (prompt KV) and at finish
        (prompt+generated KV — the next chat turn's prefix). Dense cache:
        device-to-device snapshot slice. Paged cache: NO copy — the entry
        takes a refcount on the slot's FULL pages below valid_len
        (copy-on-write sharing; later admissions map them read-only and
        prefill tails into fresh pages). Never blocks the loop. With
        `parked` the rows are a parked tenant's (_park): its own pages and
        directory, or the dense snapshot taken when it left the index."""
        if not self._prefix_enabled or valid_len < self.ecfg.prefix_cache_min:
            return
        if self._paged:
            page = self.ecfg.kv_page_size
            # Full pages only — matches always round DOWN to a page boundary
            # (_prefix_find), so pinning a partial last page would withhold
            # it from the pool without it ever being mappable.
            n_pages = valid_len // page
            valid_len = n_pages * page
            if valid_len < self.ecfg.prefix_cache_min or n_pages == 0:
                return
            page_bytes = self._prefix_span_bytes(page)
            if n_pages * page_bytes > self.ecfg.prefix_cache_bytes:
                return
        key = np.asarray(key_tokens, np.int32)[:valid_len]
        # Skip saves that barely extend existing coverage (min_extend > 0 —
        # the ADMISSION-side callers). Every cached HIT used to re-save its
        # freshly-assembled prompt span: the new span out-keyed the stored
        # one by a couple of tail tokens, so each warm admit queued a
        # full-bucket device snapshot (dense) or re-pinned the span's pages
        # (paged) ahead of the next request's program — asymmetric standing
        # device work a cold MISS never paid, which is what put BENCH_r04's
        # dense prefix_ttft_speedup at 0.34 (a HIT slower than a MISS). An
        # admission-side span must now add at least prefix_cache_min tokens
        # of new coverage to be worth storing — the same floor that gates a
        # span's minimum size. Finish-time saves pass min_extend=0: the
        # generated-KV suffix is NEW information (multi-turn reuse) however
        # short it is.
        if min_extend:
            cov = 0
            for e in self._prefix_entries:
                n = min(e["valid"], valid_len)
                if n <= cov:
                    continue
                eq = e["key"][:n] == key[:n]
                cov = max(cov, n if eq.all() else int(np.argmin(eq)))
            if cov and valid_len - cov < min_extend:
                return
        if parked is not None and parked.spill:
            # Restoring writes the slot's table, which is the next tenant's
            # by now: degrade as on pool pressure below, no save.
            return
        if (self._paged and parked is None
                and self._slot_spill[slot_idx]):
            # Cold pages were spilled off-device — a span can only pin HOT
            # pages. Restore them byte-exactly first; on pool pressure (or
            # an injected page_spill fault) skip the save: the request is
            # already finished, a missing span just means re-prefill later.
            try:
                restored = self._restore_spilled(slot_idx)
            except Exception as e:  # noqa: BLE001 — degrade to no-save
                self._jnote_fault(e)
                if not isinstance(e, faults.InjectedFault):
                    log.exception("spill restore failed (slot %d)", slot_idx)
                restored = False
            if not restored:
                return
        # Skip if an existing entry already covers this span; drop entries
        # this span subsumes.
        kept = []
        for e in self._prefix_entries:
            n = min(len(key), e["valid"])
            if e["valid"] >= valid_len and (e["key"][:n] == key[:n]).all():
                return  # covered by a longer (or equal) stored span
            if e["valid"] <= valid_len and (e["key"][:e["valid"]] == key[:e["valid"]]).all():
                self._prefix_drop(e)
                continue  # subsumed by the new span
            kept.append(e)
        if self._paged and self._prefix_host:
            # Host-tier spans the new device span subsumes are dead weight.
            keep_h = []
            for e in self._prefix_host:
                if (e["valid"] <= valid_len
                        and (e["key"][:e["valid"]] == key[:e["valid"]]).all()):
                    with self._host_lock:
                        self._host_bytes -= e["bytes"]
                    continue
                keep_h.append(e)
            with self._host_lock:
                self._prefix_host = keep_h
        if self._paged:
            pages = (parked.pages if parked
                     else self._slot_pages[slot_idx])[: n_pages]
            if len(pages) < n_pages:
                self._prefix_entries = kept
                return  # slot reservation shorter than the span (shouldn't happen)
            self._pages_addref(pages)
            entry_new = {"key": key, "valid": valid_len, "pages": list(pages)}
            if self._hier:
                # Directory half of CoW span sharing (ISSUE 14): the entry
                # pins the slot's table pages covering the span, so later
                # admissions map the L1 chunks by addref.
                entry_new["tps"] = self._entry_tps(slot_idx, n_pages, parked)
            kept.insert(0, entry_new)
            while len(kept) > self.ecfg.prefix_cache_entries:
                self._prefix_drop(kept.pop())
            budget = self.ecfg.prefix_cache_bytes // max(
                self._prefix_span_bytes(self.ecfg.kv_page_size), 1
            )
            total = 0
            for idx, e in enumerate(kept):
                total += len(e["pages"])
                if total > budget:
                    for drop in kept[idx:]:
                        self._prefix_drop(drop)
                    del kept[idx:]
                    break
            self._prefix_entries = kept
            return
        snap = (parked.snap if parked is not None
                else self._snapshot_rows(slot_idx, valid_len))
        if snap is None:  # over the byte budget (a parked one: when it left)
            self._prefix_entries = kept
            return
        pb, k, v = snap
        kept.insert(0, {"key": key, "valid": valid_len, "pb": pb, "k": k, "v": v})
        del kept[self.ecfg.prefix_cache_entries:]
        total = 0
        for idx, e in enumerate(kept):
            total += self._prefix_span_bytes(e["pb"])
            if total > self.ecfg.prefix_cache_bytes:
                del kept[idx:]
                break
        self._prefix_entries = kept

    def _prefix_drop(self, entry: dict) -> None:
        """Release one prefix entry's resources (paged entries hold page
        refcounts — and table-page refcounts under hierarchical tables;
        dense snapshots just GC)."""
        if self._paged and "pages" in entry:
            self._pages_release(entry["pages"])
            entry["pages"] = []
        if self._hier and entry.get("tps"):
            self._tp_release(entry["tps"])
            entry["tps"] = []

    def _prefix_evict_for_pages(self, need: int,
                                protect: Optional[list] = None) -> None:
        """Free pool pages by evicting LRU prefix entries until `need` pages
        are available (or only protected entries remain). Live requests
        always outrank cached spans — a span can be re-prefilled, a queued
        request cannot be served otherwise. `protect` lists entries this
        admission round is about to map (evicting them would turn the hits
        into misses that need MORE pages)."""
        protect = protect or []
        idx = len(self._prefix_entries) - 1
        while len(self._free_pages) < need and idx >= 0:
            e = self._prefix_entries[idx]
            if any(e is p for p in protect):
                idx -= 1
                continue
            # Second chance in host RAM: a later hit swaps the span back in
            # instead of re-prefilling it (budget permitting).
            self._prefix_spill(e)
            self._prefix_drop(e)
            self._prefix_entries.pop(idx)
            idx -= 1

    def _prefix_spill(self, entry: dict) -> None:
        """Copy an about-to-be-evicted span's pages to the host tier (the
        prefix cache's second level, bounded by kv_swap_bytes)."""
        if not self._paged or self.ecfg.kv_swap_bytes <= 0:
            return
        pages = entry.get("pages")
        if not pages:
            return
        sz = len(pages) * self._page_bytes()
        if not self._host_make_room(sz):
            return
        hk, hv = self._swap_out_pages(pages)
        self._prefix_host.insert(0, {
            "key": entry["key"], "valid": entry["valid"],
            "hk": hk, "hv": hv, "bytes": sz,
        })
        with self._host_lock:
            self._host_bytes += sz
        self.m_kv_swap_bytes_out += sz

    def _prefix_promote(self, hentry: dict) -> Optional[dict]:
        """Swap a host-tier span back into pool pages and re-enter it in
        the device tier (serving a hit from RAM instead of re-prefilling).
        Returns the device entry, or None when the pool cannot cover the
        span right now (the hit degrades to a miss)."""
        npg = hentry["hk"].shape[1]
        # Claim the entry first so _host_make_room (run for spills during
        # the eviction below) can never evict the span we are promoting.
        with self._host_lock:
            self._prefix_host = [e for e in self._prefix_host
                                 if e is not hentry]
            self._host_bytes -= hentry["bytes"]
        if len(self._free_pages) < npg:
            self._prefix_evict_for_pages(npg)
        pages = self._pages_claim(npg)
        if pages is None:
            self._prefix_host.insert(0, hentry)  # back to the tier, LRU-bumped
            with self._host_lock:
                self._host_bytes += hentry["bytes"]
            return None
        self._swap_in_pages(pages, hentry["hk"], hentry["hv"])
        entry = {"key": hentry["key"], "valid": hentry["valid"],
                 "pages": pages}
        if self._hier:
            entry["tps"] = self._entry_tps_for_pages(pages)
        self._prefix_entries.insert(0, entry)
        while len(self._prefix_entries) > self.ecfg.prefix_cache_entries:
            dead = self._prefix_entries.pop()
            self._prefix_spill(dead)
            self._prefix_drop(dead)
        self.m_kv_swap_bytes_in += hentry["bytes"]
        self.m_prefix_host_hits += 1
        return entry

    def _prefix_span_bytes(self, pb: int) -> int:
        """Device bytes of one stored span (k+v) with a pb-row sequence.
        Sized by the cache's STORAGE dtype — under fp8 KV the budget must
        count half-size rows, or spans would be refused/evicted at half the
        configured capacity."""
        cfg = self.cfg
        return (
            cfg.cache_layers * pb * cfg.cache_kv_heads
            * (cfg.cache_k_dim + cfg.cache_v_dim)
            * jnp.dtype(self.ecfg.cache_dtype(cfg.dtype)).itemsize
        )

    # ------------------------------------------------------------------ #
    # Cluster KV-span transfer (ISSUE 6, docs/CLUSTER.md): a prefill-role
    # replica exports a stored prefix span as a versioned frame; a decode-
    # role replica imports it into its host tier and the next admission of
    # that prompt hits it exactly like a locally-spilled span (promote →
    # copy-on-write page mapping → tail-only prefill).
    # ------------------------------------------------------------------ #

    def _span_geometry(self) -> dict:
        """The cache geometry a span frame must match to be importable —
        same layers/heads/dims/page size/storage dtype, or the raw bytes
        would reinterpret into garbage KV."""
        cfg = self.cfg
        return {
            "layers": cfg.num_layers,
            "kv_heads": cfg.cache_kv_heads,
            "k_dim": cfg.cache_k_dim,
            "v_dim": cfg.cache_v_dim,
            "page_size": self.ecfg.kv_page_size,
            "dtype": str(jnp.dtype(self.ecfg.cache_dtype(cfg.dtype))),
        }

    def export_prefix_span(self, prompt_ids, max_bytes: int = 0,
                           trace_id: str = ""):
        """Serialize the longest stored device-tier span matching this
        prompt (page-aligned, like every prefix mapping) as a transfer
        frame, or None when nothing exportable is stored. Read-only and
        callable from any thread: the entry list reference is snapshotted,
        the page gather reads an immutable cache snapshot, and the entry's
        continued presence is re-checked after the gather so a span evicted
        mid-export is discarded instead of shipped stale."""
        if not self._paged or not self._prefix_enabled:
            return None
        from localai_tpu.cluster import transfer

        prompt = np.asarray(list(prompt_ids), np.int32)
        page = self.ecfg.kv_page_size
        # Runs on exporter (HTTP/pump) threads while the loop mutates the
        # tier: list() is an atomic C-level copy, iterating the live list
        # here raced loop-side appends/evictions (shared-state-race).
        entries = list(self._prefix_entries)
        best, best_len = None, 0
        for entry in entries:
            if not entry.get("pages"):
                continue
            n = min(entry["valid"], len(prompt), len(entry["key"]))
            eq = entry["key"][:n] == prompt[:n]
            match = n if eq.all() else int(np.argmin(eq))
            match = (match // page) * page
            if match > best_len:
                best, best_len = entry, match
        if best is None or best_len < page:
            return None
        pages = list(best["pages"][: best_len // page])
        hk, hv = self._swap_out_pages(pages)
        if not any(e is best for e in list(self._prefix_entries)):
            return None  # evicted mid-gather — pages may have been recycled
        frame = transfer.encode_span(
            key=best["key"][:best_len], valid=best_len, hk=hk, hv=hv,
            geom=self._span_geometry(),
            max_bytes=max_bytes or transfer.DEFAULT_MAX_BYTES,
            trace_id=trace_id,
        )
        self.m_span_exports += 1
        # Any-thread caller → staged journal emit (ISSUE 11).
        self._jstage("span_export", rid=trace_id, a=float(best_len))
        return frame

    def import_span_bytes(self, frame: bytes, max_bytes: int = 0,
                          timeout_s: float = 10.0) -> bool:
        """Land a transfer frame in this engine's host prefix tier. Safe
        from any thread: the decoded entry stages in _span_inbox and the
        loop thread merges it (host-tier state is loop-owned); this call
        waits for that merge so the caller can submit the decode request
        immediately after. Returns False on any rejection — the caller's
        contract is recompute, never a wedged handoff."""
        if not self._paged or not self._prefix_enabled:
            return False
        from localai_tpu.cluster import transfer

        try:
            key, valid, hk, hv = transfer.decode_span(
                frame, geom=self._span_geometry(),
                max_bytes=max_bytes or transfer.DEFAULT_MAX_BYTES,
            )
        except transfer.SpanTransferError as e:
            log.warning("span import rejected: %s", e)
            # Caller-thread increment races the loop's drain-side rejects
            # — same lock on both sides (shared-state-race).
            with self._span_inbox_lock:
                self.m_span_import_rejects += 1
            return False
        entry = {
            "key": key, "valid": valid, "hk": hk, "hv": hv,
            "bytes": hk.shape[1] * self._page_bytes(),
            # Trace continuity (ISSUE 11): the frame header carries the
            # exporter's trace id so the import journals under it.
            "trace": transfer.span_meta(frame).get("trace", ""),
        }
        done = threading.Event()
        with self._span_inbox_lock:
            self._span_inbox.append((entry, done))
        self._wake.set()
        self.start()
        if not done.wait(timeout_s):
            return False
        return bool(entry.get("accepted"))

    def _drain_span_inbox(self) -> None:
        """Loop thread: merge staged span imports into the host tier under
        the shared kv_swap_bytes budget. A span that does not fit (or that
        an existing entry already covers) is rejected, not queued — the
        importer falls back to recompute."""
        if not self._span_inbox:  # unlocked peek — len() is atomic
            return
        with self._span_inbox_lock:
            staged = list(self._span_inbox)
            self._span_inbox[:] = []
        for entry, done in staged:
            try:
                covered = any(
                    e["valid"] >= entry["valid"]
                    and (np.asarray(e["key"][: entry["valid"]])
                         == entry["key"][: entry["valid"]]).all()
                    for tier in (self._prefix_entries, self._prefix_host)
                    for e in tier
                )
                if covered:
                    entry["accepted"] = True  # already served locally
                    self.m_span_imports += 1
                    self._jnote("span_import", rid=entry.get("trace", ""),
                                a=float(entry["valid"]))
                elif self._host_make_room(entry["bytes"]):
                    self._prefix_host.insert(0, entry)
                    with self._host_lock:
                        self._host_bytes += entry["bytes"]
                    entry["accepted"] = True
                    self.m_span_imports += 1
                    self._jnote("span_import", rid=entry.get("trace", ""),
                                a=float(entry["valid"]))
                else:
                    with self._span_inbox_lock:
                        self.m_span_import_rejects += 1
            finally:
                done.set()

    def _spawn_admit_compile(self, key: tuple, full_args: tuple) -> None:
        """AOT-compile a cached-admit program shape on a daemon thread and
        publish it into _admit_cache; until then hits of this shape fall
        back to full admission (prefix_admit_async_compile). Avals are
        taken from the actual dispatch args, so the compiled executable is
        byte-compatible with the live serving state."""
        with self._admit_compile_lock:
            if key in self._admit_cache or key in self._admit_compiling:
                return
            self._admit_compiling.add(key)

        def aval(x):
            # Shardings must ride into the AOT avals: params/cache are
            # device_put with NamedShardings on multi-device plans, and an
            # executable compiled for default placement raises an input-
            # sharding mismatch on its first real call (ADVICE r5 medium).
            # Only COMMITTED arrays pin one, though: the per-dispatch
            # control arrays are uncommitted jnp.asarray results that a
            # live call moves to wherever the program runs, and pinning
            # them to the default device makes every tp>1 lowering fail
            # with "incompatible devices" (PR 21, found by chip_smoke.py).
            committed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(
                np.shape(x), x.dtype,
                sharding=x.sharding if committed else None,
            )

        avals = jax.tree.map(aval, full_args)

        def work():
            try:
                if key[0] == "cached":
                    fn = self._get_admit_cached(*key[1:], build_only=True)
                else:
                    fn = self._get_admit_cached_paged(*key[1:], build_only=True)
                with self.mesh:
                    compiled = fn.lower(*avals).compile()
                with self._admit_compile_lock:
                    self._admit_cache.setdefault(key, compiled)
            except Exception:  # noqa: BLE001 — hits keep falling back
                log.exception("background cached-admit compile failed (%s)",
                              key)
            finally:
                with self._admit_compile_lock:
                    self._admit_compiling.discard(key)

        threading.Thread(target=work, daemon=True,
                         name="prefix-admit-compile").start()

    def _dispatch_admit_cached(self, request: GenRequest, handle: RequestHandle,
                               slot_idx: int, entry: dict, match_len: int,
                               dfa_tables: Optional[dict] = None,
                               with_logits: bool = False):
        """Admission via the prompt cache: ship only the tail tokens.
        Returns True (admitted), False (stale hit / pool pressure — paged
        callers requeue), or "full" (cached program still compiling in the
        background — caller must serve via full admission NOW)."""
        t0 = time.monotonic()
        V = self.cfg.vocab_size
        ids = request.prompt_ids
        tail = ids[match_len:]
        tb = self._bucket_for(len(tail))
        draft = self.draft_cfg is not None
        if not self._cached_admit_ok(request):
            # Unreachable from the engine loop (planning and _dispatch_admit
            # both gate on _cached_admit_ok); direct callers get the same
            # full-admission answer.
            return "full"
        if self._paged and self.cfg.attention_window:
            # Windowed+sink paged serving routes every hit through the
            # chunk programs (_chunkable); a hit found late (saved after
            # planning) degrades to full single-shot admission — by then
            # the prompt is <= prefill_chunk <= attention_window, where
            # the window mask is a no-op and full attention is exact.
            return "full"
        fbp = self._bucket_for(len(ids))  # full-prompt bucket (count row/draft)
        paged_alloc: Optional[np.ndarray] = None
        if self._paged and "hk" in entry:
            # Host-tier hit: swap the span back into pool pages first. A
            # failed promotion (pool pressure) serves via full admission —
            # requeueing would re-find the same host hit and busy-spin.
            promoted = self._prefix_promote(entry)
            if promoted is None:
                return "full"
            entry = promoted
        if self._paged:
            # The entry must still be live (pressure eviction may have
            # released its pages between the find and this dispatch).
            if not any(e is entry for e in self._prefix_entries):
                return False
            page = self.ecfg.kv_page_size
            shared = entry["pages"][: match_len // page]
            # On-demand (ISSUE 3): only the tail bucket + headroom; decode
            # growth allocates the rest as the context actually extends.
            fresh = self._pages_needed_cached(request, match_len)
            paged_alloc = self._pages_alloc(
                slot_idx, fresh, shared=shared,
                shared_tps=(entry.get("tps") if self._hier else None),
            )
            if paged_alloc is None:
                return False  # pool pressure — full admission will backpressure
        tail_toks = np.zeros((1, tb), np.int32)
        tail_toks[0, : len(tail)] = tail
        full_toks = np.zeros((1, fbp), np.int32)
        full_toks[0, : len(ids)] = ids
        aux = np.zeros((4,), np.int32)
        aux[0] = len(tail)
        aux[1] = slot_idx
        aux[2] = (
            request.seed & 0x7FFFFFFF if request.seed is not None
            else int.from_bytes(os.urandom(4), "little") & 0x7FFFFFFF
        )
        aux[3] = match_len
        samp_pack = np.zeros((7, 1), np.float32)
        for fi, kf in enumerate(_SAMPLING_FIELDS):
            samp_pack[fi, 0] = getattr(request, kf)
        has_bias = bool(request.logit_bias)
        with_dfa = self._dfa_mode_of(dfa_tables)
        with_topk = request.grammar is not None and not with_dfa
        with_lp = request.logprobs > 0
        if self._paged:
            page = self.ecfg.kv_page_size
            npg = -(-self._bucket_for(max(match_len, 1)) // page)
            pages_arr = np.full((npg,), self._scratch_page, np.int32)
            pages_arr[: len(shared)] = shared
            key = ("cached-paged", npg, tb, fbp, has_bias, with_topk, with_lp,
                   with_dfa, draft, with_logits)
            getter = self._get_admit_cached_paged
            row = (self.h_l1[slot_idx] if self._hier
                   else self.h_ptable[slot_idx])
            args = (
                self._upload(pages_arr), self._ptable_device_row(row),
            )
        else:
            key = ("cached", entry["pb"], tb, fbp, has_bias, with_topk,
                   with_lp, with_dfa, draft)
            getter = self._get_admit_cached
            args = (entry["k"], entry["v"])
        args = args + (
            self._upload(tail_toks), self._upload(full_toks), self._upload(aux),
            self._upload(samp_pack),
        )
        if has_bias:
            bias_rows = np.zeros((1, V), np.float32)
            for tid, bval in request.logit_bias.items():
                if 0 <= int(tid) < V:
                    bias_rows[0, int(tid)] = bval
            args = args + (self._upload(bias_rows),)
        if with_dfa:
            host = dfa_tables["host"]
            row = np.unpackbits(
                host.mask_bits[host.init_state], bitorder="little"
            )[:V].astype(bool)
            gmask0 = np.where(row, 0.0, -1e30).astype(np.float32)[None, :]
            ginit = np.full((1,), host.init_state, np.int32)
            args = args + (
                self._upload(gmask0), self._dfa_table(dfa_tables, with_dfa),
                dfa_tables["tok_cls"], self._upload(ginit),
            )
        state = (
            self.params, self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions,
        )
        if with_dfa:
            state = state + (self.d_gstate,)
        if draft:
            state = state + (self.draft_params, self.d_cache)
        full_args = state + args
        if (self.ecfg.prefix_admit_async_compile
                and key not in self._admit_cache):
            # A prefix hit is an optimization — never worth a multi-second
            # XLA compile stall on the serving thread. Compile this shape in
            # the background and serve the request via full admission ("full"
            # tells the caller to fall through rather than requeue — a paged
            # requeue would re-find the hit and busy-spin until the compile
            # lands).
            self._spawn_admit_compile(key, full_args)
            if paged_alloc is not None:
                self._pages_free(slot_idx)
            return "full"
        fn = self._admit_cache.get(key)
        if fn is None:
            fn = getter(*key[1:])
        try:
            with self._phases.call(
                    "dispatch/admit_cached_paged" if key[0] == "cached-paged"
                    else "dispatch/admit_cached", m=1, bucket=tb,
                    tokens=len(tail)):
                out = fn(*full_args)
        except Exception:
            if paged_alloc is not None:
                self._pages_free(slot_idx)
            if isinstance(fn, jax.stages.Compiled):
                # A background-published AOT executable that cannot run
                # against the live state (it raises on input validation,
                # before any donation) would fail every future hit of this
                # shape — evict it and serve THIS request via full admission
                # instead of erroring forever (ADVICE r5 medium).
                log.exception(
                    "published cached-admit executable failed; evicting %s",
                    key,
                )
                with self._admit_compile_lock:
                    if self._admit_cache.get(key) is fn:
                        del self._admit_cache[key]
                return "full"
            raise
        (
            self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, toks, tk, lp,
        ) = out[:9]
        if with_dfa:
            self.d_gstate = out[9]
        elif draft:
            self.d_cache = out[9]
        if with_logits:
            self._fork_logits = out[-1]
        self._host_copy_async(toks)
        self._count_admit(tb, len(tail))
        # LRU bump + metrics. Identity scan, not `in`: dict == would compare
        # the numpy key arrays elementwise (and raises on length mismatch).
        for idx, e in enumerate(self._prefix_entries):
            if e is entry:
                self._prefix_entries.pop(idx)
                self._prefix_entries.insert(0, entry)
                break
        self.m_prefix_hits += 1
        self.m_prefix_tokens += match_len
        self._jnote("prefix_hit", rid=handle.rid, slot=slot_idx,
                    a=float(match_len))
        self._jnote("admitted", rid=handle.rid, slot=slot_idx,
                    a=float(len(ids)))
        tr0 = handle.trace
        if tr0 is not None:
            tr0.note("prefix_hit", matched_tokens=match_len)
        for kf in _SAMPLING_FIELDS:
            self.h_sampling[kf][slot_idx] = getattr(request, kf)
        if self._mrope:
            self.h_rope_delta[slot_idx] = 0  # cached path is text-only
        self._slot_gen[slot_idx] += 1
        self.slots[slot_idx] = _Slot(
            request=request, handle=handle, prompt_len=len(ids), scheduled=1,
            t_submit=t0, dfa=with_dfa, sched_rows=len(ids),
        )
        self._apply_resume(slot_idx)
        self.h_active[slot_idx] = True
        self.h_override_mask[slot_idx] = False
        self.h_gmask[slot_idx] = 1.0 if with_dfa else 0.0
        self._track(_Entry(
            kind="admit", toks=toks, tk=tk, lp=lp, gen=list(self._slot_gen),
            items=[(slot_idx, request, handle, len(ids), t0)],
        ))
        self._plan_dirty()
        self._last_admit_t = time.monotonic()
        # The freshly-assembled prompt span is itself the best prefix for the
        # next request in the conversation — but only if it extends stored
        # coverage enough to beat the snapshot it costs (min_extend).
        self._defer_prefix_save(slot_idx, ids, len(ids))
        return True

    def _get_spec_block(self, mode: str, kb: int, with_dfa=False,
                        with_lora: bool = False):
        """Speculative verify block for one draft source (ISSUE 12,
        docs/SPECULATIVE.md): a kb-token draft window is scored by ONE
        target decode_chunk, and an accept-scan applies the canonical
        speculative-sampling test per slot — accept draft token x with
        probability min(1, p(x)/q(x)), on rejection resample from
        normalize(max(p - q, 0)), and append one bonus sample from p when
        the slot's whole window survives. Unbiased for ANY q, so
        temperature>0 requests keep the draft speedup; temperature==0
        degenerates to exact greedy (p becomes a one-hot and the test
        reduces to argmax agreement — byte-identical to the plain blocks).

        Draft sources:
          draft_model   — n_draft-style separate checkpoint: kb draft-model
                          steps SAMPLE a window from the draft's processed
                          distribution q (the original stochastic verify).
          self_draft    — the target's own first self_draft_layers layers +
                          unembed (llama.self_draft_view) draft against the
                          dense scratch sd_cache; q from the early exit.
          prompt_lookup — the draft window arrives from the HOST (per-slot
                          suffix-index matches); q is a point mass, so the
                          test reduces to accept-w.p.-p(x) and the residual
                          to p-without-x (ops/sampling.deterministic_accept).

        Per-slot draft lengths ride pack row 8: slot b treats step
        t == dlen[b] as its bonus draw and stops after it, so one compiled
        program (keyed by the BUCKETED window kb) serves heterogeneous
        lengths — a dlen-0 slot simply takes one plain sample from p.
        with_dfa (model-free modes only) masks p to the slot automaton's
        legal set and advances the state per EMITTED token, exactly like
        the plain with_dfa blocks; with_lora threads the stacked adapter
        factors into the verify decode_chunk so multi-tenant slots verify
        against their own deltas. p and q both come from
        ops/sampling.processed_logprobs — one shared implementation is
        what makes the acceptance test exact. Generates 1..kb+1 tokens per
        dispatch; device-state contract matches the normal blocks.
        """
        key = ("spec", mode, kb, with_dfa, with_lora)
        fn = self._block_cache.get(key)
        if fn is not None:
            return fn
        cfg, dcfg = self.cfg, self.draft_cfg
        B, S, V = self.ecfg.max_slots, self.ecfg.max_seq, self.cfg.vocab_size
        k = kb
        paged = self._paged
        from localai_tpu.ops.sampling import (
            deterministic_accept,
            processed_logprobs,
            update_counts,
        )

        def spec(params, dparams, cache, dcache, counts, rngs, bias,
                 tokens, positions, pack, drafts=None, ptable=None,
                 mask_bits=None, gtrans=None, tok_cls=None, gstate=None,
                 lora=None):
            active = pack[0] > 0
            samp = SamplingParams(
                temperature=pack[1], top_k=pack[2].astype(jnp.int32),
                top_p=pack[3], min_p=pack[4], repeat_penalty=pack[5],
                presence_penalty=pack[6], frequency_penalty=pack[7],
            )
            dlen = pack[8].astype(jnp.int32)  # [B] per-slot draft length
            counts0 = counts  # round-start counts condition the draft's q
            if with_dfa:
                gmask = pack[9] > 0
                gstate = jnp.where(gmask, gstate, 0)  # FREE for unconstrained

            # 1. Draft window. Model draft sources sample kb proposals from
            # their own processed distribution; prompt lookup ships them
            # from the host (qlogs stays None — deterministic q).
            qlogs = None
            if mode == "prompt_lookup":
                chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
            else:
                def dstep(carry, i):
                    cur, dkv, rngs = carry
                    pos_i = jnp.minimum(positions + i, S - 1)
                    if mode == "self_draft":
                        scfg, sparams = llama.self_draft_view(cfg, params)
                        logits, dkv = llama.decode_step(
                            scfg, sparams, cur, pos_i, dkv, ep=self.plan.ep
                        )
                    else:
                        logits, dkv = llama.decode_step(
                            dcfg, dparams, cur, pos_i, dkv, ep=self.plan.ep
                        )
                    ql = processed_logprobs(logits, samp, counts0, bias)
                    split = jax.vmap(lambda kk: jax.random.split(kk, 2))(rngs)
                    rngs, draw = split[:, 0], split[:, 1]
                    nxt = jax.vmap(jax.random.categorical)(draw, ql).astype(jnp.int32)
                    return (nxt, dkv, rngs), (nxt, ql)

                (last, dcache, rngs), (dtoks, qlogs) = jax.lax.scan(
                    dstep, (tokens, dcache, rngs), jnp.arange(k)
                )  # dtoks [k, B]; qlogs [k, B, V]
                # One more KV-only step so a fully-accepted window's next
                # round (position pos+k+1) sees the last proposal's kv row;
                # its logits and proposal are irrelevant, so no sampling
                # work here.
                if mode == "self_draft":
                    scfg, sparams = llama.self_draft_view(cfg, params)
                    _, dcache = llama.decode_step(
                        scfg, sparams, last,
                        jnp.minimum(positions + k, S - 1), dcache,
                        ep=self.plan.ep,
                    )
                else:
                    _, dcache = llama.decode_step(
                        dcfg, dparams, last,
                        jnp.minimum(positions + k, S - 1), dcache,
                        ep=self.plan.ep,
                    )
                chunk = jnp.concatenate([tokens[:, None], dtoks.T], axis=1)

            # 2. Target scores the whole window in one chunked decode
            # (paged mode walks the page pool and writes through the table).
            if paged:
                # Idle slots' positions keep ratcheting; unpinned they would
                # drive the paged fori_loop bound to the full table. Their
                # writes resolve through SCRATCH tables, their outputs are
                # discarded — pin to 0 for this chunk only.
                pos_base = jnp.where(active, positions, 0)
            else:
                pos_base = positions
            pos_chunk = jnp.minimum(
                pos_base[:, None] + jnp.arange(k + 1)[None, :], S - 1
            )
            logits_all, cache = llama.decode_chunk(
                cfg, params, chunk, pos_chunk, cache, ep=self.plan.ep,
                ptable=ptable, paged_impl=self.ecfg.paged_kernel,
                mesh=self._op_mesh, kv_scale=self._kv_scales, lora=lora,
            )

            # 3. Accept-scan with counts updated token by token, so
            # repeat/presence/frequency semantics match the plain blocks.
            idx = jnp.arange(B)

            def vstep(carry, t):
                counts, still, cur_tok, rngs, gs = carry
                lt = jax.lax.dynamic_index_in_dim(
                    logits_all, t, axis=1, keepdims=False
                )  # [B, V]
                if with_dfa:
                    allowed = self._dfa_allowed(mask_bits, gs, V)
                    lt = jnp.where(allowed, lt, NEG_INF)
                pl = processed_logprobs(lt, samp, counts, bias)
                split = jax.vmap(lambda kk: jax.random.split(kk, 3))(rngs)
                rngs, k_u, k_res = split[:, 0], split[:, 1], split[:, 2]

                x = jax.lax.dynamic_index_in_dim(
                    chunk, jnp.minimum(t + 1, k), axis=1, keepdims=False
                )  # draft token under test (valid for t < dlen)
                if qlogs is None:
                    ratio, res_log = deterministic_accept(pl, x)
                else:
                    ql = jax.lax.dynamic_index_in_dim(
                        qlogs, jnp.minimum(t, k - 1), axis=0, keepdims=False
                    )
                    ratio = pl[idx, x] - ql[idx, x]
                    # rejection draw: normalize(max(p - q, 0)); exact-match
                    # rows (residual mass ~0) fall back to p itself
                    res = jnp.maximum(jnp.exp(pl) - jnp.exp(ql), 0.0)
                    res_mass = res.sum(axis=-1, keepdims=True)
                    res_log = jnp.where(
                        res_mass > 1e-9,
                        jnp.log(res / jnp.maximum(res_mass, 1e-9) + 1e-38),
                        pl,
                    )
                u = jax.vmap(lambda kk: jax.random.uniform(kk))(k_u)
                accepted = jnp.log(jnp.maximum(u, 1e-38)) < ratio

                is_bonus = t >= dlen  # [B]: past the slot's window → p draw
                draw_log = jnp.where(is_bonus[:, None], pl, res_log)
                y = jax.vmap(jax.random.categorical)(k_res, draw_log).astype(jnp.int32)

                take_draft = accepted & ~is_bonus
                emit_tok = jnp.where(take_draft, x, y)
                emit = still & active
                counts = update_counts(counts, emit_tok, emit)
                if with_dfa:
                    ns = self._dfa_advance(with_dfa, gtrans, tok_cls, gs,
                                           emit_tok)
                    gs = jnp.where(emit, ns, gs)  # FREE rows self-loop
                cur_tok = jnp.where(emit, emit_tok, cur_tok)
                still = still & take_draft  # reject or bonus ends the window
                return ((counts, still, cur_tok, rngs, gs),
                        jnp.where(emit, emit_tok, -1))

            gs0 = gstate if with_dfa else jnp.zeros((B,), jnp.int32)
            (counts, _, cur_tok, rngs, gs), toks_out = jax.lax.scan(
                vstep,
                (counts, jnp.ones((B,), bool), tokens, rngs, gs0),
                jnp.arange(k + 1),
            )  # toks_out [k+1, B], -1 where not emitted
            acc = jnp.sum((toks_out >= 0).astype(jnp.int32), axis=0)  # [B]
            new_tokens = jnp.where(active, cur_tok, tokens)
            new_positions = jnp.minimum(positions + acc, S - 1)
            out = (cache, dcache, counts, rngs, new_tokens, new_positions,
                   toks_out, acc)
            if with_dfa:
                out = out + (gs,)
            return out

        # Positional wrapper mirroring _dispatch_spec_block's argument
        # assembly: [mode-specific head] bias tokens positions pack
        # [drafts?] [ptable?] [dfa: mask, trans, cls, gstate] [lora: stacks,
        # ids]. Donated: every consumed device-state buffer.
        has_dstate = mode in ("draft_model", "self_draft")
        nhead = 4 if has_dstate else 2  # params [dparams] cache [dcache]

        def program(*args):
            if mode == "draft_model":
                params, dparams, cache, dcache = args[:4]
            elif mode == "self_draft":
                params, cache, dcache = args[:3]
                dparams = None
            else:
                params, cache = args[:2]
                dparams = dcache = None
            i = nhead if mode != "self_draft" else 3
            counts, rngs, bias, tokens, positions, pack = args[i: i + 6]
            i += 6
            drafts = None
            if mode == "prompt_lookup":
                drafts = args[i]
                i += 1
            ptable = None
            if paged:
                ptable = args[i]
                i += 1
            mask_bits = gtrans = tok_cls = gstate = None
            if with_dfa:
                mask_bits, gtrans, tok_cls, gstate = args[i: i + 4]
                i += 4
            lora = (args[i], args[i + 1]) if with_lora else None
            res = spec(params, dparams, cache, dcache, counts, rngs, bias,
                       tokens, positions, pack, drafts=drafts, ptable=ptable,
                       mask_bits=mask_bits, gtrans=gtrans, tok_cls=tok_cls,
                       gstate=gstate, lora=lora)
            if not has_dstate:
                # drop the dcache slot for the stateless draft source
                res = res[:1] + res[2:]
            return res

        if mode == "draft_model":
            donate = (2, 3, 4, 5, 7, 8)
            base = 10
        elif mode == "self_draft":
            donate = (1, 2, 3, 4, 6, 7)
            base = 9
        else:
            donate = (1, 2, 3, 5, 6)
            base = 8 + 1  # + drafts operand
        if with_dfa:
            donate = donate + (base + (1 if paged else 0) + 3,)
        fn = self._jit(program, "spec_block", donate_argnums=donate)
        self._block_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # Public API
    # ------------------------------------------------------------------ #

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop_guard, daemon=True, name="engine-loop"
            )
            self._thread.start()
        if self._drain_thread is None:
            self._drain_thread = threading.Thread(
                target=self._drain_loop, daemon=True, name="engine-drain"
            )
            self._drain_thread.start()

    def _drain_loop(self) -> None:
        """Pull every in-flight entry's results to the host with BLOCKING
        copies, in dispatch order.

        An explicit blocking copy returns when the entry's own program
        completes, independent of what was dispatched after it, and
        overlaps later blocks' compute; the loop thread keeps dispatching
        meanwhile and only touches finished numpy arrays. Whether polling
        `is_ready` from the loop thread would see completion as promptly
        on the current runtime has not been measured (ROADMAP S6).
        """
        while True:
            e = self._drain_q.get()
            if e is None:
                return
            try:
                toks = np.asarray(e.toks)
                tk = np.asarray(e.tk) if e.tk is not None else None
                lp = (tuple(np.asarray(a) for a in e.lp)
                      if e.lp is not None else None)
                moe = np.asarray(e.moe) if e.moe is not None else None
                e.host = (toks, tk, lp, moe)
            except Exception as ex:  # noqa: BLE001 — surface via processing
                e.host = ex
            e.host_done = True
            self._wake.set()

    def _track(self, e: _Entry) -> None:
        self._inflight.append(e)
        self._drain_q.put(e)

    def stop(self) -> None:
        self._shutdown.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._drain_thread is not None:
            self._drain_q.put(None)
            self._drain_thread.join(timeout=30)
            self._drain_thread = None
        # No consumer may hang across stop(): the loop is gone, so any
        # request still holding a slot or sitting in the queue would never
        # get a terminal event (observed: the manager watchdog's busy-kill
        # can fire inside the admission gap — cancel_all() sees neither
        # pending nor slot — then evict the engine, leaving the caller
        # blocked on the stream forever). Duplicate done events on already-
        # finished streams are harmless (the consumer stopped reading).
        for slot in self._tenants():
            slot.handle._q.put(TokenEvent(kind="done", finish_reason="stop"))
            for _r, bh in (slot.request.fork_group or ()):
                bh._q.put(TokenEvent(kind="done", finish_reason="stop"))
            slot.request.fork_group = None
        with self._pending_lock:
            pending, self._pending = list(self._pending), deque()
        for req, handle in pending:
            self._resume_discard(req)
            handle._q.put(TokenEvent(kind="done", finish_reason="stop"))
            for _r, bh in (req.fork_group or ()):
                bh._q.put(TokenEvent(kind="done", finish_reason="stop"))
            req.fork_group = None
        with self._fork_lock:
            staged, self._fork_requests = self._fork_requests, []
        for _src, _seeds, handles in staged:
            for bh in handles:
                bh._q.put(TokenEvent(kind="done", finish_reason="stop"))
        if self._tok_fp is not None:
            # Release grammar tables prewarm pinned against this engine's
            # tokenizer — they can never hit again after the model swaps.
            from localai_tpu.functions import dfa as dfa_mod

            dfa_mod.unpin(self._tok_fp)

    def submit(self, request: GenRequest) -> RequestHandle:
        if not request.prompt_ids:
            raise ValueError("empty prompt")
        # Never mutate the caller's request object (it may be reused).
        request = dataclasses.replace(request, prompt_ids=list(request.prompt_ids))
        limit = self.ecfg.max_seq - 1
        if len(request.prompt_ids) > limit:
            # Truncate from the left but keep the leading token (BOS / system
            # prompt head), mirroring llama.cpp context-shift semantics.
            head = request.prompt_ids[0]
            request.prompt_ids = [head] + request.prompt_ids[-(limit - 1):]
            log.warning(
                "prompt truncated to %d tokens (max_seq=%d)", limit, self.ecfg.max_seq
            )
        if self._paged and self._pages_worst(request) > self.ecfg.kv_pages:
            # Worst-case gate only: admission reserves prompt+headroom and
            # grows on demand, but a request whose full context can NEVER
            # fit the pool would preempt everyone and still starve.
            raise ValueError(
                f"request needs up to {self._pages_worst(request)} KV pages, "
                f"pool has {self.ecfg.kv_pages} — lower max_new_tokens or "
                "grow kv_pages"
            )
        if request.image_embeds is not None:
            if self.draft_cfg is not None:
                raise ValueError(
                    "multimodal requests are not supported with a draft model"
                )
            n = int(np.asarray(request.image_embeds).shape[0])
            if request.image_offset < 0 or request.image_offset + n > len(request.prompt_ids):
                raise ValueError(
                    f"image span [{request.image_offset}, {request.image_offset + n}) "
                    f"outside the prompt ({len(request.prompt_ids)} tokens)"
                )
        if request.mrope_positions is not None:
            if self.draft_cfg is not None:
                # The draft admit path has no mrope arg slot (and multimodal
                # is excluded with drafts anyway — see above).
                raise ValueError(
                    "mrope requests are not supported with a draft model"
                )
            p3 = np.asarray(request.mrope_positions)
            if p3.shape != (3, len(request.prompt_ids)):
                raise ValueError(
                    f"mrope_positions shape {p3.shape} != (3, prompt_len)"
                )
        if request.adapter is not None:
            # Fail fast on tenant-identity errors; the actual fetch/promote
            # happens at admission on the loop thread (and may still fail
            # with an error event — disk, faults, pinned rows).
            if self.draft_cfg is not None:
                raise AdapterError(
                    "adapter requests are not supported with a separate "
                    "draft model — use model-free spec_mode instead"
                )
            with self._adapter_lock:
                known = request.adapter in self._adapter_registry
            if not known:
                raise AdapterError(
                    f"unknown adapter {request.adapter!r} — "
                    "register_adapter() first"
                )
        if request.grammar is not None and self._tok_strs is None:
            self._token_str(0)  # build the table here, not in the engine loop
        handle = RequestHandle()
        handle.t_submit = time.monotonic()
        # Lifecycle tracing (ISSUE 11): every request gets a journal id;
        # span-tree recording only when the caller named the request (the
        # HTTP layer always does) or sent a W3C traceparent — anonymous
        # library/bench submits stay zero-overhead on the trace side.
        handle.rid = request.request_id or f"h{id(handle):x}"
        tr = None
        if request.request_id or request.traceparent:
            tr = otrace.RequestTrace(
                handle.rid, traceparent=request.traceparent,
                engine=self.cfg.name,
            )
            handle.trace = tr
            handle._q.trace = tr
            otrace.STORE.register(tr)
            tr.note("queued", prompt_tokens=len(request.prompt_ids))
        deadline_s = request.deadline_s or self.ecfg.deadline_s
        if deadline_s > 0:
            handle.deadline = handle.t_submit + deadline_s
            # Deadline index (ISSUE 17): the loop's housekeeping tick asks
            # the heap "is anything due?" instead of scanning the queue
            # every iteration. Lazy-deletion — an early finish just pops
            # as a no-op tick when it comes due.
            self._deadlines.push(handle.deadline)
        if self.ecfg.queue_timeout_s > 0:
            self._deadlines.push(handle.t_submit + self.ecfg.queue_timeout_s)
        # Dead-check and append share _pending_lock with _loop_guard's
        # set-dead-and-drain: either this submit observes the death (error
        # event below) or its entry lands before the drain and is drained
        # with an error event — never appended after it and orphaned.
        try:
            with self._pending_lock:
                dead = self._loop_dead
                if dead is None:
                    if (self.ecfg.max_pending
                            and len(self._pending) >= self.ecfg.max_pending):
                        # Shed at the door (ISSUE 4): a queue past
                        # max_pending only manufactures timeouts. Raise a
                        # typed error the HTTP layer maps to 429 +
                        # Retry-After.
                        self.m_queue_shed += 1
                        raise QueueFullError(
                            len(self._pending), self.ecfg.max_pending,
                            self.admission_wait_estimate(),
                        )
                    self._pending.append((request, handle))
                    self._last_submit_t = handle.t_submit
        except QueueFullError as e:
            # The handle never reaches a consumer — close its trace here
            # so the span tree still ends in exactly one terminal.
            if tr is not None:
                tr.terminal(TokenEvent(kind="error", error=str(e)))
            raise
        if dead is not None:
            # The loop thread is gone — nothing will ever serve this request.
            handle._q.put(TokenEvent(kind="error", error=dead))
            return handle
        self._jstage("queued", rid=handle.rid,
                     a=float(len(request.prompt_ids)))
        self._wake.set()
        self.start()
        return handle

    def admission_wait_estimate(self) -> float:
        """Observed submit→admission latency (EWMA, seconds), floored at 1 —
        the Retry-After hint for shed requests."""
        return max(1.0, self._admit_wait_ewma)

    def _note_admitted(self, handle: RequestHandle) -> None:
        """Record one request's queue wait into the admission-latency EWMA
        (loop thread only; handles built outside submit() carry no stamp)."""
        if handle.join_blocks == -1:
            handle.join_blocks = sum(
                1 for e in self._inflight if e.kind in ("block", "spec"))
        if handle.t_submit <= 0.0:
            return
        handle.t_admit = time.monotonic()
        tr = handle.trace
        if tr is not None:
            tr.note("admitted")
        wait = max(0.0, time.monotonic() - handle.t_submit)
        if self._admit_wait_ewma == 0.0:
            self._admit_wait_ewma = wait
        else:
            self._admit_wait_ewma = 0.8 * self._admit_wait_ewma + 0.2 * wait

    @property
    def is_dead(self) -> bool:
        """True once the engine loop died of an unexpected exception. A dead
        engine fails every submit with an error event and never recovers
        in-process — the ModelManager observes this state, evicts the model
        and transparently reloads it on the next request (crash-only
        supervision, ISSUE 4 / docs/ROBUSTNESS.md)."""
        return self._loop_dead is not None

    def generate(self, prompt_ids: list[int], **kw) -> tuple[str, TokenEvent]:
        return self.submit(GenRequest(prompt_ids=list(prompt_ids), **kw)).result()

    def cancel_all(self) -> int:
        """Cancel every active and pending request (watchdog busy-kill path —
        reference: watchdog.go:250-279 kills the wedged backend process; here
        the slots drain via their cancelled handles). Returns count.

        Pending entries are not just flagged: the loop's _purge_pending pops
        them and posts a terminal event, so a consumer blocked in result()
        or a stream drain always unblocks — previously a cancelled entry sat
        in _pending until a slot freed (or forever, with the loop dead) and
        its caller hung (ISSUE 4 satellite). If no loop thread is alive to
        purge (never started, stopped, or dead), drain here instead — there
        is no thread to race with host-tier state then."""
        n = 0
        with self._pending_lock:
            for _req, handle in self._pending:
                handle.cancel()
                n += 1
                for _r, bh in (_req.fork_group or ()):
                    bh.cancel()
                    n += 1
        for slot in self._tenants():
            slot.handle.cancel()
            n += 1
        self._wake.set()
        loop = self._thread
        if loop is None or not loop.is_alive():
            with self._pending_lock:
                pending, self._pending = list(self._pending), deque()
            for request, handle in pending:
                self._resume_discard(request)
                handle._q.put(TokenEvent(kind="done", finish_reason="stop"))
                for _r, bh in (request.fork_group or ()):
                    bh._q.put(TokenEvent(kind="done", finish_reason="stop"))
                request.fork_group = None
        return n

    def embed(self, ids_batch: list[list[int]]) -> np.ndarray:
        """Batched sentence embeddings [N, D] (L2-normalized)."""
        S = self._bucket_for(max(len(x) for x in ids_batch))
        N = len(ids_batch)
        toks = np.zeros((N, S), np.int32)
        lens = np.zeros((N,), np.int32)
        for i, ids in enumerate(ids_batch):
            ids = ids[: S]
            toks[i, : len(ids)] = ids
            lens[i] = len(ids)
        return np.asarray(self._embed_fn(self.params, toks, lens))

    def rerank(self, query_ids: list[int], docs_ids: list[list[int]]) -> np.ndarray:
        """Relevance scores [N]: mean conditional log-likelihood of each
        document given the query (rerank capability — backend.proto Rerank,
        core/backend/rerank.go). Higher is more relevant."""
        limit = self.ecfg.max_seq - 1
        q = list(query_ids)[: limit // 2]
        rows = []
        for d in docs_ids:
            d = list(d)[: limit - len(q)] or [0]
            rows.append(q + d)
        S = self._bucket_for(max(len(r) for r in rows))
        N = len(rows)
        toks = np.zeros((N, S), np.int32)
        lens = np.zeros((N,), np.int32)
        conds = np.full((N,), len(q), np.int32)
        for i, r in enumerate(rows):
            toks[i, : len(r)] = r
            lens[i] = len(r)
        return np.asarray(self._score_fn(self.params, toks, lens, conds))

    def _jit(self, fn, name: str, **kw):
        return _named_jit(fn, name, sites=self.quant_sites, **kw)

    def metrics(self) -> dict[str, float]:
        tps = self._decode_tokens / self._decode_time if self._decode_time > 0 else 0.0
        out = {
            "prompt_tokens_processed": float(self.m_prompt_tokens),
            "tokens_generated": float(self.m_generated_tokens),
            "tokens_per_second": tps,
            "active_slots": float(int(self.h_active.sum())),
            "queue_depth": float(len(self._pending)),
            # Request-lifecycle robustness gauges (ISSUE 4).
            "queue_shed": float(self.m_queue_shed),
            "queue_timeouts": float(self.m_queue_timeouts),
            "deadline_expired": float(self.m_deadline_expired),
            "admit_wait_ms": float(self._admit_wait_ewma * 1000.0),
            "loop_dead": 1.0 if self._loop_dead is not None else 0.0,
        }
        if self._prefix_enabled:
            out["prefix_cache_hits"] = float(self.m_prefix_hits)
            out["prefix_tokens_reused"] = float(self.m_prefix_tokens)
            out["prefix_cache_entries"] = float(len(self._prefix_entries))
        if self.m_dfa_tokens:
            out["grammar_dfa_tokens"] = float(self.m_dfa_tokens)
        if self._paged:
            out["kv_pages_total"] = float(self.ecfg.kv_pages)
            out["kv_pages_free"] = float(len(self._free_pages))
            out["kv_pages_grown"] = float(self.m_kv_pages_grown)
            out["kv_pages_peak"] = float(self.m_kv_pages_peak)
            out["kv_preemptions"] = float(self.m_kv_preemptions)
            out["kv_preempt_swaps"] = float(self.m_kv_preempt_swaps)
            out["kv_preempt_recomputes"] = float(self.m_kv_preempt_recomputes)
            out["kv_preempt_recover_ms"] = float(self.m_kv_preempt_recover_ms)
            out["kv_swap_bytes_out"] = float(self.m_kv_swap_bytes_out)
            out["kv_swap_bytes_in"] = float(self.m_kv_swap_bytes_in)
            out["kv_host_tier_bytes"] = float(self._host_bytes)
            out["prefix_host_tier_entries"] = float(len(self._prefix_host))
            out["prefix_host_tier_hits"] = float(self.m_prefix_host_hits)
            if self._spill_on or self.m_kv_pages_spilled:
                # Cold-page spill (ISSUE 14): live spilled pages + churn.
                # list(): scrape threads must not iterate live loop-owned
                # structure (shared-state-race) — the copy is GIL-atomic.
                out["kv_spilled_pages"] = float(
                    sum(len(d) for d in list(self._slot_spill))
                    + sum(len(s.parked.spill)
                          for s in list(self._parked.values()))
                )
                out["kv_spill_host_bytes"] = float(self._spill_bytes)
                out["kv_spill_bytes_out"] = float(self.m_kv_spill_bytes_out)
                out["kv_spill_bytes_in"] = float(self.m_kv_spill_bytes_in)
                out["kv_pages_spilled"] = float(self.m_kv_pages_spilled)
                out["kv_pages_restored"] = float(self.m_kv_pages_restored)
            if self._hier:
                out["kv_table_pages_total"] = float(len(self._tp_refs) - 1)
                out["kv_table_pages_free"] = float(len(self._tp_free))
            # Cluster span transfer (ISSUE 6): disaggregation hand-offs.
            out["span_exports"] = float(self.m_span_exports)
            out["span_imports"] = float(self.m_span_imports)
            out["span_import_rejects"] = float(self.m_span_import_rejects)
        with self._adapter_lock:
            n_adapters = len(self._adapter_registry)
        if n_adapters or self._lora_tree is not None:
            # Multi-tenant LoRA (ISSUE 10): registry size, device residency
            # and the host-tier footprint per tenant churn.
            out["adapters_registered"] = float(n_adapters)
            out["adapter_device_resident"] = float(
                sum(1 for nm in list(self._adapter_rows) if nm is not None)
            )
            out["adapter_host_bytes"] = float(self._adapter_host_bytes)
            out["adapter_fetches"] = float(self.m_adapter_fetches)
            out["adapter_promotes"] = float(self.m_adapter_promotes)
            out["adapter_evictions"] = float(self.m_adapter_evictions)
        out["peak_active_slots"] = float(self.m_peak_active)
        out["decode_rows_dispatched"] = float(self.m_rows_dispatched)
        out["decode_rows_posted"] = float(self.m_rows_posted)
        out["decode_rows_overshoot"] = float(self.m_rows_overshoot)
        out["decode_rows_empty"] = float(self.m_rows_empty)
        out["slots_released"] = float(self.m_slots_released)
        out["slots_released_early"] = float(self.m_slots_released_early)
        out["admit_programs"] = float(self.m_admit_programs)
        out["admit_rows_dispatched"] = float(self.m_admit_rows_dispatched)
        out["admit_rows_prompt"] = float(self.m_admit_rows_prompt)
        if self.cfg.is_moe:
            # Routing of the decode blocks processed, see _count_routing.
            out["moe_expert_slots"] = float(self.m_moe_slots)
            out["moe_expert_slots_hit"] = float(self.m_moe_slots_hit)
            out["moe_rows_busiest"] = float(self.m_moe_rows_busiest)
            out["moe_rows_mean"] = float(self.m_moe_rows_mean)
            if self.cfg.expert_share is not None:
                out["moe_picks"] = float(self.m_moe_picks)
                out["moe_picks_here"] = float(self.m_moe_picks_here)
                out["moe_admit_rows"] = float(self.m_moe_admit_rows)
                out["moe_admit_rows_held"] = float(self.m_moe_admit_rows_held)
        if self.cfg.recurrent_kind == "swa":
            # The window layers' rings beside the full layers' pages.
            out["window_state_bytes"] = float(self._slot_state_bytes())
            out["window_rows_read"] = float(self.m_window_rows_read)
            out["window_rows_full"] = float(self.m_window_rows_full)
        elif self.cfg.is_hybrid:
            # The second kind of per-slot state (engine/state.py).
            out["recurrent_state_bytes"] = float(self._slot_state_bytes())
        if self.cfg.is_hybrid:
            out["state_restores"] = float(self.m_state_restores)
            out["prefix_reuse_off"] = float(
                self.ecfg.prefix_cache_entries > 0)
        bound = rstate.admit_rows(self.cfg)
        if bound:  # the byte bound on an admission program (KDA, SSD, MLA)
            out["admit_splits"] = float(self.m_admit_splits)
            out["admit_rows_max"] = float(bound)
        # Call sites over every program traced so far: the Pallas kernel read
        # its layer out of the stacked operand (weights; the paged K/V pool),
        # or the layer was sliced out first (ops/stacked.SiteCounts).
        sites = self.quant_sites.totals()
        if sites["stacked"] or sites["sliced"]:
            out["quant_matmul_stacked_sites"] = float(sites["stacked"])
            out["quant_matmul_sliced_sites"] = float(sites["sliced"])
            out["quant_matmul_grouped_sites"] = float(sites["grouped"])
            # and the weight block the rule gave each kernel call: the
            # weight's whole rows, or a column strip (stacked.note_blocks)
            out["quant_matmul_wholerow_sites"] = float(sites["wholerow"])
            out["quant_matmul_narrowed_sites"] = float(sites["narrowed"])
        if sites["paged_attention_stacked"] or sites["paged_attention_sliced"]:
            out["paged_attention_stacked_sites"] = float(
                sites["paged_attention_stacked"])
            out["paged_attention_sliced_sites"] = float(
                sites["paged_attention_sliced"])
            # and what the Pallas kernel's dots were fed at those sites: the
            # page as stored, or float32 tiles (stacked.note_arith)
            out["paged_attention_native_sites"] = float(
                sites["paged_attention_native"])
            out["paged_attention_f32_sites"] = float(
                sites["paged_attention_f32"])
            # and what a visit of its page walk held: several pages side
            # by side under one dot, or one (stacked.note_visit)
            out["paged_attention_multipage_sites"] = float(
                sites["paged_attention_multipage"])
            out["paged_attention_onepage_sites"] = float(
                sites["paged_attention_onepage"])
            # and how its walk crosses a slot boundary: one stream of visits
            # over all the slots, or a slot's own prefetch (stacked.note_walk)
            out["paged_attention_stream_sites"] = float(
                sites["paged_attention_stream"])
            out["paged_attention_prefetch_sites"] = float(
                sites["paged_attention_prefetch"])
        if (sites["paged_attention_value_lanes"]
                or sites["paged_attention_value_row"]):
            # a latent pool's kernel calls alone, by the lanes their value
            # dot runs over: the stated value lanes, or the whole row
            # (stacked.note_value_lanes)
            out["paged_attention_value_lanes_sites"] = float(
                sites["paged_attention_value_lanes"])
            out["paged_attention_value_row_sites"] = float(
                sites["paged_attention_value_row"])
        if sites["pool_write_inplace"] or sites["pool_write_scatter"]:
            # how each decode block's window reached its two page pools: the
            # DMA kernel in place, or XLA's scatter (stacked.note_pool_write)
            out["pool_write_inplace_sites"] = float(
                sites["pool_write_inplace"])
            out["pool_write_scatter_sites"] = float(
                sites["pool_write_scatter"])
        if sites["ssd_decode_pallas"] or sites["ssd_decode_xla"]:
            # the form each SSD layer's decode update took: the kernel on the
            # stacked state in place, or the XLA step (stacked.note_ssd)
            out["ssd_decode_pallas_sites"] = float(sites["ssd_decode_pallas"])
            out["ssd_decode_xla_sites"] = float(sites["ssd_decode_xla"])
        if sites["s6_decode_pallas"] or sites["s6_decode_xla"]:
            # the same for each S6 layer's decode update (stacked.note_s6)
            out["s6_decode_pallas_sites"] = float(sites["s6_decode_pallas"])
            out["s6_decode_xla_sites"] = float(sites["s6_decode_xla"])
        if self.m_forks or self.m_fork_clone_fallbacks:
            # Tree-batched fork sampling (ISSUE 18): branches admitted by
            # slot fork vs degraded to the N-clone path (fault/pressure).
            out["fork_branches"] = float(self.m_forks)
            out["fork_clone_fallbacks"] = float(self.m_fork_clone_fallbacks)
        if self.m_loop_blocks:
            # Pipelined loop runtime (ISSUE 17): host ms spent per decode
            # block outside the wait phase, and the control-stager's
            # transfer economy (skips = commits served from cache).
            out["loop_blocks"] = float(self.m_loop_blocks)
            out["loop_host_ms_total"] = float(self.m_loop_host_ms)
            out["loop_blocked_ms_total"] = float(self.m_loop_blocked_ms)
            # Where the working phases' ms went (ISSUE 51; LoopPhases):
            # inside jax calls, in the collector on the loop's thread, off
            # the CPU; the rest of loop_host_ms_total less the blocked ms
            # is Python. Maxima are since start.
            ph = self._phases
            out["loop_call_ms_total"] = float(self.m_loop_call_ms)
            out["loop_gc_ms_total"] = float(self.m_loop_gc_ms)
            out["loop_off_cpu_ms_total"] = float(max(self.m_loop_off_ms, 0.0))
            out["loop_late_ms_max"] = float(ph.late_max_ever)
            out["loop_stretch_ms_max"] = float(ph.stretch_max_ever)
            out["loop_stalls"] = float(ph.stall_count)
        # The collector's pauses anywhere in the process (observe/gcwatch.py).
        out.update(gcwatch.WATCH.counters())
        if self._ctrl.commits:
            out["ctrl_commits"] = float(self._ctrl.commits)
            out["ctrl_transfers"] = float(self._ctrl.transfers())
            out["ctrl_commit_skips"] = float(self._ctrl.skips)
        if self._journal is not None:
            # Lifecycle journal health (ISSUE 11): total events recorded
            # and cross-thread events dropped by a stalled writer.
            out["journal_events"] = float(self._journal.n)
            out["journal_dropped"] = float(self._journal.dropped_staged)
        if self.ecfg.prefill_chunk:
            out["prefill_chunks"] = float(self.m_prefill_chunks)
            out["chunked_admissions"] = float(self.m_chunked_admits)
        if self._spec_mode != "off":
            # Speculative decoding (ISSUE 12): acceptance fed from the
            # per-slot EWMA scheduler. accept_rate = emitted / scored
            # (drafted tokens + one bonus/resample per round) — identical
            # to the old rounds×(n_draft+1) denominator when every slot
            # drafts the full window.
            out["spec_rounds"] = float(self.m_spec_rounds)
            out["spec_tokens_accepted"] = float(self.m_spec_accepted)
            out["spec_tokens_drafted"] = float(self.m_spec_drafted)
            out["spec_accept_rate"] = (
                self.m_spec_accepted
                / max(1, self.m_spec_drafted + self.m_spec_rounds)
                if self.m_spec_rounds else 0.0
            )
            out["spec_draft_len"] = float(self.m_spec_draft_len)
            out["spec_accept_ewma"] = (
                float(self.h_accept_ewma[self.h_active].mean())
                if self.h_active.any() else 1.0
            )
        return out

    def warmup(self, prompt_len: int = 8, grammar: bool = False, logprobs: bool = False) -> None:
        """Compile AND execute the serving programs before traffic arrives.

        Runs every admission group size (powers of two up to max_slots at
        `prompt_len`'s bucket) and every greedy/simple decode-block size once
        against throwaway state, so neither the first burst of traffic nor
        the first sampled request stalls active slots on a mid-serving XLA
        compile — real executions populate the jit dispatch cache, which
        AOT lower/compile alone does not. The persistent compilation cache
        (utils/compile_cache.py) makes repeat warmups much faster.

        With grammar=True, also compiles the single-step grammar block and
        exercises a constrained request end-to-end.
        """
        bucket = self._bucket_for(prompt_len)
        # Two passes: the very first execution transitions the live state's
        # avals (fresh zeros → committed program outputs); the second pass
        # re-traces every program against the stabilized avals so serving
        # never pays a retrace.
        for _pass in range(2):
            m = 1
            while m <= self.ecfg.max_slots:
                self._warm_admit(m, bucket)
                m *= 2
            # Bias/grammar/logprobs requests always admit as singletons (see
            # _admit_pending), so only their m=1 variants need warming.
            self._warm_admit(1, bucket, has_bias=True)
            self._warm_admit(1, bucket, with_topk=True)
            if logprobs:
                self._warm_admit(1, bucket, with_lp=True)
            for n in self.ecfg.block_sizes:
                # "filtered" is the variant real traffic hits under the
                # server's sampling defaults (temperature+top_k/top_p), so it
                # must be warm too.
                for variant in ("greedy", "simple", "filtered"):
                    self._warm_block(variant, n)
                    if logprobs:
                        self._warm_block(variant, n, with_lp=True)
            # KV-windowed variants of the throughput block (read-side HBM
            # saver; _dispatch_block picks the bucket) — warm every bucket so
            # context growth never hits a mid-serving compile.
            if not self._paged and self._ring_mesh is None:
                w = self._KV_WIN_MIN
                while w < self.ecfg.max_seq:
                    for variant in ("greedy", "simple", "filtered"):
                        self._warm_block(variant, self.ecfg.block_sizes[0],
                                         kv_win=w)
                        if logprobs:
                            self._warm_block(variant, self.ecfg.block_sizes[0],
                                             with_lp=True, kv_win=w)
                    w *= 2
        # Prefix-save snapshot programs compile per bucket ON THE LOOP
        # THREAD at the first save of that bucket — a finish-time save of an
        # unwarmed bucket otherwise stalls serving mid-measurement (~0.75 s
        # observed inside the bench's decode window). Touch every bucket.
        if self._prefix_enabled and not self._paged:
            pb = self._bucket_for(self.ecfg.prefix_cache_min)
            while True:
                jax.block_until_ready(
                    self._get_snapshot(pb)(self.cache, jnp.int32(0))
                )
                if pb >= self.ecfg.max_seq:
                    break
                pb = self._bucket_for(pb + 1)
        self._lp_warmed = self._lp_warmed or logprobs
        _, ev = self.generate([1] * prompt_len, max_new_tokens=2)
        assert ev.kind == "done"
        if grammar:
            from localai_tpu.functions.jsonschema import GrammarConstraint

            self._token_str(0)  # build the table outside the engine loop
            _, ev = self.generate(
                [1] * prompt_len, max_new_tokens=4,
                grammar=GrammarConstraint({"type": "boolean"}),
            )
            assert ev.kind == "done"

    # ------------------------------------------------------------------ #
    # Warmup helpers
    # ------------------------------------------------------------------ #
    #
    # Warmup executes the real programs against the LIVE engine state, not
    # throwaway clones: jit caches key on the concrete avals (sharding and
    # layout included), and the live state's avals change once the first
    # program output replaces the freshly-initialized arrays. Warming on
    # clones leaves every program to pay a several-hundred-ms retrace on its
    # first real call. Running on live state is safe before serving: all
    # slots are free, admission resets every per-slot row, and inactive-slot
    # decode writes only into rows that the next admission overwrites.

    def _warm_block(self, variant: str, n: int, with_lp: bool = False,
                    kv_win: Optional[int] = None) -> None:
        B = self.ecfg.max_slots
        fn = self._get_block(variant, n, with_lp, kv_win=kv_win)
        pack = np.zeros((10, B), np.float32)
        pack[3] = 1.0  # top_p
        pack[5] = 1.0  # repeat_penalty
        args = (
            self.params, self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, jnp.asarray(pack),
        )
        if self._mrope:
            args = args + (jnp.asarray(self.h_rope_delta),)
        if self._paged:
            args = args + (self._ptable_device(),)
        (
            self.cache, self.counts, self.rngs, self.d_tokens, self.d_positions,
            toks, _tk, _lp, _moe,
        ) = fn(*args)
        jax.block_until_ready(toks)

    def _warm_admit(self, m: int, bucket: int, has_bias: bool = False,
                    with_topk: bool = False, with_lp: bool = False) -> None:
        fn = self._get_admit(m, bucket, has_bias, with_topk, with_lp)
        aux = np.zeros((3, m), np.int32)
        aux[0] = 1  # lens
        aux[1] = np.arange(m) % self.ecfg.max_slots  # slot ids
        samp_pack = np.zeros((7, m), np.float32)
        samp_pack[2] = 1.0  # top_p
        samp_pack[4] = 1.0  # repeat_penalty
        args = (
            jnp.zeros((m, bucket), jnp.int32), jnp.asarray(aux), jnp.asarray(samp_pack),
            jnp.zeros((m, self.cfg.vocab_size), jnp.float32),
        )
        if self._paged:
            # Warm against the scratch page so throwaway writes land nowhere.
            if self._hier:
                args = args + ((
                    jnp.full((m, self._ml1), self._scratch_tp, jnp.int32),
                    jnp.asarray(self.h_l0),
                ),)
            else:
                args = args + (jnp.full(
                    (m, self._max_pages), self._scratch_page, jnp.int32
                ),)
        if self.draft_cfg is None:
            out = fn(
                self.params, self.cache, self.counts, self.rngs, self.bias,
                self.d_tokens, self.d_positions, *args,
            )
        else:
            out = fn(
                self.params, self.cache, self.counts, self.rngs, self.bias,
                self.d_tokens, self.d_positions, self.draft_params, self.d_cache,
                *args,
            )
            self.d_cache = out[-1]
        (
            self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, toks, _tk, _lp,
        ) = out[:9]  # then the held rows' count (_held_rows), the draft cache
        jax.block_until_ready(toks)

    # ------------------------------------------------------------------ #
    # Engine loop
    # ------------------------------------------------------------------ #

    def _bucket_for(self, n: int) -> int:
        for b in self.ecfg.buckets():
            if n <= b:
                return b
        return self.ecfg.max_seq

    def _legacy_grammar_active(self) -> bool:
        """Any active slot whose grammar needs the host candidate walk
        (schema didn't compile to a DFA) — forces single-step blocks."""
        return any(
            self.h_active[i] and self.slots[i] is not None
            and self.slots[i].request.grammar is not None
            and not self.slots[i].dfa
            for i in range(self.ecfg.max_slots)
        )

    def _dfa_grammar_active(self) -> bool:
        return any(
            self.h_active[i] and self.slots[i] is not None and self.slots[i].dfa
            for i in range(self.ecfg.max_slots)
        )

    def prewarm_grammar(self, schema: Any) -> bool:
        """Synchronously compile a schema's grammar tables into the module
        cache so the FIRST request for it already runs on the device DFA
        (uncached schemas otherwise build off-thread while their first
        request serves via the host walk). Call at deployment warmup with
        the tool schemas a service will use. Returns True when the DFA will
        serve this schema, False when it will fall back to the host walk."""
        from localai_tpu.functions import dfa as dfa_mod

        if self._tok_strs is None:
            self._tok_strs = self.tokenizer.token_strings()
        tables = dfa_mod.tables_for(
            schema, self._tok_strs, set(self.tokenizer.eos_ids),
            self.cfg.vocab_size, tokenizer_id=self._tok_fingerprint(),
            pin=True,  # prewarmed schemas are exempt from the LRU bound
        )
        return tables is not None

    # ------------------------------------------------------------------ #
    # On-device grammar DFA (functions/dfa.py)
    # ------------------------------------------------------------------ #

    # Pad table shapes so programs compile once per bucket, not per schema.
    _DFA_STATE_BUCKETS = (64, 256, 1024, 3073)
    _DFA_CLASS_BUCKETS = (128, 256)

    def _dfa_for(self, request: GenRequest) -> Optional[dict]:
        """Device tables for this request's grammar, or None → host walk.

        One table set is active at a time (schemas repeat across requests —
        tool-calling reuses one for a whole deployment); it can only be
        swapped while no DFA-constrained slot is live, because in-flight
        per-slot states index the active set. A second concurrent schema
        falls back to the host walk rather than waiting.
        """
        if request.grammar is None:
            return None
        if os.environ.get("LOCALAI_GRAMMAR_DFA", "1") == "0":
            return None
        schema = getattr(request.grammar, "schema", None)
        if isinstance(schema, dict) and "__gbnf__" in schema:
            # Only a GbnfConstraint may carry the GBNF marker: a USER JSON
            # schema containing that key would compile a GBNF DFA on device
            # while the host walk runs the JSON machine — desynced masks.
            from localai_tpu.functions.gbnf import GbnfConstraint

            if not isinstance(request.grammar, GbnfConstraint):
                return None
        from localai_tpu.functions import dfa as dfa_mod

        key = dfa_mod.schema_key(schema)
        if self._dfa is not None and self._dfa["key"] == key:
            return self._dfa
        if self._dfa_grammar_active():
            return None  # active slots pin the current table set
        if self._tok_strs is None:
            self._tok_strs = self.tokenizer.token_strings()
        # Table compilation takes seconds for large schemas and this runs on
        # the engine loop thread — an inline build would stall admission of
        # EVERY request arriving meanwhile, not just the requesting stream.
        # Always build uncached tables on a worker thread and serve this
        # request via the host-walk fallback; the loop thread never blocks
        # on a schema compile.
        if key in self._dfa_building:
            return None
        if not dfa_mod.is_cached(
            schema, self._tok_fingerprint(), self.cfg.vocab_size
        ):
            self._dfa_building.add(key)

            def build():
                try:
                    dfa_mod.tables_for(
                        schema, self._tok_strs, set(self.tokenizer.eos_ids),
                        self.cfg.vocab_size, tokenizer_id=self._tok_fingerprint(),
                    )
                finally:
                    self._dfa_building.discard(key)
                    self._wake.set()

            threading.Thread(target=build, daemon=True,
                             name="grammar-dfa-build").start()
            return None
        # cached_only: even if the entry was LRU-evicted between the
        # is_cached check above and here, the loop thread must never become
        # the builder — a miss host-walks this request and the next request
        # re-triggers the async build.
        tables = dfa_mod.tables_for(
            schema, self._tok_strs, set(self.tokenizer.eos_ids),
            self.cfg.vocab_size, tokenizer_id=self._tok_fingerprint(),
            cached_only=True,
        )
        if tables is None:
            return None
        S1, C = tables.trans.shape
        S_pad = next((b for b in self._DFA_STATE_BUCKETS if b >= S1), None)
        C_pad = next((b for b in self._DFA_CLASS_BUCKETS if b >= C), None)
        if S_pad is None or C_pad is None:
            return None
        mask_bits = np.zeros((S_pad, tables.mask_bits.shape[1]), np.uint8)
        mask_bits[:S1] = tables.mask_bits
        trans = np.zeros((S_pad, C_pad), np.int16)
        trans[:S1, :C] = tables.trans
        self._dfa = {
            "key": key,
            "mask_bits": jnp.asarray(mask_bits),
            "trans": jnp.asarray(trans),
            "tok_cls": jnp.asarray(tables.tok_cls),
            "host": tables,
        }
        if tables.next_tok is not None:
            # Small automaton: a direct [S, V] state-after-token table makes
            # the per-step transition ONE gather instead of a 32-step char
            # walk (~40% of constrained decode throughput).
            nt = np.zeros((S_pad, tables.next_tok.shape[1]), np.int16)
            nt[:S1] = tables.next_tok
            self._dfa["next_tok"] = jnp.asarray(nt)
        log.info("grammar DFA ready: %d states (padded %d), schema %.60s...",
                 S1, S_pad, key)
        return self._dfa

    def _tok_fingerprint(self) -> str:
        """Stable identity of the tokenizer's string table for the DFA table
        cache — id() can be reused after GC and would alias two different
        tokenizers' tables."""
        if self._tok_fp is None:
            import hashlib

            if self._tok_strs is None:
                self._tok_strs = self.tokenizer.token_strings()
            h = hashlib.md5()
            h.update(str(len(self._tok_strs)).encode())
            for s in self._tok_strs:
                h.update(s.encode("utf-8", "surrogateescape"))
                h.update(b"\x00")
            self._tok_fp = h.hexdigest()
        return self._tok_fp

    @staticmethod
    def _dfa_next_state(trans, tok_cls, state, tok):
        """Walk each sampled token's char classes through the transition
        table: state [B] i32, tok [B] i32 → next state [B] i32. The FREE row
        (0) self-loops, so unconstrained slots are fixed points."""
        seq = tok_cls[tok]  # [B, L] i16, -1 padded

        def step(s, c):
            nxt = trans[jnp.maximum(s, 0), jnp.maximum(c, 0).astype(jnp.int32)]
            return jnp.where(c >= 0, nxt.astype(jnp.int32), s), None

        s, _ = jax.lax.scan(step, state, seq.T)
        return s

    @staticmethod
    def _dfa_mode_of(tables: Optional[dict]):
        """False | "walk" | "fast" — part of program cache keys, so the two
        transition implementations compile as distinct variants."""
        if tables is None:
            return False
        return "fast" if tables.get("next_tok") is not None else "walk"

    @staticmethod
    def _dfa_table(tables: dict, mode):
        """The transition operand matching `mode` — keep the cache key and
        the operand derivation in one place (a mismatch would feed a [S, C]
        walk table to a program compiled for the [S, V] gather)."""
        return tables["next_tok"] if mode == "fast" else tables["trans"]

    def _dfa_mode(self):
        return self._dfa_mode_of(self._dfa)

    @classmethod
    def _dfa_advance(cls, mode, gtrans, tok_cls, state, tok):
        """State after emitting `tok`: direct table gather (fast) or char
        walk. In fast mode `gtrans` IS the [S, V] next-token table."""
        with scope("sample"):  # the grammar's automaton, beside the draw
            if mode == "fast":
                return gtrans[state, tok].astype(jnp.int32)
            return cls._dfa_next_state(gtrans, tok_cls, state, tok)

    @staticmethod
    def _dfa_allowed(mask_bits, state, V):
        """Unpack per-state legality bits: state [B] → bool [B, V]."""
        with scope("sample"):
            rows = mask_bits[state]  # [B, ceil(V/8)] u8
            bits = (rows[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)[None, None, :]) & 1
            return bits.reshape(state.shape[0], -1)[:, :V].astype(bool)

    def _lp_active(self) -> bool:
        return any(
            self.h_active[i] and self.slots[i] is not None
            and self.slots[i].request.logprobs > 0
            for i in range(self.ecfg.max_slots)
        )

    def _loop_guard(self) -> None:
        """Run the engine loop; if it dies of an unexpected exception, fail
        every live and pending request with an error event instead of
        leaving their callers blocked on queues forever (BENCH_r05 hung to
        the harness timeout exactly this way — the loop thread died and
        every generate() waited on a token that would never come)."""
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — terminal: report and drain
            log.exception("engine loop died; failing all live requests")
            err = f"engine loop died: {type(e).__name__}: {e}"
            # Set-dead + drain atomically w.r.t. submit()'s check-and-append
            # (same lock), so no entry can slip in AFTER this drain yet miss
            # the dead-engine error event.
            with self._pending_lock:
                self._loop_dead = err
                pending, self._pending = list(self._pending), deque()
            # Flight-recorder context (ISSUE 11): capture the dying
            # request set BEFORE the teardown clears it — the postmortem
            # names exactly what was live/pending at death, and the error
            # events below post through these captured handles.
            live_slots = [
                (i, s) for i, s in enumerate(self.slots) if s is not None
            ] + [(i, s) for (i, _g), s in self._parked.items()]
            live_snapshot = [
                (i, s.handle.rid, len(s.generated), s.prompt_len)
                for i, s in live_slots
            ]
            pending_rids = [h.rid for _r, h in pending]
            # Crash-only teardown (ISSUE 4): release every per-request
            # claim on the page pool and host tier BEFORE any terminal
            # event posts — the moment a caller unblocks it may assert the
            # pool fully accounted (the fault sweep does exactly that), so
            # the release must already be complete, not merely imminent.
            # Queued resume images surrender their host-tier bytes first
            # (release zeroes the tier wholesale; discarding after it
            # would double-subtract).
            try:
                for request, _handle in pending:
                    self._resume_discard(request)
                self._release_all_state()
            except Exception:  # noqa: BLE001 — best-effort on a dead engine
                log.exception("post-death state release failed")
            for _i, slot in live_slots:
                slot.handle._q.put(TokenEvent(kind="error", error=err))
                for _r, bh in (slot.request.fork_group or ()):
                    bh._q.put(TokenEvent(kind="error", error=err))
                slot.request.fork_group = None
            for _request, handle in pending:
                handle._q.put(TokenEvent(kind="error", error=err))
                for _r, bh in (_request.fork_group or ()):
                    bh._q.put(TokenEvent(kind="error", error=err))
                _request.fork_group = None
            # Staged mid-stream forks (Engine.fork) can never execute now.
            with self._fork_lock:
                staged_forks, self._fork_requests = self._fork_requests, []
            for _src, _seeds, fhandles in staged_forks:
                for bh in fhandles:
                    bh._q.put(TokenEvent(kind="error", error=err))
            # Flight recorder (ISSUE 11): this thread is the journal's
            # writer, so the final events and the dump race nothing.
            try:
                j = self._journal
                if j is not None:
                    j.drain_staged()
                self._jnote("loop_dead", a=float(len(live_snapshot)),
                            b=float(len(pending_rids)))
                self._jnote_fault(e)
                self._postmortem_path = self._write_postmortem(
                    err, live_snapshot, pending_rids
                )
                log.error("engine postmortem written to %s",
                          self._postmortem_path)
            except Exception:  # noqa: BLE001 — the dump must not mask the crash
                log.exception("postmortem write failed")
            # No re-raise: the failure is fully reported (log + error events);
            # an unhandled thread exception would only add noise.

    def _release_all_state(self) -> None:
        """Drop all slot/pool/host-tier request state after a loop death.
        Every handle has already received its terminal event; this only
        reconciles the allocator and host tier (loop thread — it is the
        dying thread's last act, so nothing races it)."""
        self._inflight.clear()
        self._chunkings = []
        self._growth_blocked = False
        for i in range(self.ecfg.max_slots):
            self.slots[i] = None
            self.h_active[i] = False
            self.h_override_mask[i] = False
            self.h_gmask[i] = 0.0
            self.h_adapter[i] = 0
            if self._paged and self._slot_pages[i]:
                self._pages_free(i)
        for slot in list(self._parked.values()):
            self._release_parked(slot)
        # No slot references an adapter row anymore; zero the pins so the
        # device rows are evictable (the registry and host tier survive —
        # a reloaded engine starts cold on factors, not on metadata).
        if len(self._adapter_refs):
            self._adapter_refs[:] = 0
        if self._paged:
            # Prefix spans hold pool-page references (and table-page
            # references under hierarchical tables); the reloaded engine
            # starts cold anyway.
            for entry in self._prefix_entries:
                if entry.get("pages"):
                    self._pages_release(entry["pages"])
                if self._hier and entry.get("tps"):
                    self._tp_release(entry["tps"])
        self._prefix_entries = []
        self._spill_bytes = 0
        with self._host_lock:
            self._prefix_host = []
            self._host_bytes = 0
        # Staged span imports can never merge now — unblock their waiters
        # (entry["accepted"] stays unset, so importers report failure and
        # their callers fall back to recompute).
        with self._span_inbox_lock:
            staged = list(self._span_inbox)
            self._span_inbox[:] = []
        for _entry, done in staged:
            done.set()

    def _loop(self) -> None:
        self._charge_last = time.monotonic()
        self._charge_was_active = False
        # From here on a collection that runs on this thread is booked to
        # the phase it interrupted (observe/gcwatch.py; the first loop of
        # the process installs the hook, the last to leave removes it).
        self._phases.own()
        gcwatch.WATCH.enter(self._phases.collector)
        self._gc_seen = gcwatch.WATCH.n
        try:
            self._loop_body()
        finally:
            self._phases.end()  # closes the open loop/<phase> span
            self._note_stalls()
            gcwatch.WATCH.leave()

    def _loop_body(self) -> None:
        # Every moment of the loop lies in one phase (LoopPhases): a phase
        # begins where its work does and runs until the next one begins, so
        # an iteration that finds nothing to do stays in `wait` and a
        # waiting loop is one `loop/wait` span, not one per spin.
        ph = self._phases
        while not self._shutdown.is_set():
            faults.fire("engine_loop")  # injected loop death (ISSUE 4)
            self._charge()
            ph.iters += 1
            did = processed = False
            jr = self._journal
            if jr is not None and (jr.staged()
                                   or gcwatch.WATCH.n != self._gc_seen):
                # Move cross-thread events (queued, span export) and the
                # collector's pauses into the single-writer ring in order.
                ph.begin("drain")
                jr.drain_staged()
                self._gc_seen = gcwatch.WATCH.drain(self._gc_seen,
                                                    self._jnote_gc)
            # Budgeted sidecar (ISSUE 17): purge/deadline sweeps run on
            # a DUE tick — the deadline heap says something expired, or
            # the forced interval elapsed — instead of scanning every
            # pending request every iteration.
            now = time.monotonic()
            if self._hk_due(now):
                ph.begin("housekeeping")
                self._housekeeping(now)
            self._drain_span_inbox()

            if self._growth_blocked and not self.h_active.any():
                # The growth-starved slots are gone (finished or preempted
                # during the drain) — nothing is waiting on pages anymore,
                # so admission must unblock or the queue starves.
                self._growth_blocked = False
            if self._pending or self._fork_requests or self._admit_hold_start:
                ph.begin("admit")
            if self._fork_requests:
                # Mid-stream forks (Engine.fork) execute at a quiesce point;
                # while any are staged, hold new admissions and blocks so
                # in-flight work drains and the fork wait stays bounded.
                self._service_forks()
            admitted = (False if self._fork_requests
                        else self._admit_pending())
            # Only host-walk grammars force single-step, serialized blocks;
            # DFA-constrained slots pipeline at full depth like everyone else.
            grammar = self._legacy_grammar_active()
            depth = 1 if grammar else self.ecfg.pipeline_depth
            nblocks = sum(1 for e in self._inflight if e.kind == "block")
            active = bool(self.h_active.any())

            dispatchable = (active and nblocks < depth
                            and not (grammar and self._inflight)
                            and not self._fork_requests)
            if dispatchable and not grammar and not self._has_unscheduled():
                # Every active slot's budget is already covered by in-flight
                # blocks — another dispatch would compute only discarded
                # overshoot tokens. Wait for results instead.
                dispatchable = False
            # Coalesce a burst: hold the first block briefly so near-
            # simultaneous arrivals share its phase (a block costs the
            # same with 1 active slot as with all of them). The hold only
            # suppresses DISPATCH — chunk progress, cold-page spill and
            # in-flight result processing below still run (the pre-ISSUE-17
            # `continue` here starved them for the whole hold window).
            hold = (dispatchable and nblocks == 0
                    and self.ecfg.admit_coalesce_ms > 0
                    and any(s is None for s in self.slots)
                    and (time.monotonic() - self._last_admit_t) * 1000
                    < self.ecfg.admit_coalesce_ms)
            if dispatchable and not hold:
                t0 = time.monotonic()
                try:
                    # begins the prep, commit and dispatch phases itself
                    did = self._dispatch_block(grammar)
                except Exception as e:  # noqa: BLE001 — fail requests, not the loop
                    self._fail_block(e)
                    self._flush_loop_iter(False, False)
                    continue
                if did:
                    dispatch_ms = (time.monotonic() - t0) * 1000.0
                    self._jnote("decode_block", slot=-1,
                                a=float(self._inflight[-1].n), b=dispatch_ms)
                    nblocks += 1
                elif not self._inflight:
                    # Pool exhausted mid-decode and every in-flight dispatch
                    # has drained (their writes target the victim's pages
                    # through the tables they shipped): preempt the
                    # youngest slot so the others stop stalling.
                    self._preempt_youngest()

            # Chunked prefill rides between decode-block dispatches: one
            # chunk in flight at a time, so the device alternates decode
            # blocks and prefill chunks instead of stalling every live slot
            # behind a monolithic long-prompt prefill.
            if self._chunkings:
                ph.begin("dispatch")
                self._advance_chunked()

            if self._inflight:
                front = self._inflight[0]
                # A parked tenant (_park) is still being served: keep the
                # loop turning for arrivals and deadlines while its last
                # block runs, as for a live one.
                if (front.ready() or nblocks >= depth
                        or not (active or self._parked)):
                    # begins the pull and process phases itself
                    posted = self.m_generated_tokens
                    finished = self.m_slots_released
                    self._process_entry(self._inflight.popleft())
                    # what this stretch of `process` did (a loop_stall's)
                    ph.note(self.m_generated_tokens - posted,
                            self.m_slots_released - finished)
                    processed = True
                else:
                    # The loop would otherwise wait on the in-flight block:
                    # prepare the NEXT block's control plan (so the post-
                    # result path is commit + dispatch only), give the
                    # budgeted sidecar the idle window, then sleep.
                    staged = False
                    if not grammar:
                        try:
                            # begins the prep phase when it builds a plan
                            staged = self._stage_plan()
                        except Exception as e:  # noqa: BLE001 — same containment as dispatch
                            self._fail_block(e)
                            self._flush_loop_iter(False, False)
                            continue
                    now = time.monotonic()
                    if self._hk_due(now, idle=True):
                        ph.begin("housekeeping")
                        self._housekeeping(now)
                    if not staged:
                        # Nothing ready, nothing to prepare (e.g. grammar
                        # mode waiting on an in-flight admit): don't
                        # busy-spin.
                        ph.begin("wait")
                        ph.wait(self._wake, 0.001)
                        self._wake.clear()
            elif not active and not admitted:
                now = time.monotonic()
                if self._hk_due(now, idle=True):
                    ph.begin("housekeeping")
                    self._housekeeping(now)
                ph.begin("wait")
                ph.wait(self._wake, 0.05)
                self._wake.clear()
            elif hold and not did:
                # Held dispatch with nothing in flight to process: brief
                # pause (chunk progress and spill above already ran).
                ph.begin("wait")
                ph.sleep(0.0005)
            self._flush_loop_iter(did, processed)

    # thread: engine-loop-only
    def _fail_block(self, e: Exception) -> None:
        """Containment for a failed decode-block dispatch OR a failed
        prepare-ahead plan (both run the same planning code, so both take
        the same path): post a typed error event to every active request
        and release its state — fail requests, not the loop. A parked
        tenant (_park) needs no further dispatch: it gets its tokens and
        its `done` from the block already in flight."""
        log.exception("decode block dispatch failed")
        self._jnote("error", a=1.0)
        self._jnote_fault(e)
        for i in range(self.ecfg.max_slots):
            slot = self.slots[i]
            if slot is not None:
                slot.handle._q.put(TokenEvent(
                    kind="error", error=f"{type(e).__name__}: {e}"
                ))
                # A chunked fork primary still carries its branch group
                # until the final chunk activates it.
                self._fork_group_fail(slot.request, TokenEvent(
                    kind="error", error=f"{type(e).__name__}: {e}"
                ))
                self._release(i)

    # Housekeeping cadence (ISSUE 17): the forced interval bounds how stale
    # purge/deadline/spill sweeps can get while the loop is busy; the idle
    # interval lets a waiting loop tick more eagerly since the time is free.
    _HK_INTERVAL_S = 0.02
    _HK_IDLE_S = 0.002

    # thread: engine-loop-only
    def _hk_due(self, now: float, idle: bool = False) -> bool:
        """Is a housekeeping tick due? O(1): the deadline heap's earliest
        expiry, or the forced interval."""
        if self._deadlines.due(now):
            return True
        return now - self._hk_last >= (self._HK_IDLE_S if idle
                                       else self._HK_INTERVAL_S)

    # thread: engine-loop-only
    def _housekeeping(self, now: float) -> None:
        """One budgeted sidecar tick (ISSUE 17): lifecycle-critical sweeps
        first (pending purge + active-deadline enforcement run on EVERY due
        tick), then optional work — deferred prefix-span saves, cold-page
        spill — only while the tick is under housekeeping_budget_ms. The
        budget is checked before each optional task, so a tick overruns by
        at most one bounded task; that bound is what "housekeeping never
        delays a ready dispatch beyond its budget" means in
        docs/ENGINE_RUNTIME.md."""
        self._hk_last = now
        budget_s = self.ecfg.housekeeping_budget_ms / 1000.0
        self._purge_pending()
        self._enforce_deadlines()
        if time.monotonic() - now >= budget_s:
            return
        self._flush_deferred_saves()
        if time.monotonic() - now >= budget_s:
            return
        self._spill_cold_pages()

    # thread: engine-loop-only
    def _defer_prefix_save(self, slot_idx: int, ids, rows: int) -> None:
        """Admission-time prefix-span save, moved off the admission path
        (ISSUE 17): the snapshot costs a device gather + host copy that would
        otherwise stand before the next dispatch. The save is parked for the
        budgeted sidecar; _finish flushes (or subsumes) whatever is still
        parked, so a span is saved later than at admission, never lost."""
        if not self._prefix_enabled:
            return
        self._deferred_saves.append(
            (slot_idx, list(ids), int(rows), self._slot_gen[slot_idx])
        )

    # thread: engine-loop-only
    def _flush_deferred_saves(self, slot_idx: Optional[int] = None) -> None:
        """Run parked admission saves (all of them, or one slot's before it
        finishes). Entries whose slot generation moved on are dropped — the
        slot was preempted or released, so the rows the save would snapshot
        no longer belong to that request."""
        if not self._deferred_saves:
            return
        run: list = []
        keep: list = []
        for item in self._deferred_saves:
            (run if slot_idx is None or item[0] == slot_idx
             else keep).append(item)
        self._deferred_saves = keep
        for si, ids, rows, gen in run:
            if self._slot_gen[si] == gen and self.slots[si] is not None:
                self._prefix_save(si, ids, rows,
                                  min_extend=self.ecfg.prefix_cache_min)

    # thread: engine-loop-only
    def _flush_loop_iter(self, did: bool, processed: bool) -> None:
        """Coalesced loop_iter emission (ISSUE 17): every host millisecond
        lands in exactly ONE loop_iter window, attributed by phase. A
        window closes on dispatch, on result processing, or after ~25 ms of
        quiet waiting/housekeeping — emitting each of the ~1/ms wait
        iterations instead would flood the 4096-event ring and evict the
        lifecycle events a postmortem needs."""
        ph = self._phases
        ph.sync()
        if ph.stalls:
            self._note_stalls()
        host_ms = ph.total()  # excludes the wait phase
        if not (did or processed) and host_ms < 25.0:
            if ph.ms["wait"] >= 1000.0:
                # Pure idle: drop the window instead of emitting — a
                # long-idle server must not evict lifecycle events with
                # wait-only loop_iter records.
                ph.reset()
            return
        self.m_loop_host_ms += host_ms
        self.m_loop_blocked_ms += ph.ms["pull"]
        self.m_loop_call_ms += ph.working(ph.call_ms)
        self.m_loop_gc_ms += ph.working(ph.gc_ms)
        self.m_loop_off_ms += ph.working(ph.off_ms)
        if did:
            self.m_loop_blocks += 1
        j = self._journal
        if j is not None:
            j.append("loop_iter", slot=-1, a=float(int(self.h_active.sum())),
                     b=host_ms, phases=ph.vector(), causes=ph.causes(),
                     extra=ph.extras())
        ph.reset()

    # thread: engine-loop-only
    def _note_stalls(self) -> None:
        """Journal each stretch of runtime.STALL_MS or more as its own
        `loop_stall`: it outlives the loop_iter windows around it in the
        ring and reaches the postmortem."""
        ph = self._phases
        stalls, ph.stalls = ph.stalls, []
        j = self._journal
        if j is not None:
            for st in stalls:
                j.append("loop_stall", a=float(ph.names.index(st[0])),
                         b=st[1], extra=ph.extras(stall=st))

    # thread: engine-loop-only
    def _jnote_gc(self, t: float, generation: int, ms: float,
                  mine: bool) -> None:
        """One collector pause from gcwatch's ring, as `gc_pause`."""
        j = self._journal
        if j is not None:
            j.append_at(t, "gc_pause", slot=0 if mine else -1,
                        a=float(generation), b=ms)

    # ------------------------------------------------------------------ #
    # Request-lifecycle enforcement (ISSUE 4, docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------ #

    def _purge_pending(self) -> None:
        """Drop cancelled / deadline-expired / queue-timed-out entries from
        the pending queue, posting exactly one terminal event each (loop
        thread, and stop()/cancel_all() after the loop is gone). Admission
        also drops cancelled entries at the queue head, but only when a slot
        is free — a saturated engine would otherwise hold a cancelled
        caller's stream open indefinitely."""
        if not self._pending:  # unlocked peek — len() is atomic in CPython
            return
        with self._pending_lock:
            if not self._pending:
                return
            now = time.monotonic()
            qt = self.ecfg.queue_timeout_s
            kept: deque[tuple[GenRequest, RequestHandle]] = deque()
            dropped: list[tuple[GenRequest, RequestHandle, Optional[str]]] = []
            for request, handle in self._pending:
                if handle.cancelled.is_set():
                    dropped.append((request, handle, None))
                elif handle.deadline is not None and now > handle.deadline:
                    dropped.append((request, handle, "deadline"))
                elif (qt > 0 and handle.t_submit > 0
                        and now - handle.t_submit > qt):
                    dropped.append((request, handle, "queue-timeout"))
                else:
                    kept.append((request, handle))
            self._pending = kept
        for request, handle, why in dropped:
            self._resume_discard(request)
            if why is None:
                handle._q.put(TokenEvent(kind="done", finish_reason="stop"))
                # A cancelled fork primary's live branches requeue as
                # independents (each pays its own prefill).
                self._fork_group_requeue(request)
                continue
            if why == "deadline":
                self.m_deadline_expired += 1
                waited = now - handle.t_submit if handle.t_submit else 0.0
                err = (f"deadline exceeded after {waited:.1f}s in queue "
                       f"(deadline_s)")
            else:
                self.m_queue_timeouts += 1
                err = (f"request timed out after "
                       f"{self.ecfg.queue_timeout_s:.1f}s in queue "
                       f"(queue_timeout_s) — server saturated")
            handle.cancel()  # a racing admit must not serve it anyway
            handle._q.put(TokenEvent(kind="error", error=err))
            # An expired fork primary takes its whole group down — the
            # branches share its prompt, deadline pressure and fate.
            self._fork_group_fail(request,
                                  TokenEvent(kind="error", error=err))

    def _enforce_deadlines(self) -> None:
        """Cancel ACTIVE slots whose deadline has passed (loop thread). The
        cancelled handle drains through the ordinary paths — _post_token /
        _advance_chunked finish the slot and release its KV pages / host-
        tier bytes. When nothing is in flight (so no dispatched write can
        still target the slot's pages) a cancelled slot is torn down right
        here: a growth-blocked or otherwise stalled engine must not pin a
        cancelled request's pages while waiting for traffic."""
        now = time.monotonic()
        for slot in self._tenants():
            h = slot.handle
            if (h.deadline is not None and now > h.deadline
                    and not h.cancelled.is_set()):
                self.m_deadline_expired += 1
                h.cancel()
        if not self._inflight:
            chunking = {st["slot"] for st in self._chunkings}
            for i in range(self.ecfg.max_slots):
                slot = self.slots[i]
                if (slot is not None and slot.handle.cancelled.is_set()
                        and i not in chunking):
                    self._finish(i, "stop")

    # ------------------------------------------------------------------ #
    # Admission
    # ------------------------------------------------------------------ #

    def _admit_pending(self) -> bool:
        admitted = False
        if self._growth_blocked:
            # A live slot is waiting on pages — new admissions would steal
            # the pool out from under the growth/preemption cycle.
            return admitted
        while True:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return admitted
            # Submit-burst coalescing (r5): a cold burst arrives staggered
            # over a few ms; admitting eagerly splits it into several
            # prefill programs (observed m=2+4+2 for an 8-request burst,
            # each paying its own dispatch and prefill pass). While
            # the ENGINE IS IDLE and submits are still arriving, hold
            # admission until the burst settles (bounded by 4x the window)
            # so the whole burst prefills as ONE program. Never holds while
            # decoding — those admissions ride between blocks anyway.
            if (self.ecfg.admit_coalesce_ms > 0 and not self.h_active.any()):
                now = time.monotonic()
                with self._pending_lock:
                    npend = len(self._pending)
                if npend == 0:
                    self._admit_hold_start = 0.0
                elif npend < len(free):
                    if self._admit_hold_start == 0.0:
                        self._admit_hold_start = now
                    window = self.ecfg.admit_coalesce_ms / 1000.0
                    if ((now - self._last_submit_t) < window
                            and (now - self._admit_hold_start) < 4 * window):
                        time.sleep(window / 8)
                        return admitted
                    self._admit_hold_start = 0.0
                else:
                    self._admit_hold_start = 0.0
            group: list[tuple[GenRequest, RequestHandle]] = []
            bucket = 0
            pages_planned = 0
            chunk_item = None  # ((request, handle), hit) → chunked admission
            swap_item = None  # (request, handle) → swap-preempted resume
            fork_item = None  # (request, handle) → fork-group primary
            prefix_hits: dict[int, tuple] = {}  # id(request) -> (entry, len)
            # Cancelled fork primaries found during the locked scan requeue
            # their live branches AFTER the lock drops (_fork_group_requeue
            # takes _pending_lock itself; the branches land at the queue
            # tail either way).
            requeue_forks: list[GenRequest] = []
            with self._pending_lock:
                while self._pending and len(group) < len(free):
                    request, handle = self._pending[0]
                    if handle.cancelled.is_set():
                        self._pending.popleft()
                        self._resume_discard(request)
                        handle._q.put(TokenEvent(kind="done", finish_reason="stop"))
                        if request.fork_group:
                            requeue_forks.append(request)
                        continue
                    if (self._paged and request.resume is not None
                            and request.resume.get("mode") == "swap"):
                        # Swap resumes dispatch alone (no prefill program to
                        # batch); page budgeting happens outside the lock.
                        if group:
                            break
                        swap_item = self._pending.popleft()
                        break
                    # Long prompts admit through the chunked state machine
                    # (decode keeps streaming between chunks). A prefix hit
                    # whose TAIL fits one chunk stays on the cheaper
                    # single-shot cached path below.
                    if self._chunk_size:
                        hit0 = prefix_hits.get(id(request))
                        if hit0 is None and self._cached_admit_ok(request):
                            hit0 = self._prefix_find(request.prompt_ids)
                            if hit0 is not None:
                                prefix_hits[id(request)] = hit0
                        if self._chunkable(request, hit0[1] if hit0 else 0):
                            if group:
                                break  # dispatch the batched group first
                            chunk_item = (self._pending.popleft(), hit0)
                            break
                    if request.fork_group is not None:
                        # Fork primaries plan as singleton rounds (ISSUE 18):
                        # _fork_after_admit claims EXTRA slots right after
                        # the primary's admission dispatch, which must not
                        # collide with slots this round already handed to
                        # other chunks. Budgeting happens outside the lock.
                        if group:
                            break  # dispatch the batched group first
                        fork_item = self._pending.popleft()
                        break
                    if self._paged:
                        # A prefix hit shares the span's pages — gate on the
                        # reduced (tail-only) need. Requests the cached path
                        # can't serve budget as misses (full pages).
                        hit = prefix_hits.get(id(request))
                        if hit is None:
                            hit = (self._prefix_find(request.prompt_ids)
                                   if self._cached_admit_ok(request) else None)
                        if hit is not None:
                            prefix_hits[id(request)] = hit
                            need = self._pages_needed_cached(
                                request, hit[1], host="hk" in hit[0]
                            )
                        else:
                            need = self._pages_needed(request)
                        if pages_planned + need > len(self._free_pages):
                            # Cached spans can be re-prefilled; a queued
                            # request can't be served any other way — evict
                            # LRU prefix entries (sparing ones this round's
                            # admissions will map) before backpressuring.
                            keep = [h[0] for h in prefix_hits.values()]
                            self._prefix_evict_for_pages(
                                pages_planned + need, protect=keep
                            )
                        if pages_planned + need > len(self._free_pages):
                            break  # pool backpressure — wait for a finish
                        pages_planned += need
                    b = self._bucket_for(len(request.prompt_ids))
                    if not group:
                        bucket = b
                    elif b != bucket:
                        break  # different bucket — next round
                    group.append(self._pending.popleft())
            for _req in requeue_forks:
                self._fork_group_requeue(_req)
            if swap_item is not None:
                request, handle = swap_item
                need = self._resume_swap_pages(request)
                if len(self._free_pages) < need:
                    self._prefix_evict_for_pages(need)
                if (len(self._free_pages) >= need
                        and self._dispatch_resume_swap(request, handle, free[0])):
                    self._note_admitted(handle)
                    tr = handle.trace
                    if tr is not None:
                        # Swap resumes skip the admission program entirely
                        # (no first-token entry will mark the decode phase).
                        tr.note("resumed")
                    admitted = True
                    continue  # re-plan the remaining queue
                with self._pending_lock:
                    self._pending.appendleft(swap_item)
                return admitted  # pool backpressure — wait for a finish
            if chunk_item is not None:
                (request, handle), hit = chunk_item
                if self._chunk_start(request, handle, hit):
                    self._note_admitted(handle)
                    admitted = True
                    continue  # re-plan the remaining queue
                return admitted  # pool backpressure — wait for a finish
            if fork_item is not None:
                request, handle = fork_item
                if self._paged:
                    hit = prefix_hits.get(id(request))
                    if hit is None and self._cached_admit_ok(request):
                        hit = self._prefix_find(request.prompt_ids)
                        if hit is not None:
                            prefix_hits[id(request)] = hit
                    need = (self._pages_needed_cached(request, hit[1],
                                                      host="hk" in hit[0])
                            if hit is not None
                            else self._pages_needed(request))
                    # Budget the whole tree: the primary's prefill pages plus
                    # each branch's boundary-copy + headroom claim. Branches
                    # the pool can't cover at fork time degrade to clones,
                    # but planning for the full tree avoids flapping.
                    need += sum(self._pages_fork_need(r)
                                for r, _h in request.fork_group)
                    if need > len(self._free_pages):
                        self._prefix_evict_for_pages(
                            need,
                            protect=[h[0] for h in prefix_hits.values()],
                        )
                    if need > len(self._free_pages):
                        with self._pending_lock:
                            self._pending.appendleft(fork_item)
                        return admitted  # pool backpressure — wait
                self._note_admitted(handle)
                try:
                    self._dispatch_admit(
                        [fork_item],
                        self._bucket_for(len(request.prompt_ids)), [free[0]],
                        prefix_hit=prefix_hits.get(id(request)),
                    )
                    admitted = True
                except Exception as e:  # noqa: BLE001 — surface to callers, keep serving
                    log.exception("fork admission dispatch failed")
                    self._jnote("error", a=1.0)
                    self._jnote_fault(e)
                    ev = TokenEvent(kind="error",
                                    error=f"{type(e).__name__}: {e}")
                    handle._q.put(ev)
                    self._fork_group_fail(request, ev)
                continue  # re-plan the remaining queue
            if not group:
                return admitted
            for _req, gh in group:
                self._note_admitted(gh)
            # Requests with logit_bias, a grammar, or logprobs select
            # different program variants (has_bias / with_topk / with_lp);
            # admit them as singletons so only the (m=1, ...) variants ever
            # compile — those are warmed.

            def _special(r: GenRequest) -> bool:
                if (bool(r.logit_bias) or r.grammar is not None
                        or r.logprobs > 0 or r.image_embeds is not None
                        or r.adapter is not None):
                    # Adapter requests admit as singletons so a fetch/
                    # promote failure fails exactly one tenant's request.
                    return True
                # One LCP scan per request per round; hits are handed to
                # _dispatch_admit rather than re-searched there. A memoized
                # MISS deliberately re-checks at dispatch: an earlier chunk
                # in the same round may have just saved the matching span.
                if self._prefix_enabled and id(r) not in prefix_hits:
                    prefix_hits[id(r)] = self._prefix_find(r.prompt_ids)
                return prefix_hits.get(id(r)) is not None

            special = [gh for gh in group if _special(gh[0])]
            plain = [gh for gh in group if not _special(gh[0])]
            # Dispatch plain requests in power-of-two chunks (binary
            # decomposition) so each admission program compiles for a small
            # fixed set of M values.
            chunks: list[list[tuple[GenRequest, RequestHandle]]] = [[gh] for gh in special]
            idx = 0
            # A KDA model's admission holds its prompts' KDA operands in
            # float32, every request's at once: bounded bytes a program.
            bound = rstate.admit_rows(self.cfg)
            m_max = max(1, bound // bucket) if bound else len(plain)
            unbounded = bin(len(plain)).count("1")  # programs without m_max
            while idx < len(plain):
                m = 1
                while m * 2 <= min(len(plain) - idx, m_max):
                    m *= 2
                chunks.append(plain[idx: idx + m])
                idx += m
            if len(chunks) - len(special) > unbounded:
                self.m_admit_splits += 1
                self._jnote("admit_split", a=float(len(chunks) - len(special)),
                            b=float(len(plain)))
            for chunk in chunks:
                try:
                    self._dispatch_admit(
                        chunk, bucket, [free.pop(0) for _ in chunk],
                        prefix_hit=prefix_hits.get(id(chunk[0][0])),
                    )
                    admitted = True
                except Exception as e:  # noqa: BLE001 — surface to callers, keep serving
                    log.exception("admission dispatch failed (m=%d)", len(chunk))
                    self._jnote("error", a=float(len(chunk)))
                    self._jnote_fault(e)
                    for request, handle in chunk:
                        handle._q.put(
                            TokenEvent(kind="error", error=f"{type(e).__name__}: {e}")
                        )

    def _dispatch_admit(
        self,
        chunk: list[tuple[GenRequest, RequestHandle]],
        bucket: int,
        slot_ids: list[int],
        prefix_hit: tuple | None = None,
    ) -> None:
        faults.fire("device_dispatch")
        if self.plan.total > 1:
            # Sharded admission launches a multi-chip program (ICI
            # collectives at the qkv/o boundaries) — give the fault harness
            # a hook that only exists on sharded engines (ISSUE 7).
            faults.fire("collective_dispatch")
        m = len(chunk)
        V = self.cfg.vocab_size
        # Fork primaries (ISSUE 18) are admitted as singletons and need the
        # final-position logits stashed for _fork_after_admit.
        with_logits = (m == 1 and chunk[0][0].fork_group is not None
                       and self._paged and self.draft_cfg is None)
        dfa_tables = None
        # Resume requests keep the HOST grammar walk: the machine object
        # carries the mid-stream state a fresh device-DFA init would lose.
        # Cluster grammar failovers (grammar_pos > 0, ISSUE 19) skip the
        # DFA for the same reason: the replayed machine is mid-stream.
        if (m == 1 and chunk[0][0].grammar is not None
                and chunk[0][0].image_embeds is None
                and chunk[0][0].resume is None
                and chunk[0][0].grammar_pos == 0):
            dfa_tables = self._dfa_for(chunk[0][0])
        if (m == 1 and chunk[0][0].image_embeds is None
                and self._cached_admit_ok(chunk[0][0])):
            # Without a hit from the admission round, scan here: covers
            # direct callers (tests, warmup) and round-memoized misses whose
            # span an earlier chunk this round may have just saved. The scan
            # is numpy over ≤prefix_cache_entries keys — trivial next to the
            # dispatch it precedes.
            hit = prefix_hit if prefix_hit is not None else self._prefix_find(
                chunk[0][0].prompt_ids
            )
            if hit is not None:
                res = self._dispatch_admit_cached(
                    chunk[0][0], chunk[0][1], slot_ids[0], *hit,
                    dfa_tables=dfa_tables, with_logits=with_logits,
                )
                if res is True:
                    if chunk[0][0].fork_group is not None:
                        # Fork of a prefix-hit span: the siblings addref the
                        # hit's pages through the primary's slot — pure
                        # sharing, zero prefill.
                        self._fork_after_admit(slot_ids[0], chunk[0][0],
                                               dfa_tables)
                    return
                if res == "full":
                    # Cached-admit program still compiling in the background:
                    # serve via full admission NOW. Under the paged pool the
                    # planner only budgeted the tail pages, so re-check the
                    # full need first and requeue if the pool can't cover it.
                    if (self._paged
                            and self._pages_needed(chunk[0][0])
                            > len(self._free_pages)):
                        with self._pending_lock:
                            self._pending.appendleft(chunk[0])
                        self._wake.set()
                        return
                elif self._paged:
                    # Stale hit under pool churn (the span was evicted or its
                    # fresh pages can't be covered): requeue so the next
                    # planning round re-budgets and re-scans — only the
                    # planning loop enforces pool backpressure, so an
                    # unbudgeted full admission here could hard-fail a
                    # request that merely needed to wait.
                    with self._pending_lock:
                        self._pending.appendleft(chunk[0])
                    self._wake.set()
                    return
        t0 = time.monotonic()
        # Multi-tenant LoRA (ISSUE 10): pin each request's adapter into a
        # device row BEFORE anything else is claimed — a fetch/promote
        # failure (disk error, injected adapter_fetch fault, all rows
        # pinned) then fails just this chunk (adapter requests admit as
        # singletons via _special) with nothing to unwind.
        adapter_rows = [0] * m
        acquired_rows: list[int] = []
        try:
            for j, (r, _h) in enumerate(chunk):
                if r.adapter:
                    row = self._adapter_acquire(r.adapter)
                    adapter_rows[j] = row
                    acquired_rows.append(row)
        except Exception:
            for row in acquired_rows:
                self._adapter_unpin(row)
            raise
        prompt_toks = np.zeros((m, bucket), np.int32)
        aux = np.zeros((3, m), np.int32)  # lens, slot ids, seeds
        aux[1] = np.asarray(slot_ids, np.int32)
        samp_pack = np.zeros((7, m), np.float32)
        bias_rows = None
        with_topk = False
        with_lp = False
        items = []
        for j, (r, _handle) in enumerate(chunk):
            ids = r.prompt_ids
            prompt_toks[j, : len(ids)] = ids
            aux[0, j] = len(ids)
            if r.seed is not None:
                aux[2, j] = r.seed & 0x7FFFFFFF
            else:
                # Randomized per request (reference default RAND_SEED=-1,
                # core/config/model_config.go:18).
                aux[2, j] = int.from_bytes(os.urandom(4), "little") & 0x7FFFFFFF
            for fi, k in enumerate(_SAMPLING_FIELDS):
                samp_pack[fi, j] = getattr(r, k)
            if r.logit_bias:
                if bias_rows is None:
                    bias_rows = np.zeros((m, V), np.float32)
                for tid, bval in r.logit_bias.items():
                    if 0 <= int(tid) < V:
                        bias_rows[j, int(tid)] = bval
            if r.grammar is not None and dfa_tables is None:
                with_topk = True
            if r.logprobs > 0:
                with_lp = True

        has_bias = bias_rows is not None
        # Multimodal admissions are singletons (m == 1, see _special).
        n_img = 0
        if m == 1 and chunk[0][0].image_embeds is not None:
            n_img = int(np.asarray(chunk[0][0].image_embeds).shape[0])
        with_mrope = (m == 1 and chunk[0][0].mrope_positions is not None)
        # Once any adapter is device-resident EVERY admission runs the
        # lora-enabled program (id 0 rows ride the exact-zero null adapter)
        # so mixed-tenant and adapter-less admissions share one compile.
        with_lora = self._lora_tree is not None
        with_dfa = self._dfa_mode_of(dfa_tables)
        fn = self._get_admit(m, bucket, has_bias, with_topk, with_lp, n_img,
                             with_dfa=with_dfa, with_mrope=with_mrope,
                             with_lora=with_lora, with_logits=with_logits)
        args_in = (
            self._upload(prompt_toks), self._upload(aux), self._upload(samp_pack),
            # lint: ignore[trace-safety] admit programs are compiled per (m, bucket) by design and warmed (warmup()); m is the admission group size, already bucketed by the batching loop
            self._upload(bias_rows) if has_bias else jnp.zeros((m, V), jnp.float32),
        )
        if n_img:
            embeds = np.asarray(chunk[0][0].image_embeds, np.float32)[None]  # [1, N, D]
            offsets = np.asarray([chunk[0][0].image_offset], np.int32)
            args_in = args_in + (self._upload(embeds), self._upload(offsets))
        if with_mrope:
            # [1, 3, bucket]: the prompt's 3D streams, padding continued
            # sequentially (padded rows are masked out of attention anyway).
            p3 = np.asarray(chunk[0][0].mrope_positions, np.int32)
            L3 = p3.shape[1]
            mrope_full = np.zeros((1, 3, bucket), np.int32)
            mrope_full[0, :, :L3] = p3
            if bucket > L3:
                last = p3[:, -1] if L3 else np.zeros((3,), np.int32)
                mrope_full[0, :, L3:] = (
                    last[:, None] + 1 + np.arange(bucket - L3)[None, :]
                )
            args_in = args_in + (self._upload(mrope_full),)
        if with_dfa:
            host = dfa_tables["host"]
            row = np.unpackbits(
                host.mask_bits[host.init_state], bitorder="little"
            )[:V].astype(bool)
            gmask0 = np.where(row, 0.0, -1e30).astype(np.float32)[None, :]
            ginit = np.full((m,), host.init_state, np.int32)
            args_in = args_in + (
                self._upload(gmask0), self._dfa_table(dfa_tables, with_dfa),
                dfa_tables["tok_cls"], self._upload(ginit),
            )
        allocated_slots: list[int] = []
        if self._paged:
            rows_tbl = np.zeros(
                (m, self._ml1 if self._hier else self._max_pages), np.int32
            )
            for j, (r, _h) in enumerate(chunk):
                prow = self._pages_alloc(slot_ids[j], self._pages_needed(r))
                if prow is None:
                    # Admission is page-gated at planning, but a cached-path
                    # fallback earlier this round may have spent more than
                    # its tail-only budget. Requeue the chunk (graceful
                    # backpressure) instead of killing the engine loop.
                    for s in allocated_slots:
                        self._pages_free(s)
                    for row in acquired_rows:
                        self._adapter_unpin(row)
                    with self._pending_lock:
                        for item in reversed(chunk):
                            self._pending.appendleft(item)
                    self._wake.set()
                    return
                allocated_slots.append(slot_ids[j])
                rows_tbl[j] = prow
            if self._hier:
                args_in = args_in + (
                    (self._upload(rows_tbl), self._upload(self.h_l0)),
                )
            else:
                args_in = args_in + (self._upload(rows_tbl),)
        if with_lora:
            args_in = args_in + (
                self._lora_tree, self._upload(adapter_rows, dtype=jnp.int32),
            )
        try:
            # The span a trace matches the admission's device execution to,
            # with the prompt tokens that execution carries.
            tokens = int(aux[0].sum())
            with self._phases.call("dispatch/admit", m=m, bucket=bucket,
                                   tokens=tokens):
                if self.draft_cfg is None:
                    pre = (self.params, self.cache, self.counts, self.rngs,
                           self.bias, self.d_tokens, self.d_positions)
                    if with_dfa:
                        pre = pre + (self.d_gstate,)
                    out = fn(*pre, *args_in)
                else:
                    pre = (self.params, self.cache, self.counts, self.rngs,
                           self.bias, self.d_tokens, self.d_positions,
                           self.draft_params, self.d_cache)
                    if with_dfa:
                        # admit_spec takes the dfa inputs after bias_rows, d_gstate last.
                        out = fn(*pre, *args_in, self.d_gstate)
                    else:
                        out = fn(*pre, *args_in)
        except Exception:
            # Slots were never claimed, so _release won't run — return the
            # reserved pages and adapter pins before surfacing the error.
            for s in allocated_slots:
                self._pages_free(s)
            for row in acquired_rows:
                self._adapter_unpin(row)
            raise
        (
            self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, toks, tk, lp,
        ) = out[:9]
        rest = out[9:]
        held = None
        if self._held_rows:
            held, rest = rest[0], rest[1:]
        if with_dfa:
            self.d_gstate = rest[0]
            rest = rest[1:]
        if self.draft_cfg is not None:
            self.d_cache = rest[0]
        if with_logits:
            self._fork_logits = out[-1]
        self._host_copy_async(toks)
        self._count_admit(m * bucket, tokens)
        # Claim slots only after a successful dispatch so a failed admission
        # (e.g. compile error) never leaks slot state.
        for j, ((r, handle), slot_idx) in enumerate(zip(chunk, slot_ids)):
            for k in _SAMPLING_FIELDS:
                self.h_sampling[k][slot_idx] = getattr(r, k)
            if self._mrope:
                # decode rope position = cache row + delta (0 for text-only)
                p3 = r.mrope_positions
                self.h_rope_delta[slot_idx] = (
                    int(np.asarray(p3).max()) + 1 - len(r.prompt_ids)
                    if p3 is not None else 0
                )
            self._slot_gen[slot_idx] += 1
            self.slots[slot_idx] = _Slot(
                request=r, handle=handle, prompt_len=int(aux[0, j]), scheduled=1,
                t_submit=t0, dfa=with_dfa, sched_rows=int(aux[0, j]),
            )
            self._apply_resume(slot_idx)
            self.h_active[slot_idx] = True
            self.h_override_mask[slot_idx] = False
            self.h_gmask[slot_idx] = 1.0 if with_dfa else 0.0
            self.h_adapter[slot_idx] = adapter_rows[j]
            items.append((slot_idx, r, handle, int(aux[0, j]), t0))
            self._jnote("admitted", rid=handle.rid, slot=slot_idx,
                        a=float(aux[0, j]), b=float(m))
            if r.image_embeds is None and r.adapter is None:
                # Adapter slots never feed the prefix cache: their K/V rows
                # are tenant-specific (wk/wv deltas), so a token-keyed span
                # would leak one tenant's KV into another's admission.
                self._defer_prefix_save(slot_idx, r.prompt_ids,
                                        int(aux[0, j]))
        self._track(
            _Entry(kind="admit", toks=toks, tk=tk, lp=lp, gen=list(self._slot_gen),
                   items=items, moe=held)
        )
        self._plan_dirty()
        self._last_admit_t = time.monotonic()
        if m == 1 and chunk[0][0].fork_group is not None:
            self._fork_after_admit(slot_ids[0], chunk[0][0], dfa_tables)

    # ------------------------------------------------------------------ #
    # Decode blocks
    # ------------------------------------------------------------------ #

    def _has_unscheduled(self) -> bool:
        """Some active slot still has token budget not covered by blocks
        already in flight."""
        for i in range(self.ecfg.max_slots):
            s = self.slots[i]
            if s is None or not self.h_active[i]:
                continue
            if (s.request.max_new_tokens - s.scheduled > 0
                    and self.ecfg.max_seq - s.prompt_len - s.scheduled > 0):
                return True
        return False

    def _pick_block_size(self) -> int:
        """Largest remaining token budget over active slots picks the block.

        remaining >= block_sizes[0] → that block (throughput: with a full
        batch some slot always has that much left, so a saturated engine
        dispatches nothing else). Otherwise the smallest block that covers
        `remaining` — one slightly-overshooting dispatch beats a tail of
        tiny dispatches, each with its own fixed dispatch cost. On the
        default sizes (16, 4, 1): 5 and more left give 16, 2-4 give 4, 1
        gives 1."""
        remaining = 1
        for i in range(self.ecfg.max_slots):
            s = self.slots[i]
            if s is None or not self.h_active[i]:
                continue
            rem = max(
                1,
                min(
                    s.request.max_new_tokens - s.scheduled,
                    self.ecfg.max_seq - s.prompt_len - s.scheduled,
                ),
            )
            remaining = max(remaining, rem)
        chosen = self.ecfg.block_sizes[0]
        for n in sorted(self.ecfg.block_sizes):
            if n >= remaining:
                return n
            chosen = n
        return chosen

    # thread: engine-loop-only
    def _plan_dirty(self) -> None:
        """Invalidate any prepared-ahead block plan (ISSUE 17). Called by
        every mutation that can change the next block's control decisions —
        slot claim/activation, release, preempt/resume, grammar override
        writes. One int bump; the staging path replans on the next idle
        wait, so a consumed plan is always what _plan_block would build at
        dispatch time (the byte-exactness invariant of the pipeline)."""
        self._ctrl_epoch += 1

    # thread: engine-loop-only
    def _stage_plan(self) -> bool:
        """Prepare-ahead (ISSUE 17): build the NEXT block's control plan
        while the loop waits on in-flight results, so the post-result path
        is commit + dispatch only. Plain decode only — _spec_plan COMMITS
        probe/bookkeeping state when it runs (must stay on the dispatch
        edge), and legacy-grammar blocks serialize at depth 1 anyway.
        Returns True when a plan was built this call (planning was this
        iteration's useful work, so the caller skips its sleep)."""
        if self._spec_mode != "off" or self._growth_blocked:
            return False
        sp = self._staged_plan
        if sp is not None and sp.epoch == self._ctrl_epoch:
            return False
        self._staged_plan = None
        if not self.h_active.any() or not self._has_unscheduled():
            return False
        self._phases.begin("prep")
        plan = self._plan_block(False)
        if isinstance(plan, _BlockPlan):
            self._staged_plan = plan
            return True
        return False

    def _plan_block(self, grammar: bool):
        """Build one decode block's control plan: no device work; the only
        scheduler mutation is on-demand page growth, which is monotone and
        idempotent (pages grown for a plan that is later invalidated stay
        valid for the replan, and page frees bump the plan epoch so a
        stale plan never survives them — running growth at STAGE time is
        therefore byte-equivalent to running it at dispatch).

        Returns a _BlockPlan; or "wait" when host history lags an
        in-flight spec verify round (drain before re-drafting); or None
        when the paged pool could not be grown to cover the block
        (_grow_for_decode already set _growth_blocked; the loop drains
        in-flight work and preempts the youngest slot, ISSUE 3).

        Shared verbatim by the dispatch path and the prepare-ahead path:
        pipelining exactness rests on this being the ONLY place block
        shape/variant/pack decisions are made."""
        B = self.ecfg.max_slots
        if grammar:
            variant, n = "grammar", 1
        else:
            act = [i for i in range(B) if self.h_active[i]]
            hs = self.h_sampling
            needs_filter = any(
                hs["temperature"][i] > 0
                and (hs["top_k"][i] > 0 or hs["top_p"][i] < 1 or hs["min_p"][i] > 0)
                for i in act
            )
            any_temp = any(hs["temperature"][i] > 0 for i in act)
            variant = "filtered" if needs_filter else ("simple" if any_temp else "greedy")
            n = self._pick_block_size()
        with_dfa = self._dfa_mode() if self._dfa_grammar_active() else False
        with_lp = self._lp_active()

        # Read-side KV window: smallest warmed bucket covering every ACTIVE
        # slot's current position (idle rows' reads are discarded, so any
        # window is safe for them). Only the throughput block size gets
        # windowed variants — small tail blocks move too few tokens to
        # matter and would multiply the compile surface.
        kv_win: Optional[int] = None
        # with_lp windows are warmed only when warmup(logprobs=True) ran;
        # engines warmed without it must not combine the two (mid-serving
        # compile stall).
        if (not grammar and not with_dfa and not (with_lp and not self._lp_warmed)
                and not self._paged
                and self._ring_mesh is None and n == self.ecfg.block_sizes[0]):
            maxpos = 1
            for i in range(B):
                s = self.slots[i]
                if s is not None and self.h_active[i]:
                    maxpos = max(maxpos, s.prompt_len + s.scheduled)
            w = self._KV_WIN_MIN
            while w < min(maxpos, self.ecfg.max_seq):
                w *= 2
            if w < self.ecfg.max_seq:
                kv_win = w

        # Speculative decoding (ISSUE 12): pick the draft source, plan this
        # round's per-slot draft lengths from the acceptance EWMA (and, for
        # prompt lookup, match availability), and dispatch a verify block
        # whenever anyone drafts. Stochastic verify keeps speculation exact
        # for sampled requests (greedy degenerates to argmax agreement);
        # model-free modes additionally compose with the device grammar DFA.
        smode = self._spec_mode
        spec_ok = (
            smode != "off"
            and not grammar
            and not with_lp
            and not self.h_override_mask.any()
            and not (smode == "draft_model" and with_dfa)
        )
        plan = self._spec_plan(smode) if spec_ok else None
        if isinstance(plan, str):  # "wait": host history lags an in-flight
            return "wait"          # verify round — drain before re-drafting
        if plan is None and spec_ok and smode in ("prompt_lookup",
                                                  "self_draft"):
            # Nothing to draft THIS round — keep the fallback block short
            # so the scheduler re-plans soon (token streams turn repetitive
            # mid-flight; a block longer than _SPEC_REPLAN_BLOCK would sail
            # past every match).
            for bs in sorted(self.ecfg.block_sizes, reverse=True):
                if bs <= self._SPEC_REPLAN_BLOCK:
                    n = min(n, bs)
                    break
        # On-demand page growth (ISSUE 3): the block's writes must resolve
        # through real pages BEFORE dispatch — rows past a slot's table
        # land in SCRATCH and would be silently lost.
        if not self._grow_for_decode((plan[0] + 1) if plan else n):
            return None
        self.m_peak_active = max(self.m_peak_active, int(self.h_active.sum()))
        with_lora = self._lora_tree is not None
        if plan is not None:
            return _BlockPlan(
                grammar=grammar, variant=variant, n=n, with_dfa=with_dfa,
                with_lp=with_lp, kv_win=kv_win, with_lora=with_lora,
                spec=(smode, plan), active=None, pack=None,
                epoch=self._ctrl_epoch,
            )
        active_snapshot = self.h_active.copy()
        pack = np.zeros((11 if with_dfa else 10, B), np.float32)
        pack[0] = active_snapshot
        for fi, k in enumerate(_SAMPLING_FIELDS):
            pack[1 + fi] = self.h_sampling[k]
        pack[8] = self.h_override_tok
        pack[9] = self.h_override_mask
        if with_dfa:
            pack[10] = self.h_gmask
        return _BlockPlan(
            grammar=grammar, variant=variant, n=n, with_dfa=with_dfa,
            with_lp=with_lp, kv_win=kv_win, with_lora=with_lora, spec=None,
            active=active_snapshot, pack=pack, epoch=self._ctrl_epoch,
        )

    def _dispatch_block(self, grammar: bool) -> bool:
        """Dispatch one decode block (or speculative round). Returns False
        without dispatching when the paged pool could not be grown to cover
        the block's writes — the loop then drains in-flight work and
        preempts the youngest slot (ISSUE 3). Consumes the prepared-ahead
        plan when one is still valid (same epoch, same grammar mode);
        otherwise plans inline (ISSUE 17)."""
        faults.fire("device_dispatch")
        if self.plan.total > 1:
            # Sharded decode dispatch — see _dispatch_admit (ISSUE 7).
            faults.fire("collective_dispatch")
        plan = self._staged_plan
        self._staged_plan = None
        if (not isinstance(plan, _BlockPlan) or plan.epoch != self._ctrl_epoch
                or plan.grammar != grammar):
            self._phases.begin("prep")
            plan = self._plan_block(grammar)
        if plan is None or isinstance(plan, str):
            return False
        if plan.spec is not None:
            self._phases.begin("dispatch")
            smode, sp = plan.spec
            self._dispatch_spec_block(smode, sp[0], sp[1], sp[2],
                                      plan.with_dfa)
            return True
        return self._commit_block(plan)

    def _commit_ctrl(self, p: "_BlockPlan"):
        """ONE batched H2D control commit for a decode block (ISSUE 17):
        the sampling/override pack plus, when the model takes them, the
        rope-delta and adapter-row vectors ride a single stacked f32 array
        through the dirty-diff stager — a steady-state block whose control
        state did not change issues ZERO transfers; any change issues
        exactly one. Every carried value is f32 sampling state or a small
        int (< 2^24: token ids, rope deltas, adapter rows), so the f32
        stack is exact and the int rows cast back losslessly. Returns
        (d_pack, d_rope, d_adapter)."""
        faults.fire("control_commit")
        rope = self._mrope
        adapter = p.with_lora
        parts = [p.pack]
        if rope:
            parts.append(np.asarray(self.h_rope_delta, np.float32)[None])
        if adapter:
            parts.append(np.asarray(self.h_adapter, np.float32)[None])
        ctrl = p.pack if len(parts) == 1 else np.concatenate(parts, axis=0)
        npk = p.pack.shape[0]
        extra = len(parts) > 1

        def build(dev):
            # Runs only on upload; the derived views are cached with the
            # entry, so a steady-state hit re-serves them with zero device
            # work.
            d_pack = dev[:npk] if extra else dev
            i = npk
            d_rope = d_adapter = None
            if rope:
                d_rope = dev[i].astype(jnp.int32)
                i += 1
            if adapter:
                d_adapter = dev[i].astype(jnp.int32)
            return (d_pack, d_rope, d_adapter)

        return self._ctrl.commit(f"ctrl{ctrl.shape[0]}", ctrl, build=build)

    def _commit_block(self, p: "_BlockPlan") -> bool:
        """Commit + dispatch a planned plain decode block: upload whatever
        control state changed (usually nothing), launch the block program,
        advance scheduling. The post-result hot path of the pipelined loop
        is exactly this method (ISSUE 17)."""
        n = p.n
        active_snapshot = p.active
        self._phases.begin("commit")
        fn = self._get_block(p.variant, n, p.with_lp, p.with_dfa, p.kv_win,
                             p.with_lora)
        d_pack, d_rope, d_adapter = self._commit_ctrl(p)
        args = (
            self.params, self.cache, self.counts, self.rngs, self.bias,
            self.d_tokens, self.d_positions, d_pack,
        )
        if self._mrope:
            args = args + (d_rope,)
        if self._paged:
            args = args + (self._ptable_device(),)
        lora_args = ((self._lora_tree, d_adapter) if p.with_lora else ())
        self._phases.begin("dispatch")
        if p.with_dfa:
            d = self._dfa
            args = args + (d["mask_bits"], self._dfa_table(d, p.with_dfa),
                           d["tok_cls"], self.d_gstate)
        # The span a trace matches the block's device execution to: its
        # steps and the rows that were live when it was dispatched.
        with self._phases.call("dispatch/decode_block", n=n,
                               live=int(active_snapshot.sum())):
            out = fn(*args, *lora_args)
        if p.with_dfa:
            (
                self.cache, self.counts, self.rngs, self.d_tokens,
                self.d_positions, toks_block, tk_block, lp_block, moe_block,
                self.d_gstate,
            ) = out
            self.m_dfa_tokens += n * int((self.h_gmask * active_snapshot).sum())
        else:
            (
                self.cache, self.counts, self.rngs, self.d_tokens, self.d_positions,
                toks_block, tk_block, lp_block, moe_block,
            ) = out
        self._host_copy_async(toks_block)
        if tk_block is not None:
            self._host_copy_async(tk_block)
        self.h_override_mask[:] = False
        held = 0  # pool rows the live slots hold as the block starts
        ringed = 0  # ... and of those, the rows inside a window layer's ring
        ring = self.cfg.ring_rows if self.cfg.recurrent_kind == "swa" else 0
        for i in range(self.ecfg.max_slots):
            if active_snapshot[i] and self.slots[i] is not None:
                held += self.slots[i].sched_rows
                ringed += min(self.slots[i].sched_rows, ring)
                self.slots[i].scheduled += n
                self.slots[i].sched_rows += n
        if ring:
            # What ONE window layer's reader walks in this block and what it
            # would have walked at full length: a slot's rows at dispatch,
            # cut to the ring, every step (the block's own ride in its
            # window).
            self.m_window_rows_read += n * ringed
            self.m_window_rows_full += n * held
            self._jnote("window_rows", a=float(n * ringed), b=float(n * held))
        if self.cfg.is_mla and self._paged:
            # What the latent walk reads: every step of the block walks the
            # rows its slots held at dispatch (the block's own rows ride in
            # the window), of a pool of so many rows.
            self._jnote("latent_rows", a=float(n * held), b=float(
                n * self.ecfg.kv_pages * self.ecfg.kv_page_size))
        entry = _Entry(
            kind="block", toks=toks_block, tk=tk_block, lp=lp_block,
            gen=list(self._slot_gen), active=active_snapshot, n=n,
            moe=moe_block,
        )
        self._track(entry)
        for i in range(self.ecfg.max_slots):
            if active_snapshot[i] and self._budget_covered(i):
                self._park(i, entry)
        return True

    # thread: engine-loop-only
    def _budget_covered(self, i: int) -> bool:
        """Will no block after the ones in flight carry a token of slot i's
        request, whatever it generates? True when its budget (max_new_tokens,
        or the end of the context) is scheduled in full and nothing but the
        budget decides what the next block holds for it: a host-walk grammar
        writes the slot's override for its next block, a speculative
        engine's `scheduled` is a lower bound, a staged fork copies the live
        slot's rows."""
        s = self.slots[i]
        if s is None or self._spec_mode != "off":
            return False
        if s.request.grammar is not None and not s.dfa:
            return False
        if (s.request.max_new_tokens - s.scheduled > 0
                and self.ecfg.max_seq - s.prompt_len - s.scheduled > 0):
            return False
        if self._fork_requests:
            with self._fork_lock:
                if any(src is s.handle for src, _s, _h in self._fork_requests):
                    return False
        return True

    # thread: engine-loop-only
    def _park(self, i: int, last: _Entry) -> None:
        """Hand slot index i on: its request's budget is covered by `last`,
        the block just dispatched, so the rows of every later block are
        free for the next request. What the index held moves to a _Parked
        record on the request, found again by the generation `last` and the
        earlier entries carry (_tenant); the index is left as _release
        leaves it, and the next _admit_pending seats the queue's head there
        with its admission program behind `last`. The device runs programs
        in dispatch order and `last` shipped the table it was planned with,
        so the old tenant's rows and the new one's prefill never meet."""
        slot = self.slots[i]
        will_save = self._saves_at_finish(slot)
        self._settle_deferred_saves(i, will_save)
        snap = None
        if will_save and not self._paged:
            # The finish-time save reads the cache row, which the next
            # tenant's prefill overwrites: copy it now, behind `last`.
            rows = slot.prompt_len + min(slot.scheduled,
                                         slot.request.max_new_tokens) - 1
            if rows >= self.ecfg.prefix_cache_min:
                snap = self._snapshot_rows(i, min(rows, self.ecfg.max_seq))
        slot.parked = _Parked(
            idx=i, gen=self._slot_gen[i], last=last,
            pages=self._slot_pages[i], tps=self._slot_tps[i],
            spill=self._slot_spill[i], adapter_row=int(self.h_adapter[i]),
            snap=snap,
        )
        self._parked[(i, self._slot_gen[i])] = slot
        # The record owns them now; the index keeps nothing to free.
        self._slot_pages[i] = []
        self._slot_tps[i] = []
        self._slot_spill[i] = {}
        self.h_adapter[i] = 0
        self._release(i)  # moves the index on to its next generation

    # thread: engine-loop-only
    def _tenant(self, i: int, gen: int) -> Optional[_Slot]:
        """The request a dispatched entry carried in row i under generation
        `gen`: the live one, a parked one, or None (ended, preempted)."""
        if self._slot_gen[i] == gen:
            return self.slots[i]
        return self._parked.get((i, gen))

    def _tenants(self) -> list[_Slot]:
        """Every request the engine holds state for: the slots' live ones
        and the parked ones (_park). A copy; any thread may ask."""
        return [s for s in [*self.slots, *list(self._parked.values())]
                if s is not None]

    def _spec_len_for(self, i: int, kmax: int) -> int:
        """EWMA-chosen draft length for one active slot (pure — probe
        bookkeeping happens when the plan COMMITS). Below the floor a cold
        slot drafts 0 (plain decode) until its probe counter re-tries the
        smallest nonzero bucket so it can warm back up when its stream
        turns predictable again."""
        a = float(self.h_accept_ewma[i])
        if a < self._SPEC_EWMA_FLOOR:
            if self._spec_probe[i] >= self._SPEC_PROBE_EVERY:
                for b in self._spec_buckets:
                    if b > 0:
                        return min(b, kmax)
            return 0
        return max(1, min(kmax, int(round(a * kmax))))

    def _lookup_propose(self, i: int, kmax: int) -> list:
        """Draft continuation for slot i from its suffix index, (re)built
        lazily per slot generation and fed only the history delta since the
        last call (prompt first, then the generated tail)."""
        slot = self.slots[i]
        gen = self._slot_gen[i]
        st = self._lookup[i]
        if st is None or st[0] != gen:
            st = (gen, speclookup.SuffixIndex(), 0)
        _g, ix, fed = st
        hist_p = slot.request.prompt_ids
        total = len(hist_p) + len(slot.generated)
        if fed < total:
            if fed < len(hist_p):
                ix.extend(hist_p[fed:])
                fed = len(hist_p)
            ix.extend(slot.generated[fed - len(hist_p):])
            fed = total
        self._lookup[i] = (gen, ix, fed)
        return ix.propose(kmax)

    def _spec_plan(self, mode: str):
        """Plan one verify round: per-slot draft lengths from the
        acceptance EWMA (+ proposal availability for prompt lookup), the
        block's draft window bucketed up to the smallest covering entry of
        spec_draft_buckets. Returns (kb, dlens [B], drafts [B, kb] | None),
        None when every active slot drafts 0 this round (the caller then
        dispatches a plain block), or "wait" when a prompt-lookup draft is
        available but in-flight dispatches still carry unprocessed tokens —
        proposals mined from a lagging host history would continue from the
        wrong point and be rejected wholesale, so the loop drains first
        (a round then drafts against the true suffix)."""
        B = self.ecfg.max_slots
        kmax = self._spec_buckets[-1]
        dlens = np.zeros((B,), np.int32)
        drafts = np.zeros((B, kmax), np.int32) if mode == "prompt_lookup" else None
        for i in range(B):
            if not self.h_active[i] or self.slots[i] is None:
                continue
            want = self._spec_len_for(i, kmax)
            if mode == "prompt_lookup" and want > 0:
                prop = self._lookup_propose(i, kmax)
                want = min(want, len(prop))
                if want > 0:
                    drafts[i, :want] = prop[:want]
            dlens[i] = want
        need = int(dlens.max()) if dlens.size else 0
        if need > 0 and mode == "prompt_lookup":
            for e in self._inflight:
                # Any entry that will still append tokens to the history
                # ("admit"/"block"/"spec") makes the mined suffix stale.
                if e.kind != "chunk":
                    return "wait"
        # COMMIT: probe ticks + the draft-length histogram record only for
        # plans that actually schedule (wait iterations spin on the loop).
        for i in range(B):
            if not self.h_active[i] or self.slots[i] is None:
                continue
            if dlens[i] == 0:
                if self.h_accept_ewma[i] < self._SPEC_EWMA_FLOOR:
                    self._spec_probe[i] += 1
            elif self.h_accept_ewma[i] < self._SPEC_EWMA_FLOOR:
                self._spec_probe[i] = 0  # probe fired: one trial round
            self.m_spec_dlen_hist[int(dlens[i])] = (
                self.m_spec_dlen_hist.get(int(dlens[i]), 0) + 1
            )
        if need == 0:
            return None
        kb = next(b for b in self._spec_buckets if b >= need)
        if mode == "self_draft":
            self._spec_sd_sync()
        return kb, dlens, (drafts[:, :kb] if drafts is not None else None)

    def _spec_sd_sync(self) -> None:
        """Resync the self-draft scratch KV for slots whose generation
        changed (fresh admission, swap/recompute resume): the target
        cache's stored rows for the first self_draft_layers layers are
        exactly what the early-exit scan would have written, so one copy
        program serves every admission flavor — no new admit families."""
        for i in range(self.ecfg.max_slots):
            if not self.h_active[i] or self.slots[i] is None:
                continue
            if self._sd_gen[i] == self._slot_gen[i]:
                continue
            if self._paged:
                pages = self._slot_pages[i]
                npgb = self._pow2_pages(max(1, len(pages)))
                rows = np.full((npgb,), self.ecfg.kv_pages, np.int32)
                rows[:len(pages)] = pages  # padding gathers SCRATCH rows
                with self._phases.call("call/sd_sync"):
                    self.sd_cache = self._get_sd_sync_paged(npgb)(
                        self.sd_cache, self.cache, self._upload(rows),
                        jnp.int32(i),
                    )
            else:
                with self._phases.call("call/sd_sync"):
                    self.sd_cache = self._get_sd_sync()(
                        self.sd_cache, self.cache, jnp.int32(i)
                    )
            self._sd_gen[i] = self._slot_gen[i]

    def _get_sd_sync(self):
        """Dense-cache → self-draft scratch copy for one slot (full row —
        rows past the live context are never attended)."""
        fn = self._block_cache.get(("sd-sync",))
        if fn is not None:
            return fn
        kl = self._sd_layers

        def sync(sd, cache, slot):
            return llama.KVCache(
                k=sd.k.at[:, slot].set(cache.k[:kl, slot].astype(sd.k.dtype)),
                v=sd.v.at[:, slot].set(cache.v[:kl, slot].astype(sd.v.dtype)),
            )

        fn = self._jit(sync, "sd_sync", leaf="attention/cache_write",
                       donate_argnums=(0,))
        self._block_cache[("sd-sync",)] = fn
        return fn

    def _get_sd_sync_paged(self, npgb: int):
        """Page-pool → self-draft scratch gather for one slot, compiled per
        power-of-two page-count bucket (same family policy as the swap
        gathers). fp8 pool rows dequantize through the engine's kv scales
        so the scratch stays model-dtype like a draft model's cache."""
        key = ("sd-sync", npgb)
        fn = self._block_cache.get(key)
        if fn is not None:
            return fn
        kl = self._sd_layers
        page = self.ecfg.kv_page_size
        S = self.ecfg.max_seq
        W = min(npgb * page, S)
        scales = self._kv_scales

        def sync(sd, cache, pages, slot):
            gk = cache.k[:kl, pages]  # [kl, npgb, page, K, Dk]
            gv = cache.v[:kl, pages]
            gk = gk.reshape(kl, npgb * page, *gk.shape[3:])[:, :W]
            gv = gv.reshape(kl, npgb * page, *gv.shape[3:])[:, :W]
            if scales is not None:
                gk = gk.astype(jnp.float32) * scales[0][None, None, :, None]
                gv = gv.astype(jnp.float32) * scales[1][None, None, :, None]
            return llama.KVCache(
                k=sd.k.at[:, slot, :W].set(gk.astype(sd.k.dtype)),
                v=sd.v.at[:, slot, :W].set(gv.astype(sd.v.dtype)),
            )

        fn = self._jit(sync, "sd_sync_paged", leaf="attention/cache_write",
                       donate_argnums=(0,))
        self._block_cache[key] = fn
        return fn

    def _dispatch_spec_block(self, mode: str, kb: int, dlens: np.ndarray,
                             drafts: Optional[np.ndarray],
                             with_dfa) -> None:
        """One speculative round for the chosen draft source: draft a
        (per-slot ≤ kb) window + verify. Emits 1..kb+1 tokens per active
        slot (kind="spec"; tk carries accepted counts)."""
        faults.fire("spec_verify")
        B = self.ecfg.max_slots
        active_snapshot = self.h_active.copy()
        pack = np.zeros((10, B), np.float32)
        pack[0] = active_snapshot
        for fi, k in enumerate(_SAMPLING_FIELDS):
            pack[1 + fi] = self.h_sampling[k]
        pack[8] = dlens
        if with_dfa:
            pack[9] = self.h_gmask
        # Draft-model engines reject adapters (typed AdapterError); the
        # model-free verify chunk threads the tenant deltas through.
        with_lora = self._lora_tree is not None and mode != "draft_model"
        fn = self._get_spec_block(mode, kb, with_dfa=with_dfa,
                                  with_lora=with_lora)
        if mode == "draft_model":
            args = (self.params, self.draft_params, self.cache, self.d_cache)
        elif mode == "self_draft":
            args = (self.params, self.cache, self.sd_cache)
        else:
            args = (self.params, self.cache)
        args = args + (
            self.counts, self.rngs, self.bias, self.d_tokens,
            self.d_positions, self._upload(pack),
        )
        if mode == "prompt_lookup":
            args = args + (self._upload(drafts),)
        if self._paged:
            args = args + (self._ptable_device(),)
        if with_dfa:
            d = self._dfa
            args = args + (d["mask_bits"], self._dfa_table(d, with_dfa),
                           d["tok_cls"], self.d_gstate)
        if with_lora:
            args = args + (self._lora_tree, self._upload(self.h_adapter))
        with self._phases.call("dispatch/spec_block", n=kb + 1,
                               live=int(active_snapshot.sum())):
            out = fn(*args)
        if mode == "draft_model":
            self.cache, self.d_cache = out[0], out[1]
            rest = out[2:]
        elif mode == "self_draft":
            self.cache, self.sd_cache = out[0], out[1]
            rest = out[2:]
        else:
            self.cache = out[0]
            rest = out[1:]
        (
            self.counts, self.rngs, self.d_tokens, self.d_positions,
            toks_out, acc,
        ) = rest[:6]
        if with_dfa:
            self.d_gstate = rest[6]
            self.m_dfa_tokens += int((self.h_gmask * active_snapshot).sum())
        self._host_copy_async(toks_out)
        self._host_copy_async(acc)
        nact = int(active_snapshot.sum())
        drafted = int(dlens[active_snapshot].sum())
        self.h_draft_len[active_snapshot] = dlens[active_snapshot]
        self.m_spec_draft_len = drafted / max(1, nact)
        self._jnote("spec_draft", a=float(drafted), b=float(kb))
        for i in range(B):
            if active_snapshot[i] and self.slots[i] is not None:
                self.slots[i].scheduled += 1  # ≥1 token guaranteed per round
                # Page growth must cover the whole verify window (kb+1 rows
                # are written even when fewer tokens are accepted).
                self.slots[i].sched_rows += kb + 1
        self._track(
            _Entry(
                kind="spec", toks=toks_out, tk=acc,
                gen=list(self._slot_gen), active=active_snapshot,
                n=kb + 1, dlens=dlens.copy(),
            )
        )

    # ------------------------------------------------------------------ #
    # Result processing (host bookkeeping)
    # ------------------------------------------------------------------ #

    def _charge(self) -> None:
        """Account wall time toward decode throughput. An interval counts if
        slots were active at EITHER end — the iteration that processes a
        block's results (and deactivates finished slots) spends the block's
        whole execution inside np.asarray, and charging by the end state
        alone would drop it, inflating tok/s most for large blocks. Runs on
        the loop thread only."""
        now = time.monotonic()
        active = bool(self.h_active.any())
        if self._charge_was_active or active:
            self._decode_time += now - self._charge_last
        self._charge_last = now
        self._charge_was_active = active

    def _process_entry(self, e: _Entry) -> None:
        # `pull` is the time blocked on the device result (nothing when the
        # drainer already has it); `process` is posting it.
        self._phases.begin("pull")
        if isinstance(e.host, Exception):
            raise e.host
        if e.host is not None:
            toks, tk, lp, moe = e.host  # pre-pulled by the drainer thread
        else:
            # Forced processing (depth pressure) before the drainer got
            # there: wait for the result in slices, so that the blocked
            # time is `loop/pull` spans a capture can hold (LoopPhases);
            # the drainer sets _wake the moment its own copy is done. Then
            # pull inline. np.asarray is idempotent, so the drainer
            # finishing its own copy later is harmless.
            while not e.ready():
                self._phases.wait(self._wake, SPAN_SLICE_S)
                self._wake.clear()
                self._phases.begin("pull")
            # lint: ignore[trace-safety] deliberate sync point: the drainer thread usually completed the copy (this is a cheap wait, not a walk), and when it has not, the loop NEEDS these results to schedule the next block
            toks = np.asarray(e.toks)
            # lint: ignore[trace-safety] same drainer-backed pull as toks above
            tk = np.asarray(e.tk) if e.tk is not None else None
            lp = (
                tuple(np.asarray(a) for a in e.lp) if e.lp is not None else None
            )  # (tok_lp, lp_ids, lp_vals)
            moe = np.asarray(e.moe) if e.moe is not None else None
        self._phases.begin("process")
        # Charge the just-completed block's interval BEFORE any done events
        # post: a caller reading the throughput counters right after
        # result() returns must see this block's time in the denominator.
        self._charge()
        if e.kind == "chunk":
            # Mid prefill chunk: its KV landed on device, nothing to post —
            # the FINAL chunk rides an "admit" entry with the first token.
            return
        if e.kind == "spec":
            # toks [kb+1, B] with -1 marking not-emitted; tk holds accepted
            # counts per slot. Only slots that actually emit count toward the
            # acceptance-rate denominator (pipelined overshoot rounds after a
            # request finished would otherwise dilute it).
            consumed = 0
            emitted_per = np.zeros((self.ecfg.max_slots,), np.int64)
            for step in range(e.n):
                self._phases.begin("process")  # slices a long phase's span
                for i in range(self.ecfg.max_slots):
                    if not e.active[i] or self._slot_gen[i] != e.gen[i]:
                        continue
                    if self.slots[i] is None:
                        continue
                    tok = int(toks[step, i])
                    if tok < 0:
                        continue
                    consumed += 1
                    emitted_per[i] += 1
                    self._note_decode_first(i, self.slots[i].handle)
                    self._post_token(i, tok)
            self.m_spec_rounds += int((emitted_per > 0).sum())
            self.m_spec_accepted += consumed
            self._decode_tokens += consumed
            self._count_rows(e, consumed)
            # Acceptance-aware scheduling (ISSUE 12): fold each slot's
            # accepted/drafted ratio into its EWMA — the NEXT round's draft
            # length comes from it. A round always emits one non-draft
            # token (bonus or resample), so accepted drafts = emitted - 1.
            # Slots freed while processing keep their claim-time reset.
            drafted = 0
            alpha = self.ecfg.spec_accept_ewma
            for i in range(self.ecfg.max_slots):
                if emitted_per[i] == 0 or e.dlens is None:
                    continue
                drafted += int(e.dlens[i])
                if (e.dlens[i] > 0 and self.slots[i] is not None
                        and self._slot_gen[i] == e.gen[i]):
                    ratio = (emitted_per[i] - 1) / float(e.dlens[i])
                    self.h_accept_ewma[i] = (
                        (1.0 - alpha) * self.h_accept_ewma[i] + alpha * ratio
                    )
            self.m_spec_drafted += drafted
            self._jnote("spec_verify", a=float(drafted), b=float(consumed))
            return
        if e.kind == "admit":
            if moe is not None:
                self._count_admit_routing(moe)
            for j, (slot_idx, request, handle, plen, _t0) in enumerate(e.items):
                # A short budget is covered by the request's first block,
                # which may be dispatched (and the request parked) before
                # this admission has come back.
                slot = self._tenant(slot_idx, e.gen[slot_idx])
                if slot is None:
                    continue
                tok = int(toks[j])
                if request.grammar is not None and not slot.dfa:
                    chosen = self._grammar_choose(request, tok, tk[j])
                    if chosen is None:
                        handle._q.put(TokenEvent(
                            kind="error",
                            error="grammar admits no token from this model's vocabulary",
                        ))
                        self._release(slot_idx)
                        continue
                    if chosen != tok:
                        self.h_override_tok[slot_idx] = chosen
                        self.h_override_mask[slot_idx] = True
                        self._plan_dirty()
                    tok = chosen
                tr = handle.trace
                if not slot.t_first:
                    # Resumed slots keep their original TTFT; only a truly
                    # first token stamps it.
                    slot.t_first = time.monotonic()
                    self._jnote("first_token", rid=handle.rid, slot=slot_idx)
                    if tr is not None:
                        tr.note("first_token")
                elif tr is not None:
                    # A recompute resume re-admits through the ordinary
                    # admission program — mark the stream back in decode.
                    tr.note("resumed")
                self.m_prompt_tokens += plen
                lpj = (lp[0][j], lp[1][j], lp[2][j]) if lp is not None else None
                self._post_token(slot_idx, tok, lpj, slot)
            return

        consumed = 0
        for step in range(e.n):
            self._phases.begin("process")  # slices a long phase's span
            for i in range(self.ecfg.max_slots):
                if not e.active[i]:
                    continue
                # The row's tokens go to the request of the entry's own
                # generation: the live one, or one parked since (_park).
                slot = self._tenant(i, e.gen[i])
                if slot is None:
                    continue
                tok = int(toks[step, i])
                if slot.request.grammar is not None and not slot.dfa:
                    chosen = self._grammar_choose(slot.request, tok, tk[step, i])
                    if chosen is None:
                        slot.handle._q.put(TokenEvent(
                            kind="error",
                            error="grammar admits no token from the candidate set",
                        ))
                        self._release(i)
                        continue
                    if chosen != tok:
                        self.h_override_tok[i] = chosen
                        self.h_override_mask[i] = True
                        self._plan_dirty()
                    tok = chosen
                consumed += 1
                lpi = (lp[0][step, i], lp[1][step, i], lp[2][step, i]) if lp is not None else None
                self._note_decode_first(i, slot.handle)
                self._post_token(i, tok, lpi, slot)
        for slot in [s for s in self._parked.values() if s.parked.last is e]:
            # Cannot happen while `scheduled` counts a plain block's tokens
            # exactly; a tenant left parked would never get its `done`.
            log.error("parked tenant of slot %d outlived its last block",
                      slot.parked.idx)
            self._finish(slot.parked.idx, "length", slot)
        self._decode_tokens += consumed
        self._count_rows(e, consumed)
        if moe is not None:
            self._count_routing(e, moe)

    # thread: engine-loop-only
    def _count_admit_routing(self, walked: np.ndarray) -> None:
        """Account one admission program's expert path under an expert
        share, as its grouped kernel saw it (`llama.prefill(expert_rows=
        True)`, summed on the device over the MoE layers): the sorted (row,
        pick) pairs the kernel was compiled for and those in a held group;
        the rest sort last and their tiles are not visited. Nothing where
        the kernel did not run (few rows, off the TPU: `llama._mlp`)."""
        pairs, held = int(walked[0]), int(walked[1])
        if not pairs:
            return
        self.m_moe_admit_rows += pairs
        self.m_moe_admit_rows_held += held
        self._jnote("moe_admit_rows", a=float(pairs), b=float(held))

    # thread: engine-loop-only
    def _count_routing(self, e: _Entry, sums: np.ndarray) -> None:
        """Account one decode block's routing, summed on the device over its
        steps and MoE layers: of the expert slots offered (steps x MoE layers
        x experts) how many got at least one of the compiled batch rows, and
        the busiest expert's rows against the mean rows per expert (rows x
        top-k / experts). Every compiled row counts, live or not: the
        all-experts kernel computes them all."""
        cfg = self.cfg
        layers = cfg.num_layers - cfg.first_k_dense
        slots = e.n * layers * cfg.experts_here
        picks = (e.n * layers * self.ecfg.max_slots
                 * cfg.num_experts_per_token)
        mean = picks / cfg.num_experts
        if cfg.expert_share is not None:
            # Of the picks the router made, those of an expert held here.
            self.m_moe_picks += picks
            self.m_moe_picks_here += int(sums[2])
            self._jnote("moe_here", a=float(picks), b=float(sums[2]))
        self.m_moe_slots += slots
        self.m_moe_slots_hit += int(sums[0])
        self.m_moe_rows_busiest += int(sums[1])
        self.m_moe_rows_mean += mean
        self._jnote("moe_experts", a=float(slots), b=float(sums[0]))
        self._jnote("moe_load", a=float(sums[1]), b=float(mean))

    # thread: engine-loop-only
    def _count_rows(self, e: _Entry, posted: int) -> None:
        """Account one decode or spec block's rows: steps x compiled batch
        rows were computed; `posted` of them carried a token that a handle
        received; rows not live at dispatch were `empty`; the rest were
        live at dispatch and lost before their step (the request ended
        inside the block, it was preempted, or a verify round rejected the
        draft) — `overshoot`. dispatched = posted + overshoot + empty,
        exactly. A request that ends on its budget overshoots by the rest
        of its last block only (its index is handed on when that block is
        dispatched, _park: the rows after it are the next request's, or
        `empty`); one that ends sooner, a host-walk grammar's and a
        speculative engine's by up to pipeline_depth blocks more."""
        rows = e.n * self.ecfg.max_slots
        live = e.n * int(e.active.sum())
        self.m_rows_dispatched += rows
        self.m_rows_posted += posted
        self.m_rows_empty += rows - live
        self.m_rows_overshoot += live - posted
        # The same account per block, for a reader that needs it over an
        # exact span of time rather than between two scrapes.
        self._jnote("decode_rows", a=float(rows), b=float(posted))
        self._jnote("decode_rows_lost", a=float(live - posted),
                    b=float(rows - live))
        if self.cfg.is_hybrid:
            # Every row of the recurrent state is updated every step
            # (a, over the recurrent layers); b of them belonged to a tenant.
            kl = len(self.cfg.recurrent_layers)
            self._jnote("state_rows", a=float(rows * kl), b=float(live * kl))

    # thread: engine-loop-only
    def _note_decode_first(self, slot_idx: int, h: RequestHandle) -> None:
        """Journal, once per request, the first token it gets from a decode
        block: `first_token` -> `decode_first` is its wait to join the
        decode stream (blocks dispatched before its admission run first)."""
        if h.join_blocks < 0:
            return
        self._jnote("decode_first", rid=h.rid, slot=slot_idx,
                    a=float(h.join_blocks))
        if h.trace is not None:
            h.trace.note("decode_first")
        h.join_blocks = -2

    # ------------------------------------------------------------------ #
    # Grammar-constrained decoding
    # ------------------------------------------------------------------ #

    def _token_str(self, tok: int) -> str:
        if self._tok_strs is None:
            self._tok_strs = self.tokenizer.token_strings()
        return self._tok_strs[tok] if 0 <= tok < len(self._tok_strs) else ""

    def token_text(self, tok: int) -> str:
        """Decoded string for one token id (logprob entries in the API)."""
        return self._token_str(tok)

    def _first_char_buckets(self) -> dict[str, list[int]]:
        """Token ids grouped by first character (built once per tokenizer) —
        bounds the full-vocab grammar fallback to buckets whose first char the
        machine currently allows."""
        if not hasattr(self, "_fc_buckets"):
            buckets: dict[str, list[int]] = {}
            eos = set(self.tokenizer.eos_ids)
            for tok in range(self.cfg.vocab_size):
                if tok in eos:
                    continue
                s = self._token_str(tok)
                if s:
                    buckets.setdefault(s[0], []).append(tok)
            self._fc_buckets = buckets
        return self._fc_buckets

    def _grammar_choose(self, request: GenRequest, sampled: int, candidates: np.ndarray) -> Optional[int]:
        """Pick the highest-probability grammar-valid token.

        The sampled token keeps priority (preserves temperature sampling when
        the model already follows the grammar); otherwise candidates are
        walked in probability order; EOS is valid only once the grammar is
        complete. Falls back to a first-char-bucketed vocab scan before
        giving up.
        """
        g = request.grammar
        complete = g.complete()

        def ok(tok: int) -> bool:
            if tok in self.tokenizer.eos_ids:
                return complete
            return g.allowed(self._token_str(tok))

        if ok(sampled):
            self._grammar_advance(g, sampled)
            return sampled
        for tok in candidates.tolist():
            if tok == sampled:
                continue
            if ok(tok):
                self._grammar_advance(g, int(tok))
                return int(tok)
        # Rare fallback: scan only the first-char buckets the machine allows,
        # so the worst case is bounded by the size of the legal buckets, not
        # |V| machine clones.
        for c, toks in self._first_char_buckets().items():
            if not g.allowed(c):
                continue
            for tok in toks:
                if g.allowed(self._token_str(tok)):
                    self._grammar_advance(g, tok)
                    return tok
        if complete:
            return next(iter(self.tokenizer.eos_ids), None)
        return None

    def _grammar_advance(self, g, tok: int) -> None:
        if tok not in self.tokenizer.eos_ids:
            g.advance(self._token_str(tok))

    # ------------------------------------------------------------------ #
    # Token bookkeeping / streaming
    # ------------------------------------------------------------------ #

    def _post_token(self, slot_idx: int, tok: int, lp=None,
                    slot: Optional[_Slot] = None) -> None:
        """Append one generated token to a slot: stream text, check stops.

        lp, when present, is this step's (tok_lp scalar, lp_ids [LK],
        lp_vals [LK]) from the decode/admit program. `slot` is the request
        the token belongs to when that is not (or may not be) the index's
        live one: a parked tenant (_tenant).
        """
        if slot is None:
            slot = self.slots[slot_idx]
        assert slot is not None
        r, handle = slot.request, slot.handle
        if handle.cancelled.is_set():
            self._finish(slot_idx, "stop", slot)
            return

        logprob = None
        top_logprobs = None
        if lp is not None and r.logprobs > 0:
            tok_lp, lp_ids, lp_vals = lp
            logprob = float(tok_lp)
            # Grammar overrides replace the sampled token; recover the
            # emitted token's logprob from the top-LK list when possible.
            # (DFA slots sample directly from the masked distribution, so
            # their tok_lp already describes the emitted token.)
            ids = lp_ids.tolist()
            if r.grammar is not None and not slot.dfa:
                logprob = float(lp_vals[ids.index(tok)]) if tok in ids else None
            top_logprobs = [
                (int(i), float(v)) for i, v in zip(ids[: r.logprobs], lp_vals[: r.logprobs])
            ]

        is_eos = (not r.ignore_eos) and tok in self.tokenizer.eos_ids
        if not is_eos:
            slot.generated.append(tok)
            self.m_generated_tokens += 1

        text = self._decoded(slot)
        new = text[slot.emitted_len:]

        # Stop-sequence scan over the un-emitted tail (+ held-back overlap).
        finish: Optional[str] = None
        if is_eos:
            finish = "stop"
        elif r.stop:
            window_start = max(0, slot.emitted_len - max(len(s) for s in r.stop))
            window = text[window_start:]
            cut = None
            for s in r.stop:
                idx = window.find(s)
                if idx >= 0:
                    cut = window_start + idx if cut is None else min(cut, window_start + idx)
            if cut is not None:
                new = text[slot.emitted_len: cut]
                finish = "stop"
        # DFA slots have no host-side machine to consult; they finish via
        # EOS instead (a strictly-complete automaton state masks everything
        # but EOS, so the very next sample ends the request).
        if (finish is None and r.grammar is not None and not slot.dfa
                and r.grammar.strictly_complete()):
            finish = "stop"  # constrained output can no longer be extended — done
        if finish is None and (
            len(slot.generated) >= r.max_new_tokens
            or slot.prompt_len + len(slot.generated) >= self.ecfg.max_seq
        ):
            finish = "length"

        if finish is None:
            # Hold back partial UTF-8 (decoder emits U+FFFD for incomplete
            # sequences — mirror of core/backend/llm.go:146-166) and any tail
            # that could be the start of a stop sequence.
            hold = 0
            if new.endswith("�"):
                hold = 1
            if r.stop:
                # Trailing replacement chars may be INCOMPLETE sequences the
                # next event re-renders — scan stop prefixes against the
                # stable part only, or a stop landing just before the
                # pending bytes slips out one event early (observed: held
                # 0xDE rendered '\x05�', the '\x05' flushed, and the stop
                # '\x05ޠ' was found only after emitted_len passed its cut).
                stable = new.rstrip("�")
                pend = len(new) - len(stable)
                for s in r.stop:
                    for k in range(min(len(s) - 1, len(stable)), 0, -1):
                        if stable.endswith(s[:k]):
                            hold = max(hold, pend + k)
                            break
            if hold:
                new = new[: len(new) - hold]

        if not is_eos or new:
            # EVERY generated token posts exactly one event, even when its
            # bytes are all held back (incomplete UTF-8 / possible stop
            # prefix): streamed SSE chunk count must equal usage
            # completion_tokens — the 8B HTTP bench asserts it, and OpenAI
            # stream consumers count content chunks as tokens. An EOS that
            # flushes held-back text still posts that text (the `or new`).
            slot.emitted_len += len(new)
            handle._q.put(TokenEvent(
                kind="token", text=new, token_id=tok,
                logprob=logprob, top_logprobs=top_logprobs,
            ))
        if finish is not None:
            self._finish(slot_idx, finish, slot)

    _DECODE_TAIL = 16  # tokens re-decoded behind a settled prefix

    def _decoded(self, slot: _Slot) -> str:
        """`tokenizer.decode(slot.generated)`, without decoding the whole
        answer again for every token (the loop's largest cost a token, and
        one that grew with the answer: PERF.md section 6, PR 43). The text
        of the first `dec_n` tokens is kept once it is settled: a decode is
        context-free but for what adjoins a cut (a split UTF-8 sequence, a
        SentencePiece space, HF's clean-up of " ." and the like), so a cut
        is taken only with _DECODE_TAIL tokens behind it, only where the
        two sides decode to the whole, and never behind a replacement
        character that later bytes could complete."""
        gen, dec = slot.generated, self.tokenizer.decode
        tail = dec(gen[slot.dec_n:])
        text = slot.dec_text + tail
        pending = len(gen) - slot.dec_n
        if pending >= 2 * self._DECODE_TAIL and pending % self._DECODE_TAIL == 0:
            cut = len(gen) - self._DECODE_TAIL
            head = dec(gen[slot.dec_n:cut])
            if not head.endswith("\ufffd") and head + dec(gen[cut:]) == tail:
                slot.dec_text += head
                slot.dec_n = cut
        return text

    def _saves_at_finish(self, slot: _Slot) -> bool:
        """Does _finish store this request's prompt + generated rows as a
        prefix span? Adapter rows are tenant-specific, image rows have no
        token key."""
        return (self._prefix_enabled and slot.request.image_embeds is None
                and slot.request.adapter is None)

    # thread: engine-loop-only
    def _settle_deferred_saves(self, slot_idx: int, will_save: bool) -> None:
        """Before a request leaves its slot index: the finish-time span
        covers prompt + generated rows, a superset of any admission save
        still waiting on the sidecar (ISSUE 17), so with one to come drop
        the waiting save instead of paying its snapshot twice; without, run
        it now, while the rows are still the index's."""
        if will_save:
            self._deferred_saves = [
                x for x in self._deferred_saves if x[0] != slot_idx
            ]
        else:
            self._flush_deferred_saves(slot_idx)

    def _finish(self, slot_idx: int, reason: str,
                slot: Optional[_Slot] = None) -> None:
        """Post the request's `done`, take its finish-time prefix save and
        release what it holds: the slot index, or for a parked tenant
        (`slot.parked`, see _park) the record's pages and pin, the index
        being another request's already."""
        if slot is None:
            slot = self.slots[slot_idx]
        assert slot is not None
        parked = slot.parked
        will_save = self._saves_at_finish(slot)
        if parked is None:
            self._settle_deferred_saves(slot_idx, will_save)
        if will_save:
            # Rows for prompt + all but the last generated token are
            # guaranteed written (a token's KV row lands when it is consumed
            # as the next step's input). A span that carries generated rows
            # is NEW information (multi-turn reuse — always save); one that
            # doesn't is a re-keyed copy of the prompt span the admission
            # already ruled on, so it takes the same min-extension bar.
            valid = slot.prompt_len + max(0, len(slot.generated) - 1)
            self._prefix_save(
                slot_idx, list(slot.request.prompt_ids) + slot.generated,
                valid,
                min_extend=(0 if valid > slot.prompt_len
                            else self.ecfg.prefix_cache_min),
                parked=parked,
            )
        now = time.monotonic()
        t_first = slot.t_first or now
        h = slot.handle
        queue_wait = 0.0
        if h.t_submit > 0.0 and h.t_admit >= h.t_submit:
            queue_wait = h.t_admit - h.t_submit
        self._jnote("terminal", rid=h.rid, slot=slot_idx,
                    a=float(len(slot.generated)))
        # Had the index been handed on before this `done`? (_park)
        self.m_slots_released += 1
        self.m_slots_released_early += parked is not None
        self._jnote("slot_turnover", rid=h.rid, slot=slot_idx,
                    a=float(parked is not None), b=1.0)
        h._q.put(
            TokenEvent(
                kind="done",
                finish_reason=reason,
                prompt_tokens=slot.prompt_len,
                completion_tokens=len(slot.generated),
                timing_prompt_processing=t_first - slot.t_submit,
                timing_token_generation=now - t_first,
                timing_queue_wait=queue_wait,
            )
        )
        if parked is None:
            self._release(slot_idx)
        else:
            self._release_parked(slot)

    # thread: engine-loop-only
    def _release_parked(self, slot: _Slot) -> None:
        """Give back what a parked tenant's record holds (_park). Pages are
        freed, so a staged block plan is rebuilt, as in _release."""
        parked = slot.parked
        if self._parked.pop((parked.idx, parked.gen), None) is None:
            return  # released already
        self._plan_dirty()
        self._adapter_unpin(parked.adapter_row)
        self._pages_release(parked.pages)
        if parked.spill:
            self._spill_bytes -= len(parked.spill) * self._page_bytes()
        self._tp_release(parked.tps)

    def _release(self, slot_idx: int) -> None:
        # Membership changed — and for paged engines the teardown below
        # frees pages, so a block plan staged before this release (its
        # growth included) must be rebuilt (ISSUE 17).
        self._plan_dirty()
        self.slots[slot_idx] = None
        # An (index, generation) names one tenancy: what is still in flight
        # for this one finds nobody (_tenant), whoever is seated here next
        # and however (a chunked admission claims the index chunks before
        # the program that activates it).
        self._slot_gen[slot_idx] += 1
        # A chunked prefill whose slot is being torn down (dispatch failure,
        # stop) must not keep dispatching chunks into a freed slot.
        self._chunkings = [
            st for st in self._chunkings if st["slot"] != slot_idx
        ]
        self.h_active[slot_idx] = False
        # Acceptance scheduling state is per-REQUEST: the next occupant of
        # this slot index starts optimistic, not with its predecessor's
        # statistics (ISSUE 12).
        self.h_accept_ewma[slot_idx] = 1.0
        self._spec_probe[slot_idx] = 0
        self.h_override_mask[slot_idx] = False
        self.h_gmask[slot_idx] = 0.0
        self._slot_release_adapter(slot_idx)
        if self._paged:
            self._pages_free(slot_idx)
