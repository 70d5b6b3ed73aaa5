"""Engine tests: continuous batching, streaming, stops, sampling, slot reuse.

The reference has no in-repo harness for its slot machinery (it lives in
vendored llama.cpp); here the engine is first-class and tested hermetically
on the virtual CPU mesh (SURVEY.md §4 last row).
"""

import threading

import jax
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params, prefill
from localai_tpu.parallel.mesh import MeshPlan


@pytest.fixture(scope="module")
def engine():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg,
        params,
        ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=4, max_seq=128, min_prefill_bucket=16),
    )
    eng.start()
    yield eng
    eng.stop()


def test_greedy_deterministic(engine):
    text1, ev1 = engine.generate([65, 66, 67], max_new_tokens=12, ignore_eos=True)
    text2, ev2 = engine.generate([65, 66, 67], max_new_tokens=12, ignore_eos=True)
    assert text1 == text2
    assert ev1.completion_tokens == 12
    assert ev1.finish_reason == "length"
    assert ev1.prompt_tokens == 3
    assert ev1.timing_prompt_processing > 0


def test_greedy_matches_prefill_logits(engine):
    """Each greedily-decoded token must equal argmax of a fresh full prefill."""
    prompt = [10, 20, 30, 40]
    text, ev = engine.generate(prompt, max_new_tokens=5, ignore_eos=True)
    cfg = engine.cfg
    seq = list(prompt)
    import jax.numpy as jnp

    for step in range(5):
        toks = jnp.array([seq + [0] * (32 - len(seq))], jnp.int32)
        logits, _, _ = prefill(cfg, engine.params, toks, jnp.array([len(seq)], jnp.int32))
        nxt = int(jnp.argmax(logits[0]))
        seq.append(nxt)
    expected = engine.tokenizer.decode(seq[len(prompt):])
    assert text == expected


def test_concurrent_batching(engine):
    """More requests than slots; all complete, greedy results stay correct."""
    ref, _ = engine.generate([65, 66], max_new_tokens=8, ignore_eos=True)
    results = {}

    def run(i):
        if i % 2 == 0:
            results[i] = engine.generate([65, 66], max_new_tokens=8, ignore_eos=True)[0]
        else:
            results[i] = engine.generate(
                [70 + i], max_new_tokens=8, temperature=0.9, seed=i, ignore_eos=True
            )[0]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 10
    for i in range(0, 10, 2):
        assert results[i] == ref, f"greedy result changed under batching (req {i})"


def test_seeded_sampling_reproducible(engine):
    kw = dict(max_new_tokens=10, temperature=0.8, top_k=50, seed=1234, ignore_eos=True)
    t1, _ = engine.generate([97, 98, 99], **kw)
    t2, _ = engine.generate([97, 98, 99], **kw)
    assert t1 == t2


def test_stop_sequence(engine):
    # Find what greedy emits, then use a substring of it as a stop sequence.
    full, _ = engine.generate([65, 66, 67], max_new_tokens=10, ignore_eos=True)
    assert len(full) > 2
    stop = full[2:4]
    text, ev = engine.generate([65, 66, 67], max_new_tokens=10, ignore_eos=True, stop=[stop])
    assert ev.finish_reason == "stop"
    assert stop not in text
    assert text == full[: full.index(stop)]


def test_eos_stops(engine):
    """Bias sampling so EOS is emitted immediately."""
    eos = engine.tokenizer.eos_ids[0]
    text, ev = engine.generate([65], max_new_tokens=10, logit_bias={eos: 1e9})
    assert ev.finish_reason == "stop"
    assert ev.completion_tokens == 0
    assert text == ""


def test_streaming_events(engine):
    handle = engine.submit(GenRequest(prompt_ids=[72, 73], max_new_tokens=6, ignore_eos=True))
    kinds = [ev.kind for ev in handle]
    assert kinds[-1] == "done"
    assert all(k == "token" for k in kinds[:-1])


def test_metrics(engine):
    before = engine.metrics()
    engine.generate([1, 2, 3, 4], max_new_tokens=4, ignore_eos=True)
    after = engine.metrics()
    assert after["prompt_tokens_processed"] >= before["prompt_tokens_processed"] + 4
    assert after["tokens_generated"] >= before["tokens_generated"] + 4
    assert after["tokens_per_second"] > 0


def test_embed(engine):
    out = engine.embed([[1, 2, 3], [4, 5]])
    assert out.shape == (2, engine.cfg.hidden_size)
    norms = np.linalg.norm(out, axis=-1)
    assert np.allclose(norms, 1.0, atol=1e-3)
    # Embeddings are padding-invariant by construction (masked mean-pool).
    again = engine.embed([[1, 2, 3]])
    assert np.allclose(out[0], again[0], atol=1e-3)


def test_long_prompt_truncated(engine):
    ids = [65] * 500  # > max_seq=128
    text, ev = engine.generate(ids, max_new_tokens=4, ignore_eos=True)
    assert ev.prompt_tokens <= 127
    assert ev.kind == "done"


def test_sharded_engine(devices8):
    """Engine over a dp=2 x tp=2 mesh: decode path must match full prefill
    under the *same* sharding (greedy argmax can legitimately differ from the
    unsharded run on a random model — float reassociation across tp shards —
    so the invariant is self-consistency, like test_greedy_matches_prefill_logits)."""
    import jax.numpy as jnp

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg,
        params,
        ByteTokenizer(cfg.vocab_size),
        mesh_plan=MeshPlan(dp=2, tp=2),
        engine_cfg=EngineConfig(max_slots=2, max_seq=64, min_prefill_bucket=16),
    )
    prompt = [65, 66, 67]
    out, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
    assert ev.completion_tokens == 8

    seq = list(prompt)
    for _ in range(8):
        toks = jnp.array([seq + [0] * (32 - len(seq))], jnp.int32)
        logits, _, _ = eng._prefill_fn(eng.params, toks, jnp.array([len(seq)], jnp.int32))
        seq.append(int(jnp.argmax(logits[0])))
    eng.stop()
    assert out == eng.tokenizer.decode(seq[len(prompt):])


def test_logprobs_match_prefill(engine):
    """Streamed logprobs must match a recomputed forward pass (VERDICT #9)."""
    import jax.numpy as jnp

    prompt = [11, 22, 33]
    handle = engine.submit(GenRequest(
        prompt_ids=prompt, max_new_tokens=4, ignore_eos=True, logprobs=5,
    ))
    events = [ev for ev in handle if ev.kind == "token"]
    assert len(events) == 4
    cfg = engine.cfg
    seq = list(prompt)
    for ev in events:
        assert ev.logprob is not None
        assert len(ev.top_logprobs) == 5
        toks = jnp.array([seq + [0] * (32 - len(seq))], jnp.int32)
        logits, _, _ = prefill(cfg, engine.params, toks, jnp.array([len(seq)], jnp.int32))
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
        assert abs(float(logp[ev.token_id]) - ev.logprob) < 2e-2
        # top-1 alternative is the argmax (= greedy token)
        top_id, top_lp = ev.top_logprobs[0]
        assert top_id == int(jnp.argmax(logp))
        assert abs(float(logp[top_id]) - top_lp) < 2e-2
        # descending order
        lps = [v for _, v in ev.top_logprobs]
        assert lps == sorted(lps, reverse=True)
        seq.append(ev.token_id)


def test_logprobs_concurrent_with_plain(engine):
    """lp and non-lp requests share the batch without corrupting each other."""
    h_lp = engine.submit(GenRequest(prompt_ids=[1, 2], max_new_tokens=6,
                                    ignore_eos=True, logprobs=3))
    h_plain = engine.submit(GenRequest(prompt_ids=[3, 4], max_new_tokens=6,
                                       ignore_eos=True))
    lp_events = [ev for ev in h_lp if ev.kind == "token"]
    text, ev = h_plain.result()
    assert ev.finish_reason == "length"
    assert all(e.logprob is not None for e in lp_events)
    # plain request must match its solo run
    text2, _ = engine.generate([3, 4], max_new_tokens=6, ignore_eos=True)
    assert text == text2


def test_long_context_ring_serving_matches_dense():
    """VERDICT #7: a long prompt served with sp=2 (ring-attention prefill)
    matches the dense single-device answer, end-to-end through the engine."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(1))
    tok = ByteTokenizer(cfg.vocab_size)
    ecfg = EngineConfig(max_slots=2, max_seq=4096, min_prefill_bucket=32)
    rng = np.random.default_rng(42)
    prompt = [int(x) for x in rng.integers(1, 256, size=3000)]

    eng_sp = Engine(cfg, params, tok, mesh_plan=MeshPlan(sp=2), engine_cfg=ecfg)
    assert eng_sp._ring_mesh is not None
    eng_sp.start()
    try:
        text_sp, ev_sp = eng_sp.generate(prompt, max_new_tokens=6, ignore_eos=True)
        assert ev_sp.prompt_tokens == 3000
    finally:
        eng_sp.stop()

    eng_dense = Engine(cfg, params, tok, engine_cfg=ecfg)
    assert eng_dense._ring_mesh is None
    eng_dense.start()
    try:
        text_dense, _ = eng_dense.generate(prompt, max_new_tokens=6, ignore_eos=True)
    finally:
        eng_dense.stop()

    assert text_sp == text_dense


def test_sp_sharded_kv_cache(devices8):
    """VERDICT r2 item 4: with sp=2 the serving cache's sequence axis shards
    over "sp" — per-chip KV residency is S/sp (asserted on the real device
    buffers), and decode over the sharded cache matches the dense engine."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(1))
    tok = ByteTokenizer(cfg.vocab_size)
    ecfg = EngineConfig(max_slots=2, max_seq=256, min_prefill_bucket=32)
    eng = Engine(cfg, params, tok, mesh_plan=MeshPlan(sp=2), engine_cfg=ecfg)
    shard_shapes = {sh.data.shape for sh in eng.cache.k.addressable_shards}
    assert shard_shapes == {
        (cfg.num_layers, 2, 128, cfg.num_kv_heads, cfg.head_dim_)
    }, shard_shapes  # 256 / sp=2 = 128 rows per chip

    rng = np.random.default_rng(7)
    prompt = [int(x) for x in rng.integers(1, 256, size=150)]
    eng.start()
    try:
        text_sp, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
        assert ev.completion_tokens == 8
    finally:
        eng.stop()

    eng_d = Engine(cfg, params, tok, engine_cfg=ecfg)
    eng_d.start()
    try:
        text_d, _ = eng_d.generate(prompt, max_new_tokens=8, ignore_eos=True)
    finally:
        eng_d.stop()
    assert text_sp == text_d


def test_kv_windowed_blocks_bit_match_full():
    """The read-side KV window (kv_win buckets) must not change output: a
    max_seq big enough to trigger windowing produces the same greedy tokens
    as a window-disabled engine, and the windowed program is actually used."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompt = [7, 11, 13] * 20  # plen 60; block 64: positions stay < 256

    def run(min_win):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(max_slots=2, max_seq=1024,
                                    min_prefill_bucket=16),
        )
        eng._KV_WIN_MIN = min_win
        eng.start()
        try:
            text, ev = eng.generate(prompt, max_new_tokens=80, ignore_eos=True)
            keys = list(eng._block_cache.keys())
        finally:
            eng.stop()
        return text, ev, keys

    # min_win 2048 > max_seq → every bucket search lands at full cache
    text_full, ev_full, keys_full = run(2048)
    assert all(k[4] is None for k in keys_full)
    text_win, ev_win, keys_win = run(256)
    assert any(k[4] == 256 for k in keys_win), "windowed program never ran"
    assert text_win == text_full
    assert ev_win.completion_tokens == ev_full.completion_tokens == 80


# --------------------------------------------------------------------- #
# Chunked ragged prefill (EngineConfig.prefill_chunk — ISSUE 2)
# --------------------------------------------------------------------- #

RAGGED_PROMPTS = [
    [(i * 7 + j) % 250 + 1 for j in range(n)]
    for i, n in enumerate([100, 37, 64, 5, 90])
]


def _mk_chunk_engine(chunk: int, paged: bool, **ecfg_kw):
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(
            max_slots=4, max_seq=256, min_prefill_bucket=16,
            prefill_chunk=chunk,
            kv_pages=14 if paged else 0, kv_page_size=64,
            **ecfg_kw,
        ),
    )
    eng.start()
    return eng


def test_chunked_prefill_token_identical_dense():
    """Dense chunked admission must produce byte-identical greedy output to
    first-principles prefill+argmax across ragged prompt lengths. Prompts
    longer than the chunk go through the chunk machine (asserted via the
    counters); short ones keep the single-shot path."""
    import jax.numpy as jnp

    eng = _mk_chunk_engine(32, paged=False)
    try:
        for p in RAGGED_PROMPTS:
            got, _ = eng.generate(p, max_new_tokens=6, ignore_eos=True)
            seq = list(p)
            for _ in range(6):
                toks = jnp.array([seq + [0] * (128 - len(seq))], jnp.int32)
                logits, _, _ = prefill(eng.cfg, eng.params, toks,
                                       jnp.array([len(seq)], jnp.int32))
                seq.append(int(jnp.argmax(logits[0])))
            assert got == eng.tokenizer.decode(seq[len(p):]), len(p)
        # 4 of the 5 prompts exceed the 32-token chunk.
        assert eng.m_chunked_admits >= 4
        assert eng.m_prefill_chunks > eng.m_chunked_admits  # real mid chunks
    finally:
        eng.stop()


def test_chunked_prefill_token_identical_paged():
    """Paged chunked admission == single-shot paged admission, byte for
    byte: greedy across ragged lengths, seeded-sampled, and logprob
    streams. Also asserts the chunk machine released every pool page."""
    results = {}
    for chunk in (0, 32):
        eng = _mk_chunk_engine(chunk, paged=True)
        try:
            texts = [eng.generate(p, max_new_tokens=6, ignore_eos=True)[0]
                     for p in RAGGED_PROMPTS]
            sampled = eng.generate(RAGGED_PROMPTS[0], max_new_tokens=6,
                                   temperature=0.9, seed=11,
                                   ignore_eos=True)[0]
            lp_evs = [e for e in eng.submit(GenRequest(
                prompt_ids=RAGGED_PROMPTS[4], max_new_tokens=4,
                ignore_eos=True, logprobs=3,
            )) if e.kind == "token"]
            results[chunk] = (
                texts, sampled,
                [(e.token_id, round(e.logprob, 4)) for e in lp_evs],
            )
            if chunk:
                assert eng.m_chunked_admits >= 4
                assert eng.m_prefill_chunks > eng.m_chunked_admits
                # Prefix-cache spans pin pool pages copy-on-write; drop
                # them before asserting the chunk machine leaked none.
                for e in list(eng._prefix_entries):
                    eng._prefix_drop(e)
                eng._prefix_entries.clear()
                m = eng.metrics()
                assert m["kv_pages_free"] == m["kv_pages_total"]
        finally:
            eng.stop()
    assert results[32] == results[0]


@pytest.mark.parametrize("paged", [False, True])
def test_chunked_prefill_prefix_tail_reuses_chunk_path(paged):
    """A prefix-cache hit whose tail exceeds the chunk admits through the
    chunk machine starting at the matched offset — same greedy tokens as
    raw prefill+argmax, and the hit is still recorded."""
    import jax.numpy as jnp

    sys_p = [65 + (i * 7) % 26 for i in range(64)]
    tail_b = [150 + i for i in range(40)]
    eng = _mk_chunk_engine(
        32, paged, prefix_cache_entries=4, prefix_cache_min=16,
        prefix_admit_async_compile=False,
    )
    try:
        eng.generate(sys_p + [100 + i for i in range(40)], max_new_tokens=5,
                     ignore_eos=True)  # seeds the span (chunked itself)
        h0 = eng.m_prefix_hits
        got, _ = eng.generate(sys_p + tail_b, max_new_tokens=5,
                              ignore_eos=True)  # hit, 40-token tail
        assert eng.m_prefix_hits - h0 >= 1
        assert eng.m_chunked_admits >= 2  # both admissions exceeded the chunk
        # First-principles reference: fresh full prefill + argmax per step.
        seq = list(sys_p + tail_b)
        for _ in range(5):
            toks = jnp.array([seq + [0] * (128 - len(seq))], jnp.int32)
            logits, _, _ = prefill(eng.cfg, eng.params, toks,
                                   jnp.array([len(seq)], jnp.int32))
            seq.append(int(jnp.argmax(logits[0])))
        assert got == eng.tokenizer.decode(seq[len(sys_p) + len(tail_b):])
    finally:
        eng.stop()


def test_chunked_prefill_composes_with_draft_model():
    """Chunked admission + speculative decode: the final chunk prefills the
    draft's dense cache with the full prompt, and the output stays
    byte-identical to the unchunked draft engine (dense and paged pools)."""
    from localai_tpu.models.config import ArchConfig

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    draft_cfg = ArchConfig(
        name="tiny-draft", vocab_size=cfg.vocab_size, hidden_size=32,
        intermediate_size=64, num_layers=1, num_heads=2, num_kv_heads=1,
        max_position=256,
    )
    draft_params = init_params(draft_cfg, jax.random.key(9))
    prompt = [(j * 3) % 200 + 1 for j in range(90)]

    def run(paged):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            draft_cfg=draft_cfg, draft_params=draft_params, n_draft=4,
            engine_cfg=EngineConfig(
                max_slots=2, max_seq=256, min_prefill_bucket=16,
                prefill_chunk=32,
                kv_pages=8 if paged else 0, kv_page_size=64,
            ),
        )
        eng.start()
        try:
            text, ev = eng.generate(prompt, max_new_tokens=10, ignore_eos=True)
            assert ev.completion_tokens == 10
            assert eng.m_chunked_admits == 1
            return text
        finally:
            eng.stop()

    # Speculative greedy is exact vs plain greedy (test_speculative), so the
    # first-principles prefill+argmax chain is the reference.
    import jax.numpy as jnp

    seq = list(prompt)
    for _ in range(10):
        toks = jnp.array([seq + [0] * (128 - len(seq))], jnp.int32)
        logits, _, _ = prefill(cfg, params, toks,
                               jnp.array([len(seq)], jnp.int32))
        seq.append(int(jnp.argmax(logits[0])))
    ref = ByteTokenizer(cfg.vocab_size).decode(seq[len(prompt):])
    for paged in (False, True):
        got = run(paged)
        assert got == ref, f"draft compose mismatch (paged={paged})"


def test_short_request_completes_during_chunked_prefill():
    """Liveness: a short request submitted while a long prompt is mid-chunk
    admits and finishes before the long one — the long prefill no longer
    monopolizes the engine."""
    import time

    eng = _mk_chunk_engine(16, True)
    try:
        # 13 chunks of prefill. "Mid-chunk" is made to hold, not slept for:
        # the short request goes in when the first chunk has run. (A 90-token
        # prompt and a 20 ms sleep left the short one to arrive after the
        # last chunk under six workers' load; the two then decode in one
        # block and finish together: 4 of 20 runs, 18 of 20 with the short
        # path compiled. This form: 20 of 20, 1.8 s apart or more; PR 28.)
        long_ids = [(j * 3) % 200 + 1 for j in range(200)]
        warm_ids = [(j * 7) % 190 + 3 for j in range(200)]  # no prefix hit
        eng.generate(warm_ids, max_new_tokens=2, ignore_eos=True)
        warm_chunks = eng.m_prefill_chunks
        done = {}

        def run(name, ids, n):
            eng.generate(ids, max_new_tokens=n, ignore_eos=True)
            done[name] = time.monotonic()

        tl = threading.Thread(target=run, args=("long", long_ids, 40))
        ts = threading.Thread(target=run, args=("short", [5, 6, 7], 4))
        tl.start()
        deadline = time.monotonic() + 60
        while eng.m_prefill_chunks == warm_chunks and time.monotonic() < deadline:
            time.sleep(0.001)
        ts.start()
        tl.join(timeout=120)
        ts.join(timeout=120)
        assert done["short"] < done["long"], done
        assert eng.m_prefill_chunks >= warm_chunks + 5  # 200 tokens / 16
    finally:
        eng.stop()


def test_every_generated_token_posts_one_event(engine):
    """SSE chunk-count contract (ISSUE 2 satellite): one token event per
    generated token even when its text is entirely held back (stop-prefix /
    incomplete UTF-8) — streamed chunk count must equal completion_tokens."""
    # A stop sequence that never fires but whose first char matches
    # generated text forces hold-back events; byte prompts also emit
    # multi-byte UTF-8 holdbacks on their own.
    full, _ = engine.generate([65, 66, 67], max_new_tokens=12,
                              ignore_eos=True)
    stop = (full[:1] + "\x00never") if full else "\x00never"
    handle = engine.submit(GenRequest(
        prompt_ids=[65, 66, 67], max_new_tokens=12, ignore_eos=True,
        stop=[stop],
    ))
    events = list(handle)
    done = events[-1]
    assert done.kind == "done"
    tok_events = [e for e in events if e.kind == "token"]
    assert len(tok_events) == done.completion_tokens
    if done.finish_reason == "length":  # stop almost surely never fires
        assert "".join(e.text for e in tok_events) == full


def test_idle_coalesce_admission_keeps_loop_alive():
    """Regression (BENCH_r05 rc=124): the idle-engine submit-burst coalesce
    path reads _admit_hold_start/_last_submit_t on the FIRST admission of a
    fresh engine (one pending request, more free slots) — unset attributes
    killed the loop thread with AttributeError and every caller hung. The
    request must complete AND the loop thread must survive it."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=4, max_seq=128,
                                min_prefill_bucket=16, admit_coalesce_ms=6.0),
    )
    try:
        assert eng.ecfg.admit_coalesce_ms > 0
        text, ev = eng.generate([1, 2, 3], max_new_tokens=4, ignore_eos=True)
        assert ev.kind == "done"
        assert eng._thread is not None and eng._thread.is_alive(), (
            "engine loop thread died during the idle-coalesce admission"
        )
    finally:
        eng.stop()


def test_loop_death_fails_requests_instead_of_hanging():
    """If the engine loop dies of an unexpected exception, callers must get
    an error event (not block forever on the token queue)."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                min_prefill_bucket=16),
    )
    try:
        eng._admit_pending = None  # simulate an unexpected loop crash
        handle = eng.submit(GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=4))
        events = list(handle)
        assert events and events[-1].kind == "error"
        assert "engine loop died" in events[-1].error
    finally:
        eng.stop()


def test_stop_terminates_live_streams():
    """Regression: the manager watchdog's busy-kill can fire inside the
    admission gap (cancel_all sees neither pending nor slot) and then evict
    the engine — stop() must post terminal events to every live consumer so
    nobody blocks on the stream forever (test_manager's wedged-kill test
    hung tier-1 exactly this way)."""
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                min_prefill_bucket=16),
    )
    handle = eng.submit(GenRequest(
        prompt_ids=[1, 2, 3], max_new_tokens=10_000, ignore_eos=True,
    ))
    eng.stop()  # mid-admission or mid-decode — either way the stream ends
    events = list(handle)
    assert events and events[-1].kind in ("done", "error")
