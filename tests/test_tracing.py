"""The measurement inside the program (ISSUE 24, docs/OBSERVABILITY.md
section 5): program and kernel names, loop phases as spans, the decode-row
account, the `decode_first` event and the `join` phase."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import engine as engine_mod
from localai_tpu.engine import runtime
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.observe import journal as jmod
from localai_tpu.observe.trace import STORE


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=4, max_seq=128, min_prefill_bucket=16,
                    block_sizes=(4, 16))
    defaults.update(kw)
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


def _run(eng, lengths, tag="r"):
    """Submit one request per entry of `lengths` (tokens to generate) and
    wait for all; returns the request ids."""
    rids = [f"{tag}-{i}-{time.monotonic_ns()}" for i in range(len(lengths))]
    handles = [eng.submit(GenRequest(
        prompt_ids=[1, 5, 9, 3 + i], max_new_tokens=n, temperature=0.0,
        ignore_eos=True, request_id=rid))
        for i, (n, rid) in enumerate(zip(lengths, rids))]
    for h, n in zip(handles, lengths):
        _text, final = h.result()
        assert final.kind == "done" and final.completion_tokens == n
    return rids


@pytest.fixture(scope="module")
def mixed_run(tiny):
    """Three requests on four slots (one slot idle), ending at different
    steps inside their blocks; then a second wave through the same slots."""
    eng = _engine(tiny)
    try:
        rids = _run(eng, [3, 11, 22], "a") + _run(eng, [6, 2], "b")
        deadline = time.monotonic() + 10.0
        while eng.metrics()["active_slots"] and time.monotonic() < deadline:
            time.sleep(0.01)
        yield eng, rids, [3, 11, 22, 6, 2]
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# C: counters where the work happens
# --------------------------------------------------------------------- #


def test_row_counters_add_up_exactly(mixed_run):
    eng, _rids, lengths = mixed_run
    m = eng.metrics()
    d, p, o, e = (m[f"decode_rows_{k}"]
                  for k in ("dispatched", "posted", "overshoot", "empty"))
    assert d == p + o + e and d > 0
    # every token but each request's first (from its admission) was posted
    # by a decode block
    assert p == sum(n - 1 for n in lengths)
    # a slot stayed idle and requests ended inside their blocks
    assert e > 0 and o > 0
    blocks = [ev for ev in eng.journal.snapshot()
              if ev["event"] == "decode_block"]
    assert d == sum(ev["a"] for ev in blocks) * eng.ecfg.max_slots


def test_decode_first_is_journalled_once_per_request(mixed_run):
    eng, rids, lengths = mixed_run
    evs = eng.journal.snapshot()
    for rid, n in zip(rids, lengths):
        mine = [ev["event"] for ev in evs if ev["rid"] == rid]
        assert mine.count("decode_first") == (1 if n > 1 else 0), (rid, mine)
        assert mine.index("first_token") < mine.index("decode_first")
        assert mine.index("decode_first") < mine.index("terminal")
    firsts = [ev for ev in evs if ev["event"] == "decode_first"]
    assert all(ev["a"] >= 0 and ev["slot"] >= 0 for ev in firsts)


def test_trace_tiles_queue_admit_join_decode(mixed_run):
    _eng, rids, _lengths = mixed_run
    for rid in rids:
        j = STORE.get(rid)[-1].to_json()
        names = [s["name"] for s in j["spans"]]
        assert names == ["queue", "admit", "join", "decode"], names
        total = sum(s["duration_ms"] for s in j["spans"])
        assert abs(total - j["wall_ms"]) <= 0.05 * j["wall_ms"] + 0.05


def test_a_request_of_one_token_never_joins(tiny):
    eng = _engine(tiny)
    try:
        (rid,) = _run(eng, [1], "one")
        names = [s["name"] for s in STORE.get(rid)[-1].to_json()["spans"]]
        assert names == ["queue", "admit", "join"]
        assert not [ev for ev in eng.journal.snapshot()
                    if ev["event"] == "decode_first"]
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# B: loop phases
# --------------------------------------------------------------------- #


def test_pull_is_a_phase_and_blocked_time_is_part_of_host_time(mixed_run):
    eng, _rids, _lengths = mixed_run
    assert runtime.LOOP_PHASES == jmod.LOOP_PHASES
    i = runtime.LOOP_PHASES.index("pull")
    assert runtime.LOOP_PHASES[i + 1] == "process"
    iters = [ev for ev in eng.journal.snapshot() if ev["event"] == "loop_iter"]
    seen = set().union(*(ev.get("phases", {}) for ev in iters))
    assert {"pull", "process"} <= seen <= set(jmod.LOOP_PHASES)
    # loop_iter.b is host ms, always: the window's phases outside `wait`
    for ev in iters:
        ph = ev.get("phases", {})
        host = sum(v for k, v in ph.items() if k != "wait")
        assert ev["b"] == pytest.approx(host, rel=1e-3, abs=1e-3)
    m = eng.metrics()
    assert 0.0 <= m["loop_blocked_ms_total"] <= m["loop_host_ms_total"]
    pulls = sum(ev.get("phases", {}).get("pull", 0.0) for ev in iters)
    assert m["loop_blocked_ms_total"] == pytest.approx(pulls, rel=1e-3, abs=1e-3)


class _Span:
    """Stands in for jax.profiler.TraceAnnotation."""

    log: list = []
    enabled = True

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Span.log.append(("open", self.name))

    def __exit__(self, *exc):
        _Span.log.append(("close", self.name))

    @staticmethod
    def is_enabled():
        return _Span.enabled


def test_loop_phases_open_one_span_per_phase_and_merge_waits():
    _Span.log, _Span.enabled = [], True
    ph = runtime.LoopPhases(annotate=_Span)
    for name in ("admit", "prep", "commit", "dispatch", "wait", "wait",
                 "wait", "pull", "process", "wait"):
        ph.begin(name)
        ph.sync()  # what the loop does at the end of every iteration
    ph.end()
    want = []
    for name in ("admit", "prep", "commit", "dispatch", "wait", "pull",
                 "process", "wait"):
        want += [("open", f"loop/{name}"), ("close", f"loop/{name}")]
    assert _Span.log == want
    assert all(v >= 0.0 for v in ph.ms.values())
    assert ph.total() == pytest.approx(
        sum(v for k, v in ph.ms.items() if k != "wait"))


def test_loop_phases_make_no_span_without_a_capture():
    _Span.log, _Span.enabled = [], False
    ph = runtime.LoopPhases(annotate=_Span)
    ph.begin("admit")
    ph.begin("wait")
    _Span.enabled = True   # a capture starts in the middle of a phase
    ph.begin("wait")       # the next spin of the same phase opens its span
    ph.begin("pull")
    _Span.enabled = False  # and stops in the middle of another
    ph.begin("process")
    ph.end()
    assert _Span.log == [("open", "loop/wait"), ("close", "loop/wait"),
                         ("open", "loop/pull"), ("close", "loop/pull")]
    assert set(k for k, v in ph.ms.items() if v > 0) <= {
        "admit", "wait", "pull", "process"}


def test_a_long_phase_is_spans_of_one_slice(monkeypatch):
    """The profiler records a span when it ends, so one that is open when a
    capture stops is lost: a phase is cut into slices of SPAN_SLICE_S. On a
    clock the test turns (six workers' compiles took most of a 20 ms
    wall-clock window from this thread): steps of 2**-12 s, slices of eight."""
    _Span.log, _Span.enabled = [], True
    now = [128.0]
    monkeypatch.setattr(runtime, "time",
                        types.SimpleNamespace(monotonic=lambda: now[0]))
    monkeypatch.setattr(runtime, "SPAN_SLICE_S", 2.0 ** -9)
    ph = runtime.LoopPhases(annotate=_Span)
    for _ in range(80):
        ph.begin("pull")   # what the loop does every slice of its wait
        now[0] += 2.0 ** -12
    ph.end()
    opens = [e for e in _Span.log if e == ("open", "loop/pull")]
    assert len(opens) == 10  # 80 steps in slices of eight
    assert _Span.log == [("open", "loop/pull"), ("close", "loop/pull")] * len(opens)
    assert ph.ms["pull"] == pytest.approx(80 * 2.0 ** -12 * 1e3)
    assert ph.total() == ph.ms["pull"]


def test_the_loops_spans_reach_a_real_capture(tiny, tmp_path):
    """The engine-loop thread's phases and the dispatch annotation are host
    events of a jax.profiler capture taken as /debug/profile takes it."""
    from jax.profiler import ProfileData

    from localai_tpu.observe import profile as oprofile

    eng = _engine(tiny)
    try:
        _run(eng, [2], "warm")
        jax.profiler.start_trace(str(tmp_path), **oprofile.trace_options(jax))
        _run(eng, [9, 9], "cap")
        time.sleep(0.05)
        jax.profiler.stop_trace()
    finally:
        eng.stop()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names, blocks = set(), []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("loop/"):
                    names.add(ev.name)
                if ev.name == "dispatch/decode_block":
                    blocks.append(dict(ev.stats))
    assert {"loop/wait", "loop/pull", "loop/process", "loop/dispatch"} <= names
    assert names <= {f"loop/{p}" for p in jmod.LOOP_PHASES}
    assert blocks and all(b["n"] in (4, 16) and 1 <= b["live"] <= 4
                          for b in blocks)


# --------------------------------------------------------------------- #
# A: names on the device
# --------------------------------------------------------------------- #


def _program_names(eng):
    names = set()
    for cache in (eng._block_cache, eng._admit_cache, eng._snap_cache):
        for fn in cache.values():
            # a background AOT compile publishes the executable itself,
            # which carries no function name
            if not isinstance(fn, jax.stages.Compiled):
                names.add(getattr(fn, "__name__", None))
    for fn in (eng._prefill_fn, eng._embed_fn, eng._score_fn):
        names.add(fn.__name__)
    return names


@pytest.mark.parametrize("kw", [
    dict(),
    dict(kv_pages=24, kv_page_size=16, prefix_cache_min=8),
], ids=["dense", "paged_prefix"])
def test_every_engine_program_has_a_name_of_the_allowed_set(tiny, kw):
    eng = _engine(tiny, **kw)
    try:
        prompt = [1] + [7, 8, 9, 10] * 6
        for _ in range(2):  # the second admission may hit the prefix cache
            _t, final = eng.submit(GenRequest(
                prompt_ids=prompt, max_new_tokens=6, temperature=0.0,
                ignore_eos=True)).result()
            assert final.kind == "done"
        names = _program_names(eng)
    finally:
        eng.stop()
    assert {"decode_block", "admit"} <= names
    assert names <= engine_mod.PROGRAM_NAMES, names - engine_mod.PROGRAM_NAMES
    assert "wrapped" not in names and None not in names


def test_a_program_outside_the_allowed_set_is_refused():
    with pytest.raises(AssertionError):
        engine_mod._named_jit(lambda x: x, "wrapped")
    fn = engine_mod._named_jit(lambda x: x + 1, "page_copy")
    assert "jit_page_copy" in fn.lower(jnp.zeros((2,))).as_text()[:200]


def _lower_int8():
    from localai_tpu.models.quant import quantize_tensor
    from localai_tpu.ops import quant_matmul as Q

    w = quantize_tensor(jnp.ones((256, 256), jnp.bfloat16))
    x = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda x, w: Q.dispatch_matmul(x, w, impl="pallas")).lower(x, w)


def _lower_int4():
    from localai_tpu.models.quant import quantize_tensor_g4
    from localai_tpu.ops import quant_matmul as Q

    w = quantize_tensor_g4(jnp.ones((256, 256), jnp.bfloat16))
    x = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda x, w: Q.dispatch_matmul(x, w, impl="pallas")).lower(x, w)


def _lower_unembed():
    from localai_tpu.models.quant import unembed_matmul

    w = {"q": jnp.ones((512, 256), jnp.int8), "s": jnp.ones((512, 1), jnp.float32)}
    h = jnp.ones((8, 256), jnp.bfloat16)
    return jax.jit(lambda h, w: unembed_matmul(h, w, impl="pallas")).lower(h, w)


def _lower_paged():
    from localai_tpu.ops.paged_flash import paged_decode_partials

    q = jnp.ones((2, 4, 128), jnp.bfloat16)
    pool = jnp.ones((5, 16, 2, 128), jnp.bfloat16)
    table = jnp.asarray(np.arange(4, dtype=np.int32).reshape(2, 2) + 1)
    limits = jnp.asarray([20, 9], jnp.int32)
    return jax.jit(lambda *a: paged_decode_partials(*a, interpret=True)).lower(
        q, pool, pool, table, limits)


def _lower_flash():
    from localai_tpu.ops.flash import flash_prefill_attention

    q = jnp.ones((1, 128, 4, 64), jnp.bfloat16)
    kv = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    return flash_prefill_attention.lower(
        q, kv, kv, jnp.asarray([100], jnp.int32), interpret=True)


def _lower_lora():
    from localai_tpu.ops.lora_matmul import _lora_call

    x = jnp.ones((4, 128), jnp.bfloat16)
    a = jnp.ones((2, 128, 8), jnp.bfloat16)
    b = jnp.ones((2, 8, 128), jnp.bfloat16)
    return jax.jit(_lora_call).lower(x, a, b, jnp.asarray([0, 1, 1, 0], jnp.int32))


@pytest.mark.parametrize("name,lower", [
    ("int8_matmul", _lower_int8), ("int4_matmul", _lower_int4),
    ("int8_unembed", _lower_unembed), ("paged_attention", _lower_paged),
    ("flash_prefill", _lower_flash), ("lora_matmul", _lower_lora),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_pallas_kernel_carries_its_name_into_the_lowered_program(name, lower):
    text = lower().as_text(debug_info=True)
    assert f"{name}/pallas_call" in text, name


def test_layer_scopes_are_in_the_decode_steps_lowering(tiny):
    from localai_tpu.models import llama

    cfg, params = tiny
    B, n = 2, 4
    cache = llama.KVCache(
        k=jnp.zeros((cfg.num_layers, B, 32, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16),
        v=jnp.zeros((cfg.num_layers, B, 32, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16))
    local = jnp.zeros((cfg.num_layers, B, n, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16)
    tok = jnp.zeros((B,), jnp.int32)
    text = jax.jit(lambda p, t, pos, c, lk, lv, s: llama.decode_step_windowed(
        cfg, p, t, pos, c, lk, lv, s)).lower(
        params, tok, tok, cache, local, local, jnp.int32(0)).as_text(debug_info=True)
    for scope in ("layer_weights", "layer_kv_pool", "attention", "mlp", "lm_head"):
        assert f"{scope}/" in text, scope
