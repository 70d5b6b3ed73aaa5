"""The named scopes are a partition of every engine program (ISSUE 37).

Every instruction that does work in the programs `decode_block` and `admit`
compile to, for the tiny dense, MoE, KDA + MLA, KDA + GQA and conv + GQA
configurations,
is written under exactly one leaf of `observe.scopes.SCOPES` (or a per-layer
slice scope): device time can then be read by program and scope out of a
profiler capture with nothing emitted at run time. And the rows of every
admission program are counted where its group is built."""

import collections
import dataclasses
import functools
import re

import jax
import pytest

from benchmark.reducers import scope_share
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import get_arch
from localai_tpu.models import llama as L
from localai_tpu.observe import scopes
from tools.same_program import tiny_engine_programs

CONFIGS = ("tiny", "tiny-olmoe", "tiny-kimi-linear", "tiny-solar-open2",
           "tiny-lfm2", "tiny-granite-h", "tiny-jamba2")
PROGRAMS = ("decode_block", "admit")
# what does no work: the issue's list
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
_INSTR = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = (?:\([^=]*\)|\S+) ([\w\-]+)\(")


def leaf_of(op_name):
    """The reader's rule (a capture's `tf_op` is `<op_name>:<op type>`)."""
    return scope_share.leaf_of(op_name + ":")


def _cfg(name):
    cfg = get_arch(name)
    if cfg.recurrent_kind in ("kda", "ssd"):  # as served: a share of the experts
        cfg = dataclasses.replace(cfg, expert_share=(0, 2))
    return cfg


def _engine(cfg, **kw):
    kw = {"max_slots": 4, "max_seq": 256, "block_sizes": (8, 1),
          "kv_pages": 64, "kv_page_size": 16, "trace_journal_events": 2048,
          **kw}
    eng = Engine(cfg, L.init_params(cfg, jax.random.key(0)),
                 ByteTokenizer(cfg.vocab_size), engine_cfg=EngineConfig(**kw))
    eng.start()
    return eng


# ---- the vocabulary ---------------------------------------------------------- #


def test_the_vocabulary_is_pinned_between_the_program_and_its_reader():
    assert scopes.SCOPES == scope_share.SCOPES
    assert scopes.SLICES == scope_share.SLICES
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    # no leaf is a prefix of another: a path ends in one leaf only
    for a in scopes.SCOPES:
        assert not any(b != a and b.startswith(a + "/") for b in scopes.SCOPES)


@pytest.mark.parametrize("op_name,leaf", [
    ("jit(decode_block)/control/while/body/layer/while/body/attention/proj/dot_general",
     "attention/proj"),
    ("jit(decode_block)/control/while/body/layer/while/body/mul", "layer"),
    ("jit(decode_block)/control/while/body/add", "control"),
    ("jit(admit)/layer/while/body/cond/branch_1_fun/mlp/experts/layer_weights/dynamic_slice",
     "slices"),
    ("jit(admit)/layer/while/body/closed_call/attention/mix/reshape;attention/mix/reshape",
     "attention/mix"),
    ("jit(admit)/while/body/attention/dot_general", "none"),
    ("shift_right_logical", "none"),
    # XLA:TPU's own name for the custom call it rewrites `lax.ragged_dot` into
    ("ragged-dot-none.2", "mlp/experts"),
    ("ragged-dot-metadata", "mlp/experts"),
])
def test_an_op_belongs_to_the_leaf_its_path_ends_in(op_name, leaf):
    assert leaf_of(op_name) == leaf


def test_a_scope_outside_the_vocabulary_is_refused():
    with scopes.scope("attention/mix"):
        pass
    with pytest.raises(ValueError, match="attention"):
        scopes.scope("attention")


# ---- the partition ------------------------------------------------------------ #


@pytest.fixture(scope="module")
def compiled():
    """config -> {program: [compiled HLO text]} of the programs a tiny engine
    of that kind builds for two requests, one greedy and one sampled."""
    return functools.cache(tiny_engine_programs)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("config", CONFIGS)
def test_every_instruction_that_does_work_is_under_one_leaf(compiled, config, program):
    """Compiled for the CPU: an instruction the compiler made itself (a
    layout copy, a convert) carries no op_name at all and jax's threefry
    function no name stack (a bare primitive); every instruction that came
    through the program's own trace starts with `jit(` and has to end in a
    leaf."""
    texts = compiled(config)[program]
    assert texts, (config, program)
    for text in texts:
        named, unscoped, leaves = 0, collections.Counter(), set()
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m or m.group(1) in NO_WORK:
                continue
            name = re.search(r'op_name="([^"]*)"', line)
            if not name or not name.group(1).startswith("jit("):
                continue
            named += 1
            leaf = leaf_of(name.group(1))
            leaves.add(leaf)
            if leaf == "none":
                unscoped[(m.group(1), name.group(1))] += 1
        assert named > 300, named  # the check is not vacuous
        assert not unscoped, unscoped.most_common(10)
        assert {"embed", "lm_head", "sample", "control", "attention/proj",
                "attention/mix", "attention/cache_write", "attention/out",
                "layer", "slices"} <= leaves, leaves
        assert leaves & {"mlp/dense", "mlp/experts"}


def _compile_model(cfg, entry):
    """`llama.prefill` or one paged `decode_step_windowed`, compiled on their
    own: no engine program around them, so nothing lends them its `control`
    and a model op written under no scope shows as unscoped."""
    import jax.numpy as jnp

    params = L.init_params(cfg, jax.random.key(0))
    B, n, page, pages = 2, 4, 16, 9
    rec = ()
    if cfg.is_hybrid:
        rec = rstate.allocate(cfg, B, jnp.dtype(cfg.dtype))
    if entry == "prefill":
        tok = jnp.ones((B, 32), jnp.int32)
        lens = jnp.asarray([20, 32], jnp.int32)
        fn = jax.jit(lambda p, t, ln, slots, *r: L.prefill(
            cfg, p, t, ln, **({"recurrent": (*r, slots)} if r else {})))
        return fn.lower(params, tok, lens, jnp.arange(B), *rec) \
            .compile().as_text()
    pool = L.paged_cache_zeros(cfg, pages, page)
    local = [jnp.zeros((cfg.cache_layers, B, n, cfg.cache_kv_heads, d),
                       pool.k.dtype) for d in (cfg.cache_k_dim, cfg.cache_v_dim)]
    table = jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4) % pages
    tok = jnp.ones((B,), jnp.int32)
    fn = jax.jit(lambda p, t, pos, c, lk, lv, tb, *r: L.decode_step_windowed(
        cfg, p, t, pos, c, lk, lv, 1, ptable=tb,
        expert_rows=cfg.is_moe, **({"recurrent": r} if r else {})))
    return fn.lower(params, tok, tok * 20, pool, *local, table, *rec) \
        .compile().as_text()


@pytest.mark.parametrize("entry", ("prefill", "decode_step_windowed"))
@pytest.mark.parametrize("config", CONFIGS)
def test_the_models_own_ops_need_no_engine_around_them(config, entry):
    text = _compile_model(_cfg(config), entry)
    names = re.findall(r'op_name="(jit\([^"]*)"', text)
    assert len(names) > 200
    unscoped = collections.Counter(
        n for n in names if leaf_of(n) == "none")
    assert not unscoped, unscoped.most_common(10)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_mlp_scopes_tell_the_models_apart(compiled, config):
    leaves = {leaf_of(n)
              for text in compiled(config)["decode_block"]
              for n in re.findall(r'op_name="(jit\([^"]*)"', text)}
    cfg = _cfg(config)
    assert ("mlp/router" in leaves) == ("mlp/experts" in leaves) == cfg.is_moe
    assert ("mlp/shared" in leaves) == bool(cfg.is_moe and cfg.n_shared_experts)
    assert ("attention/rope" in leaves) == (
        config in ("tiny", "tiny-olmoe", "tiny-lfm2"))


@pytest.mark.parametrize("config", [c for c in CONFIGS if get_arch(c).is_moe])
def test_ragged_dot_is_called_under_the_leaf_its_rewritten_name_is_read_as(config):
    """XLA:TPU rewrites `lax.ragged_dot` into a custom call it names
    `ragged-dot-none`, and `REWRITTEN` reads that bare name as `mlp/experts`:
    every equation of that primitive in an admission's trace has to be
    written under it (the CPU expands it in line, so the trace is asked)."""
    import jax.numpy as jnp

    cfg = _cfg(config)
    params = L.init_params(cfg, jax.random.key(0))
    rec = rstate.allocate(cfg, 2, jnp.dtype(cfg.dtype)) if cfg.is_hybrid else ()
    jaxpr = jax.make_jaxpr(lambda p, t, ln, slots, *r: L.prefill(
        cfg, p, t, ln, **({"recurrent": (*r, slots)} if r else {})))(
        params, jnp.ones((2, 32), jnp.int32), jnp.asarray([20, 32], jnp.int32),
        jnp.arange(2), *rec)
    stacks = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                stacks.append(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert len(stacks) >= 3  # gate, up, down
    assert all(leaf_of(st + "/ragged_dot") == "mlp/experts"
               for st in stacks), stacks


# ---- admission's rows ---------------------------------------------------------- #


def _admit_account(eng):
    events = eng.journal.snapshot()
    rows = [e for e in events if e["event"] == "admit_rows"]
    admitted = [e for e in events if e["event"] == "admitted"]
    return rows, admitted, eng.metrics()


def _check_account(eng, rows, admitted, m, programs):
    assert len(rows) == programs == m["admit_programs"]
    assert sum(e["a"] for e in rows) == m["admit_rows_dispatched"] \
        == sum(eng._bucket_for(int(e["a"])) for e in admitted)
    assert sum(e["b"] for e in rows) == m["admit_rows_prompt"] \
        == sum(e["a"] for e in admitted)
    assert all(e["a"] >= e["b"] > 0 for e in rows)


def test_admission_rows_are_counted_where_the_group_is_built():
    """Prompts of two buckets arrive together: one event per admission
    program, a = group size x bucket, b = the prompt tokens it holds; the
    gauges hold the journal's sums."""
    eng = _engine(_cfg("tiny"), max_slots=8)
    try:
        lengths = [20, 21, 22, 23, 40, 41, 70]
        handles = [eng.submit(GenRequest(
            prompt_ids=list(range(1, 1 + n)), max_new_tokens=2,
            temperature=0.0, ignore_eos=True)) for n in lengths]
        assert all(h.result()[1].kind == "done" for h in handles)
        rows, admitted, m = _admit_account(eng)
        # a group of size b is b `admitted` events: 1/b of a program each
        programs = round(sum(1.0 / e["b"] for e in admitted))
        _check_account(eng, rows, admitted, m, programs)
    finally:
        eng.stop()
    assert sorted(e["a"] for e in admitted) == sorted(map(float, lengths))
    assert m["admit_rows_prompt"] == sum(lengths)
    assert m["admit_rows_dispatched"] > m["admit_rows_prompt"]


def test_admission_rows_are_counted_per_program_when_the_byte_bound_cuts_a_group(monkeypatch):
    from localai_tpu.ops import kda as KDA

    cfg = _cfg("tiny-solar-open2")
    per_token = 2 * cfg.kda_heads * KDA.SUB * cfg.kda_head_dim * 4
    monkeypatch.setattr(rstate, "ADMIT_BYTES", 64 * per_token)
    eng = _engine(cfg, max_slots=8)
    try:
        bucket = eng._bucket_for(20)
        handles = [eng.submit(GenRequest(
            prompt_ids=list(range(1, 21)), max_new_tokens=1, temperature=0.0,
            ignore_eos=True)) for _ in range(8)]
        assert all(h.result()[1].kind == "done" for h in handles)
        rows, admitted, m = _admit_account(eng)
        programs = round(sum(1.0 / e["b"] for e in admitted))
        _check_account(eng, rows, admitted, m, programs)
    finally:
        eng.stop()
    assert m["admit_splits"] >= 1 and programs > 1
    assert max(e["a"] for e in rows) <= max(64, bucket)
    assert m["admit_rows_dispatched"] == 8 * bucket and m["admit_rows_prompt"] == 160


def test_a_chunked_admission_counts_each_chunks_own_rows():
    eng = _engine(_cfg("tiny"), prefill_chunk=32)
    try:
        h = eng.submit(GenRequest(prompt_ids=list(range(1, 101)),
                                  max_new_tokens=2, temperature=0.0,
                                  ignore_eos=True))
        assert h.result()[1].kind == "done"
        rows, _admitted, m = _admit_account(eng)
    finally:
        eng.stop()
    # 100 tokens in chunks of 32: three mid chunks and a final tail of 4
    assert [(e["a"], e["b"]) for e in rows][:3] == [(32.0, 32.0)] * 3
    assert rows[-1]["b"] == 4.0 and rows[-1]["a"] >= 4.0
    assert m["admit_programs"] == len(rows) == 4
    assert m["admit_rows_prompt"] == 100.0
