"""Multi-tenant LoRA serving (ISSUE 10, docs/LORA_SERVING.md).

Tenancy must be INVISIBLE numerically: a mixed-tenant batch (distinct
adapters + adapter-less slots in one decode block) produces token ids
byte-identical to each tenant run solo — greedy and seeded, dense and paged
caches, tp=1 and tp=2 — the ragged Pallas delta kernel (interpret mode on
CPU) matches the XLA gather oracle, LRU-evicted→re-fetched adapters are
byte-exact vs a merged-at-load oracle, and a failed adapter fetch errors
exactly one tenant's request while refcounts stay fully accounted.
"""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from safetensors.numpy import save_file

from localai_tpu.engine import (
    AdapterError,
    ByteTokenizer,
    Engine,
    EngineConfig,
    GenRequest,
)
from localai_tpu.engine.weights import (
    apply_lora,
    load_lora_deltas,
    load_lora_factors,
    save_hf_checkpoint,
)
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.parallel.mesh import MeshPlan
from localai_tpu.testing import faults
from localai_tpu.testing.streams import assert_same_until_near_tie, stream

PAGE = 32
PROMPT = [(i * 37) % 251 + 1 for i in range(20)]
PROMPT2 = [(i * 13) % 251 + 2 for i in range(33)]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _make_adapter(path, cfg, r=4, alpha=8, seed=0, scale=0.05,
                  with_row_targets=False):
    """PEFT-format adapter dir targeting q/v (+ o/down for row-parallel
    coverage when asked)."""
    rng = np.random.default_rng(seed)
    D, F = cfg.hidden_size, cfg.intermediate_size
    H = cfg.num_heads * cfg.head_dim_
    K = cfg.num_kv_heads * cfg.head_dim_
    mods = [("self_attn.q_proj", D, H), ("self_attn.v_proj", D, K)]
    if with_row_targets:
        mods += [("self_attn.o_proj", H, D), ("mlp.down_proj", F, D),
                 ("mlp.gate_proj", D, F)]
    tensors = {}
    for i in range(cfg.num_layers):
        for mod, d_in, d_out in mods:
            pre = f"base_model.model.model.layers.{i}.{mod}"
            tensors[f"{pre}.lora_A.weight"] = rng.normal(
                0, scale, (r, d_in)).astype(np.float32)
            tensors[f"{pre}.lora_B.weight"] = rng.normal(
                0, scale, (d_out, r)).astype(np.float32)
    os.makedirs(path, exist_ok=True)
    save_file(tensors, os.path.join(path, "adapter_model.safetensors"))
    with open(os.path.join(path, "adapter_config.json"), "w") as f:
        json.dump({"r": r, "lora_alpha": alpha}, f)
    return tensors


@pytest.fixture(scope="module")
def adapters(tiny, tmp_path_factory):
    cfg, _ = tiny
    root = tmp_path_factory.mktemp("adapters")
    dirs = {}
    for i, kw in enumerate([
        dict(seed=1, with_row_targets=True),  # col + row + mlp targets
        dict(seed=2),
        dict(seed=3, r=6),  # distinct rank — exercises stack rank growth
        dict(seed=4),
    ]):
        d = str(root / f"a{i}")
        _make_adapter(d, cfg, **kw)
        dirs[f"t{i}"] = d
    return dirs


def _mk(tiny, tp=1, paged=False, **kw):
    cfg, params = tiny
    defaults = dict(
        max_slots=4, max_seq=128, min_prefill_bucket=16,
        prefix_admit_async_compile=False,
    )
    if paged:
        defaults.update(kv_pages=14, kv_page_size=PAGE)
    defaults.update(kw)
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        mesh_plan=MeshPlan(tp=tp) if tp > 1 else None,
        engine_cfg=EngineConfig(**defaults),
    )
    eng.start()
    return eng


def _stop(eng):
    assert all(int(r) == 0 for r in eng._adapter_refs), (
        "adapter refcounts not fully accounted at quiesce: "
        f"{eng._adapter_refs}"
    )
    eng.stop()
    eng.params = None
    eng.cache = None


def _gen_ids(eng, prompt=PROMPT, adapter=None, **kw):
    kw.setdefault("max_new_tokens", 10)
    h = eng.submit(GenRequest(prompt_ids=list(prompt), ignore_eos=True,
                              adapter=adapter, **kw))
    ids = []
    for ev in h:
        assert ev.kind != "error", ev.error
        if ev.kind == "token":
            ids.append(ev.token_id)
    return ids


# --------------------------------------------------------------------- #
# Factor loader
# --------------------------------------------------------------------- #


def test_load_lora_factors_matches_merge_deltas(tiny, adapters):
    """The factorized runtime form must span exactly the delta the merge
    path computes: A_f @ B_f == weight·(alpha/r)·(B@A)^T per layer."""
    cfg, _ = tiny
    rank, per_key = load_lora_factors(adapters["t1"], weight=0.5, cfg=cfg)
    deltas = load_lora_deltas(adapters["t1"], weight=0.5, cfg=cfg)
    assert rank == 4
    assert set(per_key) == {"wq", "wv"}
    for key, layers_d in per_key.items():
        for li, (a, b) in layers_d.items():
            np.testing.assert_allclose(a @ b, deltas[key][li], rtol=1e-5,
                                       atol=1e-6)


def test_load_lora_factors_rejects_expert_targets(tiny, tmp_path):
    cfg, _ = tiny
    d = tmp_path / "moe_adapter"
    os.makedirs(d)
    t = {
        "base_model.model.model.layers.0.block_sparse_moe.experts.0.w1"
        ".lora_A.weight": np.zeros((4, cfg.hidden_size), np.float32),
        "base_model.model.model.layers.0.block_sparse_moe.experts.0.w1"
        ".lora_B.weight": np.zeros((8, 4), np.float32),
    }
    save_file(t, os.path.join(d, "adapter_model.safetensors"))
    with open(os.path.join(d, "adapter_config.json"), "w") as f:
        json.dump({"r": 4, "lora_alpha": 4}, f)
    with pytest.raises(ValueError, match="expert"):
        load_lora_factors(str(d), cfg=cfg)


# --------------------------------------------------------------------- #
# Kernel: Pallas (interpret) vs XLA oracle
# --------------------------------------------------------------------- #


def test_lora_kernel_interpret_matches_xla_oracle():
    from localai_tpu.ops.lora_matmul import _lora_call, lora_delta_xla

    rng = np.random.default_rng(0)
    B, IN, R, OUT, NA = 6, 64, 8, 128, 4
    x = jnp.asarray(rng.normal(size=(B, IN)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(NA, IN, R)), jnp.float32).at[0].set(0.0)
    b = jnp.asarray(rng.normal(size=(NA, R, OUT)), jnp.float32).at[0].set(0.0)
    # Rank padding rows (a real stack pads every adapter to the stack rank).
    a = a.at[1, :, 6:].set(0.0)
    b = b.at[1, 6:, :].set(0.0)
    ids = jnp.asarray([0, 1, 1, 2, 3, 0], jnp.int32)
    ref = lora_delta_xla(x, a, b, ids)
    got = _lora_call(x, a, b, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
    # Null adapter is an EXACT zero, not an approximate one.
    assert float(jnp.abs(got[0]).max()) == 0.0
    assert float(jnp.abs(got[5]).max()) == 0.0


@pytest.mark.multichip
def test_lora_kernel_tp2_shard_map_matches_oracle(multichip):
    if multichip < 2:
        pytest.skip("needs 2 devices")
    from localai_tpu.ops.lora_matmul import _sharded_lora_delta, lora_delta_xla
    from localai_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshPlan(tp=2))
    rng = np.random.default_rng(1)
    B, IN, R, OUT, NA = 4, 64, 4, 64, 3
    x = jnp.asarray(rng.normal(size=(B, IN)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(NA, IN, R)), jnp.float32).at[0].set(0.0)
    b = jnp.asarray(rng.normal(size=(NA, R, OUT)), jnp.float32).at[0].set(0.0)
    ids = jnp.asarray([2, 0, 1, 2], jnp.int32)
    ref = lora_delta_xla(x, a, b, ids)
    with mesh:
        col = _sharded_lora_delta(x, a, b, ids, mesh, "col")
        row = _sharded_lora_delta(x, a, b, ids, mesh, "row")
    np.testing.assert_allclose(np.asarray(col), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(row), np.asarray(ref), atol=1e-4)


# --------------------------------------------------------------------- #
# Tenancy correctness: mixed batch == solo, dense + paged, greedy + seeded
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_mixed_tenant_batch_matches_solo(tiny, adapters, paged):
    eng = _mk(tiny, paged=paged)
    try:
        for name in ("t0", "t1", "t2"):
            eng.register_adapter(name, adapters[name])
        plans = [
            (PROMPT, None, {}),
            (PROMPT, "t0", {}),
            (PROMPT2, "t1", {}),
            (PROMPT, "t2", dict(seed=11, temperature=0.8, top_k=20)),
        ]
        solo = [_gen_ids(eng, p, ad, **kw) for p, ad, kw in plans]
        assert len({tuple(s) for s in solo}) == len(solo), (
            "adapters did not change the output — test is vacuous"
        )
        mixed: dict[int, list] = {}

        def run(i, p, ad, kw):
            mixed[i] = _gen_ids(eng, p, ad, **kw)

        ths = [threading.Thread(target=run, args=(i, p, ad, kw))
               for i, (p, ad, kw) in enumerate(plans)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=300)
        for i, s in enumerate(solo):
            assert mixed[i] == s, f"slot {i}: mixed {mixed[i]} != solo {s}"
    finally:
        _stop(eng)


def test_lru_evicted_adapter_refetch_byte_exact_vs_merged_oracle(
        tiny, adapters):
    """Device rows cap at max_slots+1; churning 4 tenants through 3 rows
    forces eviction, and adapter_cache_bytes=1 disables the host tier so
    the re-fetch goes all the way to disk — output must stay byte-exact,
    and equal to a merged-at-load engine's greedy ids."""
    cfg, params = tiny
    eng = _mk(tiny, max_slots=2, paged=True, adapter_cache_bytes=1)
    try:
        for name in ("t0", "t1", "t2", "t3"):
            eng.register_adapter(name, adapters[name])
        first = {n: _gen_ids(eng, adapter=n) for n in ("t0", "t1", "t2", "t3")}
        assert eng.metrics()["adapter_evictions"] > 0
        again = _gen_ids(eng, adapter="t0")
        assert again == first["t0"]
    finally:
        _stop(eng)

    merged = apply_lora(cfg, params, adapters["t0"], weight=1.0)
    oracle = Engine(
        cfg, merged, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                min_prefill_bucket=16, kv_pages=14,
                                kv_page_size=PAGE,
                                prefix_admit_async_compile=False),
    )
    oracle.start()
    try:
        assert _gen_ids(oracle) == first["t0"]
    finally:
        oracle.stop()
        oracle.params = None
        oracle.cache = None


@pytest.mark.multichip
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tp2_adapter_output_identical_to_tp1(tiny, adapters, multichip,
                                             paged):
    """The adapter-less row: the same tokens. The adapter rows: what two
    reduction orders can promise (localai_tpu/testing/streams.py), logprobs
    within LOGPROB_TOL while the context is shared and tokens identical up
    to a tie within it. The greedy adapter stream parts at step 6, where its
    two best candidates sit 4.1e-4 apart at tp=1 (PERF.md §6, PR 28). An
    adapter delta applied on one shard only, or sliced on the wrong axis of
    a row-parallel target, moves the logprobs by some 0.1 at step 0."""
    if multichip < 2:
        pytest.skip("needs 2 devices")

    def run(tp):
        eng = _mk(tiny, tp=tp, paged=paged, max_slots=2)
        try:
            eng.register_adapter("t0", adapters["t0"])
            return (
                _gen_ids(eng),
                stream(eng, PROMPT, adapter="t0", max_new_tokens=10),
                stream(eng, PROMPT, adapter="t0", max_new_tokens=10, seed=5,
                       temperature=0.9),
            )
        finally:
            _stop(eng)

    (base1, *tenant1), (base2, *tenant2) = run(1), run(2)
    assert base1 == base2
    for want, got in zip(tenant1, tenant2):
        assert assert_same_until_near_tie(want, got) >= 4


# --------------------------------------------------------------------- #
# Host tier + fault containment + typed errors
# --------------------------------------------------------------------- #


def test_adapter_fetch_fault_fails_one_tenant_only(tiny, adapters):
    eng = _mk(tiny, max_slots=2)
    try:
        eng.register_adapter("t0", adapters["t0"])
        eng.register_adapter("t1", adapters["t1"])
        with faults.active(faults.FaultSchedule(
                seed=7, rate=1.0, sites=("adapter_fetch",), max_faults=1)):
            h = eng.submit(GenRequest(prompt_ids=list(PROMPT),
                                      max_new_tokens=6, ignore_eos=True,
                                      adapter="t0"))
            evs = list(h)
            assert evs[-1].kind == "error", evs[-1]
            assert "injected" in evs[-1].error
            # The engine keeps serving the OTHER tenant mid-schedule.
            assert _gen_ids(eng, adapter="t1", max_new_tokens=6)
        # And the failed tenant recovers once the fault clears.
        assert _gen_ids(eng, adapter="t0", max_new_tokens=6)
    finally:
        _stop(eng)  # asserts refcounts fully accounted at quiesce


def test_typed_adapter_errors(tiny, adapters):
    cfg, params = tiny
    eng = _mk(tiny, max_slots=2)
    try:
        with pytest.raises(AdapterError, match="unknown adapter"):
            eng.submit(GenRequest(prompt_ids=[1, 2, 3], adapter="nope"))
        eng.register_adapter("t0", adapters["t0"])
        # Idempotent re-register is fine; rebinding is not.
        eng.register_adapter("t0", adapters["t0"])
        with pytest.raises(AdapterError, match="already registered"):
            eng.register_adapter("t0", adapters["t1"])
    finally:
        _stop(eng)
    # Only engines with a SEPARATE draft model reject runtime adapters
    # (model-free spec_mode serves tenants — ISSUE 12).
    deng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                min_prefill_bucket=16),
        draft_cfg=cfg, draft_params=params, n_draft=2,
    )
    try:
        with pytest.raises(AdapterError, match="separate"):
            deng.register_adapter("t0", adapters["t0"])
        with pytest.raises(AdapterError, match="draft"):
            deng.submit(GenRequest(prompt_ids=[1, 2], adapter="t0"))
    finally:
        deng.stop()
        deng.params = None
        deng.cache = None
    moe = get_arch("tiny-moe")
    meng = Engine(
        moe, init_params(moe, jax.random.key(1)),
        ByteTokenizer(moe.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=64,
                                min_prefill_bucket=16),
    )
    try:
        with pytest.raises(AdapterError, match="MoE"):
            meng.register_adapter("t0", adapters["t0"])
    finally:
        meng.stop()
        meng.params = None
        meng.cache = None


def test_adapter_requests_skip_prefix_cache(tiny, adapters):
    """Tenant K/V is adapter-specific: an adapter slot must neither SAVE a
    prefix span nor HIT one saved by the base tenant."""
    eng = _mk(tiny, paged=True, prefix_cache_min=8, max_slots=2)
    try:
        eng.register_adapter("t0", adapters["t0"])
        base_first = _gen_ids(eng)  # saves a span for PROMPT
        hits0 = eng.metrics().get("prefix_cache_hits", 0)
        t0_ids = _gen_ids(eng, adapter="t0")  # same prompt, adapter tenant
        assert eng.metrics().get("prefix_cache_hits", 0) == hits0
        assert _gen_ids(eng, adapter="t0") == t0_ids
        assert _gen_ids(eng) == base_first  # base reuse still byte-stable
    finally:
        _stop(eng)


# --------------------------------------------------------------------- #
# Merge/runtime seam + virtual models (manager resolution)
# --------------------------------------------------------------------- #


def test_merge_runtime_seam_typed_errors(tiny, adapters):
    from localai_tpu.config import LoraConfigError, ModelConfig

    with pytest.raises(LoraConfigError, match="ONE path"):
        ModelConfig(name="x", base_model="b", adapter="a",
                    lora_adapters=["p"]).validate()
    with pytest.raises(LoraConfigError, match="BOTH"):
        ModelConfig(name="x", adapter="a").validate()
    with pytest.raises(LoraConfigError, match="BOTH"):
        ModelConfig(name="x", base_model="b").validate()


def test_apply_lora_quantized_rejection_names_runtime_path(tiny, adapters):
    from localai_tpu.models.quant import quantize_params

    cfg, params = tiny
    qp = jax.jit(lambda p: quantize_params(cfg, p, "int8"))(params)
    with pytest.raises(ValueError, match="runtime|base_model"):
        apply_lora(cfg, qp, adapters["t0"])


def test_virtual_model_resolves_to_shared_engine(tiny, adapters, tmp_path):
    from localai_tpu.config import ApplicationConfig, LoraConfigError
    from localai_tpu.server.manager import ModelManager

    cfg, params = tiny
    models = tmp_path / "models"
    os.makedirs(models)
    ck = str(models / "base-ckpt")
    save_hf_checkpoint(cfg, params, ck)
    docs = [
        {"name": "base", "model": "base-ckpt", "context_size": 128,
         "max_slots": 2},
        {"name": "tenant1", "base_model": "base", "adapter": adapters["t0"],
         "context_size": 128, "system_prompt": "you are tenant 1"},
        {"name": "merged-base", "model": "base-ckpt", "context_size": 128,
         "lora_adapters": [adapters["t1"]]},
        {"name": "tenant-on-merged", "base_model": "merged-base",
         "adapter": adapters["t0"], "context_size": 128},
    ]
    for d in docs:
        with open(models / f"{d['name']}.yaml", "w") as f:
            yaml.safe_dump(d, f)
    mgr = ModelManager(ApplicationConfig(models_dir=str(models)))
    try:
        lm, lease = mgr.lease("tenant1")
        try:
            base = mgr.get("base")
            assert lm.engine is base.engine  # ONE engine, N tenants
            assert lm.adapter == "tenant1"
            assert lm.cfg.system_prompt == "you are tenant 1"
            tenant_ids = _gen_ids(lm.engine, adapter=lm.adapter,
                                  max_new_tokens=6)
            base_ids = _gen_ids(lm.engine, max_new_tokens=6)
            assert tenant_ids != base_ids
        finally:
            lease.release()
        # The seam: a base that merges lora_adapters at load must not also
        # serve runtime tenants.
        with pytest.raises(LoraConfigError, match="pristine"):
            mgr.get("tenant-on-merged")
    finally:
        mgr.shutdown()


# --------------------------------------------------------------------- #
# Model-free speculation × tenancy (ISSUE 12, docs/SPECULATIVE.md)
# --------------------------------------------------------------------- #


def test_model_free_spec_serves_adapter_tenants(tiny, adapters):
    """The PR 10 restriction only applies to a SEPARATE draft model: with
    spec_mode=prompt_lookup the target's own weights verify, the per-slot
    deltas thread into the verify chunk (llama.decode_chunk lora=), and a
    mixed-tenant batch under speculation is byte-identical to each tenant
    solo on a plain engine."""
    plain = _mk(tiny, paged=True)
    spec = _mk(tiny, paged=True, spec_mode="prompt_lookup")
    try:
        for eng in (plain, spec):
            eng.register_adapter("t1", adapters["t1"])
            eng.register_adapter("t2", adapters["t2"])
        # Repetitive prompt so lookup actually drafts while tenants decode.
        rep = [11, 12, 13] * 8
        solo = {
            name: _gen_ids(plain, prompt=rep, adapter=name,
                           max_new_tokens=12)
            for name in (None, "t1", "t2")
        }
        ths, got = [], {}
        def run(name):
            got[name] = _gen_ids(spec, prompt=rep, adapter=name,
                                 max_new_tokens=12)
        for name in (None, "t1", "t2"):
            ths.append(threading.Thread(target=run, args=(name,)))
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=600)
            assert not t.is_alive(), "mixed-tenant spec batch hung"
        for name in (None, "t1", "t2"):
            assert got[name] == solo[name], (name, solo[name], got[name])
        assert got["t1"] != got[None]  # the delta actually applied
    finally:
        _stop(plain)
        _stop(spec)


def test_draft_model_engine_still_rejects_adapters(tiny, adapters):
    """spec_mode=draft_model keeps the typed AdapterError (the draft would
    decode without the delta)."""
    cfg, params = tiny
    from localai_tpu.models.config import ArchConfig

    dc = ArchConfig(name="d", vocab_size=cfg.vocab_size, hidden_size=32,
                    intermediate_size=64, num_layers=1, num_heads=2,
                    num_kv_heads=1, max_position=256)
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                min_prefill_bucket=16),
        draft_cfg=dc, draft_params=init_params(dc, jax.random.key(3)),
        n_draft=3,
    )
    try:
        with pytest.raises(AdapterError, match="model-free"):
            eng.register_adapter("t1", adapters["t1"])
    finally:
        eng.stop()
