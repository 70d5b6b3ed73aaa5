"""What the GLM-4.7-Flash configuration brought: `costs_mla_moe` against the
model's published size and a step's bytes by hand, its plain reference's
attention against a walk computed by hand in numpy, the readers of its eight
metrics on hand-made contexts (the helpers are test_tracing_readers.py's and
test_scope_readers.py's), its traffic mix, and its entries in the manifest."""

import re

import numpy as np
import pytest

from benchmark.harness import costs_mla_moe as costs
from benchmark.harness import spec as S
from benchmark.harness import trace_reduce as TRD
from benchmark.harness import traffic as TR
from benchmark.reducers import mla_moe_roofline
from benchmark.reducers.hybrid_roofline import kernel_step_s
from benchmark.tests import test_scope_readers as SR
from benchmark.tests.test_tracing_readers import MS, chip, ev, host

CONFIG = "glm-4.7-flash-int8-ep8"
CELL = CONFIG + ".decode-reasoning"
NEW = ("mlamoe_latent_attention_hbm_roofline_share",
       "mlamoe_held_experts_hbm_roofline_share",
       "mlamoe_proj_matmul_hbm_roofline_share",
       "mlamoe_decode_hbm_roofline_share", "mlamoe_latent_write_share",
       "mlamoe_held_expert_active_share", "mlamoe_routed_here_share",
       "mlamoe_held_load_max_over_mean")


# ---- the byte counts -------------------------------------------------------- #


def test_costs_match_the_published_size():
    cfg = S.config(CONFIG)
    held = costs.held_params(cfg)
    assert costs.layers(cfg) == {"mla": 47, "dense": 1, "moe": 46}
    mla = costs.mla_layer_params(cfg)
    assert mla["int8"] == 2048 * 768 + 768 * 5120 + 2048 * 576 + 5120 * 2048
    assert mla["small"] == 20 * (192 + 256) * 512 + 512 + 768
    assert mla["int8"] + mla["small"] == pytest.approx(21.76e6, rel=1e-3)
    assert costs.expert_params(cfg) == 3 * 2048 * 1536  # 9.437 M
    assert held["experts_held"] == 46 * 8 * 3 * 2048 * 1536  # 3.47 B
    assert held["shared_experts"] == 46 * 3 * 2048 * 1536  # 0.43 B
    assert held["routers"] == 46 * 2048 * 64  # all 64 outputs, not the 8 held
    assert held["dense_mlp"] == 3 * 2048 * 10240
    assert held["head"] == held["embedding"] == 154880 * 2048
    # the model card's 30B-A3B, without its MTP block
    assert costs.param_count(cfg) == pytest.approx(29.94e9, rel=1e-3)
    assert costs.active_params(cfg) == pytest.approx(3.58e9, rel=2e-3)
    # a token's latent rows: 47 layers x 640 values x bf16; the YAML's pool
    assert costs.latent_bytes_per_token(cfg, 2) == 60160
    pages = cfg["yaml"]["kv_pages"] * cfg["yaml"]["kv_page_size"]
    assert pages * 60160 == pytest.approx(6.9e9, rel=0.01)


def test_a_steps_bytes_by_hand():
    cfg = S.config(CONFIG)
    proj = 47 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 5120 * 2048) \
        + 46 * 3 * 2048 * 1536 + 3 * 2048 * 10240
    small = 47 * (20 * 448 * 512 + 512 + 768) + 46 * 2048 * 64
    experts = 46 * 8 * 3 * 2048 * 1536
    head = 154880 * 2048
    assert costs.proj_matmul_bytes(cfg, 1) == proj
    w = costs.weight_bytes(cfg, 1)
    assert w == proj + head + experts + 2 * small
    assert w == pytest.approx(5.54e9, rel=2e-3)  # ISSUE 49's count
    # half the (layer, held expert) pairs idle: half the held experts' bytes
    assert costs.weight_bytes(cfg, 1, 0.5) == w - experts / 2
    assert costs.held_expert_bytes(cfg, 1, 0.5) == experts / 2
    # 75,000 live latent rows are 4.5 GB, 45% of the step
    step = costs.decode_step_bytes(cfg, 75000, 1, 2)
    assert step == w + 75000 * 60160
    assert 75000 * 60160 / step == pytest.approx(0.45, abs=0.005)


# ---- the reference ------------------------------------------------------------ #


def _tiny_layer(rng, D=16, H=3, r=8, n=6, rot=4, v=10, ql=5):
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return {"attn_norm": 1 + 0.1 * f(D), "wq_a": f(D, ql), "q_norm_a": 1 + 0.1 * f(ql),
            "wq_b": f(ql, H * (n + rot)), "wkv_a": f(D, r + rot),
            "kv_norm": 1 + 0.1 * f(r), "w_kb": f(H, n, r), "w_vb": f(H, v, r),
            "wo": 0.3 * f(H * v, D)}


def _by_hand(h, lw, H, r, n, rot, theta, eps, rope=True):
    """The module docstring's equations in numpy float64, token by token."""
    h = h.astype(np.float64)
    lw = {k: np.asarray(a, np.float64) for k, a in lw.items()}
    norm = lambda x, w: x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w  # noqa: E731

    def rot_at(x, t):  # x [..., rot], half-split pairs (i, i + rot/2)
        if not rope:
            return x
        inv = 1.0 / theta ** (np.arange(0, rot, 2) / rot)
        c, s = np.cos(t * inv), np.sin(t * inv)
        a, b = x[..., : rot // 2], x[..., rot // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s], -1)

    T = h.shape[0]
    a = norm(h, lw["attn_norm"])
    q = (norm(a @ lw["wq_a"], lw["q_norm_a"]) @ lw["wq_b"]).reshape(T, H, n + rot)
    ckv = a @ lw["wkv_a"]
    c = norm(ckv[:, :r], lw["kv_norm"])
    out = np.zeros((T, H, lw["w_vb"].shape[1]))
    for t in range(T):
        for hd in range(H):
            qt = np.concatenate([q[t, hd, :n], rot_at(q[t, hd, n:], t)])
            scores = []
            for s_ in range(t + 1):
                k = np.concatenate([lw["w_kb"][hd] @ c[s_], rot_at(ckv[s_, r:], s_)])
                scores.append(qt @ k / np.sqrt(n + rot))
            p = np.exp(scores - np.max(scores))
            p /= p.sum()
            out[t, hd] = sum(p[s_] * (lw["w_vb"][hd] @ c[s_]) for s_ in range(t + 1))
    return h + out.reshape(T, -1) @ lw["wo"]


@pytest.mark.parametrize("rope", [True, False])
def test_reference_attention_is_the_equations_by_hand(rope):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla_moe as REF

    rng = np.random.default_rng(0)
    lw = _tiny_layer(rng)
    h = rng.standard_normal((7, 16)).astype(np.float32)
    kw = dict(heads=3, rank=8, nope=6, rot=4, theta=100.0, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(REF.mla_attention(
            jnp.asarray(h), {k: jnp.asarray(v) for k, v in lw.items()},
            rope=rope, **kw))
    want = _by_hand(h, lw, 3, 8, 6, 4, 100.0, 1e-5, rope=rope)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the rotation weighs: NoPE is another function at these sizes
    other = _by_hand(h, lw, 3, 8, 6, 4, 100.0, 1e-5, rope=not rope)
    assert np.abs(other - want).max() > 1e-2


def test_reference_rounds_the_latent_row_to_eight_bits():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla_moe as REF

    x = jnp.asarray([1.0, 1.06, 0.3, -17.3, 200.0], jnp.float32)
    np.testing.assert_array_equal(
        REF._round_fp8(x), x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    rng = np.random.default_rng(1)
    lw = {k: jnp.asarray(v) for k, v in _tiny_layer(rng).items()}
    h = jnp.asarray(rng.standard_normal((7, 16)), jnp.float32)
    kw = dict(heads=3, rank=8, nope=6, rot=4, theta=100.0, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(REF.mla_attention(h, lw, **kw))
        held = np.asarray(REF.mla_attention(h, lw, kv_round="fp8", **kw))
    assert 1e-3 < np.abs(held - exact).max() < 0.1 * np.abs(exact).max()
    with pytest.raises(ValueError):
        REF.mla_attention(h, lw, kv_round="fp4", **kw)


# ---- the readers ------------------------------------------------------------ #

LAT = ("%latent_paged_attention.20 = (f32[64,20,640], f32[64,20,128]) "
       "custom-call(%q)")
MM = "%int8_matmul.176 = bf16[1,64,2048]{2,1,0} custom-call(%x)"  # a projection
EXP = "%int8_matmul.182 = bf16[8,64,2048]{2,1,0} custom-call(%x)"  # held experts


def capture(n=2):
    """Four decode blocks of 60 ms (the first is cut by the capture), each a
    `while` envelope over n steps of 8 ms of the latent walk, 3 ms of
    projections and 6 ms of the held experts' matmul."""
    ops, mods = [], []
    for k in range(4):
        t = k * 60 * MS
        ops.append(("%while.9 = (s32[]) while(%t)", t, 60 * MS))
        for s in range(n):
            t0 = t + s * 25 * MS
            ops += [(LAT, t0, 8 * MS), (MM, t0 + 8 * MS, 3 * MS),
                    (EXP, t0 + 11 * MS, 6 * MS)]
        mods.append(("jit_decode_block(7)", t, 60 * MS))
    mods.append(("jit_decode_block(7)", 240 * MS, 1 * MS))
    dispatch = [("dispatch/decode_block", 0.0, 1.0, {"n": n, "live": 64})] * 3
    return {"planes": [chip(0, ops, mods)], "dispatch": dispatch}


def scoped_planes():
    """One chip, a 100 ms window: a 40 ms decode block of which 1 ms is the
    latent pool's staged write, and an admission's cache write, which is not
    the block's."""
    D, A = 22, 11
    block = "jit(decode_block)/control/"
    ops = [
        SR.op("%latent_paged_attention.20", 10 * MS, 27 * MS, D,
              block + "while/body/layer/while/body/attention/mix/"
              "latent_paged_attention/pallas_call:"),
        SR.op("%int8_matmul.7", 37 * MS, 12 * MS, D,
              block + "while/body/layer/while/body/mlp/experts/int8_matmul/"
              "pallas_call:"),
        SR.op("%latent_pool_write.1", 49 * MS, 1 * MS, D,
              block + "attention/cache_write/latent_write/"
              "jit(latent_pool_write)/latent_pool_write:"),
        SR.op("%fusion.5", 60 * MS, 8 * MS, A,
              "jit(admit)/attention/cache_write/scatter:"),
        SR.op("%latent_pool_write.1", -25 * MS, 20 * MS, D,  # before the mark
              block + "attention/cache_write/latent_write/"
              "jit(latent_pool_write)/latent_pool_write:"),
    ]
    modules = [("jit_decode_block(22)", 10 * MS, 40 * MS),
               ("jit_admit(11)", 60 * MS, 8 * MS)]
    return [SR.chip(0, ops, modules),
            SR.host([(TRD.WINDOW_MARK, 0.0, 100 * MS)])]


def context(cap=None, journal=None, xplanes=None):
    class Ecfg:
        max_slots = 64
        kv_pages = 768
        kv_page_size = 128

    pool = 768.0 * 128.0
    return {"trace": {"capture": cap, "xplanes": xplanes, "t_start": 0.0,
                      "t_end": 1.0,
                      "reduced": {"modules": {"jit_decode_block(7)": {
                          "total_s": 1.0, "whole": {"mean_s": 0.06}}}}},
            # the generator's stamps count a queued client's prompt too: the
            # readers here do not look at them
            "stamps": {"requests": [
                {"send": -1.0, "end": None, "prompt_tokens": 300, "chunks": []}]},
            "journal": journal if journal is not None else [
                ev(0.1, "decode_block", a=2.0), ev(0.15, "loop_iter", a=1.0),
                # a block of 2 steps whose slots held 70,000 rows, one of 4
                # steps whose slots held 76,000: 74,000 a step
                ev(0.1, "latent_rows", a=2 * 70000.0, b=2 * pool),
                ev(0.5, "latent_rows", a=4 * 76000.0, b=4 * pool),
                ev(0.2, "moe_experts", a=736.0, b=552.0),
                ev(0.2, "moe_here", a=640.0, b=96.0),
                ev(0.2, "moe_load", a=16.0, b=8.0),
                ev(0.6, "moe_experts", a=736.0, b=552.0),
                ev(0.6, "moe_here", a=640.0, b=64.0),
                ev(0.6, "moe_load", a=8.0, b=8.0)],
            "config": S.config(CONFIG), "cell": {"chips": 1},
            "engine_cfg": Ecfg, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_counter_shares_sum_the_windows_blocks():
    ctx = context()
    assert S.reader("mlamoe_held_expert_active_share")(ctx) == pytest.approx(75.0)
    assert S.reader("mlamoe_routed_here_share")(ctx) == pytest.approx(12.5)
    assert S.reader("mlamoe_held_load_max_over_mean")(ctx) == pytest.approx(150.0)
    assert mla_moe_roofline.latent_rows(ctx) == pytest.approx(74000.0)


def test_rooflines_count_the_bytes_over_each_kernels_own_time():
    ctx = context(capture())
    cfg, cap = ctx["config"], ctx["trace"]["capture"]
    assert kernel_step_s(cap, "latent_paged_attention") == pytest.approx(8e-3)
    assert kernel_step_s(cap, "int8_matmul", lead=8) == pytest.approx(6e-3)
    assert kernel_step_s(cap, "int8_matmul", lead=1) == pytest.approx(3e-3)
    # the rows the program says its slots held, 60,160 B each
    got = S.reader("mlamoe_latent_attention_hbm_roofline_share")(ctx)
    assert got == pytest.approx(100.0 * (74000 * 60160 / 819e9) / 8e-3)
    assert 60.0 < got < 75.0
    # three quarters of the (layer, held expert) pairs were chosen
    experts = 0.75 * 46 * 8 * 3 * 2048 * 1536
    assert S.reader("mlamoe_held_experts_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (experts / 819e9) / 6e-3)
    proj = costs.proj_matmul_bytes(cfg, 1)
    assert S.reader("mlamoe_proj_matmul_hbm_roofline_share")(
        ctx) == pytest.approx(100.0 * (proj / 819e9) / 3e-3)
    # the whole step: 60 ms a block of 2 steps (the journal's decode_block size)
    step = costs.decode_step_bytes(cfg, 74000, 1, 2, 0.75)
    whole = S.reader("mlamoe_decode_hbm_roofline_share")(ctx)
    assert whole == pytest.approx(100.0 * (step / 819e9) / 30e-3)
    assert 0.0 < whole < 100.0
    with pytest.raises(ValueError):
        mla_moe_roofline.read(ctx, "no_such_metric")


def test_latent_write_share_is_the_decode_blocks_own():
    ctx = context(xplanes=scoped_planes())
    assert S.reader("mlamoe_latent_write_share")(ctx) == pytest.approx(2.5)
    # the existing reader drops the word and books the op to its leaf
    from benchmark.reducers import scope_share
    assert scope_share.leaf_of(
        "jit(decode_block)/control/attention/cache_write/latent_write/"
        "jit(latent_pool_write)/latent_pool_write:") == "attention/cache_write"
    assert scope_share.read({"trace": ctx["trace"]}, "attention/cache_write",
                            ["jit_decode_block"]) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read(name):
    """A parent that lacks the model (it journals no latent rows and no
    routing, its capture has no such kernel or scope), an untraced run:
    None, never an exception."""
    other = [ev(0.1, "decode_block", a=2.0), ev(0.2, "decode_rows", a=64.0, b=40.0)]
    assert S.reader(name)({**context(journal=other), "trace": None}) is None
    if name == "mlamoe_latent_write_share":
        planes = scoped_planes()
        planes[0]["ops"] = [o for o in planes[0]["ops"]
                            if "latent_write" not in o.tf_op]
        assert S.reader(name)(context(xplanes=planes)) is None
        return
    if "roofline" not in name:
        assert S.reader(name)(context(capture(), journal=other)) is None
        return
    planes = [host([(TRD.WINDOW_MARK, 0.0, 10 * MS)]),
              chip(0, [("%fusion.1 = f32[8] fusion()", 0.0, 9 * MS)],
                   [("jit_wrapped(1)", 0.0, 3 * MS)] * 3)]
    kernelless = context({"planes": planes, "dispatch": []})
    for ctx in ({**context(), "trace": None},
                {**context(capture()), "peaks": None}, context()):
        assert S.reader(name)(ctx) is None
    if name != "mlamoe_decode_hbm_roofline_share":
        assert S.reader(name)(kernelless) is None
    if name != "mlamoe_proj_matmul_hbm_roofline_share":
        assert S.reader(name)(context(capture(), journal=other)) is None


# ---- the mix ------------------------------------------------------------------ #


def test_the_mix_is_short_prompts_and_long_answers_the_same_on_every_seed():
    cell = S.cell(CELL)
    mix, load = cell["mix"], cell["cell"]["load"]
    assert (mix["loop"], mix["stratum"], mix["queue"]) == ("closed", 64, 2048)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 2048}
    a = TR.schedule(mix, load, seed=1, overhead=51)
    b = TR.schedule(mix, load, seed=2 ** 31 + 7, overhead=51)
    assert a["clients"] == 80 and len(a["requests"]) >= 2048
    for key in ("prompt_tokens", "max_tokens"):
        assert sorted(r[key] for r in a["requests"]) == sorted(
            r[key] for r in b["requests"])
        for lo in range(0, 2048, 64):  # every stratum the same multiset
            assert sorted(r[key] for r in a["requests"][lo:lo + 64]) == sorted(
                r[key] for r in b["requests"][:64])
    assert [r["prompt"] for r in a["requests"]] != [r["prompt"] for r in b["requests"]]
    first = a["requests"][:64]
    p = sum(r["prompt_tokens"] for r in first) / 64
    o = sum(r["max_tokens"] for r in first) / 64
    assert p == pytest.approx(320 + 51, abs=1) and o == pytest.approx(1536, abs=1)
    # the first wave's crest: every slot holds its prompt and 1,024 tokens,
    # whole pages, and three 16-step blocks scheduled ahead: inside the pool
    page = cell["config"]["yaml"]["kv_page_size"]
    crest = sum(-(-(r["prompt_tokens"] + 1024 + 48) // page) for r in first)
    assert crest < 0.9 * cell["config"]["yaml"]["kv_pages"]
    assert max(r["prompt_tokens"] + r["max_tokens"] for r in a["requests"]) \
        < cell["config"]["yaml"]["context_size"]


# ---- the manifest ------------------------------------------------------------ #


def test_the_new_metrics_are_listed_for_the_one_cell():
    man = S.manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"][:11]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] in layers
        assert listed[name]["moves"] == "out_tokens_per_s"
        assert listed[name]["unit"] == "%"
    # in this order, wherever a later PR's appended entries put them
    assert [m["name"] for m in man["per_layer"] if m["name"] in NEW] == list(NEW)
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    config = next(c for c in man["configs"] if c["name"] == CONFIG)
    # nobody else's list holds the cell
    for m in man["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
    assert (entry["traffic"], entry["chips"]) == ("decode-reasoning", 1)
    assert all(len(e["why"]) <= 200 for e in man["workloads"] + man["configs"])
    cell = S.cell(CELL)
    assert cell["cell"]["load"]["clients"] == 80
    assert cell["cell"]["trace_s"] == 12.0
    cfg = cell["config"]
    assert cfg["reduced"] == ["n_routed_experts"] == config["reduced"]
    y = cfg["yaml"]
    assert (y["model"], y["quantization"], y["max_slots"], y["kv_page_size"],
            y["context_size"], y["expert_share"]) == (
                "glm-4.7-flash", "int8", 64, 128, 4096, [0, 8])
    assert y.get("prefill_chunk") is None and cfg["reference"] == "mla_moe"
    assert cfg["check"]["prompt_tokens"] == [48, 200, 700, 2000]
    assert cfg["check"]["new_tokens"] == 17
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names and "collective_share" not in names
    assert {"admit_device_share", "device_idle_share", "kernel_time_share",
            "hbm_peak_gb", "kv_preemptions"} <= names
    assert not {"decode_hbm_roofline_share", "hybrid_decode_hbm_roofline_share",
                "latent_attention_hbm_roofline_share",
                "ssdgqa_decode_hbm_roofline_share"} & names
    # the 25 metrics that list no cells are every cell's, this one's too
    assert len([m for m in cell["per_layer"] if "workloads" not in m]) == 25


def test_every_why_is_about_its_own_cell():
    """A cell's `why` names the traffic that the cell's own mix sends, and no
    `why` is a placeholder (this PR's first manifest had Solar-Open2's entry
    describe this cell's mix and this cell's say "to be filled")."""
    man = S.manifest()
    for e in man["workloads"] + man["configs"]:
        assert not re.search(r"to be filled|todo|tbd|fixme", e["why"], re.I), e
    for w in man["workloads"]:
        mix = S.cell(w["name"])["mix"]
        for word, key in (("prompts", "prompt_tokens"),
                          ("outputs|answers", "output_tokens")):
            said = re.search(rf"(?:{word}) ([\d,]+)-([\d,]+)", w["why"])
            if said:
                lo, hi = (int(x.replace(",", "")) for x in said.groups())
                assert (lo, hi) == (mix[key]["min"], mix[key]["max"]), w
    ours = next(w for w in man["workloads"] if w["name"] == CELL)
    assert "latent walk" in ours["why"] and "prompts 128-512" in ours["why"]


def test_the_manifest_only_gained_entries():
    """Against the manifest of the newest commit that lacks this cell (the
    parent, while this PR is a working tree or the tip): every entry it has
    is here, unchanged and in its place; a per-layer metric's `workloads`
    may have grown at its end."""
    import json
    import subprocess

    parent = None
    for rev in ("HEAD", "HEAD~1"):
        got = subprocess.run(["git", "show", f"{rev}:BENCHMARK.json"],
                             cwd=S.ROOT, capture_output=True, text=True)
        if got.returncode:
            break
        m = json.loads(got.stdout)
        if CELL not in [w["name"] for w in m["workloads"]]:
            parent = m
            break
    if parent is None:
        pytest.skip("no commit without this cell within reach of git")
    man = S.manifest()
    for key, was in parent.items():
        if not isinstance(was, list) or key in ("command", "paths"):
            assert man[key] == was, key
            continue
        now = man[key][:len(was)]
        if key == "per_layer":
            for a, b in zip(was, now):
                assert b.get("workloads", [])[:len(a.get("workloads", []))] \
                    == a.get("workloads", []), a["name"]
            now = [{**b, "workloads": a["workloads"]} if "workloads" in a
                   else b for a, b in zip(was, now)]
        assert now == was, key


def test_the_file_holds_every_number_of_the_published_config():
    """Every number of the catalog's `config` under its own key but the one
    reduced, which is the held count beside the published one; every assumed
    size with its reason; the deployment; the MTP departure."""
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "GLM-4.7-Flash"' in line)
    cfg = S.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k == "n_routed_experts":
            assert (cfg[k], cfg["published"][k]) == (8, v == 64 and 64)
        else:
            assert cfg[k] == v, k
    for word in ("scoring_func", "norm_topk_eps", "rope_pairing",
                 "softmax_scale", "latent_row_values", "latent_row",
                 "precision", "weights", "tokenizer"):
        assert word in cfg["assumed"], word
    assert "chip 0 of the 8" in cfg["deployment"]
    assert "model.layers.47." in cfg["departures"]["mtp_block"]
    assert cfg["rehearsal"]["yaml"]["model"] == "tiny-glm-4.7-flash"
