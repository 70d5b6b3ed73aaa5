"""tools/bench_gate: the bench-regression gate (ISSUE 11 satellite —
compare BENCH_rNN vs rNN-1, fail on >10% drops on shared keys)."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.bench_gate import (  # noqa: E402
    compare,
    direction,
    load_metrics,
    main,
)


def _write(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return str(p)


def test_direction_heuristics():
    assert direction("decode_tokens_per_sec_paged") == "higher"
    assert direction("p50_ttft_ms") == "lower"
    assert direction("paged_preempt_recover_ms") == "lower"
    assert direction("spec_accept_rate") == "higher"
    assert direction("pct_of_hbm_roofline") == "higher"
    # speedup wins even though the key also mentions ttft.
    assert direction("prefix_ttft_speedup") == "higher"
    assert direction("kv_swap_bytes_out") == "lower"
    assert direction("some_unknown_metric") == "higher"


def test_direction_markers_cover_multihost_rows():
    """BENCH_MULTIHOST keys (ISSUE 13 satellite) gate in the right
    direction from their first shared round."""
    assert direction("multihost_tps") == "higher"
    assert direction("multihost_p99_ttft_ms") == "lower"
    assert direction("multihost_span_transfer_ms") == "lower"
    assert direction("multihost_span_frame_bytes") == "lower"
    assert direction("multihost_disagg_ttft_ms") == "lower"
    assert direction("multihost_recompute_ttft_ms") == "lower"
    assert direction("multihost_remote_handoffs") == "higher"


def test_direction_markers_cover_loop_rows():
    """BENCH_LOOP keys (ISSUE 17, docs/ENGINE_RUNTIME.md) gate in the
    right direction from their first shared round: host overhead per
    block must not RISE, the pipelined-vs-serial ratio must not DROP."""
    for occ in (1, 8, 16):
        assert direction(
            f"loop_host_overhead_per_block_ms_bs{occ}_pipelined") == "lower"
        assert direction(
            f"loop_host_overhead_per_block_ms_bs{occ}_serial") == "lower"
        # "speedup" outranks the lower-is-better "overhead" marker.
        assert direction(f"loop_overhead_speedup_bs{occ}") == "higher"


def test_direction_markers_cover_longctx_rows():
    """BENCH_LONGCTX keys (ISSUE 14, docs/LONG_CONTEXT.md) gate in the
    right direction from their first shared round."""
    assert direction("longctx_32k_prefill_tok_per_s") == "higher"
    assert direction("longctx_128k_prefill_tok_per_s") == "higher"
    assert direction("longctx_512k_prefill_tok_per_s") == "higher"
    assert direction("longctx_512k_decode_tok_per_s") == "higher"
    assert direction("longctx_512k_ttft_ms") == "lower"
    assert direction("longctx_users_agg_tok_per_s") == "higher"
    assert direction("longctx_users_prefix_hit_rate") == "higher"
    # Workload descriptor, pinned so a bigger benchmark document can never
    # read as a regression.
    assert direction("longctx_users_doc_tokens") == "higher"


def test_direction_markers_cover_fork_rows():
    """BENCH_FORK keys (ISSUE 18, docs/TREE_SAMPLING.md) gate in the
    right direction from their first shared round: a rising KV ratio
    means CoW sharing broke; the fork-vs-clone speedup must not drop."""
    assert direction("fork_best_of_1_decode_tok_per_s") == "higher"
    assert direction("fork_best_of_8_decode_tok_per_s") == "higher"
    assert direction("fork_best_of_1_p99_ttft_ms") == "lower"
    assert direction("fork_best_of_8_p99_ttft_ms") == "lower"
    assert direction("fork_kv_bytes_ratio") == "lower"
    # "speedup" outranks the lower-is-better "ttft" marker.
    assert direction("fork_vs_clone_ttft_speedup") == "higher"


def test_compare_flags_drops_in_the_bad_direction():
    old = {"decode_tps": 1000.0, "p99_ttft_ms": 100.0, "accept_rate": 0.5}
    new = {"decode_tps": 850.0, "p99_ttft_ms": 125.0, "accept_rate": 0.52}
    r = compare(new, old, threshold=0.10)
    keys = {x["key"] for x in r["regressions"]}
    assert keys == {"decode_tps", "p99_ttft_ms"}
    assert not r["missing"] and not r["added"]


def test_compare_tolerates_within_threshold_and_good_moves():
    old = {"decode_tps": 1000.0, "p99_ttft_ms": 100.0}
    new = {"decode_tps": 950.0, "p99_ttft_ms": 60.0}  # -5% tps, better p99
    r = compare(new, old, threshold=0.10)
    assert r["regressions"] == []
    assert {x["key"] for x in r["improvements"]} == {"p99_ttft_ms"}


def test_compare_only_shared_keys_gate():
    old = {"a_tps": 100.0, "removed_tps": 50.0}
    new = {"a_tps": 100.0, "added_tps": 1.0}
    r = compare(new, old)
    assert r["regressions"] == []
    assert r["missing"] == ["removed_tps"]
    assert r["added"] == ["added_tps"]
    # A zero baseline is skipped, not divided by.
    assert compare({"x_tps": 5.0}, {"x_tps": 0.0})["regressions"] == []


def test_load_metrics_unwraps_bench_rnn_payloads(tmp_path):
    raw = {"metric": "decode", "unit": "tok/s", "value": 100.0,
           "decode_tps": 100.0, "note": "str ignored", "flag": True}
    p1 = _write(tmp_path, "raw.json", raw)
    assert load_metrics(p1) == {"decode_tps": 100.0}
    wrapped = {"n": 4, "cmd": "python bench.py", "rc": 0, "tail": "…",
               "parsed": raw}
    p2 = _write(tmp_path, "wrapped.json", wrapped)
    assert load_metrics(p2) == {"decode_tps": 100.0}


def test_main_exit_codes(tmp_path, capsys):
    good_old = _write(tmp_path, "old.json", {"decode_tps": 100.0})
    good_new = _write(tmp_path, "new.json", {"decode_tps": 99.0})
    bad_new = _write(tmp_path, "bad.json", {"decode_tps": 50.0})
    assert main([good_new, good_old]) == 0
    assert main([bad_new, good_old]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION decode_tps" in out
    # Usage/parse errors exit 2.
    assert main([str(tmp_path / "missing.json"), good_old]) == 2
    notjson = tmp_path / "notjson.json"
    notjson.write_text("not json")
    assert main([str(notjson), good_old]) == 2
    assert main([good_new, good_old, "--threshold", "0"]) == 2
    # --json contract.
    assert main([bad_new, good_old, "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["regressions"][0]["key"] == "decode_tps"


def test_gate_on_real_rounds_if_present(tmp_path):
    """A payload of the wrapped shape the round records had ({n, cmd, rc,
    tail, parsed}) parses, string and boolean fields and all. The records
    themselves (BENCH_r01-r05) were deleted in PR 21; the figures below are
    made up."""
    p = _write(tmp_path, "round.json", {
        "n": 4, "cmd": "python bench.py", "rc": 0,
        "tail": "bench devices: [...]\n{\"metric\": ...}",
        "parsed": {
            "metric": "decode_tokens_per_sec_http_bs8", "unit": "tok/s",
            "value": 600.0, "vs_baseline": None,
            "decode_tokens_per_sec_paged": 1300.0,
            "decode_tokens_per_sec_int8": 2700.0,
            "paged_vs_dense_tps": 0.7, "long_ctx_paged": True,
            "long_ctx_prefill_ms": 3500.0, "p50_ttft_ms": 100.0,
        },
    })
    m = load_metrics(p)
    assert "decode_tokens_per_sec_paged" in m
    assert "long_ctx_paged" not in m and "unit" not in m
    r = compare(m, m)
    assert r["regressions"] == [] and r["improvements"] == []
