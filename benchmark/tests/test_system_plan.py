from types import SimpleNamespace

from benchmark.harness import system as SY


def ecfg(**kw):
    base = dict(max_slots=32, prefill_chunk=0, kv_page_size=128, kv_pages=256,
                block_sizes=(64, 16, 4, 1))
    base.update(kw)
    ns = SimpleNamespace(**base)
    ns.buckets = lambda: [32, 64, 128, 256, 512, 1024, 2048, 4096]
    return ns


def test_groups_follow_bucket_shares_and_the_pool():
    # 10% of prompts in the 128 bucket, 90% in the 2048 bucket
    lengths = [100] * 10 + [1500] * 90
    plan = SY.warm_plan(ecfg(), lengths, 512, 32, requests=150)
    by_len = {}
    for m, length in plan["admit"]:
        by_len.setdefault(length, []).append(m)
    # a run of 8 short prompts is too rare to ever form (150 * 0.1**8)
    assert by_len[100] == [1, 2, 4]
    # 16 long prompts cannot hold 17 pages each in a pool of 256
    assert by_len[1500] == [1, 2, 4, 8]
    assert plan["decode"] == [2, 5, 17, 65] and plan["chunked"] == []


def test_chunked_prompts_get_one_request_per_program():
    lengths = [100, 300, 700, 701]
    plan = SY.warm_plan(ecfg(prefill_chunk=256), lengths, 8, 4, requests=10)
    assert {length for _, length in plan["admit"]} == {100}
    # 300 -> tail 44 (bucket 64) in bucket 512; 700 and 701 -> tail 188/189
    # (bucket 256) in bucket 1024: one request stands for both
    assert plan["chunked"] == [300, 700]
    assert plan["decode"] == [2, 5]
