"""The traffic generator is a function of (mix, seed, duration) alone."""

import os

import numpy as np

from chipbench import harness, loadgen

CHAT = harness.load_json(os.path.join(harness.HERE, "traffic", "chat.json"))


def test_same_seed_same_schedule_other_seed_other_schedule():
    a = loadgen.schedule(CHAT, 7, 20.0)
    b = loadgen.schedule(CHAT, 7, 20.0)
    c = loadgen.schedule(CHAT, 8, 20.0)
    assert a == b
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [r.prompt for r in a[:5]] != [r.prompt for r in c[:5]]
    assert [r.probe for r in a] != [r.probe for r in c]


def test_schedule_keeps_to_the_mix():
    reqs = loadgen.schedule(CHAT, 3, 60.0)
    spec_p, spec_o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert reqs[0].due_s >= -CHAT["ramp_s"] and reqs[-1].due_s < 60.0
    assert all(a.due_s <= b.due_s for a, b in zip(reqs, reqs[1:]))
    for r in reqs:
        assert spec_p["min"] <= r.prompt_tokens <= spec_p["max"]
        assert len(r.prompt.encode()) + 1 == r.prompt_tokens   # + BOS
        assert r.max_tokens == 1 if r.probe else \
            spec_o["min"] <= r.max_tokens <= spec_o["max"]
        assert r.scored == (r.due_s >= 0)
    scored = [r for r in reqs if r.scored]
    rate = len(scored) / 60.0
    assert abs(rate - CHAT["rate_per_s"]) < 0.25 * CHAT["rate_per_s"]
    share = np.mean([r.probe for r in reqs])
    assert abs(share - CHAT["probe_share"]) < 0.08
    med = np.median([r.prompt_tokens for r in reqs])
    assert abs(med - spec_p["median"]) < 0.2 * spec_p["median"]


def test_bursty_and_shared_prefix_mixes_need_no_new_code():
    bursty = {**CHAT, "arrival": "gamma", "arrival_cv": 3.0}
    gaps = np.diff([r.due_s for r in loadgen.schedule(bursty, 1, 120.0)])
    assert np.std(gaps) / np.mean(gaps) > 2.0
    shared = {**CHAT, "shared_prefix_tokens": 64, "prefix_pool": 2}
    heads = {r.prompt[:60] for r in loadgen.schedule(shared, 1, 20.0)
             if r.prompt_tokens > 70}
    assert len(heads) == 2


def test_prompt_buckets_cover_the_mix():
    assert loadgen.prompt_buckets(CHAT, 1024) == [64, 128, 256, 512, 1024]
    small = {"prompt_tokens": {"min": 8, "max": 100}}
    assert loadgen.prompt_buckets(small, 128) == [64, 128]
