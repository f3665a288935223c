import json
from pathlib import Path

import pytest

from perfbench.spec import ROOT
from perfbench.traffic import gaps, lengths, make_requests

CHAT = json.loads((Path(__file__).parent
                   / "data/traffic/chat-steady.json").read_text())
BATCH = json.loads((ROOT / "perfbench/traffic/batch-decode.json").read_text())


def _key(reqs):
    return [(r.due_s, r.client, r.prompt, r.body, r.max_tokens) for r in reqs]


def test_same_seed_same_schedule_lengths_and_request_seeds():
    a = make_requests(CHAT, {"rate_rps": 3.0}, 151936, 40, 2**31 + 12345, 4096)
    b = make_requests(CHAT, {"rate_rps": 3.0}, 151936, 40, 2**31 + 12345, 4096)
    assert _key(a) == _key(b)
    assert len(a) == 120 and all("seed" in r.body for r in a)


def test_other_seed_same_work_in_another_order():
    a = make_requests(CHAT, {"rate_rps": 3.0}, 151936, 40, 1, 4096)
    b = make_requests(CHAT, {"rate_rps": 3.0}, 151936, 40, 2, 4096)
    assert _key(a) != _key(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    ga = sorted(round(y.due_s - x.due_s, 9) for x, y in zip(a, a[1:]))
    gb = sorted(round(y.due_s - x.due_s, 9) for x, y in zip(b, b[1:]))
    # the gap after the last request is not observable: compare the rest
    assert len(set(ga) ^ set(gb)) <= 2
    assert a[0].due_s == 0.0 and max(r.due_s for r in a) < 40


def test_chat_mix_is_what_the_file_says():
    n = 2000
    p = lengths(CHAT["prompt_len"], n)
    o = lengths(CHAT["output_len"], n)
    assert min(p) == 32 and max(p) == 3500 and 8 <= min(o) <= 12 and max(o) == 512
    assert p[n // 2] == pytest.approx(400, abs=2)
    assert o[n // 2] == pytest.approx(120, abs=1)
    assert 560 < sum(p) / n < 620        # mean ~590
    assert 140 < sum(o) / n < 160        # mean ~150
    assert 0.03 < sum(x > 2048 for x in p) / n < 0.05
    g = gaps({"process": "poisson"}, n)
    assert sum(g) / n == pytest.approx(1.0)
    assert all(len(r.prompt) + r.max_tokens <= 4096 for r in
               make_requests(CHAT, {"rate_rps": 5.0}, 1000, 40, 3, 4096))


def test_closed_loop_deals_requests_to_clients():
    reqs = make_requests(BATCH, {"clients": 64}, 151936, 40, 9, 4096)
    assert len(reqs) == 64 * BATCH["per_client"]
    assert {r.client for r in reqs} == set(range(64))
    assert all(r.due_s is None and r.body == {"temperature": 0.0}
               for r in reqs)
    assert all(64 <= len(r.prompt) <= 256 and 192 <= r.max_tokens <= 640
               for r in reqs)


def test_arrival_gaps_have_mean_one_and_are_the_same_for_every_seed():
    for process, cv in (("poisson", 1.0), ("uniform", 0.0)):
        g = gaps({"process": process}, 500)
        mean = sum(g) / 500
        spread = (sum((x - mean) ** 2 for x in g) / 500) ** 0.5 / mean
        assert mean == pytest.approx(1.0)
        assert spread == pytest.approx(cv, abs=0.05)
    with pytest.raises(ValueError):
        gaps({"process": "gamma", "cv": 2.5}, 10)
