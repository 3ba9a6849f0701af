"""The generator and the end-to-end arithmetic, without a server."""
import json
import os
from collections import Counter

import e2e
import traffic
from conftest import BENCH


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_every_seed_offers_the_same_work_in_another_order():
    mix = _mix("chat-steady")
    times, shapes = traffic.open_cycle(mix, 6.5, 40.0)
    again = traffic.open_cycle(mix, 6.5, 40.0)
    assert (times, shapes) == again and len(times) == 260
    assert times == sorted(times) and 0 <= times[0] and times[-1] < 40.0
    p = [s.prompt_tokens for s in shapes]
    o = [s.output_tokens for s in shapes]
    assert min(p) >= 32 and max(p) <= 2048 and 400 < sorted(p)[130] < 640
    assert min(o) >= 16 and max(o) <= 512 and 100 < sorted(o)[130] < 160
    r = {traffic.rotation(seed, 260) for seed in range(1, 40)}
    assert len(r) > 20 and all(0 <= x < 260 for x in r)
    assert traffic.rotation(2 ** 31 + 11, 260) == traffic.rotation(2 ** 31 + 11, 260)


def test_closed_lists_and_contents():
    mix = _mix("decode-saturate")
    lists = traffic.closed_lists(mix, 64, 12)
    assert lists == traffic.closed_lists(mix, 64, 12)
    assert all(256 <= s.prompt_tokens <= 768 and 768 <= s.output_tokens <= 1280
               for lst in lists for s in lst)
    words = [f"w{i}" for i in range(1000)]
    a = traffic.content_for(words, 1, "m3", lists[0][0], 8)
    assert a == traffic.content_for(words, 1, "m3", lists[0][0], 8)
    assert a != traffic.content_for(words, 2, "m3", lists[0][0], 8)
    assert a != traffic.content_for(words, 1, "w3", lists[0][0], 8)
    assert len(a.split()) == lists[0][0].prompt_tokens - 8
    shared = traffic.Shape(300, 10, prefix_group=1, prefix_tokens=200)
    x = traffic.content_for(words, 1, "m1", shared, 8).split()
    y = traffic.content_for(words, 1, "m2", shared, 8).split()
    assert x[:200] == y[:200] and x[200:] != y[200:] and len(x) == 292


def test_end_to_end_arithmetic():
    win = [100.0, 140.0]
    reqs = [
        # due in window, first token 0.5 s after due, 11 tokens over 1 s
        {"id": "a", "in_window": True, "due": 101.0, "launched": 101.001,
         "t_first": 101.5, "t_last": 102.5, "tokens": 11, "max_tokens": 11,
         "tokens_in_window": 11, "status": "ok"},
        # warm-up request that finished inside the window
        {"id": "b", "in_window": False, "due": 95.0, "launched": 95.0,
         "t_first": 95.2, "t_last": 105.2, "tokens": 101, "max_tokens": 101,
         "tokens_in_window": 52, "status": "ok"},
        # due in window, never answered
        {"id": "c", "in_window": True, "due": 139.0, "launched": 139.0,
         "tokens": 0, "max_tokens": 5, "tokens_in_window": 0,
         "status": "cut"},
        # due in window, streaming when the run was cut: not a failure
        {"id": "d", "in_window": True, "due": 138.0, "launched": 138.0,
         "t_first": 138.25, "t_last": 144.0, "tokens": 40, "max_tokens": 99,
         "tokens_in_window": 17, "status": "cut"},
    ]
    art = {"requests": reqs, "window": win, "cutoff_s": 5.0, "seconds": 40.0,
           "cell": {"chips": 1}}
    assert e2e.counts(art) == {"attempted": 3, "failed": 1, "sent": 4,
                               "finished_in_window": 2}
    m = e2e.metrics(art)
    assert sorted(round(t, 6) for t in e2e.ttfts(art)) == [0.25, 0.5, 6.0]
    assert round(m["ttft_p50_ms"], 6) == 500.0
    assert round(m["ttft_p95_ms"], 6) == 6000.0
    assert sorted(round(t, 6) for t in e2e.tpots(art)) == [0.1, 0.1]
    assert m["out_tok_s"] == (11 + 52 + 17) / 40.0
    assert e2e.percentile(list(range(1, 101)), 95) == 95
