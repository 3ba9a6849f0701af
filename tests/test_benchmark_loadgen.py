"""The benchmark's load generator and its end-to-end arithmetic
(benchmark/lib/traffic.py, loadgen.py, e2e.py), held in tier-1: what a
mix's `schedule_seed` and the run's `--seed` each decide, bursts and
shared prefixes, the clip of every length distribution, an open loop
that launches on schedule whatever the server does, a closed loop that
waits for its own answers, a broken stream booked as failed, and the
metrics of a hand-made request log. Imports benchmark/lib by path and
edits nothing there."""

from __future__ import annotations

import asyncio
import contextlib
import json
import math
import os
import random
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(ROOT, "benchmark", "lib")


@pytest.fixture(scope="module")
def libs():
    sys.path.insert(0, LIB)
    try:
        import e2e
        import loadgen
        import traffic
        yield traffic, loadgen, e2e
    finally:
        sys.path.remove(LIB)


def _mix(loop="open", **over):
    mix = {"loop": loop, "schedule_seed": 23,
           "prompt_tokens": {"dist": "uniform", "min": 16, "max": 64},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12}}
    mix.update(over)
    return mix


# ----------------------------------------------------------- the schedule


def _open_multiset(traffic, mix):
    times, shapes = traffic.open_cycle(mix, 8.0, 10.0)
    assert times == sorted(times) and 0.0 <= times[0] and times[-1] < 10.0
    return Counter(zip(times, shapes))


def _closed_multiset(traffic, mix):
    return Counter(s for lst in traffic.closed_lists(mix, 6, 5) for s in lst)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_schedule_seed_fixes_the_multiset(libs, loop):
    traffic = libs[0]
    draw = _open_multiset if loop == "open" else _closed_multiset
    a = draw(traffic, _mix(loop))
    assert a == draw(traffic, _mix(loop))
    assert sum(a.values()) == (80 if loop == "open" else 30)
    shapes = [k[1] for k in a] if loop == "open" else list(a)
    assert all(16 <= s.prompt_tokens <= 64 and 4 <= s.output_tokens <= 12
               for s in shapes)
    assert a != draw(traffic, _mix(loop, schedule_seed=24))


def test_burst_adds_simultaneous_arrivals_to_the_base(libs):
    traffic = libs[0]
    base, _ = traffic.open_cycle(_mix(), 4.0, 10.0)
    burst = {"process": "poisson", "burst": {"every_s": 4.0, "size": 7}}
    times, shapes = traffic.open_cycle(_mix(arrivals=burst), 4.0, 10.0)
    # crests at every_s / 2, then every every_s inside the period
    assert Counter(times) - Counter(base) == Counter({2.0: 7, 6.0: 7})
    assert len(shapes) == len(times) == len(base) + 14
    with pytest.raises(ValueError, match="arrival process"):
        traffic.open_cycle(_mix(arrivals={"process": "gamma"}), 4.0, 10.0)


@pytest.mark.parametrize("groups", [3, 0])
def test_prefix_groups_share_their_leading_words(libs, groups):
    traffic = libs[0]
    share = {"prefix_groups": groups,
             "prefix_tokens": {"dist": "uniform", "min": 40, "max": 80}}
    lists = traffic.closed_lists(_mix("closed", sharing=share), 4, 8)
    shapes = [s for lst in lists for s in lst]
    words = [f"w{i}" for i in range(500)]
    text = [traffic.content_for(words, 9, f"t{i}", s, 2).split()
            for i, s in enumerate(shapes)]
    assert all(len(t) == s.prompt_tokens - 2 for t, s in zip(text, shapes))
    if not groups:
        assert all(s.prefix_group == -1 and s.prefix_tokens == 0
                   for s in shapes)
        # unique prompts: no two start alike
        assert len({tuple(t[:8]) for t in text}) == len(text)
        return
    assert {s.prefix_group for s in shapes} == set(range(groups))
    by_group: dict[int, list] = {}
    for t, s in zip(text, shapes):
        assert 40 <= s.prefix_tokens <= 80
        assert 16 <= s.prompt_tokens - s.prefix_tokens <= 64
        by_group.setdefault(s.prefix_group, []).append((t, s))
    heads = set()
    for members in by_group.values():
        n = members[0][1].prefix_tokens
        assert all(s.prefix_tokens == n for _, s in members)
        head = tuple(members[0][0][:n])
        assert all(tuple(t[:n]) == head for t, _ in members)
        # past the shared head every request is its own
        assert len({tuple(t[n:]) for t, _ in members}) == len(members)
        heads.add(head)
    assert len(heads) == groups


def test_run_seed_rotates_the_start_and_makes_the_words(libs):
    traffic = libs[0]
    assert traffic.rotation(7, 0) == 0
    starts = {traffic.rotation(seed, 100) for seed in range(50)}
    assert len(starts) > 25 and all(0 <= r < 100 for r in starts)
    assert traffic.rotation(2 ** 31 + 5, 100) == traffic.rotation(
        2 ** 31 + 5, 100)
    words = [f"w{i}" for i in range(500)]
    shape = traffic.Shape(60, 8, prefix_group=1, prefix_tokens=20)
    a = traffic.content_for(words, 1, "m3", shape, 4)
    assert a == traffic.content_for(words, 1, "m3", shape, 4)
    b = traffic.content_for(words, 2, "m3", shape, 4)
    # another run: new words, the shared head included
    assert a.split()[:20] != b.split()[:20] and a != b
    # same run, another request: same head, another body
    c = traffic.content_for(words, 1, "w3", shape, 4)
    assert a.split()[:20] == c.split()[:20] and a != c
    # a template longer than the prompt still leaves one word
    assert len(traffic.content_for(words, 1, "x", traffic.Shape(3, 1),
                                   8).split()) == 1


@pytest.mark.parametrize("spec, lo, hi", [
    (17, 17, 17),
    ({"dist": "fixed", "value": 9}, 9, 9),
    ({"dist": "uniform", "min": 5, "max": 11}, 5, 11),
    ({"dist": "loguniform", "min": 8, "max": 64}, 8, 64),
    ({"dist": "lognormal", "median": 100, "sigma": 2.0,
      "min": 32, "max": 256}, 32, 256),
])
def test_draw_length_stays_inside_its_clip(libs, spec, lo, hi):
    traffic = libs[0]
    rng = random.Random(3)
    got = [traffic.draw_length(spec, rng) for _ in range(2000)]
    assert all(isinstance(n, int) and lo <= n <= hi for n in got)
    if lo < hi:
        # the clip is reached, not merely respected
        assert min(got) == lo and max(got) == hi


def test_draw_length_refuses_an_unknown_distribution(libs):
    traffic = libs[0]
    with pytest.raises(ValueError, match="zipf"):
        traffic.draw_length({"dist": "zipf", "min": 1, "max": 2},
                            random.Random(0))


# ------------------------------------------------- the client, on a socket


def _chunk(piece: str) -> bytes:
    return b"data: " + json.dumps(
        {"choices": [{"delta": {"content": piece}}]}).encode() + b"\n\n"


@contextlib.asynccontextmanager
async def _client(libs, tmp_path, mix, handler):
    """A `loadgen.Client` pointed at a local server whose one route is
    `handler`."""
    from aiohttp import web

    app = web.Application()
    app.router.add_post("/v1/chat/completions", handler)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = runner.addresses[0][1]
    words = tmp_path / "words.json"
    words.write_text(json.dumps([f"w{i}" for i in range(200)]))
    plan = {"base_url": f"http://127.0.0.1:{port}", "model": "m",
            "mix": mix, "template_tokens": 2, "words_file": str(words)}
    try:
        async with libs[1].Client(plan) as client:
            yield client
    finally:
        await runner.cleanup()


async def _stream(request, pieces, gap_s=0.0):
    from aiohttp import web

    resp = web.StreamResponse(headers={"content-type": "text/event-stream"})
    await resp.prepare(request)
    for p in pieces:
        await resp.write(_chunk(p))
        if gap_s:
            await asyncio.sleep(gap_s)
    await resp.write(b"data: [DONE]\n\n")
    return resp


async def test_open_loop_launches_on_schedule_against_a_slow_server(
        libs, tmp_path):
    """The server answers nobody until EVERY arrival of the window is in:
    a generator that waited for an answer before its next launch would
    never get one."""
    _, _, e2e = libs
    mix = _mix(output_tokens=3, warmup_s=0.0, cutoff_s=1.0)
    n = 20
    arrived, everyone = [], asyncio.Event()

    async def handler(request):
        body = await request.json()
        arrived.append(request.headers["x-request-id"])
        assert body["stream"] and body["max_tokens"] == 3
        assert body["nvext"] == {"ignore_eos": True}
        if len(arrived) == n:
            everyone.set()
        await everyone.wait()
        return await _stream(request, ["a ", "b ", "c "])

    events = []
    async with _client(libs, tmp_path, mix, handler) as client:
        art = await client.load(
            {"cmd": "load", "rate_rps": float(n), "seconds": 1.0,
             "seed": 5}, events.append)
    reqs = art["requests"]
    assert len(reqs) == n and len(set(arrived)) == n
    assert [e["event"] for e in events] == ["window_open", "window_close"]
    lo, hi = art["window"]
    assert all(r["in_window"] and lo <= r["due"] < hi for r in reqs)
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    # launched when due, while nothing had completed
    assert max(r["launched"] - r["due"] for r in reqs) < 0.5
    assert all(r["status"] == "ok" and r["tokens"] == 3 for r in reqs)
    first_answer = min(r["t_first"] for r in reqs)
    assert all(r["launched"] <= first_answer for r in reqs)
    art["seconds"] = 1.0
    c = e2e.counts(art)
    assert (c["attempted"], c["failed"], c["sent"]) == (n, 0, n)


@pytest.mark.parametrize("fault, status", [
    ("refuse", "http_503"), ("break", "error"), ("silent", "no_tokens")])
async def test_a_request_that_went_wrong_is_failed_not_lost(
        libs, tmp_path, fault, status):
    """Every other request is refused, broken after its first token, or
    ended without one: each stays in the log and counts as failed."""
    from aiohttp import web

    _, _, e2e = libs
    mix = _mix(output_tokens=4, warmup_s=0.0, cutoff_s=0.5)
    seen = [0]

    async def handler(request):
        await request.read()
        seen[0] += 1
        if seen[0] % 2:
            return await _stream(request, ["a ", "b ", "c ", "d "])
        if fault == "refuse":
            return web.Response(status=503, text="overloaded")
        if fault == "silent":
            return await _stream(request, [])
        resp = web.StreamResponse()
        await resp.prepare(request)
        await resp.write(_chunk("a "))
        request.transport.close()
        return resp

    async with _client(libs, tmp_path, mix, handler) as client:
        art = await client.load(
            {"cmd": "load", "rate_rps": 12.0, "seconds": 1.0, "seed": 1},
            lambda _: None)
    art["seconds"] = 1.0
    got = Counter(r["status"] for r in art["requests"])
    assert got == Counter({"ok": 6, status: 6})
    c = e2e.counts(art)
    assert (c["attempted"], c["failed"], c["sent"]) == (12, 6, 12)
    bad = [r for r in art["requests"] if r["status"] == status]
    assert all(r["tokens"] == (1 if fault == "break" else 0) for r in bad)
    if fault != "silent":
        assert all(r["error"] for r in bad)
    # a failed request's TTFT is the moment the run gave up on it
    gave_up = art["window"][1] + art["cutoff_s"]
    assert sorted(e2e.ttfts(art))[-6:] == sorted(
        gave_up - r["due"] for r in bad)


async def test_closed_loop_sends_the_next_when_the_last_one_ended(
        libs, tmp_path):
    mix = _mix("closed", output_tokens=2, warmup_s=0.2, ramp_s=0.1,
               cutoff_s=0.3, requests_per_client=50)
    inflight, peak = [0], [0]

    async def handler(request):
        await request.read()
        inflight[0] += 1
        peak[0] = max(peak[0], inflight[0])
        try:
            return await _stream(request, ["a ", "b "], gap_s=0.02)
        finally:
            inflight[0] -= 1

    async with _client(libs, tmp_path, mix, handler) as client:
        art = await client.load(
            {"cmd": "load", "clients": 3, "seconds": 0.6, "seed": 4},
            lambda _: None)
    assert peak[0] == 3
    lo, hi = art["window"]
    by_client: dict[int, list] = {}
    for r in art["requests"]:
        by_client.setdefault(r["client"], []).append(r)
        assert r["due"] < hi and r["in_window"] == (lo <= r["due"] < hi)
    assert sorted(by_client) == [0, 1, 2]
    for recs in by_client.values():
        assert any(r["in_window"] for r in recs)
        assert any(not r["in_window"] for r in recs)  # the warm-up
        for prev, nxt in zip(recs, recs[1:]):
            assert prev["status"] == "ok" and nxt["due"] >= prev["t_last"]


# ------------------------------------------------ the metrics of a run


def _req(i, due, first=None, last=None, tokens=0, max_tokens=11,
         status="ok", in_window=True, in_win=None):
    r = {"id": f"r{i}", "in_window": in_window, "due": due,
         "launched": due + 0.001, "tokens": tokens,
         "max_tokens": max_tokens, "status": status,
         "tokens_in_window": tokens if in_win is None else in_win}
    if first is not None:
        r.update(t_first=first, t_last=last)
    return r


def test_metrics_of_a_hand_made_log(libs):
    e2e = libs[2]
    reqs = [
        _req(0, 101.0, 101.5, 102.5, tokens=11),            # tpot 0.1
        _req(1, 102.0, 102.25, 106.25, tokens=11),          # tpot 0.4
        # warm-up request that finished in the window: tpot yes, ttft no
        _req(2, 95.0, 95.5, 105.5, tokens=101, max_tokens=101,
             in_window=False, in_win=60),                   # tpot 0.1
        # cut while streaming: attempted, not failed, no tpot
        _req(3, 138.0, 139.0, 144.0, tokens=40, max_tokens=99,
             status="cut", in_win=9),
        # stopped short of max_tokens: no tpot
        _req(4, 110.0, 110.125, 111.0, tokens=5),
        # one token: no gap to take
        _req(5, 120.0, 120.75, 120.75, tokens=1, max_tokens=1),
        # finished after the window closed: no tpot
        _req(6, 139.5, 139.75, 141.0, tokens=11, in_win=3),
        _req(7, 130.0, status="http_429"),
    ]
    art = {"requests": reqs, "window": [100.0, 140.0], "cutoff_s": 5.0,
           "seconds": 40.0, "cell": {"chips": 2}}
    assert e2e.counts(art) == {"attempted": 7, "failed": 1, "sent": 8,
                               "finished_in_window": 3}
    assert sorted(round(t, 6) for t in e2e.tpots(art)) == [0.1, 0.1, 0.4]
    assert sorted(round(t, 6) for t in e2e.ttfts(art)) == [
        0.125, 0.25, 0.25, 0.5, 0.75, 1.0, 15.0]
    m = e2e.metrics(art)
    assert round(m["tpot_p95_ms"], 6) == 400.0
    assert round(m["ttft_p50_ms"], 6) == 500.0
    assert round(m["ttft_p95_ms"], 6) == 15000.0
    assert m["out_tok_s"] == (11 + 11 + 60 + 9 + 5 + 1 + 3) / 40.0 / 2
    assert e2e.percentile(list(range(1, 101)), 95) == 95
    assert e2e.percentile([4.0], 50) == 4.0


@pytest.mark.parametrize("case", ["empty", "all_failed"])
def test_metrics_of_a_run_that_served_nothing(libs, case):
    e2e = libs[2]
    reqs = [] if case == "empty" else [
        _req(0, 1.0, status="http_503"),
        _req(1, 2.0, status="cut"),                     # never answered
        _req(2, 3.0, 3.5, 3.5, tokens=1, status="error"),   # broke
    ]
    art = {"requests": reqs, "window": [0.0, 10.0], "cutoff_s": 5.0,
           "seconds": 10.0}
    n = len(reqs)
    assert e2e.counts(art) == {"attempted": n, "failed": n, "sent": n,
                               "finished_in_window": 0}
    assert e2e.tpots(art) == []
    assert e2e.ttfts(art) == [15.0 - r["due"] for r in reqs]
    m = e2e.metrics(art)
    assert math.isnan(m["tpot_p95_ms"])
    assert m["out_tok_s"] == (0.0 if case == "empty" else 0.1)
    if case == "empty":
        assert math.isnan(m["ttft_p50_ms"]) and math.isnan(m["ttft_p95_ms"])
    else:
        assert m["ttft_p50_ms"] == 13000.0 and m["ttft_p95_ms"] == 14000.0
