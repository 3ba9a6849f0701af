"""End-to-end metrics from the load generator's request log: what a
client saw, on the client's clock. The rules, the same in every cell:

attempted  requests DUE inside the window (open loop) or started inside
           it (closed loop).
failed     of those: an HTTP error or refusal, a stream that broke, or
           no first token by `cutoff_s` after the window closed.
ttft       first streamed token - the time the request was due (not the
           time it was sent: a late generator is the server's queue as
           far as the user can tell), over attempted requests; a failed
           one counts at the time the run gave up on it.
tpot       per request, (last token - first token) / (tokens - 1), over
           requests that FINISHED inside the window with >= 2 tokens,
           whenever they started. Not a per-token gap: the engine
           delivers tokens in bursts of `decode_steps`.
out_tok_s  output tokens that reached a client inside the window, from
           any request, per second of window, per chip.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) — every value it returns was
    measured."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def _attempted(art: dict) -> list[dict]:
    return [r for r in art["requests"] if r.get("in_window")]


def _failed(r: dict) -> bool:
    return "t_first" not in r or r["status"] not in ("ok", "cut")


def counts(art: dict) -> dict:
    att = _attempted(art)
    return {"attempted": len(att), "failed": sum(map(_failed, att)),
            "sent": len(art["requests"]),
            "finished_in_window": len(_finished(art))}


def _finished(art: dict) -> list[dict]:
    lo, hi = art["window"]
    return [r for r in art["requests"]
            if r["status"] == "ok" and r["tokens"] >= 2
            and r["tokens"] == r["max_tokens"] and lo <= r["t_last"] < hi]


def ttfts(art: dict) -> list[float]:
    gave_up = art["window"][1] + art["cutoff_s"]
    return [(r["t_first"] if not _failed(r) else gave_up) - r["due"]
            for r in _attempted(art)]


def tpots(art: dict) -> list[float]:
    return [(r["t_last"] - r["t_first"]) / (r["tokens"] - 1)
            for r in _finished(art)]


def metrics(art: dict) -> dict:
    chips = art.get("cell", {}).get("chips", 1)
    ttft, tpot = ttfts(art), tpots(art)
    return {
        "ttft_p50_ms": percentile(ttft, 50) * 1e3,
        "ttft_p95_ms": percentile(ttft, 95) * 1e3,
        "tpot_p95_ms": percentile(tpot, 95) * 1e3,
        "out_tok_s": sum(r["tokens_in_window"] for r in art["requests"])
        / art["seconds"] / chips,
    }
