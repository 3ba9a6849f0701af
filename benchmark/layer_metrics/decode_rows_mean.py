"""Scheduler: mean live rows per decode or mixed dispatch in the window,
from the flight recorder's per-step digests."""


def read(art):
    rows = [d["rows"] for d in art["digests"] if d["kind"] in ("decode", "mixed")]
    return sum(rows) / len(rows) if rows else None
