"""The host's wall clock in a run with no capture: what the program's
always-on phase clock (`utils/tracing.py: phase`, since PR 38) leaves on
the flight digests and in `compile_stats()`, read back by tick and by
stretch of set-up. Every function returns None where its column or key is
absent (a program from before PR 38).

A tick is the stretch between the ends of two landings on the engine
loop's thread (`tick_s` on a `sync` / `overlap` digest; 0, and skipped
here, on the first landing and on the first after the loop sat idle).
Its parts, by thread:

    fetch     the landing's `wall_s`: the loop awaiting the device's tokens
    dispatch  the larger of `lock_s + upload_s + enqueue_s` over the
              dispatch rows booked since the landing before (a worker
              thread inside its dispatch) and the landing's `join_s` (the
              loop awaiting that worker)
    host      the loop's own work: `build_s` of those dispatch rows +
              `emit_s` + `admit_s` + `gc_s` of the landing
    unphased  the landing's `unphased_s`: the loop's thread in no `eng.*`
              phase (the event loop elsewhere, or the process not running)

Threads overlap (the fetch runs beside the next dispatch), so the parts do
not sum to the tick."""

from __future__ import annotations

import statistics

DISPATCH = ("prefill", "decode", "mixed", "spec_verify")


def ticks(art) -> list[dict] | None:
    """The window's ticks, oldest first: {"tick", "fetch", "dispatch", "host",
    "unphased"}, in seconds."""
    out, worker, build = [], 0.0, 0.0
    for d in art["digests"]:
        if d["kind"] in DISPATCH:
            if "lock_s" not in d:
                return None
            worker = max(worker, d["lock_s"] + d["upload_s"] + d["enqueue_s"])
            build += d["build_s"]
        elif d["kind"] in ("sync", "overlap"):
            if "tick_s" not in d:
                return None
            if d["tick_s"] > 0:
                out.append({
                    "tick": d["tick_s"], "fetch": d["wall_s"],
                    "dispatch": max(worker, d["join_s"]),
                    "host": build + d["emit_s"] + d["admit_s"] + d["gc_s"],
                    "unphased": d["unphased_s"]})
            worker, build = 0.0, 0.0
    return out or None


def tick_ms(art, stat) -> float | None:
    """`stat` (a function of a list) of the ticks' lengths, in ms."""
    rows = ticks(art)
    return None if rows is None else stat([r["tick"] for r in rows]) * 1e3


def stall_s(art) -> float | None:
    """Seconds of the window lost to stops: over the ticks longer than
    twice the median tick, what each took beyond the median."""
    rows = ticks(art)
    if rows is None:
        return None
    lengths = [r["tick"] for r in rows]
    median = statistics.median(lengths)
    return sum(t - median for t in lengths if t > 2 * median)


def longest_excess_ms(art, part: str) -> float | None:
    """`part` of the longest tick less the median of that part over the
    window's ticks, in ms: ~0 in a steady run, the stop's length in the
    part that held it."""
    rows = ticks(art)
    if rows is None:
        return None
    longest = max(rows, key=lambda r: r["tick"])
    return (longest[part] - statistics.median(r[part] for r in rows)) * 1e3


def setup_marks(art) -> tuple[float, float, float, float] | None:
    """Set-up's marks on the window's clock (`time.monotonic`): process
    start, the `compile_stats()` snapshot after the engine build (`at_s`),
    the one after the probes, the window's opening."""
    stats = art["compile"]
    if "at_s" not in stats["build"] or "at_s" not in stats["probe"]:
        return None
    opened = art["window"][0]
    return (opened - art["setup_s"], stats["build"]["at_s"],
            stats["probe"]["at_s"], opened)


def setup_stat(art, *keys: str) -> float | None:
    """Sum of `keys` of the `compile_stats()` snapshot taken at the
    window's opening: cumulative from process start."""
    before = art["compile"]["before"]
    if any(k not in before for k in keys):
        return None
    return sum(before[k] for k in keys)
