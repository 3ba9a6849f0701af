"""The one traffic generator: a mix's data file + a cell's parameters +
the run's seed -> the requests of a run.

A traffic mix (`benchmark/traffic/<mix>.json`) is data: loop kind, length
distributions, arrivals, bursts, sharing. A cell (`benchmark/cells/
<cell>.json`) adds the load it offers: `rate_rps` (open loop) or
`clients` (closed loop). No mix needs code of its own.

Steadiness: the SHAPE of the work (lengths, arrival gaps, bursts, which
prompts share a prefix) is drawn once from the mix's `schedule_seed`, so
every run of a cell offers the same multiset of requests. The run's
`--seed` only (a) rotates where in that fixed cycle the window starts and
(b) makes the words of every prompt, so prompts are new in every run and
unique within it. An open-loop cycle is exactly as long as the measured
window, so the window always holds one whole cycle: every arrival of the
cycle once, in cyclic order, from a seed-chosen starting point.

Imports nothing but the standard library: the load generator's process
must stay off jax.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass

_MASK = (1 << 63) - 1


def _rng(*parts) -> random.Random:
    """A generator keyed by its parts (ints and strings), stable across
    processes (no `hash()`)."""
    h = 1469598103934665603
    for p in parts:
        for b in str(p).encode() + b"\x00":
            h = ((h ^ b) * 1099511628211) & _MASK
    return random.Random(h)


def draw_length(spec: dict | int, rng: random.Random) -> int:
    """One length from a distribution spec:
    {"dist": "fixed", "value": n} | {"dist": "uniform", "min", "max"} |
    {"dist": "loguniform", "min", "max"} |
    {"dist": "lognormal", "median", "sigma", "min", "max"}; an int is
    a fixed length."""
    if isinstance(spec, int):
        return spec
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if kind == "uniform":
        return rng.randint(lo, hi)
    if kind == "loguniform":
        x = math.exp(rng.uniform(math.log(lo), math.log(hi)))
    elif kind == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return max(lo, min(hi, int(round(x))))


@dataclass(frozen=True)
class Shape:
    """One request of the cycle, before the run's seed gives it words."""

    prompt_tokens: int      # whole prompt as the engine counts it
    output_tokens: int
    prefix_group: int = -1  # >= 0: shares that group's leading words
    prefix_tokens: int = 0


def _shapes(mix: dict, n: int, stream: str) -> list[Shape]:
    rng = _rng(mix["schedule_seed"], "shapes", stream)
    share = mix.get("sharing") or {}
    groups = int(share.get("prefix_groups", 0))
    plens = []
    if groups:
        prng = _rng(mix["schedule_seed"], "prefixes")
        plens = [draw_length(share["prefix_tokens"], prng)
                 for _ in range(groups)]
    out = []
    for _ in range(n):
        p = draw_length(mix["prompt_tokens"], rng)
        o = draw_length(mix["output_tokens"], rng)
        g = rng.randrange(groups) if groups else -1
        out.append(Shape(p + (plens[g] if groups else 0), o, g,
                         plens[g] if groups else 0))
    return out


def open_cycle(mix: dict, rate_rps: float, period_s: float):
    """The fixed cycle of an open-loop mix: sorted arrival offsets in
    [0, period_s) and one Shape each. Base arrivals are a Poisson process
    conditioned on its count (`round(rate * period)` uniform order
    statistics); `arrivals.burst` = {"every_s", "size"} adds `size`
    simultaneous arrivals every `every_s` on top of the base."""
    arr = mix.get("arrivals") or {"process": "poisson"}
    if arr.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rng = _rng(mix["schedule_seed"], "arrivals", rate_rps, period_s)
    times = [rng.uniform(0.0, period_s)
             for _ in range(int(round(rate_rps * period_s)))]
    burst = arr.get("burst")
    if burst:
        t = burst["every_s"] / 2.0
        while t < period_s:
            times += [t] * int(burst["size"])
            t += burst["every_s"]
    times.sort()
    return times, _shapes(mix, len(times), f"open:{rate_rps}:{period_s}")


def closed_lists(mix: dict, clients: int, per_client: int):
    """The fixed request lists of a closed-loop mix: `clients` lists of
    `per_client` Shapes, more than any client sends in a run."""
    return [_shapes(mix, per_client, f"closed:{c}") for c in range(clients)]


def rotation(seed: int, n: int) -> int:
    """Where in a cycle of n the run with this seed starts."""
    return _rng(seed, "rotation").randrange(n) if n else 0


def content_for(words: list[str], seed: int, tag, shape: Shape,
                template_tokens: int) -> str:
    """The user message of one request: `shape.prompt_tokens` less the
    template's tokens, as words. Requests of one prefix group start with
    the same words (the same in every request of the run, new in every
    run); the rest is unique to (seed, tag)."""
    n = max(shape.prompt_tokens - template_tokens, 1)
    head: list[str] = []
    if shape.prefix_group >= 0:
        prng = _rng(seed, "prefix", shape.prefix_group)
        head = [prng.choice(words)
                for _ in range(min(shape.prefix_tokens, n - 1))]
    rng = _rng(seed, "content", tag)
    body = [rng.choice(words) for _ in range(n - len(head))]
    return " ".join(head + body)
