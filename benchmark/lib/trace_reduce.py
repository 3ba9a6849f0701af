"""From a profiler trace (`*.xplane.pb`) to the numbers the per-layer
metrics read. Two stages, so that the second can be checked on a small
recording kept under `benchmark/tests/data/`:

  load(path)      xplane -> a plain table {"planes": [{"name", "lines":
                  [{"name", "events": [[name, start_ns, dur_ns, stats]]}]}]}
                  (jax.profiler.ProfileData; nothing but jax needed)
  reduce(table)   table -> busy/idle, program executions, operations with
                  self time, idle gaps named by the host annotation open
                  at the time

What a TPU v5e trace looks like (looked at by hand, PR 23): one plane per
chip, `/device:TPU:<n>`, whose line `XLA Modules` has one event per
program execution (named `jit_<fn>(<fingerprint>)`), whose line `XLA Ops`
has one event per operation, nested where an operation (a `while`, the
`decode_steps` scan) contains others; `/host:CPU` has one line per
thread, where `engine/profiler.py`'s TraceAnnotations appear under their
names (`prefill`, `decode`, `mixed`, `spec_verify`) on the device trace's
clock. The CPU backend (rehearsals) writes no device plane, and `reduce`
then has nothing to read. The recording under `benchmark/tests/data/` is
`cut(load(<xplane.pb>), start_ms, length_ms)` written out as JSON.
"""

from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_KINDS = ("prefill", "decode", "mixed", "spec_verify")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def load(path: str, start_ms: float = 0.0, length_ms: float | None = None
         ) -> dict:
    """Read an xplane file (or a table written by `cut`) into the plain
    table. `start_ms`/`length_ms` keep only events that start inside that
    stretch, counted from the first device event."""
    if path.endswith((".json", ".json.gz")):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            table = json.load(f)
        return table if length_ms is None else cut(table, start_ms, length_ms)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                # an operation's name is its whole HLO line; its head
                # (`%fusion.12`) is all the reduction reads
                events.append([ev.name.split(" = ", 1)[0], int(ev.start_ns),
                               int(ev.duration_ns), {}])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    table = {"planes": planes}
    return table if length_ms is None else cut(table, start_ms, length_ms)


def cut(table: dict, start_ms: float, length_ms: float) -> dict:
    """A small recording: only what `reduce` reads (device operations and
    program executions, the engine's dispatch annotations on the host),
    only events that start inside the stretch, times from 0."""
    t0 = _first_device_ns(table) + int(start_ms * 1e6)
    t1 = t0 + int(length_ms * 1e6)
    planes = []
    for plane in table["planes"]:
        device = _is_device(plane)
        if not device and plane["name"] != "/host:CPU":
            continue
        lines = []
        for line in plane["lines"]:
            if device and line["name"] not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e[0], e[1] - t0, e[2], {}] for e in line["events"]
                      if t0 <= e[1] < t1 and (device or e[0] in STEP_KINDS)]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def _is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:") and any(
        ln["name"] == OPS_LINE for ln in plane["lines"])


def _device_lines(table: dict):
    """[(plane name, ops events, module events)] per device."""
    out = []
    for plane in table["planes"]:
        if _is_device(plane):
            by = {ln["name"]: sorted(ln["events"],
                                     key=lambda e: (e[1], -e[2]))
                  for ln in plane["lines"]}
            out.append((plane["name"], by[OPS_LINE], by.get(MODULES_LINE, [])))
    return out


def _first_device_ns(table: dict) -> int:
    starts = [ev[0][1] for _, ev, _ in _device_lines(table) if ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts)


def _union(events) -> list[list[int]]:
    """Merged [start, end) intervals of events sorted by start."""
    merged: list[list[int]] = []
    for _, s, d, _ in events:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return merged


def _self_times(events) -> dict[str, list]:
    """Per operation name: [count, total_ns, self_ns]. Self time is an
    event's duration less the events nested inside it (a `while` holding
    the decode scan's body would otherwise count its body twice)."""
    acc: dict[str, list] = defaultdict(lambda: [0, 0, 0])
    stack: list[list] = []  # [name, end, child_ns, dur_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, child, dur = stack.pop()
            acc[name][2] += dur - child
    for name, s, d, _ in events:
        close(s)
        if stack:
            stack[-1][2] += d
        acc[name][0] += 1
        acc[name][1] += d
        stack.append([name, s + d, 0, d])
    close(float("inf"))
    return acc


def program_name(event_name: str) -> str:
    """`jit_decode_fn(123456789)` -> `jit_decode_fn`."""
    return _FINGERPRINT.sub("", event_name)


def _host_steps(table: dict) -> list[tuple[int, int, str]]:
    """(start, end, kind) of the engine's dispatch annotations."""
    out = []
    for plane in table["planes"]:
        if plane["name"] != "/host:CPU":
            continue
        for ln in plane["lines"]:
            out += [(e[1], e[1] + e[2], e[0]) for e in ln["events"]
                    if e[0] in STEP_KINDS]
    return sorted(out)


def reduce(table: dict) -> dict:
    devices = _device_lines(table)
    if not devices:
        raise ValueError("the trace holds no device operation")
    # the traced window: first start to last end over host and device
    # events alike, so that a chip idle at either end still counts as idle
    spans = [(e[1], e[1] + e[2]) for plane in table["planes"]
             for ln in plane["lines"] for e in ln["events"]]
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    busy_ns, programs, ops = [], defaultdict(list), defaultdict(
        lambda: [0, 0, 0])
    ops_in: dict[str, dict] = defaultdict(lambda: defaultdict(lambda: [0, 0]))
    gaps = []
    steps = _host_steps(table)
    for _, op_events, mod_events in devices:
        busy = _union(op_events)
        busy_ns.append(sum(b - a for a, b in busy))
        for name, s, d, _ in mod_events:
            programs[program_name(name)].append(d)
        for name, (n, tot, own) in _self_times(op_events).items():
            ops[name][0] += n
            ops[name][1] += tot
            ops[name][2] += own
        # self time of each operation inside each program
        mods = sorted((s, s + d, program_name(n)) for n, s, d, _ in mod_events)
        if mods:
            i = 0
            per_prog_events: dict[str, list] = defaultdict(list)
            for ev in op_events:
                while i < len(mods) and mods[i][1] <= ev[1]:
                    i += 1
                if i < len(mods) and mods[i][0] <= ev[1]:
                    per_prog_events[mods[i][2]].append(ev)
            for prog, evs in per_prog_events.items():
                for name, (n, _, own) in _self_times(evs).items():
                    ops_in[prog][name][0] += n
                    ops_in[prog][name][1] += own
        for (a0, a1), (b0, _) in zip(busy, busy[1:]):
            gaps.append((b0 - a1, a1))
    gaps.sort(reverse=True)
    named = []
    for dur, at in gaps[:10]:
        # the dispatch annotation open when the gap began, else "none"
        kind = next((k for s, e, k in steps if s <= at < e), "none")
        named.append([kind, dur / 1e9])
    n_dev = len(devices)
    return {
        "devices": [name for name, _, _ in devices],
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "programs": {
            name: {"count": len(durs), "total_s": sum(durs) / 1e9,
                   "median_s": sorted(durs)[len(durs) // 2] / 1e9}
            for name, durs in programs.items()
        },
        "ops": {name: {"count": n, "total_s": tot / 1e9, "self_s": own / 1e9}
                for name, (n, tot, own) in ops.items()},
        "ops_in_program": {
            prog: {name: {"count": n, "self_s": own / 1e9}
                   for name, (n, own) in table_.items()}
            for prog, table_ in ops_in.items()
        },
        "idle_gaps": named,
        "host_steps": {k: sum(1 for *_, kk in steps if kk == k)
                       for k in STEP_KINDS},
    }


def op_family(event_name: str) -> str:
    """`%fused_paged_decode_attention.17 = (...) custom-call(...)` ->
    `fused_paged_decode_attention`: XLA numbers every layer's copy of an
    operation, so times are summed over the name without its numbers."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", re.sub(r"\.\d+", "", head)) or head


def family_times(ops: dict) -> dict[str, float]:
    """Self seconds per operation family, over a reduced `ops` table."""
    out: dict[str, float] = defaultdict(float)
    for name, v in ops.items():
        out[op_family(name)] += v["self_s"]
    return dict(out)


def breakdown(reduced: dict) -> dict:
    """The final line's optional `breakdown`: the ten operation families
    with most device self time, and the ten longest idle gaps by the host
    annotation open when each began."""
    top = sorted(family_times(reduced["ops"]).items(),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[name, s] for name, s in top],
            "idle_gaps": reduced["idle_gaps"]}
