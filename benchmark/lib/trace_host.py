"""Who owes the device's idle time, and which part of the model owns its
busy time: a second reader of the profiler trace, beside `trace_reduce`
(which the accepted metrics use and which this file does not replace).

`trace_reduce.load` drops every event's stats and keeps no host event
but the four dispatch annotations, so what the program writes since PR 24
(`dynamo_tpu/utils/tracing.py: phase`) needs a reader of its own:

  host phases   `eng.tick` and its children on the engine loop's thread
                (`eng.admit`, `eng.*.build`, `eng.fetch`, `eng.emit`,
                `eng.wait`), `eng.lock` / `eng.upload` / `eng.enqueue` /
                `eng.carry` on the dispatch workers' threads (inside the
                dispatch annotation), `fe.preprocess` / `fe.stream` on the
                loop's thread again (the HTTP frontend shares it);
  op scopes     `jax.named_scope` names (`attn.qkv`, `mlp.down`, ...) end
                up in each operation's HLO `op_name`. The xplane keeps it
                as the stat `tf_op` of the operation's EVENT METADATA,
                which `jax.profiler.ProfileData` does not show (it lists
                an event's own stats only), so `_op_scopes` reads that one
                table from the file's protobuf wire format directly.

The idle split (`split`). The traced slice is what `trace_reduce.reduce`
calls the window: first start to last end over every event of every
plane. Idle = window - union of the device's operation intervals. It is
divided BY OVERLAP IN TIME (not by the instant a gap starts) into

  in_programs   idle while a program (an `XLA Modules` event) executes:
                gaps between its operations, no business of the host;
  and the idle between programs, each stretch given to the first of
  enqueue       a thread is in `eng.lock|eng.upload|eng.enqueue|eng.carry`:
                a dispatch is under way and its next program (the step
                program, or one of the small eager operations around it:
                state flush, carry overrides and write-backs, each a
                launch of its own) was not yet queued;
  host          else a thread is in `eng.admit|eng.*.build|eng.emit`;
  frontend      else the loop's thread is in `fe.*`, or in no phase at all;
  dry           else (only `eng.fetch` or `eng.wait` is open on the loop's
                thread): nothing was queued behind the program that ended.

The five sum to the idle time exactly. One thing the recording cannot
hold: a phase that was open when the capture began (the profiler records
an annotation only if it opens during the capture). Two rules for that
head of the slice. Until the loop's thread opens its first recorded
phase, time with no phase open there is taken as `dry`: `eng.fetch` and
`eng.wait` are the only phases of that thread that last longer than a
few milliseconds. And a thread whose first recorded event is an
`eng.upload`, `eng.enqueue` or `eng.carry` with no dispatch annotation
before it was inside a dispatch when the capture began (a dispatch opens
its annotation and `eng.lock` first): the time up to that event counts
as `enqueue`. An `eng.upload` lasts most of a tick, so most captures
begin inside one.

Scopes of asynchronous copies. XLA's scheduler splits a copy or a slice
that feeds an operation into `*-start` / `*-done` and gives both the
metadata of the loop they sit in (`jit(_decode_multi)/while`), not of a
model scope; the time the core waits in a `*-done` is time the
operation that consumes it could not start. Where a `*-done` has no
scope of its own it takes the scope of the operation whose operand it
is (read from that operation's HLO text).

A table here is `trace_reduce`'s (`{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns, stats]]}]}]}`) with an operation's
scope as its stats (`{"scope": ...}`) and, for a whole xplane, `"span"`: the window over ALL events, of which the table
keeps only those it reads. `cut` makes the small recording under
`benchmark/tests/data/`; `trace_reduce.reduce` reads the same file.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import re

from trace_reduce import MODULES_LINE, OPS_LINE, STEP_KINDS, program_name

HOST_PLANE = "/host:CPU"
ENQUEUE = ("eng.lock", "eng.upload", "eng.enqueue", "eng.carry")
QUIET = ("eng.fetch", "eng.wait")
SCOPE = re.compile(r"^(attn\.\w+|mlp\.\w+|norm|head|sample)$")
_DONE = re.compile(r"%[\w.-]+-done[\w.]*")


def _is_host_work(name: str) -> bool:
    return name in ("eng.admit", "eng.emit") or (
        name.startswith("eng.") and name.endswith(".build"))


# --------------------------------------------------------------- intervals
# lists of disjoint [start, end) pairs, sorted


def union(pairs) -> list:
    out: list = []
    for a, b in sorted(pairs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(iv) -> int:
    return sum(b - a for a, b in iv)


def intersect(x, y) -> list:
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append([a, b])
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x, y) -> list:
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > a:
                out.append([a, y[k][0]])
            a = max(a, y[k][1])
            k += 1
        if a < b:
            out.append([a, b])
    return out


# ------------------------------------------------- the xplane's wire format
# XSpace{1: planes}; XPlane{2: name, 4: event_metadata<id, XEventMetadata>,
# 5: stat_metadata<id, XStatMetadata>}; XEventMetadata{2: name, 5: stats};
# XStat{1: metadata_id, 3: uint64, 4: int64, 5: str, 7: ref (a stat
# metadata id whose name is the value)}; XStatMetadata{2: name}


def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            step = 8 if wt == 1 else 4
            v = buf[i:i + step]
            i += step
        else:
            raise ValueError(f"wire type {wt}")
        yield key >> 3, v


def _map_entries(plane, field):
    for f, v in _fields(plane):
        if f == field:
            key = value = None
            for g, w in _fields(v):
                if g == 1:
                    key = w
                elif g == 2:
                    value = w
            if value is not None:
                yield key, value


def _op_scopes(path: str) -> dict:
    """{device plane: {(program id, operation's whole name): tf_op}} from
    the event metadata of each `/device:` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((bytes(v).decode() for g, v in _fields(plane) if g == 2),
                    "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for key, meta in _map_entries(plane, 5):
            stat_names[key] = next(
                (bytes(v).decode() for g, v in _fields(meta) if g == 2), "")
        ops = {}
        for _, meta in _map_entries(plane, 4):
            op_name, tf_op, program = "", None, 0
            for g, v in _fields(meta):
                if g == 2:
                    op_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    what = stat_names.get(stat.get(1))
                    if what == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
                    elif what == "program_id":
                        program = stat.get(3, stat.get(4, 0))
            if tf_op:
                ops[(program, op_name)] = tf_op
        out[name] = ops
    return out


def scope_of(tf_op: str) -> str:
    """`jit(_decode_multi)/while/body/attn.qkv/dot_general:` ->
    `attn.qkv`: the innermost model scope on the path, else ""."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if SCOPE.match(part):
            return part
    return ""


# -------------------------------------------------------------------- load


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """An xplane file (read once per process) or a recording made by
    `cut`, as a table."""
    if path.endswith((".json", ".json.gz")):
        with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    tf_ops = _op_scopes(path)
    lo, hi, planes = None, None, []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            keep = (line.name in (OPS_LINE, MODULES_LINE) if device
                    else plane.name == HOST_PLANE)
            events = []
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                name = ev.name
                if not keep:
                    continue
                # an operation's scope is settled below
                if device or name.startswith(("eng.", "fe.")) or (
                        name in STEP_KINDS):
                    events.append([name, s, d, {}])
            if events:
                lines.append({"name": line.name, "events": events})
        if device:
            _name_ops(lines, tf_ops.get(plane.name, {}))
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "span": [lo, hi]}


def _name_ops(lines: list, tf_ops: dict) -> None:
    """Give each operation its scope (by the program that contains the
    event: the same text can occur in two programs) and cut its name to
    its head, as `trace_reduce` does."""
    by = {ln["name"]: ln["events"] for ln in lines}
    fed = {}  # (program, head of a *-done) -> scope of what consumes it
    for (program, text), tf_op in tf_ops.items():
        scope = scope_of(tf_op)
        if scope and " = " in text:
            for done in _DONE.findall(text.split(" = ", 1)[1]):
                fed.setdefault((program, done), scope)
    mods = sorted((e[1], e[1] + e[2], e[0]) for e in by.get(MODULES_LINE, ()))
    ids = [int(m.group(1)) if (m := re.search(r"\((\d+)\)$", n)) else 0
           for *_, n in mods]
    i = 0
    for e in sorted(by.get(OPS_LINE, ()), key=lambda e: e[1]):
        while i < len(mods) and mods[i][1] <= e[1]:
            i += 1
        program = ids[i] if i < len(mods) and mods[i][0] <= e[1] else 0
        scope = scope_of(tf_ops.get((program, e[0]), ""))
        e[0] = e[0].split(" = ", 1)[0]
        scope = scope or fed.get((program, e[0]), "")
        if scope:
            e[3] = {"scope": scope}


def cut(table: dict, start_ms: float, length_ms: float) -> dict:
    """A small recording: the device's events that START inside the
    stretch (counted from the first device operation) and the host's
    phases that overlap it, clipped to it; times from 0. Both this file
    and `trace_reduce` read it."""
    first = min(e[1] for p in table["planes"] if p["name"].startswith(
        "/device:") for ln in p["lines"] if ln["name"] == OPS_LINE
        for e in ln["events"])
    t0 = first + int(start_ms * 1e6)
    t1 = t0 + int(length_ms * 1e6)
    planes = []
    for plane in table["planes"]:
        lines, host = [], plane["name"] == HOST_PLANE
        for ln in plane["lines"]:
            events = [[e[0], max(e[1], t0) - t0,
                       min(e[1] + e[2], t1) - max(e[1], t0), e[3]]
                      for e in ln["events"] if (
                          e[1] < t1 and e[1] + e[2] > t0 if host
                          else t0 <= e[1] < t1)]
            if events:
                lines.append({"name": ln["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def find(art: dict) -> str | None:
    """The xplane file the run behind `art` left in its work directory
    (the harness traces into `.bench_work/<cell>/trace`)."""
    if not art.get("trace"):
        return None  # this run traced nothing (or had no device plane)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    files = sorted(glob.glob(os.path.join(
        root, ".bench_work", art["cell"]["name"], "trace", "plugins",
        "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


# ------------------------------------------------------------------- split


def _window(table: dict) -> tuple:
    if table.get("span"):
        return tuple(table["span"])
    spans = [(e[1], e[1] + e[2]) for p in table["planes"]
             for ln in p["lines"] for e in ln["events"]]
    return min(s for s, _ in spans), max(e for _, e in spans)


def _host_lines(table: dict) -> list:
    return [ln["events"] for p in table["planes"] if p["name"] == HOST_PLANE
            for ln in p["lines"]]


def _dispatch_head(events: list) -> int | None:
    """Start of a thread's first recorded event if that shows a dispatch
    under way when the capture began (see the top of the file)."""
    first = min((e for e in events if e[0] in ENQUEUE or e[0] in STEP_KINDS),
                key=lambda e: e[1], default=None)
    return first[1] if first and first[0] in ENQUEUE[1:] else None


def split(table: dict) -> dict | None:
    """Seconds of the window, of device busy time and of each of the
    five kinds of idle time (see the top of the file); None without a
    device plane. Without any `eng.` phase in the table (a program from
    before PR 24) only `in_programs` and `between` are known."""
    devices = [{ln["name"]: ln["events"] for ln in p["lines"]}
               for p in table["planes"] if p["name"].startswith("/device:")
               and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if not devices:
        return None
    t0, t1 = _window(table)
    lines = _host_lines(table)
    named = [(e[0], e[1], e[1] + e[2]) for ln in lines for e in ln]
    enqueue = union([(a, b) for n, a, b in named if n in ENQUEUE]
                    + [(t0, a) for a in map(_dispatch_head, lines) if a])
    work = union((a, b) for n, a, b in named if _is_host_work(n))
    # the loop's thread: the line with most of the loop's own phases
    loop = max(lines, default=[], key=lambda ln: sum(
        e[0] in QUIET or _is_host_work(e[0]) for e in ln))
    on_loop = [(e[0], e[1], e[1] + e[2]) for e in loop]
    fe = union((a, b) for n, a, b in on_loop if n.startswith("fe."))
    quiet = union((a, b) for n, a, b in on_loop if n in QUIET)
    phases = [a for n, a, _ in on_loop
              if n.startswith(("eng.", "fe.")) and n != "eng.tick"]
    known_from = min(phases, default=t1)
    have_phases = any(n.startswith("eng.") for n, _, _ in named)

    acc = dict.fromkeys(
        ("busy", "in_programs", "enqueue", "host", "frontend", "dry"), 0)
    for dev in devices:
        busy = union((e[1], e[1] + e[2]) for e in dev[OPS_LINE])
        progs = union((e[1], e[1] + e[2]) for e in dev.get(MODULES_LINE, ()))
        acc["busy"] += total(busy)
        acc["in_programs"] += total(subtract(progs, busy))
        rest = subtract(subtract([[t0, t1]], progs), busy)
        for key, iv in (("enqueue", enqueue), ("host", work),
                        ("frontend", fe), ("dry", quiet)):
            acc[key] += total(intersect(rest, iv))
            rest = subtract(rest, iv)
        head = intersect(rest, [[t0, known_from]])
        acc["dry"] += total(head)
        acc["frontend"] += total(rest) - total(head)
    n = len(devices)
    out = {k: v / n / 1e9 for k, v in acc.items()}
    out["window"] = (t1 - t0) / 1e9
    out["between"] = sum(out[k] for k in ("enqueue", "host", "frontend",
                                           "dry"))
    if not have_phases:
        for k in ("enqueue", "host", "frontend", "dry"):
            out[k] = None
    return out


def idle_pct(art: dict, kind: str):
    """One of the five shares, in % of the traced slice: what the
    `idle_*_pct` metrics read."""
    path = find(art)
    parts = split(load(path)) if path else None
    if not parts or parts[kind] is None or not parts["window"]:
        return None
    return parts[kind] / parts["window"] * 100.0


# ------------------------------------------------------------------ scopes


def scope_times(table: dict) -> dict:
    """Device self time in seconds per program and model scope
    (`{program: {scope: s}}`, "" = no scope) plus `programs`: executions
    per program. Self time as in `trace_reduce`: an operation's duration
    less the operations nested in it; an asynchronous `*-done` counts
    under the scope its own metadata carries, which is the scope of the
    operation that issued the copy."""
    times: dict = {}
    runs: dict = {}
    for p in table["planes"]:
        by = {ln["name"]: ln["events"] for ln in p["lines"]}
        if not p["name"].startswith("/device:") or OPS_LINE not in by:
            continue
        mods = sorted((e[1], e[1] + e[2], program_name(e[0]))
                      for e in by.get(MODULES_LINE, ()))
        for *_, prog in mods:
            runs[prog] = runs.get(prog, 0) + 1
        i, stack = 0, []  # stack: [end, child_ns, dur, scope, program]

        def close(upto):
            while stack and stack[-1][0] <= upto:
                _, child, dur, scope, prog = stack.pop()
                t = times.setdefault(prog, {})
                t[scope] = t.get(scope, 0) + dur - child
        for e in sorted(by[OPS_LINE], key=lambda e: (e[1], -e[2])):
            s, d = e[1], e[2]
            close(s)
            while i < len(mods) and mods[i][1] <= s:
                i += 1
            prog = mods[i][2] if i < len(mods) and mods[i][0] <= s else ""
            if stack:
                stack[-1][1] += d
            stack.append([s + d, 0, d, e[3].get("scope", ""), prog])
        close(float("inf"))
    return {"programs": runs,
            "times": {prog: {k: v / 1e9 for k, v in t.items()}
                      for prog, t in times.items()}}


def scopes(art: dict) -> dict | None:
    path = find(art)
    return scope_times(load(path)) if path else None
