"""Request-scoped tracing: spans, lifecycle events, Perfetto export.

The reference treats observability as a first-class plane — tracing init
(reference: lib/runtime/src/logging.rs:62-130 layers a tracing subscriber
under every component) and per-request distributed context. This module is
the TPU port's equivalent: a dependency-free span recorder that answers
"what happened to THIS request" and "what ran in THIS engine step", the two
questions the cumulative counters (`Engine.metrics()`, `phase_stats`,
`ServiceMetrics`) cannot.

Design:

- **Off by default, near-zero when off.** `DYN_TRACE=1` (or a runtime
  `enable()`) arms recording; every public helper first checks one module
  bool, and `span()` returns a shared no-op context manager when disarmed,
  so the hot paths pay a single attribute load + compare per call site.
  The one thing that is always on is `phase`'s host clock: two
  `perf_counter` reads and an add into the thread's own table per phase
  (`phase_totals()`), which is what a run with no capture and no ring
  keeps of where its host time went.
- **Ring-buffered.** Completed events land in a bounded deque
  (`DYN_TRACE_BUFFER` events, default 65536, newest win) — tracing a
  long-running server can never grow without limit. `deque.append` is
  atomic, so worker threads (prefill/decode dispatch threads) record
  without a lock on the hot path.
- **Contextvar request propagation.** The HTTP frontend binds the request
  id (`set_request`) for the duration of the handler; spans recorded
  downstream in the same task tree (preprocessor, router) inherit it, and
  `utils.logging.JsonlFormatter` stamps it on every log record so JSONL
  logs join against spans. The engine loop is a *separate* task — engine
  call sites pass the id explicitly (`req=seq.ctx.id`).
- **Chrome trace-event export.** `export()` returns the
  ``{"traceEvents": [...]}`` JSON object chrome://tracing and
  https://ui.perfetto.dev load directly: spans are complete ``"X"`` events
  (matched by construction — no dangling B/E), point events are instants
  (``"i"``), and per-track ``"M"`` thread_name metadata names the rows.
  Events are sorted so ``ts`` is monotonic. Tracks: one row per request id
  plus named engine rows (e.g. ``engine.steps`` for the dispatch
  timeline).
- **Cross-process merge (the fleet plane).** Each process carries a
  label (`set_process`, default from ``DYN_TRACE_PROCESS`` or
  ``proc-<pid>``). `wire_events()` snapshots the ring in a
  process-independent wire form (track NAMES instead of local tids,
  absolute unix-epoch timestamps instead of the local perf_counter
  epoch); `ingest()` on the receiving side rebases those stamps into its
  own clock domain and stores them as *foreign* events. `export()` then
  renders ONE merged trace: the local process is pid 0, every ingested
  process gets its own pid + ``process_name`` metadata, and every
  (process, track) pair its own named row — a request that crossed
  frontend → router → worker reads as parallel tracks of one timeline.
  `add_sink()` registers a callable fed each completed wire event, the
  hook the span shipper (`runtime/trace_plane.py`) uses to forward
  worker-side spans over the hub without scanning the ring.

See docs/observability.md for the trace model and a Perfetto walkthrough.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Callable, Iterator, Optional

__all__ = [
    "enabled",
    "enable",
    "disable",
    "clear",
    "set_request",
    "reset_request",
    "current_request",
    "request_scope",
    "set_process",
    "set_process_default",
    "process_label",
    "make_traceparent",
    "parse_traceparent",
    "add_sink",
    "remove_sink",
    "wire_events",
    "ingest",
    "span",
    "instant",
    "complete",
    "phase",
    "phase_table",
    "phase_totals",
    "export",
    "dump",
]

_DEFAULT_BUFFER = 65536

_enabled: bool = os.environ.get("DYN_TRACE", "") not in ("", "0")
_events: deque = deque(
    maxlen=int(os.environ.get("DYN_TRACE_BUFFER", str(_DEFAULT_BUFFER)))
)
# perf_counter epoch: every ts is microseconds since module import, so
# exported timestamps are small, positive and comparable across threads.
# _T0_UNIX is the SAME instant on the wall clock — the bridge that lets
# wire_events/ingest rebase timestamps between processes (NTP-class skew
# between hosts is the error bar; export() sorts, so the merged trace
# stays monotonic regardless).
_T0 = time.perf_counter()
_T0_UNIX = time.time()

# active request id for this task tree (None outside a request)
_request_var: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "dyn_trace_request", default=None
)

# track name -> tid; Perfetto renders one row per (pid, tid). BOUNDED like
# the event ring: a long-running server sees a new request id per request,
# and an ever-growing name map would leak RSS and bloat every export's
# metadata block long after the ring evicted the events. Past the cap the
# oldest name is dropped (its ring events keep their numeric tid, they
# just lose the pretty row label); tids come from a counter so a reused
# name can never collide with a live one. Names registered via an
# explicit `track=` (the handful of static engine rows) are PINNED —
# insertion-order eviction would otherwise throw out exactly those
# oldest-registered hot rows first and fragment the step timeline across
# fresh tids every _TRACKS_MAX requests.
_TRACKS_MAX = 4096
_tracks: dict[str, int] = {}
_pinned: set = set()
_next_tid = 0
_tracks_lock = threading.Lock()

# process identity for the cross-process merge: the local process label
# (None until set; resolved lazily so an engine/run-mode can claim it
# first), plus the foreign-event store — events ingested from OTHER
# processes, kept in their own bounded ring with per-(process, track)
# tid assignment at export time. Local events stay pid 0; each foreign
# process gets a fresh pid in ingestion order. Both registries are
# BOUNDED like the local track table: a frontend that outlives weeks of
# worker churn (every restart mints a new worker-<...> label) must not
# leak registry entries, or emit metadata for processes whose events
# the ring expired long ago. Past the caps the oldest entries drop —
# their surviving events keep numeric pids/tids, they just lose the
# pretty labels; ids come from counters so reuse can never collide.
_FOREIGN_PIDS_MAX = 256
_process: Optional[str] = os.environ.get("DYN_TRACE_PROCESS") or None
_foreign: deque = deque(maxlen=_events.maxlen)
_foreign_pids: dict[str, int] = {}
_foreign_tracks: dict[tuple, int] = {}  # (process, track) -> tid
_next_fpid = 0

# span-export sinks: callables fed each completed wire event (dict with
# a track NAME and absolute unix-us ts — process-independent). Only
# consulted when recording is armed; with no sinks the hot path pays one
# falsy check.
_sinks: list = []

_NOOP_CM = contextlib.nullcontext()


def enabled() -> bool:
    return _enabled


def enable(buffer: Optional[int] = None) -> None:
    """Arm recording (idempotent). `buffer` resizes the ring (and clears
    it — a resize cannot preserve a deque's maxlen)."""
    global _enabled, _events
    if buffer is not None and buffer != _events.maxlen:
        _events = deque(maxlen=buffer)
    _enabled = True


def disable() -> None:
    """Disarm recording; the buffer keeps already-recorded events."""
    global _enabled
    _enabled = False


def clear() -> None:
    _events.clear()
    _foreign.clear()
    with _tracks_lock:
        _tracks.clear()
        _pinned.clear()
        _foreign_pids.clear()
        _foreign_tracks.clear()


# ------------------------------------------------------------------ context


def set_request(request_id: Optional[str]):
    """Bind the active request id for this task tree; returns a token for
    `reset_request`. Cheap enough to run unconditionally (the JSONL log
    join uses it even when span recording is off)."""
    return _request_var.set(request_id)


def reset_request(token) -> None:
    _request_var.reset(token)


def current_request() -> Optional[str]:
    return _request_var.get()


@contextlib.contextmanager
def request_scope(request_id: Optional[str]) -> Iterator[None]:
    token = _request_var.set(request_id)
    try:
        yield
    finally:
        _request_var.reset(token)


# ------------------------------------------------------- process identity


def set_process(name: Optional[str]) -> None:
    """Label THIS process for merged exports (worker id, "frontend", …).
    Unconditional; pass None to unset (tests). Run modes and engines
    should use `set_process_default` so an explicit label — including
    ``DYN_TRACE_PROCESS`` — is never clobbered."""
    global _process
    _process = name


def set_process_default(name: str) -> None:
    """Claim the process label only if nothing has set one yet (env var
    or an earlier caller wins) — the first-wins entry point for run
    modes and engine init."""
    global _process
    if _process is None:
        _process = name


def process_label() -> str:
    """The local process label, defaulting to ``proc-<pid>``."""
    return _process or f"proc-{os.getpid()}"


def make_traceparent(request_id: str) -> str:
    """Mint a traceparent for an outbound hop: W3C-shaped
    ``00-<request_id>-<parent_span_hex16>-01``. The request id doubles as
    the trace id (it already joins spans, logs and headers everywhere);
    the span id names this hop so the receiver can record which caller
    handed it the request."""
    return f"00-{request_id}-{uuid.uuid4().hex[:16]}-01"


def parse_traceparent(tp: str) -> tuple[Optional[str], Optional[str]]:
    """(request_id, parent_span_id) from a traceparent string; (None,
    None) when malformed. Request ids may contain dashes (forked
    contexts), so the span id is taken from the fixed tail."""
    parts = tp.split("-")
    if len(parts) < 4:
        return None, None
    return "-".join(parts[1:-2]) or None, parts[-2] or None


# ------------------------------------------------------------------- sinks


def add_sink(fn: Callable[[dict], None]) -> None:
    """Register a span-export sink: called inline with each completed
    WIRE event (see `wire_events` for the shape) while recording is
    armed. Sinks must be cheap and non-blocking — buffer and flush
    elsewhere (runtime/trace_plane.SpanShipper)."""
    if fn not in _sinks:
        _sinks.append(fn)


def remove_sink(fn: Callable[[dict], None]) -> None:
    with contextlib.suppress(ValueError):
        _sinks.remove(fn)


def _wire(ev: dict, tname: str) -> dict:
    """Local ring event -> process-independent wire form: the track NAME
    instead of the local tid, absolute unix-epoch microseconds instead
    of the local perf_counter epoch."""
    w = {
        "name": ev["name"],
        "ph": ev["ph"],
        "ts_unix_us": round(ev["ts"] + _T0_UNIX * 1e6, 1),
        "cat": ev["cat"],
        "track": tname,
        "args": ev["args"],
    }
    if "dur" in ev:
        w["dur"] = ev["dur"]
    return w


def _feed_sinks(ev: dict, tname: str) -> None:
    w = _wire(ev, tname)
    for fn in _sinks:
        try:
            fn(w)
        except Exception:  # noqa: BLE001 — a broken sink must not take
            pass           # down the traced code path


# ---------------------------------------------------------------- recording


def _track_name(track: Optional[str], req: Optional[str]) -> str:
    return track or req or _request_var.get() or "main"


def _tid_for(name: str, pin: bool) -> int:
    global _next_tid
    tid = _tracks.get(name)
    if tid is None:
        with _tracks_lock:
            tid = _tracks.get(name)
            if tid is None:
                while len(_tracks) >= _TRACKS_MAX:
                    victim = next(
                        (n for n in _tracks if n not in _pinned), None
                    )
                    if victim is None:
                        break  # everything pinned; let the map grow
                    _tracks.pop(victim)
                _next_tid += 1
                tid = _tracks[name] = _next_tid
                if pin:
                    _pinned.add(name)
    return tid


def _tid(track: Optional[str], req: Optional[str]) -> int:
    return _tid_for(_track_name(track, req), track is not None)


def _us(t: float) -> float:
    return round((t - _T0) * 1e6, 1)


def complete(
    name: str,
    t0: float,
    t1: float,
    cat: str = "",
    req: Optional[str] = None,
    track: Optional[str] = None,
    **args,
) -> None:
    """Record a complete ("X") event from two `time.perf_counter` stamps —
    the shape the engine's dispatch sites use (they already hold t0/t1 for
    the phase counters)."""
    if not _enabled:
        return
    if req is None and track is None:
        req = _request_var.get()
    if req is not None:
        args.setdefault("request_id", req)
    tname = _track_name(track, req)
    ev = {
        "name": name,
        "ph": "X",
        "ts": _us(t0),
        "dur": max(round((t1 - t0) * 1e6, 1), 0.0),
        "pid": 0,
        "tid": _tid_for(tname, track is not None),
        "cat": cat or "span",
        "args": args,
    }
    _events.append(ev)
    if _sinks:
        _feed_sinks(ev, tname)


def instant(
    name: str,
    cat: str = "",
    req: Optional[str] = None,
    track: Optional[str] = None,
    ts: Optional[float] = None,
    **args,
) -> None:
    """Record a point-in-time ("i") event, e.g. a sequence lifecycle edge.
    `ts` is an optional perf_counter stamp (default: now)."""
    if not _enabled:
        return
    if req is None and track is None:
        req = _request_var.get()
    if req is not None:
        args.setdefault("request_id", req)
    tname = _track_name(track, req)
    ev = {
        "name": name,
        "ph": "i",
        "s": "t",
        "ts": _us(ts if ts is not None else time.perf_counter()),
        "pid": 0,
        "tid": _tid_for(tname, track is not None),
        "cat": cat or "event",
        "args": args,
    }
    _events.append(ev)
    if _sinks:
        _feed_sinks(ev, tname)


def span(
    name: str,
    cat: str = "",
    req: Optional[str] = None,
    track: Optional[str] = None,
    **args,
):
    """Context manager recording a complete event around its body. When
    recording is off this returns a shared no-op context manager (no
    allocation, no perf_counter call)."""
    if not _enabled:
        return _NOOP_CM
    return _Span(name, cat, req, track, args)


# the device trace's clock: `jax.profiler.TraceAnnotation`, set by
# engine/profiler.py when it is imported (this module stays off jax);
# None = no profiler in this process
annotation = None

# the host's clock, always on: every thread sums the seconds and the
# count of each phase it closes in a table of its own (no lock on the
# hot path; `phase_totals` adds the tables up). A thread that has ended
# leaves its table to `_phase_retired`, so a server that turns its
# worker threads over keeps the totals and not the tables.
_phase_local = threading.local()
_phase_tables: list = []  # (weakref to the thread, its table)
_phase_retired: dict = {}


def phase_table() -> dict:
    """The calling thread's live `{name: [seconds, count]}`: what a
    caller on that thread reads twice to get its own growth over a
    stretch (the engine's digest columns `lock_s` ... `unphased_s`)."""
    table = getattr(_phase_local, "table", None)
    if table is None:
        table = _phase_local.table = {}
        with _tracks_lock:
            _phase_tables.append(
                (weakref.ref(threading.current_thread()), table))
    return table


def phase_totals() -> dict:
    """`{name: [seconds, count]}` of every phase closed since the
    process began, over all threads, whether or not a capture or the
    ring was on. A phase that spans an await holds whatever its thread's
    event loop ran meanwhile; a phase still open is not in it yet."""
    with _tracks_lock:
        live = []
        for ref, table in _phase_tables:
            thread = ref()
            if thread is not None and thread.is_alive():
                live.append((ref, table))
            else:
                _add_phases(_phase_retired, table)
        _phase_tables[:] = live
        out = {name: list(cell) for name, cell in _phase_retired.items()}
    for _, table in live:
        # copy(): one C call, safe beside the owner's inserts
        _add_phases(out, table.copy())
    return out


def _add_phases(into: dict, table: dict) -> None:
    for name, (seconds, count) in table.items():
        cell = into.setdefault(name, [0.0, 0])
        cell[0] += seconds
        cell[1] += count


class phase:
    """The one way to mark a host phase, on three clocks: the host's
    (always: the elapsed seconds and one count go to the thread's table,
    `phase_totals`), an annotation named `name` carrying `attrs` on the
    device trace (a no-op of well under a microsecond while no capture
    runs) and a complete event on this ring when it is armed (the
    request's track inside a request, else ``engine.phases``). `set()`
    adds attributes found inside the body; they reach the ring only (the
    annotation's are fixed when it opens). Names:
    docs/observability.md."""

    __slots__ = ("_name", "_attrs", "_req", "_ann", "_t0")

    def __init__(self, name: str, req: Optional[str] = None, **attrs):
        self._name = name
        self._attrs = attrs
        self._req = req
        self._ann = annotation(name, **attrs) if annotation else None
        self._t0 = 0.0

    def __enter__(self) -> "phase":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        table = phase_table()
        cell = table.get(self._name)
        if cell is None:
            table[self._name] = [t1 - self._t0, 1]
        else:
            cell[0] += t1 - self._t0
            cell[1] += 1
        if _enabled:
            req = self._req or current_request()
            complete(
                self._name, self._t0, t1, cat="phase",
                req=req, track=None if req else "engine.phases",
                **self._attrs,
            )


class _Span:
    __slots__ = ("_name", "_cat", "_req", "_track", "_args", "_t0")

    def __init__(self, name, cat, req, track, args):
        self._name = name
        self._cat = cat
        self._req = req
        self._track = track
        self._args = args

    def __enter__(self) -> "_Span":
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Attach result args discovered inside the span body."""
        self._args.update(args)

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self._args.setdefault("error", exc_type.__name__)
        complete(
            self._name,
            self._t0,
            time.perf_counter(),
            cat=self._cat,
            req=self._req,
            track=self._track,
            **self._args,
        )


# ------------------------------------------------- cross-process wire/ingest


def wire_events(request_id: Optional[str] = None) -> dict:
    """Snapshot the local ring in wire form for another process to
    `ingest`: ``{"process": label, "events": [...]}`` where each event
    carries its track NAME and an absolute unix-us timestamp instead of
    local tid / local epoch. `request_id` filters to one request's
    events (matched on the ``request_id`` arg every request-scoped
    event carries)."""
    with _tracks_lock:
        names = {tid: name for name, tid in _tracks.items()}
    out = []
    for ev in _events.copy():
        if request_id is not None and (
            ev["args"].get("request_id") != request_id
        ):
            continue
        out.append(_wire(ev, names.get(ev["tid"], "main")))
    return {"process": process_label(), "events": out}


def ingest(events: list, process: str) -> int:
    """Store wire events from another process for merged export. Their
    absolute timestamps are rebased into this process's clock domain;
    returns the number of events accepted (malformed ones are dropped —
    a bad batch from one worker must not poison the merge)."""
    base = _T0_UNIX * 1e6
    n = 0
    for w in events:
        try:
            ev = {
                "name": w["name"],
                "ph": w["ph"],
                "ts": round(float(w["ts_unix_us"]) - base, 1),
                "cat": w.get("cat") or "span",
                "args": dict(w.get("args") or {}),
                "process": process,
                "track": str(w.get("track") or "main"),
            }
            if "dur" in w:
                ev["dur"] = max(float(w["dur"]), 0.0)
            if w["ph"] == "i":
                ev["s"] = "t"
        except (KeyError, TypeError, ValueError):
            continue
        _foreign.append(ev)
        n += 1
    return n


def _foreign_pid(process: str) -> int:
    global _next_fpid
    pid = _foreign_pids.get(process)
    if pid is None:
        while len(_foreign_pids) >= _FOREIGN_PIDS_MAX:
            victim = next(iter(_foreign_pids))
            _foreign_pids.pop(victim)
            for key in [k for k in _foreign_tracks if k[0] == victim]:
                _foreign_tracks.pop(key)
        _next_fpid += 1
        pid = _foreign_pids[process] = _next_fpid
    return pid


def _foreign_tid(process: str, track: str) -> int:
    global _next_tid
    key = (process, track)
    tid = _foreign_tracks.get(key)
    if tid is None:
        while len(_foreign_tracks) >= _TRACKS_MAX:
            _foreign_tracks.pop(next(iter(_foreign_tracks)))
        _next_tid += 1
        tid = _foreign_tracks[key] = _next_tid
    return tid


# ------------------------------------------------------------------- export


def export(
    request_id: Optional[str] = None,
    track: Optional[str] = None,
    max_events: Optional[int] = None,
) -> dict:
    """Snapshot the ring as a Chrome trace-event JSON object: events
    sorted by ts (monotonic), one thread_name metadata record per track.
    Foreign events ingested from other processes merge in on their own
    pid with ``process_name`` metadata — each process a named track
    group of ONE timeline. `request_id` filters the export (metadata
    records for the surviving tracks are kept) — the /debug/trace
    per-request view. `track` filters to one named track (request rows
    are named by their request id; foreign tracks match on their wire
    name regardless of process). `max_events` keeps only the NEWEST N
    non-metadata events — the response-size cap a multi-MB merged fleet
    ring needs on every HTTP scrape; the count dropped is reported as
    ``truncatedEvents`` (Perfetto ignores unknown top-level keys)."""
    # copy() is a single C call that never runs Python code mid-loop, so
    # it cannot observe a concurrent worker-thread append mid-iteration —
    # sorting the live deque directly could raise "mutated during
    # iteration" under a /debug/trace scrape during serving
    local = list(_events.copy())
    foreign = list(_foreign.copy())
    if request_id is not None:
        local = [
            e for e in local if e["args"].get("request_id") == request_id
        ]
        foreign = [
            e for e in foreign if e["args"].get("request_id") == request_id
        ]
    if track is not None:
        with _tracks_lock:
            names = {tid: name for name, tid in _tracks.items()}
        local = [e for e in local if names.get(e["tid"]) == track]
        foreign = [e for e in foreign if e["track"] == track]
    remote = []
    with _tracks_lock:
        tracks = dict(_tracks)
        for ev in foreign:
            ev = dict(ev)
            process = ev.pop("process")
            track = ev.pop("track")
            ev["pid"] = _foreign_pid(process)
            ev["tid"] = _foreign_tid(process, track)
            remote.append(ev)
        proc_pids = dict(_foreign_pids)
        foreign_tracks = dict(_foreign_tracks)
    events = sorted(local + remote, key=lambda e: e["ts"])
    truncated = 0
    if max_events is not None and len(events) > max_events:
        # newest win, like the ring itself: the tail of the timeline is
        # the part a latency postmortem reads first
        truncated = len(events) - max_events
        events = events[truncated:]
    meta = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 0,
            "tid": 0,
            "args": {"name": process_label()},
        }
    ]
    meta += [
        {
            "name": "thread_name",
            "ph": "M",
            "pid": 0,
            "tid": tid,
            "args": {"name": name},
        }
        for name, tid in sorted(tracks.items(), key=lambda kv: kv[1])
    ]
    meta += [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": name},
        }
        for name, pid in sorted(proc_pids.items(), key=lambda kv: kv[1])
    ]
    meta += [
        {
            "name": "thread_name",
            "ph": "M",
            "pid": proc_pids[process],
            "tid": tid,
            "args": {"name": track},
        }
        for (process, track), tid in sorted(
            foreign_tracks.items(), key=lambda kv: kv[1]
        )
        if process in proc_pids
    ]
    out = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
    if truncated:
        out["truncatedEvents"] = truncated
    return out


def dump(path: str) -> int:
    """Write the Perfetto-loadable JSON to `path`; returns the number of
    non-metadata events written."""
    trace = export()
    with open(path, "w") as f:
        json.dump(trace, f)
        f.write("\n")
    return sum(1 for e in trace["traceEvents"] if e["ph"] != "M")
