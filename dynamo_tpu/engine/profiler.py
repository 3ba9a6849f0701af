"""On-device profiling: xprof phase annotations + on-demand capture.

The trace ring (utils/tracing.py) stops at the jit boundary — a slow
``decode`` rectangle says *that* the device was busy, never *where the
device time went*. This module crosses that boundary two ways:

- **Phase annotations.** Every engine dispatch wraps its jit call in a
  `jax.profiler.TraceAnnotation` named EXACTLY like its `engine.steps`
  span (``prefill`` / ``decode`` / ``spec_verify`` / ``mixed``) plus a
  `StepTraceAnnotation` carrying the engine step number — so an xprof
  capture and the Perfetto ring export join on the same names, and
  xprof's step-time analysis groups kernels under real engine steps.
  Annotations are TraceMe no-ops (~ns) while no capture is running, so
  they stay on unconditionally.
- **Host phases.** `phase(name)` (`utils/tracing.py`'s, under this
  module's name too) is the one way to mark what the host is doing: it
  opens a `TraceAnnotation` (so the interval lies on the device trace's
  clock during a capture), on exit hands the same interval to the
  trace ring when that is armed, and always adds its seconds to the
  host's own clock (`tracing.phase_totals()`: what an untraced run
  keeps). The engine loop's tick (``eng.tick`` and its children), the
  dispatch workers (``eng.lock`` / ``eng.upload`` / ``eng.enqueue``
  inside the dispatch annotation) and the frontend (``fe.*``) all use
  it; `benchmark/lib/trace_host.py` reads them back to say who owes the
  device's idle time, `benchmark/lib/host_clock.py` the tick's length
  and set-up's split. Names: docs/observability.md.
- **On-demand capture.** ``POST /debug/profile?duration_ms=`` on a live
  engine runs `jax.profiler.start_trace` into ``DYN_PROFILE_DIR`` for
  the requested window and stops — replacing the ad-hoc one-off
  ``scripts/profile_*.py`` workflow for live engines. A
  **single-capture-in-flight gate** rejects concurrent captures
  (overlapping XLA profiling sessions corrupt each other); the busy
  caller gets a typed `ProfilerBusy` (HTTP 409).

Load the output with xprof/TensorBoard (``tensorboard --logdir <dir>``)
or convert via xprof's trace viewer; see docs/observability.md
"Forensics plane" for the Perfetto-join walkthrough.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import tempfile
import threading
import time
from typing import Optional

from dynamo_tpu.utils import counters, tracing
from dynamo_tpu.utils.logging import get_logger

log = get_logger("dynamo_tpu.profiler")

try:  # pragma: no cover — exercised by the import itself
    from jax import profiler as _jprof
except Exception:  # noqa: BLE001 — profiling is optional everywhere
    _jprof = None

# zero-series at import (scripts/check_prom.py gates these rendering
# from the first scrape via utils/counters.PromCounters)
counters.declare("profiler_captures_total")
counters.declare("profiler_busy_total")

_NOOP = contextlib.nullcontext()
_lock = threading.Lock()
_active_dir: Optional[str] = None
_t_start = 0.0


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (the single-capture gate)."""


class ProfilerUnavailable(RuntimeError):
    """jax.profiler is missing or disabled (``DYN_PROFILE=0``)."""


def available() -> bool:
    if os.environ.get("DYN_PROFILE", "") == "0":
        return False
    return _jprof is not None and hasattr(_jprof, "start_trace")


def annotate(name: str):
    """Context manager naming a dispatch phase for xprof; the name must
    match the phase's ``engine.steps`` span so the two traces join.
    No-op when jax.profiler is absent."""
    if _jprof is None:
        return _NOOP
    return _jprof.TraceAnnotation(name)


# the one way to mark a host phase (utils/tracing.py, which stays off
# jax); importing this module puts its annotations on the device trace
phase = tracing.phase
if _jprof is not None:
    tracing.annotation = _jprof.TraceAnnotation


def step_annotation(step_num: int):
    """xprof step marker carrying the engine step number (feeds xprof's
    step-time analysis)."""
    if _jprof is None:
        return _NOOP
    return _jprof.StepTraceAnnotation("engine.step", step_num=step_num)


def profile_dir(override: Optional[str] = None) -> str:
    """Capture output dir: explicit override > ``DYN_PROFILE_DIR`` >
    a tmpdir subdirectory."""
    return (
        override
        or os.environ.get("DYN_PROFILE_DIR")
        or os.path.join(tempfile.gettempdir(), "dynamo_tpu_profile")
    )


def active() -> Optional[str]:
    """The in-flight capture's logdir, or None."""
    return _active_dir


def start(logdir: Optional[str] = None) -> str:
    """Begin an on-device capture; returns the logdir. Raises
    `ProfilerBusy` when one is already in flight and
    `ProfilerUnavailable` when jax.profiler cannot capture here."""
    global _active_dir, _t_start
    if not available():
        raise ProfilerUnavailable("jax.profiler unavailable or disabled")
    with _lock:
        if _active_dir is not None:
            counters.inc("profiler_busy_total")
            raise ProfilerBusy(
                f"capture already in flight -> {_active_dir}"
            )
        d = os.path.join(
            profile_dir(logdir), time.strftime("%Y%m%d-%H%M%S")
        )
        os.makedirs(d, exist_ok=True)
        try:
            _jprof.start_trace(d)
        except Exception as exc:  # noqa: BLE001 — platform-dependent
            raise ProfilerUnavailable(f"start_trace failed: {exc}") from exc
        _active_dir = d
        _t_start = time.perf_counter()
        return d


def stop() -> dict:
    """End the in-flight capture; returns ``{dir, duration_ms}``."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            raise ProfilerUnavailable("no capture in flight")
        d, _active_dir = _active_dir, None
        try:
            _jprof.stop_trace()
        except Exception as exc:  # noqa: BLE001
            raise ProfilerUnavailable(f"stop_trace failed: {exc}") from exc
    counters.inc("profiler_captures_total")
    return {
        "dir": d,
        "duration_ms": round((time.perf_counter() - _t_start) * 1e3, 1),
    }


async def capture(duration_ms: float, logdir: Optional[str] = None) -> dict:
    """One bounded capture window (the ``POST /debug/profile`` body):
    start, serve traffic for `duration_ms`, stop. The gate in `start`
    makes concurrent calls fail fast instead of corrupting each other."""
    start(logdir)
    try:
        await asyncio.sleep(max(duration_ms, 1.0) / 1000.0)
    finally:
        info = stop()
    return info
