"""Engine-side device telemetry: HBM usage + jit compile events.

The two silent killers of TPU serving latency are invisible in the PR-4
spine: HBM pressure (an auto-sized KV pool can sit a few percent from
OOM with nothing exported) and jit cache misses (a cold shape family is
a multi-second stall that reads as one mysteriously slow request). This
module surfaces both:

- **`device_memory_stats()`** wraps `jax` device ``memory_stats()`` into
  flat gauges (``hbm_bytes_in_use`` / ``hbm_bytes_limit`` /
  ``hbm_utilization``). CPU backends return no stats — the dict is empty
  there, and `Engine.metrics()` simply omits the series (the Prometheus
  checker treats absent-on-CPU as fine, zero-series rules apply to
  registered counters, not platform-gated gauges).
- **`HeapWatch`** books the seconds CPython's cyclic collector takes
  from the host (a `gc.callbacks` hook: a full pass over the ~375k
  objects of a warm engine's compiled programs stops the loop's thread
  for 0.2-0.4 s) and, once the step programs stand still, moves what
  set-up built out of the collector's reach (`gc.freeze`).
- **`install_compile_listener()`** registers a process-wide
  `jax.monitoring` duration listener counting XLA backend compiles and
  their wall time (and summing jax's walls of tracing, lowering and
  reading the persistent cache: `compile_stats()`), and — when tracing
  is armed — records each one as an
  ``engine.compile`` complete event on its own track, so the
  multi-second gaps in a step timeline finally carry a name. Idempotent;
  the listener is process-global because compilation is (one jit cache
  per process, however many engines).
"""

from __future__ import annotations

import gc
import logging
import threading
import time

import jax

from dynamo_tpu.utils import tracing

# jax monitoring event key for an XLA backend compile (jit cache miss or
# a read from the persistent cache): the multi-second one of a first run
_COMPILE_KEY = "/jax/core/compile/backend_compile_duration"
# tracing a function to a jaxpr and lowering the jaxpr to MLIR: host work
# that every start pays for every program, cache or no cache (a step
# program unrolls the model's depth, so neither is cheap: ROADMAP S10)
_TRACE_KEY = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_KEY = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# recorded once per program served from the persistent compilation cache
# (utils/compile_cache.py) instead of being compiled
_CACHE_HIT_KEY = "/jax/compilation_cache/cache_hits"
# wall of reading one such program back (a duration event)
_CACHE_READ_KEY = "/jax/compilation_cache/cache_retrieval_time_sec"

log = logging.getLogger("dynamo_tpu.engine")

_lock = threading.Lock()
_installed = False
_compile_events = 0
_compile_time_s = 0.0
_cache_hits = 0
# walls that are only summed, by their event key
_walls = {_CACHE_READ_KEY: 0.0, _TRACE_KEY: 0.0, _LOWER_KEY: 0.0}


def _on_event_duration(name: str, duration_s: float, **_kw) -> None:
    global _compile_events, _compile_time_s
    if name in _walls:
        with _lock:
            _walls[name] += duration_s
        return
    if name != _COMPILE_KEY:
        return
    with _lock:
        _compile_events += 1
        _compile_time_s += duration_s
    if tracing.enabled():
        t1 = time.perf_counter()
        tracing.complete(
            "engine.compile", t1 - duration_s, t1, cat="compile",
            track="engine.compile", duration_s=round(duration_s, 4),
        )


def _on_event(name: str, **_kw) -> None:
    global _cache_hits
    if name == _CACHE_HIT_KEY:
        with _lock:
            _cache_hits += 1


def install_compile_listener() -> None:
    """Register the compile listener once per process (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    try:
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration
        )
        jax.monitoring.register_event_listener(_on_event)
    except Exception:  # noqa: BLE001 — telemetry must never block init
        pass


def compile_stats() -> dict:
    """Cumulative compile gauges for `Engine.metrics()`. jax reports a
    ``backend_compile`` event for a program it reads back from the
    persistent cache too, so ``compile_events`` counts both;
    ``backend_compiles`` is what the compiler really built (events less
    cache hits) and ``cache_read_s`` the wall of the reads. ``trace_s``
    and ``lower_s`` sum jax's own walls of tracing to a jaxpr and of
    lowering it to MLIR (a traced function that calls another counts the
    inner trace in both). ``phase_s`` is the host's clock by phase
    (`tracing.phase_totals()`, seconds alone) and ``at_s`` the
    `time.monotonic()` of this snapshot: two snapshots give where the
    host's time went between them. `Engine.metrics()` renders ``phase_s``
    as one labelled series and leaves ``at_s`` out."""
    phase_s = {
        name: round(cell[0], 4)
        for name, cell in sorted(tracing.phase_totals().items())
    }
    with _lock:
        return {
            "compile_events": _compile_events,
            "compile_time_s": round(_compile_time_s, 4),
            "persistent_cache_hits": _cache_hits,
            "backend_compiles": max(_compile_events - _cache_hits, 0),
            "cache_read_s": round(_walls[_CACHE_READ_KEY], 4),
            "trace_s": round(_walls[_TRACE_KEY], 4),
            "lower_s": round(_walls[_LOWER_KEY], 4),
            "phase_s": phase_s,
            "at_s": time.monotonic(),
        }


# ticks of the engine's loop over which `compile_events` must stand still
# before the heap is frozen: 5 s of a 155 ms tick, well inside a warm-up
HEAP_QUIET_TICKS = 32


class HeapWatch:
    """One engine's view of the cyclic collector. `gc_s` sums the seconds
    of every pass since construction (`lap()`: its growth since the call
    before, which the engine books on each landing's digest),
    `full_passes` / `full_pass_s` the generation-2 passes alone. `settle()` is the cure for their length:
    a full pass walks every tracked object, and nearly all of a warm
    engine's are its compiled programs, which never die. The rule is one
    the engine can observe: `compile_events` (compiled or read back from
    the persistent cache) unchanged over `HEAP_QUIET_TICKS` ticks that
    dispatched or landed something -> `gc.collect()` then `gc.freeze()`,
    once, and again only after the count moves. The heap is the
    process's, so an embedding process gets the freeze too; `close()`
    gives it back (`gc.unfreeze`)."""

    def __init__(self):
        self.gc_s = 0.0
        self.full_passes = 0
        self.full_pass_s = 0.0
        self._t0 = self._lap = 0.0
        self._compiles = -1  # compile_events when the quiet stretch began
        self._quiet = 0      # ticks since; -1 = frozen and nothing moved
        self._froze = False
        gc.callbacks.append(self._on_pass)

    def _on_pass(self, phase: str, info: dict) -> None:
        # passes never nest (the collector refuses to re-enter)
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        self.gc_s += dt
        if info.get("generation") == 2:
            self.full_passes += 1
            self.full_pass_s += dt

    def lap(self) -> float:
        """Seconds of collector passes since the call before."""
        was, self._lap = self._lap, self.gc_s
        return self._lap - was

    def settle(self) -> bool:
        """Between two ticks of the loop; True when it froze the heap."""
        n = _compile_events
        if n != self._compiles:
            self._compiles, self._quiet = n, 0
        elif self._quiet >= 0:
            self._quiet += 1
            if self._quiet >= HEAP_QUIET_TICKS:
                t0 = time.perf_counter()
                gc.collect()
                gc.freeze()
                self._quiet, self._froze = -1, True
                log.info(
                    "programs warm (%d compiled or loaded): heap frozen, "
                    "%d objects, %.3f s", n, gc.get_freeze_count(),
                    time.perf_counter() - t0,
                )
                return True
        return False

    def stats(self) -> dict:
        return {
            "gc_full_passes_total": self.full_passes,
            "gc_full_pass_s_total": round(self.full_pass_s, 4),
            "gc_frozen_objects": gc.get_freeze_count(),
        }

    def detach(self) -> None:
        """Stop counting (an engine dropped without `close()`: its
        finalizer; no unfreeze from inside a collector pass)."""
        try:
            gc.callbacks.remove(self._on_pass)
        except ValueError:
            pass

    def close(self) -> None:
        self.detach()
        if self._froze:
            self._froze = False
            gc.unfreeze()


def device_memory_stats(device=None) -> dict:
    """Flat HBM gauges from the device's ``memory_stats()``; empty when
    the backend exposes none (CPU) or the probe fails (a scrape must
    never 500 on telemetry)."""
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:  # noqa: BLE001
        return {}
    if not stats:
        return {}
    out = {}
    in_use = stats.get("bytes_in_use")
    limit = stats.get("bytes_limit")
    if in_use is not None:
        out["hbm_bytes_in_use"] = int(in_use)
    if limit:
        out["hbm_bytes_limit"] = int(limit)
        if in_use is not None:
            out["hbm_utilization"] = round(in_use / limit, 4)
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        out["hbm_peak_bytes_in_use"] = int(peak)
    return out
