"""Prometheus-format service metrics (no external prometheus dependency).

Equivalent of the reference's HTTP metrics (reference:
lib/llm/src/http/service/metrics.rs:36-201): `{prefix}_requests_total`
(model/endpoint/status labels), `{prefix}_inflight_requests`,
`{prefix}_request_duration_seconds` histogram, plus the RAII
`InflightGuard` that records status on exit.
"""

from __future__ import annotations

import time
from collections import defaultdict, deque
from typing import Iterable, Optional

DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_le(bound: float) -> str:
    """Bucket `le` label value: canonical float repr ("1.0", "0.005",
    "+Inf"), never locale-dependent and never the bare-int "1" an
    int-typed bucket tuple would produce via str() — consecutive scrapes
    must diff cleanly whatever Python built the bucket bounds."""
    f = float(bound)
    if f == float("inf"):
        return "+Inf"
    return repr(f)


class Counter:
    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._values: dict[tuple, float] = defaultdict(float)

    def declare(self, **labels: str) -> None:
        """Materialize a labeled series at 0 BEFORE its first increment
        (the Histogram zero-series rule applied to counters): rate()
        queries and dashboards need the series present from the first
        scrape, and a counter that appears mid-flight reads as a reset."""
        self._values.setdefault(tuple(sorted(labels.items())), 0.0)

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        self._values[tuple(sorted(labels.items()))] += amount

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} counter"
        if not self._values:
            yield f"{self.name} 0"
        # sorted keys: consecutive scrapes diff cleanly whatever order
        # the series were first touched in
        for key in sorted(self._values):
            yield f"{self.name}{_fmt_labels(dict(key))} {self._values[key]}"


class Gauge:
    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._values: dict[tuple, float] = defaultdict(float)

    def declare(self, **labels: str) -> None:
        """Materialize a labeled series at 0 before its first set/add
        (see Counter.declare)."""
        self._values.setdefault(tuple(sorted(labels.items())), 0.0)

    def set(self, value: float, **labels: str) -> None:
        self._values[tuple(sorted(labels.items()))] = value

    def add(self, amount: float, **labels: str) -> None:
        self._values[tuple(sorted(labels.items()))] += amount

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} gauge"
        if not self._values:
            yield f"{self.name} 0"
        for key in sorted(self._values):
            yield f"{self.name}{_fmt_labels(dict(key))} {self._values[key]}"


class Histogram:
    def __init__(self, name: str, help_: str, buckets: tuple[float, ...] = DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.buckets = buckets
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = defaultdict(float)
        self._totals: dict[tuple, int] = defaultdict(int)

    def observe(self, value: float, **labels: str) -> None:
        key = tuple(sorted(labels.items()))
        if key not in self._counts:
            self._counts[key] = [0] * len(self.buckets)
        # per-bucket counts here; render() accumulates into cumulative form
        for i, b in enumerate(self.buckets):
            if value <= b:
                self._counts[key][i] += 1
                break
        self._sums[key] += value
        self._totals[key] += 1

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} histogram"
        # the unlabeled base series ALWAYS renders (zero before any
        # observation, and it stays once labeled series appear): scrapers
        # and rate() queries need _sum/_count points to exist from the
        # first scrape AND never go stale later — a series that appears,
        # vanishes and reappears breaks continuity. Sorted keys + .get
        # (no defaultdict insertion side effects) keep scrapes diffable.
        for key in sorted({(), *self._counts}):
            counts = self._counts.get(key) or [0] * len(self.buckets)
            labels = dict(key)
            total = self._totals.get(key, 0)
            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                yield f'{self.name}_bucket{_fmt_labels({**labels, "le": _fmt_le(b)})} {cum}'
            yield f'{self.name}_bucket{_fmt_labels({**labels, "le": "+Inf"})} {total}'
            yield f"{self.name}_sum{_fmt_labels(labels)} {self._sums.get(key, 0.0)}"
            yield f"{self.name}_count{_fmt_labels(labels)} {total}"


class ServiceMetrics:
    def __init__(self, prefix: str = "dynamo_tpu"):
        self._prefix = prefix
        self.requests_total = Counter(
            f"{prefix}_http_service_requests_total", "Total HTTP LLM requests"
        )
        self.inflight = Gauge(
            f"{prefix}_http_service_inflight_requests", "In-flight HTTP LLM requests"
        )
        self.duration = Histogram(
            f"{prefix}_http_service_request_duration_seconds",
            "HTTP LLM request duration",
        )
        self.extra: list = []  # extra renderables (engine metrics etc.)

    def inflight_guard(self, model: str, endpoint: str) -> "InflightGuard":
        return InflightGuard(self, model, endpoint)

    def render(self) -> str:
        # leading instance-info series (build_info convention): the ONE
        # place a scrape names the emitting process, joinable in PromQL
        # against every other series of this endpoint — multi-worker
        # fleets attribute scrapes without labeling every series
        from dynamo_tpu.utils import instance

        lines: list[str] = [
            f"# TYPE {self._prefix}_instance_info gauge",
            f'{self._prefix}_instance_info'
            f'{{worker_id="{instance.worker_id()}"}} 1',
        ]
        for metric in (self.requests_total, self.inflight, self.duration, *self.extra):
            lines.extend(metric.render())
        return "\n".join(lines) + "\n"


# inter-token latencies sit in the single-digit-millisecond range on TPU;
# the default (request-duration) buckets would dump every observation in
# the first bucket
ITL_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)
TOKENS_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                  512.0, 1024.0, 2048.0, 4096.0, 8192.0, 16384.0)


class EngineMetrics:
    """Engine-side request latency histograms + `Engine.metrics()` gauges,
    rendered through `ServiceMetrics.extra` so ONE `GET /metrics` scrape
    covers the service and the engine behind it (reference: the stats
    plane merges ForwardPassMetrics into the HTTP exposition).

    The histograms are fed by the engine's per-request summaries
    (`JaxEngine.subscribe_requests`, fired at finish): TTFT is submit →
    first token emitted by the engine (fetch included, transport to the
    client excluded), ITL the request's mean inter-token gap, queue wait
    submit → decode-slot admission. Gauges re-read `engine.metrics()` at
    every render, so they are scrape-time fresh without a poll loop."""

    def __init__(
        self,
        engine=None,
        prefix: str = "dynamo_tpu",
        slo: Optional["SloTracker"] = None,
        worker_id: Optional[str] = None,
    ):
        self.engine = engine
        self._prefix = prefix
        # optional SLO attainment tracker: fed from the same finish
        # summaries, rendered through the same scrape
        self.slo = slo
        # optional stable instance label (utils/instance.worker_id):
        # when set, every engine gauge carries worker_id="..." so a
        # fleet Prometheus can tell multi-worker scrapes apart. Default
        # None keeps single-process scrapes label-free.
        self._worker_label = (
            f'{{worker_id="{worker_id}"}}' if worker_id else ""
        )
        self._worker_id = worker_id
        self.ttft = Histogram(
            f"{prefix}_engine_ttft_seconds",
            "Engine TTFT: request submit to first token emitted",
        )
        self.itl = Histogram(
            f"{prefix}_engine_itl_seconds",
            "Mean inter-token latency per finished request",
            buckets=ITL_BUCKETS,
        )
        self.queue_wait = Histogram(
            f"{prefix}_engine_queue_wait_seconds",
            "Request submit to decode-slot admission",
        )
        self.tokens = Histogram(
            f"{prefix}_engine_tokens_per_request",
            "Generated tokens per finished request",
            buckets=TOKENS_BUCKETS,
        )
        if engine is not None and hasattr(engine, "subscribe_requests"):
            engine.subscribe_requests(self.observe)

    def observe(self, summary: dict) -> None:
        """Request-finish hook (see JaxEngine._finish for the fields)."""
        if summary.get("ttft_s") is not None:
            self.ttft.observe(summary["ttft_s"])
        if summary.get("itl_s") is not None:
            self.itl.observe(summary["itl_s"])
        if summary.get("queue_wait_s") is not None:
            self.queue_wait.observe(summary["queue_wait_s"])
        if summary.get("tokens"):
            self.tokens.observe(float(summary["tokens"]))
        if self.slo is not None:
            self.slo.observe(summary)

    def render(self) -> Iterable[str]:
        if self.engine is not None:
            try:
                gauges = self.engine.metrics()
            except Exception:  # noqa: BLE001 — a scrape must never 500
                gauges = {}
            for key, val in gauges.items():
                name = f"{self._prefix}_engine_{key}"
                if isinstance(val, dict):
                    # `phase_seconds_total`: the host's clock by phase
                    # (utils/tracing.phase_totals), one series a phase; a
                    # family with no sample yet is left out whole
                    if val:
                        yield f"# TYPE {name} counter"
                    for phase, seconds in val.items():
                        labels = {"phase": phase}
                        if self._worker_id:
                            labels["worker_id"] = self._worker_id
                        yield f"{name}{_fmt_labels(labels)} {float(seconds)}"
                    continue
                yield f"# TYPE {name} gauge"
                if key == "gspmd_fallback_dispatches":
                    # executor attribution: the refusal reason rides as
                    # a label so a silently-refused tp_overlap config
                    # reads straight off the scrape
                    labels = {}
                    if self._worker_id:
                        labels["worker_id"] = self._worker_id
                    reason = getattr(
                        self.engine, "tp_overlap_refusal_reason", ""
                    )
                    if reason:
                        labels["reason"] = str(reason)
                    yield f"{name}{_fmt_labels(labels)} {float(val)}"
                    continue
                yield f"{name}{self._worker_label} {float(val)}"
        for h in (self.ttft, self.itl, self.queue_wait, self.tokens):
            yield from h.render()
        # forensics counters (engine/flight_recorder.py): the labeled
        # step_anomalies{phase} + dump/suppressed families ride the same
        # scrape as the engine gauges (zero-series declared at recorder
        # construction — scripts/check_prom.py gates them rendering)
        fr = getattr(self.engine, "flight", None)
        if fr is not None:
            yield from fr.render_prom()
        # custody ledger (engine/kv_ledger.py): transitions/violations/
        # audits counter families, zero-series declared at construction
        # (scripts/check_prom.py pins these rendering too)
        ledger = getattr(self.engine, "kv_ledger", None)
        if ledger is not None:
            yield from ledger.render_prom()
        if self.slo is not None:
            yield from self.slo.render()


# ---------------------------------------------------------------------- SLO

# the request-summary fields an SLO can target (engine _note_finished
# keys), with the Prometheus-facing metric slug they render under
SLO_METRICS = {
    "ttft_s": "ttft",
    "itl_s": "itl",
    "queue_wait_s": "queue_wait",
}


class SloTracker:
    """Rolling-window SLO attainment accounting (docs/observability.md
    "Fleet plane").

    Targets come from config as ``{tenant: {ttft_s|itl_s|queue_wait_s:
    seconds}}``; the ``"default"`` tenant covers requests with no tenant
    label (the HTTP frontend stamps ``x-tenant-id`` into Context
    metadata). Fed per finished request from the engine's summaries
    (`JaxEngine.subscribe_requests`), it keeps a bounded rolling window
    per (tenant, metric) and renders:

    - ``slo_attainment{tenant,metric}`` — attained fraction over the
      window (1.0 with no samples: an idle tenant is not in breach).
      A value exactly AT the target attains (<=) — the boundary rule.
    - ``slo_breaches_total{tenant,metric}`` / ``slo_requests_total`` —
      monotonic burn-rate counters (zero-series declared at
      registration so dashboards see them from the first scrape).

    The attained fractions also feed the worker's stats handler
    (`KvMetricsPublisher`), making every worker's attainment visible to
    `KvMetricsAggregator` — the fleet signal the SLO-driven planner
    scales on."""

    def __init__(
        self,
        targets: Optional[dict] = None,
        window_s: float = 300.0,
        max_samples: int = 4096,
        prefix: str = "dynamo_tpu",
    ):
        self.targets: dict = targets or {}
        self.window_s = window_s
        self.max_samples = max_samples
        # breach hook (forensics plane): called with (tenant_row, metric
        # slug, value, target, request_id) for every request that missed
        # its target — run.py wires it to the engine flight recorder's
        # `on_slo_breach` so the forensic artifact exists the moment the
        # breach lands, rate-limited recorder-side. Exceptions are
        # contained: forensics must never break the finish path.
        self.on_breach: Optional[callable] = None
        # (tenant, metric) -> deque[(monotonic_ts, attained_bool)]
        self._windows: dict[tuple, deque] = {}
        self.breaches = Counter(
            f"{prefix}_slo_breaches_total",
            "Requests that missed their SLO target (burn rate numerator)",
        )
        self.requests = Counter(
            f"{prefix}_slo_requests_total",
            "Requests evaluated against an SLO target",
        )
        self.attainment = Gauge(
            f"{prefix}_slo_attainment",
            "Attained fraction over the rolling window (1.0 = all within "
            "target)",
        )
        # zero-series at registration: every configured (tenant, metric)
        # renders from the first scrape, before any request finishes
        for tenant, tspec in self.targets.items():
            for field_name, slug in SLO_METRICS.items():
                if (tspec or {}).get(field_name) is None:
                    continue
                self.breaches.declare(tenant=tenant, metric=slug)
                self.requests.declare(tenant=tenant, metric=slug)
                self.attainment.set(1.0, tenant=tenant, metric=slug)

    def _resolve(self, tenant: str) -> tuple[str, dict]:
        """(row, targets) for a request's tenant: a CONFIGURED tenant
        uses its own spec under its own row — an explicitly empty spec
        means exempt, not fall-through — while unknown tenants ride the
        default target and aggregate under the "default" row (the row
        always matches the spec that judged the request)."""
        if tenant in self.targets:
            return tenant, self.targets[tenant] or {}
        return "default", self.targets.get("default") or {}

    def observe(self, summary: dict, now: Optional[float] = None) -> None:
        """Request-finish hook (wire into `JaxEngine.subscribe_requests`
        or call from `EngineMetrics.observe`)."""
        tenant = str(summary.get("tenant") or "default")
        row, tspec = self._resolve(tenant)
        if not tspec:
            return
        now = time.monotonic() if now is None else now
        for field_name, slug in SLO_METRICS.items():
            target = tspec.get(field_name)
            value = summary.get(field_name)
            if target is None or value is None:
                continue
            attained = value <= target  # AT the target attains
            win = self._windows.setdefault(
                (row, slug), deque(maxlen=self.max_samples)
            )
            win.append((now, attained))
            self.requests.inc(tenant=row, metric=slug)
            if not attained:
                self.breaches.inc(tenant=row, metric=slug)
                if self.on_breach is not None:
                    try:
                        self.on_breach(
                            row, slug, value, target,
                            summary.get("request_id"),
                        )
                    except Exception:  # noqa: BLE001 — forensics must
                        pass           # not break the finish path
            self._refresh(row, slug, now)

    def _refresh(self, tenant: str, slug: str, now: float) -> None:
        win = self._windows.get((tenant, slug))
        if win is None:
            return
        horizon = now - self.window_s
        while win and win[0][0] < horizon:
            win.popleft()
        if win:
            frac = sum(1 for _, ok in win if ok) / len(win)
        else:
            frac = 1.0  # idle window: vacuously attaining
        self.attainment.set(round(frac, 4), tenant=tenant, metric=slug)

    def attained_fraction(
        self, tenant: str, metric: str, now: Optional[float] = None
    ) -> float:
        """Window fraction for one (tenant, metric slug); 1.0 when idle."""
        now = time.monotonic() if now is None else now
        self._refresh(tenant, metric, now)
        win = self._windows.get((tenant, metric))
        if not win:
            return 1.0
        return sum(1 for _, ok in win if ok) / len(win)

    def snapshot(self, now: Optional[float] = None) -> dict:
        """``{"tenant/metric": fraction}`` for every tracked window —
        the compact form that rides worker stats replies
        (ForwardPassMetrics.slo_attainment)."""
        now = time.monotonic() if now is None else now
        out = {}
        for (tenant, slug) in list(self._windows):
            out[f"{tenant}/{slug}"] = round(
                self.attained_fraction(tenant, slug, now), 4
            )
        return out

    def render(self) -> Iterable[str]:
        now = time.monotonic()
        for (tenant, slug) in list(self._windows):
            self._refresh(tenant, slug, now)
        yield from self.attainment.render()
        yield from self.breaches.render()
        yield from self.requests.render()


class InflightGuard:
    """RAII request accounting (reference: metrics.rs:201 InflightGuard)."""

    def __init__(self, metrics: ServiceMetrics, model: str, endpoint: str):
        self._m = metrics
        self._model = model
        self._endpoint = endpoint
        self._start = time.monotonic()
        self.status = "error"
        self._m.inflight.add(1, model=model)

    def mark_ok(self) -> None:
        self.status = "success"

    def close(self) -> None:
        self._m.inflight.add(-1, model=self._model)
        self._m.requests_total.inc(
            1, model=self._model, endpoint=self._endpoint, status=self.status
        )
        self._m.duration.observe(time.monotonic() - self._start, model=self._model)
