"""`python -m dynamo_tpu.run` — the single-binary serving CLI.

Equivalent of the reference's `dynamo-run` (reference:
launch/dynamo-run/src/{main,lib,opt,flags}.rs): wire an input to an output.

    in=http       OpenAI HTTP server
    in=text       interactive chat REPL
    in=stdin      one prompt from stdin, completion to stdout
    in=batch:F    JSONL prompts file -> outputs + TTFT/ITL stats
    in=dyn://...  worker mode: serve the engine on a distributed endpoint

    out=jax       native TPU engine (requires --model-path)
    out=echo_core / out=echo_full   CPU fake backends
    out=dyn://... ingress mode: route to discovered remote workers

Examples:
    python -m dynamo_tpu.run in=http out=jax --model-path /models/llama
    python -m dynamo_tpu.run in=http out=dyn://demo.backend.generate --hub H:P
    python -m dynamo_tpu.run in=dyn://demo.backend.generate out=jax \
        --model-path /models/llama --hub H:P [--disagg-mode decode|prefill]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Optional

from dynamo_tpu.utils.logging import configure_logging, get_logger

log = get_logger("dynamo_tpu.run")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dynamo_tpu.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("io", nargs="+", help="in=... out=... (any order)")
    p.add_argument("--model-path", help="local HF-style model dir")
    p.add_argument("--model-name", help="public model name (default: dir name)")
    p.add_argument("--hub", help="hub address host:port (distributed modes)")
    p.add_argument("--http-host", default="0.0.0.0")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--router-mode", default="round_robin",
                   choices=["random", "round_robin", "kv"])
    p.add_argument("--tensor-parallel-size", "--tp", type=int, default=1, dest="tp")
    p.add_argument("--sequence-parallel-size", "--sp", type=int, default=1, dest="sp",
                   help="ring-attention long-context prefill (needs prefill-chunk >= max-model-len)")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=None)
    p.add_argument("--prefill-chunk", type=int, default=512)
    p.add_argument("--decode-steps", type=int, default=8)
    p.add_argument("--attn-backend", default="auto",
                   choices=["auto", "pallas", "gather"])
    p.add_argument("--quantization", default=None, choices=["int8"],
                   help="W8A8 int8 serving (the TPU match for the "
                        "reference's FP8 baselines)")
    p.add_argument("--kv-quantization", default=None, choices=["int8"],
                   help="int8 KV cache pages (halves decode HBM traffic; "
                        "use --page-size 128 to keep the pallas kernels)")
    p.add_argument("--host-kv-pages", type=int, default=0,
                   help="HBM->host KV offload pool size (0 disables)")
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument("--extra-engine-args", help="JSON file of EngineConfig overrides")
    p.add_argument("--request-template",
                   help="JSON file of request defaults (model/temperature/"
                        "max_completion_tokens), ref request_template.rs")
    p.add_argument("--request-timeout", type=float, default=None,
                   help="default end-to-end deadline per request, seconds "
                        "(per-request x-request-timeout header overrides; "
                        "expired requests shed with 429 — "
                        "docs/robustness.md)")
    p.add_argument("--slo-targets",
                   help="JSON file of per-tenant SLO targets "
                        '({"default": {"ttft_s": 2.0, "itl_s": 0.05, '
                        '"queue_wait_s": 1.0, "priority": 0}, '
                        '"<tenant>": {...}}; the '
                        "DYN_SLO_TARGETS env var takes inline JSON) — "
                        "renders slo_attainment/slo_breaches_total on "
                        "/metrics, rides worker stats replies, and the "
                        "optional per-tenant priority int feeds the "
                        "admission/preemption ladder "
                        "(docs/observability.md, docs/control.md)")
    p.add_argument("--admission", action="store_true",
                   help="arm the front-door admission gate (DYN_ADMISSION=1 "
                        "equivalent): under overload (SLO attainment "
                        "burning + queue over watermark) lowest-priority "
                        "tenants shed with 429/503 + Retry-After "
                        "(docs/control.md)")
    p.add_argument("--disagg-mode", choices=["agg", "decode", "prefill"],
                   default="agg", help="worker role in a disaggregated graph")
    p.add_argument("--max-local-prefill-length", type=int, default=128)
    p.add_argument("--max-tokens", type=int, default=256,
                   help="default generation budget for text/stdin/batch inputs")
    # multi-host bootstrap (reference: launch/dynamo-run/src/lib.rs:232-276
    # --num-nodes/--node-rank; here jax.distributed instead of Ray/MPI)
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--coordinator",
                   help="host:port of node 0 (required when --num-nodes > 1)")
    return p


def parse_io(tokens: list[str]) -> tuple[str, str]:
    inp, out = "http", "echo_full"
    for t in tokens:
        if t.startswith("in="):
            inp = t[3:]
        elif t.startswith("out="):
            out = t[4:]
        else:
            raise SystemExit(f"unrecognized positional {t!r} (want in=/out=)")
    return inp, out


def load_slo_targets(args):
    """Per-tenant SLO targets: --slo-targets file > DYN_SLO_TARGETS
    inline JSON > None (no tracker)."""
    import os

    if getattr(args, "slo_targets", None):
        with open(args.slo_targets) as f:
            return json.load(f)
    inline = os.environ.get("DYN_SLO_TARGETS")
    if inline:
        return json.loads(inline)
    return None


def build_slo_tracker(args):
    from dynamo_tpu.llm.http.metrics import SloTracker

    targets = load_slo_targets(args)
    return SloTracker(targets) if targets else None


def build_admission(args):
    """Front-door admission gate (docs/control.md): armed by
    --admission (or DYN_ADMISSION=1) with tenant priority classes from
    the same --slo-targets file ("priority": int per tenant). Signals
    (queue depth + attainment) are late-bound once the engine or fleet
    aggregator exists."""
    import os

    if not (getattr(args, "admission", False)
            or os.environ.get("DYN_ADMISSION", "") not in ("", "0")):
        return None
    from dynamo_tpu.llm.http.admission import (
        AdmissionConfig,
        AdmissionController,
        priorities_from_targets,
    )

    cfg = AdmissionConfig()
    if os.environ.get("DYN_ADMISSION_QUEUE_HIGH"):
        cfg.queue_high_watermark = float(os.environ["DYN_ADMISSION_QUEUE_HIGH"])
    if os.environ.get("DYN_ADMISSION_ATTAIN_FLOOR"):
        cfg.attainment_floor = float(os.environ["DYN_ADMISSION_ATTAIN_FLOOR"])
    return AdmissionController(
        priorities=priorities_from_targets(load_slo_targets(args)), cfg=cfg
    )


def _bind_ingress_admission(admission, watcher) -> None:
    """Fleet signals for an ingress-mode admission gate: mean waiting
    depth per worker + worst fleet attainment. router_mode=kv reads the
    kv routers' metrics aggregators; round-robin/random modes read the
    standalone per-service stats aggregators the ModelWatcher starts
    when collect_stats is set (same worker stats plane, no router) —
    so the gate is never signal-blind just because routing is dumb."""
    import statistics

    def _aggs():
        kv = [
            r.router.aggregator
            for r in watcher._kv_routers.values()
            if getattr(r, "router", None) is not None
        ]
        return kv + list(watcher.stats_aggregators.values())

    def queue_depth():
        waits = [
            m.num_requests_waiting
            for agg in _aggs()
            for m in agg.current.endpoints.values()
        ]
        return statistics.fmean(waits) if waits else 0.0

    def attainment():
        mins = [
            v["min"]
            for agg in _aggs()
            for v in agg.attainment().values()
        ]
        return min(mins) if mins else None

    admission.bind(queue_depth_fn=queue_depth, attainment_fn=attainment)


def build_engine_config_kwargs(args) -> dict:
    from dynamo_tpu.parallel.mesh import MeshConfig

    kw = dict(
        mesh=MeshConfig(tp=args.tp, sp=args.sp),
        dtype=args.dtype,
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_batch_size=args.max_batch_size,
        max_model_len=args.max_model_len,
        prefill_chunk=args.prefill_chunk,
        decode_steps=args.decode_steps,
        attn_backend=args.attn_backend,
        quantization=args.quantization,
        kv_quantization=args.kv_quantization,
        host_kv_pages=args.host_kv_pages,
    )
    if args.extra_engine_args:
        with open(args.extra_engine_args) as f:
            kw.update(json.load(f))
    return kw


async def build_output(args, out: str, drt=None):
    """Returns (pipeline_engine, card|None, jax_engine|None): something with
    .generate(Context) serving OpenAI-shaped or token-shaped requests."""
    from dynamo_tpu.llm.engines import EchoEngineCore, EchoEngineFull

    if out == "echo_full":
        return EchoEngineFull(), None, None
    if out == "echo_core":
        from dynamo_tpu.llm.backend import Backend
        from dynamo_tpu.llm.local_model import LocalModel
        from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
        from dynamo_tpu.runtime.pipeline.engine import link

        if not args.model_path:
            raise SystemExit("out=echo_core needs --model-path (tokenizer)")
        lm = LocalModel.prepare(args.model_path, name=args.model_name)
        pipeline = link(
            OpenAIPreprocessor(lm.card), Backend.from_card(lm.card), EchoEngineCore()
        )
        return pipeline, lm.card, None
    if out == "jax":
        from dynamo_tpu.llm.backend import Backend
        from dynamo_tpu.llm.local_model import LocalModel
        from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
        from dynamo_tpu.runtime.pipeline.engine import link

        if not args.model_path:
            raise SystemExit("out=jax needs --model-path")
        lm = LocalModel.prepare(args.model_path, name=args.model_name)
        engine = lm.build_engine(**build_engine_config_kwargs(args))
        pipeline = link(
            OpenAIPreprocessor(lm.card), Backend.from_card(lm.card), engine
        )
        return pipeline, lm.card, engine
    raise SystemExit(f"unknown out={out!r}")


# ---------------------------------------------------------------- in= modes


async def build_http_service(args, out: str):
    """The OpenAI HTTP service for `in=http`, wired to `out` but not yet
    listening. Returns (service, jax_engine|None) so embedders (and
    chip_smoke.py) can start it on a port of their choosing and read
    the engine's own report."""
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.utils import instance, tracing

    # frontend process label for the merged trace (workers name
    # themselves at engine start; DYN_TRACE_PROCESS and earlier callers
    # win — first-wins lives in set_process_default)
    tracing.set_process_default("frontend")
    template = None
    if args.request_template:
        from dynamo_tpu.llm.request_template import RequestTemplate

        template = RequestTemplate.load(args.request_template)
    admission = build_admission(args)
    svc = HttpService(
        request_template=template, request_timeout_s=args.request_timeout,
        admission=admission,
    )
    # process-global health counters (hub reconnects, lease expiries,
    # transport retries, breaker trips, injected faults) ride the same
    # /metrics scrape as the service + engine series
    from dynamo_tpu.utils.counters import PromCounters

    svc.metrics.extra.append(PromCounters())
    engine = None
    if out.startswith("dyn://"):
        # ingress: discover models from the hub
        from dynamo_tpu.llm.http.discovery import ModelWatcher
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        drt = await DistributedRuntime.from_settings(hub_addr=args.hub)
        watcher = ModelWatcher(
            drt, svc.manager, router_mode=args.router_mode,
            # armed admission needs overload signals in EVERY router
            # mode: non-kv modes start a standalone stats aggregator
            # per discovered service (docs/control.md)
            collect_stats=admission is not None,
        )
        await watcher.start()
        if admission is not None:
            _bind_ingress_admission(admission, watcher)
        if tracing.enabled():
            # fleet trace plane: collect spans shipped by workers so
            # /debug/trace renders ONE merged timeline across processes
            # (held on the service: the loop references tasks weakly, a
            # fire-and-forget aggregator could be GC'd mid-serve)
            from dynamo_tpu.runtime.trace_plane import TraceAggregator

            svc.trace_aggregator = await TraceAggregator(drt.hub).start()
        # NOTE: no SloTracker on the ingress scrape — attainment is
        # measured where requests finish (the workers), rides their
        # stats replies, and aggregates via KvMetricsAggregator /
        # metrics_export. Rendering an unfed tracker here would pin
        # every series at 1.0 and read "all SLOs attained" during a
        # fleet-wide breach.
    else:
        pipeline, card, engine = await build_output(args, out)
        name = args.model_name or (card.display_name if card else "echo")
        svc.manager.add_chat_model(name, pipeline)
        svc.manager.add_completion_model(name, pipeline)
        if engine is not None:
            # one scrape covers service + engine: Engine.metrics() gauges
            # and the TTFT/ITL/queue-wait/tokens histograms render through
            # the /metrics endpoint via the ServiceMetrics.extra hook,
            # labeled with the stable instance id and feeding the SLO
            # attainment tracker when targets are configured
            from dynamo_tpu.llm.http.metrics import EngineMetrics

            slo = build_slo_tracker(args)
            if slo is not None and getattr(engine, "flight", None) is not None:
                # forensics plane: an SLO breach dumps the correlated
                # flight-recorder artifact (digest window + the
                # breaching request's trace slice) the moment it lands —
                # rate-limited recorder-side (docs/observability.md)
                slo.on_breach = engine.flight.on_slo_breach
            svc.metrics.extra.append(
                EngineMetrics(
                    engine, slo=slo,
                    worker_id=instance.worker_id(),
                )
            )
            if admission is not None:
                # local signals: the engine's own waiting depth + the
                # local tracker's worst rolling fraction
                def _local_attain():
                    snap = slo.snapshot() if slo is not None else {}
                    return min(snap.values()) if snap else None

                admission.bind(
                    queue_depth_fn=lambda: float(
                        engine.metrics().get("num_requests_waiting", 0)
                    ),
                    attainment_fn=_local_attain,
                )
    return svc, engine


async def run_http(args, out: str) -> None:
    svc, _engine = await build_http_service(args, out)
    await svc.start(args.http_host, args.http_port)
    log.info("serving OpenAI HTTP on %s:%d", args.http_host, svc.port)
    await asyncio.Event().wait()


async def run_worker(args, inp: str, out: str) -> None:
    """in=dyn://ns.comp.ep: register as a worker on the hub."""
    from dynamo_tpu.llm.http.discovery import register_llm
    from dynamo_tpu.llm.kv_router import KvEventPublisher, KvMetricsPublisher
    from dynamo_tpu.llm.local_model import LocalModel
    from dynamo_tpu.runtime.component import EndpointId
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    if out != "jax" and not out.startswith("echo"):
        raise SystemExit("worker mode needs out=jax or out=echo_*")
    drt = await DistributedRuntime.from_settings(hub_addr=args.hub)
    eid = EndpointId.parse(inp)

    from dynamo_tpu.runtime import trace_plane
    from dynamo_tpu.utils import instance

    if trace_plane.export_enabled():
        # ship this worker's spans to the hub trace subject so the
        # frontend's /debug/trace merges them (docs/observability.md
        # "Fleet plane"); no-op unless DYN_TRACE armed recording. Held
        # on the runtime: the loop references tasks weakly, and a
        # fire-and-forget shipper could be GC'd mid-serve.
        drt.trace_shipper = trace_plane.SpanShipper(drt.hub).start()

    if out.startswith("echo"):
        from dynamo_tpu.llm.engines import EchoEngineCore

        lm = LocalModel.prepare(args.model_path, name=args.model_name)
        await register_llm(drt, EchoEngineCore(), lm.card, inp)
        log.info("echo worker serving %s", inp)
        await asyncio.Event().wait()
        return

    lm = LocalModel.prepare(args.model_path, name=args.model_name)
    engine = lm.build_engine(**build_engine_config_kwargs(args))
    lm.card.kv_cache_block_size = args.page_size
    component = drt.namespace(eid.namespace).component(eid.component)
    # SLO attainment (per-tenant targets): the tracker feeds off the
    # engine's finish summaries and its window fractions ride every
    # stats reply, so the aggregator sees fleet attainment
    slo = build_slo_tracker(args)
    if slo is not None:
        engine.subscribe_requests(slo.observe)
        if getattr(engine, "flight", None) is not None:
            # breach -> forensic artifact, worker-side too (the trace
            # slice still joins the frontend via the shipped spans)
            slo.on_breach = engine.flight.on_slo_breach

    if args.disagg_mode == "prefill":
        from dynamo_tpu.llm.disagg import PrefillHandler

        PrefillHandler(drt, engine, eid.namespace, eid.component).start()
        log.info("prefill worker on queue for %s.%s", eid.namespace, eid.component)
        await asyncio.Event().wait()
        return

    serving_engine = engine
    disagg_stats = None
    if args.disagg_mode == "decode":
        from dynamo_tpu.llm.disagg import (
            DisaggConfig,
            DisaggDecodeWorker,
            DisaggRouter,
        )

        worker = DisaggDecodeWorker(
            drt, engine, eid.namespace, eid.component,
            router=DisaggRouter(
                drt, model=lm.card.display_name,
                config=DisaggConfig(
                    max_local_prefill_length=args.max_local_prefill_length
                ),
            ),
        )
        await worker.attach()
        serving_engine = worker
        # remote/local prefill counts + live queue depth ride the stats
        # replies (ForwardPassMetrics.disagg) so the controller's inputs
        # are scrape-visible via metrics_export
        disagg_stats = worker.stats
    metrics = KvMetricsPublisher.for_engine(
        engine, slo=slo, disagg_source=disagg_stats
    )

    # cross-worker prefix pulls (docs/kv_cache.md): serve this worker's
    # cached prefixes on the component's kv_export subject, and execute
    # router pull decisions (Context metadata kv_pull_from) before the
    # engine serves — requests without the metadata pass straight through
    from dynamo_tpu.llm.kv_router.pull import KvExportHandler, PrefixPuller

    await KvExportHandler(drt, engine, eid.namespace, eid.component).start()
    serving_engine = PrefixPuller(drt, serving_engine, engine, eid)

    # attach the event publisher BEFORE the worker becomes discoverable:
    # events from requests arriving in the gap would be lost forever (the
    # indexer has no replay)
    KvEventPublisher(component, drt.primary_lease.lease_id).attach(engine).start()
    await register_llm(
        drt, serving_engine, lm.card, inp, stats_handler=metrics.stats_handler,
        # echo the stable instance label minted at engine start so hub
        # consumers join InstanceInfo to logs/Prometheus/trace tracks
        metadata={"instance": instance.worker_id()},
    )
    log.info("worker (%s) serving %s", args.disagg_mode, inp)
    await asyncio.Event().wait()


async def _chat_once(pipeline, model: str, messages: list, max_tokens: int):
    from dynamo_tpu.llm.protocols.openai import ChatCompletionRequest
    from dynamo_tpu.runtime.pipeline.context import Context

    req = ChatCompletionRequest.from_body(
        {"model": model, "messages": messages, "max_tokens": max_tokens}
    )
    t0 = time.perf_counter()
    ttft = None
    text = ""
    async for chunk in await pipeline.generate(Context(req)):
        if chunk.get("__annotation__"):
            continue
        for choice in chunk.get("choices") or []:
            piece = (choice.get("delta") or {}).get("content")
            if piece:
                if ttft is None:
                    ttft = time.perf_counter() - t0
                text += piece
                print(piece, end="", flush=True)
    print()
    return text, ttft, time.perf_counter() - t0


async def run_text(args, out: str) -> None:
    pipeline, card, _ = await build_output(args, out)
    model = args.model_name or (card.display_name if card else "echo")
    messages: list = []
    print(f"chat with {model} — empty line or ^D to quit")
    while True:
        try:
            line = await asyncio.to_thread(input, "> ")
        except EOFError:
            return
        if not line.strip():
            return
        messages.append({"role": "user", "content": line})
        text, _, _ = await _chat_once(pipeline, model, messages, args.max_tokens)
        messages.append({"role": "assistant", "content": text})


async def run_stdin(args, out: str) -> None:
    pipeline, card, _ = await build_output(args, out)
    model = args.model_name or (card.display_name if card else "echo")
    prompt = sys.stdin.read().strip()
    await _chat_once(pipeline, model, [{"role": "user", "content": prompt}],
                     args.max_tokens)


async def run_batch(args, out: str, path: str) -> None:
    """JSONL file of {"text": ...} prompts; writes outputs + latency stats
    (reference: launch/dynamo-run/src/input/batch.rs:44-280)."""
    pipeline, card, _ = await build_output(args, out)
    model = args.model_name or (card.display_name if card else "echo")
    ttfts, totals = [], []
    out_path = path + ".out.jsonl"
    with open(path) as f, open(out_path, "w") as of:
        for line in f:
            if not line.strip():
                continue
            item = json.loads(line)
            text, ttft, total = await _chat_once(
                pipeline, model,
                [{"role": "user", "content": item["text"]}], args.max_tokens,
            )
            ttfts.append(ttft or 0.0)
            totals.append(total)
            of.write(json.dumps({"input": item["text"], "output": text}) + "\n")
    if ttfts:
        import statistics

        print(
            f"batch done: n={len(ttfts)} "
            f"ttft_p50={statistics.median(ttfts) * 1000:.1f}ms "
            f"total_p50={statistics.median(totals) * 1000:.1f}ms "
            f"-> {out_path}"
        )


def main(argv: Optional[list[str]] = None) -> None:
    configure_logging()
    args = build_parser().parse_args(argv)
    inp, out = parse_io(args.io)

    if args.num_nodes > 1:
        from dynamo_tpu.parallel.multihost import MultiHostConfig, initialize

        initialize(
            MultiHostConfig(
                num_nodes=args.num_nodes,
                node_rank=args.node_rank,
                coordinator=args.coordinator,
            )
        )

    if inp == "http":
        coro = run_http(args, out)
    elif inp == "text":
        coro = run_text(args, out)
    elif inp == "stdin":
        coro = run_stdin(args, out)
    elif inp.startswith("batch:"):
        coro = run_batch(args, out, inp[len("batch:"):])
    elif inp.startswith("dyn://"):
        coro = run_worker(args, inp, out)
    else:
        raise SystemExit(f"unknown in={inp!r}")
    try:
        asyncio.run(coro)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
