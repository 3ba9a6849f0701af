"""Request-failover FLEET scenario: prove in-flight requests survive
worker death (docs/robustness.md "Request failover").

PR 6's breakers and PR 8's control loop recover the FLEET after a
worker dies; this scenario scores the missing third leg — the REQUESTS
that were streaming on the dead worker. Real components in one process:

    HubServer <- N x { JaxEngine + KvEventPublisher + KvMetricsPublisher
                       + KvExportHandler + PrefixPuller }   (workers on
        ^              the real data plane)
    frontend: discovery Client -> KvPushRouter (prefix-overlap routing)
              -> FailoverEngine (journal + replay) -> LIVE HttpService
              (greedy SSE streams over a real socket)

Three legs, each on a fresh fleet:

1. **cold** — concurrent greedy SSE streams; a ``dataplane.die`` fault
   (the DYN_FAULTS grammar, utils/faults.py) severs the serving
   worker's whole data plane mid-stream — on the wire identical to a
   SIGKILLed process. Every stream must complete **byte-identical** to
   the reference serve with zero duplicated or skipped tokens; the
   replay recomputes the continuation prompt (the recompute bar).
2. **reuse** — the stream prompts' shared prefix is warm on EVERY
   worker before the kill, so the KV-aware replay routes to a surviving
   holder and rides its prefix cache: ``reused`` continuation tokens
   replace recompute.
3. **pull** — the prefix is held ONLY by a saturated worker; the replay
   lands on an idle worker that PULLS the prefix from the holder
   (``kv_export`` -> ``ingest_prefix``, the PR 9 path) instead of
   recomputing it: ``pull`` tokens on the replay serve.

The reuse/pull kills abort the observed serving worker's data plane
directly (`DataPlaneServer._die_abruptly`, the exact action the
``dataplane.die`` fault point maps to) so the victim deterministically
holds live streams; the cold leg goes through the fault registry
itself to prove the DYN_FAULTS story end to end.

Scored: per-leg and pooled ``recovered_frac`` (broken streams that
finished clean),
``replay_ttft_gap_p50_s`` (how long the client stalled across the
death), and the continuation-token economics (recompute vs reused vs
pulled). Run directly it prints the JSON and exits non-zero when the
proof failed (a stream repeated/gapped a token, a broken stream was
lost, or the reuse/pull legs recomputed).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

from dynamo_tpu.engine.kv_ledger import quiesce_census  # noqa: E402
from dynamo_tpu.runtime.component import EndpointId  # noqa: E402
from dynamo_tpu.runtime.distributed import DistributedRuntime  # noqa: E402
from dynamo_tpu.runtime.hub.server import HubServer  # noqa: E402
from dynamo_tpu.runtime.pipeline.context import Context  # noqa: E402
from dynamo_tpu.utils import counters, faults  # noqa: E402

NS, COMP, EP = "failover", "backend", "generate"

# counter keys snapshotted around every chaos burst (deltas = the score)
_KEYS = (
    "failover_replays_total",
    "failover_recovered_total",
    "failover_giveup_total",
    "failover_storm_shed_total",
    "failover_recompute_tokens_total",
    "failover_reused_tokens_total",
    "failover_pull_tokens_total",
)


def _defaults() -> dict:
    """Tiny-scale defaults (CPU CI finishes the three legs in ~1 min)."""
    return dict(
        page=16,               # KV page size (gather backend)
        prefix_pages=4,        # shared-prefix pages (reuse/pull legs)
        suffix=8,              # per-request fresh suffix tokens
        osl=32,                # generated tokens per stream
        streams=4,             # concurrent streams per chaos burst
        pull_streams=1,        # pull-leg streams: ONE, so the replay's
        #                        target is an idle worker that has never
        #                        seen the prefix (a second stream's own
        #                        first-serve pull would pre-warm it and
        #                        the replay would score as reuse)
        max_batch=4,           # decode slots per worker
        num_pages=256,
        hold_osl=96,           # held-stream length saturating the holder
        pull_threshold_pages=2,
        pull_busy_frac=0.7,    # saturation bar: the holder's looping
        #                        hold lanes dip a slot between rounds,
        #                        and a scrape catching the dip must not
        #                        read the holder as idle
        poll_interval_s=5.0,   # aggregator cadence (cold/reuse legs:
        #                        stats arrivals must not swallow the
        #                        frame-counted fault hit)
        pull_poll_interval_s=0.25,  # pull leg needs fresh saturation
        retry_budget=2,        # DYN_FAILOVER_RETRIES equivalent
    )


def _cfgs(d: dict):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.models import config as cfgmod

    mcfg = cfgmod.get_config("tiny")
    isl = d["prefix_pages"] * d["page"] + d["suffix"]
    ecfg = EngineConfig(
        model=mcfg, dtype="float32", page_size=d["page"],
        num_pages=d["num_pages"], max_batch_size=d["max_batch"],
        max_model_len=isl + max(d["osl"], d["hold_osl"]) + 32,
        prefill_chunk=isl,
        # routing/replay economics, not kernels: the gather oracle runs
        # identically on CPU CI and on a chip
        attn_backend="gather",
    )
    return mcfg, ecfg, isl


@contextlib.asynccontextmanager
async def _fleet(
    d: dict, n_workers: int, poll_interval: float, census_out=None
):
    """Hub + n real workers (full KV plane) + the frontend failover
    stack behind a live HttpService; yields a handle dict.

    With `census_out` (a list), the teardown runs the zero-orphan
    quiesce census over the leg's engines BEFORE closing them and
    appends the result — a chaos-killed worker's engine outlives its
    data plane, so its severed streams' pages must still drain."""
    from dynamo_tpu.engine import JaxEngine
    from dynamo_tpu.llm.http.discovery import RouterEngine
    from dynamo_tpu.llm.http.engine_service import engine_http_service
    from dynamo_tpu.llm.http.failover import FailoverConfig, FailoverEngine
    from dynamo_tpu.llm.kv_router import (
        KvEventPublisher,
        KvMetricsPublisher,
        KvPushRouter,
        KvRouter,
    )
    from dynamo_tpu.llm.kv_router.pull import KvExportHandler, PrefixPuller

    mcfg, ecfg, isl = _cfgs(d)
    hub = HubServer()
    await hub.start("127.0.0.1", 0)
    hub_addr = f"127.0.0.1:{hub.port}"
    eid = EndpointId(NS, COMP, EP)
    drts, engines = [], []
    try:
        for _ in range(n_workers):
            drt = await DistributedRuntime.from_settings(hub_addr=hub_addr)
            drts.append(drt)
            engine = JaxEngine(ecfg)
            engines.append(engine)
            ep = drt.namespace(NS).component(COMP).endpoint(EP)
            KvEventPublisher(
                ep.component, drt.primary_lease.lease_id
            ).attach(engine).start()
            await KvExportHandler(drt, engine, NS, COMP).start()
            puller = PrefixPuller(drt, engine, engine, eid)
            metrics = KvMetricsPublisher.for_engine(engine)
            await ep.serve_engine(puller, stats_handler=metrics.stats_handler)

        fe = await DistributedRuntime.from_settings(hub_addr=hub_addr)
        drts.append(fe)
        ep = fe.namespace(NS).component(COMP).endpoint(EP)
        client = await ep.client()
        for _ in range(200):
            if len(client.instance_ids()) >= n_workers:
                break
            await asyncio.sleep(0.05)
        router = KvRouter(
            ep.component, client, block_size=d["page"],
            poll_interval=poll_interval,
            pull_threshold_tokens=d["pull_threshold_pages"] * d["page"],
            pull_busy_frac=d["pull_busy_frac"],
        )
        await router.start()
        push = KvPushRouter(client, router)
        eng = FailoverEngine(
            RouterEngine(client, "kv", kv_router=push),
            client=client, drt=fe,
            cfg=FailoverConfig(max_retries=d["retry_budget"]),
        )
        async with engine_http_service(
            eng, vocab_size=mcfg.vocab_size
        ) as svc:
            yield {
                "failover": eng,
                "engines": engines,
                "worker_drts": drts[:n_workers],
                "client": client,
                "router": router,
                "svc": svc,
                "vocab": mcfg.vocab_size,
                "isl": isl,
            }
    finally:
        if census_out is not None:
            with contextlib.suppress(Exception):
                census_out.append(
                    await asyncio.to_thread(quiesce_census, engines)
                )
        for e in engines:
            with contextlib.suppress(Exception):
                await e.close()
        for drt in drts:
            with contextlib.suppress(Exception):
                await drt.shutdown()
        await hub.stop()


async def _warm_compile(fleet, d: dict, rng) -> None:
    """Pay every worker's prefill/decode + warm-continuation compile
    families before anything is measured."""
    for engine in fleet["engines"]:
        wp = rng.randint(1, fleet["vocab"], size=fleet["isl"]).tolist()
        for _ in range(2):
            await _direct_serve(engine, wp, d["osl"] // 4)


async def _direct_serve(engine, tokens, osl: int) -> list[int]:
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    pre = PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True),
    )
    out = []
    async for frame in await engine.generate(Context(pre.to_dict())):
        out.extend(frame.get("token_ids") or [])
    return out


async def _sse_stream(session, tokens, osl: int, rid: str) -> dict:
    """One greedy SSE stream; returns {ttft, texts, ok, error}."""
    body = {
        "model": "loadgen", "prompt": list(tokens), "stream": True,
        "max_tokens": osl,
        "dyn_ext": {"ignore_eos": True, "greed_sampling": True},
    }
    t0 = time.perf_counter()
    texts: list[str] = []
    ttft = None
    try:
        async with session.post(
            "/v1/completions", json=body, headers={"x-request-id": rid}
        ) as resp:
            if resp.status != 200:
                return {"ok": False, "ttft": None, "texts": texts,
                        "error": f"http {resp.status}"}
            async for raw in resp.content:
                line = raw.decode().rstrip("\n")
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data == "[DONE]":
                    break
                item = json.loads(data)
                text = "".join(
                    c.get("text") or "" for c in item.get("choices") or []
                )
                if text:
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    texts.append(text)
    except Exception as exc:  # noqa: BLE001 — a broken stream is data
        return {"ok": False, "ttft": ttft, "texts": texts,
                "error": f"{type(exc).__name__}: {exc}"}
    return {"ok": True, "ttft": ttft, "texts": texts, "error": None}


def _stream_ids(out: dict) -> list[str]:
    return "".join(out["texts"]).split()


async def _abort_serving_worker(fleet, victims: list[int]) -> int:
    """Wait until one of `victims` (engine indexes) is serving a
    journaled stream that has DELIVERED tokens (strictly mid-stream,
    not during prefill), then sever its whole data plane — the
    worker.die action, targeted so the death deterministically breaks
    live streams."""
    wids = {
        fleet["worker_drts"][i].primary_lease.lease_id: i for i in victims
    }
    for _ in range(4000):
        for s in fleet["failover"].live_streams():
            i = wids.get(s["instance"])
            if i is not None and s["emitted"] >= 2:
                fleet["worker_drts"][i].data_plane._die_abruptly()
                return i
        await asyncio.sleep(0.005)
    raise RuntimeError(f"no victim among {victims} ever served a stream")


def _leg_score(
    name: str, before: dict, outs: list[dict], refs: list[list[int]],
    replays_before: int, ttft_nofault: list,
) -> dict:
    from dynamo_tpu.llm.http import failover as fomod

    delta = {k: int(counters.get(k) - before[k]) for k in _KEYS}
    new_recs = fomod.recent_replays()[replays_before:]
    gaps = [r["gap_s"] for r in new_recs if r["gap_s"] is not None]
    identical = [
        _stream_ids(o) == [str(t) for t in ref]
        for o, ref in zip(outs, refs)
    ]
    broken = delta["failover_replays_total"] + delta["failover_giveup_total"]
    failures = (
        delta["failover_giveup_total"]
        + delta["failover_storm_shed_total"]
        + sum(1 for o in outs if not o["ok"])
    )
    ttfts = sorted(o["ttft"] for o in outs if o["ttft"] is not None)
    return {
        "streams": len(outs),
        "byte_identical": all(identical),
        "mismatches": [i for i, ok in enumerate(identical) if not ok],
        "broken": broken,
        "recovered": delta["failover_recovered_total"],
        "failed": failures,
        "recovered_frac": (
            round((broken - failures) / broken, 4) if broken else None
        ),
        "replay_gap_p50_s": (
            round(float(np.percentile(gaps, 50)), 4) if gaps else None
        ),
        "ttft_nofault_p50_s": (
            round(float(np.percentile(ttft_nofault, 50)), 4)
            if ttft_nofault else None
        ),
        "ttft_chaos_p50_s": (
            round(float(np.percentile(ttfts, 50)), 4) if ttfts else None
        ),
        "tokens": {
            "recompute": delta["failover_recompute_tokens_total"],
            "reused": delta["failover_reused_tokens_total"],
            "pull": delta["failover_pull_tokens_total"],
        },
        "replays": new_recs,
    }


async def _chaos_burst(fleet, session, prompts, refs, osl, kill) -> tuple:
    """Launch the streams, fire `kill` once they are mid-flight, gather.
    `kill` is ("faults", spec) or ("abort", [victim engine indexes])."""
    if kill[0] == "faults":
        faults.configure(kill[1])
        killer = None
    else:
        killer = asyncio.create_task(_abort_serving_worker(fleet, kill[1]))
    outs = await asyncio.gather(*(
        _sse_stream(session, p, osl, f"chaos-{i}")
        for i, p in enumerate(prompts)
    ))
    victim = None
    if killer is not None:
        with contextlib.suppress(Exception):
            victim = await asyncio.wait_for(killer, 5)
    faults.reset()
    return outs, victim


async def run_scenario(**overrides) -> dict:
    import aiohttp

    from dynamo_tpu.engine import JaxEngine
    from dynamo_tpu.llm.http import failover as fomod

    d = {**_defaults(), **overrides}
    rng = np.random.RandomState(11)
    mcfg, ecfg, isl = _cfgs(d)
    osl = d["osl"]

    # byte-identity oracle: a standalone engine with the identical
    # config serves every chaos prompt once — greedy decode is
    # deterministic across same-config engines, so these ARE the tokens
    # an uninterrupted fleet serve would stream
    ref_engine = JaxEngine(ecfg)

    async def refs_for(prompts):
        out = []
        for p in prompts:
            out.append(await _direct_serve(ref_engine, p, osl))
        return out

    def fresh_prompts(n):
        return [
            rng.randint(1, mcfg.vocab_size, size=isl).tolist()
            for _ in range(n)
        ]

    def prefixed_prompts(prefix, n):
        return [
            list(prefix)
            + rng.randint(1, mcfg.vocab_size, size=d["suffix"]).tolist()
            for _ in range(n)
        ]

    legs: dict[str, dict] = {}
    censuses: list[dict] = []
    try:
        # ---- leg 1: cold (DYN_FAULTS kill, recompute replay) ----------
        async with _fleet(
            d, 2, d["poll_interval_s"], census_out=censuses
        ) as fleet:
            await _warm_compile(fleet, d, rng)
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{fleet['svc'].port}"
            ) as session:
                bar = await asyncio.gather(*(
                    _sse_stream(session, p, osl, f"bar-{i}")
                    for i, p in enumerate(fresh_prompts(d["streams"]))
                ))
                ttft_bar = [o["ttft"] for o in bar if o["ttft"] is not None]
                # the fault fires on the Nth data-plane frame after
                # arming — mid-flight of the stream wave
                spec = f"dataplane.die.fail@{d['streams'] * osl // 2}x1"
                for attempt in range(2):
                    prompts = fresh_prompts(d["streams"])
                    refs = await refs_for(prompts)
                    before = {k: counters.get(k) for k in _KEYS}
                    n_recs = len(fomod.recent_replays())
                    outs, _ = await _chaos_burst(
                        fleet, session, prompts, refs, osl, ("faults", spec)
                    )
                    legs["cold"] = _leg_score(
                        "cold", before, outs, refs, n_recs, ttft_bar
                    )
                    legs["cold"]["faults"] = spec
                    if legs["cold"]["broken"] >= 1:
                        break
                    # the one-shot fault can land on a stats frame of a
                    # stream-less worker; re-arm once on fresh prompts

        # ---- leg 2: reuse (prefix warm fleet-wide; replay rides the
        # survivor's cache) ---------------------------------------------
        async with _fleet(
            d, 2, d["poll_interval_s"], census_out=censuses
        ) as fleet:
            await _warm_compile(fleet, d, rng)
            prefix = rng.randint(
                1, mcfg.vocab_size, size=d["prefix_pages"] * d["page"]
            ).tolist()
            for engine in fleet["engines"]:
                await _direct_serve(
                    engine,
                    prefix + rng.randint(
                        1, mcfg.vocab_size, size=2
                    ).tolist(),
                    2,
                )
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{fleet['svc'].port}"
            ) as session:
                prompts = prefixed_prompts(prefix, d["streams"])
                refs = await refs_for(prompts)
                before = {k: counters.get(k) for k in _KEYS}
                n_recs = len(fomod.recent_replays())
                outs, victim = await _chaos_burst(
                    fleet, session, prompts, refs, osl, ("abort", [0, 1])
                )
                legs["reuse"] = _leg_score(
                    "reuse", before, outs, refs, n_recs, []
                )
                legs["reuse"]["victim"] = victim

        # ---- leg 3: pull (prefix only on a saturated holder; the
        # replay PULLS it instead of recomputing) -----------------------
        async with _fleet(
            d, 3, d["pull_poll_interval_s"], census_out=censuses
        ) as fleet:
            await _warm_compile(fleet, d, rng)
            prefix = rng.randint(
                1, mcfg.vocab_size, size=d["prefix_pages"] * d["page"]
            ).tolist()
            holder = 0
            await _direct_serve(
                fleet["engines"][holder],
                prefix + rng.randint(1, mcfg.vocab_size, size=2).tolist(),
                2,
            )
            want_blocks = d["prefix_pages"]
            for _ in range(200):
                if fleet["router"].indexer.tree.num_blocks >= want_blocks:
                    break
                await asyncio.sleep(0.05)
            # byte-identity refs BEFORE saturating (the ref engine must
            # not compete with the held lanes for CPU)
            prompts = prefixed_prompts(prefix, d["pull_streams"])
            refs = await refs_for(prompts)
            # saturate the holder and KEEP it saturated: each lane
            # re-serves as soon as its stream finishes, so the
            # aggregator reads full slots at the first-serve decision
            # AND at the replay decision after the kill
            stop_hold = asyncio.Event()

            async def hold_lane(lane_prompt):
                # ONE fixed prompt per lane, re-served in a loop: slots
                # stay full but the holder's cache usage stays bounded
                # (fresh prompts each round would balloon usage and sink
                # the holder's selector logit below the idle workers —
                # then the replay routes cold and no pull ever fires)
                while not stop_hold.is_set():
                    with contextlib.suppress(Exception):
                        await _direct_serve(
                            fleet["engines"][holder], lane_prompt,
                            d["hold_osl"],
                        )

            # max_batch + 2 lanes: the two surplus lanes keep the
            # holder's WAITING queue non-empty, so a scrape landing in
            # a lane-restart dip still reads saturated (the router's
            # _saturated() honors queue depth as well as slots)
            held = [
                asyncio.create_task(hold_lane(
                    rng.randint(1, mcfg.vocab_size, size=isl).tolist()
                ))
                for _ in range(d["max_batch"] + 2)
            ]
            agg = fleet["router"].aggregator
            holder_wid = fleet["worker_drts"][holder].primary_lease.lease_id
            for _ in range(400):
                m = agg.current.endpoints.get(holder_wid)
                if m is not None and m.request_active_slots >= d["max_batch"]:
                    break
                await asyncio.sleep(d["pull_poll_interval_s"] / 2)
            async with aiohttp.ClientSession(
                f"http://127.0.0.1:{fleet['svc'].port}"
            ) as session:
                before = {k: counters.get(k) for k in _KEYS}
                n_recs = len(fomod.recent_replays())
                outs, victim = await _chaos_burst(
                    fleet, session, prompts, refs, osl, ("abort", [1, 2])
                )
                legs["pull"] = _leg_score(
                    "pull", before, outs, refs, n_recs, []
                )
                legs["pull"]["victim"] = victim
                legs["pull"]["pulls_landed"] = int(
                    counters.get("kv_pull_landed_total")
                )
            stop_hold.set()
            for t in held:
                t.cancel()
            with contextlib.suppress(Exception):
                await asyncio.gather(*held, return_exceptions=True)
    finally:
        with contextlib.suppress(Exception):
            await ref_engine.close()
        faults.reset()

    gaps = [
        r["gap_s"] for leg in legs.values() for r in leg["replays"]
        if r["gap_s"] is not None
    ]
    broken = sum(leg["broken"] for leg in legs.values())
    failed = sum(leg["failed"] for leg in legs.values())
    tokens = {
        k: sum(leg["tokens"][k] for leg in legs.values())
        for k in ("recompute", "reused", "pull")
    }
    # zero-orphan gate: every leg's fleet drained custody at teardown —
    # a chaos kill that stranded KV pages fails the proof even when all
    # the streams came back byte-identical
    cviol: dict[str, int] = {}
    for c in censuses:
        for k, v in (c.get("violations") or {}).items():
            cviol[k] = cviol.get(k, 0) + int(v)
    kv_census = {
        "fleets": len(censuses),
        "engines": sum(c["engines"] for c in censuses),
        "ok": bool(censuses) and all(c["ok"] for c in censuses),
        "orphan_pages": sum(
            len(c.get("orphan_pages") or []) for c in censuses
        ),
        "violations": cviol,
        "per_fleet": censuses,
    }
    return {
        "scenario": {
            k: d[k]
            for k in ("page", "prefix_pages", "suffix", "osl", "streams",
                      "pull_streams", "max_batch", "retry_budget")
        },
        "legs": legs,
        "byte_identical": all(leg["byte_identical"] for leg in legs.values()),
        "broken_streams": broken,
        "recovered_frac": (
            round((broken - failed) / broken, 4) if broken else None
        ),
        "replay_ttft_gap_p50_s": (
            round(float(np.percentile(gaps, 50)), 4) if gaps else None
        ),
        "tokens": tokens,
        "kv_census": kv_census,
    }


def run(**overrides) -> dict:
    return asyncio.run(run_scenario(**overrides))


def proof_ok(out: dict) -> bool:
    legs = out["legs"]
    return bool(
        out["byte_identical"]
        and out["recovered_frac"] == 1.0
        and out["broken_streams"] >= 2
        and legs["cold"]["tokens"]["recompute"] > 0
        and legs["reuse"]["tokens"]["reused"] > 0
        and legs["pull"]["tokens"]["pull"] > 0
        and out["kv_census"]["ok"]
    )


if __name__ == "__main__":
    out = run()
    print(json.dumps(out, indent=2))
    if not proof_ok(out):
        print("request failover proof FAILED", file=sys.stderr)
        sys.exit(1)
    print(
        f"failover proof: {out['broken_streams']} broken streams all "
        f"recovered byte-identical; replay gap p50 "
        f"{out['replay_ttft_gap_p50_s']}s; tokens {out['tokens']}",
        file=sys.stderr,
    )
    sys.exit(0)
