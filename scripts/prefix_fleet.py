"""Multi-tenant shared-prefix FLEET scenario: prove the KV cache plane
pays at the fleet level, not just inside one engine (docs/kv_cache.md).

Million-user traffic is dominated by shared prefixes (system prompts,
few-shot templates, multi-turn). This scenario replays that shape
against the whole KV plane with REAL components in one process:

    HubServer <- 2 x { JaxEngine + KvEventPublisher + KvMetricsPublisher
                       + KvExportHandler + PrefixPuller }  (workers)
        ^
    KvPushRouter (radix indexer fed live engine events, tier-weighted
    selector, saturation-aware cross-worker pull decision)

Phases:

1. **cold** — T tenants, each with a distinct shared prefix (several
   full pages) + a per-request suffix, routed through the KV router;
   nothing is cached anywhere. Tenant TTFTs here are the recompute bar.
2. **warm** — fresh suffixes on the same tenant prefixes. The router's
   indexer has ingested the workers' stored-block events, so requests
   route to the worker already holding their prefix and ride its cache.
   Scored: warm-vs-cold TTFT (target >= 1.3x on TPU), the fraction
   routed back to the holder, and the fraction whose ledger shows real
   block reuse.
3. **pull** — the holder of one tenant's prefix is SATURATED (held
   decode streams fill its slots). New requests for that tenant would
   previously recompute the prefix on the idle worker; now the router
   routes them there with ``kv_pull_from`` metadata and the worker
   PULLS the prefix from the holder (export_prefix -> ingest_prefix)
   instead. Scored: pulls landed + tokens moved.

Run directly it prints one JSON dict and exits non-zero when the plane
failed (no routing reuse, no pull landed, or a page left in custody).
"""

from __future__ import annotations

import asyncio
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
from dynamo_tpu.utils import counters  # noqa: E402

NS, COMP, EP = "fleet", "backend", "generate"


def _defaults() -> dict:
    """Tiny-scale defaults (CPU CI finishes in well under a minute)."""
    return dict(
        tenants=4,            # distinct shared prefixes
        page=16,              # KV page/block size (gather backend)
        prefix_pages=6,       # full pages per shared prefix
        suffix=8,             # fresh per-request suffix tokens
        osl=8,                # generated tokens per request
        cold_per_tenant=1,
        warm_per_tenant=3,
        pull_requests=2,      # pull-phase requests on the saturated tenant
        max_batch=2,          # worker decode slots (saturation = 2 held)
        num_pages=256,
        hold_osl=64,          # held-stream length during the pull phase
        pull_threshold_pages=2,
        poll_interval=0.25,   # aggregator scrape cadence
    )


async def run_scenario(**overrides) -> dict:
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.kv_router import (
        KvEventPublisher,
        KvMetricsPublisher,
        KvPushRouter,
        KvRouter,
    )
    from dynamo_tpu.llm.kv_router.pull import KvExportHandler, PrefixPuller
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models import config as cfgmod

    d = {**_defaults(), **overrides}
    page = d["page"]
    prefix_len = d["prefix_pages"] * page
    isl = prefix_len + d["suffix"]
    cfg = cfgmod.get_config("tiny")
    rng = np.random.RandomState(7)

    def engine_config() -> EngineConfig:
        return EngineConfig(
            model=cfg, dtype="float32", page_size=page,
            num_pages=d["num_pages"], max_batch_size=d["max_batch"],
            max_model_len=isl + d["hold_osl"] + 32,
            prefill_chunk=isl,
            # the scenario scores routing/transfer economics, not
            # kernels — the gather oracle runs identically on CPU CI
            # and on a chip
            attn_backend="gather",
        )

    hub = HubServer()
    await hub.start("127.0.0.1", 0)
    hub_addr = f"127.0.0.1:{hub.port}"
    eid = EndpointId(NS, COMP, EP)
    pull_counters0 = {
        k: counters.get(k)
        for k in ("kv_pull_decisions_total", "kv_pull_landed_total",
                  "kv_pull_tokens_total", "kv_pull_failed_total")
    }

    drts, engines, pullers = [], [], []
    wids: list[int] = []           # engine index -> hub worker id
    served: dict[str, int] = {}   # request_id -> worker index
    ledgers: dict[str, dict] = {}  # request_id -> prefix ledger
    try:
        for i in range(2):
            drt = await DistributedRuntime.from_settings(hub_addr=hub_addr)
            drts.append(drt)
            wids.append(drt.primary_lease.lease_id)
            engine = JaxEngine(engine_config())
            engines.append(engine)

            def _observe(summary, i=i):
                served[summary["request_id"]] = i
                ledgers[summary["request_id"]] = summary.get("prefix") or {}

            engine.subscribe_requests(_observe)
            ep = drt.namespace(NS).component(COMP).endpoint(EP)
            KvEventPublisher(
                ep.component, drt.primary_lease.lease_id
            ).attach(engine).start()
            await KvExportHandler(drt, engine, NS, COMP).start()
            puller = PrefixPuller(drt, engine, engine, eid)
            pullers.append(puller)
            metrics = KvMetricsPublisher.for_engine(engine)
            await ep.serve_engine(
                puller, stats_handler=metrics.stats_handler
            )

        rdrt = await DistributedRuntime.from_settings(hub_addr=hub_addr)
        drts.append(rdrt)
        ep = rdrt.namespace(NS).component(COMP).endpoint(EP)
        client = await ep.client()
        for _ in range(200):
            if len(client.instance_ids()) >= 2:
                break
            await asyncio.sleep(0.05)
        router = KvRouter(
            ep.component, client, block_size=page,
            poll_interval=d["poll_interval"],
            pull_threshold_tokens=d["pull_threshold_pages"] * page,
        )
        await router.start()
        push = KvPushRouter(client, router)

        prefixes = [
            rng.randint(1, cfg.vocab_size, size=prefix_len).tolist()
            for _ in range(d["tenants"])
        ]

        async def serve(tenant: int, rec: dict, osl: int) -> str:
            tokens = prefixes[tenant] + rng.randint(
                1, cfg.vocab_size, size=d["suffix"]
            ).tolist()
            pre = PreprocessedRequest(
                token_ids=tokens,
                stop_conditions=StopConditions(
                    max_tokens=osl, ignore_eos=True
                ),
                sampling_options=SamplingOptions(greedy=True),
            )
            ctx = Context(pre.to_dict())
            t0 = time.perf_counter()
            ticks = []
            async for frame in await push.generate(pre.to_dict(), context=ctx):
                if frame.get("token_ids"):
                    ticks.append(time.perf_counter())
            rec["ttft"] = ticks[0] - t0
            rec["request_id"] = ctx.id
            rec["tenant"] = tenant
            return ctx.id

        # compile warmup: serve one sacrificial random prompt per worker
        # DIRECT to its engine (cold-path prefill/decode families) and
        # re-serve it (warm continuation family) — the measured phases
        # must compare compute, not the jit compiler
        for engine in engines:
            wp = rng.randint(1, cfg.vocab_size, size=isl).tolist()
            for _ in range(2):
                pre = PreprocessedRequest(
                    token_ids=wp,
                    stop_conditions=StopConditions(
                        max_tokens=d["osl"], ignore_eos=True
                    ),
                    sampling_options=SamplingOptions(greedy=True),
                )
                async for _ in await engine.generate(Context(pre.to_dict())):
                    pass

        # ---- phase 1: cold — every tenant's first serve, nothing
        # cached. SEQUENTIAL serving in both measured phases: the two
        # tiny workers have max_batch slots each, and a concurrent
        # gather would fold queue-wait noise into the TTFT comparison
        cold_recs = [dict() for _ in range(d["tenants"] * d["cold_per_tenant"])]
        for r in range(d["cold_per_tenant"]):
            for t in range(d["tenants"]):
                await serve(t, cold_recs[r * d["tenants"] + t], d["osl"])
        holder = {  # tenant -> worker index that served it cold
            rec["tenant"]: served.get(rec["request_id"])
            for rec in cold_recs
        }

        # events propagate into the router's radix index before warm
        want_blocks = d["tenants"] * d["prefix_pages"]
        for _ in range(200):
            if router.indexer.tree.num_blocks >= want_blocks:
                break
            await asyncio.sleep(0.05)

        # ---- phase 2: warm — fresh suffixes on the same prefixes; the
        # router must send each tenant back to its holder
        warm_recs = [
            dict() for _ in range(d["tenants"] * d["warm_per_tenant"])
        ]
        for r in range(d["warm_per_tenant"]):
            for t in range(d["tenants"]):
                await serve(t, warm_recs[r * d["tenants"] + t], d["osl"])
        to_holder = sum(
            1 for rec in warm_recs
            if served.get(rec["request_id"]) == holder.get(rec["tenant"])
        )
        warm_reused = sum(
            1 for rec in warm_recs
            if (ledgers.get(rec["request_id"], {}).get("reused_blocks", 0)
                + ledgers.get(rec["request_id"], {}).get(
                    "restored_blocks", 0)) > 0
        )

        # ---- phase 3: pull — saturate one tenant's holder; new
        # requests for it must land on the idle worker via a prefix PULL
        # instead of a recompute
        victim_tenant = 0
        hold_idx = holder.get(victim_tenant) or 0
        hold_engine = engines[hold_idx]

        async def hold_one():
            pre = PreprocessedRequest(
                token_ids=rng.randint(1, cfg.vocab_size, size=isl).tolist(),
                stop_conditions=StopConditions(
                    max_tokens=d["hold_osl"], ignore_eos=True
                ),
                sampling_options=SamplingOptions(greedy=True),
            )
            async for _ in await hold_engine.generate(Context(pre.to_dict())):
                pass

        held = [
            asyncio.create_task(hold_one()) for _ in range(d["max_batch"])
        ]
        # the aggregator must SEE the saturation (scrape cadence) before
        # the pull-phase requests are scheduled
        for _ in range(100):
            m = router.aggregator.current.endpoints.get(wids[hold_idx])
            if m is not None and m.request_active_slots >= d["max_batch"]:
                break
            await asyncio.sleep(d["poll_interval"] / 2)
        pull_recs = [dict() for _ in range(d["pull_requests"])]
        for rec in pull_recs:
            await serve(victim_tenant, rec, d["osl"])
        await asyncio.gather(*held)

        def p50(recs):
            return round(
                float(np.percentile([r["ttft"] for r in recs], 50)), 4
            )

        pulls = {
            k[len("kv_pull_"):-len("_total")]: int(
                counters.get(k) - pull_counters0[k]
            )
            for k in pull_counters0
        }
        pulls["tokens_moved"] = sum(p.pull_tokens for p in pullers)
        # zero-orphan quiesce census (engine/kv_ledger.py): every page
        # the phases touched must be back to free/cached custody before
        # teardown — a leak here fails the proof, not just a dashboard
        census = await asyncio.to_thread(quiesce_census, engines)
        return {
            "scenario": {
                k: d[k]
                for k in ("tenants", "page", "prefix_pages", "suffix",
                          "osl", "warm_per_tenant", "pull_requests",
                          "max_batch")
            },
            "ttft_cold_p50_s": p50(cold_recs),
            "ttft_warm_p50_s": p50(warm_recs),
            "ttft_pull_p50_s": p50(pull_recs),
            "warm_vs_cold_ttft": round(
                p50(cold_recs) / p50(warm_recs), 3
            ),
            "route_to_holder_frac": round(to_holder / len(warm_recs), 3),
            "warm_reuse_frac": round(warm_reused / len(warm_recs), 3),
            "router_blocks": router.indexer.tree.num_blocks,
            "pulls": pulls,
            "kv_census": census,
        }
    finally:
        for e in engines:
            try:
                await e.close()
            except Exception:  # noqa: BLE001
                pass
        for drt in drts:
            try:
                await drt.shutdown()
            except Exception:  # noqa: BLE001
                pass
        await hub.stop()


def run(**overrides) -> dict:
    return asyncio.run(run_scenario(**overrides))


if __name__ == "__main__":
    out = run()
    print(json.dumps(out, indent=2))
    ok = (
        out["warm_reuse_frac"] > 0
        and out["pulls"]["landed"] >= 1
        and out["router_blocks"] > 0
        and out["kv_census"]["ok"]
    )
    sys.exit(0 if ok else 1)
