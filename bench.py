"""Serving benchmark on the local TPU chip — prints ONE JSON line.

Protocol (scaled-down from the reference's genai-perf sweep, BASELINE.md:
ISL 3000 / OSL 150, concurrency sweep): N concurrent requests with a fixed
ISL/OSL through the full engine (continuous batching, paged KV, on-device
sampling); measures steady-state decode throughput per chip plus p50
TTFT/ITL.

Baseline for `vs_baseline`: the north star is tokens/sec/chip parity with
vLLM on H100 for Llama-3.1-8B (BASELINE.json), 2000 tok/s/GPU. With int8
weights the REAL 8B model fits the 16 GB v5e chip and is benched against
that bar UNSCALED; only when a smaller preset must be used (bf16 runs) is
the bar scaled by relative parameter count so the ratio stays comparable.
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import numpy as np

PARITY_8B_TOKS_PER_CHIP = 2000.0
_8B_PARAMS = 8.03e9

ISL = int(os.environ.get("BENCH_ISL", "512"))
OSL = int(os.environ.get("BENCH_OSL", "64"))
DECODE_STEPS = int(os.environ.get("BENCH_DECODE_STEPS", "16"))
# int8 W8A8 weights + int8 KV pages are the default protocol: the
# reference's baselines serve FP8 on H100 (BASELINE.md "70B FP8"), so the
# fully-quantized path is the apples-to-apples configuration — and it is
# what fits the real 8B north-star model on a 16 GB v5e chip.
# BENCH_QUANT=none / BENCH_KV_QUANT=none for bf16 variants.
QUANT = os.environ.get("BENCH_QUANT", "int8")
QUANT = None if QUANT in ("", "none") else QUANT
KV_QUANT = os.environ.get("BENCH_KV_QUANT", "int8")
KV_QUANT = None if KV_QUANT in ("", "none") else KV_QUANT
# int8-KV pallas kernels put page tokens in lanes (page 128); bf16 runs
# use 64-token pages — fixed here because the PREFIX PROBE must know it
PAGE_SIZE = 128 if KV_QUANT else 64
# prefix-probe prompt length: at least 2 full pages + a partial tail
# regardless of BENCH_ISL. A prompt shorter than one page has NO
# cacheable block, so its "warm" serve reuses nothing and the reported
# speedup is pure noise — exactly how BENCH_r06 (ISL=64, page
# 128) printed the phantom 0.68x "regression". The engine config below
# sizes prefill_chunk/max_model_len to cover this.
PROBE_ISL = max(ISL, 2 * PAGE_SIZE + PAGE_SIZE // 2)
# BENCH_FAST=1: headline wave + prefix probe only (the concurrency sweep
# runs one engine init per point — skip the paced/offload/phase extras)
FAST = os.environ.get("BENCH_FAST", "") not in ("", "0")
# BENCH_SPEC=1: self-speculative decoding A/B — a repetitive-text wave
# served with spec off then on (same engine, runtime toggle), recording
# acceptance rate, effective tokens-per-verify-step and the tok/s delta.
# NOTE: spec_decode is incompatible with the packed pallas+int8 KV pools
# (the engine refuses at init) — on TPU run it with BENCH_KV_QUANT=none.
SPEC = os.environ.get("BENCH_SPEC", "") not in ("", "0")
SPEC_K = int(os.environ.get("BENCH_SPEC_K", "4"))
SPEC_NGRAM = int(os.environ.get("BENCH_SPEC_NGRAM", "3"))
SPEC_OSL = int(os.environ.get("BENCH_SPEC_OSL", str(max(OSL, 128))))
# BENCH_MIXED=1: stall-free mixed batching A/B — hold N streams in
# steady decode, inject an admission wave of fresh prompts, and record
# the held streams' decode ITL p50/p99 DURING the wave plus the wave's
# TTFT, mixed batching off then on (runtime toggle, same engine).
# NOTE: mixed batching is incompatible with the packed pallas+int8 KV
# pools (the engine degrades to normal paths and the A/B reads ~1x) —
# on TPU run it with BENCH_KV_QUANT=none.
MIXED = os.environ.get("BENCH_MIXED", "") not in ("", "0")
MIXED_TOKENS = int(os.environ.get("BENCH_MIXED_TOKENS", "1024"))
MIXED_HELD = int(os.environ.get("BENCH_MIXED_HELD", "8"))
MIXED_WAVE = int(os.environ.get("BENCH_MIXED_WAVE", "16"))
MIXED_OSL = int(os.environ.get("BENCH_MIXED_OSL", str(max(OSL, 128))))
# BENCH_PIPELINE=1: step-pipeline A/B — the same held+wave mixed cycle
# run serialized (EngineConfig.step_pipeline=False: every step is
# dispatch -> fetch -> sync) then pipelined, reporting the sync-fetch
# wall (`mixed_sync_s + decode_sync_s`) as a fraction of the total
# dispatch+sync step wall. Also runs whenever BENCH_MIXED=1 is set.
PIPE = MIXED or os.environ.get("BENCH_PIPELINE", "") not in ("", "0")
# BENCH_PREFIX_FLEET=1: multi-tenant shared-prefix FLEET scenario
# (scripts/prefix_fleet.py) — in-process hub + two real workers + the
# KV-aware router with live engine events, scoring warm-vs-cold TTFT
# across the fleet, route-to-holder rate, cross-worker prefix pulls
# (saturated holder -> export/ingest transfer instead of recompute) and
# $-per-million-tokens. Emits the `prefix_fleet` BENCH_OUT section.
PREFIX_FLEET = os.environ.get("BENCH_PREFIX_FLEET", "") not in ("", "0")
# BENCH_CONTROL=1: chaos-controller scenario (scripts/control_chaos.py)
# — spawn a real hub + supervisor-managed worker pool, inject a load
# spike + DYN_FAULTS worker death, and score the SLO-driven planner on
# the attainment recovery curve (time-to-recover, goodput retained,
# graceful lease-revoke drain). Pure control-plane: no model, runs the
# same at any BENCH_MODEL. Emits the `control` BENCH_OUT section.
CONTROL = os.environ.get("BENCH_CONTROL", "") not in ("", "0")
# BENCH_FAILOVER=1: request-failover chaos scenario
# (scripts/failover_chaos.py) — in-process hub + real workers + the
# journaled failover plane; worker.die severs the serving data plane
# mid-stream and every greedy SSE stream must complete byte-identical.
# Scores recovered_frac, the replay TTFT gap, and the continuation
# economics (recompute vs cache-reuse vs cross-worker pull). Emits the
# `failover` BENCH_OUT section.
FAILOVER = os.environ.get("BENCH_FAILOVER", "") not in ("", "0")
# BENCH_KV_CAPACITY=1: KV-tier capacity census (scripts/kv_capacity.py)
# — bf16/int8/int4 page bytes measured off live pools, max resident
# streams at a fixed byte budget (BENCH_KV_CAPACITY_MB), a saturating
# decode wave per quantized tier, and the margin-stable greedy
# token-match quality bound vs the f32-KV reference. Emits the
# `kv_capacity` BENCH_OUT section; spawns its own tiny engines, so it
# runs the same at any BENCH_MODEL.
KV_CAPACITY = os.environ.get("BENCH_KV_CAPACITY", "") not in ("", "0")
KV_CAPACITY_MB = float(os.environ.get("BENCH_KV_CAPACITY_MB", "64"))
# BENCH_TP_OVERLAP=1: TP comm/compute overlap ledger
# (scripts/tp_overlap_bench.py) — per-layer step wall serialized-psum vs
# the ring executor (parallel/tp_overlap.py) plus the measured
# collective-byte ledger: exposed bytes EXACTLY 0.5x, total wire bytes
# conserved, greedy argmax byte-identical to tp=1. Runs as a SUBPROCESS
# (it needs its own 8-virtual-device CPU mesh, and this process already
# initialized jax against the real backend); emits the `tp_overlap`
# BENCH_OUT section. Independent of BENCH_MODEL.
TP_OVERLAP = os.environ.get("BENCH_TP_OVERLAP", "") not in ("", "0")
# BENCH_SCENARIOS=1: trace-driven scenario suite (dynamo_tpu/loadgen/,
# docs/loadgen.md) — one seeded open-loop scenario per workload the
# engine supports (chat, rag, shared-prefix, bursty+admission,
# long-context ring, MoE, vision, structured sampling), each scored by
# the SLO-gated goodput machinery. Scenario engines are built at
# LOADGEN_SCALE (default tiny), INDEPENDENT of the headline model — so
# one invocation can bench the REAL-model headline and still run the
# tiny scenario suite (the r06 mistake was conflating the two).
SCENARIOS = os.environ.get("BENCH_SCENARIOS", "") not in ("", "0")
# BENCH_OUT=path: ALSO write a machine-readable JSON results file with
# every section keyed separately (headline, spec, mixed, mixed_spec) —
# the stdout line stays the one-line headline artifact. Downstream
# trajectory tooling parses the file, not stdout.
BENCH_OUT = os.environ.get("BENCH_OUT", "")
# SLO target for the goodput section: tokens only count as "good" when
# their request's client TTFT met this budget — throughput that blows
# the latency target is not serving capacity (goodput accounting,
# docs/observability.md "Fleet plane")
SLO_TTFT = float(os.environ.get("BENCH_SLO_TTFT", "2.0"))
# BENCH_TRACE=path: arm the span recorder (dynamo_tpu/utils/tracing.py)
# for the whole run and dump Chrome/Perfetto trace-event JSON there at
# exit — request spans (submit->finish) plus the engine step timeline
# (prefill/decode/mixed/spec_verify dispatches with rows/tokens/walls).
# Load the file at https://ui.perfetto.dev (docs/observability.md).
BENCH_TRACE = os.environ.get("BENCH_TRACE", "")

ENV_HELP = """bench.py — serving benchmark; configuration via env vars:
  BENCH_MODEL                  preset override (auto-picked from HBM)
  BENCH_ISL / BENCH_OSL        input/output sequence lengths (512 / 64)
  BENCH_DECODE_STEPS           decode steps per jit dispatch (16)
  BENCH_QUANT                  weights quant: int8|none (int8)
  BENCH_KV_QUANT               KV cache quant: int8|int4|none (int8);
                               int4 nibble-packs two values per pool
                               byte — quarter of bf16's KV bytes
                               (docs/kv_cache.md "int4 packed tier")
  BENCH_FAST=1                 headline wave + prefix probe only
  BENCH_CONCURRENCY            concurrent requests (128 big / 256 small)
  BENCH_PREFILL_GROUP          prefill group token budget
  BENCH_HOST_KV_PAGES          host offload tier pages (16)
  BENCH_PREFILL_WINDOW         admission batching window seconds (0.25)
  BENCH_REPS                   measured-wave repetitions (3)
  BENCH_PACED_FRAC(_HI)        paced-arrival operating points (0.35/0.5)
  BENCH_SPEC=1                 speculative-decode A/B (off by default)
  BENCH_SPEC_K                 drafted tokens per verify step (4)
  BENCH_SPEC_NGRAM             longest proposer n-gram (3)
  BENCH_SPEC_OSL               output length of the spec A/B waves
                               (max(BENCH_OSL, 128))
  BENCH_SPEC_CONC              concurrency of the spec A/B waves (32)
  BENCH_MIXED=1                mixed-batching A/B: held-decode ITL
                               p50/p99 during an admission wave, mixed
                               off vs on (off by default; on TPU pair
                               with BENCH_KV_QUANT=none — packed int8
                               pools cannot run mixed steps)
  BENCH_MIXED_TOKENS           mixed step token budget (1024)
  BENCH_MIXED_HELD             streams held in steady decode (8)
  BENCH_MIXED_WAVE             admission-wave prompt count (16)
  BENCH_MIXED_OSL              held streams' output length
                               (max(BENCH_OSL, 128))
  BENCH_PIPELINE=1             step-pipeline A/B: the held+wave mixed
                               cycle serialized (step_pipeline=False)
                               vs pipelined — sync-fetch wall as a
                               fraction of the step wall (also runs
                               whenever BENCH_MIXED=1)
  BENCH_OUT                    path: write a machine-readable JSON file
                               with every section's numbers keyed as
                               {headline, spec, mixed, mixed_spec,
                               pipeline_ab, prefix_ab, prefix_fleet,
                               control, failover, kv_capacity,
                               scenarios, goodput} (sections not run are
                               null; goodput + prefix_ab always
                               present: SLO-gated throughput, the
                               per-request prefix/offload ledgers and
                               the cold/warm counter breakdown of the
                               probes); stdout keeps the one-line
                               headline artifact
  BENCH_PREFIX_FLEET=1         multi-tenant shared-prefix FLEET
                               scenario: in-process hub + two real
                               workers + the KV-aware router fed live
                               engine events — warm-vs-cold TTFT,
                               route-to-holder rate, cross-worker
                               prefix pulls, $-per-M-tokens (adds the
                               `prefix_fleet` BENCH_OUT section;
                               scripts/prefix_fleet.py)
  BENCH_CHIP_HOUR_USD          $/chip-hour for the fleet scenario's
                               $-per-million-tokens line (1.20)
  BENCH_CONTROL=1              chaos-controller scenario: worker death +
                               load spike scored on SLO-attainment
                               recovery (adds the `control` BENCH_OUT
                               section; scripts/control_chaos.py)
  BENCH_FAILOVER=1             request-failover chaos scenario: a
                               worker.die mid-stream must resume every
                               greedy SSE stream byte-identical —
                               recovered_frac, replay TTFT gap,
                               recompute-vs-reuse-vs-pull tokens (adds
                               the `failover` BENCH_OUT section;
                               scripts/failover_chaos.py)
  BENCH_KV_CAPACITY=1          KV-tier capacity census: bf16/int8/int4
                               page bytes off live pools + max resident
                               streams at a fixed budget, per-tier
                               decode waves, and the margin-stable
                               greedy token-match quality bound (adds
                               the `kv_capacity` BENCH_OUT section;
                               scripts/kv_capacity.py)
  BENCH_KV_CAPACITY_MB         census byte budget in MiB (64)
  BENCH_TP_OVERLAP=1           TP comm/compute overlap ledger: per-layer
                               step wall serialized-psum vs the ring
                               executor + measured collective bytes
                               (exposed EXACTLY 0.5x, total conserved)
                               + greedy byte-identity vs tp=1 (adds the
                               `tp_overlap` BENCH_OUT section; subprocess
                               on 8 virtual CPU devices —
                               scripts/tp_overlap_bench.py)
  BENCH_SCENARIOS=1            trace-driven scenario suite (adds the
                               `scenarios` BENCH_OUT section): seeded
                               open-loop traces replayed per workload
                               (chat, rag, shared_prefix, bursty with
                               admission+priorities, long_context ring,
                               moe, vision, structured sampling), each
                               scored by SLO-gated goodput — see
                               docs/loadgen.md
  LOADGEN_SCENARIOS            csv | default | all (all adds the
                               prefix_fleet + control_chaos adapters)
  LOADGEN_SCALE                tiny | real scenario sizing (tiny)
  LOADGEN_MODEL                real-scale scenario preset
                               (llama-3.2-1b)
  LOADGEN_SEED                 trace seed (0); same seed reproduces
                               byte-identical trace files
  LOADGEN_N / LOADGEN_RATE     requests per trace / offered req/s
  LOADGEN_TRACE_DIR            dump each scenario's trace JSONL here
  BENCH_TRACE                  path: record the whole run with the span
                               recorder (utils/tracing.py) and dump
                               Perfetto-loadable trace-event JSON there
                               (request spans + engine step timeline)
  BENCH_SLO_TTFT               goodput TTFT budget in seconds (2.0):
                               the goodput section counts a request's
                               tokens only when its TTFT met this
  (BENCH_MIXED=1 BENCH_SPEC=1 together add the COMPOSED spec x mixed
  A/B: repetitive held streams + an admission wave, mixed-only vs
  mixed+spec — ragged verify rows inside the mixed steps)
"""


def main() -> None:
    import jax

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.pipeline.context import Context

    import __graft_entry__

    if BENCH_TRACE:
        from dynamo_tpu.utils import tracing

        tracing.enable()

    if os.environ.get("BENCH_MODEL"):
        # explicit preset (CI smokes run the tiny preset on CPU, where
        # there is no device memory to pick by)
        from dynamo_tpu.models.config import get_config

        cfg = get_config(os.environ["BENCH_MODEL"])
    else:
        cfg = __graft_entry__._pick_config(QUANT)
    n_chips = len(jax.local_devices())
    big = cfg.name == "llama-3.1-8b"
    # 8B on a 16 GB chip: the KV pool budget (~5 GB after int8 weights)
    # holds ~128 concurrent 608-token sequences; higher concurrency would
    # thrash the allocator with preemptions instead of adding throughput
    concurrency = int(
        os.environ.get("BENCH_CONCURRENCY", "128" if big else "256")
    )
    prefill_group = int(
        os.environ.get("BENCH_PREFILL_GROUP", "16384" if big else "32768")
    )
    engine = JaxEngine(
        EngineConfig(
            model=cfg,
            dtype="bfloat16",
            max_batch_size=concurrency,
            max_model_len=max(ISL, PROBE_ISL) + max(
                OSL,
                SPEC_OSL if SPEC else 0,
                MIXED_OSL if (MIXED or PIPE) else 0,
            ) + 32,
            # prefill_chunk covers the probe prompts too, so they stay
            # single-chunk (a sub-page prefill_chunk would start later
            # chunks off page boundaries, which the pallas write path
            # refuses); for the default ISL=512 this is unchanged
            prefill_chunk=max(ISL, PROBE_ISL),
            decode_steps=DECODE_STEPS,
            prefill_group_tokens=prefill_group,
            quantization=QUANT,
            kv_quantization=KV_QUANT,
            # spec A/B: init validates the combo (packed int8 pools
            # refuse); the main protocol's random prompts never draft,
            # so the headline numbers are unaffected — the A/B flips
            # this flag per wave
            spec_decode=SPEC,
            spec_k_max=SPEC_K,
            spec_ngram_max=SPEC_NGRAM,
            # mixed-batching A/B: the flag itself is a per-tick host
            # decision toggled per wave below; only the budget is fixed
            # at init. spec COMPOSES with mixed (ragged verify rows) —
            # with both env flags set the composed A/B below toggles the
            # two flags together.
            mixed_batching=False,
            mixed_step_tokens=MIXED_TOKENS,
            # int8-KV pallas kernels put page tokens in lanes
            page_size=PAGE_SIZE,
            # HBM->host offload tier ON (the reference baselines run with
            # their multi-tier KV manager active); sized for the TTFT
            # probe, small enough to stay out of the headline's way
            host_kv_pages=int(os.environ.get("BENCH_HOST_KV_PAGES", "16")),
            # paced arrivals: briefly batch trickling admissions (A/B on
            # this rig: +38% paced throughput AND better TTFT — fewer
            # decode-plane interruptions)
            prefill_batch_window_s=float(
                os.environ.get("BENCH_PREFILL_WINDOW", "0.25")
            ),
        )
    )
    # park the offload tier outside its probe: a D2H page gather holds
    # the KV lock for the whole copy and would serialize
    # the throughput/paced measurements
    engine.offload_paused = True
    # spec stays parked outside its own A/B too (a runtime host-side
    # toggle): tiny-vocab/random-prompt runs would otherwise draft on
    # the HEADLINE wave and muddy the baseline numbers
    engine.config.spec_decode = False
    n_params = engine.param_count

    # goodput accounting: every finished request's summary (latency +
    # the per-request prefix/offload ledger stamped at page
    # reservation) collects here; the probes below snapshot index
    # ranges to attribute ledgers to their wave — the data that finally
    # EXPLAINS a prefix-hit ratio instead of just reporting it
    summaries: list = []
    engine.subscribe_requests(summaries.append)
    goodput: dict = {}

    def ledger_agg(batch):
        pf = [s.get("prefix") or {} for s in batch]
        reasons: dict = {}
        for p in pf:
            r = p.get("gate_reason")
            if r:
                reasons[r] = reasons.get(r, 0) + 1
        return {
            "requests": len(batch),
            "reused_blocks": sum(p.get("reused_blocks", 0) for p in pf),
            "restored_blocks": sum(p.get("restored_blocks", 0) for p in pf),
            "declined_blocks": sum(p.get("declined_blocks", 0) for p in pf),
            "gate_reasons": reasons,
            # per-request rows (capped): which requests reused/restored
            # how many blocks — the request-level ledger
            "per_request": [
                {
                    "request": (s.get("request_id") or "")[:8],
                    "prompt_tokens": s.get("prompt_tokens"),
                    **(s.get("prefix") or {}),
                }
                for s in batch[:32]
            ],
        }

    rng = np.random.RandomState(0)

    async def one(prompt, record, max_tokens=OSL):
        pre = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=True
            ),
            sampling_options=SamplingOptions(greedy=True),
        )
        t0 = time.perf_counter()
        ticks = []
        async for frame in await engine.generate(Context(pre.to_dict())):
            if frame.get("token_ids"):
                ticks.append(time.perf_counter())
            meta = frame.get("meta")
            if meta and "engine_ttft_s" in meta:
                # engine-side split (scheduler stamps): submit->dispatch-
                # returned, excludes the result fetch and delivery to the client
                record["engine_ttft"] = meta["engine_ttft_s"]
                record["queue_wait"] = meta.get("queue_wait_s")
        record["ttft"] = ticks[0] - t0
        # Effective ITL: tokens arrive in multi-step bursts, so intra-burst
        # frame diffs are meaningless — report the per-request average
        # token-to-token latency over the whole decode instead.
        record["itl"] = (
            (ticks[-1] - ticks[0]) / (len(ticks) - 1) if len(ticks) > 1 else None
        )
        record["tokens"] = len(ticks)

    def _probe_ratio(cold, warm):
        return cold["ttft"] / warm["ttft"]

    async def run():
        # warmup at FULL concurrency so every compiled shape family
        # (prefill group sizes, decode batch) is built before measuring;
        # distinct prompts so no measured request rides the prefix cache.
        # TWO waves: admission timing varies between waves, so the set of
        # prefill-group row counts (power-of-two families) a wave hits is
        # not deterministic — one wave can leave a family uncompiled
        for _ in range(2):
            warm_prompts = [
                rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                for _ in range(concurrency)
            ]
            await asyncio.gather(*(one(p, {}) for p in warm_prompts))
        # paced arrivals dispatch SMALL prefill groups (and small decode
        # buckets) the full-concurrency waves never hit — compile every
        # power-of-two family (rows 1..32) now or the paced phase
        # measures compiler stalls as TTFT (measured: a 40 s mid-wave
        # stall from one cold [8, 512] prefill family). FAST mode skips
        # the paced phase, so it needs none of these
        for k in (() if FAST else (1, 2, 3, 6, 12, 24, 48)):
            if k >= concurrency:
                break
            batch = [
                rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                for _ in range(k)
            ]
            await asyncio.gather(*(one(p, {}) for p in batch))
        # cached-continuation shape: a prefix-cache hit prefills only the
        # final partial page — its small bucket family must be compiled
        # before the warm probe measures it
        dup = rng.randint(1, cfg.vocab_size, size=ISL).tolist()
        await one(dup, {})
        await one(dup, {})
        # ---- measured waves x3 (median-of-3: run-to-run drift is ~±10% and
        # decides whether the headline reads 0.61 or 0.67); the engine's
        # phase counters are snapshotted for the raw artifact
        n_reps = 1 if FAST else int(os.environ.get("BENCH_REPS", "3"))
        ps0 = engine.phase_stats
        reps = []
        for _ in range(n_reps):
            rep_prompts = [
                rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                for _ in range(concurrency)
            ]
            recs = [dict() for _ in rep_prompts]
            t0 = time.perf_counter()
            await asyncio.gather(*(one(p, r) for p, r in zip(rep_prompts, recs)))
            reps.append((time.perf_counter() - t0, recs))
        ps1 = engine.phase_stats
        phase_delta = {k: ps1[k] - ps0[k] for k in ps0}
        wall_spread = [round(r[0], 3) for r in reps]  # chronological
        reps.sort(key=lambda x: x[0])
        wall, records = reps[len(reps) // 2]  # median wall's wave

        # ---- phase split: a MEASURED prefill-only wave (OSL=1, whole-
        # wave wall — per-request RTTs overlap, and the engine-side token
        # counter confirms what it prefilled). Dispatch-call walls are
        # NOT usable as device walls (a jit call returns once the work
        # is enqueued) and fencing each dispatch serializes host and
        # device instead — the dedicated wave measures the phase
        # without changing it.
        prefill_wall = prefill_wave_tokens = None
        if not FAST:
            pf0 = engine.phase_stats
            pf_prompts = [
                rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                for _ in range(concurrency)
            ]
            t1 = time.perf_counter()
            await asyncio.gather(*(one(p, {}, max_tokens=1) for p in pf_prompts))
            prefill_wall = time.perf_counter() - t1
            prefill_wave_tokens = (
                engine.phase_stats["prefill_tokens"] - pf0["prefill_tokens"]
            )

        async def spec_ab():
            """Speculative-decode A/B on a repetitive-text workload: the
            same wave greedy-served with spec_decode off, then on (the
            flag is a per-tick host decision, so a runtime toggle is
            sound). Distinct 16-token segments tiled to ISL: every
            suffix n-gram recurs within its own prompt, no cross-request
            prefix-cache hits."""
            n_spec = min(
                concurrency, int(os.environ.get("BENCH_SPEC_CONC", "32"))
            )

            def rep_prompts():
                return [
                    np.tile(
                        rng.randint(1, cfg.vocab_size, size=16),
                        SPEC_OSL // 16 + ISL // 16 + 2,
                    )[:ISL].tolist()
                    for _ in range(n_spec)
                ]

            engine.config.spec_decode = False
            # warm the off-wave compile families (small-row prefill
            # groups this concurrency may never have hit)
            await asyncio.gather(
                *(one(p, {}, max_tokens=SPEC_OSL) for p in rep_prompts()[:2])
            )
            off = rep_prompts()
            t0 = time.perf_counter()
            await asyncio.gather(
                *(one(p, {}, max_tokens=SPEC_OSL) for p in off)
            )
            wall_off = time.perf_counter() - t0
            engine.config.spec_decode = True
            # compile the verify families before measuring
            await asyncio.gather(
                *(one(p, {}, max_tokens=SPEC_OSL) for p in rep_prompts()[:2])
            )
            ps_a = engine.phase_stats
            on = rep_prompts()
            t0 = time.perf_counter()
            await asyncio.gather(
                *(one(p, {}, max_tokens=SPEC_OSL) for p in on)
            )
            wall_on = time.perf_counter() - t0
            ps_b = engine.phase_stats
            engine.config.spec_decode = False
            d = {k: ps_b[k] - ps_a[k] for k in ps_a}
            toks = n_spec * SPEC_OSL
            return {
                "k_max": SPEC_K,
                "ngram_max": SPEC_NGRAM,
                "concurrency": n_spec,
                "osl": SPEC_OSL,
                "acceptance_rate": (
                    round(d["spec_accepted"] / d["spec_drafted"], 4)
                    if d["spec_drafted"] else None
                ),
                "effective_tokens_per_step": (
                    round(d["spec_emitted"] / d["spec_rows"], 3)
                    if d["spec_rows"] else None
                ),
                "verify_steps": d["spec_dispatches"],
                "toks_per_sec_chip_off": round(toks / wall_off / n_chips, 1),
                "toks_per_sec_chip_on": round(toks / wall_on / n_chips, 1),
                "speedup": round(wall_off / wall_on, 3),
            }

        async def held_one(prompt, record):
            pre = PreprocessedRequest(
                token_ids=prompt,
                stop_conditions=StopConditions(
                    max_tokens=MIXED_OSL, ignore_eos=True
                ),
                sampling_options=SamplingOptions(greedy=True),
            )
            # bind the LIVE list before streaming: the wave launcher
            # polls it to detect steady decode
            ticks = record["ticks"] = []
            async for frame in await engine.generate(
                Context(pre.to_dict())
            ):
                if frame.get("token_ids"):
                    ticks.append(time.perf_counter())

        def mixed_prompts(k, repetitive=False):
            if repetitive:
                # distinct 16-token segments tiled: every suffix n-gram
                # recurs within its own prompt (draftable), no
                # cross-request prefix-cache hits
                return [
                    np.tile(
                        rng.randint(1, cfg.vocab_size, size=16),
                        MIXED_OSL // 16 + ISL // 16 + 2,
                    )[:ISL].tolist()
                    for _ in range(k)
                ]
            return [
                rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                for _ in range(k)
            ]

        async def mixed_wave(mixed_on, spec_on=False, repetitive_held=False):
            """One held+wave cycle: MIXED_HELD streams in steady decode,
            then MIXED_WAVE fresh prompts as one admission wave; returns
            the held streams' inter-token gaps DURING the wave (p50/p99
            — the p99 IS the admission stall) and the wave's TTFT."""
            engine.config.mixed_batching = mixed_on
            engine.config.spec_decode = spec_on
            held_recs = [dict() for _ in range(MIXED_HELD)]
            t_all0 = time.perf_counter()
            tasks = [
                asyncio.create_task(held_one(p, r))
                for p, r in zip(
                    mixed_prompts(MIXED_HELD, repetitive_held), held_recs
                )
            ]
            # wait for steady decode: every held stream past its
            # first few tokens before the wave lands. A held task
            # dying here would otherwise spin this poll forever —
            # surface its error instead.
            while not all(
                len(r.get("ticks", ())) >= 4 for r in held_recs
            ):
                for t in tasks:
                    if t.done() and t.exception() is not None:
                        raise t.exception()
                await asyncio.sleep(0.02)
            wave_recs = [dict() for _ in range(MIXED_WAVE)]
            t_w0 = time.perf_counter()
            await asyncio.gather(*(
                one(p, r)
                for p, r in zip(mixed_prompts(MIXED_WAVE), wave_recs)
            ))
            t_w1 = time.perf_counter()
            await asyncio.gather(*tasks)
            wall_all = time.perf_counter() - t_all0
            engine.config.mixed_batching = False
            engine.config.spec_decode = False
            gaps = []
            for r in held_recs:
                ts = r["ticks"]
                for a, b in zip(ts, ts[1:]):
                    # gaps overlapping the admission-wave window
                    if b >= t_w0 and a <= t_w1:
                        gaps.append(b - a)
            toks = MIXED_HELD * MIXED_OSL + sum(
                r["tokens"] for r in wave_recs
            )

            def pct(vals, q):
                # gaps can be empty when the held streams drained
                # before the wave landed (MIXED_OSL too short for
                # this rig) — report None rather than crash
                return (
                    round(float(np.percentile(vals, q)), 4)
                    if len(vals) else None
                )

            return {
                "wave_itl_p50_s": pct(gaps, 50),
                "wave_itl_p99_s": pct(gaps, 99),
                "wave_ttft_p50_s": pct(
                    [r["ttft"] for r in wave_recs], 50
                ),
                "toks_per_sec_chip": round(toks / wall_all / n_chips, 1),
            }

        async def mixed_ab():
            """Stall-free mixed batching A/B: held streams + admission
            wave, mixed off then on. Fresh random prompts per wave: no
            prefix-cache hits, no draftable n-grams."""
            # warm both modes with a FULL held+wave cycle: mixed step
            # families ([pow2 rows, bucket] + the ragged attention path)
            # only compile when decode rows and prefill chunks actually
            # coexist — a plain warm wave never builds them, and the
            # measured ON wave would pay the compiles as fake stalls
            for on in (False, True):
                await mixed_wave(on)
            ps_a = engine.phase_stats
            off = await mixed_wave(False)
            on = await mixed_wave(True)
            ps_b = engine.phase_stats
            d = {k: ps_b[k] - ps_a[k] for k in ps_a}
            return {
                "step_tokens": MIXED_TOKENS,
                "held_streams": MIXED_HELD,
                "wave_prompts": MIXED_WAVE,
                "held_osl": MIXED_OSL,
                "off": off,
                "on": on,
                "mixed_steps": d["mixed_steps"],
                "mixed_decode_rows": d["mixed_decode_rows"],
                "mixed_prefill_tokens": d["mixed_prefill_tokens"],
                "decode_stall_saved_s": round(
                    d["mixed_decode_stall_saved_s"], 3
                ),
                "itl_p99_speedup": (
                    round(off["wave_itl_p99_s"] / on["wave_itl_p99_s"], 3)
                    if off["wave_itl_p99_s"] and on["wave_itl_p99_s"]
                    else None
                ),
            }

        async def mixed_spec_ab():
            """COMPOSED spec x mixed A/B: repetitive held streams (their
            n-grams draft, so decode rows ride the mixed steps as ragged
            1+k verify windows) + an admission wave of fresh prompts,
            mixed-only vs mixed+spec. The effective tokens per model
            step of the held rows is the spec win; the wave ITL p99
            proves composing did not reopen the admission stall."""
            for spec_on in (False, True):  # compile both families
                await mixed_wave(True, spec_on=spec_on, repetitive_held=True)
            base = await mixed_wave(True, repetitive_held=True)
            ps_m = engine.phase_stats
            comp = await mixed_wave(True, spec_on=True, repetitive_held=True)
            ps_b = engine.phase_stats
            d = {k: ps_b[k] - ps_m[k] for k in ps_b}
            return {
                "step_tokens": MIXED_TOKENS,
                "held_streams": MIXED_HELD,
                "wave_prompts": MIXED_WAVE,
                "held_osl": MIXED_OSL,
                "mixed_only": base,
                "mixed_spec": comp,
                # decode rows that rode mixed steps as verify windows
                "mixed_spec_rows": d["mixed_spec_rows"],
                "mixed_steps": d["mixed_steps"],
                "acceptance_rate": (
                    round(d["spec_accepted"] / d["spec_drafted"], 4)
                    if d["spec_drafted"] else None
                ),
                # >= 1.0; mixed-only decode rows are 1.0 by construction
                "effective_tokens_per_step": (
                    round(d["spec_emitted"] / d["spec_rows"], 3)
                    if d["spec_rows"] else None
                ),
                "itl_p99_ratio": (
                    round(
                        comp["wave_itl_p99_s"] / base["wave_itl_p99_s"], 3
                    )
                    if base["wave_itl_p99_s"] and comp["wave_itl_p99_s"]
                    else None
                ),
            }

        async def pipeline_ab():
            """Step-pipeline A/B (EngineConfig.step_pipeline): one
            held+wave mixed cycle fully SERIALIZED (every step is
            dispatch -> fetch -> sync; mixed ticks "hold" behind
            in-flight dispatches) vs pipelined (dispatch N+1 launches
            behind N; the fetch overlaps device compute). The honest
            comparison is the sync-fetch wall as a FRACTION of the
            total dispatch+sync step wall — absolute walls vary with
            how many steps each wave happens to run."""
            for on in (False, True):  # compile both paths' families
                engine.config.step_pipeline = on
                await mixed_wave(True)
            out = {}
            for key, on in (("serialized", False), ("pipelined", True)):
                engine.config.step_pipeline = on
                ps_a = engine.phase_stats
                wave = await mixed_wave(True)
                ps_b = engine.phase_stats
                d = {k: ps_b[k] - ps_a[k] for k in ps_b}
                sync = d["mixed_sync_s"] + d["decode_sync_s"]
                step = (
                    d["mixed_dispatch_s"] + d["decode_dispatch_s"]
                    + d["spec_dispatch_s"] + d["spec_sync_s"] + sync
                )
                out[key] = {
                    "mixed_sync_s": round(d["mixed_sync_s"], 4),
                    "decode_sync_s": round(d["decode_sync_s"], 4),
                    "sync_wall_s": round(sync, 4),
                    "step_wall_s": round(step, 4),
                    "sync_frac": round(sync / step, 4) if step else None,
                    # syncs whose fetch ran while another dispatch was
                    # already queued on device, and the wall they hid
                    # (counted in pipeline_overlap_s INSTEAD of the
                    # *_sync_s stall counters); overlap_frac = hidden
                    # share of the total fetch wall
                    "overlapped_syncs": d["pipeline_overlapped"],
                    "overlap_hidden_s": round(d["pipeline_overlap_s"], 4),
                    "overlap_frac": (
                        round(
                            d["pipeline_overlap_s"]
                            / (d["pipeline_overlap_s"] + sync), 4
                        )
                        if d["pipeline_overlap_s"] + sync else None
                    ),
                    "mixed_holds": d["mixed_holds"],
                    "mixed_carry_rows": d["mixed_carry_rows"],
                    "wave": wave,
                }
            engine.config.step_pipeline = True
            sf_ser = out["serialized"]["sync_frac"]
            sf_pipe = out["pipelined"]["sync_frac"]
            out["sync_frac_improved"] = (
                sf_ser is not None and sf_pipe is not None
                and sf_pipe < sf_ser
            )
            return out

        # ---- prefix-cache TTFT probe, WAVE-based, shared by FAST and
        # full runs (BASELINE.md: KV-aware routing's TTFT win comes from
        # prefix hits). A single idle request's TTFT is mostly fixed
        # per-request cost on both serves. A wave of distinct PROBE_ISL prompts served cold
        # then re-served (every full page a prefix hit) measures the
        # saved compute under real queuing, and the prefix_ab breakdown
        # (prefill/prefix/compile counter deltas per leg) makes a slow
        # warm wave ATTRIBUTABLE — reuse that didn't happen reads as
        # prefix_hits 0, a compile-contaminated leg as compile_events>0.
        AB_KEYS = (
            "prefill_dispatch_s", "prefill_tokens", "prefill_dispatches",
            "prefix_hits", "prefix_full_hits", "prefix_reused_tokens",
            "prefix_restored_tokens", "prefix_tail_tokens",
        )

        async def prefix_probe(n_probe):
            def probe_prompts():
                return [
                    rng.randint(1, cfg.vocab_size, size=PROBE_ISL).tolist()
                    for _ in range(n_probe)
                ]

            # sacrificial set A, served twice: the SECOND serve
            # dispatches [n, tail-bucket] prefill groups over full-width
            # block tables — continuation families the cold-path warmups
            # never build. Without this the measured warm wave pays ~30 s
            # remote compiles per family and every later phase measures
            # the compiler (observed: 65 s paced p50 TTFT from exactly
            # this cascade). The prefix_ab compile_events delta proves
            # per-leg whether the warmup actually covered the families.
            set_a = probe_prompts()
            await asyncio.gather(*(one(p, {}) for p in set_a))
            await asyncio.gather(*(one(p, {}) for p in set_a))
            set_b = probe_prompts()
            legs = {}
            prefix_ab = {"probe_isl": PROBE_ISL, "n_probe": n_probe}
            probe_summary = {}
            i0 = len(summaries)
            for leg in ("cold", "warm"):
                recs = [dict() for _ in range(n_probe)]
                ps_a, m_a = engine.phase_stats, engine.metrics()
                t0 = time.perf_counter()
                await asyncio.gather(
                    *(one(p, r) for p, r in zip(set_b, recs))
                )
                wall = time.perf_counter() - t0
                ps_b, m_b = engine.phase_stats, engine.metrics()
                i1 = len(summaries)
                ttft = float(np.percentile([r["ttft"] for r in recs], 50))
                legs[leg] = {"ttft": ttft, "wall": wall}
                prefix_ab[leg] = {
                    "ttft_p50_s": round(ttft, 4),
                    "wall_s": round(wall, 4),
                    **{
                        k: (
                            round(ps_b[k] - ps_a[k], 4)
                            if isinstance(ps_b[k], float)
                            else ps_b[k] - ps_a[k]
                        )
                        for k in AB_KEYS
                    },
                    "compile_events": (
                        m_b["compile_events"] - m_a["compile_events"]
                    ),
                    "compile_time_s": round(
                        m_b["compile_time_s"] - m_a["compile_time_s"], 4
                    ),
                }
                # per-request ledger of the leg: the warm wave's
                # reused_blocks tell exactly how much prefill the cache
                # skipped — a sub-1.0 "speedup" with full reuse points
                # at dispatch/compile overhead, with zero reuse at
                # eviction (or a probe too short to span a page)
                probe_summary[leg] = {
                    **ledger_agg(summaries[i0:i1]),
                    "ttft_p50_s": round(ttft, 4),
                    "wall_s": round(wall, 4),
                }
                i0 = i1
            speedup = legs["cold"]["ttft"] / legs["warm"]["ttft"]
            prefix_ab["ttft_speedup"] = round(speedup, 3)
            goodput["prefix_probe"] = {
                **probe_summary, "ttft_speedup": round(speedup, 3),
            }
            return legs, prefix_ab

        # ---- host-tier offload probe (BASELINE.md's +40% TTFT claim),
        # also shared by FAST and full runs: serve a fresh prompt, wait
        # for its pages to write-through to the host pool, EVICT them
        # from HBM, re-serve — restore-from-host vs full recompute,
        # under the cost gate. `restored > 0` here is the standing proof
        # the tier works (the r06 gate sat idle because the FAST probe
        # never forced an eviction).
        async def offload_probe_run():
            from dynamo_tpu.llm.tokens import compute_block_hashes

            def evict_all():
                grabbed = []
                while True:
                    got = engine.allocator.allocate(1)
                    if not got:
                        break
                    grabbed.extend(got)
                engine.allocator.release(grabbed)

            async def await_offloaded(tokens):
                hs = compute_block_hashes(tokens, engine.page_size)
                hs = hs[: PROBE_ISL // engine.page_size]
                for _ in range(200):
                    if engine.host_pool is not None and all(
                        h in engine.host_pool for h in hs
                    ):
                        return True
                    engine._wake.set()
                    await asyncio.sleep(0.05)
                return False

            engine.offload_paused = False
            # warm cycle: the restore path (H2D inject + registration)
            # has its own compile families — pay them before measuring
            wprobe = rng.randint(1, cfg.vocab_size, size=PROBE_ISL).tolist()
            await one(wprobe, {})
            if await await_offloaded(wprobe):
                evict_all()
                await one(wprobe, {})

            oprobe = rng.randint(1, cfg.vocab_size, size=PROBE_ISL).tolist()
            ocold, owarm = {}, {}
            await one(oprobe, ocold)
            offloaded = await await_offloaded(oprobe)
            # evict every evictable HBM page (incl. the probe's)
            evict_all()
            i_ow = len(summaries)
            await one(oprobe, owarm)
            engine.offload_paused = True
            speedup = _probe_ratio(ocold, owarm) if offloaded else None
            # the re-serve's ledger says whether the tier RESTORED or
            # the gate declined (and why) — the "restored: 0, declined:
            # 0" blindness of BENCH_r06 becomes an attributed decision
            goodput["offload_probe"] = {
                "offloaded": bool(offloaded),
                "warm": ledger_agg(summaries[i_ow:]),
                "ttft_speedup": round(speedup, 3) if speedup else None,
            }
            return speedup

        if FAST:
            legs, prefix_ab = await prefix_probe(min(4, concurrency))
            offload_speedup = await offload_probe_run()
            return (
                records, wall, wall_spread, phase_delta,
                None, None,
                {
                    "ttft": legs["cold"]["ttft"] / legs["warm"]["ttft"],
                    "wall": legs["cold"]["wall"] / legs["warm"]["wall"],
                },
                [], 0.0, 0.0, [], 0.0, 0.0, offload_speedup,
                await spec_ab() if SPEC else None,
                await mixed_ab() if MIXED else None,
                await mixed_spec_ab() if (SPEC and MIXED) else None,
                await pipeline_ab() if PIPE else None,
                prefix_ab,
            )

        legs, prefix_ab = await prefix_probe(min(32, concurrency))
        cold = {"ttft": legs["cold"]["ttft"]}
        warm = {"ttft": legs["warm"]["ttft"]}
        prefix_cold_wall = legs["cold"]["wall"]
        prefix_warm_wall = legs["warm"]["wall"]

        offload_speedup = await offload_probe_run()

        # ---- paced (Poisson) arrivals: the reference benches with
        # genai-perf's paced load (perf.sh:22-46); closed-loop-burst TTFT
        # (every request arriving at t=0) says nothing about latency at a
        # given request RATE. Pace at BENCH_PACED_FRAC of the closed-loop
        # request rate and report p50/p95 TTFT there.
        closed_rate = concurrency / wall  # requests/s sustained

        async def paced_run(frac):
            rate = frac * closed_rate
            n_paced = concurrency
            recs = [dict() for _ in range(n_paced)]
            gaps = rng.exponential(1.0 / rate, size=n_paced)
            tasks = []
            tp0 = time.perf_counter()
            for i in range(n_paced):
                p = rng.randint(1, cfg.vocab_size, size=ISL).tolist()
                tasks.append(asyncio.create_task(one(p, recs[i])))
                await asyncio.sleep(float(gaps[i]))
            await asyncio.gather(*tasks)
            return rate, recs, time.perf_counter() - tp0

        # two operating points: below the knee (TTFT ~ service latency)
        # and at ~50% of closed-loop (the prefill plane saturates when
        # arrivals come singly — TTFT is queue-dominated there)
        lo_frac = float(os.environ.get("BENCH_PACED_FRAC", "0.35"))
        hi_frac = float(os.environ.get("BENCH_PACED_FRAC_HI", "0.5"))
        paced_rate, paced_records, paced_wall = await paced_run(lo_frac)
        hi_rate, hi_records, hi_wall = await paced_run(hi_frac)

        return (
            records, wall, wall_spread, phase_delta,
            prefill_wall, prefill_wave_tokens,
            {
                "ttft": _probe_ratio(cold, warm),
                "wall": prefix_cold_wall / prefix_warm_wall,
            },
            paced_records, paced_rate, paced_wall,
            hi_records, hi_rate, hi_wall,
            offload_speedup,
            await spec_ab() if SPEC else None,
            await mixed_ab() if MIXED else None,
            await mixed_spec_ab() if (SPEC and MIXED) else None,
            await pipeline_ab() if PIPE else None,
            prefix_ab,
        )

    (
        records, wall, wall_spread, phase_delta,
        prefill_wall, prefill_wave_tokens,
        prefix_speedup,
        paced_records, paced_rate, paced_wall,
        hi_records, hi_rate, hi_wall,
        offload_speedup,
        spec_result,
        mixed_result,
        mixed_spec_result,
        pipeline_result,
        prefix_ab_result,
    ) = asyncio.run(run())
    total_tokens = sum(r["tokens"] for r in records)
    toks_per_sec_chip = total_tokens / wall / n_chips
    ttft_p50 = float(np.percentile([r["ttft"] for r in records], 50))
    itls = [r["itl"] for r in records if r["itl"] is not None]
    itl_p50 = float(np.percentile(itls, 50)) if itls else 0.0

    # SLO goodput over the measured wave: a request's tokens count only
    # when its client TTFT met the budget (exactly-at attains) — the
    # number the SLO-driven planner should defend, as opposed to raw
    # throughput which can look healthy while every request breaches
    good = [r for r in records if r["ttft"] <= SLO_TTFT]
    goodput["slo"] = {
        "ttft_target_s": SLO_TTFT,
        "attained_frac": round(len(good) / len(records), 4),
        "goodput_toks_per_sec_chip": round(
            sum(r["tokens"] for r in good) / wall / n_chips, 2
        ),
        "throughput_toks_per_sec_chip": round(toks_per_sec_chip, 2),
    }
    goodput["offload_gate"] = dict(engine.offload_gate_stats)

    def p50(recs, key):
        vals = [r[key] for r in recs if r.get(key) is not None]
        return round(float(np.percentile(vals, 50)), 4) if vals else None

    # phase split: measured prefill-only wave (engine-confirmed token
    # count) + combined wall minus it for the decode share — the
    # dispatch-call counters go into the artifact raw for transparency
    prefill_rate = decode_rate = None
    if prefill_wall and prefill_wave_tokens:
        prefill_rate = prefill_wave_tokens / prefill_wall / n_chips
        decode_wall = wall - prefill_wall
        if decode_wall > wall * 0.05:
            decode_rate = total_tokens / decode_wall / n_chips

    if big:
        # the real north-star model: vs_baseline is the UNSCALED 2000
        # tok/s/GPU bar (BASELINE.json), no parameter-count modeling
        target = PARITY_8B_TOKS_PER_CHIP
    else:
        target = PARITY_8B_TOKS_PER_CHIP * (_8B_PARAMS / n_params)
    headline_note = None
    if n_params < 5e8:
        # the r06 trap: a tiny/debug preset makes vs_baseline read ~0.0
        # and goes DARK on the real-model trajectory (r03: 5247, r04:
        # 1339 tok/s/chip at llama scale). Tiny-scale coverage belongs
        # to BENCH_SCENARIOS (its engines are independent of the
        # headline model) — the headline itself should stay real.
        headline_note = (
            f"headline model '{cfg.name}' ({n_params:.0f} params) is NOT "
            "the real-model trajectory; vs_baseline vs the parameter-"
            "scaled 8B bar is not comparable to the r03/r04 llama "
            "numbers. Unset BENCH_MODEL (auto-picks the largest llama "
            "preset for the chip) to re-measure the real headline; use "
            "BENCH_SCENARIOS=1 for tiny-scale workload coverage."
        )
        import sys as _sys

        print(f"bench: {headline_note}", file=_sys.stderr)
    qtag = f" {QUANT}" if QUANT else ""
    qtag += f" {KV_QUANT}kv" if KV_QUANT else ""
    headline = {
                "metric": f"{cfg.name}{qtag} serving "
                f"decode throughput (ISL={ISL} OSL={OSL} conc={concurrency})",
                "value": round(toks_per_sec_chip, 2),
                "unit": "tokens/sec/chip",
                "vs_baseline": round(toks_per_sec_chip / target, 4),
                "extra": {
                    "model": cfg.name,
                    # non-None exactly when the benched model cannot
                    # speak for the real-model trajectory (BENCH_NOTES.md)
                    **({} if headline_note is None else {
                        "headline_note": headline_note,
                    }),
                    "p50_ttft_s": round(ttft_p50, 4),
                    # engine-side split (scheduler stamps): p50 of
                    # submit->prefill-dispatch-returned, and the slot
                    # queue wait — client TTFT minus engine TTFT is the
                    # result fetch + delivery share
                    "engine_p50_ttft_s": p50(records, "engine_ttft"),
                    "engine_p50_queue_wait_s": p50(records, "queue_wait"),
                    "p50_itl_s": round(itl_p50, 6),
                    "chips": n_chips,
                    "params": n_params,
                    "parity_target_toks_per_chip": round(target, 1),
                    # median-of-N wave walls (run-to-run drift record)
                    "bench_reps": len(wall_spread),
                    "wave_walls_s": wall_spread,
                    # the wall includes prefilling ISL tokens per request;
                    # total token throughput shows the full device output
                    "total_toks_per_sec_chip": round(
                        (concurrency * ISL + total_tokens) / wall / n_chips, 1
                    ),
                    # MEASURED phases: prefill from a dedicated OSL=1
                    # wave (engine-counter-confirmed tokens), decode from
                    # the combined wall minus it
                    "prefill_phase_toks_per_sec_chip": (
                        round(prefill_rate, 1) if prefill_rate else None
                    ),
                    "decode_phase_toks_per_sec_chip": (
                        round(decode_rate, 1) if decode_rate else None
                    ),
                    # raw engine counters over the measured waves
                    # (dispatch-CALL walls — enqueue time on the host,
                    # NOT device walls; prefill tokens exact, decode
                    # tokens = dispatched slots incl. overshoot)
                    "engine_phase_counters": {
                        k: round(v, 3) if isinstance(v, float) else v
                        for k, v in phase_delta.items()
                    },
                    # Poisson arrivals at two operating points: below
                    # the knee (default 0.35x closed-loop) and at the
                    # queue-dominated 0.5x point
                    **({} if not paced_records else {
                        "paced_rate_req_s": round(paced_rate, 2),
                        "paced_p50_ttft_s": p50(paced_records, "ttft"),
                        "paced_p95_ttft_s": round(float(np.percentile(
                            [r["ttft"] for r in paced_records], 95)), 4),
                        "paced_engine_p50_ttft_s": p50(
                            paced_records, "engine_ttft"
                        ),
                        "paced_engine_p50_queue_wait_s": p50(
                            paced_records, "queue_wait"
                        ),
                        "paced_toks_per_sec_chip": round(
                            sum(r["tokens"] for r in paced_records)
                            / paced_wall / n_chips, 1
                        ),
                        "paced_hi_rate_req_s": round(hi_rate, 2),
                        "paced_hi_p50_ttft_s": p50(hi_records, "ttft"),
                        "paced_hi_p95_ttft_s": round(float(np.percentile(
                            [r["ttft"] for r in hi_records], 95)), 4),
                        "paced_hi_engine_p50_ttft_s": p50(
                            hi_records, "engine_ttft"
                        ),
                    }),
                    # wave-based cold/warm p50 TTFT + wall on identical
                    # prompt sets (prefix cache under real queuing)
                    # SLO-gated goodput (BENCH_SLO_TTFT budget): tokens
                    # from requests whose TTFT met the target
                    "slo_goodput": goodput.get("slo"),
                    "prefix_hit_ttft_speedup": round(prefix_speedup["ttft"], 2),
                    "prefix_hit_wall_speedup": (
                        round(prefix_speedup["wall"], 2)
                        if prefix_speedup["wall"] else None
                    ),
                    # restore-from-host-tier TTFT vs full recompute
                    # (HBM pages evicted between serves). The engine's
                    # cost gate declines restores that would LOSE to
                    # recompute (calibrated from measured rates), so on
                    # rigs where H2D is slow this probe converges to
                    # ~1.0 instead of below it
                    "offload_hit_ttft_speedup": (
                        round(offload_speedup, 2)
                        if offload_speedup is not None else None
                    ),
                    "offload_gate": dict(engine.offload_gate_stats),
                    # BENCH_SPEC=1: repetitive-text A/B, spec off vs on
                    **({} if spec_result is None else {
                        "spec": spec_result,
                    }),
                    # BENCH_MIXED=1: admission-wave A/B, mixed batching
                    # off vs on (held-decode ITL during the wave)
                    **({} if mixed_result is None else {
                        "mixed": mixed_result,
                    }),
                    # BENCH_MIXED=1 BENCH_SPEC=1: composed spec x mixed
                    # A/B (ragged verify rows riding the mixed steps)
                    **({} if mixed_spec_result is None else {
                        "mixed_spec": mixed_spec_result,
                    }),
                    # BENCH_PIPELINE=1 (or BENCH_MIXED=1): step-pipeline
                    # A/B — sync-fetch wall fraction, serialized vs
                    # pipelined
                    **({} if pipeline_result is None else {
                        "pipeline_ab": pipeline_result,
                    }),
                },
            }
    # fleet scenarios LAST (they spawn their own hub + workers; the
    # engine above is done by now, so nothing contends)
    if PREFIX_FLEET or CONTROL or FAILOVER or KV_CAPACITY:
        import sys as _sys

        _sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "scripts")
        )
    prefix_fleet_result = None
    if PREFIX_FLEET:
        import prefix_fleet

        prefix_fleet_result = prefix_fleet.run()
        print(
            "prefix_fleet: warm_vs_cold={} route_to_holder={} pulls={} "
            "usd_per_mtok={}".format(
                prefix_fleet_result["warm_vs_cold_ttft"],
                prefix_fleet_result["route_to_holder_frac"],
                prefix_fleet_result["pulls"]["landed"],
                prefix_fleet_result["dollars"]["usd_per_mtok"],
            ),
            file=_sys.stderr,
        )
    scenarios_result = None
    if SCENARIOS:
        import gc
        import sys as _sys

        # scenario engines are tiny-by-default and independent of the
        # headline engine above, so the real-model headline and the
        # CI-scale scenario suite ride ONE invocation. The headline
        # engine's auto-sized KV pool holds most of free HBM though —
        # every stat it feeds the sections above is already
        # snapshotted, so close it and DROP the reference before any
        # scenario engine allocates (on a real chip the scenarios
        # would otherwise fight for the ~15% slack, or fail outright
        # at LOADGEN_SCALE=real).
        asyncio.run(engine.close())
        engine = None
        gc.collect()
        from dynamo_tpu.loadgen import bench as loadgen_bench

        scenarios_result = loadgen_bench.run_suite()
        n_ok = sum(
            1 for r in scenarios_result["results"].values()
            if "error" not in r
        )
        print(
            f"scenarios: {n_ok}/{len(scenarios_result['results'])} ok "
            f"(scale={scenarios_result['scale']['name']})",
            file=_sys.stderr,
        )
    failover_result = None
    if FAILOVER:
        import failover_chaos

        failover_result = failover_chaos.run()
        print(
            "failover: recovered_frac={} byte_identical={} gap_p50={}s "
            "tokens={}".format(
                failover_result["recovered_frac"],
                failover_result["byte_identical"],
                failover_result["replay_ttft_gap_p50_s"],
                failover_result["tokens"],
            ),
            file=_sys.stderr,
        )
    control_result = None
    if CONTROL:
        import control_chaos

        control_result = control_chaos.run()
        # the sampler timeline is diagnostic; cap it so BENCH_OUT stays
        # a small trajectory artifact
        control_result["timeline"] = control_result["timeline"][:200]
        print(
            "control: ttr={} goodput_retained={} ups={} drain_clean={}".format(
                control_result["time_to_recover_s"],
                control_result["goodput"]["retained"],
                control_result["scaling"]["ups"],
                control_result["drain"]["clean"],
            ),
            file=_sys.stderr,
        )
    tp_overlap_result = None
    if TP_OVERLAP:
        import subprocess
        import sys as _sys

        # subprocess: the section needs a fresh jax on 8 virtual CPU
        # devices, and this process is already bound to the real backend
        proc = subprocess.run(
            [
                _sys.executable,
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "scripts", "tp_overlap_bench.py",
                ),
            ],
            capture_output=True, text=True, timeout=600,
        )
        if proc.returncode:
            raise RuntimeError(
                "tp_overlap bench failed (rc=%d):\n%s"
                % (proc.returncode, proc.stderr[-4000:])
            )
        tp_overlap_result = json.loads(proc.stdout.splitlines()[-1])
        print(
            "tp_overlap: exposed_ratio={} wall serialized={}s "
            "overlap={}s identical={}".format(
                tp_overlap_result["exposed_ratio"],
                tp_overlap_result["legs"]["serialized"]["layer_step_wall_s"],
                tp_overlap_result["legs"]["overlap"]["layer_step_wall_s"],
                tp_overlap_result["greedy_byte_identical_vs_tp1"],
            ),
            file=_sys.stderr,
        )
    kv_capacity_result = None
    if KV_CAPACITY:
        import kv_capacity

        kv_capacity_result = kv_capacity.run(budget_mb=KV_CAPACITY_MB)
        cap = kv_capacity_result["capacity"]
        print(
            "kv_capacity: streams bf16={} int8={} int4={} "
            "(x{} vs bf16) int4_match={}".format(
                cap["tiers"]["bf16"]["resident_streams"],
                cap["tiers"]["int8"]["resident_streams"],
                cap["tiers"]["int4"]["resident_streams"],
                cap["capacity_ratio_int4_vs_bf16"],
                kv_capacity_result["quality"]["tiers"]["int4"][
                    "greedy_token_match"
                ],
            ),
            file=_sys.stderr,
        )

    print(json.dumps(headline))
    if BENCH_OUT:
        # machine-readable trajectory artifact: one file, every section
        # keyed (null = section not requested this run)
        sections = {
                    "headline": headline,
                    "spec": spec_result,
                    "mixed": mixed_result,
                    "mixed_spec": mixed_spec_result,
                    "pipeline_ab": pipeline_result,
                    # prefix probe attribution (always present): per-leg
                    # prefill/prefix/compile counter deltas of the
                    # cold/warm waves — the breakdown that explains the
                    # headline prefix_hit_ttft_speedup
                    "prefix_ab": prefix_ab_result,
                    # BENCH_PREFIX_FLEET=1: multi-tenant shared-prefix
                    # fleet scenario (two workers + KV router + pulls)
                    "prefix_fleet": prefix_fleet_result,
                    # BENCH_CONTROL=1: chaos-controller recovery curve
                    # (worker death + spike vs the SLO-driven planner)
                    "control": control_result,
                    # BENCH_FAILOVER=1: request-failover chaos proof
                    # (worker.die mid-stream -> byte-identical resume;
                    # recovered_frac + replay gap + token economics)
                    "failover": failover_result,
                    # BENCH_KV_CAPACITY=1: KV-tier capacity census —
                    # per-tier page bytes + resident streams at a
                    # fixed byte budget, per-tier decode waves, and
                    # the margin-stable greedy token-match quality
                    # bound vs the f32-KV reference
                    "kv_capacity": kv_capacity_result,
                    # BENCH_TP_OVERLAP=1: TP comm/compute overlap ledger
                    # — serialized vs overlapped per-layer step wall +
                    # the measured collective-byte ledger (exposed
                    # exactly 0.5x, total conserved) + greedy
                    # byte-identity vs tp=1
                    "tp_overlap": tp_overlap_result,
                    # BENCH_SCENARIOS=1: the trace-driven scenario suite
                    # (dynamo_tpu/loadgen/) — {scale, results: {name:
                    # section}}, each section scored by SLO-gated
                    # goodput with its trace identity (docs/loadgen.md)
                    "scenarios": scenarios_result,
                    # goodput accounting (always present): SLO-gated
                    # throughput over the measured wave + the
                    # per-request prefix/offload ledgers of the probes
                    "goodput": goodput,
        }
        # provenance: extra.rev (git SHA) + extra.ts in EVERY section,
        # so scripts/bench_history.py joins runs to commits without
        # filename archaeology
        _stamp_provenance(sections)
        with open(BENCH_OUT, "w") as f:
            json.dump(sections, f, indent=2)
            f.write("\n")
    if BENCH_TRACE:
        import sys

        from dynamo_tpu.utils import tracing as _tracing

        # stdout stays the one-line headline artifact; the trace note
        # goes to stderr like other diagnostics. The ring is process-
        # global (the engine may already be closed when BENCH_SCENARIOS
        # freed its HBM above), so dump via the tracing module.
        n_ev = _tracing.dump(BENCH_TRACE)
        print(f"trace: {n_ev} events -> {BENCH_TRACE}", file=sys.stderr)


def _stamp_provenance(sections: dict) -> None:
    """extra.rev (git SHA) + extra.ts on every emitted section: the
    join key scripts/bench_history.py uses to line a BENCH_OUT up
    against commits. GITHUB_SHA wins (CI checkouts can be detached or
    shallow); a local git rev-parse covers dev runs; rev stays null
    outside both."""
    import subprocess

    rev = os.environ.get("GITHUB_SHA") or None
    if not rev:
        try:
            rev = subprocess.run(
                ["git", "rev-parse", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip() or None
        except Exception:  # noqa: BLE001 — provenance is best-effort
            rev = None
    ts = int(time.time())
    for section in sections.values():
        if isinstance(section, dict):
            extra = section.setdefault("extra", {})
            extra.setdefault("rev", rev)
            extra.setdefault("ts", ts)


if __name__ == "__main__":
    import sys

    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(ENV_HELP, end="")
    else:
        main()
