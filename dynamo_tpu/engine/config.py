"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Union

from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.parallel.mesh import MeshConfig


@dataclass
class EngineConfig:
    model: Union[str, ModelConfig] = "tiny"
    checkpoint_dir: Optional[str] = None  # HF safetensors dir; None = random init
    mesh: MeshConfig = field(default_factory=MeshConfig)
    dtype: str = "bfloat16"

    # tokens per KV page (= block_size in KV events). 64 keeps page DMAs
    # >= 64 KB on the fused decode kernel's critical path; drop to 16 for
    # finer prefix-cache granularity at some decode-bandwidth cost
    page_size: int = 64
    num_pages: Optional[int] = None  # total pages incl. trash page 0; None = auto from HBM
    hbm_utilization: float = 0.85    # fraction of free HBM given to KV when auto-sizing

    # "auto": pallas paged kernel on TPU, gather oracle elsewhere;
    # "pallas": force the kernel (interpret mode off-TPU); "gather": oracle
    attn_backend: str = "auto"

    # None = bf16 weights; "int8" = W8A8 dynamic quantization of the dense
    # projections + vocab head (ops/quant.py) — the TPU-native match for
    # the reference baselines' FP8 serving (docs/architecture.md:76-83).
    # Attention activations, norms, embeddings stay bf16.
    quantization: Optional[str] = None

    # None = KV pages in the model dtype; "int8" = per-token-per-kv-head
    # symmetric int8 KV pages with f32 scale pools (ops/quant.py
    # quantize_kv_rows). Decode attention streams every live page each
    # step, so this halves the dominant HBM traffic of the decode phase;
    # all attention math still runs f32 after in-kernel dequantization.
    # "int4" packs two 4-bit values per byte (ops/quant.py
    # quantize_kv_rows_int4): pools shrink to a QUARTER of bf16, with
    # grouped symmetric scales (kv_quant_group features per scale group).
    kv_quantization: Optional[str] = None
    # int4 scale-group size in features per kv head; None = head_dim (one
    # scale per token per kv head, same granularity as the int8 tier —
    # the only grouping the pallas kernels support). Smaller power-of-two
    # divisors of head_dim tighten the quality bound on the gather
    # backend at the cost of more scale channels. Ignored unless
    # kv_quantization == "int4".
    kv_quant_group: Optional[int] = None

    # HBM->host KV offload tier (reference: lib/llm/src/kv reuse/manager):
    # 0 disables; else pages whose refcount hits 0 are write-through
    # copied to a host-RAM pool of this many pages, restored on prefix
    # hit after HBM eviction
    host_kv_pages: int = 0
    offload_batch_pages: int = 16  # pages per background gather dispatch

    max_batch_size: int = 8       # decode slots
    max_model_len: int = 2048     # context limit per sequence
    prefill_chunk: int = 512      # longest single prefill call (longer prompts chunk)
    # activation-memory cap: total tokens (rows x bucket) in one batched
    # prefill dispatch — bounds the [n, bucket, heads, hd] temporaries a
    # big admission wave would otherwise OOM on
    prefill_group_tokens: int = 32768
    decode_steps: int = 8         # decode steps per jit dispatch (lax.scan):
    # amortizes host<->device round trips; finished sequences overshoot at
    # most decode_steps-1 positions (discarded host-side)
    # self-speculative decoding (engine/spec.py): draft the next k tokens
    # by prompt-lookup over the sequence's own history, verify all of
    # them in ONE multi-query model step (rejection-sampling acceptance
    # keeps the sampled distribution exact; greedy acceptance is exact
    # match).  Decode is memory-bandwidth-bound, so every accepted draft
    # token is a model step the sequence did not pay for.  Per-sequence
    # EMA gating drives k -> 0 on unpredictable text (today's behavior).
    spec_decode: bool = False
    spec_k_max: int = 4       # max drafted tokens per verify step
    spec_ngram_max: int = 3   # longest suffix n-gram the proposer matches
    # sliding window (positions) of the per-sequence n-gram index: the
    # proposer evicts registrations older than this, bounding its memory
    # at ~window x ngram_max entries on arbitrarily long streams
    spec_index_window: int = 8192
    # stall-free mixed batching (Sarathi-style): whenever decode-ready
    # rows and pending prefill chunks coexist, pack both into ONE
    # token-budgeted model step — decode rows ride as q_len=1 rows next
    # to the prefill chunks, so an admission wave never stalls running
    # decode streams for longer than one budgeted step. Composes with
    # spec_decode: spec-eligible decode rows inside a mixed step carry
    # their n-gram drafts as ragged q_len = 1+k verify rows (the budget
    # counts 1+k per row, so drafts trade off against prefill chunk
    # size). Unsupported with sp>1.
    # Composes with the int32-packed pallas+quantized KV pools: mid-page
    # decode rows land via byte-lane surgery on the packed rows
    # (ops/quant.scatter_packed_kv_rows), width-agnostic so the int4
    # nibble tier rides too. Runtime-togglable like spec_decode: incompatible
    # engines just never build a mixed step (logged once).
    mixed_batching: bool = False
    # token budget of one mixed step: decode rows always join at 1 each
    # and prefill chunks shrink to fit the leftover (non-final chunks
    # round down to a page multiple). Bounds how long one step can stall
    # decode — the
    # knob that trades ITL (smaller) against prefill throughput (larger).
    # NOTE the budget counts REAL tokens; the dispatch itself is a dense
    # [pow2 rows, chunk-bucket] rectangle, so each decode row also pays
    # bucket-width padded compute (masked in attention, real in the
    # MLP). The per-step wall is bounded either way — a ragged kernel
    # that skips padded query tiles is the named follow-up
    # (ops/pallas_attention.ragged_paged_attention).
    mixed_step_tokens: int = 1024
    # zero-stall step pipeline: build and dispatch step N+1 while step
    # N's sampled tokens are still in flight to the host. Mixed steps'
    # q_len=1 decode rows read their input token from the carry the
    # step programs keep on the device (no host round trip; a dispatch
    # is host arrays plus one launch), so a mixed window can
    # launch behind an in-flight decode or mixed dispatch instead of
    # holding a tick; spec-eligible rows whose host history is stale
    # shed their drafts and still advance at q_len=1 (drafts resume
    # once the sync catches host history up). Greedy streams are
    # byte-identical on vs off. False restores the serialized
    # dispatch->fetch->sync steps (the A/B baseline).
    step_pipeline: bool = True
    # TP comm/compute overlap (tp > 1 meshes): serve through the
    # latency-hiding manual-TP layer executor (parallel/tp_overlap.py)
    # — per-layer psums decomposed into ring reduce-scatter +
    # matmul-fused all-gather with norms/residuals on the row-scattered
    # view, halving EXPOSED collective bytes per layer (asserted in
    # tests/test_tp_overlap.py). Greedy streams stay byte-identical to
    # tp=1 (docs/parallelism.md documents the reduction-order
    # invariant). Serves the pallas backend with int8/int4 packed KV
    # (the kernels' per-layer shard_maps collapse into the executor's
    # single one; block tables, packed pools and scale tiles ride
    # shard-local) and int8 weights (ring_rs_matmul's int32 accumulator
    # ring + global pmax activation scale — bitwise tp=1-identical).
    # Only sp>1 ring prefill and MoE routing still fall back to the
    # GSPMD path, with XLA's latency-hiding scheduler flags requested
    # at init (logged once, reason in tp_overlap_refusal_reason;
    # metrics() attributes tp_overlap_dispatches vs
    # gspmd_fallback_dispatches). Also feeds the collective_bytes /
    # collective_wall_s phase counters the flight recorder digests.
    tp_overlap: bool = False
    # ---- fault-tolerance spine (docs/robustness.md) ----
    # default end-to-end deadline per request, seconds (0 = none). A
    # request-level `x-request-timeout` header overrides it. Expired
    # requests are shed from the admission queue (429 before any device
    # work) or cancelled mid-flight via the cancellation sweep with
    # finish_reason="timeout".
    request_timeout_s: float = 0.0
    # prefill-worker page-wait budget (was a hardcoded 60 s): how long
    # `prefill_only` waits for KV pages before surfacing a typed
    # PoolExhaustedError (HTTP 503). A request deadline shrinks the
    # effective wait further — the wait always fits the caller's budget.
    prefill_wait_s: float = 60.0
    # engine watchdog: a dispatch or result fetch that has not completed
    # within this many seconds trips the degrade ladder and dumps a
    # crash artifact (trace ring + phase stats). 0 disables. Set it well
    # above the slowest expected jit COMPILE on the deployment — the
    # watchdog cannot tell a hung dispatch from a 40 s TPU compile.
    watchdog_dispatch_s: float = 0.0
    # seconds a watchdog-tripped degrade rung stays shed before
    # re-probing (engine/degrade.py); permanent trips (failed dispatch
    # families) never re-probe.
    degrade_reprobe_s: float = 30.0
    # crash-artifact directory for watchdog dumps (trace ring + phase
    # stats JSON); None = DYN_CRASH_DIR env or /tmp.
    crash_dir: Optional[str] = None
    # ---- forensics plane (docs/observability.md "Forensics plane") ----
    # always-on flight recorder: a bounded ring of per-step digests +
    # per-phase latency baselines; SLO breaches / watchdog fires /
    # deadline-shed bursts / sustained anomalies dump a rate-limited
    # forensic artifact (engine/flight_recorder.py; ring size and
    # trigger knobs ride DYN_FLIGHT_* env vars). False disables the
    # ring entirely (byte-identical serving either way).
    flight_recorder: bool = True
    # KV page-custody ledger audit period in seconds
    # (engine/kv_ledger.py; docs/observability.md "KV ledger"). The
    # audit runs at the top of the engine-loop tick — accounting
    # identities, orphan detector, in-flight transfer deadlines — and a
    # violation ticks kv_ledger_violations_total{kind} + arms the
    # flight recorder's kv_leak trigger. None = DYN_KV_AUDIT_S env,
    # default 5.0; 0 disables the audit (transition stamping stays on —
    # it is O(1) per transition and feeds /debug/kv either way).
    kv_audit_s: Optional[float] = None
    seed: int = 0

    def model_config(self) -> ModelConfig:
        cfg = get_config(self.model) if isinstance(self.model, str) else self.model
        return cfg if cfg.dtype == self.dtype else cfg.with_(dtype=self.dtype)

    @property
    def max_pages_per_seq(self) -> int:
        return -(-self.max_model_len // self.page_size)

    def prefill_buckets(self) -> list[int]:
        """Power-of-two token buckets for prefill calls, ending at
        prefill_chunk — each bucket is one compiled graph."""
        buckets = []
        b = max(self.page_size, 16)
        while b < self.prefill_chunk:
            buckets.append(b)
            b *= 2
        buckets.append(self.prefill_chunk)
        return buckets
