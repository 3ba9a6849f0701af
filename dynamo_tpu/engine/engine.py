"""JaxEngine: the continuous-batching execution loop.

Replaces the reference's engine adapters + vLLM core (reference:
lib/engines/vllm0_8/src/lib.rs, SURVEY.md §2.3) with a native loop designed
for XLA's compile-once regime:

- **two compiled step families**: bucketed prefill `[1, T_bucket]` and a
  fixed-shape decode `[max_batch, 1]` — no dynamic shapes, ever;
- the KV cache is **donated** through every step, so scatters update HBM
  in place;
- sampling runs on device inside the same jit (no logits on the host);
- decode attention runs the **Pallas paged kernel** on TPU
  (`ops/pallas_attention.py`), the jnp gather oracle elsewhere;
- the host loop is single-threaded asyncio (the reference's
  progress-engine-with-mailboxes pattern, SURVEY.md §5) and owns the
  allocator, slots and queues.

Scheduling (one loop tick): admit waiting sequences into free slots, run at
most ONE prefill chunk per sequence — same-bucket chunks batched into one
`[n, bucket]` dispatch, capped by `prefill_group_tokens` — then one decode
dispatch, so a long prompt never stalls active decode streams for more than
a chunk (the reference's disagg rationale, reference
docs/disagg_serving.md:1-10, applied to aggregated serving).

Decode — and, with `EngineConfig.step_pipeline` (default), mixed
prefill+decode steps — are **pipelined**: dispatch N+1 is enqueued in a
worker thread (using the on-device sampled tokens of dispatch N as carry
— no host round trip) while N's tokens are fetched for emission, so host
work overlaps device compute. A dispatch is host arrays plus ONE launch:
what decode keeps on the device between dispatches (carry, sampling key,
penalty counts: `StepState`) is taken and returned by the step programs
themselves, and the slow-changing inputs (block tables, sampling/penalty
params) ride from host mirrors in each dispatch's fused uploads
(docs/architecture.md "Step pipeline").
Overshoot tokens of sequences that finished in N are discarded at sync;
their trailing writes land in pages that are never hash-registered, so the
prefix cache stays sound.

Uniform step invariant: a sequence always has KV computed for exactly
`total_tokens - 1` positions when decoding (the newest sampled token is fed
back and its KV written by the next step). Prefill — fresh or resumed after
preemption — computes KV for every current token and samples the next, so
admission and preemption-resume are the same code path.
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
import logging
import os
import threading
import time
import weakref
from collections import deque
from typing import AsyncIterator, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.allocator import PageAllocator
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.degrade import DegradeLadder
from dynamo_tpu.engine.scheduler import (
    Sequence,
    pick_admission_index,
    pick_preemption_victim,
)
from dynamo_tpu.llm.protocols.common import (
    FINISH_REASON_CANCELLED,
    FINISH_REASON_ERROR,
    FINISH_REASON_LENGTH,
    FINISH_REASON_TIMEOUT,
    DeadlineExceededError,
    EngineOutput,
    PoolExhaustedError,
    PreprocessedRequest,
)
from dynamo_tpu.models import llama
from dynamo_tpu.engine.spec import NgramProposer
from dynamo_tpu.ops.sampling import (
    TOP_LOGPROBS_MAX,
    bump_counts,
    sample_block,
    sample_tokens,
    verify_draft_tokens,
)
from dynamo_tpu.engine import flight_recorder as flightmod
from dynamo_tpu.engine import kv_ledger as kvledgermod
from dynamo_tpu.models.config import FULL, WINDOW
from dynamo_tpu.engine import profiler, telemetry
from dynamo_tpu.parallel import mesh as meshmod
from dynamo_tpu.runtime.pipeline.context import Context
from dynamo_tpu.utils import (
    artifacts,
    compile_cache,
    faults,
    instance,
    tracing,
)

log = logging.getLogger("dynamo_tpu.engine")

# a dispatch worker's phases, in the order of the digest's `lock_s`,
# `upload_s`, `enqueue_s`
_WORKER_PHASES = ("eng.lock", "eng.upload", "eng.enqueue")

# how a refusal names each option of `EngineConfig` that a kind of cache
# may refuse at construction ("mesh": one of more than one device)
_REFUSABLE_OPTIONS = {
    "kv_quantization": "kv_quantization={!r}",
    "quantization": "quantization={!r}",
    "mesh": "a mesh of more than one device",
    "host_kv_pages": "host KV offload (host_kv_pages)",
    "spec_decode": "spec_decode",
    "mixed_batching": "mixed_batching",
}

# What a cache that is not "a K pool and a V pool a layer under one list
# of page ids" refuses, a row a kind, keyed by the `ModelConfig` property
# that says the model has it: `why` is the sentence every refused plane
# or option gets (`JaxEngine._refuse_plane`), `options` what construction
# refuses with the few words of why (`_refuse_config`), `mixed` what a
# runtime toggle of mixed batching logs (`_mixed_unsupported_reason`).
# Planes asked of a running engine (disaggregation, prefix export, page
# inject / extract, device-path transfer) call `_refuse_plane` themselves.
CACHE_KIND_REFUSALS = {
    "latent": {
        "why": (
            "{plane} is not served with latent attention ('{name}'): it "
            "is written for a K pool and a V pool a layer, and a latent "
            "cache is ONE pool of [c ; k_r] rows"
        ),
        "options": {
            "kv_quantization": "the latent pool is served in the model's "
                               "dtype; no quantized latent rows yet",
            "quantization": "int8 weights",
            "mesh": "the latent pool has no head axis to shard; tp / sp / "
                    "ep / dp all refuse",
            "host_kv_pages": "",
            "spec_decode": "",
        },
        "mixed": (
            "mixed_batching unsupported with latent attention: the "
            "ragged kernel reads a K pool and a V pool"
        ),
    },
    "hybrid": {
        "why": (
            "{plane} is not served with window beside full attention "
            "('{name}'): it is written for one list of page ids a "
            "sequence, and this cache has two kinds of page, of which the "
            "window kind releases behind the window"
        ),
        "options": {
            "kv_quantization": "the two kinds of pool are served in the "
                               "model's dtype",
            "quantization": "int8 weights",
            "mesh": "tp / sp / ep / dp: the layer holds its share of the "
                    "experts without an exchange",
            "host_kv_pages": "",
            "spec_decode": "the verify step",
            "mixed_batching": "",
        },
        "mixed": (
            "mixed_batching unsupported with window beside full "
            "attention: the ragged step takes one block table a row"
        ),
    },
    "recurrent": {
        "why": (
            "{plane} is not served with Mamba-2 layers beside attention "
            "('{name}'): it moves or shares pages, and this model also "
            "keeps a fixed-size state a sequence that no page holds; pages "
            "without the state at their boundary give a wrong answer"
        ),
        "options": {
            "kv_quantization": "the pages and the state are served in the "
                               "model's dtype",
            "quantization": "int8 weights",
            "mesh": "tp / sp / ep / dp: the state pool has no sharding rule",
            "host_kv_pages": "",
            "spec_decode": "a rejected draft would have to roll the state "
                           "back",
            "mixed_batching": "",
        },
        "mixed": (
            "mixed_batching unsupported with Mamba-2 layers beside "
            "attention: the ragged step has no state slot a row"
        ),
    },
    # not a kind of CACHE (the pools are plain K and V under one list of
    # page ids) but of STEP: what is written for a step that carries one
    # token a sequence, or for rows that stay as a step wrote them
    "dlm": {
        "why": (
            "{plane} is not served with generation by diffusion over "
            "blocks ('{name}'): it is written for a step that carries one "
            "token a sequence over rows that stay as they were written, "
            "and this model's step carries a block whose rows are "
            "rewritten by every pass until its commit pass"
        ),
        "options": {
            "kv_quantization": "a block pass reads its own rows back "
                               "through the ragged kernel in the model's "
                               "dtype",
            "quantization": "int8 weights are not judged for this family",
            "mesh": "tp / sp / ep / dp: the block step is one device's",
            "host_kv_pages": "",
            "spec_decode": "a block pass is its own draft and verify",
            "mixed_batching": "",
        },
        "mixed": (
            "mixed_batching unsupported with generation by diffusion "
            "over blocks: the ragged step has no block rows"
        ),
    },
}


class StepState(NamedTuple):
    """What the decode path keeps on the device between dispatches. The
    step programs own it the way they own `kv`: every program takes it
    whole (donated), slices to its own static width inside, and returns
    its successor, so a dispatch is host arrays plus ONE launch. Rows a
    program does not advance keep their values, so a program that ends
    late can never clobber a row that another one armed."""

    toks: jax.Array  # [B] i32: each slot's next input token (the carry)
    lps: jax.Array   # [B] f32: its logprob
    tid: jax.Array   # [B, TOP_LOGPROBS_MAX] i32: its alternatives
    tlp: jax.Array   # [B, TOP_LOGPROBS_MAX] f32
    key: jax.Array   # sampling key; a program splits it and returns the rest
    # [B, V] int8 occurrence counts: rides only into the penalty / seeded
    # programs (allocated on first use); None for every other program
    counts: Optional[jax.Array] = None
    # a model generated by diffusion over blocks (`_dlm_multi`), a row a
    # slot: (the open block's ids [B, L] i32, which of its positions still
    # hold a mask [B, L] bool, its first position [B] i32). Rides only
    # into the block step program; None for every other program and model
    dlm: Optional[tuple] = None

    def split(self) -> tuple:
        """(state holding the successor key, this program's key): the
        stream `key, sub = jax.random.split(key)` draws on the host."""
        ks = jax.random.split(self.key)
        return self._replace(key=ks[0]), ks[1]

    def arm(self, slots, S) -> "StepState":
        """Write sampled rows `S` = (toks, lps[, tid, tlp]) into the
        carry at `slots` [n]; a slot >= B drops the row."""
        new = self._replace(
            toks=self.toks.at[slots].set(S[0], mode="drop"),
            lps=self.lps.at[slots].set(S[1], mode="drop"),
        )
        if len(S) == 4:
            new = new._replace(
                tid=self.tid.at[slots].set(S[2], mode="drop"),
                tlp=self.tlp.at[slots].set(S[3], mode="drop"),
            )
        return new


class _Dispatch:
    """One in-flight dispatch (decode scan, spec verify, or a pipelined
    mixed step): device tokens + the slot snapshot it was built from."""

    __slots__ = ("out_dev", "snapshot", "steps", "spec", "pos0", "moe",
                 "draft_lens", "mixed", "bld")

    def __init__(self, out_dev, snapshot, steps, spec=False, pos0=None,
                 draft_lens=None, mixed=False, bld=None, moe=None):
        self.out_dev = out_dev          # [steps, B] device array
        # an expert model's decode dispatch: its load, [2] on the device
        # (experts with a token, most tokens on one expert; means)
        self.moe = moe
        self.snapshot = snapshot        # list[(slot_index, Sequence)]
        self.steps = steps
        # speculative verify dispatch: out_dev is (tokens [B, T],
        # n_emit [B]); pos0/draft_lens are the per-slot positions and
        # draft lengths the build used (rollback at sync needs them)
        self.spec = spec
        self.pos0 = pos0
        self.draft_lens = draft_lens
        # pipelined mixed step: out_dev is the mixed step's sampled
        # tokens (or (out, n_emit) with spec rows); bld is the host
        # build dict — sync routes through _sync_mixed
        self.mixed = mixed
        self.bld = bld


class _DecodeBuild:
    """Host-built inputs for one decode dispatch (see
    JaxEngine._maybe_dispatch_decode)."""

    __slots__ = ("positions", "tables", "act", "temp", "topk", "topp",
                 "rows_i", "rows_f", "use_ext", "want_lps",
                 "want_tops", "active", "steps", "all_greedy",
                 "width", "spec", "tokens", "draft", "dlen", "pos0",
                 "build_s", "win_pages", "block_pos0")

    def __init__(self, **kw):
        self.spec = False  # speculative verify build (host-built tokens)
        self.build_s = 0.0  # host time of the build (the digest's column)
        # a hybrid model's decode build: `_kv_window_pages` of it
        self.win_pages = None
        for k, v in kw.items():
            setattr(self, k, v)


class JaxEngine:
    """Paged continuous-batching engine over a jax Mesh.

    Conforms to the pipeline engine protocol: `await generate(Context) ->
    AsyncIterator[dict]` streaming EngineOutput dicts (token ids; the
    detokenizing Backend sits downstream).
    """

    def __init__(self, config: EngineConfig, params=None, devices=None):
        self.config = config
        self.model_cfg = config.model_config()
        self._dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32

        # fleet observability (docs/observability.md "Fleet plane"):
        # mint the process's stable instance label (it stamps JSONL
        # logs, Prometheus series and the hub registration), claim the
        # trace process label unless the run mode already did, and arm
        # the process-wide compile-event listener so every jit cache
        # miss lands as an `engine.compile` span + counter instead of a
        # silent multi-second stall.
        self.worker_label = instance.worker_id()
        tracing.set_process_default(f"worker-{self.worker_label}")
        telemetry.install_compile_listener()
        compile_cache.configure()

        meshmod.validate_model_mesh(self.model_cfg, config.mesh)
        self.mesh = meshmod.build_mesh(config.mesh, devices)
        self._kv_sharding = meshmod.kv_cache_sharding(self.mesh)

        backend = jax.default_backend()
        # the serving engine's mesh is tp-only (dp = separate workers, sp
        # for long prefill, ep future); the pallas decode kernel runs
        # under tp via shard_map (AttnSpec.mesh) — other axes fall back
        mc = config.mesh
        tp_only = mc.num_devices == mc.tp
        # Mosaic needs the folded KV width lane-aligned per tp shard (the
        # kernels slice [*, K*Hd] refs); tiny test models fall back
        kw_ok = (
            self.model_cfg.latent_pool_width if self.model_cfg.latent
            else self.model_cfg.num_kv_heads * self.model_cfg.head_dim
        ) % (128 * mc.tp) == 0
        # window beside full attention: pools, page ids and block tables
        # per kind of layer (docs/kv_cache.md "Window pools")
        self._hybrid = self.model_cfg.hybrid
        # Mamba-2 layers beside attention: a fixed-size state a sequence
        # in pools indexed by the decode slot, beside the pages of the
        # attention layers (docs/kv_cache.md "State pools")
        self._recurrent = self.model_cfg.recurrent
        self._state_resets = 0  # first chunks dispatched: a state zeroed
        # pages of such a model never enter the prefix cache: a hash
        # would name pages whose window-kind twin is released, or whose
        # state at the page's boundary nobody kept
        self._no_prefix_cache = self._hybrid or self._recurrent
        # generation by diffusion over blocks: the decode dispatch is the
        # block step (`_dlm_multi`), the prompt is encoded under the
        # block-causal mask up to its last whole block, and what a pass
        # writes counts only once the block's commit pass has run
        # (docs/kv_cache.md "Block steps")
        self._dlm = self.model_cfg.dlm
        self._mask_block = self.model_cfg.block_length or 1
        # (bucket, page width, statics) whose wider groups are loaded
        self._tail_groups_loaded: set[tuple] = set()
        if self._hybrid:
            kw_ok = all(
                w % 128 == 0
                for kind in (FULL, WINDOW)
                for w in (self.model_cfg.attn_kind(kind).k_width,
                          self.model_cfg.attn_kind(kind).v_width)
            )
        self._refuse_config()
        if config.attn_backend == "auto":
            self._attn_pallas = backend == "tpu" and tp_only and kw_ok
            self._attn_interpret = False
            if backend == "tpu" and not self._attn_pallas:
                # LOUD: on TPU the gather fallback is the slow path — a
                # silently degraded flagship mesh is the failure this guards.
                # dp>1 inside ONE engine cannot run the fused kernel
                # soundly (it writes pages; dp-replicated pools would
                # diverge per shard) — dp is designed as separate
                # workers (docs/parallelism.md); sp is a documented v1
                # kernel limit; kw misalignment is a model-shape limit.
                why = (
                    "mesh has non-tp axes "
                    f"(dp={mc.dp} sp={mc.sp} ep={mc.ep})"
                    if not tp_only
                    else "folded KV width not lane-aligned per tp shard"
                )
                log.warning(
                    "attn_backend='auto' on TPU falls back to GATHER "
                    "attention (%s): decode will be far below the pallas "
                    "kernel's throughput. For dp, run separate workers "
                    "per replica (docs/parallelism.md) instead of an "
                    "in-engine dp mesh.",
                    why,
                )
        elif config.attn_backend == "pallas":
            if not tp_only:
                raise ValueError(
                    "attn_backend='pallas' supports single-device or "
                    "tp-only meshes (got "
                    f"{dict(dp=mc.dp, sp=mc.sp, ep=mc.ep)}); "
                    "use 'auto'"
                )
            self._attn_pallas = True
            self._attn_interpret = backend != "tpu"
        elif config.attn_backend == "gather":
            self._attn_pallas = False
            self._attn_interpret = False
        else:
            raise ValueError(
                f"unknown attn_backend {config.attn_backend!r}; "
                "expected 'auto', 'pallas' or 'gather'"
            )
        # mesh for shard_map'ing the kernel; None on a single device
        self._attn_mesh = self.mesh if mc.num_devices > 1 else None
        if self._attn_pallas and config.prefill_chunk % config.page_size:
            # the pallas prefill page-scatter writes WHOLE pages; a
            # non-page-multiple chunk would end mid-page and the next
            # chunk's write would clobber it from offset 0
            raise ValueError(
                f"prefill_chunk ({config.prefill_chunk}) must be a "
                f"multiple of page_size ({config.page_size}) on the "
                "pallas attention backend"
            )

        # sequence-parallel serving: sp > 1 prefills prompts with RING
        # attention over the sp axis (ops/ring_attention.py) — the
        # long-context mode. The uncached tail must prefill in ONE chunk
        # (ring = one pass over the sharded sequence); the prefix cache
        # COMPOSES: cached pages join as an extra softmax block and the
        # ring runs only over the tail (cached-prefix ring prefill)
        self._sp = mc.sp > 1
        if self._sp:
            if config.prefill_chunk < config.max_model_len:
                raise ValueError(
                    f"sp>1 (ring attention) needs prefill_chunk "
                    f"({config.prefill_chunk}) >= max_model_len "
                    f"({config.max_model_len}): prompts prefill whole"
                )
            if config.host_kv_pages:
                raise ValueError("host KV offload unsupported with sp>1")

        # int8 KV cache: per-token-per-kv-head quantized pages + f32 scale
        # pools (ops/quant.quantize_kv_rows) — halves the page streaming
        # that dominates decode. Scope: the serving paths (pallas +
        # gather, prefill + decode, disagg, offload) AND ring (sp) long-
        # context serving (the ring attends the fresh chunk's bf16 k/v;
        # quantization touches the pool write and the cached-prefix
        # gather)
        self._kv_quant = config.kv_quantization
        if self._kv_quant is not None and self._kv_quant not in ("int8", "int4"):
            raise ValueError(
                f"unknown kv_quantization {config.kv_quantization!r}; "
                "expected 'int8' or 'int4'"
            )
        # int4 tier: two nibbles per pool byte (ops/quant.
        # quantize_kv_rows_int4) — a QUARTER of bf16's page bytes, with
        # grouped scales. _kv_int4_groups = scale groups per kv head
        # (head_dim // kv_quant_group); 0 on the int8/bf16 tiers.
        self._kv_int4_groups = 0
        if self._kv_quant == "int4":
            hd_ = self.model_cfg.head_dim
            grp = config.kv_quant_group or hd_
            if grp <= 0 or hd_ % grp:
                raise ValueError(
                    f"kv_quant_group={config.kv_quant_group} must divide "
                    f"head_dim={hd_}"
                )
            self._kv_int4_groups = hd_ // grp
            if self._kv_int4_groups > 1 and self._attn_pallas:
                # the int4 pallas kernels fold scales with a per-head
                # repeat: only one scale group per head fits that layout.
                # Finer groups are a gather-backend refinement.
                if config.attn_backend == "pallas":
                    raise ValueError(
                        f"kv_quant_group={grp} (< head_dim) with "
                        "attn_backend='pallas' is unsupported: the int4 "
                        "kernels need one scale group per kv head — drop "
                        "kv_quant_group or use attn_backend='gather'"
                    )
                log.warning(
                    "kv_quantization='int4' with kv_quant_group=%d (< "
                    "head_dim): falling back to gather attention — the "
                    "pallas kernels need one scale group per head", grp,
                )
                self._attn_pallas = False
        if self._kv_quant and self._attn_pallas and config.page_size % 128:
            # the int8 kernels put scale-page tokens in lanes: page_size
            # must be a lane multiple for Mosaic to slice the scale tiles
            if config.attn_backend == "pallas":
                raise ValueError(
                    f"kv_quantization with attn_backend='pallas' needs "
                    f"page_size % 128 == 0 (got {config.page_size})"
                )
            log.warning(
                "kv_quantization with page_size=%d (not a multiple of 128): "
                "falling back to gather attention — use page_size=128 to "
                "keep the pallas kernels", config.page_size,
            )
            self._attn_pallas = False
        # int32-PACKED int8 pools (ops/quant.pack_kv_slots): f32-class DMA
        # tiling recovers the int8 (32,128)-tile penalty (the packed
        # format is what both benchmark cells run). Serving (pallas) path
        # only — the gather/sp paths keep dense int8 pools, and the
        # wire/offload formats stay dense int8 (pack/unpack at the edges)
        self._kv_packed = bool(
            self._kv_quant and self._attn_pallas and not self._sp
        )

        # self-speculative decoding (engine/spec.py): the verify step is
        # a multi-query unified step — row-scatter KV write + the oracle
        # attention over the slot matrix (gather backends) or the ragged
        # flash kernel (pallas backends, same path mixed steps read
        # through). int32-PACKED pools row-scatter through the byte-lane
        # write (ops/quant.scatter_packed_kv_rows), so the packed
        # pallas+quantized tier composes.
        if config.spec_decode and config.spec_k_max < 1:
            raise ValueError("spec_k_max must be >= 1")

        # stall-free mixed batching (docs/architecture.md "Stall-free
        # mixed batching"): decode rows ride chunked-prefill steps as
        # q_len=1 rows of one token-budgeted dispatch. The flag is
        # runtime-togglable like spec_decode; explicit misconfiguration
        # at init fails fast, a runtime toggle on an incompatible engine
        # just never builds a mixed step (logged once, _mixed_tick).
        self._mixed_warned = False
        # tripped (with a loud log) when a mixed dispatch fails: the
        # engine degrades to the contained normal paths permanently
        # rather than retrying a broken compiled family every tick
        self._mixed_disabled = False
        if config.mixed_batching:
            why = self._mixed_unsupported_reason()
            if why:
                raise ValueError(why)

        # TP comm/compute overlap (EngineConfig.tp_overlap,
        # docs/parallelism.md "TP comm/compute overlap"): prefer the
        # latency-hiding manual-TP layer executor — per-layer psums
        # decomposed into ring reduce-scatter + matmul-fused all-gather
        # (parallel/tp_overlap.py), halving exposed collective bytes.
        # The executor covers dense tp-only meshes on BOTH serving
        # backends — the pallas kernels and the int8/int4 packed KV
        # pools run inside the executor's single shard_map (the
        # kernels' per-layer shard_maps collapse into it), and int8
        # quantized weights ride the ring matmuls with an int32
        # reduce-scatter epilogue. The refusals (MoE routing, sp>1 /
        # non-tp mesh axes) fall back to GSPMD with XLA's
        # latency-hiding scheduler flags requested instead.
        self._tp_overlap_manual = bool(
            config.tp_overlap and mc.tp > 1 and tp_only
            and not self.model_cfg.num_experts
        )
        # why the manual executor did NOT serve (the /metrics
        # gspmd_fallback_dispatches{reason} label; "" when it serves or
        # tp_overlap is off/moot)
        self.tp_overlap_refusal_reason = ""
        if config.tp_overlap and mc.tp > 1 and not self._tp_overlap_manual:
            why = (
                "MoE routing" if self.model_cfg.num_experts
                else "sp>1 ring prefill" if self._sp
                else "non-tp mesh axes"
            )
            self.tp_overlap_refusal_reason = why
            added = []
            if backend == "tpu":
                from dynamo_tpu.parallel.tp_overlap import (
                    request_gspmd_overlap_flags,
                )

                added = request_gspmd_overlap_flags()
            log.info(
                "tp_overlap: manual ring executor refused (%s) — "
                "GSPMD fallback%s",
                why,
                (
                    f" with XLA overlap flags {added}"
                    " (effective for computations compiled after this"
                    " point; set them in the launch env to cover"
                    " already-compiled executables)"
                    if added else ""
                ),
            )
        elif self._tp_overlap_manual:
            log.info(
                "tp_overlap: manual ring executor is the serving path "
                "(tp=%d, exposed collective bytes/layer halved)", mc.tp
            )

        # make or load, quantize, place; closed when the leaves are ready
        with profiler.phase("eng.init.weights"):
            if params is None:
                if config.checkpoint_dir:
                    from dynamo_tpu.models.weights import load_params

                    params = load_params(
                        config.checkpoint_dir, self.model_cfg,
                        dtype=self._dtype,
                    )
                    # logical model size, before quantization adds scale
                    # vectors and a standalone int8 vocab head
                    self.param_count = llama.param_count(params)
                    if config.quantization:
                        from dynamo_tpu.ops.quant import quantize_params

                        params = quantize_params(
                            params, self.model_cfg, mode=config.quantization
                        )
                else:
                    if config.quantization not in (None, "int8"):
                        raise ValueError(
                            f"unknown quantization {config.quantization!r}"
                        )
                    from dynamo_tpu.ops.quant import logical_param_count

                    # every dense leaf is created under its target sharding
                    # (no device ever holds the whole tree), and quantized
                    # layers are quantized AS they are initialized: peak
                    # memory is "int8 so far + one bf16 layer", which lets
                    # 8B-class models random-init on a 16 GB chip
                    params = llama.init_params(
                        self.model_cfg, jax.random.PRNGKey(config.seed),
                        dtype=self._dtype, quantize=bool(config.quantization),
                        shardings=meshmod.param_shardings(
                            self.model_cfg, self.mesh),
                    )
                    self.param_count = logical_param_count(
                        params, self.model_cfg)
                params = meshmod.shard_params(
                    params, self.model_cfg, self.mesh)
            else:
                from dynamo_tpu.ops.quant import (
                    is_quantized,
                    logical_param_count,
                )

                if config.quantization and not any(
                    is_quantized(lp.get("wq")) for lp in params["layers"]
                ):
                    raise ValueError(
                        "quantization set but caller-provided params are "
                        "unquantized — pass ops.quant.quantize_params output"
                    )
                self.param_count = logical_param_count(params, self.model_cfg)
            jax.block_until_ready(params)

        self.page_size = config.page_size
        with profiler.phase("eng.init.pools"):
            # the window kind's pool (0 pages for every other model): what
            # `max_batch_size` rows can hold at once; the full kind takes the
            # rest of the memory (`_auto_num_pages`)
            self.win_num_pages = self._win_pool_pages() if self._hybrid else 0
            self.num_pages = config.num_pages or self._auto_num_pages(params)
            num_slots = self.num_pages * self.page_size
            # the pools are created UNDER their shardings: the pool is
            # sized to each device's free memory, so a layer's whole
            # unsharded pool is tp times what one device can hold. Scale
            # pools [P, SUBL, S] shard over tp on the sublane-row dim (each
            # shard gets an aligned >=8-row block of its heads)
            self.kv = llama.init_kv_cache(
                self.model_cfg, num_slots, dtype=self._dtype,
                kv_quant=self._kv_quant, page_size=self.page_size,
                tp=config.mesh.tp, packed=self._kv_packed,
                kv_quant_group=config.kv_quant_group,
                sharding=self._kv_sharding,
                scale_sharding=jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec(None, "tp", None)
                ),
                win_slots=self.win_num_pages * self.page_size,
                # a row a decode slot and the trash row (0 for a model
                # that keeps no state: no pool, no argument)
                state_slots=(
                    config.max_batch_size + 1 if self._recurrent else 0
                ),
            )
            jax.block_until_ready(self.kv)
        self.params = params

        self._event_seq = 0
        self._event_subscribers: list[Callable[[dict], None]] = []
        # per-request finish summaries (ttft/itl/queue-wait/tokens) feed
        # the Prometheus histograms (llm/http/metrics.EngineMetrics) and
        # anything else that wants request-level latency without scraping
        # per-frame meta fields
        self._request_observers: list[Callable[[dict], None]] = []
        self.allocator = PageAllocator(
            self.num_pages, self.page_size, on_event=self._emit_event,
            on_cached=self._on_page_cached if config.host_kv_pages else None,
        )
        # page-custody ledger (engine/kv_ledger.py): every allocator
        # transition stamped, holdings attributed per request/plane, and
        # a periodic loop audit (config.kv_audit_s / DYN_KV_AUDIT_S)
        # runs the orphan detector; violations arm the flight
        # recorder's kv_leak trigger via _on_kv_leak
        self.kv_ledger = kvledgermod.KvLedger(
            allocator=self.allocator,
            on_leak=self._on_kv_leak,
        )
        self.allocator.ledger = self.kv_ledger
        # the window kind's pages: their own ids, free list and ledger
        # (the same owners; both audits run, both must close)
        self.win_allocator = self.kv_ledger_win = None
        self._win_released = 0  # window pages released behind the window
        if self._hybrid:
            self.win_allocator = PageAllocator(
                self.win_num_pages, self.page_size
            )
            self.kv_ledger_win = kvledgermod.KvLedger(
                allocator=self.win_allocator, on_leak=self._on_kv_leak,
            )
            self.win_allocator.ledger = self.kv_ledger_win
        # HBM->host offload tier (engine/offload.py); None when disabled
        self.host_pool = None
        # pause switch: a D2H page gather holds _kv_lock for its whole
        # copy — callers that need clean latency windows (benchmarks,
        # admission-heavy phases) can park the tier and resume later
        self.offload_paused = False
        self._pending_offload: dict[int, tuple[int, Optional[int]]] = {}
        self._offload_task: Optional[asyncio.Task] = None
        # restore cost gate (reference: the tiered manager's +40% TTFT
        # claim is the UPSIDE case — the tier must never make TTFT
        # worse): EMAs of the measured restore H2D rate and the
        # effective serving prefill rate decide per hit whether a
        # host-tier restore beats recomputing the prefix. Both calibrate
        # from real traffic (first restore always runs).
        self._ema_restore_bps: Optional[float] = None
        self._ema_prefill_tps: Optional[float] = None
        self.offload_gate_stats = {"restored": 0, "declined": 0, "failed": 0}
        # strong refs to fire-and-forget calibration tasks (the loop
        # holds tasks only weakly; an unreferenced one can be GC'd
        # mid-flight and silently drop its EMA update)
        self._bg_tasks: set = set()
        if config.host_kv_pages:
            from dynamo_tpu.engine.offload import HostKvPool

            _kw = self.model_cfg.num_kv_heads * self.model_cfg.head_dim
            self.host_pool = HostKvPool(
                config.host_kv_pages,
                self.model_cfg.num_layers,
                self.page_size,
                # int4 pool rows are nibble-packed: half the byte width
                _kw // 2 if self._kv_quant == "int4" else _kw,
                dtype=np.int8 if self._kv_quant else self._dtype.dtype,
                on_event=self._emit_event,
                scale_width=(
                    self._kv_scale_channels() if self._kv_quant else None
                ),
            )
            self.host_pool.ledger = self.kv_ledger
            self.kv_ledger.host_pool = self.host_pool

        self.waiting: deque[Sequence] = deque()
        self.slots: list[Optional[Sequence]] = [None] * config.max_batch_size
        self._prefilling: deque[Sequence] = deque()
        self._inflight: Optional[_Dispatch] = None
        # slot -> host-known carry override (a disagg inject's first
        # token, the re-arm after a speculative / mixed sync): it enters
        # the next decode program as a value and a mask column of the
        # fused upload. First tokens sampled on the device need none:
        # the prefill program writes them into the carry itself.
        self._overrides: dict[int, int] = {}
        # device-carry validity: _carry_ok[slot] means the device carry
        # vector row holds the slot's CURRENT input token (set after a
        # decode dispatch updates it, or after a mixed step's in-jit
        # carry scatter) — the step pipeline's license to build the next
        # window from the device carry while host history is still
        # stale. Invalidated whenever an override supersedes the carry
        # (prefill first tokens, spec verify syncs, disagg injects) and
        # on preemption/finish (the slot may be reused).
        self._carry_ok = np.zeros(config.max_batch_size, bool)
        # slow-changing per-slot dispatch inputs: block tables and
        # sampling/penalty params. The loop thread keeps these host
        # mirrors current (`_mark_slot_state`: admit / page growth) and
        # every dispatch BUILD snapshots the rows it needs into its
        # fused upload (a few KB) — nothing of them lives on the device
        # between dispatches. Layout: samp_f = [temp, top_p, freq_pen,
        # pres_pen, rep_pen], rows_i = [top_k, seed, block table]. Rows
        # of released slots keep garbage (inactive rows are masked /
        # write the trash page).
        _B = config.max_batch_size
        _W = config.max_pages_per_seq
        # a hybrid model's row carries two tables: [full | window]
        self._host_rows_i = np.zeros(
            (_B, 2 + _W * (2 if self._hybrid else 1)), np.int32
        )
        self._host_samp_i = self._host_rows_i[:, :2]  # views of one
        self._host_tables = self._host_rows_i[:, 2:]  # upload block
        self._host_samp_i[:, 1] = -1  # seed sentinel
        self._host_samp_f = np.zeros((_B, 5), np.float32)
        self._host_samp_f[:, 1] = 1.0  # top_p
        self._host_samp_f[:, 4] = 1.0  # rep_pen
        # serializes the donated self.kv and self._state between the
        # decode worker thread and prefill dispatches the event-loop
        # thread may run concurrently via the public prefill_only path
        self._kv_lock = threading.Lock()
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False
        # replicated over the mesh, as the step programs return it
        self._state_sharding = meshmod.replicated(self.mesh)
        self._state = jax.device_put(
            StepState(
                toks=np.zeros(_B, np.int32),
                lps=np.zeros(_B, np.float32),
                tid=np.zeros((_B, TOP_LOGPROBS_MAX), np.int32),
                tlp=np.zeros((_B, TOP_LOGPROBS_MAX), np.float32),
                key=jax.random.PRNGKey(config.seed ^ 0x5EED),
                dlm=(
                    np.zeros((_B, self._mask_block), np.int32),
                    np.ones((_B, self._mask_block), bool),
                    np.zeros(_B, np.int32),
                ) if self._dlm else None,
            ),
            self._state_sharding,
        )
        self._step_count = 0
        # engine-side phase accounting: cumulative wall spent inside the
        # (device-serializing) prefill/decode dispatch calls and the
        # decode result fetches, plus the token counts they moved. A jit
        # call on an attached chip returns once the work is ENQUEUED, so
        # the dispatch walls are host enqueue time (plus any wait for a
        # free slot in the runtime's queue) and the sync walls are the
        # real waits for the device; the token counters are the
        # load-bearing part. Snapshot via phase_stats.
        self._phase_stats = {
            "prefill_dispatch_s": 0.0,
            "prefill_tokens": 0,
            "prefill_dispatches": 0,
            "decode_dispatch_s": 0.0,
            "decode_sync_s": 0.0,
            "decode_tokens": 0,
            "decode_dispatches": 0,
            # speculative decode: one spec dispatch = ONE model step that
            # verifies up to spec_k_max drafted tokens per row;
            # spec_rows = sequence-steps (rows x dispatches), so
            # spec_emitted / spec_rows is the per-sequence effective
            # tokens-per-model-step (non-speculative decode is 1.0)
            "spec_dispatch_s": 0.0,
            "spec_sync_s": 0.0,
            "spec_dispatches": 0,
            "spec_rows": 0,
            "spec_drafted": 0,
            "spec_accepted": 0,
            "spec_emitted": 0,
            # generation by diffusion over blocks: one dlm dispatch =
            # `decode_steps` PASSES of the block step, each carrying a
            # whole block a row. Counted where a dispatch LANDS, over the
            # rows still live then: dlm_row_passes = rows x passes (the
            # commit passes among them), dlm_filled = masked positions
            # those passes filled, dlm_committed = blocks whose commit
            # pass ran; dlm_filled / dlm_row_passes is the tokens a row a
            # pass (block / (steps + 1) when every row is in step).
            # dlm_passes counts passes dispatched (dispatches x passes)
            "dlm_dispatch_s": 0.0,
            "dlm_sync_s": 0.0,
            "dlm_dispatches": 0,
            "dlm_passes": 0,
            "dlm_row_passes": 0,
            "dlm_filled": 0,
            "dlm_committed": 0,
            # mixed prefill+decode steps (stall-free batching): one
            # mixed_step = ONE dispatch carrying mixed_decode_rows
            # decode rows (1 budget token each) + mixed_prefill_tokens
            # chunk tokens; tokens_max is the largest per-step budget
            # use (the scheduler must keep it <= mixed_step_tokens).
            # decode_stall_saved_s approximates the decode stall the
            # piggybacked steps avoided: the dispatch+fetch wall of every
            # mixed step that carried decode rows — exactly the window
            # those rows would have spent parked behind a separate
            # prefill dispatch on the donated cache.
            "mixed_dispatch_s": 0.0,
            "mixed_sync_s": 0.0,
            "mixed_steps": 0,
            "mixed_decode_rows": 0,
            "mixed_prefill_tokens": 0,
            "mixed_step_tokens_max": 0,
            "mixed_decode_stall_saved_s": 0.0,
            # spec x mixed composition: decode rows that rode a mixed
            # step as ragged verify windows (their drafted/accepted/
            # emitted counts fold into the spec_* counters above, so
            # spec_acceptance_rate/spec_tokens_per_step stay one truth)
            "mixed_spec_rows": 0,
            # step pipeline (EngineConfig.step_pipeline): sync walls
            # spent while ANOTHER dispatch was already in flight — time
            # the host fetch overlapped device compute instead of
            # serializing against it. pipeline_overlapped counts the
            # syncs that overlapped; mixed_holds counts the ticks the
            # SERIALIZED mixed path parked both planes waiting for an
            # in-flight decode dispatch (0 with pipelining on);
            # mixed_carry_rows counts mixed decode rows whose input
            # token came from the device carry instead of host history;
            # mixed_spec_shed counts spec-eligible rows that shed their
            # drafts because host history was stale (they advanced at
            # q_len=1 — the shed-don't-stall fallback).
            "pipeline_overlap_s": 0.0,
            "pipeline_overlapped": 0,
            "mixed_holds": 0,
            "mixed_carry_rows": 0,
            "mixed_spec_shed": 0,
            # 0/1: mixed dispatch failed and the engine degraded to the
            # contained normal paths (see _mixed_disabled)
            "mixed_disabled": 0,
            # fault-tolerance spine (docs/robustness.md): watchdog
            # firings (a dispatch/fetch stalled past watchdog_dispatch_s
            # and tripped a degrade rung), requests shed past-deadline
            # BEFORE any device work (429), and mid-flight deadline
            # expirations resolved by the cancellation sweep (timeout)
            "watchdog_fired": 0,
            "deadline_shed": 0,
            "deadline_timeouts": 0,
            # prefix/offload economics (docs/kv_cache.md): reservations
            # that reused >= 1 cached block, fully-cached prompts (only
            # the trailing page recomputes), tokens reused from the HBM
            # tier / restored from the host tier, and the tail tokens a
            # hit still had to prefill — the engine-side attribution the
            # bench's prefix_ab section diffs cold vs warm
            "prefix_hits": 0,
            "prefix_full_hits": 0,
            "prefix_reused_tokens": 0,
            "prefix_restored_tokens": 0,
            "prefix_tail_tokens": 0,
            # per-layer TP collective attribution (tp>1 tp-only meshes;
            # docs/parallelism.md "TP comm/compute overlap"): EXPOSED
            # collective bytes per dispatch kind — the closed form
            # behind the exposed-bytes 0.5x invariant
            # (tp_overlap.collective_bytes_per_layer) times the
            # dispatch's physical token rows — plus collective_wall_s,
            # those bytes over the init-time psum bandwidth probe (an
            # ESTIMATE of the comm share of dispatch wall, not a device
            # measurement; the flight recorder digests it as such).
            "prefill_collective_bytes": 0,
            "decode_collective_bytes": 0,
            "spec_collective_bytes": 0,
            "mixed_collective_bytes": 0,
            "collective_wall_s": 0.0,
            # per-dispatch executor attribution (tp>1 tp-only meshes):
            # dispatches the manual ring executor served vs dispatches
            # that took the GSPMD path (with tp_overlap requested, that
            # means a silently-refused config — the refusal reason rides
            # /metrics as gspmd_fallback_dispatches{reason}). A config
            # the executor was expected to serve but didn't reads here
            # in telemetry instead of in a profile.
            "tp_overlap_dispatches": 0,
            "gspmd_fallback_dispatches": 0,
        }
        # updates run in worker threads outside _kv_lock (serving prefill
        # + concurrent prefill_only dispatches) — guard the RMWs
        self._phase_lock = threading.Lock()
        self._preemptions = 0  # sequences preempted for want of KV pages
        # frames put on out_queues and the tokens in them (`_emit`): a
        # landing puts ONE frame per sequence, so tokens / frames is
        # `decode_steps` in steady decode and 1 for a first-token emit
        self._frames = self._frame_tokens = 0
        # the collector's passes, and the freeze of what set-up built
        # (telemetry.HeapWatch; docs/observability.md "The collector")
        self._heap = telemetry.HeapWatch()
        weakref.finalize(self, self._heap.detach)
        # newest dispatch's first output (under _kv_lock): ready = the
        # device has drained all that was queued
        self._last_out = None
        self._t_fetched = 0.0  # when the newest fetch landed on the host
        # the digest's tick columns (`_tick_lap`): when the newest landing
        # ended (0 = none since the loop sat idle) and the loop thread's
        # phase seconds then
        self._t_landed = 0.0
        self._phase_lap: dict[str, float] = {}
        # per-token exposed collective bytes across the layer stack (0
        # when tp collectives are absent or owned by another executor:
        # tp=1, sp ring prefill)
        self._collective_tok_bytes = 0
        self._collective_bps = 0.0
        if mc.tp > 1 and tp_only:
            from dynamo_tpu.parallel.tp_overlap import (
                collective_bytes_per_layer,
            )

            self._collective_tok_bytes = (
                self.model_cfg.num_layers * collective_bytes_per_layer(
                    self.model_cfg.hidden_size, 1, mc.tp,
                    itemsize=jnp.dtype(self._dtype).itemsize,
                    overlap=self._tp_overlap_manual,
                )
            )
            self._collective_bps = self._calibrate_collective_bw()

        # ---- fault-tolerance spine (docs/robustness.md) ----
        faults.load_env()  # arm DYN_FAULTS points (no-op when unset)
        # degrade ladder: ordered feature shedding with re-probe
        # recovery, generalizing the one-way mixed_disabled trip. A trip
        # also resets the restore-gate EMAs (ADVICE r5 follow-up): the
        # rates were measured on the pre-degrade configuration — e.g. a
        # pipelined engine's prefill tps — and a gate calibrated there
        # would mis-price restore-vs-recompute on the degraded engine.
        self._degrade = DegradeLadder(
            reprobe_s=config.degrade_reprobe_s,
            on_trip=self._reset_offload_ema,
        )
        # flight recorder (docs/observability.md "Forensics plane"):
        # always-on per-step digest ring sampled at the _phase_stats
        # sites + rolling per-phase latency baselines; SLO breaches,
        # watchdog fires, deadline-shed bursts, sustained anomalies and
        # GET /debug/snapshot dump a correlated, rate-limited artifact
        self.flight = flightmod.FlightRecorder(
            context_fn=self._flight_context,
            directory=config.crash_dir,
        ) if config.flight_recorder else None
        # KV ledger audit cadence: config.kv_audit_s wins, else
        # DYN_KV_AUDIT_S, default 5 s; 0 disables. Runs at the top of
        # the loop tick — O(pool) reads off the dispatch path.
        audit_s = config.kv_audit_s
        if audit_s is None:
            try:
                audit_s = float(os.environ.get("DYN_KV_AUDIT_S", "") or 5.0)
            except ValueError:
                audit_s = 5.0
        self._kv_audit_s = float(audit_s)
        self._kv_audit_next = 0.0
        # watchdog: in-flight device-critical ops (dispatch calls and
        # result fetches) register here as {token: (label, t_start)};
        # the monitor task trips the ladder + dumps a crash artifact
        # when one stalls past _watchdog_s. Mutated from worker threads
        # under the GIL (token allocation via itertools.count is atomic).
        self._watchdog_s = float(config.watchdog_dispatch_s or 0.0)
        self._ops: dict[int, tuple[str, float]] = {}
        self._op_ids = itertools.count(1)
        self._watch_fired: set[int] = set()
        self._watchdog_task: Optional[asyncio.Task] = None
        self.last_crash_artifact: Optional[str] = None
        # deadline sweep runs only when some live request carries one
        self._has_deadlines = False

        # slot-matrix width: whole context in token slots (gather prefill)
        self._smat_width = config.max_pages_per_seq * config.page_size

        # one jitted step; jax retraces per (B, T, C) shape family (and
        # per all_greedy variant — static so the pure-greedy batch skips
        # the sampling shortlist entirely). Every step program takes
        # (params, kv, state, ...) and donates kv and the state; a state
        # that holds counts keys the penalty / seeded variant
        self._step_fn = jax.jit(
            self._model_step, donate_argnums=(1, 2),
            static_argnums=(13, 14, 15), static_argnames=("sp_cached",),
        )
        # multi-step decode: `decode_steps` iterations per dispatch;
        # want_lps static so the common no-logprobs batch skips the
        # per-step logsumexp over [B, V]
        self._decode_fn = jax.jit(
            self._decode_multi, donate_argnums=(1, 2),
            static_argnums=(5, 6, 7),
        )
        # the block step of a model generated by diffusion over blocks:
        # `decode_steps` passes a dispatch, the same statics
        self._dlm_fn = jax.jit(
            self._dlm_multi, donate_argnums=(1, 2),
            static_argnums=(5, 6, 7),
        )
        # speculative verify: one multi-query step over [carry, drafts]
        # with rejection-sampling acceptance (all_greedy static)
        self._spec_fn = jax.jit(
            self._spec_verify_step, donate_argnums=(1, 2),
            static_argnums=(12,),
        )
        # mixed prefill+decode step: decode rows (q_len=1 — or ragged
        # 1+k VERIFY windows when spec composes) + prefill chunk rows in
        # ONE [n, T] ragged dispatch; every row samples at its last
        # valid column (all_greedy + the pallas table width static). The
        # step scatters decode rows' samples into the state's carry
        # in-jit, which is what lets a pipelined build read the next
        # input token without a host round trip.
        self._mixed_fn = jax.jit(
            self._mixed_model_step, donate_argnums=(1, 2),
            static_argnums=(8, 9),
        )
        # occurrence counts for penalty sampling (`_state.counts`) are
        # allocated on first use (B x V int8; ~33 MB at B=256, V=128k)
        self._reset_count_fn = jax.jit(
            self._reset_and_count, donate_argnums=(0,), static_argnums=(3,)
        )
        # disagg KV transfer: in-place scatter of received blocks / gather
        # of computed blocks (reference: the NIXL read/write data plane,
        # patch nixl.py — here device<->host staged, see llm/disagg);
        # wire format is layer-stacked [L, T, K*Hd] (+ [L, T, S] scales
        # when the source engine runs a quantized KV cache; int4 wire
        # rows are the nibble-packed bytes, [L, T, K*Hd/2])
        kh = self.model_cfg.num_kv_heads
        s_ch = self._kv_scale_channels()
        kv_tp = config.mesh.tp
        from dynamo_tpu.ops.quant import (
            gather_kv_scales,
            gather_packed_kv,
            pack_kv_slots,
            scales_to_page_tiles,
            scatter_kv_scales,
        )

        _eng_ps = self.config.page_size
        _eng_packed = self._kv_packed
        _eng_interp = self._attn_interpret

        def _inject(kv, slots, nk, nv, nks=None, nvs=None):
            # nks/nvs: dense wire scales [L, T, K] -> pool-layout scatter.
            # Every caller passes page-run slots (whole allocated pages,
            # or a page-aligned chunk whose tail rows may be garbage —
            # the paged_kv_write contract), padded with trash slot 0.
            if _eng_packed:
                # int32-packed pools: page-granular write through the
                # pallas page-scatter kernel (a byte-level slot scatter
                # into packed rows would need collision-safe RMW; whole
                # pages sidestep it and reuse the prefill path). Under
                # tp>1 the kernel must run per-shard inside shard_map —
                # a pallas custom call has no GSPMD partitioning rule
                # (same reason the model path wraps it, llama.py)
                from dynamo_tpu.ops.pallas_kv_write import paged_kv_write

                import functools as _ft

                wr = _ft.partial(
                    paged_kv_write, page_size=_eng_ps, interpret=_eng_interp
                )
                if self._attn_mesh is not None:
                    P = jax.sharding.PartitionSpec
                    wr = jax.shard_map(
                        wr,
                        mesh=self._attn_mesh,
                        in_specs=(
                            P(None, "tp"), P(None, "tp"), P(),
                            P(None, None, "tp"), P(None, None, "tp"),
                            P(None, "tp", None), P(None, "tp", None),
                            P(None, "tp", None), P(None, "tp", None),
                        ),
                        out_specs=(
                            P(None, "tp"), P(None, "tp"),
                            P(None, "tp", None), P(None, "tp", None),
                        ),
                        check_vma=False,
                    )

                t = slots.shape[0]
                t_pad = -(-t // _eng_ps) * _eng_ps
                if t_pad != t:
                    pad = ((0, 0), (0, t_pad - t), (0, 0))
                    nk = jnp.pad(nk, pad)
                    nv = jnp.pad(nv, pad)
                    nks = jnp.pad(nks, pad, constant_values=1.0)
                    nvs = jnp.pad(nvs, pad, constant_values=1.0)
                    slots = jnp.pad(slots, (0, t_pad - t))
                n_pg = t_pad // _eng_ps
                page_table = slots[:: _eng_ps] // _eng_ps
                ks_out, vs_out, k_out, v_out = [], [], [], []
                for l in range(len(kv.k)):
                    kpg = pack_kv_slots(nk[l].reshape(n_pg, _eng_ps, -1))
                    vpg = pack_kv_slots(nv[l].reshape(n_pg, _eng_ps, -1))
                    kt = scales_to_page_tiles(nks[l], _eng_ps, s_ch, kv_tp)
                    vt = scales_to_page_tiles(nvs[l], _eng_ps, s_ch, kv_tp)
                    ok, ov, oks, ovs = wr(
                        kv.k[l], kv.v[l], page_table, kpg, vpg,
                        kv.ks[l], kv.vs[l], kt, vt,
                    )
                    k_out.append(ok)
                    v_out.append(ov)
                    ks_out.append(oks)
                    vs_out.append(ovs)
                return llama.KVCache(
                    k=tuple(k_out), v=tuple(v_out),
                    ks=tuple(ks_out), vs=tuple(vs_out),
                )
            return llama.KVCache(
                k=tuple(x.at[slots].set(nk[l]) for l, x in enumerate(kv.k)),
                v=tuple(x.at[slots].set(nv[l]) for l, x in enumerate(kv.v)),
                ks=tuple(
                    scatter_kv_scales(x, slots, nks[l], s_ch, kv_tp)
                    for l, x in enumerate(kv.ks)
                ) if kv.quantized else None,
                vs=tuple(
                    scatter_kv_scales(x, slots, nvs[l], s_ch, kv_tp)
                    for l, x in enumerate(kv.vs)
                ) if kv.quantized else None,
            )

        self._inject_fn = jax.jit(_inject, donate_argnums=(0,))
        if self._cache_kind():
            def _refuse(*_a, **_k):
                self._refuse_plane("KV page inject / extract")

            self._inject_fn = self._extract_fn = _refuse
            return

        def _extract(kv, slots):
            if _eng_packed:
                out = (
                    jnp.stack([gather_packed_kv(x, slots) for x in kv.k]),
                    jnp.stack([gather_packed_kv(x, slots) for x in kv.v]),
                )
            else:
                out = (
                    jnp.stack([x[slots] for x in kv.k]),
                    jnp.stack([x[slots] for x in kv.v]),
                )
            if kv.quantized:
                out = out + (
                    jnp.stack([
                        gather_kv_scales(x, slots, s_ch, kv_tp) for x in kv.ks
                    ]),
                    jnp.stack([
                        gather_kv_scales(x, slots, s_ch, kv_tp) for x in kv.vs
                    ]),
                )
            return out

        self._extract_fn = jax.jit(_extract)
        # wire-format conversion for mixed quantized/unquantized disagg
        # pairs: quantize bf16 payloads entering a quantized pool,
        # dequantize int8 payloads entering a model-dtype pool
        from dynamo_tpu.ops.quant import dequantize_kv_rows as _dq
        from dynamo_tpu.ops.quant import quantize_kv_rows as _q

        if self._kv_quant == "int4":
            from dynamo_tpu.ops.quant import (
                dequantize_kv_rows_int4 as _dq4,
                quantize_kv_rows_int4 as _q4,
            )

            _grp = self.model_cfg.head_dim // self._kv_int4_groups
            self._kv_quantize_fn = jax.jit(lambda a: _q4(a, kh, _grp))
            self._kv_dequantize_fn = jax.jit(
                lambda a, s: _dq4(a, s, kh, out_dtype=self._dtype)
            )
        else:
            self._kv_quantize_fn = jax.jit(lambda a: _q(a, kh))
            self._kv_dequantize_fn = jax.jit(
                lambda a, s: _dq(a, s, out_dtype=self._dtype)
            )

    def _cache_kind(self) -> Optional[str]:
        """The row of `CACHE_KIND_REFUSALS` this model's cache is (the
        first that applies), None for K and V pools under one page list."""
        return next(
            (k for k in CACHE_KIND_REFUSALS if getattr(self.model_cfg, k)),
            None)

    def _refuse_plane(self, plane: str) -> None:
        """A plane that moves, shares or converts pages by one list of
        page ids over a K pool and a V pool a layer, asked of a model
        whose cache is something else: refused with the first reason
        that applies, never run on half a cache."""
        kind = self._cache_kind()
        if kind:
            raise ValueError(CACHE_KIND_REFUSALS[kind]["why"].format(
                plane=plane, name=self.model_cfg.name))

    def _refuse_config(self) -> None:
        """What this model's kind of cache cannot be combined with yet,
        each refused at construction (docs/kv_cache.md "Latent pools",
        "Window pools", "State pools")."""
        kind = self._cache_kind()
        options = CACHE_KIND_REFUSALS[kind]["options"] if kind else {}
        for option, why in options.items():
            value = getattr(self.config, option)
            if (value.num_devices > 1 if option == "mesh"
                    else value not in (None, 0, False)):
                self._refuse_plane(
                    _REFUSABLE_OPTIONS[option].format(value)
                    + (f" ({why})" if why else ""))

    def _win_pool_pages(self) -> int:
        """Pages of the window kind's pool, from what the configuration
        and the flags say: every one of `max_batch_size` rows may hold
        the pages its window, the tokens of the dispatches in flight and
        the next dispatch touch, plus one it is about to release; a
        quarter of the rows may sit in prefill holding a chunk's pages
        besides (a chunk at a time, whatever the prompt's length:
        `_reserve_window_pages`); and the trash page."""
        cfg, ps = self.config, self.config.page_size
        per_row = -(-(self.model_cfg.sliding_window - 1
                      + 3 * cfg.decode_steps) // ps) + 1
        chunk = -(-cfg.prefill_chunk // ps)
        return min(
            1 + cfg.max_batch_size * per_row
            + max(cfg.max_batch_size // 4, 1) * chunk,
            # never more than every row's whole context
            1 + cfg.max_batch_size * cfg.max_pages_per_seq,
        )

    @property
    def attention_backend(self) -> dict:
        """What this engine's attention actually runs — `attn_backend=
        "auto"` resolved: `kind` is "pallas" or "gather", `interpret`
        is True when the pallas kernels run in interpret mode (off-TPU),
        `kv_packed` when quantized pools are stored int32-packed."""
        return {
            "kind": "pallas" if self._attn_pallas else "gather",
            "interpret": bool(self._attn_pallas and self._attn_interpret),
            "kv_packed": self._kv_packed,
        }

    # ------------------------------------------------------------------
    # sizing

    def _kv_scale_channels(self) -> int:
        """Scale channels per token (S): K on the int8 tier, K * groups
        on the int4 tier, K (unused) otherwise."""
        kh = self.model_cfg.num_kv_heads
        return kh * self._kv_int4_groups if self._kv_int4_groups else kh

    def _auto_num_pages(self, params) -> int:
        cfg, m = self.config, self.model_cfg
        tp = self.config.mesh.tp
        reserved = 0  # bytes of what is sized before the pages (below)
        if self._recurrent:
            # the state pools are sized from the slots (they are built
            # after this and are not yet in `bytes_in_use`); a page holds
            # its tokens in the layers that keep pages
            from dynamo_tpu.models.mamba2 import state_bytes_per_slot

            item = self._dtype.dtype.itemsize
            page_bytes = (
                len(m.paged_layers) * cfg.page_size * m.num_kv_heads
                * m.head_dim * 2 * item
            )
            reserved = (cfg.max_batch_size + 1) * state_bytes_per_slot(
                m, item)
        elif self._hybrid:
            # the window kind's pool is sized from the rows it must hold
            # (`_win_pool_pages`); a page of the full kind's takes what
            # is left
            def kind_bytes(kind):
                spec = m.attn_kind(kind)
                return (
                    m.layers_of(kind) * cfg.page_size
                    * (spec.k_width + spec.v_width)
                    * self._dtype.dtype.itemsize
                )

            page_bytes = kind_bytes(FULL)
            reserved = self.win_num_pages * kind_bytes(WINDOW)
        elif m.latent:
            # one pool a layer, a row the lanes it occupies (576 -> 640)
            page_bytes = (
                m.num_layers * cfg.page_size * m.latent_pool_width
                * self._dtype.dtype.itemsize
            )
        elif self._kv_quant:
            # quantized data pages (int8: 1 byte/feature; int4: packed
            # nibbles, 1 byte per TWO features — exactly a quarter of
            # bf16) + [SUBL, S] f32 scale tiles per pool
            from dynamo_tpu.ops.quant import kv_scale_subl

            data = cfg.page_size * m.num_kv_heads * m.head_dim
            if self._kv_quant == "int4":
                data //= 2
            scales = (
                kv_scale_subl(self._kv_scale_channels(), tp)
                * cfg.page_size * 4
            )
            page_bytes = m.num_layers * 2 * (data + scales) // tp
        else:
            page_bytes = (
                m.num_layers * cfg.page_size * m.num_kv_heads * m.head_dim
                * 2 * self._dtype.dtype.itemsize
            ) // tp  # per-device bytes for one page's K+V
        # CPU backends report no memory stats: tests and dev runs there
        # get the smallest pool that holds a full batch. On an
        # accelerator the pool is sized from what the devices report —
        # missing stats or no room are errors, never a quiet tiny pool.
        fallback = cfg.max_batch_size * cfg.max_pages_per_seq + 17
        devices = [
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()
        ]
        # bytes_in_use must include the weights: wait for them
        jax.block_until_ready(params)
        stats = [d.memory_stats() for d in devices]
        if any(not s or "bytes_limit" not in s for s in stats):
            if devices[0].platform != "cpu":
                raise RuntimeError(
                    f"{devices[0].platform} device reports no memory_stats(); "
                    "cannot size the KV pool — pass num_pages explicitly"
                )
            return fallback
        free = min(
            s["bytes_limit"] * cfg.hbm_utilization - s["bytes_in_use"]
            for s in stats
        ) - reserved
        n = int(free // max(page_bytes, 1))
        if n < cfg.max_pages_per_seq + 1:
            raise RuntimeError(
                f"KV pool auto-sizing: {free / 2**30:.2f} GiB free per device "
                f"after weights (hbm_utilization={cfg.hbm_utilization}) holds "
                f"{n} pages of {page_bytes} bytes — fewer than one "
                f"max_model_len sequence ({cfg.max_pages_per_seq} pages)"
            )
        return n

    # ------------------------------------------------------------------
    # events / metrics

    def subscribe_events(self, cb: Callable[[dict], None]) -> None:
        """KV cache events (stored/removed) feed the KV-aware router
        (reference: lib/llm/src/kv_router/publisher.rs)."""
        self._event_subscribers.append(cb)

    def _emit_event(self, event: dict) -> None:
        event = {**event, "event_id": self._event_seq, "block_size": self.page_size}
        self._event_seq += 1
        for cb in self._event_subscribers:
            try:
                cb(event)
            except Exception:
                log.exception("kv event subscriber failed")

    def subscribe_requests(self, cb: Callable[[dict], None]) -> None:
        """Per-request finish summaries: {request_id, finish_reason,
        prompt_tokens, tokens, queue_wait_s, ttft_s, itl_s} — fired once
        per sequence at finish (see _finish)."""
        self._request_observers.append(cb)

    def dump_trace(self, path: str) -> int:
        """Write the process trace ring (utils/tracing.py) as
        Chrome/Perfetto trace-event JSON; returns the event count.
        Recording must be armed (DYN_TRACE=1 or tracing.enable()) for
        the engine's step timeline and request spans to be present."""
        return tracing.dump(path)

    def metrics(self) -> dict:
        """ForwardPassMetrics equivalent (reference:
        lib/llm/src/kv_router/protocols.rs:43-54)."""
        active = sum(1 for s in self.slots if s is not None)
        usable = self.num_pages - 1
        ps = self._phase_stats
        compiles = telemetry.compile_stats()
        compiles.pop("at_s")  # a snapshot's stamp, not a gauge
        return {
            "request_active_slots": active,
            "request_total_slots": len(self.slots),
            "kv_active_blocks": int(round(self.allocator.usage() * usable)),
            "kv_total_blocks": usable,
            "num_requests_waiting": len(self.waiting),
            "gpu_cache_usage_perc": self.allocator.usage(),
            # prefix-cache hit rate of the HBM tier (the honest key —
            # there is no GPU in this repo; the reference-named
            # `gpu_prefix_cache_hit_rate` alias rode one release, PR 9,
            # and is gone)
            "prefix_cache_hit_rate": self.allocator.hit_rate(),
            # prefix reservation breakdown (always-present zero-series:
            # metrics() computes every key, so the gauges render 0.0
            # from the first scrape per PR 7's declare convention)
            "prefix_hits": ps["prefix_hits"],
            "prefix_full_hits": ps["prefix_full_hits"],
            "prefix_reused_tokens": ps["prefix_reused_tokens"],
            "prefix_restored_tokens": ps["prefix_restored_tokens"],
            "prefix_tail_tokens": ps["prefix_tail_tokens"],
            # KV pool telemetry (engine/allocator.py): live vs cached vs
            # free pages, the pool's high-water mark, slot occupancy and
            # fragmentation (cached share of occupied pages — high here
            # plus allocation failures = eviction churn, not capacity)
            "kv_pages_used": self.allocator.pages_used,
            "kv_pages_cached": self.allocator.pages_cached,
            "kv_pages_free": self.allocator.pages_free,
            "kv_pages_peak_used": self.allocator.peak_used,
            "kv_fragmentation": round(self.allocator.fragmentation(), 4),
            # custody ledger (engine/kv_ledger.py): cumulative violations
            # by the audit + release misuse, pages currently attributed
            # to orphans, completed audit passes, open in-flight windows
            "kv_ledger_violations": self.kv_ledger.violations_total + (
                self.kv_ledger_win.violations_total if self._hybrid else 0
            ),
            "kv_ledger_orphan_pages": len(self.kv_ledger.last_orphans) + (
                len(self.kv_ledger_win.last_orphans) if self._hybrid else 0
            ),
            "kv_ledger_audits": self.kv_ledger.audits_total,
            "kv_ledger_inflight": len(self.kv_ledger._inflight),
            "slot_occupancy": (
                round(active / len(self.slots), 4) if self.slots else 0.0
            ),
            # host offload tier + restore gate (engine/offload.py):
            # request-level detail rides the finish summaries' ledger
            "offload_host_pages": (
                len(self.host_pool) if self.host_pool is not None else 0
            ),
            "offload_restored": self.offload_gate_stats["restored"],
            "offload_declined": self.offload_gate_stats["declined"],
            "offload_restore_failed": self.offload_gate_stats["failed"],
            # jit compile telemetry (engine/telemetry.py, process-wide):
            # cache misses and the wall they burned — the silent
            # multi-second stalls, now countable and traceable; with
            # them the host's clock by phase, a dict that EngineMetrics
            # renders as one series labelled {phase=}
            "phase_seconds_total": compiles.pop("phase_s"),
            **compiles,
            # HBM gauges from device memory_stats(); absent on backends
            # that expose none (CPU)
            **telemetry.device_memory_stats(),
            # sequences preempted for want of KV pages (cumulative; the
            # flight digests carry it per step as `preempted`)
            "preemptions_total": self._preemptions,
            # a hybrid model's window kind: pages in use, and pages
            # released behind the window since start (0 for any other)
            "kv_window_pages_used": (
                self.win_allocator.pages_used if self._hybrid else 0
            ),
            "kv_window_pages_released_total": self._win_released,
            # a model with state pools: slots whose state a sequence
            # holds, and first chunks dispatched (each starts its row
            # from a zero state) since start (0 for any other)
            "state_slots_used": active if self._recurrent else 0,
            "state_resets_total": self._state_resets,
            # what `_emit` put on the out_queues (one frame per sequence
            # per landing), and the collector's full passes
            "frames_total": self._frames,
            "tokens_total": self._frame_tokens,
            **self._heap.stats(),
            # speculative decode health (ForwardPassMetrics.from_dict
            # drops unknown keys, so the router wire stays compatible)
            "spec_acceptance_rate": (
                ps["spec_accepted"] / ps["spec_drafted"]
                if ps["spec_drafted"] else 0.0
            ),
            "spec_tokens_per_step": (
                ps["spec_emitted"] / ps["spec_rows"]
                if ps["spec_rows"] else 0.0
            ),
            # generation by diffusion over blocks (see _phase_stats; 0
            # for every other model): passes dispatched, rows x passes
            # landed, masked positions they filled, blocks committed, and
            # tokens a row a pass: block / (steps + 1) when every row is
            # in step, lower when rows join on a partial block or end
            # inside one (non-diffusion decode is 1.0 a step)
            "dlm_passes": ps["dlm_passes"],
            "dlm_row_passes": ps["dlm_row_passes"],
            "dlm_filled": ps["dlm_filled"],
            "dlm_committed": ps["dlm_committed"],
            "dlm_tokens_per_pass": (
                ps["dlm_filled"] / ps["dlm_row_passes"]
                if ps["dlm_row_passes"] else 0.0
            ),
            # stall-free mixed batching health (see _phase_stats):
            # steps taken, decode rows that rode them instead of
            # stalling, and prefill tokens computed inside them
            "mixed_steps": ps["mixed_steps"],
            "mixed_decode_rows": ps["mixed_decode_rows"],
            "mixed_prefill_tokens": ps["mixed_prefill_tokens"],
            "mixed_spec_rows": ps["mixed_spec_rows"],
            # 1 when a failed mixed dispatch tripped the permanent
            # degrade to the contained normal paths — the one log line
            # is easy to miss, the /metrics scrape is not
            "mixed_disabled": 1 if (
                self._mixed_disabled or self._degrade.tripped("mixed")
            ) else 0,
            # step-pipeline health (EngineConfig.step_pipeline): syncs
            # whose fetch wall overlapped an already-queued dispatch,
            # and the wall they hid
            "pipeline_overlapped": ps["pipeline_overlapped"],
            "pipeline_overlap_s": round(ps["pipeline_overlap_s"], 4),
            "mixed_carry_rows": ps["mixed_carry_rows"],
            # per-dispatch executor attribution (docs/parallelism.md):
            # which executor actually served — the manual ring overlap
            # path or the GSPMD fallback. The fallback's refusal reason
            # rides /metrics as the {reason} label (EngineMetrics reads
            # engine.tp_overlap_refusal_reason).
            "tp_overlap_dispatches": ps["tp_overlap_dispatches"],
            "gspmd_fallback_dispatches": ps["gspmd_fallback_dispatches"],
            # fault-tolerance spine (docs/robustness.md): per-rung
            # degrade state (degraded_step_pipeline/.../_decode_scan),
            # ladder transition totals, watchdog firings, deadline
            # sheds/timeouts, and faults injected this process
            **self._degrade.state(),
            "degrades_total": self._degrade.degrades_total,
            "recoveries_total": self._degrade.recoveries_total,
            "watchdog_fired": ps["watchdog_fired"],
            "deadline_shed": ps["deadline_shed"],
            "deadline_timeouts": ps["deadline_timeouts"],
            "faults_injected": faults.fired_total() if faults.active() else 0,
            # forensics plane (engine/flight_recorder.py): digest-ring
            # fill, artifacts written vs rate-limit-suppressed, and
            # total anomalous steps (the per-phase split renders as the
            # labeled engine_step_anomalies_total counter)
            "flight_digests": (
                self.flight.count if self.flight is not None else 0
            ),
            "flight_dumps": (
                self.flight.dumps_total if self.flight is not None else 0
            ),
            "flight_suppressed": (
                self.flight.suppressed_total
                if self.flight is not None else 0
            ),
            "step_anomalies": (
                self.flight.anomalies_total
                if self.flight is not None else 0
            ),
        }

    # ------------------------------------------------------------------
    # compiled steps

    def _calibrate_collective_bw(self) -> float:
        """Init-time bandwidth probe for the collective_wall_s estimate:
        best-of-3 wall of a jitted tp psum on this mesh (1 MiB/shard —
        large enough to dominate launch overhead, small enough to be
        free at init), converted to achieved bytes/s via the ring
        all-reduce wire formula. 0.0 on any failure — the byte counters
        stay exact; only the wall estimate goes dark."""
        try:
            tp = self.config.mesh.tp
            chunk = 64 * 1024  # f32 elements per shard
            P = jax.sharding.PartitionSpec
            fn = jax.jit(jax.shard_map(
                lambda a: jax.lax.psum(a, "tp"), mesh=self.mesh,
                in_specs=P("tp"), out_specs=P(), check_vma=False,
            ))
            x = jnp.zeros((tp * chunk,), jnp.float32)
            jax.block_until_ready(fn(x))  # compile outside the timing
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                best = min(best, time.perf_counter() - t0)
            moved = 2 * (tp - 1) * chunk * 4 // tp  # wire bytes/device
            return moved / best if best > 0 else 0.0
        except Exception:
            log.warning(
                "collective bandwidth probe failed; collective_wall_s "
                "estimates disabled", exc_info=True,
            )
            return 0.0

    def _note_collectives(self, kind: str, rows: int, t_end: float) -> None:
        """Attribute one dispatch's per-layer TP collective traffic:
        exposed bytes (closed form x physical token rows through the
        layer stack, padding included — the wire moves padded rows too)
        into the per-kind counter, plus the bandwidth-probe wall
        estimate and an `engine.collective` sub-span at the dispatch
        tail (an estimated comm window inside the step span, not a
        device-measured interval)."""
        if not self._collective_tok_bytes or rows <= 0:
            return
        nbytes = self._collective_tok_bytes * rows
        est = nbytes / self._collective_bps if self._collective_bps else 0.0
        with self._phase_lock:
            self._phase_stats[f"{kind}_collective_bytes"] += nbytes
            self._phase_stats["collective_wall_s"] += est
            self._phase_stats[
                "tp_overlap_dispatches" if self._tp_overlap_manual
                else "gspmd_fallback_dispatches"
            ] += 1
        if est and tracing.enabled():
            tracing.complete(
                "engine.collective", t_end - est, t_end, cat="collective",
                track="engine.collective", kind=kind, bytes=int(nbytes),
                overlap=self._tp_overlap_manual,
            )

    def _forward(self, params, kv, tokens, positions, write_slots, attn,
                 embeds=None, embeds_mask=None, moe_stats=None):
        """llama.forward, rerouted through the latency-hiding manual-TP
        executor on engines that selected it. The executor serves every
        dispatch family's AttnSpec shape on tp-only engines — gather
        oracles AND the pallas prefill/fused-decode/ragged kernels with
        any KV tier (the spec passes through whole; the executor's shard
        body reruns the kernels mesh-free on shard-local operands). Only
        the sp ring spec keeps the classic path — belt-and-suspenders,
        init gating already excludes sp engines."""
        if self._tp_overlap_manual and not attn.ring:
            from dynamo_tpu.parallel.tp_overlap import tp_overlap_forward

            return tp_overlap_forward(
                params, self.model_cfg, tokens, positions, kv,
                write_slots, attn, self.mesh,
                embeds=embeds, embeds_mask=embeds_mask,
            )
        return llama.forward(
            params, self.model_cfg, tokens, positions, kv, write_slots,
            attn, embeds=embeds, embeds_mask=embeds_mask,
            moe_stats=moe_stats,
        )

    def _model_step(self, params, kv, state, tokens, positions, write_slots,
                    slot_matrix, rows_i, rows_f, wtables=None,
                    btables=None, embeds=None, embeds_mask=None,
                    all_greedy=False, want_lps=False, want_tops=False,
                    sp_cached=False):
        """One prefill step. Returns ((sampled [n], logprobs [n][, top
        ids, top logprobs]), kv, state). Per-row inputs ride in two fused
        uploads: `rows_i` [n, 5] = [last_idx, top_k, slot (-1: none),
        final chunk, seed] and `rows_f` [n, 5] = [temp, top_p, freq_pen,
        pres_pen, rep_pen]. A row whose chunk is final has sampled its
        first token: the step writes it into the state's carry at the
        row's slot, where the next decode program reads it. A state that
        holds counts switches on the penalty path (counts gathered per
        slot row, the final-chunk rows' sampled token bumped).
        `want_lps` (static) gates the logsumexp; when off the logprob
        vector is zeros."""
        last_idx, topk, slot_rows = rows_i[:, 0], rows_i[:, 1], rows_i[:, 2]
        final_row, seeds = rows_i[:, 3].astype(bool), rows_i[:, 4]
        temp, topp = rows_f[:, 0], rows_f[:, 1]
        state, key = state.split()

        def _sample(lg, **kw):
            if want_lps:
                return sample_tokens(
                    lg, key, temp, topk, topp, all_greedy=all_greedy,
                    return_logprobs=True, top_n=TOP_LOGPROBS_MAX if want_tops else 0, **kw,
                )  # (ids, lps[, top_ids, top_lps])
            toks = sample_tokens(
                lg, key, temp, topk, topp, all_greedy=all_greedy, **kw
            )
            return toks, jnp.zeros(toks.shape[0], jnp.float32)

        if self._hybrid:
            # every per-kind input arrives as (full, window)
            full, win = (
                self._prefill_attn(
                    slot_matrix[k],
                    None if wtables is None else wtables[k],
                    None if btables is None else btables[k],
                    positions, last_idx, sp_cached,
                ) for k in (FULL, WINDOW)
            )
            hidden, kv = self._forward(
                params, kv, tokens, positions, write_slots[FULL],
                self._hybrid_spec(full, win, write_slots[WINDOW]),
            )
        else:
            attn = self._prefill_attn(
                slot_matrix, wtables, btables, positions, last_idx,
                sp_cached,
            )
            if self._recurrent:
                # a row's state is its decode slot's; a row with no slot
                # (padding) reads and writes the trash row
                attn.state_slots = jnp.where(
                    slot_rows >= 0, slot_rows, state.toks.shape[0]
                )
            hidden, kv = self._forward(
                params, kv, tokens, positions, write_slots, attn,
                embeds=embeds, embeds_mask=embeds_mask,
            )
        last_h = jnp.take_along_axis(
            hidden, last_idx[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]  # [B, D]
        lg = llama.logits(params, self.model_cfg, last_h)
        if state.counts is not None:
            # penalties/seeds on the first sampled token: counts rows
            # live per SLOT; gather this group's rows
            counts, rows = state.counts, jnp.maximum(slot_rows, 0)
            S = _sample(
                lg, counts=counts[rows],
                freq_pen=rows_f[:, 2], pres_pen=rows_f[:, 3],
                rep_pen=rows_f[:, 4],
                seeds=seeds, positions=last_idx + positions[:, 0],
            )
            # bump only final-chunk rows (others' samples are garbage);
            # scatter back through the slot mapping
            cur = counts[rows, S[0]].astype(jnp.int32)
            inc = jnp.where(final_row, 1, 0)
            state = state._replace(counts=counts.at[rows, S[0]].set(
                jnp.minimum(cur + inc, 127).astype(jnp.int8)
            ))
        else:
            S = _sample(lg)
        # padding rows, mid-prompt chunks and prefill_only rows (no
        # slot) scatter out of range and drop
        state = state.arm(
            jnp.where(final_row & (slot_rows >= 0), slot_rows,
                      state.toks.shape[0]), S,
        )
        return S, kv, self._pin_state(state)

    def _prefill_attn(self, slot_matrix, wtables, btables, positions,
                      last_idx, sp_cached):
        """The prefill step's attention spec, by backend."""
        if wtables is not None:
            # pallas prefill: page-scatter write + flash attention over
            # the streamed pages (the XLA row scatter serializes; the
            # gather oracle materializes [B,K,G,T,C] f32 logits/probs)
            return llama.AttnSpec.gather(
                slot_matrix, write_tables=wtables, page_size=self.page_size,
                interpret=self._attn_interpret, mesh=self._attn_mesh,
                block_tables=btables, q_pos0=positions[:, 0],
                lengths=last_idx + 1, kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
                mask_block=self._mask_block,
            )
        if self._sp:
            # long-context mode: ring attention over sp; on a prefix-
            # cache hit the chunk is the uncached tail and the cached
            # pool rows join as extra softmax blocks. `sp_cached` is the
            # STATIC page-bucket covering the group's longest cached
            # prefix (0 = none): the gather below is sliced to it, so a
            # short cached prefix on a 128k-context config never
            # materializes the full slot matrix
            return llama.AttnSpec.ring(
                slot_matrix, self.mesh, page_size=self.page_size,
                q_pos0=(
                    positions[:, 0] if sp_cached else None
                ),
                prefix_cols=sp_cached * self.page_size,
                kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
            )
        return llama.AttnSpec.gather(
            slot_matrix, page_size=self.page_size,
            kv_tp=self.config.mesh.tp,
            int4_groups=self._kv_int4_groups,
            mask_block=self._mask_block,
        )

    @staticmethod
    def _hybrid_spec(full, win, win_write_slots):
        """The full kind's AttnSpec carrying the window kind's (its own
        tables, its own write slots): `llama.forward` hands each layer
        its kind's."""
        win.write_slots = win_write_slots
        full.win = win
        return full

    def _pin_state(self, state: StepState) -> StepState:
        """A step program returns the state replicated over the mesh, as
        it took it: the next program's input sharding never changes."""
        if self.mesh.size == 1:
            return state
        return jax.lax.with_sharding_constraint(state, self._state_sharding)

    def _decode_multi(self, params, kv, state, rows_i, rows_f,
                      all_greedy=False, want_lps=False, want_tops=False):
        """`decode_steps` decode iterations in ONE dispatch (lax.scan with
        on-device token feedback + slot computation) — the antidote to
        per-token host round trips, which dominate wall clock when the
        device is remote or fast. Returns ((tokens [K+1, B],
        logprobs [K+1, B]), kv, state) — row 0 is the input carry.

        The dispatch is two fused uploads of the program's width `w` and
        this one launch: `rows_i` [w, 6 + W] = [position, active,
        override value, override mask, top_k, seed, block table] and
        `rows_f` [w, 5] = [temp, top_p, freq_pen, pres_pen, rep_pen],
        the host mirrors' rows as the build snapshot them. The carry
        (input tokens, their logprobs and alternatives), the sampling
        key and the counts are the full-length `state`: the program
        slices it to `w`, takes a host-known token where the override
        mask is set (a disagg inject, the re-arm after a speculative or
        mixed sync), and returns the state with the ACTIVE rows' newest
        sample; every other row keeps what it held.

        A state that holds counts switches on the penalty/seeded
        sampling path: override tokens (sampled remotely, never counted
        before) are bumped first, then each step's sampled token."""
        w = rows_i.shape[0]
        positions = rows_i[:, 0]
        active = rows_i[:, 1].astype(bool)
        ovr = rows_i[:, 3].astype(bool)
        topk, seeds = rows_i[:, 4], rows_i[:, 5]
        block_tables = rows_i[:, 6:]
        win_tables = None
        if self._hybrid:
            # [full | window]: each kind's page ids
            half = block_tables.shape[1] // 2
            block_tables, win_tables = (
                block_tables[:, :half], block_tables[:, half:]
            )
        temp, topp = rows_f[:, 0], rows_f[:, 1]
        fp, prp, rp = rows_f[:, 2], rows_f[:, 3], rows_f[:, 4]
        state, key = state.split()
        tokens = jnp.where(ovr, rows_i[:, 2], state.toks[:w])
        # a host-known token has no local logprob; NaN -> emitted as None
        carry_lps = jnp.where(ovr, jnp.nan, state.lps[:w])
        s = self.page_size
        w_pages = block_tables.shape[1]
        smat = win_smat = None
        if not self._attn_pallas:
            smat = (
                block_tables[:, :, None] * s + jnp.arange(s, dtype=jnp.int32)
            ).reshape(w, -1)
            if self._hybrid:
                win_smat = (
                    win_tables[:, :, None] * s
                    + jnp.arange(s, dtype=jnp.int32)
                ).reshape(w, -1)

        counts = state.counts
        use_pen = counts is not None
        if use_pen:
            # locally-prefilled first tokens were bumped by the prefill
            # step already; an override's token never was
            counts = bump_counts(counts, tokens, active & ovr)

        def body(carry, _):
            tokens, positions, kv, key, counts = carry  # counts: maybe None
            key, sub = jax.random.split(key)
            max_len = self.config.max_model_len
            if self._attn_pallas:
                # fused path: the kernel owns the write — no slot scatter.
                # write_pos -1 skips rows that are inactive or past the
                # model-length budget (overshoot; outputs discarded)
                wslots = jnp.zeros_like(positions)
                attn = llama.AttnSpec.pallas_decode(
                    block_tables,
                    jnp.where(
                        active, jnp.minimum(positions + 1, max_len), 0
                    ).astype(jnp.int32),
                    s,
                    write_pos=jnp.where(
                        active & (positions < max_len), positions, -1
                    ).astype(jnp.int32),
                    interpret=self._attn_interpret,
                    mesh=self._attn_mesh,
                    kv_tp=self.config.mesh.tp,
                    int4_groups=self._kv_int4_groups,
                )
                if self._hybrid:
                    attn = self._hybrid_spec(attn, llama.AttnSpec.pallas_decode(
                        win_tables, attn.lengths, s,
                        write_pos=attn.write_pos,
                        interpret=self._attn_interpret,
                    ), None)
            else:
                page_idx = jnp.minimum(positions // s, w_pages - 1)

                def slots_in(tables):
                    # inactive rows and positions past a finished
                    # sequence's budget must write the trash page, never
                    # a valid slot
                    return jnp.where(
                        active & (positions < max_len),
                        jnp.take_along_axis(
                            tables, page_idx[:, None], axis=1
                        )[:, 0] * s + positions % s,
                        0,
                    ).astype(jnp.int32)

                wslots = slots_in(block_tables)
                attn = llama.AttnSpec.gather(
                    smat, page_size=s, kv_tp=self.config.mesh.tp,
                    int4_groups=self._kv_int4_groups,
                )
                if self._hybrid:
                    attn = self._hybrid_spec(
                        attn, llama.AttnSpec.gather(win_smat, page_size=s),
                        slots_in(win_tables),
                    )
            moe = [] if self.model_cfg.num_experts else None
            hidden, kv = self._forward(
                params, kv, tokens[:, None], positions[:, None],
                wslots, attn, moe_stats=moe,
            )
            lg = llama.logits(params, self.model_cfg, hidden[:, 0])

            def _sample(**kw):
                if want_lps:
                    return sample_tokens(
                        lg, sub, temp, topk, topp, all_greedy=all_greedy,
                        return_logprobs=True, top_n=TOP_LOGPROBS_MAX if want_tops else 0,
                        **kw,
                    )  # (ids, lps[, top_ids, top_lps])
                t = sample_tokens(
                    lg, sub, temp, topk, topp, all_greedy=all_greedy, **kw
                )
                return t, jnp.zeros(t.shape[0], jnp.float32)

            if use_pen:
                ys = _sample(
                    counts=counts[:w], freq_pen=fp, pres_pen=prp,
                    rep_pen=rp, seeds=seeds, positions=positions,
                )
                counts = bump_counts(counts, ys[0], active)
            else:
                ys = _sample()
            if moe:
                # this step's expert load, mean over its expert layers:
                # [experts with a token, most tokens on one expert,
                # blocks of rows the pass ran, pairs the layer holds]
                ys = ys + (jnp.mean(
                    jnp.asarray(moe, jnp.float32), axis=0
                ),)
            return (ys[0], positions + 1, kv, key, counts), ys

        (_, _, kv, _, counts), out_t = jax.lax.scan(
            body, (tokens, positions, kv, key, counts), None,
            length=self.config.decode_steps,
        )
        # row 0 = the input carry (a prefill step's first tokens among
        # them): syncing the dispatch delivers them with no separate
        # fetch — a per-sequence fetch is one more device-to-host copy
        # and sync per sequence
        S = (
            jnp.concatenate([tokens[None], out_t[0]], axis=0),
            jnp.concatenate([carry_lps[None], out_t[1]], axis=0),
        )

        def keep(old, new):
            # active rows carry their newest sample on; a row this
            # dispatch does not advance keeps what another program wrote
            mask = active.reshape((w,) + (1,) * (new.ndim - 1))
            return old.at[:w].set(jnp.where(mask, new, old[:w]))

        state = state._replace(
            toks=keep(state.toks, out_t[0][-1]),
            lps=keep(state.lps, out_t[1][-1]), counts=counts,
        )
        if want_tops:
            carry_tlp = jnp.where(ovr[:, None], jnp.nan, state.tlp[:w])
            S = S + (
                jnp.concatenate([state.tid[:w][None], out_t[2]], axis=0),
                jnp.concatenate([carry_tlp[None], out_t[3]], axis=0),
            )
            state = state._replace(
                tid=keep(state.tid, out_t[2][-1]),
                tlp=keep(state.tlp, out_t[3][-1]),
            )
        if self.model_cfg.num_experts:
            # an expert model's dispatch ends in its load, [4] float32
            # (mean over the steps): fetched with the tokens, booked on
            # the sync digest (`_land`)
            S = S + (jnp.mean(out_t[-1], axis=0),)
        return S, kv, self._pin_state(state)

    def _dlm_multi(self, params, kv, state, rows_i, rows_f,
                   all_greedy=False, want_lps=False, want_tops=False):
        """`decode_steps` PASSES of the block step in ONE dispatch (a
        `lax.scan`, as `_decode_multi` scans steps), for a model generated
        by diffusion over blocks of L = `block_length` positions. A row
        carries its open block: the ids [L], which positions still hold a
        mask [L], and the block's first position. One pass feeds the whole
        block (the mask token where a position is masked) at positions
        `pos0 .. pos0 + L - 1`, scatters its L rows of keys and values into
        the pages (the verify step's write), attends every committed
        position before `pos0` and the block itself (`mask_block`, no
        causal line inside it), and reads logits at every position: the
        logits at position i predict position i.

        What the pass does with them is the row's PHASE, which is data: a
        row with a mask left fills `L / denoising_steps` of its masked
        positions (`ops/sampling.sample_block`); a row with none left was
        in its COMMIT pass: what it just wrote are the finished block's
        keys and values, its logits are not read, its position moves by L
        and its block resets to masks. So one compiled program a width
        serves rows that joined at different times, and nothing a
        denoising pass writes is read by a later block (the commit pass
        rewrites it first).

        The dispatch is two fused uploads of the program's width `w`:
        `rows_i` [w, 6 + W + 2 L] = [first position, active, arm, -, top_k,
        seed, block table, ids, masked] and `rows_f` as `_decode_multi`'s.
        A row whose `arm` is set (it joined since the last dispatch) takes
        its block from the upload: the prompt's tail, never masked, then
        masks; every other row its carry, `state.dlm`. Returns ((filled
        ids [K, w, L] with -1 where a pass filled nothing, their
        log-probabilities [K, w, L][, alternatives], the expert load),
        kv, state)."""
        cfg = self.model_cfg
        n = cfg.block_length
        w = rows_i.shape[0]
        active = rows_i[:, 1].astype(bool)
        arm = rows_i[:, 2].astype(bool)
        topk = rows_i[:, 4]
        block_tables = rows_i[:, 6:-2 * n]
        temp, topp = rows_f[:, 0], rows_f[:, 1]
        state, key = state.split()
        ids0, masked0, pos00 = state.dlm
        ids = jnp.where(arm[:, None], rows_i[:, -2 * n:-n], ids0[:w])
        masked = jnp.where(
            arm[:, None], rows_i[:, -n:].astype(bool), masked0[:w])
        pos0 = jnp.where(arm, rows_i[:, 0], pos00[:w])
        s = self.page_size
        w_pages = block_tables.shape[1]
        max_len = self.config.max_model_len
        q_lens = jnp.where(active, n, 0).astype(jnp.int32)
        smat = None
        if not self._attn_pallas:
            smat = (
                block_tables[:, :, None] * s + jnp.arange(s, dtype=jnp.int32)
            ).reshape(w, -1)

        def body(carry, _):
            ids, masked, pos0, kv, key = carry
            key, sub = jax.random.split(key)
            commit = active & ~jnp.any(masked, axis=1)
            tokens = jnp.where(masked, cfg.mask_token_id, ids)
            positions = pos0[:, None] + jnp.arange(n, dtype=jnp.int32)
            page_idx = jnp.minimum(positions // s, w_pages - 1)
            # inactive rows and positions past the model's length write
            # the trash page (the expert layers route them nowhere)
            wslots = jnp.where(
                active[:, None] & (positions < max_len),
                jnp.take_along_axis(block_tables, page_idx, axis=1) * s
                + positions % s,
                0,
            ).astype(jnp.int32)
            attn = llama.AttnSpec.gather(
                smat, page_size=s, interpret=self._attn_interpret,
                block_tables=block_tables if self._attn_pallas else None,
                q_pos0=pos0 if self._attn_pallas else None,
                lengths=q_lens, mask_block=n,
            )
            moe = []
            hidden, kv = self._forward(
                params, kv, tokens, positions, wslots.reshape(-1), attn,
                moe_stats=moe,
            )
            lg = llama.logits(
                params, cfg, hidden.reshape(w * n, -1))  # [w L, V]
            out = sample_block(
                lg, masked & active[:, None], sub, temp, topk, topp,
                n_fill=n // cfg.denoising_steps,
                mask_token_id=cfg.mask_token_id, all_greedy=all_greedy,
                return_logprobs=want_lps,
                top_n=TOP_LOGPROBS_MAX if want_tops else 0,
            )
            sampled, fill = out[0], out[1]
            ids = jnp.where(fill, sampled, ids)
            masked = masked & ~fill
            ys = (jnp.where(fill, sampled, -1), *out[2:], jnp.mean(
                jnp.asarray(moe, jnp.float32), axis=0))
            return (
                jnp.where(commit[:, None], cfg.mask_token_id, ids),
                masked | commit[:, None],
                pos0 + jnp.where(commit, n, 0), kv, key,
            ), ys

        (ids, masked, pos0, kv, _), out_t = jax.lax.scan(
            body, (ids, masked, pos0, kv, key), None,
            length=self.config.decode_steps,
        )

        def keep(old, new):
            # active rows carry their block on; a row this dispatch does
            # not advance keeps what it held
            mask = active.reshape((w,) + (1,) * (new.ndim - 1))
            return old.at[:w].set(jnp.where(mask, new, old[:w]))

        state = state._replace(dlm=(
            keep(ids0, ids), keep(masked0, masked), keep(pos00, pos0),
        ))
        # the passes' expert load, [4] float32 (mean over the passes)
        S = (*out_t[:-1], jnp.mean(out_t[-1], axis=0))
        return S, kv, self._pin_state(state)

    def _spec_verify_step(self, params, kv, state, tokens, positions,
                          block_tables, active, draft, draft_len, temp,
                          topk, topp, all_greedy=False):
        """One speculative verify step: every row carries `1 + draft_len`
        candidate tokens — its decode carry plus the n-gram proposer's
        drafts — through the model in ONE forward (tokens [B, T] with
        T = spec_k_max + 1, padded per row), then rejection-sampling
        acceptance (ops/sampling.verify_draft_tokens) emits the accepted
        prefix plus one corrected/bonus token.

        Attention follows the unified-step contract prefill uses (KV
        written first so each draft attends its accepted prefix): the
        chunked-prefill gather oracle (ops/attention.py) off-TPU, and on
        pallas engines the ragged flash kernel
        (ops/pallas_attention.ragged_paged_attention — per-row q_pos0 /
        q_len = draft_len+1, mid-page pos0 native) so the verify step
        rides the same flash path the mixed step uses instead of paying
        the gather oracle's materialized-logits cliff. Draft positions
        that end up REJECTED leave garbage KV in their slots; that is
        sound because the causal mask hides any slot beyond a query's
        position and the next dispatches rewrite those slots before any
        query can reach them (host-side num_computed/device_pos rewind
        keeps page registration behind the accepted prefix).

        Of the state the step touches the key alone: windows are
        host-built, and the sync re-arms the carry through the next
        decode program's override columns.

        Returns ((out_tokens [B, T], n_emit [B]), kv, state)."""
        state, key = state.split()
        s = self.page_size
        b, w = block_tables.shape
        t = tokens.shape[1]
        max_len = self.config.max_model_len
        page_idx = jnp.minimum(positions // s, w - 1)
        wslots = (
            jnp.take_along_axis(block_tables, page_idx, axis=1) * s
            + positions % s
        )
        # rows write [pos0, pos0 + draft_len]; padded columns, inactive
        # rows and past-budget positions write the trash page
        col_ok = jnp.arange(t)[None, :] <= draft_len[:, None]
        wslots = jnp.where(
            active[:, None] & col_ok & (positions < max_len), wslots, 0
        ).astype(jnp.int32)
        if self._attn_pallas:
            # ragged flash read (row-scatter write happens in
            # llama._attn_block, same as the mixed step); inactive rows
            # get q_len 0 and emit zeros
            attn = llama.AttnSpec.gather(
                None, page_size=s, interpret=self._attn_interpret,
                mesh=self._attn_mesh, block_tables=block_tables,
                q_pos0=positions[:, 0],
                lengths=jnp.where(active, draft_len + 1, 0),
                kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
            )
        else:
            smat = (
                block_tables[:, :, None] * s + jnp.arange(s, dtype=jnp.int32)
            ).reshape(b, -1)
            attn = llama.AttnSpec.gather(
                smat, page_size=s, kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
            )
        hidden, kv = self._forward(
            params, kv, tokens, positions, wslots.reshape(-1), attn,
        )
        lg = llama.logits(params, self.model_cfg, hidden)  # [B, T, V]
        out, n_emit = verify_draft_tokens(
            lg, draft, draft_len, key, temp, topk, topp,
            all_greedy=all_greedy,
        )
        return (out, n_emit), kv, self._pin_state(state)

    def _mixed_model_step(self, params, kv, state, hot, rows_i, rows_f,
                          draft=None, dlen=None, all_greedy=False, w_b=1):
        """One MIXED prefill+decode step — the stall-free batching
        dispatch (Sarathi-style): decode rows carry their last token at
        q_len=1 and prefill rows carry one chunk, per-row query lengths
        `last_idx + 1`. KV is written first, each row attends its own
        slots under the causal mask (the unified-step contract,
        ops/attention.py), and every row samples at its last valid
        column — decode rows' sample is their next token, final-chunk
        rows' sample is their first token, non-final chunk rows' sample
        is garbage the sync discards.

        Step-pipeline input contract: `hot` [3, n, T] packs the
        per-step tokens/positions/write-slots into ONE fused H2D
        upload; `rows_i` [n, 6 + W] = [last_idx, slot_row, carry_mask,
        dec_mask, top_k, seed, block table] is the second and `rows_f`
        [n, 5] (temp, top_p first) the third: the slow-changing columns
        are the host mirrors' rows of each row's slot, as the build
        snapshot them (pallas: the table sliced to the static `w_b`
        page bucket; gather: expanded to the full slot matrix). Rows
        with carry_mask read their input token from the state's carry
        instead of host token history (their previous step's sample has
        not reached the host yet — the pipelined build), and every
        decode row's newest sample is scattered back into the carry so
        the NEXT pipelined build needs no host round trip either.

        spec x mixed composition (`draft` [n, k_max] + `dlen` [n] set):
        decode rows become ragged VERIFY rows — q_len = 1 + dlen (carry
        plus n-gram drafts, exactly a standalone `_spec_verify_step`
        window riding the unified step). Each row's logits are gathered
        over a fixed (k_max+1)-wide window ending at its last valid
        column, then `verify_draft_tokens` runs rejection-sampling
        acceptance over ALL rows at once: prefill rows have dlen=0, so
        their window column 0 IS the plain sample at last_idx (greedy:
        the same argmax; sampled: the same shortlist distribution) and
        n_emit=1. Returns ((out_tokens [n, k_max+1], n_emit [n]), kv,
        state) in spec mode, (sampled [n], kv, state) otherwise.

        Attention backends: the gather oracle with ragged `q_lens`
        everywhere; on pallas engines a row-scatter KV write + the
        ragged flash kernel (the page-granular prefill scatter cannot
        express a decode row's mid-page write, see llama._attn_block).
        Verify rows need nothing new from either backend: they are just
        ragged rows whose q_pos0 is mid-page."""
        tokens, positions, wslots = hot[0], hot[1], hot[2]
        last_idx = rows_i[:, 0]
        slot_rows = rows_i[:, 1]
        carry_mask = rows_i[:, 2].astype(bool)
        dec_mask = rows_i[:, 3].astype(bool)
        n = tokens.shape[0]
        temp, topp = rows_f[:, 0], rows_f[:, 1]
        topk = rows_i[:, 4]
        tbl = rows_i[:, 6:]  # [n, W] per-row block tables
        state, key = state.split()
        carry = state.toks
        # pipelined decode rows take their input token from the device
        # carry; padding rows gather slot 0 and are masked off
        tokens = tokens.at[:, 0].set(
            jnp.where(carry_mask, carry[slot_rows], tokens[:, 0])
        )
        if self._attn_pallas:
            attn = llama.AttnSpec.gather(
                None, page_size=self.page_size,
                interpret=self._attn_interpret, mesh=self._attn_mesh,
                block_tables=tbl[:, :w_b], q_pos0=positions[:, 0],
                lengths=last_idx + 1, kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
            )
        else:
            smat = (
                tbl[:, :, None] * self.page_size
                + jnp.arange(self.page_size, dtype=jnp.int32)
            ).reshape(n, -1)
            attn = llama.AttnSpec.gather(
                smat, page_size=self.page_size,
                lengths=last_idx + 1, kv_tp=self.config.mesh.tp,
                int4_groups=self._kv_int4_groups,
            )
        hidden, kv = self._forward(
            params, kv, tokens, positions, wslots.reshape(-1), attn,
        )

        def _scatter_carry(vals):
            # every decode row's newest sample becomes the device-
            # resident q_len=1 input of the NEXT step; prefill/padding
            # rows scatter out of range and drop (a padding row shares
            # slot 0 with whatever lives there — it must not race the
            # real row's write)
            idx = jnp.where(dec_mask, slot_rows, carry.shape[0])
            return self._pin_state(state._replace(
                toks=carry.at[idx].set(vals, mode="drop")
            ))

        if draft is not None:
            # spec window: gather (k_max+1) hidden columns per row ending
            # at last_idx — decode verify rows span [0, dlen] (offset 0
            # since last_idx == dlen), prefill rows put their sample
            # column at window slot 0 and the clamped tail is garbage
            # verify never reads (dlen == 0 -> n_emit == 1)
            win = draft.shape[1] + 1
            offs = jnp.minimum(
                (last_idx - dlen)[:, None] + jnp.arange(win, dtype=jnp.int32),
                tokens.shape[1] - 1,
            ).astype(jnp.int32)
            win_h = jnp.take_along_axis(hidden, offs[:, :, None], axis=1)
            lg = llama.logits(params, self.model_cfg, win_h)  # [n, win, V]
            out, n_emit = verify_draft_tokens(
                lg, draft, dlen, key, temp, topk, topp,
                all_greedy=all_greedy,
            )
            last_col = jnp.take_along_axis(
                out, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
            )[:, 0]
            return (out, n_emit), kv, _scatter_carry(last_col)
        last_h = jnp.take_along_axis(
            hidden, last_idx[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]  # [n, D]
        lg = llama.logits(params, self.model_cfg, last_h)
        toks = sample_tokens(
            lg, key, temp, topk, topp, all_greedy=all_greedy
        )
        return toks, kv, _scatter_carry(toks)

    # ------------------------------------------------------------------
    # engine protocol

    async def generate(
        self, request: Context, _preloaded: Optional[tuple] = None,
        _blocks: Optional["TokenBlockSequence"] = None,
    ) -> AsyncIterator[dict]:
        if self._closed:
            # the loop has exited; a queued request would hang forever
            raise RuntimeError("engine is closed")
        payload = request.payload
        pre = (
            PreprocessedRequest.from_dict(payload)
            if isinstance(payload, dict)
            else payload
        )
        if len(pre.token_ids) >= self.config.max_model_len:
            raise ValueError(
                f"prompt of {len(pre.token_ids)} tokens exceeds "
                f"max_model_len={self.config.max_model_len}"
            )
        # a prompt needing more pages than the pool can ever supply would
        # hang admission forever (and head-of-line block the queue)
        usable_tokens = (self.num_pages - 1) * self.page_size
        if len(pre.token_ids) + 1 > usable_tokens:
            raise ValueError(
                f"prompt of {len(pre.token_ids)} tokens cannot fit the KV pool "
                f"({self.num_pages - 1} pages x {self.page_size} tokens)"
            )
        if len(pre.token_ids) == 0:
            raise ValueError("empty prompt")
        if self._sp and _preloaded is not None:
            raise ValueError("disagg KV ingest unsupported with sp>1 (v1)")
        if pre.prompt_embeds is not None:
            # fail fast: a silently dropped/misaligned embed span would
            # produce plausible but image-blind output
            n_emb = len(pre.prompt_embeds)
            off = pre.embeds_offset
            if n_emb == 0:
                raise ValueError("prompt_embeds is empty")
            if off < 0 or off + n_emb > len(pre.token_ids):
                raise ValueError(
                    f"embed span [{off}, {off + n_emb}) outside the "
                    f"{len(pre.token_ids)}-token prompt"
                )
            width = len(pre.prompt_embeds[0])
            if width != self.model_cfg.hidden_size:
                raise ValueError(
                    f"prompt_embeds width {width} != model hidden size "
                    f"{self.model_cfg.hidden_size}"
                )
        if _blocks is None:
            _blocks = self._blocks_from_metadata(request, pre)
        seq = Sequence.from_request(
            request, pre, self.page_size, self.config.max_model_len,
            blocks=_blocks,
        )
        if self._dlm:
            refused = {
                "frequency / presence / repetition penalties":
                    seq.has_penalties,
                "a per-request seed": seq.seed >= 0,
                "prompt_embeds": seq.prompt_embeds is not None,
            }
            for what, asked in refused.items():
                if asked:
                    raise ValueError(
                        f"{what}: not served with generation by diffusion "
                        f"over blocks ('{self.model_cfg.name}'): a block "
                        "pass samples every position of a block at once "
                        "(greedy, temperature, top-k and top-p a row)"
                    )
            seq.dlm_block = self._mask_block
        if not seq.deadline and self.config.request_timeout_s > 0:
            # deployment default budget; a request-level x-request-timeout
            # (ridden in via Context metadata) takes precedence
            seq.deadline = time.time() + self.config.request_timeout_s
        if seq.deadline:
            self._has_deadlines = True
            if seq.past_deadline():
                # shed BEFORE any device work: the caller's budget is
                # already gone, burning prefill on it helps nobody
                with self._phase_lock:
                    self._phase_stats["deadline_shed"] += 1
                raise DeadlineExceededError(
                    "request deadline expired before admission "
                    f"(deadline={seq.deadline:.3f})"
                )
        seq.t_submit = time.perf_counter()
        if tracing.enabled():
            tracing.instant(
                "seq.submit", cat="lifecycle", req=request.id,
                ts=seq.t_submit, seq_id=seq.seq_id,
                prompt_tokens=seq.prompt_len,
            )
        seq.preloaded = _preloaded
        self.waiting.append(seq)
        self._ensure_loop()
        self._wake.set()

        async def _gen() -> AsyncIterator[dict]:
            while True:
                item = await seq.out_queue.get()
                yield item
                if item.get("finish_reason"):
                    return

        return _gen()

    def _blocks_from_metadata(self, request: Context, pre):
        """Precomputed block-hash chain ridden in via Context metadata
        (stamped by the KV router, which already hashed the prompt to
        score workers) — saves the O(prompt) re-hash on the serving hot
        path. Ignored unless the block size matches this engine's page
        size and the chain covers exactly the prompt's full pages;
        `Sequence.from_request`'s mismatch guard stays the backstop."""
        md = request.metadata
        if md.get("kv_block_size") != self.page_size:
            return None
        sh, lh = md.get("kv_seq_hashes"), md.get("kv_local_hashes")
        if not sh or not lh:
            return None
        from dynamo_tpu.llm.tokens import TokenBlockSequence

        try:
            return TokenBlockSequence.with_hashes(
                pre.token_ids, self.page_size, sh, lh
            )
        except (TypeError, ValueError):
            return None

    async def generate_remote(
        self,
        request: Context,
        first_token: int,
        k_arr: np.ndarray,
        v_arr: np.ndarray,
        ks_arr: Optional[np.ndarray] = None,
        vs_arr: Optional[np.ndarray] = None,
        _blocks: Optional["TokenBlockSequence"] = None,
    ) -> AsyncIterator[dict]:
        """Decode-side disagg entry: like generate(), but the prompt's KV
        (computed by a remote prefill worker) is injected instead of
        computed, and `first_token` (sampled remotely) seeds decode.
        `ks_arr`/`vs_arr` [L, T, S] are present when the prefill worker
        serves a quantized KV cache (the wire stays the packed bytes —
        half the transfer at int8, a quarter at int4 [L, T, K*Hd/2]);
        injection converts a bf16/int8 mix to this engine's KV dtype as
        needed, while cross-tier quantized mixes raise
        KvQuantMismatchError (see _convert_wire_kv)."""
        self._refuse_plane("disaggregated decode (generate_remote)")
        payload = request.payload
        pre = (
            PreprocessedRequest.from_dict(payload)
            if isinstance(payload, dict)
            else payload
        )
        m = self.model_cfg
        kw = m.num_kv_heads * m.head_dim
        # a quantized wire may be int4 nibble-packed: half-width rows
        int4_wire = ks_arr is not None and k_arr.shape[-1] * 2 == kw
        want = (m.num_layers, len(pre.token_ids), kw // 2 if int4_wire else kw)
        for name, arr in (("k", k_arr), ("v", v_arr)):
            if tuple(arr.shape) != want:
                raise ValueError(
                    f"remote {name} KV shape {tuple(arr.shape)} != expected {want}"
                )
        if (ks_arr is None) != (vs_arr is None):
            raise ValueError("remote KV scales must come as a k/v pair")
        if ks_arr is not None:
            s_ch = self._kv_scale_channels() if int4_wire else m.num_kv_heads
            want_s = (m.num_layers, len(pre.token_ids), s_ch)
            for name, arr in (("ks", ks_arr), ("vs", vs_arr)):
                if tuple(arr.shape) != want_s:
                    raise ValueError(
                        f"remote {name} scale shape {tuple(arr.shape)} != "
                        f"expected {want_s}"
                    )
        preloaded = (int(first_token), k_arr, v_arr, ks_arr, vs_arr)
        return await self.generate(
            request, _preloaded=preloaded, _blocks=_blocks
        )

    async def prefill_only(
        self, pre: PreprocessedRequest, ctx: Optional[Context] = None,
        device_arrays: bool = False,
    ) -> tuple:
        """Prefill-side disagg entry: compute the prompt's KV (+ first
        token), extract it, and keep the pages in the prefix cache for
        future hits. Returns (first_token, k, v, ks, vs) with k/v shaped
        [L, T, Kh*Hd]; ks/vs are [L, T, S] scale arrays on a quantized
        engine (the wire stays the pool's packed bytes — int8, or
        nibble-packed int4 rows [L, T, Kh*Hd/2]), else None.

        `device_arrays=True` skips the host copy and returns jax arrays
        — the send side of the device-path transfer
        (engine/xproc_kv.py / engine/kv_transfer.py)."""
        self._refuse_plane(
            "disaggregated prefill (prefill_only: the send side of the "
            "host-staged and device-path planes)"
        )
        ctx = ctx or Context(pre.to_dict())
        usable_tokens = (self.num_pages - 1) * self.page_size
        if len(pre.token_ids) + 1 > usable_tokens:
            raise ValueError(
                f"prompt of {len(pre.token_ids)} tokens cannot fit the KV pool "
                f"({self.num_pages - 1} pages x {self.page_size} tokens)"
            )
        seq = Sequence.from_request(
            ctx, pre, self.page_size, self.config.max_model_len
        )
        # page-wait budget: the (previously hardcoded 60 s) config knob,
        # shrunk to whatever remains of the request's own deadline — the
        # wait must always fit the caller's end-to-end budget
        wait_s = float(self.config.prefill_wait_s)
        if seq.deadline:
            wait_s = min(wait_s, max(seq.deadline - time.time(), 0.0))
        deadline = asyncio.get_running_loop().time() + wait_s
        while not self._reserve_pages(seq):
            if asyncio.get_running_loop().time() > deadline:
                # typed: a capacity condition the HTTP layer maps to 503
                # + Retry-After, never a 5xx "server bug"
                raise PoolExhaustedError(
                    f"prefill worker out of KV pages after {wait_s:.1f}s"
                )
            await asyncio.sleep(0.05)
        try:
            first_token = await self._prefill_forward(seq)
            t = seq.num_computed
            slots = np.asarray(
                [self._write_slot(seq, p) for p in range(t)], np.int32
            )

            def _extract():
                with self._kv_lock:  # vs the decode thread donating kv
                    out = self._extract_fn(self.kv, jnp.asarray(slots))
                if device_arrays:
                    return out
                return tuple(np.asarray(a) for a in out)

            arrs = await asyncio.to_thread(_extract)
            if len(arrs) == 4:
                return (first_token, *arrs)
            return (first_token, arrs[0], arrs[1], None, None)
        finally:
            self._kv_drop(seq.page_ids, seq.ctx.id)
            self.allocator.release(seq.page_ids)

    def ingest_prefix(self, token_ids: list[int], k, v, ks=None, vs=None) -> int:
        """Insert externally-computed KV for a token prefix into the
        paged pool AND the prefix cache — the decode-side landing point
        of a device-path transfer (engine/xproc_kv.py): `k`/`v` are
        [L, T, K*Hd] arrays (jax arrays stay on device end to end;
        `ks`/`vs` [L, T, S] dense scales from a quantized source — int8
        rows, or nibble-packed int4 rows [L, T, K*Hd/2]).

        Only whole pages are ingested (the prefix cache is page-
        granular); returns the number of tokens now cached. A following
        `generate()` with this prompt rides the prefix cache, recomputes
        the remaining tail, and continues bit-identically to a local
        serve. bf16/int8 mixes convert exactly like the host-staged wire
        (quantize/dequantize on injection); cross-tier quantized mixes
        raise KvQuantMismatchError (_convert_wire_kv) — packed bytes are
        quantized exactly once and never requantized pool-to-pool."""
        self._refuse_plane(
            "prefix ingest (ingest_prefix: the device-path landing side)"
        )
        full_pages = len(token_ids) // self.page_size
        if full_pages == 0:
            return 0
        from dynamo_tpu.llm.tokens import TokenBlockSequence

        blocks = TokenBlockSequence(
            list(token_ids), self.page_size
        ).blocks[:full_pages]
        # skip the run already cached; ingest only the novel tail. The
        # matched pages stay PINNED until the tail is registered —
        # releasing first would let allocate() evict the very prefix the
        # registered tail chains from
        cached = self.allocator.match_prefix(
            [b.sequence_hash for b in blocks]
        )
        self._kv_hold(cached, "sys:ingest")
        start = len(cached)
        if start == full_pages:
            self._kv_drop(cached, "sys:ingest")
            self.allocator.release(cached)
            return full_pages * self.page_size
        need = full_pages - start
        pages = self.allocator.allocate(need)
        if pages is None:
            self._kv_drop(cached, "sys:ingest")
            self.allocator.release(cached)
            return start * self.page_size
        self._kv_hold(pages, "sys:ingest")
        t0, t1 = start * self.page_size, full_pages * self.page_size
        P = jax.sharding.PartitionSpec
        row_sh = jax.sharding.NamedSharding(self.mesh, P(None, None, "tp"))
        repl = jax.sharding.NamedSharding(self.mesh, P())
        slots = jax.device_put(
            jnp.concatenate([
                pid * self.page_size
                + jnp.arange(self.page_size, dtype=jnp.int32)
                for pid in pages
            ]),
            repl,
        )
        # land the rows on this engine's mesh (device-to-device; a
        # TP-degree mismatch vs the source resharding right here)
        nk, nv, nks, nvs = self._convert_wire_kv(
            jnp.asarray(k)[:, t0:t1], jnp.asarray(v)[:, t0:t1],
            jnp.asarray(ks)[:, t0:t1] if ks is not None else None,
            jnp.asarray(vs)[:, t0:t1] if vs is not None else None,
            put=lambda a: jax.device_put(a, row_sh),
        )
        with self._kv_lock:
            self.kv = self._inject_fn(self.kv, slots, nk, nv, nks, nvs)
        self.allocator.register(
            pages,
            [(b.sequence_hash, b.local_hash) for b in blocks[start:]],
            parent_hash=blocks[start].parent_sequence_hash,
        )
        # drop this call's pins: the pages stay in the prefix cache
        # (evictable at refs 0) instead of leaking pinned forever
        self._kv_drop(cached, "sys:ingest")
        self._kv_drop(pages, "sys:ingest")
        self.allocator.release(cached)
        self.allocator.release(pages)
        return full_pages * self.page_size

    def export_prefix(
        self, token_ids: list[int], hashes: Optional[list[int]] = None,
    ):
        """Extract this engine's cached KV for a prompt's longest cached
        prefix — the SOURCE side of a cross-worker prefix pull
        (docs/kv_cache.md). Returns (n_tokens, k, v, ks, vs) with k/v
        numpy [L, T, Kh*Hd] (quantized engines keep the wire on the pool
        bytes + [L, T, S] scales: int8 rows at half bf16's bytes, int4
        nibble-packed rows [L, T, Kh*Hd/2] at a quarter), or None when
        no full page of the prompt is cached.

        Matched pages are PINNED for the duration of the extract so the
        gather cannot race an eviction; pins drop before returning (the
        pages stay cached). Blocking (jit dispatch + device fetch):
        callers run it in a worker thread."""
        self._refuse_plane("prefix export (export_prefix)")
        if hashes is None:
            from dynamo_tpu.llm.tokens import compute_block_hashes

            hashes = compute_block_hashes(token_ids, self.page_size)
        pages = self.allocator.match_prefix(hashes)
        if not pages:
            return None
        self._kv_hold(pages, "sys:export")
        try:
            ps = self.page_size
            slots = np.concatenate(
                [pid * ps + np.arange(ps, dtype=np.int32) for pid in pages]
            )
            with self._kv_lock:
                out = self._extract_fn(self.kv, jnp.asarray(slots))
            arrs = tuple(np.asarray(a) for a in out)
        finally:
            self._kv_drop(pages, "sys:export")
            self.allocator.release(pages)
        if len(arrs) == 4:
            return (len(pages) * ps, *arrs)
        return (len(pages) * ps, arrs[0], arrs[1], None, None)

    def _convert_wire_kv(self, nk, nv, nks, nvs, put=lambda a: a):
        """Normalize a disagg KV payload to this engine's KV dtype — ONE
        ladder for the host-staged and device-path planes: quantize a
        model-dtype wire entering a quantized pool, pass a MATCHING-tier
        quantized wire (int8 or nibble-packed int4) through byte-
        identical, dequantize an int8 wire entering a model-dtype pool.
        Cross-tier quantized pairs (int8 wire -> int4 pool and every
        other combination that would need a requantization hop) raise
        KvQuantMismatchError: quantized pools carry bytes quantized
        exactly once at KV-write time, so there is no lossless
        conversion between tiers. `put` lands arrays on the engine's
        mesh sharding first when needed."""
        kw = self.model_cfg.num_kv_heads * self.model_cfg.head_dim
        wire = None  # the payload's tier, inferred from the row width
        if nks is not None:
            wire = "int4" if int(np.shape(nk)[-1]) * 2 == kw else "int8"
        if wire is not None and wire != (self._kv_quant or "int8"):
            from dynamo_tpu.llm.protocols.common import KvQuantMismatchError

            raise KvQuantMismatchError(
                f"wire KV payload is {wire} but this engine's pool tier "
                f"is {self._kv_quant or self.config.dtype}: cross-tier "
                "injection would requantize already-quantized bytes — "
                "both sides need matching kv_quantization"
            )
        if wire == "int4" and int(np.shape(nks)[-1]) != self._kv_scale_channels():
            from dynamo_tpu.llm.protocols.common import KvQuantMismatchError

            raise KvQuantMismatchError(
                f"int4 wire KV carries {int(np.shape(nks)[-1])} scale "
                f"channels but this engine's pools use "
                f"{self._kv_scale_channels()} (kv_quant_group mismatch) "
                "— both sides need matching kv_quantization grouping"
            )
        nk, nv = put(jnp.asarray(nk)), put(jnp.asarray(nv))
        if self._kv_quant and nks is None:
            nk, nks = self._kv_quantize_fn(nk)
            nv, nvs = self._kv_quantize_fn(nv)
        elif self._kv_quant:
            nks, nvs = put(jnp.asarray(nks)), put(jnp.asarray(nvs))
        elif nks is not None:
            nk = self._kv_dequantize_fn(nk, put(jnp.asarray(nks)))
            nv = self._kv_dequantize_fn(nv, put(jnp.asarray(nvs)))
            nks = nvs = None
        else:
            nks = nvs = None
        return nk, nv, nks, nvs

    # ------------------------------------------------------------------
    # fault-tolerance spine: feature gates, watchdog, deadlines
    # (docs/robustness.md)

    def _pipe_on(self) -> bool:
        """Step pipeline effective flag: config AND the degrade ladder.
        ONE predicate for every read site so a watchdog trip serializes
        all of them at once."""
        return self.config.step_pipeline and not self._degrade.disabled(
            "step_pipeline"
        )

    def _spec_on(self) -> bool:
        return self.config.spec_decode and not self._degrade.disabled("spec")

    def _op_begin(self, label: str) -> Optional[int]:
        """Register a device-critical op (dispatch call or result fetch)
        with the watchdog; returns a token for `_op_end`. No-op (None)
        when the watchdog is off — zero steady-state cost."""
        if not self._watchdog_s:
            return None
        tok = next(self._op_ids)
        self._ops[tok] = (label, time.perf_counter())
        return tok

    def _op_end(self, tok: Optional[int]) -> None:
        if tok is not None:
            self._ops.pop(tok, None)

    def _ensure_watchdog(self) -> None:
        if self._watchdog_s <= 0:
            return
        if self._watchdog_task is None or self._watchdog_task.done():
            self._watchdog_task = asyncio.get_running_loop().create_task(
                self._watchdog_loop()
            )

    async def _watchdog_loop(self) -> None:
        """Monitor task: notice a dispatch/fetch that has stalled past
        `watchdog_dispatch_s`, dump the trace ring + phase stats to a
        crash artifact, and walk the degrade ladder. The hung op itself
        cannot be killed (a wedged jit call sits in the runtime with the
        GIL released) — the job here is to make the hang VISIBLE and to shed
        the most speculative machinery so the next dispatch, if the
        fault was transient, runs the conservative path."""
        interval = min(max(self._watchdog_s / 4.0, 0.05), 1.0)
        try:
            while not self._closed:
                await asyncio.sleep(interval)
                if not self._ops:
                    # fired-token set tracks only live ops
                    self._watch_fired.clear()
                    continue
                now = time.perf_counter()
                for tok, (label, t0) in list(self._ops.items()):
                    stalled = now - t0
                    if stalled <= self._watchdog_s or tok in self._watch_fired:
                        continue
                    self._watch_fired.add(tok)
                    self._watchdog_fire(label, stalled)
                self._watch_fired.intersection_update(self._ops)
        except asyncio.CancelledError:
            return

    def _watchdog_fire(self, label: str, stalled_s: float) -> None:
        with self._phase_lock:
            self._phase_stats["watchdog_fired"] += 1
        reason = f"watchdog: {label} stalled {stalled_s:.2f}s"
        rung = self._degrade.trip_next(reason)
        path = self._dump_crash_artifact(label, stalled_s, rung)
        log.error(
            "engine watchdog fired: %s has not completed after %.2fs "
            "(budget %.2fs); degrade rung tripped: %s; crash artifact: %s",
            label, stalled_s, self._watchdog_s, rung or "none left", path,
        )
        if tracing.enabled():
            tracing.instant(
                "watchdog.fire", cat="degrade", op=label,
                stalled_s=round(stalled_s, 3), rung=rung or "",
            )
        if self.flight is not None:
            # forensics plane: the flight recorder's correlated artifact
            # (digest window + trace slice + context) rides every
            # watchdog fire too — rate-limited, so a storm of stalled
            # ops still writes one
            self.flight.trigger(f"watchdog:{label}")

    def _dump_crash_artifact(
        self, label: str, stalled_s: float, rung: Optional[str]
    ) -> Optional[str]:
        """Write the PR-4 trace ring + phase stats + metrics snapshot
        next to the hang, so the postmortem does not depend on the
        process surviving to serve /debug/trace. Best-effort: artifact
        IO must never take the watchdog down (the shared writer,
        utils/artifacts.py, swallows IO failures)."""
        try:
            artifact = {
                "op": label,
                "stalled_s": round(stalled_s, 3),
                "watchdog_dispatch_s": self._watchdog_s,
                "rung_tripped": rung,
                "degrade_state": self._degrade.state(),
                "phase_stats": self.phase_stats,
                "metrics": self.metrics(),
                "inflight_ops": [
                    {"op": lbl, "age_s": round(time.perf_counter() - t0, 3)}
                    for lbl, t0 in self._ops.values()
                ],
                "trace": tracing.export(),
            }
            if self.flight is not None:
                # the step-digest window rides the watchdog artifact
                # too: what the engine was doing in the seconds BEFORE
                # the hang, not just the hang itself
                artifact["digest_fields"] = list(flightmod.FIELDS)
                artifact["digests"] = self.flight.snapshot_rows()
        except Exception:  # noqa: BLE001 — the dump is best-effort
            log.exception("watchdog crash-artifact dump failed")
            return None
        path = artifacts.write_crash_artifact(
            "engine_watchdog", artifact, directory=self.config.crash_dir
        )
        if path is not None:
            self.last_crash_artifact = path
        return path

    def _shed_expired_waiting(self) -> bool:
        """Reject admission-queue requests whose deadline has passed —
        BEFORE they touch the device. They resolve with a zero-token
        `timeout` finish (the HTTP layer turns that into 429 +
        Retry-After when the response has not started streaming)."""
        if not self._has_deadlines or not self.waiting:
            return False
        now = time.time()
        expired = [s for s in self.waiting if s.past_deadline(now)]
        for seq in expired:
            self.waiting.remove(seq)
            with self._phase_lock:
                self._phase_stats["deadline_shed"] += 1
            if tracing.enabled():
                # t_submit is a perf_counter stamp — subtract in the
                # same clock domain (`now` above is epoch time.time())
                tracing.instant(
                    "seq.deadline_shed", cat="lifecycle", req=seq.ctx.id,
                    queued_s=(
                        round(time.perf_counter() - seq.t_submit, 3)
                        if seq.t_submit else 0
                    ),
                )
            self._note_finished(seq, FINISH_REASON_TIMEOUT)
            seq.out_queue.put_nowait(
                EngineOutput.final(FINISH_REASON_TIMEOUT).to_dict()
            )
        if expired and self.flight is not None:
            # a shed BURST (not one straggler) is a forensic trigger:
            # the recorder windows the counts and dumps past its
            # threshold (DYN_FLIGHT_SHED_BURST)
            self.flight.note_shed(len(expired))
        return bool(expired)

    def _sweep_expired(self, seq: Sequence, now: float) -> bool:
        """Mid-flight deadline check (cancellation-sweep companion):
        finish an admitted sequence whose budget ran out."""
        if not seq.past_deadline(now):
            return False
        with self._phase_lock:
            self._phase_stats["deadline_timeouts"] += 1
        if tracing.enabled():
            tracing.instant(
                "seq.deadline_timeout", cat="lifecycle", req=seq.ctx.id,
                generated=seq.generated,
            )
        self._finish(seq, FINISH_REASON_TIMEOUT)
        return True

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(self._loop())
        self._ensure_watchdog()

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        self._heap.close()
        if self.flight is not None:
            # freeze the final context snapshot and drop the bound
            # provider: the flight-recorder registry keeps the RING
            # dumpable post-close without pinning this engine's pools
            self.flight.seal_context()
        if self._watchdog_task is not None and not self._watchdog_task.done():
            self._watchdog_task.cancel()
            try:
                await self._watchdog_task
            except asyncio.CancelledError:
                pass
        if self._loop_task:
            try:
                await self._loop_task
            except asyncio.CancelledError:
                pass
        if self._offload_task is not None and not self._offload_task.done():
            self._offload_task.cancel()
            try:
                await self._offload_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        for seq in list(self.waiting) + [s for s in self.slots if s]:
            self._note_finished(seq, FINISH_REASON_CANCELLED)
            seq.out_queue.put_nowait(
                EngineOutput.final(FINISH_REASON_CANCELLED).to_dict()
            )

    # ------------------------------------------------------------------
    # main loop

    async def _loop(self) -> None:
        # the loop task inherits the contextvars of WHICHEVER request
        # created it; unbind the request id so engine-loop log records
        # and spans never join against that arbitrary first request
        tracing.set_request(None)
        try:
            while not self._closed:
                # one iteration = one eng.tick; its children say what
                # the loop's thread did (docs/observability.md)
                with profiler.phase("eng.tick"):
                    if await self._tick():
                        return
        except Exception:
            log.exception("engine loop crashed; failing all requests")
            for seq in list(self.waiting) + [s for s in self.slots if s]:
                # the observability plane must cover the failure case it
                # exists for: histograms + the request trace span record
                # these as errors, same as a per-sequence _finish would
                self._note_finished(seq, FINISH_REASON_ERROR)
                seq.out_queue.put_nowait(EngineOutput.final("error").to_dict())
            self.waiting.clear()
            self.slots = [None] * len(self.slots)
            self._prefilling.clear()
            self._inflight = None
            raise

    async def _tick(self) -> bool:
        """One iteration of the loop; True when the engine closed."""
        with profiler.phase("eng.admit"):
            # custody audit (off the dispatch path; gated on its
            # period so steady-state ticks pay one clock read)
            if self._kv_audit_s > 0:
                now = time.monotonic()
                if now >= self._kv_audit_next:
                    self._kv_audit_next = now + self._kv_audit_s
                    self._run_kv_audit()
            # offload first: pending write-through copies must
            # pin their pages before this tick's admission can
            # evict them
            self._maybe_start_offload()
            # deadline shed: queue members whose budget expired
            # leave with 429/timeout before they can claim a
            # slot or pages
            progressed = self._shed_expired_waiting()
            progressed |= self._admit_new()
        # stall-free mixed step first: when decode-ready rows
        # and pending prefill chunks coexist, ONE token-budgeted
        # dispatch advances both planes and the normal
        # prefill/decode ticks stand down. With the step
        # pipeline (default) the mixed tick dispatches BEHIND
        # any in-flight dispatch (q_len=1 rows read the device
        # carry), syncs the old one while the new executes, and
        # leaves its own dispatch in flight ("pipelined");
        # serialized engines instead "hold" a tick whenever a
        # dispatch is in flight (host-built windows need synced
        # token history)
        mixed = None
        if self.config.mixed_batching:
            mixed = await self._mixed_tick()
            progressed |= mixed in (True, "pipelined")
        # per tick: prefill chunks enqueue first (they own self.kv
        # until their dispatch call returns), then decode dispatch
        # N+1 runs in a worker thread WHILE the loop fetches
        # dispatch N's tokens — the dispatch call holds _kv_lock
        # and may wait on the runtime's queue, the fetch waits
        # on the device; in separate threads neither wait sits
        # on the event loop
        if mixed is None:
            progressed |= await self._prefill_tick()
        pipe = self._pipe_on()
        if not pipe and mixed != "pipelined":
            # serialized A/B baseline: dispatch -> fetch -> sync,
            # nothing overlaps — the old dispatch lands BEFORE
            # the next one is even built
            old, self._inflight = self._inflight, None
            if old is not None:
                await self._sync_dispatch(old)
                progressed = True
        new_task = None
        snapshot = (
            self._maybe_dispatch_decode() if mixed is None else None
        )
        if snapshot == "sync_first":
            # worthwhile spec drafts behind an in-flight
            # dispatch: sync it NOW and re-enter the build, so
            # the verify window dispatches THIS tick instead of
            # after a dead tick (the standalone-spec half of the
            # step pipeline — verify windows are host-built, so
            # the sync is a real data dependency, but the dead
            # tick between it and the verify dispatch was not)
            old, self._inflight = self._inflight, None
            if old is not None:
                await self._sync_dispatch(old)
                progressed = True
            snapshot = self._maybe_dispatch_decode()
            if snapshot == "sync_first":  # nothing left in flight
                snapshot = None
        if snapshot is not None:
            new_task = asyncio.create_task(
                asyncio.to_thread(self._run_decode_dispatch, snapshot)
            )
            progressed = True
        if pipe and mixed != "pipelined":
            old, self._inflight = self._inflight, None
            if old is not None:
                await self._sync_dispatch(
                    old, overlapped=new_task is not None
                )
                progressed = True
        if new_task is not None:
            with profiler.phase("eng.join"):
                self._inflight = await new_task
        if progressed:
            self._heap.settle()
            # yield so producers/consumers interleave with the loop
            await asyncio.sleep(0)
            return False
        self._wake.clear()
        if self._closed:
            return True
        if self.waiting or self._prefilling or self._inflight:
            return False
        self._t_landed = 0.0  # idle: the next landing opens no tick
        with profiler.phase("eng.wait"):
            if self._kv_audit_s > 0:
                # idle must not stall the custody audit: a request
                # that leaked pages at _finish has no successor to
                # wake the loop, so bound the sleep by the next
                # audit tick (zero cost while busy)
                try:
                    await asyncio.wait_for(
                        self._wake.wait(),
                        timeout=max(
                            self._kv_audit_next - time.monotonic(),
                            0.001,
                        ),
                    )
                except asyncio.TimeoutError:
                    pass
            else:
                await self._wake.wait()
        return False

    # ---- admission ----------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit_new(self) -> bool:
        """Assign waiting sequences to free slots + pages; actual prefill
        compute happens chunk-at-a-time in _prefill_tick."""
        progressed = False
        while self.waiting:
            slot = self._free_slot()
            if slot is None:
                break
            # priority-aware pick: highest class first, FIFO within a
            # class (scheduler.pick_admission_index) — index 0 whenever
            # no priorities are in flight, i.e. plain FIFO
            idx = pick_admission_index(self.waiting)
            seq = self.waiting[idx]
            if seq.ctx.is_stopped():
                del self.waiting[idx]
                # observability parity with _finish: requests that die in
                # the waiting queue still count in histograms/trace spans
                self._note_finished(seq, FINISH_REASON_CANCELLED)
                seq.out_queue.put_nowait(
                    EngineOutput.final(FINISH_REASON_CANCELLED).to_dict()
                )
                progressed = True
                continue
            if seq.max_new_tokens <= 0:
                del self.waiting[idx]
                self._note_finished(seq, FINISH_REASON_LENGTH)
                seq.out_queue.put_nowait(
                    EngineOutput.final(FINISH_REASON_LENGTH).to_dict()
                )
                progressed = True
                continue
            if not self._reserve_pages(seq):
                break  # out of pages; wait for something to finish
            del self.waiting[idx]
            seq.slot = slot
            seq.prefilling = True
            seq.t_admit = time.perf_counter()
            if tracing.enabled():
                tracing.instant(
                    "seq.admit", cat="lifecycle", req=seq.ctx.id,
                    ts=seq.t_admit, slot=slot,
                    prefix_cached_tokens=seq.num_cached,
                )
            seq.first_meta = {
                "prefix_cached_tokens": seq.num_cached,
                "prompt_tokens": seq.prompt_len,
            }
            self.slots[slot] = seq
            self._mark_slot_state(seq)
            if self.config.spec_decode and seq.spec is None:
                # seed the n-gram index with the prompt once; the index
                # survives preemption (the token history it covers does
                # not change across a re-prefill)
                seq.spec = NgramProposer(
                    self.config.spec_ngram_max,
                    self.config.spec_index_window,
                )
                seq.spec.extend(seq.tokens)
            if seq.has_penalties:
                self._count_prompt(seq)
            if self._dlm and seq.num_computed >= seq.prefill_end:
                # no whole block left to encode (a prompt shorter than a
                # block, or every whole block of it cached): straight to
                # its first block pass
                self._dlm_ready(seq)
            else:
                self._prefilling.append(seq)
            progressed = True
        return progressed

    def _mark_slot_state(self, seq: Sequence) -> None:
        """Refresh a slot's rows (block table + sampling params) in the
        host mirrors every dispatch build snapshots — called on admit
        and on page growth, the only times a LIVE slot's slow-changing
        inputs change (loop thread only)."""
        i = seq.slot
        row = self._host_tables[i]
        row[:] = 0
        w = self.config.max_pages_per_seq
        n = min(len(seq.page_ids), w)
        row[:n] = seq.page_ids[:n]
        if self._hybrid:
            # released pages read 0: the trash page, never attended
            n = min(len(seq.win_page_ids), w)
            row[w:w + n] = seq.win_page_ids[:n]
        self._host_samp_f[i] = (
            seq.temperature, seq.top_p, seq.frequency_penalty,
            seq.presence_penalty, seq.repetition_penalty,
        )
        self._host_samp_i[i] = (seq.top_k, seq.seed)

    def _take_state(self, counts: bool = False,
                    dlm: bool = False) -> StepState:
        """The state a step program is about to consume (under
        `_kv_lock`). The counts ride only into the penalty / seeded
        programs, allocated on first use: an upload, not a launch; the
        open blocks (`dlm`) only into the block step program."""
        st = self._state
        if not dlm:
            st = st._replace(dlm=None)
        if not counts:
            return st._replace(counts=None)
        if st.counts is None:
            st = st._replace(counts=jax.device_put(
                np.zeros(
                    (self.config.max_batch_size, self.model_cfg.vocab_size),
                    np.int8,
                ),
                self._state_sharding,
            ))
            self._state = self._state._replace(counts=st.counts)
        return st

    def _put_state(self, new: StepState) -> None:
        """The state a step program returned (under `_kv_lock`); a
        program that did not take the counts, or the open blocks, left
        them where they are."""
        if new.counts is None:
            new = new._replace(counts=self._state.counts)
        if new.dlm is None:
            new = new._replace(dlm=self._state.dlm)
        self._state = new

    def _reset_and_count(self, counts, row, tokens, reset=True):
        """Zero a slot's occurrence-count row (first chunk) and
        scatter-add prompt tokens into it (ops/sampling.count_tokens)."""
        from dynamo_tpu.ops.sampling import count_tokens

        if reset:
            counts = counts.at[row].set(0)
        return count_tokens(counts, row, tokens)

    def _count_prompt(self, seq: Sequence) -> None:
        """Seed the slot's count row with the prompt so penalties see
        "the text so far" (prompt + completion, OpenAI semantics).
        Chunked to the prefill buckets to bound compiled shapes; token
        id 0 in a prompt is not counted (pad sentinel)."""
        tokens = seq.tokens
        buckets = self.config.prefill_buckets()
        row = np.asarray(seq.slot, np.int32)
        start = 0
        with self._kv_lock:
            counts = self._take_state(counts=True).counts
            while start < len(tokens):
                chunk = tokens[start:start + buckets[-1]]
                bucket = next(b for b in buckets if b >= len(chunk))
                padded = np.zeros(bucket, np.int32)
                padded[: len(chunk)] = chunk
                counts = self._reset_count_fn(counts, row, padded, start == 0)
                start += len(chunk)
            self._state = self._state._replace(counts=counts)

    def _reserve_pages(self, seq: Sequence) -> bool:
        """Prefix-match (HBM, then host tier) and allocate pages covering
        all current tokens; host-tier hits are restored by H2D scatter."""
        try:
            # chaos hook: an injected 'fail' here simulates KV-pool
            # exhaustion — callers see the same False the real allocator
            # returns when out of pages (docs/robustness.md)
            faults.fire("engine.reserve")
        except faults.FaultError:
            return False
        t = seq.total_tokens
        # fresh reservation, fresh ledger: a preemption-resume must not
        # carry a previous attempt's decline into the summary next to
        # this reservation's reuse numbers (the reused/restored fields
        # are restamped below; the decline branches may never run again)
        seq.blocks_declined = 0
        seq.gate_reason = ""
        hashes = seq.blocks.sequence_hashes()
        cap = seq.cacheable_pages(self.page_size)
        if cap is not None and hashes:
            # embed sequences: only the text prefix below embeds_offset
            # has sound hashes (placeholder ids don't cover the image)
            hashes = hashes[:cap]
        if self._no_prefix_cache:
            hashes = []  # nothing is registered: no in-engine prefix cache
        matched = self.allocator.match_prefix(hashes)
        host_run: list[int] = []
        if self.host_pool is not None and hashes:
            host_run = self.host_pool.match_prefix(hashes[len(matched):])
        # ensure >=1 token is computed (there must be a query position)
        while (len(matched) + len(host_run)) * self.page_size >= t:
            if host_run:
                host_run.pop()
            else:
                self.allocator.release([matched[-1]])
                matched = matched[:-1]
        need = -(-t // self.page_size) - len(matched)
        fresh = self.allocator.allocate(need) if need else []
        if fresh is None:
            self.allocator.release(matched)
            return False
        if self._hybrid and not self._reserve_window_pages(
            seq, -(-min(t, self.config.prefill_chunk) // self.page_size)
        ):
            self.allocator.release(fresh)
            return False
        if host_run and not self._restore_worthwhile(len(host_run)):
            # cost gate: on this deployment restoring would be slower
            # than recomputing the prefix — the tier must never make
            # TTFT worse (pages stay host-side for a cheaper future hit)
            self.offload_gate_stats["declined"] += 1
            seq.blocks_declined = len(host_run)
            seq.gate_reason = "restore_slower_than_recompute"
            if tracing.enabled():
                tracing.instant(
                    "offload.gate", cat="kv", req=seq.ctx.id,
                    decision="declined", blocks=len(host_run),
                    reason=seq.gate_reason,
                )
            host_run = []
        if host_run:
            try:
                self._restore_from_host(seq, fresh[: len(host_run)], len(matched))
            except Exception:
                # restore is an optimization; fall back to recompute —
                # counted and traced like a gate decline so the
                # aggregate gauges agree with the per-request ledgers
                log.exception("host-tier restore failed; recomputing")
                self.offload_gate_stats["failed"] += 1
                seq.blocks_declined = len(host_run)
                seq.gate_reason = "restore_failed"
                if tracing.enabled():
                    tracing.instant(
                        "offload.gate", cat="kv", req=seq.ctx.id,
                        decision="failed", blocks=len(host_run),
                        reason=seq.gate_reason,
                    )
                host_run = []
        seq.page_ids = matched + fresh
        self._kv_hold(seq.page_ids, seq.ctx.id, tenant=seq.tenant)
        seq.num_cached = (len(matched) + len(host_run)) * self.page_size
        seq.num_computed = seq.num_cached
        seq.registered_pages = len(matched) + len(host_run)
        # per-request ledger (finish-summary `prefix` section): reflects
        # the LAST reservation — a preemption-resume restamps it with
        # what the re-admission actually reused
        seq.blocks_reused = len(matched)
        seq.blocks_restored = len(host_run)
        if host_run and tracing.enabled():
            tracing.instant(
                "offload.gate", cat="kv", req=seq.ctx.id,
                decision="restored", blocks=len(host_run),
            )
        if matched or host_run:
            # prefix attribution: the phase counters the bench's
            # prefix_ab section diffs cold vs warm, plus one event per
            # hit on the engine.prefix track so a slow warm serve is
            # attributable in the trace (which hit, how much reused,
            # how much tail it still prefilled)
            tail = t - seq.num_cached
            # "full" = only the trailing page (or less) recomputes: the
            # cache covered every other page of the prompt
            full_hit = tail <= self.page_size
            with self._phase_lock:
                st = self._phase_stats
                st["prefix_hits"] += 1
                st["prefix_full_hits"] += 1 if full_hit else 0
                st["prefix_reused_tokens"] += len(matched) * self.page_size
                st["prefix_restored_tokens"] += len(host_run) * self.page_size
                st["prefix_tail_tokens"] += tail
            if tracing.enabled():
                tracing.instant(
                    "prefix.hit", cat="kv", req=seq.ctx.id,
                    track="engine.prefix", reused_blocks=len(matched),
                    restored_blocks=len(host_run), tail_tokens=tail,
                    full=full_hit,
                )
        return True

    # ---- window pages (hybrid models) ----------------------------------

    def _reserve_window_pages(self, seq: Sequence, n: int) -> bool:
        """Window-kind pages for the `n` logical pages of a fresh
        reservation's FIRST chunk: a window layer's pages come a chunk at
        a time (`_pick_prefill_groups` grows the list through the next
        chunk once `_release_window_pages` has handed back what fell
        behind the window), so a row in prefill holds its chunk's pages
        and the one before, however long its prompt. Admission leaves one
        page a live row free, so that a decode row's growth never waits
        on a prompt."""
        wa = self.win_allocator
        if wa.num_free - n < sum(s is not None for s in self.slots):
            return False
        got = wa.allocate(n)
        if got is None:
            return False
        seq.win_page_ids = got
        seq.win_first = 0
        self.kv_ledger_win.hold(got, seq.ctx.id, tenant=seq.tenant)
        return True

    def _release_window_pages(self, seq: Sequence) -> bool:
        """Give back the window-kind pages that lie WHOLLY behind the
        window of every position still to be computed: a query at `p >=
        num_computed` reads positions above `p - window`, so logical
        page i goes once `(i + 1) * page_size <= num_computed - window +
        1`. Counted on what has LANDED: a dispatch in flight starts at or
        after it. The list keeps its positions (a released entry reads 0,
        the trash page), so tables are built as for the full kind."""
        ps = self.page_size
        first = max(
            seq.num_computed - self.model_cfg.sliding_window + 1, 0
        ) // ps
        first = min(first, len(seq.win_page_ids))
        if first <= seq.win_first:
            return False
        gone = seq.win_page_ids[seq.win_first:first]
        self.kv_ledger_win.drop(gone, seq.ctx.id)
        self.win_allocator.release(gone)
        seq.win_page_ids[seq.win_first:first] = [0] * len(gone)
        seq.win_first = first
        self._win_released += len(gone)
        return True

    def _drop_window_pages(self, seq: Sequence) -> None:
        """All of a sequence's window-kind pages, at preemption / finish."""
        held = seq.win_page_ids[seq.win_first:]
        self.kv_ledger_win.drop(held, seq.ctx.id)
        self.win_allocator.release(held)
        seq.win_page_ids = []
        seq.win_first = 0

    # ---- prefill ------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.config.prefill_buckets():
            if n <= b:
                return b
        return self.config.prefill_chunk

    def _slot_matrix_row(self, seq: Sequence, page_ids=None) -> np.ndarray:
        page_ids = seq.page_ids if page_ids is None else page_ids
        table = np.zeros(self.config.max_pages_per_seq, np.int32)
        table[: len(page_ids)] = page_ids
        return (
            table[:, None] * self.page_size + np.arange(self.page_size, dtype=np.int32)
        ).reshape(-1)

    def _write_slot(self, seq: Sequence, pos: int) -> int:
        return seq.page_ids[pos // self.page_size] * self.page_size + pos % self.page_size

    def _pick_prefill_groups(self) -> tuple:
        """This tick's prefill chunks, grouped by bucket under the
        per-tick token budget (loop thread); (groups, progressed)."""
        progressed = False
        groups: dict[int, list[Sequence]] = {}

        def padded_cost() -> int:
            # dispatch cost in activation tokens: row counts pad UP to a
            # power of two, and padding rows cost as much as real ones
            return sum(
                (1 << (len(seqs) - 1).bit_length()) * bucket
                for bucket, seqs in groups.items()
            )

        budget = self.config.prefill_group_tokens
        scanned = 0
        n_queued = len(self._prefilling)
        while self._prefilling and scanned < n_queued:
            scanned += 1
            seq = self._prefilling.popleft()
            if seq.ctx.is_stopped():
                self._finish(seq, FINISH_REASON_CANCELLED)
                progressed = True
                continue
            if self._has_deadlines and self._sweep_expired(seq, time.time()):
                # deadline expired mid-prefill: resolve before burning
                # the remaining chunks
                progressed = True
                continue
            if seq.preloaded is not None:
                try:
                    tok = self._inject_chunk(seq)
                except Exception:
                    # contain per-sequence failures (e.g. a malformed
                    # remote KV payload): fail this request, keep the
                    # loop alive
                    log.exception("prefill of seq %s failed", seq.seq_id)
                    self._finish(seq, FINISH_REASON_ERROR)
                    progressed = True
                    continue
                progressed = True
                if tok is None:
                    self._prefilling.append(seq)
                else:
                    self._mark_decode_ready(seq, tok)
                continue
            chunk = min(
                seq.prefill_end - seq.num_computed, self.config.prefill_chunk
            )
            if self._hybrid:
                # this chunk's window-kind pages (the full kind's were all
                # reserved at admission); a pool that is out preempts, as
                # for a decode row's growth: this row, or one picked before
                n_pre = self._preemptions
                alive = self._ensure_pages_through(
                    seq, seq.num_computed + chunk - 1
                )
                if self._preemptions != n_pre:
                    groups = {
                        b: kept for b, ss in groups.items()
                        if (kept := [s for s in ss if s.slot >= 0])
                    }
                if not alive:
                    progressed = True
                    continue
            bucket = self._bucket_for(chunk)
            groups.setdefault(bucket, []).append(seq)
            if padded_cost() > budget:
                groups[bucket].pop()
                if not groups[bucket]:
                    del groups[bucket]
                if groups:
                    self._prefilling.appendleft(seq)  # next tick, same order
                    break
                # a single chunk over budget still must run (tiny budget
                # misconfiguration) — dispatch it alone
                groups[bucket] = [seq]
                break
        return groups, progressed

    async def _prefill_tick(self) -> bool:
        """Dispatch up to `prefill_group_tokens` worth of prefill chunks,
        batching same-bucket chunks into one [n, bucket] model step —
        one dispatch per prompt pays the fixed host cost of a dispatch
        per prompt and leaves the MXU short rows. The per-tick
        token budget bounds how long active decode streams stall: one
        group dispatch per tick, decode interleaves between waves."""
        if not self._prefilling:
            return False
        with profiler.phase("eng.prefill.build"):
            groups, progressed = self._pick_prefill_groups()
        for bucket, seqs in groups.items():
            progressed = True
            try:
                # worker thread: the dispatch takes _kv_lock (the decode
                # worker may hold it), may trace+compile a new shape
                # (seconds), and can wait on the runtime's queue — run
                # inline any of those would freeze the event loop and
                # park every pending first-token emission and stream
                # consumer. _kv_lock serializes the donated cache
                # underneath. (Whether the thread hop still pays for
                # itself when nothing compiles is a ROADMAP queue 3
                # candidate.)
                wd = self._op_begin("prefill.dispatch")
                try:
                    with profiler.phase("eng.join"):
                        toks = await asyncio.to_thread(
                            self._prefill_group_dispatch, seqs, bucket
                        )
                finally:
                    self._op_end(wd)
                with profiler.phase("eng.emit"):
                    self._note_prefilled(seqs, bucket)
            except Exception:
                log.exception(
                    "prefill group of %d seqs failed; retrying singly",
                    len(seqs),
                )
                # contain the failure to the offending request(s): retry
                # each sequence in its own dispatch — with ITS OWN
                # bucket: the failed group's bucket was sized to the
                # group's largest chunk, and pushing a short chunk
                # through that oversized compiled family would both
                # waste the padded compute and (worse) retrace a family
                # the engine never otherwise builds
                for seq in seqs:
                    b1 = self._bucket_for(
                        min(
                            seq.prefill_end - seq.num_computed,
                            self.config.prefill_chunk,
                        )
                    )
                    try:
                        with profiler.phase("eng.join"):
                            tok1 = await asyncio.to_thread(
                                self._prefill_group_dispatch, [seq], b1
                            )
                        self._note_prefilled([seq], b1)
                    except Exception:
                        log.exception("prefill of seq %s failed", seq.seq_id)
                        self._finish(seq, FINISH_REASON_ERROR)
                        continue
                    if self._dlm and seq.num_computed >= seq.prefill_end:
                        self._dlm_ready(seq)
                    elif seq.num_computed >= seq.total_tokens:
                        self._mark_decode_ready(seq)
                        self._start_first_emit([(seq, 0)], tok1)
                    else:
                        self._prefilling.append(seq)
                continue
            with profiler.phase("eng.emit"):
                finals = []
                for j, seq in enumerate(seqs):
                    if self._dlm and seq.num_computed >= seq.prefill_end:
                        # the prompt's whole blocks are encoded: its tail
                        # is the head of the first block, which the next
                        # block dispatch arms (no token was sampled)
                        self._dlm_ready(seq)
                    elif seq.num_computed >= seq.total_tokens:
                        # final chunk: the step wrote the sampled token
                        # into the slot's decode carry AND one per-GROUP
                        # async fetch emits it early (_start_first_emit)
                        # — TTFT no longer waits for the next decode
                        # dispatch
                        self._mark_decode_ready(seq)
                        finals.append((seq, j))
                    else:
                        self._prefilling.append(seq)
                if finals:
                    self._start_first_emit(finals, toks)
        await asyncio.sleep(0)
        return progressed

    @property
    def phase_stats(self) -> dict:
        """Snapshot of the engine-side phase accounting (see __init__)."""
        return dict(self._phase_stats)

    def _flight_context(self) -> dict:
        """Engine snapshot embedded in every flight-recorder artifact
        (metrics + phase stats + in-flight ops) — the state the digest
        window alone cannot carry."""
        # _ops is mutated lock-free by dispatch worker threads; a busy
        # incident — exactly when triggers fire — can resize it mid-
        # iteration. Retry the copy rather than letting build_artifact
        # swallow the RuntimeError and ship an EMPTY context.
        ops = []
        for _ in range(4):
            try:
                ops = list(self._ops.values())
                break
            except RuntimeError:
                continue
        return {
            "metrics": self.metrics(),
            "phase_stats": self.phase_stats,
            "degrade": self._degrade.state(),
            "waiting": len(self.waiting),
            "inflight_ops": [
                {"op": lbl, "age_s": round(time.perf_counter() - t0, 3)}
                for lbl, t0 in ops
            ],
            # custody snapshot: the artifact for a kv_leak trigger names
            # the orphaned pages and their last transitions right here
            "kv_ledger": self.kv_ledger.snapshot(),
        }

    # ---- KV custody ledger (engine/kv_ledger.py) ----------------------

    def _kv_hold(self, page_ids: list[int], owner: str, tenant: str = "") -> None:
        if page_ids:
            self.kv_ledger.hold(page_ids, owner, tenant=tenant)

    def _kv_drop(self, page_ids: list[int], owner: str) -> None:
        if page_ids:
            self.kv_ledger.drop(page_ids, owner)

    def _run_kv_audit(self) -> None:
        """One ledger audit pass; forensics must never break serving."""
        try:
            violations = self.kv_ledger.audit()
            if self._hybrid:
                violations = violations + self.kv_ledger_win.audit()
        except Exception:
            log.debug("kv ledger audit failed", exc_info=True)
            return
        if violations and self.flight is not None:
            # ONE artifact per audit batch: the flight context already
            # carries the full ledger snapshot (all violations, trails),
            # and the cooldown makes a leak storm one dump anyway
            v = violations[0]
            owner = v.owner if v.owner and not v.owner.startswith("sys:") else None
            try:
                self.flight.trigger(f"kv_leak:{v.kind}", request_id=owner)
            except Exception:
                log.debug("kv_leak flight trigger failed", exc_info=True)

    def _on_kv_leak(self, violation) -> None:
        """Ledger hook for violations raised OUTSIDE an audit pass
        (allocator release misuse fires synchronously at the call
        site). Audit-pass violations arm the trigger in _run_kv_audit."""
        if self.flight is None:
            return
        if violation.kind not in ("double_release", "unknown_page"):
            return  # audit-raised kinds are handled by _run_kv_audit
        try:
            self.flight.trigger(f"kv_leak:{violation.kind}")
        except Exception:
            log.debug("kv_leak flight trigger failed", exc_info=True)

    def _flight_record(
        self, kind: str, wall_s: float, rows: int = 0, tokens: int = 0,
        budget: int = 0, **host,
    ) -> None:
        """Sample one step digest into the flight recorder (`host`: the
        tick's host-side columns, flight_recorder.FIELDS). Must never
        take down the dispatch it observes."""
        fr = self.flight
        if fr is None:
            return
        try:
            used = {"kv_frac_full": round(self.allocator.usage(), 4)}
            if self._hybrid:
                used["kv_frac_win"] = round(self.win_allocator.usage(), 4)
            active = sum(1 for s in self.slots if s is not None)
            if self._recurrent:
                # every sequence that holds a slot holds its state
                host["state_slots_held"] = active
            fr.record(
                kind, wall_s, rows=rows, tokens=tokens,
                budget_fill=round(tokens / budget, 4) if budget else 0.0,
                queue_depth=len(self.waiting),
                slots_active=active,
                # the kind that runs out first (a hybrid model has two)
                kv_frac=max(used.values()), **(used if self._hybrid else {}),
                degrade_mask=self._degrade.mask(),
                step=self._step_count,
                preempted=self._preemptions, **host,
            )
        except Exception:  # noqa: BLE001 — forensics must not break serving
            log.exception("flight-recorder digest failed")

    @contextlib.contextmanager
    def _dispatching(self, kind: str, t0: float, rec: dict):
        """The one recorder of a dispatch (worker thread): the xprof step
        marker and the dispatch annotation (named like the
        ``engine.steps`` span, so a capture and the ring join by name),
        `_kv_lock` under ``eng.lock``, the body, then [t0, now] booked
        where it is read: `_phase_stats`, collective bytes, the flight
        digest, the ring. `rec`: rows, tokens, phys_rows (token rows
        through the layer stack, padding included), optionally budget,
        build_s, span (more ring attributes), kv_pages_streamed /
        kv_pages_held (decode: `_kv_pages`); `_enqueue` adds starved. The
        digest's `lock_s` / `upload_s` / `enqueue_s` are this thread's
        growth of those three phases in here (`tracing.phase_table`). A
        body that raises books nothing. The
        wall is a dispatch-CALL wall (a jit call returns once the work
        is enqueued); the counts are the load-bearing part."""
        table = tracing.phase_table()  # this worker thread's
        was = [table[n][0] if n in table else 0.0 for n in _WORKER_PHASES]
        with profiler.step_annotation(self._step_count), \
                profiler.annotate(kind):
            with profiler.phase("eng.lock"):
                self._kv_lock.acquire()
            try:
                yield
            finally:
                self._kv_lock.release()
        t1 = time.perf_counter()
        lock_s, upload_s, enqueue_s = (
            table[n][0] - w if n in table else 0.0
            for n, w in zip(_WORKER_PHASES, was)
        )
        fam = "spec" if kind == "spec_verify" else kind
        rows, tokens = rec["rows"], rec["tokens"]
        with self._phase_lock:
            st = self._phase_stats
            st[f"{fam}_dispatch_s"] += t1 - t0
            if fam != "mixed":  # mixed steps are counted where they land
                st[f"{fam}_dispatches"] += 1
            if fam in ("prefill", "decode"):
                st[f"{fam}_tokens"] += tokens
            if fam == "dlm":
                st["dlm_passes"] += rec["dlm_passes"]
        self._note_collectives(fam, rec["phys_rows"], t1)
        self._flight_record(
            kind, t1 - t0, rows=rows, tokens=tokens,
            budget=rec.get("budget", 0), build_s=rec.get("build_s", 0.0),
            starved=rec.get("starved", 0),
            lock_s=lock_s, upload_s=upload_s, enqueue_s=enqueue_s,
            kv_pages_streamed=rec.get("kv_pages_streamed", 0),
            kv_pages_held=rec.get("kv_pages_held", 0),
            **{k: rec[k] for k in (
                "kv_pages_held_full", "kv_win_pages_held",
                "kv_win_pages_released", "kv_win_items",
                "state_rows_advanced", "dlm_passes", "dlm_work_items",
            ) if k in rec},
        )
        if tracing.enabled():
            tracing.complete(
                kind, t0, t1, cat="step", track="engine.steps",
                rows=rows, tokens=tokens, **rec.get("span", {}),
            )

    def _enqueue(self, rec: dict, fn, *args, counts: bool = False,
                 dlm: bool = False, **static):
        """The dispatch's ONE launch (under `_kv_lock`) as
        ``eng.enqueue``: the step program `fn` takes the params, the
        donated kv and state (with the counts on the penalty path), then
        `args`, and returns (outputs, kv, state); kv and state are kept,
        the outputs returned. Notes in `rec` whether the device had
        drained by then (`starved`: the newest dispatch's first output
        was ready)."""
        last = self._last_out
        try:
            rec["starved"] = int(last is None or last.is_ready())
        except Exception:  # noqa: BLE001 — a deleted buffer at shutdown
            rec["starved"] = 0
        state = self._take_state(counts, dlm)
        with profiler.phase("eng.enqueue"):
            out, self.kv, state = fn(
                self.params, self.kv, state, *args, **static
            )
        self._put_state(state)
        self._last_out = jax.tree.leaves(out)[0]
        return out

    def _record_sync(
        self, family: str, rows: int, t0: float, t1: float,
        overlapped: bool = False, bld_t0: Optional[float] = None,
    ) -> None:
        """The one recorder of a result fetch [t0, t1] (loop thread,
        before its tokens land): `_phase_stats`, the flight digest
        (`sync` / `overlap` row; `_land` fills its `emit_s`), the ring."""
        with self._phase_lock:
            if overlapped:
                # ANOTHER dispatch was already queued on device: a wait
                # the step pipeline hid. It lands in the overlap counter
                # INSTEAD of the family's `*_sync_s` (the bench
                # pipeline_ab fraction and the engine.overlap track rely
                # on this split)
                self._phase_stats["pipeline_overlap_s"] += t1 - t0
                self._phase_stats["pipeline_overlapped"] += 1
            else:
                # families stay separable: a spec verify step's fetch
                # wall belongs with its dispatch wall, not in the
                # scanned-decode sync ratio
                self._phase_stats[f"{family}_sync_s"] += t1 - t0
            if bld_t0 is not None:
                # the whole dispatch+fetch wall is time the decode rows
                # did NOT spend parked behind a separate prefill dispatch
                self._phase_stats["mixed_decode_stall_saved_s"] += t1 - bld_t0
        self._flight_record(
            "overlap" if overlapped else "sync", t1 - t0, rows=rows,
        )
        if tracing.enabled():
            tracing.complete(
                ("spec_verify" if family == "spec" else family) + ".sync",
                t0, t1, cat="step",
                # overlapped syncs land on their own track so the
                # timeline shows which fetch walls the pipeline hid
                track="engine.overlap" if overlapped else "engine.sync",
                rows=rows,
            )

    def _any_mid_decode(self) -> bool:
        """Is decode actually RUNNING? True when a decode dispatch with
        at least one LIVE row is in flight, or — covering the brief
        sync-to-build gap between dispatches — when a stream has emitted
        past its first token.

        generated == 1 wave members (first token from the prefill-group
        fetch, no decode dispatched yet) deliberately do NOT count on
        their own: treating them as mid-decode would suppress the
        sibling prefill groups' early first-token emits (and lift the
        decode-ready gate of `_build_decode`, which still sees a pure
        admission wave). A generated == 1 stream whose decode IS under way is
        caught by the in-flight test instead — the gap the bare
        `generated > 1` predicate used to mislabel idle.

        The in-flight test checks LIVENESS, not mere existence: with the
        step pipeline on, the dispatch launched speculatively behind a
        wave's final sync outlives every stream it carried — a dead
        rectangle still draining through the device. Counting it as
        mid-decode suppressed the NEXT admission's early first emits,
        parking its first tokens until a full decode dispatch + sync.
        Cold serves amortize that shadow over a long prefill; a
        prefix-hit's short tail lives entirely inside it — measured on
        the CPU tiny rig as a warm TTFT no better than a cold one.
        Dead dispatches must not gate emission."""
        if self._inflight_live():
            return True
        return any(
            s is not None and not s.prefilling and s.generated > 1
            for s in self.slots
        )

    def _inflight_live(self) -> bool:
        """Does the in-flight dispatch carry any row whose sequence
        still occupies its slot? False for the pipelined overshoot
        dispatch left behind after its streams all finished."""
        d = self._inflight
        if d is None:
            return False
        if d.mixed:
            return any(
                self.slots[slot] is seq
                for _kind, slot, seq, _chunk in d.bld["entries"]
            )
        return any(self.slots[i] is s for i, s in d.snapshot)

    def _stamp_first_meta(self, seq: Sequence) -> None:
        """Attach the engine-side latency split to the first frame's
        meta: queue_wait (submit->slot), engine_ttft (submit->the prefill
        dispatch that sampled the first token returning). Client TTFT
        minus engine_ttft is the fetch/delivery transport share."""
        if seq.first_meta is None or not seq.t_submit:
            return
        done = seq.t_first_dispatched or time.perf_counter()
        seq.first_meta.setdefault(
            "engine_ttft_s", round(done - seq.t_submit, 4)
        )
        if seq.t_admit:
            seq.first_meta.setdefault(
                "queue_wait_s", round(seq.t_admit - seq.t_submit, 4)
            )

    def _mark_decode_ready(
        self, seq: Sequence, tok: Optional[int] = None
    ) -> None:
        """`tok` None: the prefill step that just ran sampled the first
        token and wrote it into the device carry at the slot; the host
        has not seen it (`carry_pending`). An int: a disagg inject's."""
        seq.prefilling = False
        seq.device_pos = seq.num_computed
        # the first token supersedes what the host knows of the carry
        # row (a previous tenant's token) — the step pipeline must not
        # read it until a decode dispatch re-arms it
        self._carry_ok[seq.slot] = False
        seq.carry_pending = tok is None
        self._overrides.pop(seq.slot, None)
        if tok is not None:
            # sampled remotely, already on the host: it enters the next
            # decode program as an override — emit immediately, no fetch
            self._overrides[seq.slot] = int(tok)
            seq.num_computed = seq.total_tokens
            self._t_fetched = time.perf_counter()
            self._emit(seq, [int(tok)], first=True)

    def _dlm_ready(self, seq: Sequence) -> None:
        """A block-diffusion row whose whole blocks are encoded joins the
        block step: its first block opens at `prefill_end` with the rest of
        its tokens as the given, never-masked head (the prompt's tail; after
        a preemption also what the client already has of the block that was
        open) and masks behind it. The next dispatch arms the device's
        carry from this (`_build_dlm`); no token was sampled."""
        seq.prefilling = False
        seq.carry_pending = False
        seq.num_computed = seq.device_pos = seq.prefill_end
        seq.dlm_open = seq.dlm_left = (
            seq.dlm_block - (seq.total_tokens - seq.prefill_end))
        seq.dlm_arm = True

    def _start_first_emit(self, finals, S) -> None:
        """One async host fetch per prefill GROUP that emits the group's
        first tokens as soon as the copy lands (one device-to-host
        copy), instead
        of parking them until the next decode dispatch syncs. That next
        dispatch still consumes the on-device carry; its sync awaits the
        task (ordering) and skips row 0 (carry_pending already False).

        Only while NO decode stream is running (the admission-wave case
        this exists for): during steady decode the next sync emits within
        one dispatch (~decode_steps * ITL) anyway, and an extra fetch per
        trickling arrival is one more host sync queued in front of every
        subsequent decode sync."""
        if self._any_mid_decode():
            return
        task = asyncio.create_task(self._emit_first_group(finals, S))
        for seq, _ in finals:
            seq.first_task = task

    async def _emit_first_group(self, finals, S) -> None:
        try:
            with profiler.phase("eng.fetch"):
                toks, lps, tid, tlp = await asyncio.to_thread(
                    lambda: tuple(
                        np.asarray(a) if a is not None else None for a in S
                    )
                )
        except Exception:
            log.exception("first-token fetch failed; decode sync will emit")
            return
        self._t_fetched = time.perf_counter()
        with profiler.phase("eng.emit"):
            self._emit_first_tokens(finals, toks, lps, tid, tlp)

    def _emit_first_tokens(self, finals, toks, lps, tid, tlp) -> None:
        me = asyncio.current_task()
        for seq, row in finals:
            if (
                seq.first_task is not me  # preempt + re-prefill swapped in
                # a NEWER fetch: this one's token is from the old dispatch
                or not seq.carry_pending
                or seq.slot < 0
                or self.slots[seq.slot] is not seq
            ):
                continue  # preempted/finished meanwhile; normal paths own it
            seq.carry_pending = False
            seq.num_computed = seq.total_tokens
            k = seq.top_logprobs if tid is not None else 0
            self._emit(
                seq, [int(toks[row])],
                [float(lps[row])] if lps is not None else None,
                ([tid[row, :k].tolist()], [tlp[row, :k].tolist()])
                if k else None,
                first=True,
            )

    def _prefill_group_dispatch(self, seqs: list[Sequence], bucket: int):
        """Dispatch one chunk for each sequence in ONE [n, bucket] model
        step; returns the sampled-token vector [n] (valid at rows whose
        chunk was final). n is padded to a power of two so the set of
        compiled graphs stays bounded (padding rows write the trash
        page)."""
        faults.fire("engine.prefill")
        t_build0 = time.perf_counter()
        with profiler.phase("eng.prefill.build"):
            n = 1 << (len(seqs) - 1).bit_length()
            smat = np.zeros((n, self._smat_width), np.int32)
            tok_arr = np.zeros((n, bucket), np.int32)
            pos_arr = np.zeros((n, bucket), np.int32)
            wslots = np.zeros((n, bucket), np.int32)
            # the per-row inputs, two fused uploads (`_model_step`):
            # [last_idx, top_k, slot, final chunk, seed] and [temp,
            # top_p, freq_pen, pres_pen, rep_pen]; padding rows have no
            # slot and sample greedily
            rows_i = np.zeros((n, 5), np.int32)
            rows_i[:, 2] = rows_i[:, 4] = -1
            rows_f = np.zeros((n, 5), np.float32)
            rows_f[:, 1] = rows_f[:, 4] = 1.0
            # penalties/seeds need a slot-keyed count row; prefill_only seqs
            # (slot -1, disagg) sample their first token on the plain path
            use_ext = any(
                (s.has_penalties or s.seed >= 0) and s.slot >= 0 for s in seqs
            )
            ps = self.page_size
            ppc = -(-bucket // ps)  # page blocks per chunk (pallas write path)
            wtables = np.zeros((n, ppc), np.int32)
            # multimodal: a separate compiled family only when THIS chunk of
            # some sequence overlaps its embed span — the common path (and
            # later text-only chunks of an image prompt) pays nothing
            def _chunk_overlaps(s) -> bool:
                if s.prompt_embeds is None:
                    return False
                c0 = s.num_computed
                c1 = c0 + min(s.total_tokens - c0, bucket)
                return c0 < s.embeds_offset + len(s.prompt_embeds) and s.embeds_offset < c1

            has_embeds = any(_chunk_overlaps(s) for s in seqs)
            emb = emb_mask = None
            if has_embeds:
                d_model = self.model_cfg.hidden_size
                emb = np.zeros(
                    (n, bucket, d_model), self._dtype.dtype
                )  # model dtype: forward casts anyway, halve the H2D bytes
                emb_mask = np.zeros((n, bucket), bool)
            # attention table width: pages actually attended this chunk,
            # bucketed to a power of two so compile families stay bounded —
            # full width would DMA every (mostly trash) page per query tile
            w_need = max(
                -(-(seq.num_computed + min(seq.prefill_end - seq.num_computed,
                                           bucket)) // ps)
                for seq in seqs
            )
            w_b = min(
                1 << (w_need - 1).bit_length(), self.config.max_pages_per_seq
            )
            btables = np.zeros((n, w_b), np.int32)
            if self._hybrid:
                # the window kind's copies of the four per-kind inputs
                win = [np.zeros_like(a) for a in
                       (smat, wslots, wtables, btables)]
            for j, seq in enumerate(seqs):
                tokens = seq.tokens
                start = seq.num_computed
                chunk = min(seq.prefill_end - start, bucket)
                smat[j] = self._slot_matrix_row(seq)
                tok_arr[j, :chunk] = tokens[start : start + chunk]
                idx = np.arange(start, start + chunk)
                pos_arr[j, :chunk] = idx
                pages = np.asarray(seq.page_ids, np.int32)
                wslots[j, :chunk] = pages[idx // ps] * ps + idx % ps
                # chunk starts are page-aligned (prefill_chunk % ps == 0,
                # cache hits/preemption resume at page boundaries), so chunk
                # page p covers positions start + [p*ps, (p+1)*ps)
                n_pages_used = -(-chunk // ps)
                wtables[j, :n_pages_used] = pages[start // ps : start // ps + n_pages_used]
                npg = min(len(pages), w_b)
                btables[j, :npg] = pages[:npg]
                if self._hybrid:
                    wp = np.asarray(seq.win_page_ids, np.int32)
                    win[0][j] = self._slot_matrix_row(seq, wp)
                    win[1][j, :chunk] = wp[idx // ps] * ps + idx % ps
                    win[2][j, :n_pages_used] = wp[
                        start // ps : start // ps + n_pages_used
                    ]
                    nwp = min(len(wp), w_b)
                    win[3][j, :nwp] = wp[:nwp]
                if has_embeds and seq.prompt_embeds is not None:
                    # overlap of [start, start+chunk) with the embed span
                    e0 = seq.embeds_offset
                    e1 = e0 + len(seq.prompt_embeds)
                    lo, hi = max(start, e0), min(start + chunk, e1)
                    if lo < hi:
                        emb[j, lo - start:hi - start] = seq.prompt_embeds[
                            lo - e0:hi - e0
                        ]
                        emb_mask[j, lo - start:hi - start] = True
                if self._recurrent and start == 0:
                    self._state_resets += 1  # position 0: a zero state
                rows_i[j] = (
                    chunk - 1, seq.top_k, seq.slot,
                    # a block-diffusion prompt samples no first token: no
                    # row of its prefill is final
                    seq.num_computed + chunk >= seq.total_tokens
                    and not self._dlm, seq.seed,
                )
                rows_f[j] = (
                    seq.temperature, seq.top_p, seq.frequency_penalty,
                    seq.presence_penalty, seq.repetition_penalty,
                )
        t_dispatch0 = time.perf_counter()  # dispatch section only: the
        # host-side input build above is the digest's build_s
        n_tok = int(
            sum(min(s.prefill_end - s.num_computed, bucket) for s in seqs)
        )
        rec = dict(
            rows=len(seqs), tokens=n_tok, phys_rows=len(seqs) * bucket,
            build_s=t_dispatch0 - t_build0, span={"bucket": bucket},
        )
        with self._dispatching("prefill", t_dispatch0, rec):
            with profiler.phase("eng.upload"):
                # sp cached-prefix continuation: the static value is a
                # power-of-two PAGE bucket over the group's longest cached
                # prefix (0 = no cache; bounds both the compiled-family count
                # and the per-layer prefix gather width)
                spc = 0
                if self._sp:
                    max_cached = max(
                        (s.num_cached for s in seqs), default=0
                    ) // self.page_size
                    if max_cached:
                        spc = 1 << (max_cached - 1).bit_length()
                        spc = min(spc, self.config.max_pages_per_seq)
                kinds = [(smat, wslots, wtables, btables)]
                if self._hybrid:
                    kinds.append(win)

                def per_kind(i, flat=False, on=True):
                    # input `i` of every kind of page: one array, or a
                    # hybrid model's (full, window)
                    if not on:
                        return None
                    up = [jnp.asarray(k[i].reshape(-1) if flat else k[i])
                          for k in kinds]
                    return tuple(up) if self._hybrid else up[0]

                args = (
                    jnp.asarray(tok_arr), jnp.asarray(pos_arr),
                    per_kind(1, flat=True),
                    per_kind(0), jnp.asarray(rows_i),
                    jnp.asarray(rows_f),
                    per_kind(2, flat=True, on=self._attn_pallas),
                    per_kind(3, on=self._attn_pallas),
                    jnp.asarray(emb) if has_embeds else None,
                    jnp.asarray(emb_mask) if has_embeds else None,
                    bool((rows_f[:, 0] <= 0.0).all()),
                    any(s.want_logprobs for s in seqs),
                    any(s.top_logprobs > 0 for s in seqs),
                )
            S = self._enqueue(
                rec, self._step_fn, *args, counts=use_ext, sp_cached=spc
            )
            if ((self._hybrid or self._recurrent) and len(seqs) == 1
                    and not has_embeds and not args[11]  # log-probabilities
                    and w_b * ps > self.config.prefill_chunk):
                self._load_tail_groups(bucket, w_b, args, use_ext, spc)
        now = time.perf_counter()
        for seq in seqs:
            if seq.num_computed + min(
                seq.prefill_end - seq.num_computed, bucket
            ) >= seq.prefill_end:
                seq.t_first_dispatched = now
                if tracing.enabled():
                    tracing.instant(
                        "seq.first_dispatch", cat="lifecycle",
                        req=seq.ctx.id, ts=now,
                    )
                # restore-gate calibration: the prefill rate a request
                # actually experiences (admission -> prompt computed,
                # batching included) is the recompute side of the
                # restore-vs-recompute comparison. Only LOADED samples
                # count: on an idle engine the async dispatch returns in
                # ~ms and the apparent rate is inflated ~100x, which
                # would bias the gate into declining beneficial restores
                fresh_toks = seq.total_tokens - seq.num_cached
                span = now - seq.t_admit
                if seq.t_admit and fresh_toks >= self.page_size and span > 0.05:
                    tps = fresh_toks / span
                    with self._phase_lock:
                        self._ema_prefill_tps = (
                            tps if self._ema_prefill_tps is None
                            else 0.8 * self._ema_prefill_tps + 0.2 * tps
                        )
        # (toks, lps[, top_ids, top_lps]) -> uniform 4-tuple; callers run
        # _note_prefilled on the EVENT-LOOP thread — this method may run
        # in a worker thread, and allocator bookkeeping must not race the
        # loop's emission/finish callbacks
        return S if len(S) == 4 else (S[0], S[1], None, None)

    def _load_tail_groups(self, bucket: int, w_b: int, args: tuple,
                          counts: bool, spc: int) -> None:
        """The [2, bucket], [4, bucket], ... siblings of a one-row tail
        program, loaded with it (worker thread, under `_kv_lock`). The last
        chunk of a prompt longer than `prefill_chunk` attends more pages
        than a one-chunk prompt can, and under load such tails share a tick
        with one another and with fresh prompts of their bucket: a group no
        request sent alone reaches, and a program first met under load
        holds every stream while it loads. So the first one-row dispatch of
        a (bucket, page width, statics) runs each wider program the group
        budget and the slots allow once, on rows of padding (no slot, the
        trash page). Not for a request that asks for log-probabilities (the
        caller's condition; docs/kv_cache.md "Tail groups")."""
        key = (bucket, w_b, *args[10:], counts, spc)
        if key in self._tail_groups_loaded:
            return
        self._tail_groups_loaded.add(key)
        with profiler.phase("eng.load_tail_groups"):
            n = 2
            while (n * bucket <= self.config.prefill_group_tokens
                   and n < 2 * self.config.max_batch_size):
                pad = list(jax.tree.map(
                    lambda a: jnp.zeros(
                        (n * a.shape[0], *a.shape[1:]), a.dtype),
                    args[:10],
                ))
                rows_i = np.zeros((n, 5), np.int32)
                rows_i[:, 2] = rows_i[:, 4] = -1
                rows_f = np.zeros((n, 5), np.float32)
                rows_f[:, 1] = rows_f[:, 4] = 1.0
                pad[4], pad[5] = jnp.asarray(rows_i), jnp.asarray(rows_f)
                self._enqueue(
                    {}, self._step_fn, *pad, *args[10:], counts=counts,
                    sp_cached=spc,
                )
                n *= 2

    def _note_prefilled(self, seqs: list[Sequence], bucket: int) -> None:
        """Post-dispatch bookkeeping (loop thread only): advance computed
        counts and register full pages in the prefix cache."""
        for seq in seqs:
            chunk = min(seq.prefill_end - seq.num_computed, bucket)
            seq.num_computed += chunk
            seq.prefill_chunks += 1
            self._register_full_pages(seq)
            if self._hybrid and self._release_window_pages(seq):
                self._mark_slot_state(seq)

    def _prefill_chunk_dispatch(self, seq: Sequence):
        """Single-sequence chunk dispatch (disagg prefill_only path;
        worker thread). Returns (token vector, bucket) — the CALLER
        runs `_note_prefilled` on the event-loop thread (the allocator
        has no lock; bookkeeping must not race loop-side callbacks)."""
        bucket = self._bucket_for(
            min(seq.total_tokens - seq.num_computed, self.config.prefill_chunk)
        )
        toks, _lps, _tid, _tlp = self._prefill_group_dispatch([seq], bucket)
        return toks, bucket

    async def _prefill_forward(self, seq: Sequence) -> int:
        """Blocking chunked prefill (disagg prefill_only path): writes KV,
        returns the token sampled at the final position."""
        while True:
            # worker thread: the _kv_lock acquire can wait out a whole
            # in-flight decode dispatch — never block the event loop on
            # it. Bookkeeping stays HERE (event-loop thread).
            with profiler.phase("eng.join"):
                tok, bucket = await asyncio.to_thread(
                    self._prefill_chunk_dispatch, seq
                )
            self._note_prefilled([seq], bucket)
            if seq.num_computed >= seq.total_tokens:
                break
        out = await asyncio.to_thread(np.asarray, tok)
        return int(out.ravel()[0])

    def _inject_chunk(self, seq: Sequence) -> Optional[int]:
        """Scatter one chunk of remotely-computed KV into the sequence's
        pages (disagg decode side); returns the remotely-sampled first
        token when injection is complete. Payload dtype is converted to
        this engine's KV dtype when the two sides disagree (int8 wire ->
        bf16 pool or vice versa)."""
        first_token, k_arr, v_arr, ks_arr, vs_arr = seq.preloaded
        t = seq.total_tokens
        start = seq.num_computed  # locally-cached prefix needs no injection
        if start < t:
            chunk = min(t - start, self.config.prefill_chunk)
            bucket = self._bucket_for(chunk)
            slots = np.zeros(bucket, np.int32)  # pad -> trash slot 0
            for i in range(chunk):
                slots[i] = self._write_slot(seq, start + i)
            nk = np.zeros((k_arr.shape[0], bucket, *k_arr.shape[2:]), k_arr.dtype)
            nv = np.zeros_like(nk)
            nk[:, :chunk] = k_arr[:, start : start + chunk]
            nv[:, :chunk] = v_arr[:, start : start + chunk]
            nks = nvs = None
            if ks_arr is not None:
                sshape = (ks_arr.shape[0], bucket, ks_arr.shape[2])
                nks = np.ones(sshape, np.float32)
                nvs = np.ones(sshape, np.float32)
                nks[:, :chunk] = ks_arr[:, start : start + chunk]
                nvs[:, :chunk] = vs_arr[:, start : start + chunk]
            with self._kv_lock:
                nkj, nvj, nksj, nvsj = self._convert_wire_kv(nk, nv, nks, nvs)
                self.kv = self._inject_fn(
                    self.kv, jnp.asarray(slots), nkj, nvj, nksj, nvsj
                )
            seq.num_computed += chunk
            self._register_full_pages(seq)
        if seq.num_computed >= t:
            seq.preloaded = None
            seq.first_meta = {**(seq.first_meta or {}), "remote_prefill": True}
            return int(first_token)
        return None

    # ---- mixed prefill+decode steps (stall-free batching) -------------

    def _mixed_unsupported_reason(self) -> Optional[str]:
        """None when mixed steps can run on this engine, else the reason
        — init raises it for an explicit misconfig, the runtime toggle
        logs it once and keeps the normal paths. spec_decode COMPOSES
        (spec-eligible decode rows ride mixed steps as ragged q_len=1+k
        verify rows — see _build_mixed); it is no longer an exclusion."""
        kind = self._cache_kind()
        if kind:
            return CACHE_KIND_REFUSALS[kind]["mixed"]
        if self._sp:
            return (
                "mixed_batching unsupported with sp>1: ring attention "
                "prefills whole prompts in one pass — there is no chunk "
                "for decode rows to ride"
            )
        if self.config.mixed_step_tokens < 1:
            return "mixed_step_tokens must be >= 1"
        return None

    def _mixed_eligible_decode(self) -> Optional[list]:
        """Decode-ready rows a mixed step can carry (with the
        cancellation sweep _maybe_dispatch_decode would have run), or
        None when the whole batch must take the normal paths this tick:
        penalties / per-request seeds / logprobs rows need the extended
        sampler (same hot-path gate as spec decode), and a pending
        device-side carry with no fetch in flight can only be emitted by
        a normal decode sync."""
        ready = self._decode_ready_rows()
        rows = []
        for i, s in ready:
            if s.needs_ext_sampling:
                return None
            if s.carry_pending:
                if s.first_task is not None and not s.first_task.done():
                    # first token lands shortly (group fetch in flight);
                    # the row joins the next mixed step
                    continue
                return None
            rows.append((i, s))
        return rows

    def _select_mixed_prefill(self, leftover: int) -> list:
        """Strict FIFO prefix of the prefill queue fitting `leftover`
        budget tokens: each pick is (seq, chunk); a NON-final chunk
        rounds DOWN to a page multiple (the following chunk must start
        page-aligned — the prefill write paths' contract). Scanning
        stops at the first sequence that cannot join (budget-starved,
        disagg KV injection, multimodal embeds): skipping it would let
        later arrivals jump the FIFO order and starve it for as long as
        decode traffic keeps mixed steps running."""
        picks = []
        for seq in self._prefilling:
            if leftover < 1:
                break
            if seq.ctx.is_stopped():
                break  # the normal tick's sweep owns cancellation
            if seq.preloaded is not None or seq.prompt_embeds is not None:
                break
            if seq.needs_ext_sampling:
                # a FINAL chunk samples its first token in-step on the
                # plain path — penalties/seeded/logprobs requests must
                # prefill through the normal ext dispatch instead (same
                # gate as the decode side; strict FIFO, so stop here)
                break
            need = seq.total_tokens - seq.num_computed
            chunk = min(need, self.config.prefill_chunk, leftover)
            if chunk < need:
                chunk -= chunk % self.page_size
            if chunk < 1:
                break
            picks.append((seq, chunk))
            leftover -= chunk
        return picks

    async def _mixed_tick(self):
        """One stall-free MIXED step when decode-ready rows and pending
        prefill chunks coexist: both planes advance in a single
        token-budgeted dispatch, so an admission wave can never park the
        running decode streams for longer than one budgeted step
        (Sarathi-Serve's stall-free scheduling; the motivation for the
        whole family is that prefill and decode serialize on the donated
        KV cache regardless of how the host interleaves dispatches).

        With `EngineConfig.step_pipeline` (default) the step launches
        BEHIND whatever dispatch is already in flight: rows that
        advanced deterministically in that dispatch (a plain decode
        scan, or a previous mixed step's q_len=1 rows) join at q_len=1
        reading their input token from the device carry vector —
        `_carry_ok` is the license — and spec-eligible rows among them
        SHED their drafts (n-gram drafting needs synced host history;
        they still advance, drafts resume once the sync catches up,
        `mixed_spec_shed`). Rows whose in-flight advance is
        data-dependent (verify windows) sit the step out. The old
        dispatch is synced while the new one executes, and the new step
        stays in flight ("pipelined" return) for the next tick to land.

        Returns True (a serialized step ran and synced), "pipelined" (a
        step was dispatched and left in flight; the old dispatch was
        synced here), "hold" (serialized engines only: worthwhile, but
        the in-flight dispatch must sync first — host-built windows
        need current token history), or None (not applicable: normal
        paths run)."""
        if (
            self._closed or self._mixed_disabled
            or self._degrade.disabled("mixed") or not self._prefilling
        ):
            return None
        why = self._mixed_unsupported_reason()
        if why is not None:
            if not self._mixed_warned:
                self._mixed_warned = True
                log.warning("mixed_batching disabled: %s", why)
            return None
        pipeline = self._pipe_on()
        # classify the in-flight dispatch's rows: deterministic advances
        # can pipeline through the device carry, data-dependent ones
        # (verify windows) block until their sync
        stale_det: dict[int, Sequence] = {}
        blocked: set[int] = set()
        infl = self._inflight
        if infl is not None and pipeline:
            if infl.spec:
                blocked = {i for i, _ in infl.snapshot}
            elif infl.mixed:
                for kind, slot, seq, chunk in infl.bld["entries"]:
                    if kind != "dec":
                        continue
                    if chunk == 1 and self._carry_ok[slot]:
                        stale_det[slot] = seq
                    else:
                        blocked.add(slot)
            else:
                # plain decode scan: every row advances exactly
                # decode_steps and the scan's last sample is already in
                # the device carry vector
                for i, s in infl.snapshot:
                    if self._carry_ok[i]:
                        stale_det[i] = s
                    else:
                        blocked.add(i)
        rows = self._mixed_eligible_decode()
        if rows:
            rows = [(i, s) for i, s in rows if i not in blocked]
        if not rows:
            return None
        carry_rows = {i for i, s in rows if stale_det.get(i) is s}
        if (
            carry_rows and self._spec_on()
            and any(
                s.spec is not None and s.spec.gate_open()
                for i, s in rows if i in carry_rows
            )
        ):
            # a carry row whose acceptance gate is OPEN would draft if
            # its host history were current — and an accepted draft is
            # worth a whole extra token per step, which beats hiding one
            # host fetch wall. Sync the in-flight dispatch NOW (the same
            # trade the standalone path makes via "sync_first") and
            # rebuild from fresh history; gated-off rows keep the
            # zero-stall overlap and shed instead. Without this, steady
            # pipelined flow NEVER syncs between mixed steps and the
            # spec x mixed win silently disappears.
            old, self._inflight = self._inflight, None
            if old is not None:
                await self._sync_dispatch(old)
            rows = self._mixed_eligible_decode()
            if not rows:
                return True  # the sync itself made progress
            carry_rows = set()
        # spec x mixed composition: propose n-gram drafts for the decode
        # rows up front — each spec row costs 1 + k budget tokens, so
        # drafts trade off transparently against prefill chunk size. A
        # discarded build never strands a probe (only observe() re-arms
        # the proposer's countdown). Carry rows never draft: their host
        # history is stale until the in-flight sync lands, so the
        # proposer would continue the wrong suffix — shed, don't stall.
        drafts: dict[int, list[int]] = {}
        shed = 0
        if self._spec_on():
            k_cap = min(self.config.spec_k_max, self.config.prefill_chunk - 1)
            for i, seq in rows:
                if i in carry_rows:
                    if seq.spec is not None:
                        shed += 1
                        # tick the probe countdown even though stale
                        # history forbids drafting: a shed row whose
                        # gate is closed would otherwise NEVER decrement
                        # it under sustained pipelined flow (carry rows
                        # skip maybe_draft) and stay gated off until the
                        # flow breaks — when the countdown expires,
                        # gate_open flips and the sync-first escape
                        # above re-drafts from fresh history
                        seq.spec.shed_tick()
                    continue
                remaining = seq.max_new_tokens - seq.generated
                room = self.config.max_model_len - 1 - seq.device_pos
                k_i = min(k_cap, remaining - 1, room)
                d = seq.spec.maybe_draft(k_i) if seq.spec is not None else []
                if d:
                    drafts[i] = d
        budget = self.config.mixed_step_tokens
        dec_cost = sum(1 + len(drafts.get(i, ())) for i, _ in rows)

        def shed_drafts_to(room: int) -> int:
            # drafts must never abort the stall-free step itself — a
            # decode row is always valid at q_len=1, so shed drafts
            # (arbitrary rows) until the budget fits both planes again;
            # discarded drafts never strand a probe (only observe()
            # re-arms the proposer's countdown)
            cost = dec_cost
            while cost > room and drafts:
                _, d = drafts.popitem()
                cost -= len(d)
            return cost

        # every decode row joins (1 + k budget tokens each), prefill
        # shrinks into what is left
        dec_cost = shed_drafts_to(budget - 1)
        leftover = budget - dec_cost
        if leftover < 1:
            return None  # budget cannot fit both planes
        picks = self._select_mixed_prefill(leftover)
        if not picks:
            return None
        if self._inflight is not None and not pipeline:
            # serialized baseline: host-built windows need synced token
            # history — park both planes this tick (the stall the step
            # pipeline exists to remove)
            with self._phase_lock:
                self._phase_stats["mixed_holds"] += 1
            return "hold"
        # grow decode rows' pages through the positions this step writes
        # ([device_pos, device_pos + drafts]); growth may preempt
        # (possibly a participant) — refilter both sides against the
        # post-growth slot state
        max_pos = self.config.max_model_len - 1
        for i, seq in rows:
            if seq.slot < 0 or self.slots[seq.slot] is not seq:
                continue
            if not self._ensure_pages_through(
                seq,
                min(seq.device_pos + len(drafts.get(i, ())), max_pos),
            ):
                return None  # growth preempted its own row; retry next tick
        rows = [
            (i, s) for i, s in rows
            if self.slots[i] is s and not s.prefilling
        ]
        picks = [
            (s, c) for s, c in picks
            if s.slot >= 0 and self.slots[s.slot] is s
        ]
        if not rows or not picks:
            return None
        with profiler.phase("eng.mixed.build"):
            bld = self._build_mixed(
                rows, picks, drafts, carry_rows=carry_rows,
                pipelined=pipeline,
            )
        bld["n_shed"] = shed
        # the picked chunks leave the prefill queue while the step is in
        # flight (a pipelined step may still be unsynced when the next
        # prefill tick runs — it must not re-dispatch the same chunk);
        # the sync re-appends non-final chunks, the failure path restores
        for seq, _ in picks:
            self._prefilling.remove(seq)
        if pipeline:
            task = asyncio.create_task(
                asyncio.to_thread(self._run_mixed_dispatch, bld)
            )
            old, self._inflight = self._inflight, None
            if old is not None:
                # the old dispatch's fetch overlaps the mixed step just
                # queued behind it — the zero-stall handoff
                await self._sync_dispatch(old, overlapped=True)
            try:
                with profiler.phase("eng.join"):
                    S = await task
            except Exception:
                self._mixed_dispatch_failed(bld)
                return None
            self._inflight = _Dispatch(S, [], 1, mixed=True, bld=bld)
            return "pipelined"
        try:
            with profiler.phase("eng.join"):
                S = await asyncio.to_thread(self._run_mixed_dispatch, bld)
            d = _Dispatch(S, [], 1, mixed=True, bld=bld)
            fetched = await self._fetch(d)
        except Exception:
            self._mixed_dispatch_failed(bld)
            return None
        self._land(d, *fetched)
        return True

    def _mixed_dispatch_failed(self, bld: dict) -> None:
        """Contain a failed mixed dispatch like _prefill_tick contains
        prefill failures: nothing landed host-side except the build's
        own bookkeeping, so un-advance pipelined q_len=1 rows, re-arm
        every decode row's carry override from host truth (the device
        carry vector may predate earlier steps — in the pipelined case
        the previous dispatch was already synced before the failure
        surfaced, so `last_token` IS current), restore the prefill picks
        in FIFO order, then disable mixed steps on this engine —
        retrying a failing dispatch family every tick would wedge the
        loop instead of degrading to the contained normal paths."""
        log.exception(
            "mixed step of %d rows failed; disabling mixed batching "
            "(normal prefill/decode paths take over)", len(bld["entries"])
        )
        pf_restore = []
        for kind, slot, seq, chunk in bld["entries"]:
            if kind == "dec":
                if slot >= 0 and self.slots[slot] is seq:
                    if bld["pipelined"] and chunk == 1:
                        seq.device_pos -= 1
                    self._overrides[slot] = int(seq.last_token)
                    self._carry_ok[slot] = False
            elif (
                seq.slot >= 0 and self.slots[seq.slot] is seq
                and seq not in self._prefilling
            ):
                pf_restore.append(seq)
        for seq in reversed(pf_restore):
            self._prefilling.appendleft(seq)
        self._mixed_disabled = True
        # mirror into the degrade ladder (permanent: a FAILED dispatch
        # family must not re-probe — retrying it every tick would wedge
        # the loop; contrast the watchdog's transient stall trips)
        self._degrade.trip("mixed", "mixed dispatch failed", permanent=True)
        with self._phase_lock:
            self._phase_stats["mixed_disabled"] = 1

    def _build_mixed(self, rows: list, picks: list,
                     drafts: Optional[dict] = None,
                     carry_rows: frozenset = frozenset(),
                     pipelined: bool = False) -> dict:
        """Host-side input build for one mixed step: decode rows first
        (q_len=1, their host-known carry token — or a ragged 1+k verify
        window [carry, d_1..d_k] when spec composes), then one chunk per
        prefill pick. Row count pads to a power of two and T to the
        chunk's prefill bucket, so the compiled families stay the
        [pow2, bucket] grid group prefill already uses (the verify
        window k_max+1 never exceeds the smallest bucket in practice;
        t_b covers it explicitly regardless).

        Step-pipeline contract: `carry_rows` slots read their q_len=1
        input from the device carry in-jit (their host token is a stale
        placeholder here); when `pipelined`, every q_len=1 decode row's
        `device_pos` advances NOW — deterministically, exactly like the
        decode scan's build — so the NEXT build can launch behind this
        still-unsynced step. Sampling params and block tables are the
        host mirrors' rows of each row's slot, snapshot here (`w_b` is
        the static pallas attended-page bucket; 0 on gather engines,
        which expand the full slot matrix in-jit)."""
        ps = self.page_size
        use_spec = bool(drafts)
        k_max = self.config.spec_k_max if use_spec else 0
        max_len = self.config.max_model_len
        n_rows = len(rows) + len(picks)
        n = 1 << (n_rows - 1).bit_length()
        t_b = self._bucket_for(
            max(max(c for _, c in picks), k_max + 1)
        )
        t0 = time.perf_counter()
        hot = np.zeros((3, n, t_b), np.int32)  # [tokens, positions, wslots]
        tok_arr, pos_arr, wslots = hot[0], hot[1], hot[2]
        # [last_idx, slot_row, carry_mask, dec_mask] per row — the head
        # of the second fused upload (`rows_i`, completed below)
        meta = np.zeros((n, 4), np.int32)
        all_greedy = True
        draft_arr = np.zeros((n, k_max), np.int32) if use_spec else None
        dlen_arr = np.zeros(n, np.int32) if use_spec else None
        pos0_arr = np.zeros(n, np.int32)
        entries = []  # (kind, slot, seq, chunk) per built row
        w_need = 1
        n_carry = 0
        j = 0
        for slot, seq in rows:
            d = drafts.get(slot, []) if use_spec else []
            kd = len(d)
            pages = np.asarray(seq.page_ids, np.int32)
            idx = seq.device_pos + np.arange(kd + 1)
            tok_arr[j, 0] = seq.last_token
            if kd:
                tok_arr[j, 1:kd + 1] = d
                draft_arr[j, :kd] = d
            if use_spec:
                dlen_arr[j] = kd
            pos_arr[j, :kd + 1] = idx
            pos0_arr[j] = seq.device_pos
            # past-budget positions write the trash page (same clamp the
            # standalone verify build applies)
            ok = idx < max_len
            wslots[j, :kd + 1] = np.where(
                ok, pages[np.minimum(idx, max_len - 1) // ps] * ps + idx % ps, 0
            )
            meta[j] = (kd, slot, slot in carry_rows, 1)
            n_carry += slot in carry_rows
            # the step's in-jit scatter puts this row's newest sample in
            # the device carry vector — license for the next pipelined
            # build (position-deterministic only for q_len=1 rows; the
            # classifier in _mixed_tick checks that separately)
            self._carry_ok[slot] = True
            all_greedy = all_greedy and seq.temperature <= 0.0
            w_need = max(w_need, (seq.device_pos + kd) // ps + 1)
            if slot in carry_rows:
                # a stale override (set by a sync that landed after this
                # row's last build) stays put: the pending syncs of the
                # in-flight steps overwrite it before any non-stale
                # build can consume it
                pass
            else:
                # the host-built window replaces any carry override for
                # this slot (its token is already in host history)
                self._overrides.pop(slot, None)
            if pipelined and kd == 0:
                # deterministic advance, mirrored from the decode scan's
                # build: the next pipelined window builds from here while
                # this step is still in flight (sync does NOT re-advance)
                seq.device_pos += 1
            entries.append(("dec", slot, seq, 1 + kd))
            j += 1
        for seq, chunk in picks:
            tokens = seq.tokens
            start = seq.num_computed
            idx = np.arange(start, start + chunk)
            tok_arr[j, :chunk] = tokens[start:start + chunk]
            pos_arr[j, :chunk] = idx
            pos0_arr[j] = start
            pages = np.asarray(seq.page_ids, np.int32)
            wslots[j, :chunk] = pages[idx // ps] * ps + idx % ps
            meta[j] = (chunk - 1, seq.slot, 0, 0)
            all_greedy = all_greedy and seq.temperature <= 0.0
            w_need = max(w_need, -(-(start + chunk) // ps))
            entries.append(("pf", seq.slot, seq, chunk))
            j += 1
        # attended-page width buckets to a power of two like group
        # prefill (full width would DMA every trash page per tile);
        # static 0 on gather engines so w_b never forks their traces
        w_b = min(
            1 << (w_need - 1).bit_length(), self.config.max_pages_per_seq
        ) if self._attn_pallas else 0
        slot_rows = meta[:, 1]  # padding rows read slot 0's, masked off
        return dict(
            hot=hot, entries=entries,
            rows_i=np.concatenate([meta, self._host_rows_i[slot_rows]], 1),
            rows_f=self._host_samp_f[slot_rows],
            spec=use_spec, draft=draft_arr, dlen=dlen_arr, pos0=pos0_arr,
            all_greedy=all_greedy, w_b=w_b, pipelined=pipelined,
            n_carry=n_carry, n_shed=0, t0=t0,
            build_s=time.perf_counter() - t0,
        )

    def _run_mixed_dispatch(self, bld: dict):
        """Jax half of a mixed step (worker thread, _kv_lock): returns
        the device sampled-token vector [n], or (out_tokens [n, k+1],
        n_emit [n]) when spec verify rows composed in. Uploads the
        build's fused arrays and threads the donated state through the
        step (the in-jit decode-row scatter into its carry is what makes
        pipelined builds host-round-trip-free)."""
        faults.fire("engine.mixed")
        t0 = time.perf_counter()
        entries, hot = bld["entries"], bld["hot"]
        rec = dict(
            rows=len(entries), tokens=sum(e[3] for e in entries),
            # physical rows: every hot row x its chunk width flows the stack
            phys_rows=int(hot.shape[1] * hot.shape[2]),
            budget=self.config.mixed_step_tokens, build_s=bld["build_s"],
            span=dict(
                decode_rows=sum(1 for e in entries if e[0] == "dec"),
                spec=bld["spec"], pipelined=bld["pipelined"],
            ),
        )
        wd = self._op_begin("mixed.dispatch")
        try:
            with self._dispatching("mixed", t0, rec):
                with profiler.phase("eng.upload"):
                    args = (
                        jnp.asarray(hot), jnp.asarray(bld["rows_i"]),
                        jnp.asarray(bld["rows_f"]),
                        jnp.asarray(bld["draft"]) if bld["spec"] else None,
                        jnp.asarray(bld["dlen"]) if bld["spec"] else None,
                        bld["all_greedy"], bld["w_b"],
                    )
                S = self._enqueue(rec, self._mixed_fn, *args)
                self._step_count += 1
                for arr in (S if isinstance(S, tuple) else (S,)):
                    arr.copy_to_host_async()
        finally:
            self._op_end(wd)
        return S

    def _sync_mixed(self, bld: dict, toks) -> None:
        """Land a mixed step (event-loop thread): emit decode rows' next
        tokens and final chunks' first tokens, advance prefill
        bookkeeping, and re-arm each surviving row's carry override so a
        following NORMAL decode dispatch consumes the right token (mixed
        windows are host-built and never touch the device carry
        vector — the same contract as spec verify).

        spec mode (`toks` = (out [n, k+1], n_emit [n])): decode rows
        emit their accepted prefix + corrected/bonus token and REWIND
        exactly like _sync_spec — num_computed/device_pos/page
        registration advance only past emitted tokens, so a rejected
        tail's garbage KV stays unregistered and is rewritten before any
        query can attend it. Prefill rows read their sample from window
        column 0 (n_emit is 1 there by construction)."""
        spec_mode = bld["spec"]
        if spec_mode:
            out, n_emit = toks
        n_dec = n_dec_tokens = n_pf_tokens = 0
        spec_rows = drafted_total = accepted_total = emitted_total = 0
        now = time.perf_counter()
        for j, (kind, slot, seq, chunk) in enumerate(bld["entries"]):
            if kind == "dec":
                n_dec += 1
                n_dec_tokens += chunk
            else:
                n_pf_tokens += chunk
            if slot < 0 or seq.slot != slot or self.slots[slot] is not seq:
                continue  # finished/preempted while the step ran
            tok = int(out[j, 0]) if spec_mode else int(toks[j])
            if kind == "dec":
                if spec_mode:
                    spec_rows += 1
                    drafted = int(bld["dlen"][j])
                    emitted, accepted = self._emit_verify_row(
                        slot, seq, out[j], int(n_emit[j]), drafted,
                        int(bld["pos0"][j]),
                        keep_pos=bld["pipelined"] and drafted == 0,
                    )
                    drafted_total += drafted
                    accepted_total += accepted
                    emitted_total += emitted
                    continue
                if not bld["pipelined"]:
                    # pipelined builds advanced device_pos up front (the
                    # deterministic-advance contract); serialized steps
                    # advance here at sync
                    seq.device_pos += 1
                self._emit(seq, [tok])
                if self.slots[slot] is seq:
                    self._overrides[slot] = tok
                continue
            seq.num_computed += chunk
            seq.prefill_chunks += 1
            self._register_full_pages(seq)
            try:
                self._prefilling.remove(seq)
            except ValueError:
                pass
            if seq.num_computed >= seq.total_tokens:
                # final chunk: the in-step sample IS the first token —
                # emitted right here (no carry_pending round trip; the
                # sync already holds the host copy)
                seq.prefilling = False
                seq.device_pos = seq.num_computed
                seq.t_first_dispatched = now
                if tracing.enabled():
                    tracing.instant(
                        "seq.first_dispatch", cat="lifecycle",
                        req=seq.ctx.id, ts=now,
                    )
                self._emit(seq, [tok], first=True)
                if self.slots[slot] is seq:
                    self._overrides[slot] = tok
            else:
                self._prefilling.append(seq)
        with self._phase_lock:
            st = self._phase_stats
            st["mixed_steps"] += 1
            st["mixed_decode_rows"] += n_dec
            st["mixed_prefill_tokens"] += n_pf_tokens
            # budget accounting counts 1 + drafts per decode row — the
            # cap the scheduler must keep under mixed_step_tokens
            st["mixed_step_tokens_max"] = max(
                st["mixed_step_tokens_max"], n_dec_tokens + n_pf_tokens
            )
            st["mixed_carry_rows"] += bld["n_carry"]
            st["mixed_spec_shed"] += bld["n_shed"]
            if spec_mode:
                st["mixed_spec_rows"] += spec_rows
                st["spec_rows"] += spec_rows
                st["spec_drafted"] += drafted_total
                st["spec_accepted"] += accepted_total
                st["spec_emitted"] += emitted_total

    # ---- decode -------------------------------------------------------

    def _decode_ready_rows(self) -> list:
        """Decode-ready (slot, seq) rows after the cancellation sweep —
        ONE collection shared by the normal decode build and the mixed
        tick so the two paths cannot drift."""
        ready = [
            (i, s)
            for i, s in enumerate(self.slots)
            if s is not None and not s.prefilling
        ]
        now = time.time() if self._has_deadlines else 0.0
        for i, s in ready:
            if s.ctx.is_stopped():
                self._finish(s, FINISH_REASON_CANCELLED)
            elif now and self._sweep_expired(s, now):
                pass  # finished with FINISH_REASON_TIMEOUT
        return [(i, s) for i, s in ready if self.slots[i] is s]

    def _maybe_dispatch_decode(self) -> Optional["_DecodeBuild"]:
        """Host-side build of the next decode dispatch (cancellation
        sweep, page growth, input tables) as ``eng.decode.build``;
        returns None when nothing is decode-ready. The jax calls happen
        in `_run_decode_dispatch`, which the loop runs in a worker
        thread — the dispatch call can wait (on _kv_lock, a compile, the
        runtime's queue), and that wait must overlap the previous
        dispatch's result fetch."""
        t0 = time.perf_counter()
        with profiler.phase("eng.decode.build"):
            bld = self._build_decode()
        if isinstance(bld, _DecodeBuild):
            bld.build_s = time.perf_counter() - t0
        return bld

    def _build_decode(self):
        if self._closed:
            return None
        ready = self._decode_ready_rows()
        if not ready:
            return None
        if (
            self._prefilling
            and len(ready) < len(self.slots)
            and all(s.generated <= 1 for _, s in ready)
        ):
            # pure admission wave (no stream has DECODED yet — first
            # tokens emit early via the prefill-group fetch, so TTFT does
            # not wait on this gate): hold for a fuller batch. Never
            # holds once any stream is mid-decode, so a late-arriving
            # prompt cannot stall running streams.
            return None

        if self._inflight is not None and (
            self._inflight.spec or self._inflight.mixed
        ):
            # spec verify windows advance data-dependently (positions
            # and carries for the NEXT dispatch are only known after
            # sync), and a pipelined mixed step re-arms carry overrides
            # at ITS sync — a normal dispatch built from the pre-sync
            # host state would replay a stale carry. OUTSIDE the config
            # checks — runtime toggles must not let a normal dispatch
            # launch from stale host state.
            return None
        if self._dlm:
            return self._build_dlm(ready)
        if self._spec_on():
            bld = self._maybe_build_spec(ready)
            if bld == "wait":
                # worthwhile drafts exist but a normal dispatch is in
                # flight: the step pipeline syncs it and re-enters this
                # build in the SAME tick ("sync_first", see _loop);
                # serialized engines hold the build a tick so the sync
                # lands first
                return "sync_first" if self._pipe_on() else None
            if bld is not None:
                return bld

        # BUCKETED dispatch width: a fixed [max_batch] decode costs the
        # same device time at 3 live streams as at 256, which wrecks
        # TTFT/ITL under paced (non-burst) arrivals. Active slots are
        # low-packed (admission takes the first free slot), so the
        # power-of-two prefix covering the highest active slot bounds
        # compiled families to ~log2(max_batch/8)
        # last degrade rung ("serialized decode"): drop the multi-step
        # scan to ONE step per dispatch — maximally conservative, still
        # makes progress, and every host sync re-validates state
        k_steps = (
            1 if self._degrade.disabled("decode_scan")
            else self.config.decode_steps
        )
        # ensure every ready sequence has pages for all positions this
        # dispatch will write: [device_pos, device_pos + k_steps)
        prep = self._grow_and_collect(
            ready, lambda seq: seq.device_pos + k_steps - 1
        )
        if prep is None:
            return None
        active, b = prep

        # the dispatch's two fused uploads (`_decode_multi`): [position,
        # active, override value, override mask] beside the host
        # mirrors' rows as they stand now, and the float params
        rows_i = np.zeros((b, 4 + self._host_rows_i.shape[1]), np.int32)
        rows_i[:, 4:] = self._host_rows_i[:b]
        use_ext = False
        want_lps = False
        want_tops = False
        all_greedy = True
        for i, seq in active:
            rows_i[i, :2] = (seq.device_pos, 1)
            all_greedy = all_greedy and seq.temperature <= 0.0
            use_ext = use_ext or seq.has_penalties or seq.seed >= 0
            want_lps = want_lps or seq.want_logprobs
            want_tops = want_tops or seq.top_logprobs > 0
            seq.device_pos += k_steps
            # the scan ends with this row's newest sample in the device
            # carry vector — the pipelined mixed build's license to read
            # it before this dispatch syncs
            self._carry_ok[i] = True

        for slot, tok in self._overrides.items():
            if slot < b and rows_i[slot, 1]:
                rows_i[slot, 2:4] = (tok, 1)
        self._overrides.clear()
        bld = _DecodeBuild(
            rows_i=rows_i, rows_f=self._host_samp_f[:b].copy(),
            use_ext=use_ext, want_lps=want_lps, want_tops=want_tops,
            active=active, steps=k_steps, width=b, all_greedy=all_greedy,
        )
        if self._hybrid:
            # counted here, on the loop's thread: by the time the dispatch
            # worker books its digest a landing may have finished a row
            # and released the window pages this reads
            bld.win_pages = self._kv_window_pages(bld)
        return bld

    def _dlm_after(self, seq: Sequence, passes: int) -> list[tuple[int, int]]:
        """[(first position of the open block, masks left in it)] once 0,
        1, .. `passes` more passes have run on the device, from what it
        holds after every pass dispatched so far: entry k is what pass k of
        the next dispatch finds, the last what stands after it. The
        program's own rule, which fills a fixed count a pass: a block with
        no mask left commits (the position moves by a block, the block
        resets to masks), any other fills `block / steps` of its masks or
        what is left of them."""
        n = seq.dlm_block
        fill = n // self.model_cfg.denoising_steps
        pos, left = seq.device_pos, seq.dlm_left
        walk = [(pos, left)]
        for _ in range(passes):
            if left == 0:
                pos, left = pos + n, n
            else:
                left -= min(fill, left)
            walk.append((pos, left))
        return walk

    def _build_dlm(self, ready):
        """Host side of a block dispatch (`_dlm_multi`): pages a whole
        block ahead of the last pass, then the two fused uploads. A row
        that joined since the last dispatch is armed with its block (the
        given head, then masks); every other row's block is the device's."""
        n = self._mask_block
        passes = self.config.decode_steps  # what the program scans
        walk = {seq.slot: self._dlm_after(seq, passes) for _, seq in ready}
        # every position a pass of this dispatch writes: through the end
        # of the block that is open after the last pass
        prep = self._grow_and_collect(
            ready, lambda seq: walk[seq.slot][-1][0] + n - 1
        )
        if prep is None:
            return None
        active, b = prep
        h = self._host_rows_i.shape[1]
        rows_i = np.zeros((b, 4 + h + 2 * n), np.int32)
        rows_i[:, 4:4 + h] = self._host_rows_i[:b]
        want_lps = want_tops = False
        all_greedy = True
        for i, seq in active:
            rows_i[i, :2] = (seq.device_pos, 1)
            if seq.dlm_arm:
                # nothing of the row has landed since `_dlm_ready`: the
                # tokens past the encoded blocks are the block's given head
                seq.dlm_arm = False
                given = seq.tokens[seq.num_computed:]
                rows_i[i, 2] = 1
                rows_i[i, -2 * n:-2 * n + len(given)] = given
                rows_i[i, -n + len(given):] = 1
            seq.device_pos, seq.dlm_left = walk[i][-1]
            all_greedy = all_greedy and seq.temperature <= 0.0
            want_lps = want_lps or seq.want_logprobs
            want_tops = want_tops or seq.top_logprobs > 0
        return _DecodeBuild(
            rows_i=rows_i, rows_f=self._host_samp_f[:b].copy(),
            use_ext=False, want_lps=want_lps, want_tops=want_tops,
            active=active, steps=passes, width=b, all_greedy=all_greedy,
            # [passes, active rows]: the open block's first position in
            # each pass of this dispatch (`_kv_pages` books the kernel's
            # reads by them)
            block_pos0=np.asarray(
                [[p for p, _ in walk[i][:-1]] for i, _ in active],
                np.int64).T,
        )

    def _grow_and_collect(self, ready, upto):
        """Shared decode-dispatch prep: grow pages through `upto(seq)`
        (clamped to the last writable position; may preempt victims),
        re-filter the rows that survived, and bucket the dispatch width
        to the power-of-two prefix covering the highest active slot.
        Returns (active, width) or None (a growth preempted its own
        sequence, or nothing stayed decode-ready — retry next tick)."""
        max_pos = self.config.max_model_len - 1
        for _, seq in ready:
            if seq.slot < 0 or self.slots[seq.slot] is not seq:
                continue  # preempted by an earlier victim pick this pass
            if not self._ensure_pages_through(seq, min(upto(seq), max_pos)):
                return None
        active = [
            (i, s)
            for i, s in ready
            if self.slots[i] is s and not s.prefilling
        ]
        if not active:
            return None
        b_needed = 1 + max(i for i, _ in active)
        b = 8
        while b < b_needed:
            b *= 2
        return active, min(b, len(self.slots))

    def _maybe_build_spec(self, ready):
        """Host side of a speculative verify dispatch: propose n-gram
        drafts for every decode-ready row and build the [B, k_max+1]
        candidate-token window. Returns None (no worthwhile drafts —
        take the normal path), "wait" (worthwhile drafts, but host state
        is stale until the in-flight dispatch syncs), or a _DecodeBuild.

        Feature gate: rows whose carry is still on device
        (carry_pending) or that use penalties / per-request seeds /
        logprobs keep the whole batch on the scan path — the verify
        sampler covers plain greedy/temperature/top-k/top-p, which is
        the serving hot path."""
        for _, s in ready:
            if s.carry_pending or s.needs_ext_sampling:
                return None
        k_max = self.config.spec_k_max
        drafts: dict[int, list[int]] = {}
        total = 0
        for i, seq in ready:
            # never draft past the emit budget (the verify step emits at
            # most draft_len+1 tokens) or the last writable position
            remaining = seq.max_new_tokens - seq.generated
            room = self.config.max_model_len - 1 - seq.device_pos
            k_i = min(k_max, remaining - 1, room)
            d = seq.spec.maybe_draft(k_i) if seq.spec is not None else []
            drafts[i] = d
            total += len(d)
        # worthwhile only when the batch averages >= 1 drafted token per
        # row: a spec dispatch is ONE model step for every row, so rows
        # without drafts fall from decode_steps to 1 token per dispatch
        if total < max(1, len(ready)):
            return None
        if self._inflight is not None:
            return "wait"
        prep = self._grow_and_collect(
            ready, lambda seq: seq.device_pos + len(drafts.get(seq.slot, ()))
        )
        if prep is None:
            return None
        active, b = prep
        t = k_max + 1
        w = self.config.max_pages_per_seq
        if self._attn_pallas:
            # ragged flash kernel: attended-page width buckets to a
            # power of two like group prefill and the mixed build — the
            # kernel's page BlockSpecs DMA every table column per grid
            # step, so a full-width table would stream (mostly trash)
            # pages the causal mask never reads. Truncation is sound:
            # every attended position <= device_pos + draft_len lies
            # inside w_need pages.
            ps = self.page_size
            w_need = max(
                (s.device_pos + len(drafts.get(s.slot, ()))) // ps + 1
                for _, s in active
            )
            w = min(1 << (w_need - 1).bit_length(), w)
        tokens = np.zeros((b, t), np.int32)
        positions = np.zeros((b, t), np.int32)
        tables = np.zeros((b, w), np.int32)
        draft = np.zeros((b, k_max), np.int32)
        dlen = np.zeros(b, np.int32)
        pos0 = np.zeros(b, np.int32)
        act = np.zeros(b, bool)
        temp = np.zeros(b, np.float32)
        topk = np.zeros(b, np.int32)
        topp = np.ones(b, np.float32)
        for i, seq in active:
            d = drafts.get(i) or []
            act[i] = True
            pos0[i] = seq.device_pos
            tokens[i, 0] = seq.last_token  # the host-known decode carry
            if d:
                tokens[i, 1:1 + len(d)] = d
                draft[i, :len(d)] = d
                dlen[i] = len(d)
            positions[i] = seq.device_pos + np.arange(t, dtype=np.int32)
            npg = min(len(seq.page_ids), w)
            tables[i, :npg] = seq.page_ids[:npg]
            temp[i] = seq.temperature
            topk[i] = seq.top_k
            topp[i] = seq.top_p
            # the host token window replaces the device carry; any
            # stale override for this slot is already in host history.
            # The verify step advances data-dependently and never
            # touches the carry vector — it is stale until the sync
            # re-arms an int override.
            self._overrides.pop(i, None)
            self._carry_ok[i] = False
        return _DecodeBuild(
            spec=True, tokens=tokens, positions=positions, tables=tables,
            draft=draft, dlen=dlen, pos0=pos0, act=act, temp=temp,
            topk=topk, topp=topp, active=active, steps=1, width=b,
            all_greedy=bool((temp[act] <= 0.0).all()),
        )

    def _run_decode_dispatch(self, bld: "_DecodeBuild") -> _Dispatch:
        """The jax half of a decode dispatch — runs in a worker thread
        under _kv_lock (the loop awaits it before its own next kv use,
        but the public prefill_only path can dispatch concurrently)."""
        t0 = time.perf_counter()
        rows = len(bld.active)
        if bld.spec:
            rec = dict(
                rows=rows, tokens=rows + int(np.sum(bld.dlen)),
                phys_rows=int(np.asarray(bld.tokens).size),
            )
        elif self._dlm:
            n = self._mask_block
            rec = dict(
                # token rows through the layer stack: a block a row a pass
                rows=rows, tokens=rows * bld.steps * n,
                phys_rows=bld.width * bld.steps * n,
                span={"passes": bld.steps}, dlm_passes=bld.steps,
            )
            if self._attn_pallas:
                from dynamo_tpu.ops.pallas_attention import PAGES_PER_BLOCK

                rec["kv_pages_streamed"], rec["kv_pages_held"] = (
                    self._kv_pages(bld)
                )
                # work items ONE layer's block kernel walks over the passes
                rec["dlm_work_items"] = int(np.sum(-(
                    -self._attended_lengths(bld)
                    // (self.page_size * PAGES_PER_BLOCK))))
        else:
            rec = dict(
                # dispatched decode token-SLOTS (active rows x steps):
                # includes the <= steps-1 overshoot positions of rows
                # that finish mid-scan, so this bounds emitted tokens
                # from above
                rows=rows, tokens=rows * bld.steps,
                # physical rows: the scan runs the FULL padded batch
                # every step
                phys_rows=bld.width * bld.steps,
                span={"steps": bld.steps},
            )
            if self._attn_pallas:
                rec["kv_pages_streamed"], rec["kv_pages_held"] = (
                    self._kv_pages(bld)
                )
            if self._recurrent:
                # rows x steps whose state the program advances
                rec["state_rows_advanced"] = rows * bld.steps
            if self._hybrid:
                # the full kind's pages stay in kv_pages_held_full; the
                # two counters then count BOTH kinds (a layer of each)
                rec["kv_pages_held_full"] = (
                    rec["kv_pages_held"] if self._attn_pallas
                    else self._kv_pages(bld)[1]
                )
                streamed, items, held = bld.win_pages
                rec["kv_win_pages_held"] = held
                rec["kv_win_pages_released"] = self._win_released
                if self._attn_pallas:
                    rec["kv_pages_streamed"] += streamed
                    rec["kv_pages_held"] += held
                    rec["kv_win_items"] = items
        rec["build_s"] = bld.build_s
        kind = ("spec_verify" if bld.spec else "dlm" if self._dlm
                else "decode")
        wd = self._op_begin("spec.dispatch" if bld.spec else "decode.dispatch")
        try:
            # inside the watchdog's window: an injected slow dispatch is
            # a slow dispatch
            faults.fire("engine.dispatch")
            with self._dispatching(kind, t0, rec):
                if bld.spec:
                    return self._run_spec_dispatch_locked(bld, rec)
                return self._run_decode_dispatch_locked(bld, rec)
        finally:
            self._op_end(wd)

    def _kv_pages(self, bld: "_DecodeBuild") -> tuple[int, int]:
        """KV pages one layer's decode kernel copies in over this
        dispatch's steps (`ops.pallas_attention.streamed_pages`, the rule
        its work list is built to) and the pages those rows hold: the
        attended lengths are `_decode_multi`'s, from the build's
        positions (a block dispatch's: its passes', `_attended_lengths`).
        Equal while the kernel reads only what a sequence holds; the
        digest keeps both so a reader sees when it does not."""
        from dynamo_tpu.ops.pallas_attention import streamed_pages

        lengths, ps = self._attended_lengths(bld), self.page_size
        return streamed_pages(lengths, ps), int(np.sum(-(-lengths // ps)))

    def _attended_lengths(self, bld: "_DecodeBuild") -> np.ndarray:
        """[steps, active rows]: the KV count each step of this dispatch
        attends, as `_decode_multi` derives it from the build's positions;
        of a block dispatch each PASS, through the end of the row's open
        block (`ops.pallas_block.block_lengths`, the kernel's own rule)."""
        if self._dlm:
            from dynamo_tpu.ops.pallas_block import block_lengths

            n = self._mask_block
            return block_lengths(
                bld.block_pos0, 1, n,
                (bld.rows_i.shape[1] - 6 - 2 * n) * self.page_size, xp=np)
        pos = bld.rows_i[[i for i, _ in bld.active], 0]
        return np.minimum(
            pos[None, :] + 1 + np.arange(bld.steps)[:, None],
            self.config.max_model_len,
        )

    def _kv_window_pages(self, bld: "_DecodeBuild") -> tuple[int, int, int]:
        """A hybrid model's window kind, for the same rows and steps:
        (pages ONE window layer's kernel copies in, by the rule its work
        list is built to, from the static window `_attn_block` hands it;
        that kernel's work items where an item holds several sequences'
        windows, 0 where the window is too long for one; pages the rows
        hold in the window pool, over the steps)."""
        from dynamo_tpu.ops import pallas_attention as pa

        lengths = self._attended_lengths(bld)
        window, ps = self.model_cfg.sliding_window, self.page_size
        items = 0
        if pa.window_grouped(window, ps, pa.PAGES_PER_BLOCK):
            items = int(np.sum(pa.window_items(lengths, xp=np)))
        held = sum(
            len(s.win_page_ids) - s.win_first for _, s in bld.active
        )
        return (
            pa.streamed_pages(lengths, ps, window=window), items,
            held * bld.steps,
        )

    def _run_spec_dispatch_locked(
        self, bld: "_DecodeBuild", rec: dict
    ) -> _Dispatch:
        """Jax half of a speculative verify dispatch: one multi-query
        model step + on-device acceptance. The device carry vector is
        NOT updated (spec windows are host-built); sync re-arms the
        carry for a following normal dispatch via an int override."""
        with profiler.phase("eng.upload"):
            args = (
                jnp.asarray(bld.tokens), jnp.asarray(bld.positions),
                jnp.asarray(bld.tables), jnp.asarray(bld.act),
                jnp.asarray(bld.draft), jnp.asarray(bld.dlen),
                jnp.asarray(bld.temp), jnp.asarray(bld.topk),
                jnp.asarray(bld.topp), bld.all_greedy,
            )
        S = self._enqueue(rec, self._spec_fn, *args)
        self._step_count += 1
        for arr in S:
            arr.copy_to_host_async()
        return _Dispatch(
            S, bld.active, bld.steps, spec=True, pos0=bld.pos0,
            draft_lens=bld.dlen,
        )

    def _run_decode_dispatch_locked(
        self, bld: "_DecodeBuild", rec: dict
    ) -> _Dispatch:
        """Jax half of a decode dispatch: the build's two fused uploads
        (``eng.upload``) and the one launch (``eng.enqueue``)."""
        with profiler.phase("eng.upload"):
            args = (
                jnp.asarray(bld.rows_i), jnp.asarray(bld.rows_f),
                bld.all_greedy, bld.want_lps, bld.want_tops,
            )
        if self._dlm:
            S = self._enqueue(rec, self._dlm_fn, *args, dlm=True)
        else:
            S = self._enqueue(
                rec, self._decode_fn, *args, counts=bld.use_ext)
        self._step_count += 1
        for arr in S:
            arr.copy_to_host_async()
        if self.model_cfg.num_experts:
            return _Dispatch(S[:-1], bld.active, bld.steps, moe=S[-1])
        return _Dispatch(S, bld.active, bld.steps)

    async def _sync_dispatch(self, d: _Dispatch, overlapped: bool = False) -> None:
        # first-token fetch tasks for sequences in this dispatch must
        # land first: their emission precedes these decode tokens in the
        # output stream
        for task in {s.first_task for _, s in d.snapshot if s.first_task}:
            try:
                await task
            except Exception:
                log.exception("first-token emit task failed")
        self._land(d, *await self._fetch(d, overlapped), overlapped)

    async def _fetch(self, d: _Dispatch, overlapped: bool = False) -> tuple:
        """Wait for a dispatch's outputs on the host (``eng.fetch``; the
        wait runs in a worker thread, the loop serves other tasks
        meanwhile). Returns (arrays, t0, t1)."""
        out = d.out_dev
        t0 = time.perf_counter()
        wd = self._op_begin("sync.fetch")
        try:
            with profiler.phase("eng.fetch"):
                # mixed: sampled [n], or (out [n, k+1], n_emit [n]) with
                # spec rows; else (toks, lps[, top_ids, top_lps]) each
                # [K+1, B(, 8)]
                arrs = await asyncio.to_thread(
                    lambda: tuple(np.asarray(a) for a in out)
                    if isinstance(out, (tuple, list)) else np.asarray(out)
                )
        finally:
            self._op_end(wd)
        return arrs, t0, time.perf_counter()

    def _land(self, d: _Dispatch, arrs, t0: float, t1: float,
              overlapped: bool = False) -> None:
        """Book a fetched dispatch, then land it (``eng.emit``: token
        loop, stop checks, out_queue puts, finishes; the digest's
        `emit_s`)."""
        self._t_fetched = t1
        self._record_sync(
            "mixed" if d.mixed else "spec" if d.spec
            else "dlm" if self._dlm else "decode",
            len(d.bld["entries"]) if d.mixed else len(d.snapshot),
            t0, t1, overlapped, bld_t0=d.bld["t0"] if d.mixed else None,
        )
        frames, tokens = self._frames, self._frame_tokens
        with profiler.phase("eng.emit"):
            if d.mixed:
                self._sync_mixed(d.bld, arrs)
            elif d.spec:
                self._sync_spec(d, arrs)
            elif self._dlm:
                landed = self._sync_dlm(d, arrs)
            else:
                self._sync_decode(d, arrs)
        if self.flight is not None:
            now = time.perf_counter()
            host = {
                "emit_s": now - t1,
                "frames": self._frames - frames,
                "tokens": self._frame_tokens - tokens,
                # collector passes since the landing before this one
                "gc_s": self._heap.lap(),
                **self._tick_lap(now),
            }
            if self._dlm:
                host.update(landed)
            if d.moe is not None:
                # the same program made it: ready since the tokens were
                (host["moe_experts_hit"], host["moe_load_max"],
                 host["moe_row_blocks"], host["moe_pairs_held"]) = (
                    np.asarray(d.moe).tolist()
                )
            self.flight.amend("overlap" if overlapped else "sync", **host)

    def _tick_lap(self, now: float) -> dict:
        """The digest's tick columns (loop thread, at the end of a
        landing): `tick_s` since the end of the landing before (0 on the
        first, and on the first after the loop sat idle in ``eng.wait``),
        and this thread's growth since then of ``eng.admit``, of
        ``eng.join`` and (`unphased_s`) of no ``eng.*`` phase at all
        under the parent ``eng.tick``. The ``fe.*`` phases of this thread
        run inside the awaiting ``eng.*`` ones or in the unphased part,
        so they are not taken off a second time."""
        table = tracing.phase_table()
        lap = {
            n: c[0] for n, c in table.items()
            if n.startswith("eng.") and n != "eng.tick"
        }
        was, self._phase_lap = self._phase_lap, lap
        t0, self._t_landed = self._t_landed, now
        if not t0:
            return {}
        grew = {n: v - was.get(n, 0.0) for n, v in lap.items()}
        return {
            "tick_s": now - t0,
            "admit_s": grew.get("eng.admit", 0.0),
            "join_s": grew.get("eng.join", 0.0),
            "unphased_s": max(now - t0 - sum(grew.values()), 0.0),
        }

    def _sync_decode(self, d: _Dispatch, arrs) -> None:
        """Land a decode scan, a sequence at a time: its column of the
        fetched arrays, read off once, is one frame. Row 0 is the
        dispatch's input carry: a sequence that entered with a
        freshly-prefilled first token emits it here, in stream order
        before its decode tokens (one fetch covers everything)."""
        out, out_lps = arrs[0], arrs[1]
        tops = arrs[2:] if len(arrs) == 4 else None
        for i, seq in d.snapshot:
            if self.slots[i] is not seq:
                continue  # finished/preempted earlier: overshoot discarded
            first = seq.carry_pending
            if first:
                seq.carry_pending = False
                seq.num_computed = seq.total_tokens  # prefill KV all valid
            lo = 0 if first else 1
            k = seq.top_logprobs if tops is not None else 0
            self._emit(
                seq, out[lo:, i].tolist(),
                out_lps[lo:, i].tolist() if seq.want_logprobs else None,
                (tops[0][lo:, i, :k].tolist(), tops[1][lo:, i, :k].tolist())
                if k else None,
                first=first,
            )

    def _sync_dlm(self, d: _Dispatch, arrs) -> dict:
        """Land a block dispatch, a sequence at a time, replaying its
        passes in order on the masks the host knows the open block to
        hold (`dlm_open`). A pass that finds none left was the block's
        COMMIT pass: its keys and values count from now on, the block
        resets. Any other pass filled what its row of `toks` names (-1: not
        this pass), the leftmost masks, each token with the
        log-probability of the pass that filled it; they go to the client
        with this landing, not after the commit pass. One frame a sequence
        a landing; `_emit` cuts at `max_tokens` or a stop token inside a
        block, and what the device did past it is discarded. Returns the
        digest's counts over the rows still live."""
        toks, lps = arrs[0], arrs[1]    # [K, w, L]
        tops = arrs[2:4] if len(arrs) == 4 else None
        n = self._mask_block
        row_passes = filled = committed = 0
        for i, seq in d.snapshot:
            if self.slots[i] is not seq:
                continue  # finished/preempted earlier: the passes discarded
            out, blocks = [], 0
            k = seq.top_logprobs if tops is not None else 0
            for p in range(d.steps):
                row_passes += 1
                if seq.dlm_open == 0:
                    blocks += 1
                    seq.dlm_open = n
                    continue
                for j in np.flatnonzero(toks[p, i] >= 0):
                    out.append((
                        int(toks[p, i, j]), float(lps[p, i, j]),
                        (tops[0][p, i, j, :k].tolist(),
                         tops[1][p, i, j, :k].tolist()) if k else None,
                    ))
                    seq.dlm_open -= 1
            filled += len(out)
            committed += blocks
            if out:
                self._emit(
                    seq, [e[0] for e in out],
                    [e[1] for e in out] if seq.want_logprobs else None,
                    ([e[2][0] for e in out], [e[2][1] for e in out])
                    if k else None,
                    first=seq.generated == 0, computed=blocks * n,
                )
            elif blocks:
                seq.num_computed = min(
                    seq.num_computed + blocks * n, seq.total_tokens)
                self._register_full_pages(seq)
        with self._phase_lock:
            self._phase_stats["dlm_row_passes"] += row_passes
            self._phase_stats["dlm_filled"] += filled
            self._phase_stats["dlm_committed"] += committed
        return {"dlm_row_passes": row_passes, "dlm_filled": filled,
                "dlm_committed": committed}

    def _emit_verify_row(self, slot: int, seq: Sequence, out_row,
                         n: int, drafted: int, base: int,
                         keep_pos: bool = False) -> tuple:
        """Land ONE verify row (shared by the standalone spec sync and
        the mixed-step spec sync — the rollback invariants must not
        fork): emit the accepted prefix + corrected/bonus token, then
        REWIND the paged-cache bookkeeping to the accepted length —
        num_computed, device_pos and prefix-page registration advance
        only past tokens actually emitted, so the garbage KV a rejected
        tail left in its slots stays unregistered and is rewritten by
        the very next dispatch before any query can attend it. Returns
        (emitted, accepted).

        `keep_pos`: a PIPELINED mixed step's dlen=0 (shed carry) row
        advanced `device_pos` deterministically at build time, and a
        NEXT pipelined build may have advanced it again before this
        sync runs — the absolute rewind here would clobber that later
        advance (the q_len=1 row has nothing to rewind: its one token
        always lands). Rows with real drafts advance data-dependently,
        are never carried into a following build, and keep the
        rewind."""
        # EOS/length mid-window: the tail is discarded
        emitted = self._emit(seq, out_row[:n].tolist())
        if not keep_pos:
            seq.device_pos = base + emitted
        # counters reflect what actually LANDED: when an emitted draft
        # finished the stream (EOS) the discarded tail — and the
        # never-emitted bonus — must not inflate acceptance
        accepted = n - 1 if emitted == n else emitted
        if seq.spec is not None and drafted:
            seq.spec.observe(drafted, accepted)
        if self.slots[slot] is seq:
            # the last emitted token is the new decode carry; a
            # following NORMAL dispatch consumes it via its override
            # columns (verify windows are host-built and never touch
            # the device carry vector)
            self._overrides[slot] = int(out_row[n - 1])
        return emitted, accepted

    def _sync_spec(self, d: _Dispatch, arrs) -> None:
        """Land a speculative verify dispatch: one `_emit_verify_row`
        per surviving row (emit accepted prefix + corrected/bonus token,
        rewind bookkeeping to the accepted length)."""
        toks, n_emit = arrs[0], arrs[1]  # [B, T] i32, [B] i32
        drafted_total = accepted_total = emitted_total = rows = 0
        for i, seq in d.snapshot:
            if self.slots[i] is not seq:
                continue  # finished/preempted meanwhile
            rows += 1
            drafted = int(d.draft_lens[i])
            emitted, accepted = self._emit_verify_row(
                i, seq, toks[i], int(n_emit[i]), drafted, int(d.pos0[i])
            )
            drafted_total += drafted
            accepted_total += accepted
            emitted_total += emitted
        with self._phase_lock:
            self._phase_stats["spec_rows"] += rows
            self._phase_stats["spec_drafted"] += drafted_total
            self._phase_stats["spec_accepted"] += accepted_total
            self._phase_stats["spec_emitted"] += emitted_total

    def _ensure_pages_through(self, seq: Sequence, upto_pos: int) -> bool:
        # a hybrid model first hands back the window pages that fell
        # behind the window, then grows BOTH kinds' lists through
        # `upto_pos`; whichever pool runs out preempts
        grew = self._hybrid and self._release_window_pages(seq)
        while True:
            if upto_pos // self.page_size >= len(seq.page_ids):
                alloc, ledger, ids = (
                    self.allocator, self.kv_ledger, seq.page_ids
                )
            elif self._hybrid and (
                upto_pos // self.page_size >= len(seq.win_page_ids)
            ):
                alloc, ledger, ids = (
                    self.win_allocator, self.kv_ledger_win, seq.win_page_ids
                )
            else:
                break
            got = alloc.allocate(1)
            if got is not None:
                ids.extend(got)
                ledger.hold(got, seq.ctx.id, tenant=seq.tenant)
                grew = True
                continue
            live = [s for s in self.slots if s is not None]
            # lowest priority class first, most-recent within it —
            # batch traffic yields pages before interactive tenants
            # (scheduler.pick_preemption_victim; reduces to
            # max(seq_id) when no priorities are in flight)
            victim = pick_preemption_victim(live)
            self._preempt(victim)
            if victim is seq:
                return False
        if grew:
            # page growth is one of the two events (with admit) that
            # change a live slot's block-table row
            self._mark_slot_state(seq)
        return True

    def _preempt(self, seq: Sequence) -> None:
        log.info("preempting seq %s (out of KV pages)", seq.seq_id)
        self._preemptions += 1
        self._register_full_pages(seq)
        self._kv_drop(seq.page_ids, seq.ctx.id)
        self.allocator.release(seq.page_ids)
        if self._hybrid:
            self._drop_window_pages(seq)
        self.slots[seq.slot] = None
        self._overrides.pop(seq.slot, None)
        # the slot may be reused: a preempted row mid-pipeline must not
        # leave a "valid carry" claim behind (re-admission re-arms via
        # the prefill override — the carry-staleness contract)
        self._carry_ok[seq.slot] = False
        if seq in self._prefilling:
            self._prefilling.remove(seq)
        seq.slot = -1
        seq.prefilling = False
        seq.carry_pending = False
        seq.first_task = None
        seq.page_ids = []
        seq.num_cached = 0
        seq.num_computed = 0
        seq.device_pos = 0
        seq.registered_pages = 0
        self.waiting.appendleft(seq)

    # ---- bookkeeping --------------------------------------------------

    def _register_full_pages(self, seq: Sequence) -> None:
        if self._no_prefix_cache:
            # no in-engine prefix cache over two kinds of page (a hash
            # would name a full-kind page whose window-kind twin is gone)
            # or beside state pools (a shared page without the state at
            # its boundary is a wrong answer): such a prompt prefills
            return
        full = seq.num_computed // self.page_size
        cap = seq.cacheable_pages(self.page_size)
        if cap is not None:
            full = min(full, cap)  # hashes past embeds_offset are unsound
        start = seq.registered_pages
        if full <= start:
            return
        blocks = seq.blocks.blocks[start:full]
        self.allocator.register(
            seq.page_ids[start:full],
            [(blk.sequence_hash, blk.local_hash) for blk in blocks],
            parent_hash=blocks[0].parent_sequence_hash if blocks else None,
        )
        seq.registered_pages = full

    def peek_prefix_tokens(
        self, token_ids: list[int], max_tokens: Optional[int] = None,
        hashes: Optional[list[int]] = None,
    ) -> int:
        """Non-destructive cached-prefix length across BOTH tiers (HBM,
        then host continuation) — the disagg/router decision input must
        agree with what _reserve_pages would actually reuse. For embed
        requests pass `max_tokens=embeds_offset`: reservation only
        matches the text prefix below the image span. Pass `hashes`
        (the prompt's chained block hashes) when the caller computed
        them already — the disagg path hashes once per request and
        threads the list through here AND admission."""
        if hashes is None:
            from dynamo_tpu.llm.tokens import compute_block_hashes

            hashes = compute_block_hashes(token_ids, self.page_size)
        if max_tokens is not None:
            hashes = hashes[: max_tokens // self.page_size]
        n = 0
        for h in hashes:
            if h in self.allocator._by_hash:
                n += 1
            else:
                break
        if self.host_pool is not None:
            for h in hashes[n:]:
                if h in self.host_pool:
                    n += 1
                else:
                    break
        return n * self.page_size

    # ---- HBM->host offload tier --------------------------------------

    def _on_page_cached(self, pid: int, meta) -> None:
        """Allocator hook: a hashed page just hit refs==0 — queue its
        write-through copy to the host tier (reference: reuse.rs
        return-to-pool path feeding the offload manager).

        Best-effort: the queue is BOUNDED (newest wins). Under churn the
        unbounded backlog both grew without limit and guaranteed the
        copies ran far behind the pages' useful life; dropping old
        entries keeps offload an optimization, never a liability."""
        if self.offload_paused or meta.sequence_hash in self.host_pool:
            return
        cap = max(4 * self.config.offload_batch_pages, 64)
        self._pending_offload.pop(meta.sequence_hash, None)
        while len(self._pending_offload) >= cap:
            self._pending_offload.pop(next(iter(self._pending_offload)))
        self._pending_offload[meta.sequence_hash] = (
            meta.local_hash, meta.parent_hash
        )

    def _maybe_start_offload(self) -> None:
        """Launch one background offload batch if work is queued and no
        batch is in flight (single-flight keeps device pressure bounded).
        Offload yields to PREFILL work: a device-to-host page gather in
        the middle of an admission wave steals exactly the bandwidth the
        wave needs (measured ~25% prefill-phase tax on 8B); decode-only
        and idle periods absorb the copies instead."""
        if not self._pending_offload or self.offload_paused:
            return
        if self.waiting or self._prefilling:
            return
        if self._offload_task is not None and not self._offload_task.done():
            return
        batch: list[tuple[int, int, Optional[int], int, object]] = []
        # newest first: recently-freed pages are the likeliest re-hits,
        # and probes/fresh prefixes must not queue behind stale churn
        for sh in reversed(list(self._pending_offload)):
            if len(batch) >= self.config.offload_batch_pages:
                break
            lh, parent = self._pending_offload.pop(sh)
            # pin BEFORE reserving a buffer: reserve() may LRU-evict a
            # live host entry, which must not happen for a page that is
            # already gone from HBM (nothing to copy — pure data loss)
            pid = self.allocator.pin(sh)
            if pid is None:
                continue
            self._kv_hold([pid], "sys:offload")
            buf = self.host_pool.reserve()
            if buf is None:
                self._kv_drop([pid], "sys:offload")
                self.allocator.release([pid])
                self._pending_offload[sh] = (lh, parent)
                break
            batch.append((sh, lh, parent, pid, buf))
        if batch:
            self._offload_task = asyncio.create_task(self._offload_batch(batch))

    async def _offload_batch(self, batch) -> None:
        ps = self.page_size
        slots = np.concatenate(
            [pid * ps + np.arange(ps, dtype=np.int32) for *_, pid, _b in batch]
        )

        def _gather():
            with self._kv_lock:
                out = self._extract_fn(self.kv, jnp.asarray(slots))
            return tuple(np.asarray(a) for a in out)  # [L, n*ps, ...] each

        consumed = 0
        try:
            arrs = await asyncio.to_thread(_gather)
            k, v = arrs[0], arrs[1]
            for i, (sh, lh, parent, pid, buf) in enumerate(batch):
                sl = slice(i * ps, (i + 1) * ps)
                if self._kv_quant:
                    buf.value["kv"][0] = k[:, sl]
                    buf.value["kv"][1] = v[:, sl]
                    buf.value["scales"][0] = arrs[2][:, sl]
                    buf.value["scales"][1] = arrs[3][:, sl]
                else:
                    buf.value[0] = k[:, sl]
                    buf.value[1] = v[:, sl]
                self.host_pool.put(sh, lh, parent, buf)  # consumes buf
                consumed = i + 1
        except Exception:
            log.exception("offload gather failed; dropping batch")
        finally:
            # CancelledError (engine close) must not leak buffers or pins
            for _, _, _, _, buf in batch[consumed:]:
                buf.release()
            pids = [pid for _, _, _, pid, _ in batch]
            self._kv_drop(pids, "sys:offload")
            self.allocator.release(pids)
            # re-arm the loop: remaining pending entries must offload
            # before admission traffic can evict their HBM pages
            self._wake.set()

    def _restore_page_bytes(self) -> int:
        """Host-tier bytes moved per restored page (K+V pages + scale
        tiles across layers) — the H2D cost side of the restore gate."""
        m = self.model_cfg
        kw = m.num_kv_heads * m.head_dim
        if self._kv_quant == "int4":
            kw //= 2  # nibble-packed rows: one byte per two features
        per_pool = self.page_size * kw * (
            1 if self._kv_quant else self._dtype.dtype.itemsize
        )
        scales = (
            self.page_size * self._kv_scale_channels() * 4 * 2
            if self._kv_quant else 0
        )
        return m.num_layers * (2 * per_pool + scales)

    def _reset_offload_ema(self, rung: str = "", reason: str = "") -> None:
        """Degrade-ladder trip hook (ADVICE r5 follow-up): the restore
        gate's rate EMAs were calibrated on the pre-degrade engine
        configuration (e.g. pipelined prefill throughput); after a trip
        they would mis-price restore-vs-recompute, so both reset and the
        next restore/prefill re-calibrate on the degraded engine."""
        self._ema_restore_bps = None
        self._ema_prefill_tps = None

    def _restore_worthwhile(self, n_pages: int) -> bool:
        """Gate a host-tier restore on measured rates: restore wins only
        when moving the bytes beats recomputing the tokens. Unknown
        rates (cold engine) restore optimistically — the restore itself
        calibrates the EMA."""
        if self._ema_restore_bps is None or self._ema_prefill_tps is None:
            return True
        restore_s = n_pages * self._restore_page_bytes() / self._ema_restore_bps
        recompute_s = n_pages * self.page_size / self._ema_prefill_tps
        return restore_s < recompute_s

    def _restore_from_host(self, seq: Sequence, page_ids: list[int], start_block: int) -> None:
        """Scatter host-tier pages back into freshly allocated device
        pages and index them (reference: manager.rs tiered onboard +
        layer.rs CopyStream H2D)."""
        t_restore0 = time.perf_counter()
        ps = self.page_size
        blocks = seq.blocks.blocks[start_block : start_block + len(page_ids)]
        bufs = [self.host_pool.get(b.sequence_hash) for b in blocks]
        if self._kv_quant:
            nk = np.stack([b["kv"][0] for b in bufs], axis=1)
            nv = np.stack([b["kv"][1] for b in bufs], axis=1)
            nks = np.stack([b["scales"][0] for b in bufs], axis=1)
            nvs = np.stack([b["scales"][1] for b in bufs], axis=1)
            nks = nks.reshape(nks.shape[0], -1, nks.shape[-1])
            nvs = nvs.reshape(nvs.shape[0], -1, nvs.shape[-1])
        else:
            nk = np.stack([b[0] for b in bufs], axis=1)
            nv = np.stack([b[1] for b in bufs], axis=1)
            nks = nvs = None
        # [L, n, ps, kw] -> [L, n*ps, kw]
        nk = nk.reshape(nk.shape[0], -1, nk.shape[-1])
        nv = nv.reshape(nv.shape[0], -1, nv.shape[-1])
        slots = np.concatenate(
            [pid * ps + np.arange(ps, dtype=np.int32) for pid in page_ids]
        )
        with self._kv_lock:
            self.kv = self._inject_fn(
                self.kv, jnp.asarray(slots), jnp.asarray(nk), jnp.asarray(nv),
                jnp.asarray(nks) if nks is not None else None,
                jnp.asarray(nvs) if nvs is not None else None,
            )
            # read-only probe enqueued right after the inject (still
            # under the lock, so no donating dispatch can slip between):
            # fencing IT observes the transfer completing without ever
            # touching the donated pools after release
            probe = self.kv.k[0][:1]
        self.allocator.register(
            page_ids,
            [(b.sequence_hash, b.local_hash) for b in blocks],
            parent_hash=blocks[0].parent_sequence_hash if blocks else None,
        )
        self.offload_gate_stats["restored"] += 1
        n_restored = len(page_ids)

        async def _calibrate() -> None:
            # fence OFF the event loop: block_until_ready would stall
            # every stream behind the whole device queue. The EMA only
            # feeds the restore-vs-recompute gate, so stamping it a few
            # ms late is free — measuring async ENQUEUE instead of the
            # completed transfer is what biased the gate before.
            try:
                await asyncio.to_thread(jax.block_until_ready, probe)
            except Exception:
                log.exception("restore-gate calibration fence failed")
                return
            dt = max(time.perf_counter() - t_restore0, 1e-6)
            bps = n_restored * self._restore_page_bytes() / dt
            self._ema_restore_bps = (
                bps if self._ema_restore_bps is None
                else 0.5 * self._ema_restore_bps + 0.5 * bps
            )

        task = asyncio.get_running_loop().create_task(_calibrate())
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)

    def _emit(
        self, seq: Sequence, toks: list, lps: Optional[list] = None,
        alts: Optional[tuple] = None, first: bool = False,
        computed: Optional[int] = None,
    ) -> int:
        """The ONE emit path: what one fetch brought for one sequence
        (`toks`, in order; `lps` its log-probabilities when asked for,
        `alts` = (ids, lps) of the alternatives, a row per token)
        becomes ONE frame on its out_queue. The sequence keeps tokens up
        to the one that finishes it; the rest lie past its end and are
        discarded, and the final frame follows. `first`: toks[0] is the
        prompt's first token (its KV is the prefill's, so `num_computed`
        stands for it) and `first_meta` rides in the frame. `computed` (a
        block-diffusion landing): the positions whose keys and values this
        landing made final, in place of one a token kept; never past the
        tokens kept. Returns the number of tokens kept."""
        if not toks:
            return 0
        base, reason = seq.generated, None
        for n, tok in enumerate(toks, 1):
            seq.generated = base + n
            reason = seq.check_finish(tok)
            if reason:
                break
        toks = toks[:n]
        seq.blocks.extend(toks)
        if seq.spec is not None:
            seq.spec.extend(toks)
        if computed is None:
            seq.num_computed += n - first
        else:
            seq.num_computed = min(
                seq.num_computed + computed, seq.total_tokens)
        self._register_full_pages(seq)
        if base == 0:
            seq.t_first_emit = time.perf_counter()
            if seq.t_admit:
                # ttft_s = queue_wait_s + prefill_s + first_emit_s
                seq.prefill_s = self._t_fetched - seq.t_admit
                seq.first_emit_s = seq.t_first_emit - self._t_fetched
            if tracing.enabled():
                tracing.instant(
                    "seq.first_token", cat="lifecycle", req=seq.ctx.id,
                    ts=seq.t_first_emit,
                )
        frame = EngineOutput(token_ids=toks)
        if seq.want_logprobs:
            # NaN = no local logprob (disagg remotely-sampled first token)
            lps = [
                None if lp is None or lp != lp else lp
                for lp in (lps[:n] if lps is not None else [None] * n)
            ]
            for lp in lps:
                if lp is not None:
                    seq.cum_logprob += lp
            frame.log_probs = lps
            frame.cum_log_probs = seq.cum_logprob
            if alts is not None:
                # NaN alternatives (disagg first token) are dropped
                frame.top_log_probs = [
                    [[t, lp] for t, lp in zip(ids, vals) if lp == lp]
                    for ids, vals in zip(alts[0][:n], alts[1][:n])
                ]
        if first:
            self._stamp_first_meta(seq)
            if seq.first_meta:
                frame.meta = seq.first_meta
            seq.first_meta = None
        self._frames += 1
        self._frame_tokens += n
        seq.out_queue.put_nowait(frame.to_dict())
        if reason:
            self._finish(seq, reason)
        return n

    def _finish(self, seq: Sequence, reason: str) -> None:
        self._register_full_pages(seq)
        try:
            # chaos hook: an injected failure here LEAKS the pages —
            # refs stay up, the ledger holding stays attributed to the
            # finished request, and the next audit must flag the orphan
            # (the census-under-faults test drives exactly this)
            faults.fire("engine.release")
        except faults.FaultError:
            log.warning(
                "fault injected: leaking %d KV page(s) of %s",
                len(seq.page_ids), seq.ctx.id,
            )
        else:
            self._kv_drop(seq.page_ids, seq.ctx.id)
            self.allocator.release(seq.page_ids)
            if self._hybrid:
                self._drop_window_pages(seq)
        if seq.slot >= 0:
            self._overrides.pop(seq.slot, None)
            self._carry_ok[seq.slot] = False
            self.slots[seq.slot] = None
            seq.slot = -1
        if seq in self._prefilling:
            self._prefilling.remove(seq)
        seq.prefilling = False
        seq.finish = reason
        self._note_finished(seq, reason)
        seq.out_queue.put_nowait(EngineOutput.final(reason).to_dict())
        self._wake.set()

    def _note_finished(self, seq: Sequence, reason: str) -> None:
        """Request-level observability at finish: the latency summary for
        subscribe_requests observers (histograms) and the request's
        submit→finish span on the trace plane."""
        now = time.perf_counter()
        summary = {
            "request_id": seq.ctx.id,
            "finish_reason": reason,
            "prompt_tokens": seq.prompt_len,
            "tokens": seq.generated,
            "tenant": seq.tenant,
            # prefix/offload ledger (stamped at page reservation): HBM
            # prefix blocks reused, host-tier blocks restored, host hits
            # the restore gate declined (+ why) — per-request truth the
            # bench goodput section and dashboards aggregate
            "prefix": {
                "reused_blocks": seq.blocks_reused,
                "restored_blocks": seq.blocks_restored,
                "declined_blocks": seq.blocks_declined,
                "gate_reason": seq.gate_reason,
            },
            "queue_wait_s": (
                seq.t_admit - seq.t_submit
                if seq.t_admit and seq.t_submit else None
            ),
            "ttft_s": (
                seq.t_first_emit - seq.t_submit
                if seq.t_first_emit and seq.t_submit else None
            ),
            "itl_s": (
                (now - seq.t_first_emit) / (seq.generated - 1)
                if seq.t_first_emit and seq.generated > 1 else None
            ),
            # ttft_s split: admit -> first token on the host (the fetch
            # that carried it landed) -> its out_queue put; the identity
            # ttft_s = queue_wait_s + prefill_s + first_emit_s holds for
            # a request not preempted after its first token (a
            # re-admission restamps queue_wait_s)
            "prefill_s": seq.prefill_s,
            "first_emit_s": seq.first_emit_s,
            "prefill_chunks": seq.prefill_chunks,
        }
        # record the request span BEFORE notifying observers: an
        # observer can dump a forensic artifact for this very request
        # (SloTracker breach -> flight recorder), and the artifact's
        # trace slice must already contain the submit→finish span
        if tracing.enabled() and seq.t_submit:
            tracing.complete(
                "request", seq.t_submit, now, cat="request",
                req=seq.ctx.id, finish_reason=reason,
                prompt_tokens=seq.prompt_len, tokens=seq.generated,
            )
        # orphan watch: if this request still holds pages after its
        # release path ran (a skipped release, a lost frame), the next
        # ledger audit attributes the leak to this request id
        self.kv_ledger.request_finished(seq.ctx.id)
        if self._hybrid:
            self.kv_ledger_win.request_finished(seq.ctx.id)
        for cb in self._request_observers:
            try:
                cb(summary)
            except Exception:
                log.exception("request observer failed")
