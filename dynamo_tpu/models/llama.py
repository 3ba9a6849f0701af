"""Pure-functional Llama-family forward over a paged KV cache.

One `forward()` serves prefill, chunked prefill and decode (see
dynamo_tpu/ops/attention.py). Parameters are a plain pytree (dict of
arrays, per-layer list) so sharding is an external concern
(dynamo_tpu/parallel/mesh.py) and the same function runs on CPU tests,
a single TPU chip, or a pjit mesh — XLA propagates the shardings.

The reference never owns a model forward (it delegates to vLLM/sglang,
reference: lib/engines/vllm0_8/src/lib.rs, SURVEY.md §2.3); this module is
the "native engine" the TPU build adds (SURVEY.md §7 step 3).

Weight layout: [in_features, out_features] (transposed from HF) so matmuls
are `x @ w` — the natural MXU orientation.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import FULL, MAMBA, WINDOW, ModelConfig
from dynamo_tpu.ops.attention import (
    latent_attention,
    paged_attention,
    write_kv_slots,
)
from dynamo_tpu.ops.norm import rms_norm
from dynamo_tpu.ops.quant import (
    dequantize_kv_rows,
    is_quantized,
    mm,
    quant_matmul,
    quantize_kv_rows,
)
from dynamo_tpu.ops.rope import (
    apply_rope,
    pairs_to_halves,
    rope_cos_sin,
    rope_inv_freq,
    softmax_scale,
)

# Names on the device's operations: `jax.named_scope` puts its name into
# every traced operation's HLO metadata (`op_name`), where a profile
# finds it (benchmark/lib/trace_host.py: attn.qkv / attn.rope /
# attn.kv_write / attn.kernel / attn.o, mlp.gate_up / mlp.down, norm,
# head; the engine adds sample; latent attention is attn.mla_q /
# attn.mla_kv_a / attn.rope / attn.kv_write / attn.mla_absorb /
# attn.mla_kernel / attn.mla_o, an expert layer mlp.moe_router /
# mlp.moe_dispatch / mlp.moe_experts / mlp.moe_combine / mlp.moe_shared,
# models/moe.py; the boundaries of a residual of several streams are
# attn.mhc / mlp.mhc, and inside them mhc.maps / mhc.pre / mhc.post,
# models/mhc.py). Metadata only: the compiled code is
# the same without it. The persistent compile cache's key is the same
# only for a program without a pallas kernel: a kernel's serialized body
# keeps its own source locations, which the key does not strip, so a
# scope around the call re-keys the step programs once, as any edit
# that moves the caller's lines does (PERF.md, Findings, PR 24).
_norm = jax.named_scope("norm")(rms_norm)

Params = dict[str, Any]


class AttnSpec:
    """How attention reads (and the step writes) the paged KV pool — one of
    three modes, chosen statically at trace time by which fields are
    populated:

    - gather (oracle / prefill): `slot_matrix` [B, C] position-ordered
      slots; new KV is scattered by `write_kv_slots`, then
      `ops.attention.paged_attention` (pure jnp, any backend) reads it.
    - pallas decode, fused write (T==1): `block_tables` [B, W] page ids +
      `lengths` [B] attended-KV counts + `write_pos` [B] (-1 = skip); the
      flash paged kernel (`ops.pallas_attention`) injects the new token's
      KV into its page in VMEM, writes only that page back, and attends —
      no XLA scatter on the decode path.
    - pallas decode, read-only: as above with `write_pos=None`; KV is
      scattered first (oracle write), the kernel only reads.

    Registered as a pytree with `page_size`/`interpret`/`mesh` as static
    aux data so they stay Python values under jit.

    `mesh` (optional, static — jax Mesh objects hash) requests tensor-
    parallel execution of the pallas kernel: the caller's q/new-KV/pools
    are head-sharded over the mesh's `tp` axis, and `_attn_block` wraps
    the kernel in `jax.shard_map` so each shard runs it on its local KV
    heads (attention is per-head; no collectives needed inside).
    """

    def __init__(self, slot_matrix=None, block_tables=None, lengths=None,
                 write_pos=None, page_size: int = 16, interpret: bool = False,
                 mesh=None, write_tables=None, q_pos0=None, ring: bool = False,
                 kv_tp: int = 1, prefix_cols: int = 0, int4_groups: int = 0,
                 win=None, write_slots=None, state_slots=None):
        # a recurrent model's MAMBA layers (models/mamba2.py): the state
        # slot of each row, [B] int32; None = row b is slot b (a decode
        # program) and for every model that keeps no state
        self.state_slots = state_slots
        # a hybrid model's WINDOW layers read their own pools through
        # their own page ids: `win` is a second AttnSpec holding that
        # kind's slot matrix / block tables / write tables (lengths,
        # write_pos and q_pos0 are the sequence's, shared) and its
        # `write_slots` (gather-mode row scatter; the full kind's ride
        # `forward`'s argument as ever). None for every other model.
        self.win = win
        self.write_slots = write_slots
        self.slot_matrix = slot_matrix
        self.block_tables = block_tables
        self.lengths = lengths
        self.write_pos = write_pos
        self.page_size = page_size
        self.interpret = interpret
        self.mesh = mesh
        # [n_pages] page ids: prefill writes whole pages via the pallas
        # page-scatter kernel instead of the serialized XLA row scatter
        self.write_tables = write_tables
        # [B] chunk start positions (page-aligned): with block_tables +
        # lengths (=valid chunk rows) selects the pallas flash prefill
        self.q_pos0 = q_pos0
        # long-context sequence parallelism: whole-prompt prefill with the
        # token axis sharded over the mesh's sp axis — attention runs as a
        # ring over ICI (ops/ring_attention.py), KV still lands in the pool
        self.ring = ring
        # tp degree of the int8-KV scale pools' row layout (static; only
        # consulted when the cache is quantized)
        self.kv_tp = kv_tp
        # ring cached-prefix gather width in SLOTS (static bucket over
        # the group's cached pages; bounds the per-layer prefix gather)
        self.prefix_cols = prefix_cols
        # int4 nibble-packed KV pools (static): 0 = off (bf16/int8 per
        # the pools' dtypes), n > 0 = int4 with n scale groups per head
        # (S = K*n scale channels; the pallas kernels require n == 1,
        # i.e. per-token-per-kv-head scales — finer groups are
        # gather-backend only, enforced at engine init)
        self.int4_groups = int4_groups

    @classmethod
    def gather(cls, slot_matrix, write_tables=None, page_size: int = 16,
               interpret: bool = False, mesh=None, block_tables=None,
               q_pos0=None, lengths=None, kv_tp: int = 1,
               int4_groups: int = 0):
        return cls(slot_matrix=slot_matrix, write_tables=write_tables,
                   page_size=page_size, interpret=interpret, mesh=mesh,
                   block_tables=block_tables, q_pos0=q_pos0, lengths=lengths,
                   kv_tp=kv_tp, int4_groups=int4_groups)

    @classmethod
    def ring(cls, slot_matrix, mesh, page_size: int = 16, q_pos0=None,
             prefix_cols: int = 0, kv_tp: int = 1, int4_groups: int = 0):
        """sp-sharded long-context prefill: ring attention over the chunk.
        `q_pos0` [B] marks a cached-prefix continuation — the chunk is
        the uncached tail and the cached pool rows (gathered over the
        first `prefix_cols` slot columns only) join as extra
        online-softmax blocks (None = whole-prompt, no prefix pass).
        `kv_tp` must match the engine's mesh tp on int8-KV pools — the
        scale-pool row layout is tp-blocked (ops/quant.kv_scale_subl)."""
        return cls(slot_matrix=slot_matrix, mesh=mesh, page_size=page_size,
                   ring=True, q_pos0=q_pos0, prefix_cols=prefix_cols,
                   kv_tp=kv_tp, int4_groups=int4_groups)

    @classmethod
    def pallas_decode(cls, block_tables, lengths, page_size, write_pos=None,
                      interpret=False, mesh=None, kv_tp: int = 1,
                      int4_groups: int = 0):
        return cls(
            block_tables=block_tables,
            lengths=lengths,
            write_pos=write_pos,
            page_size=page_size,
            interpret=interpret,
            mesh=mesh,
            kv_tp=kv_tp,
            int4_groups=int4_groups,
        )


jax.tree_util.register_pytree_node(
    AttnSpec,
    lambda s: (
        (s.slot_matrix, s.block_tables, s.lengths, s.write_pos,
         s.write_tables, s.q_pos0, s.win, s.write_slots, s.state_slots),
        (s.page_size, s.interpret, s.mesh, s.ring, s.kv_tp, s.prefix_cols,
         s.int4_groups),
    ),
    lambda aux, children: AttnSpec(
        slot_matrix=children[0], block_tables=children[1], lengths=children[2],
        write_pos=children[3], write_tables=children[4], q_pos0=children[5],
        win=children[6], write_slots=children[7], state_slots=children[8],
        page_size=aux[0], interpret=aux[1], mesh=aux[2], ring=aux[3],
        kv_tp=aux[4], prefix_cols=aux[5], int4_groups=aux[6],
    ),
)


class KVCache(NamedTuple):
    """Per-layer flat slot pools: k/v are length-L tuples of
    [num_slots, K*Hd] arrays.

    Three deliberate layout choices (all measured on v5e):

    - per-layer buffers (not one stacked [L, ...] array) so each layer's
      pool aliases straight through jit donation and the Pallas kernels —
      the stacked layout forced an unstack/restack copy of the whole
      cache every step (~36 ms at 1.3 GB);
    - slots x (K*Hd) 2-D shape: for [N, K, Hd] XLA picks layout
      major_to_minor=(1, 2, 0) — the slot dim minor-most — which makes a
      "page" a strided scatter across the whole pool and every page DMA
      ~15x slower. [N, K*Hd] keeps row-major tiling, so a page
      ([page_size, K*Hd]) is one contiguous DMA and the reshape to
      [num_pages, page_size, K*Hd] is a free bitcast;
    - a quantized pool stays in HBM for the whole step, and every kernel
      that takes one SAYS so (`ops/pallas_attention.in_hbm` on the
      operand, `hbm_out` on the aliased result). Unsaid, XLA's
      memory-space assignment treats a loop-carried buffer that fits
      VMEM as a prefetch candidate: a 3.3 MB f32 scale pool fits, a
      26 MB K pool does not, so each of the 64 scale pools went to VMEM
      in four `slice-start/-done` pieces before its decode kernel and
      came back by `copy-start/-done` after it, on every step of the
      decode scan: 3.5-4.5 ms of a 19.4 ms step, 23% / 42% of the
      device's time in the two benchmark cells (PERF.md, PR 29). The
      kernels only DMA single pages out of the pools. A later change
      must not hand a quantized pool to a pallas_call without `in_hbm`
      (tests/test_tpu_compile.py compiles the scan for a described v5e
      and fails on any such move).

    int8 KV mode (`kv_quant="int8"`): k/v hold int8 and `ks`/`vs` hold
    the per-token-per-kv-head f32 scale pools in the page-blocked
    transposed layout `[num_pages, SUBL, page_size]` (tokens in lanes —
    the only layout Mosaic can DMA/slice; see ops/quant.py). Decode
    attention streams every live page per step, so int8 pages halve the
    decode phase's dominant HBM traffic; the scale page adds SUBL*S*4
    bytes per K*Hd*S-byte page (~6% at 8B dims). ks/vs are None in
    unquantized mode.

    Latent cache (`cfg.latent`, docs/kv_cache.md "Latent pools"): ONE
    pool a layer, `k`, whose row is a token's `[c ; k_r]` (the normed
    latent, then the rotated shared key: 512 + 64 values for
    DeepSeek-V2, in the 640 lanes such a row occupies:
    `ModelConfig.latent_pool_width`), no head axis; `v` is None: the values ARE the first
    `kv_lora_rank` columns of the same row, and the decode kernel reads
    each row once (ops/pallas_mla.py). Pages, block tables and slots are
    the same as for K/V pools.

    Hybrid cache (`cfg.hybrid`, docs/kv_cache.md "Window pools"): a
    layer's pools have its KIND's shape (`ModelConfig.attn_kind`: keys K
    x Kd wide, values K x Vd) and its kind's number of slots: window
    layers' pools are smaller and indexed by their own page ids, so
    `num_slots` is the full kind's and nothing stacks.

    State pools (`cfg.recurrent`, docs/kv_cache.md "State pools"): a
    MAMBA layer keeps no pages, so `k` / `v` hold the pools of the
    layers that do (`cfg.paged_layers`, in order) and `ssm` / `conv` one
    pool a MAMBA layer, a row a decode slot: `ssm[i]` [S, H, P, N] the
    recurrent state, `conv[i]` [S, d_conv - 1, width] the convolution's
    tail. They ride with the pages through every step program, donated
    and returned. None (no leaf, no argument) for every other model."""

    k: tuple
    v: tuple | None
    ks: tuple | None = None
    vs: tuple | None = None
    ssm: tuple | None = None
    conv: tuple | None = None

    @property
    def num_slots(self) -> int:
        return self.k[0].shape[0]

    @property
    def quantized(self) -> bool:
        return self.ks is not None

    @property
    def latent(self) -> bool:
        return self.v is None


def init_kv_cache(
    cfg: ModelConfig, num_slots: int, dtype=jnp.bfloat16,
    kv_quant: str | None = None, page_size: int = 16, tp: int = 1,
    packed: bool = False, kv_quant_group: int | None = None,
    sharding=None, scale_sharding=None, win_slots: int | None = None,
    state_slots: int = 0,
) -> KVCache:
    """`sharding` / `scale_sharding` create the data / scale pools shard
    by shard on their devices: the engine sizes the pool to each
    device's free memory, so a layer's whole unsharded pool is tp times
    what one device can hold and must never be built in one place."""
    if cfg.recurrent:
        # pools of pages for the layers that keep pages, `state_slots`
        # rows of state for the layers that keep a state
        if kv_quant is not None or cfg.hybrid or cfg.latent:
            raise ValueError(
                f"state pools ('{cfg.name}') are served beside ONE kind of "
                "K/V pool in the model's dtype: no kv_quantization, window "
                "layers or latent attention"
            )
        from dynamo_tpu.models.mamba2 import init_state_pools

        shape = (num_slots, cfg.num_kv_heads * cfg.head_dim)
        whole = sharding
        if isinstance(sharding, jax.sharding.NamedSharding):
            whole = jax.sharding.NamedSharding(
                sharding.mesh, jax.sharding.PartitionSpec())
        ssm, conv = init_state_pools(cfg, state_slots, dtype, whole)
        return KVCache(
            k=tuple(jnp.zeros(shape, dtype, device=sharding)
                    for _ in cfg.paged_layers),
            v=tuple(jnp.zeros(shape, dtype, device=sharding)
                    for _ in cfg.paged_layers),
            ssm=ssm, conv=conv,
        )
    if cfg.hybrid:
        # pools per kind: `num_slots` rows in a full-attention layer,
        # `win_slots` in a window layer, each its kind's widths
        if kv_quant is not None:
            raise ValueError(
                f"kv_quantization={kv_quant!r} with window and full "
                f"attention layers ('{cfg.name}'): the two kinds of pool "
                "are served in the model's dtype only"
            )
        kinds = [cfg.attn_kind(cfg.layer_kind(l)) for l in range(cfg.num_layers)]
        rows = [
            (win_slots or num_slots) if kd.window else num_slots
            for kd in kinds
        ]
        return KVCache(
            k=tuple(jnp.zeros((n, kd.k_width), dtype, device=sharding)
                    for n, kd in zip(rows, kinds)),
            v=tuple(jnp.zeros((n, kd.v_width), dtype, device=sharding)
                    for n, kd in zip(rows, kinds)),
        )
    if cfg.latent:
        if kv_quant is not None:
            raise ValueError(
                f"kv_quantization={kv_quant!r} with latent attention "
                f"('{cfg.name}'): the latent pool is served in the model's "
                "dtype only (no quantized latent rows yet)"
            )
        return KVCache(
            k=tuple(
                jnp.zeros((num_slots, cfg.latent_pool_width), dtype,
                          device=sharding)
                for _ in range(cfg.num_layers)
            ),
            v=None,
        )
    shape = (num_slots, cfg.num_kv_heads * cfg.head_dim)
    if kv_quant is not None:
        if kv_quant not in ("int8", "int4"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r}; expected 'int8' or 'int4'"
            )
        from dynamo_tpu.ops.quant import init_kv_scale_pool

        # scale channels: int8 = one per kv head; int4 = K * groups-per-
        # head (kv_quant_group features share a scale, default head_dim)
        s_ch = cfg.num_kv_heads
        if kv_quant == "int4":
            from dynamo_tpu.ops.quant import int4_scale_channels

            s_ch = int4_scale_channels(
                cfg.num_kv_heads, cfg.head_dim, kv_quant_group
            )
            if shape[1] % 2:
                raise ValueError("int4 KV needs an even K*Hd")
            # nibble-packed data rows are HALF the int8 width
            shape = (num_slots, shape[1] // 2)

        num_pages = num_slots // page_size
        if packed:
            # int32-packed data pools (ops/quant.pack_kv_slots layout):
            # f32-class DMA tiling for the pallas kernels, which bitcast
            # back to int8 in VMEM. Serving-path (pallas) engines only.
            if num_slots % 4:
                raise ValueError("packed quantized KV needs num_slots % 4 == 0")
            pshape = (num_slots // 4, shape[1])
            return KVCache(
                k=tuple(
                    jnp.zeros(pshape, jnp.int32, device=sharding) for _ in range(cfg.num_layers)
                ),
                v=tuple(
                    jnp.zeros(pshape, jnp.int32, device=sharding) for _ in range(cfg.num_layers)
                ),
                ks=tuple(
                    init_kv_scale_pool(
                        num_pages, page_size, s_ch, tp, scale_sharding
                    )
                    for _ in range(cfg.num_layers)
                ),
                vs=tuple(
                    init_kv_scale_pool(
                        num_pages, page_size, s_ch, tp, scale_sharding
                    )
                    for _ in range(cfg.num_layers)
                ),
            )
        return KVCache(
            k=tuple(jnp.zeros(shape, jnp.int8, device=sharding) for _ in range(cfg.num_layers)),
            v=tuple(jnp.zeros(shape, jnp.int8, device=sharding) for _ in range(cfg.num_layers)),
            ks=tuple(
                init_kv_scale_pool(
                    num_pages, page_size, s_ch, tp, scale_sharding
                )
                for _ in range(cfg.num_layers)
            ),
            vs=tuple(
                init_kv_scale_pool(
                    num_pages, page_size, s_ch, tp, scale_sharding
                )
                for _ in range(cfg.num_layers)
            ),
        )
    return KVCache(
        k=tuple(jnp.zeros(shape, dtype, device=sharding) for _ in range(cfg.num_layers)),
        v=tuple(jnp.zeros(shape, dtype, device=sharding) for _ in range(cfg.num_layers)),
    )


def _attn_block(
    lp: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,          # [B, T, D]
    cos: jnp.ndarray,        # [B, T, rotary width] at the kind's rope base
    sin: jnp.ndarray,
    kv_k: jnp.ndarray,       # [N, K*Kd] this layer's pools (int8 when quantized)
    kv_v: jnp.ndarray,       # [N, K*Vd]
    write_slots: jnp.ndarray,   # [B*T] int32
    attn: "AttnSpec",
    positions: jnp.ndarray,     # [B, T]
    kind: int = FULL,        # the layer's kind (`cfg.layer_kind`): its KV
    # heads, key width Kd, value width Vd, window and sink are
    # `cfg.attn_kind(kind)`. A model of one kind has Kd == Vd == head_dim,
    # no window and no sink; in a hybrid model cos / sin, `attn` and
    # `write_slots` are the kind's own
    kv_ks=None,              # [N, K] f32 scale pools (int8 KV mode)
    kv_vs=None,
    tp_axis=None,  # set when running INSIDE a shard_map (manual tp):
    # row-parallel projections then need an explicit psum
    tp_overlap: bool = False,  # latency-hiding manual tp (requires
    # tp_axis): x arrives ROW-SCATTERED [R/tp, D]; qkv ride the
    # all-gather-fused ring matmuls and the output projection ends in a
    # ring reduce-scatter instead of a psum (parallel/tp_overlap.py)
    bt_shape=None,  # static (b, t) — scattered x has no batch/time axes
):
    if tp_overlap:
        b, t = bt_shape
    else:
        b, t, _ = x.shape
    spec = cfg.attn_kind(kind)
    h, kh, hd, vd = cfg.num_heads, spec.kv_heads, spec.k_dim, spec.v_dim
    sink = lp["sink"].astype(jnp.float32) if spec.sink else None

    def one_kind_only(path: str) -> None:
        if spec.window or spec.sink or vd != hd:
            raise ValueError(
                f"{path} is written for one width of key and value and "
                "every position attended: no window, sink or narrower "
                f"values ('{cfg.name}')"
            )

    if tp_axis is not None:
        # manual tp: this shard holds its local slice of the heads
        tpn = jax.lax.axis_size(tp_axis)
        h //= tpn
        kh //= tpn
    quant = kv_ks is not None
    # int4 tier: nibble-packed half-width pools with s_ch = K * groups
    # scale channels; quantize-once rows at KV-write time, same as int8
    int4 = quant and attn.int4_groups > 0
    s_ch = kh * attn.int4_groups if int4 else kh

    @jax.named_scope("attn.kv_write")
    def _quant_rows(rows):
        """Quantize fresh KV rows for the pool's tier (int8 or int4)."""
        if int4:
            from dynamo_tpu.ops.quant import quantize_kv_rows_int4

            return quantize_kv_rows_int4(rows, kh, hd // attn.int4_groups)
        return quantize_kv_rows(rows, kh)

    @jax.named_scope("attn.kv_write")
    def _write_rows(kv_k, kv_v, kv_ks, kv_vs, kr, vr):
        """Row-scatter this chunk's KV into the pools (ring and gather
        modes); quantized pools quantize the rows and scatter the scales
        in the tp-blocked pool layout."""
        if kv_k.dtype == jnp.int32:
            # int32-PACKED quantized pools (ops/quant.pack_kv_slots)
            # carry 4 token rows per int32 row: the write is byte-lane
            # surgery on the packed rows (ops/quant.scatter_packed_kv_rows)
            # plus the same scale scatter as the dense int8 tier. This is
            # what lets mixed/spec-verify steps land decode rows MID-PAGE
            # on the pallas+quantized serving path; whole-page prefill
            # writes still prefer the pallas page-scatter kernel.
            from dynamo_tpu.ops.quant import (
                scatter_kv_scales,
                scatter_packed_kv_rows,
            )

            kr, krs = _quant_rows(kr)
            vr, vrs = _quant_rows(vr)
            kv_ks = scatter_kv_scales(kv_ks, write_slots, krs, s_ch, attn.kv_tp)
            kv_vs = scatter_kv_scales(kv_vs, write_slots, vrs, s_ch, attn.kv_tp)
            kv_k = scatter_packed_kv_rows(kv_k, write_slots, kr)
            kv_v = scatter_packed_kv_rows(kv_v, write_slots, vr)
            return kv_k, kv_v, kv_ks, kv_vs
        if quant:
            from dynamo_tpu.ops.quant import scatter_kv_scales

            kr, krs = _quant_rows(kr)
            vr, vrs = _quant_rows(vr)
            kv_ks = scatter_kv_scales(kv_ks, write_slots, krs, s_ch, attn.kv_tp)
            kv_vs = scatter_kv_scales(kv_vs, write_slots, vrs, s_ch, attn.kv_tp)
        kv_k, kv_v = write_kv_slots(kv_k, kv_v, write_slots, kr, vr)
        return kv_k, kv_v, kv_ks, kv_vs

    with jax.named_scope("attn.qkv"):
        if tp_overlap:
            # one gather ring serves all three projections: x's row chunks
            # circulate over ICI while the resident chunk multiplies into
            # the local head shards — the all-gather half of the decomposed
            # psum never runs as a standalone collective
            from dynamo_tpu.parallel import tp_overlap as _ov

            q, k, v = _ov.ring_ag_matmul(
                x, (lp["wq"], lp["wk"], lp["wv"]), tp_axis
            )
            # drop the ring's row padding; attention never sees pad rows
            q, k, v = q[: b * t], k[: b * t], v[: b * t]
        else:
            q = mm(x, lp["wq"])
            k = mm(x, lp["wk"])
            v = mm(x, lp["wv"])
        if cfg.attn_bias:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        if cfg.attn_value_scale != 1.0:
            v = (v * cfg.attn_value_scale).astype(v.dtype)
        if cfg.attn_scale:
            # the configuration's own score scale: every reader below
            # multiplies scores by head_dim ** -0.5, so the rest rides
            # the query (Granite: 1/64 over 1/8, a power of two: exact)
            q = (q * (cfg.attn_scale * hd ** 0.5)).astype(q.dtype)
        q = q.reshape(b, t, h, hd)
        k = k.reshape(b, t, kh, hd)
        v = v.reshape(b, t, kh, vd)

    if cfg.use_rope:
        with jax.named_scope("attn.rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    kernel = jax.named_scope(
        "attn.swa_kernel" if spec.window else "attn.kernel"
    )

    if attn.block_tables is not None and attn.write_pos is not None:
        from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention

        fused = functools.partial(
            fused_paged_decode_attention,
            page_size=attn.page_size,
            interpret=attn.interpret,
            int4=int4,
            # the kind's STATIC window (the last attended position is
            # `lengths - 1`; the kernel makes the starts): short enough,
            # it takes the work item that holds several sequences
            window=spec.window,
            sink=sink,
        )
        new_k = k[:, 0].reshape(b, kh * hd)
        new_v = v[:, 0].reshape(b, kh * vd)
        if quant:
            # quantize the new rows at trace time; the kernel injects the
            # quantized rows + scale columns into their pages in VMEM.
            # Dense [B, S] scales are padded into the pool's sublane-row
            # layout so each tp shard receives an aligned [B, >=8] block.
            # (The pallas kernels require int4_groups == 1, so S == K and
            # the sublane layout is identical to the int8 tier's.)
            from dynamo_tpu.ops.quant import _scale_rows, kv_scale_subl

            new_k, nks_dense = _quant_rows(new_k)
            new_v, nvs_dense = _quant_rows(new_v)
            subl = kv_scale_subl(s_ch, attn.kv_tp)
            rows = _scale_rows(s_ch, attn.kv_tp)
            new_ks = jnp.ones((b, subl), jnp.float32).at[:, rows].set(nks_dense)
            new_vs = jnp.ones((b, subl), jnp.float32).at[:, rows].set(nvs_dense)
        if attn.mesh is not None:
            # tensor parallel: every array argument that carries heads is
            # tp-sharded (q over H, new rows / pools over the folded K*Hd
            # — whole KV heads per shard by layout); tables/lengths/
            # write_pos replicate. Each shard runs the kernel on its
            # local heads — attention has no cross-head math.
            P = jax.sharding.PartitionSpec
            # quant adds scale pools [P, SUBL, S] + new scale rows [B, SUBL]
            scale_in = (
                (P(None, "tp", None), P(None, "tp", None),
                 P(None, "tp"), P(None, "tp")) if quant else ()
            )
            scale_out = (
                (P(None, "tp", None), P(None, "tp", None)) if quant else ()
            )
            fused = jax.shard_map(
                fused,
                mesh=attn.mesh,
                in_specs=(
                    P(None, "tp", None), P(None, "tp"), P(None, "tp"),
                    P(None, "tp"), P(None, "tp"), P(), P(), P(),
                    *scale_in,
                ),
                out_specs=(
                    P(None, "tp", None), P(None, "tp"), P(None, "tp"),
                    *scale_out,
                ),
                check_vma=False,
            )
        fused = kernel(fused)  # it also writes the new rows' pages
        if quant:
            out, kv_k, kv_v, kv_ks, kv_vs = fused(
                q[:, 0], new_k, new_v, kv_k, kv_v,
                attn.block_tables, attn.lengths, attn.write_pos,
                kv_ks, kv_vs, new_ks, new_vs,
            )
        else:
            out, kv_k, kv_v = fused(
                q[:, 0], new_k, new_v, kv_k, kv_v,
                attn.block_tables, attn.lengths, attn.write_pos,
            )
        out = out[:, None]
    elif attn.write_tables is not None:
        # prefill page-scatter: whole [page, K*Hd] blocks via the pallas
        # kernel (XLA's row scatter serializes, ~15x slower). Rows pad up
        # to whole pages; tail garbage lands in the sequence's own
        # not-yet-valid positions (masked) or the trash page.
        from dynamo_tpu.ops.pallas_kv_write import paged_kv_write

        ps = attn.page_size
        t_pad = -(-t // ps) * ps
        k2 = k.reshape(b, t, kh * hd)
        v2 = v.reshape(b, t, kh * vd)
        ks2 = vs2 = None
        if quant:
            k2, ks2 = _quant_rows(k2)
            v2, vs2 = _quant_rows(v2)
        if t_pad != t:
            k2 = jnp.pad(k2, ((0, 0), (0, t_pad - t), (0, 0)))
            v2 = jnp.pad(v2, ((0, 0), (0, t_pad - t), (0, 0)))
            if quant:
                # padding scale 1.0 (matches the pool's init value)
                ks2 = jnp.pad(ks2, ((0, 0), (0, t_pad - t), (0, 0)),
                              constant_values=1.0)
                vs2 = jnp.pad(vs2, ((0, 0), (0, t_pad - t), (0, 0)),
                              constant_values=1.0)
        n_pg = b * (t_pad // ps)
        # row width is kh*hd (values kh*vd), except the int4 tier
        # nibble-packs rows to half width at quantize time — read it off
        # the rows themselves
        k_pages = k2.reshape(n_pg, ps, k2.shape[-1])
        v_pages = v2.reshape(n_pg, ps, v2.shape[-1])
        if quant and kv_k.dtype == jnp.int32:
            # int32-packed pools: pack the chunk's source pages to match
            # (4 token rows per int32 row, ops/quant.pack_kv_slots)
            from dynamo_tpu.ops.quant import pack_kv_slots

            k_pages = pack_kv_slots(k_pages)
            v_pages = pack_kv_slots(v_pages)
        ks_pages = vs_pages = None
        if quant:
            from dynamo_tpu.ops.quant import scales_to_page_tiles

            ks_pages = scales_to_page_tiles(
                ks2.reshape(b * t_pad, s_ch), ps, s_ch, attn.kv_tp
            )
            vs_pages = scales_to_page_tiles(
                vs2.reshape(b * t_pad, s_ch), ps, s_ch, attn.kv_tp
            )
        wr = functools.partial(
            paged_kv_write, page_size=ps, interpret=attn.interpret
        )
        if attn.mesh is not None:
            P = jax.sharding.PartitionSpec
            # scale pools/pages [*, SUBL, S]: heads in sublanes
            scale_in = (
                (P(None, "tp", None), P(None, "tp", None),
                 P(None, "tp", None), P(None, "tp", None)) if quant else ()
            )
            scale_out = (
                (P(None, "tp", None), P(None, "tp", None)) if quant else ()
            )
            wr = jax.shard_map(
                wr,
                mesh=attn.mesh,
                in_specs=(
                    P(None, "tp"), P(None, "tp"), P(),
                    P(None, None, "tp"), P(None, None, "tp"),
                    *scale_in,
                ),
                out_specs=(P(None, "tp"), P(None, "tp"), *scale_out),
                check_vma=False,
            )
        wr = jax.named_scope("attn.kv_write")(wr)
        if quant:
            kv_k, kv_v, kv_ks, kv_vs = wr(
                kv_k, kv_v, attn.write_tables, k_pages, v_pages,
                kv_ks, kv_vs, ks_pages, vs_pages,
            )
        else:
            kv_k, kv_v = wr(kv_k, kv_v, attn.write_tables, k_pages, v_pages)
        if attn.block_tables is not None and attn.q_pos0 is not None:
            # flash prefill: online softmax over streamed pages — never
            # materializes the [B, K, G, T, C] logits/probs the gather
            # oracle pays ~13 GB/layer of HBM traffic for
            from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

            fl = functools.partial(
                flash_prefill_attention,
                page_size=ps, interpret=attn.interpret, int4=int4,
                window=spec.window, sink=sink,
            )
            if attn.mesh is not None:
                P = jax.sharding.PartitionSpec
                scale_specs = (
                    (P(None, "tp", None), P(None, "tp", None)) if quant else ()
                )
                fl = jax.shard_map(
                    fl,
                    mesh=attn.mesh,
                    in_specs=(
                        P(None, None, "tp", None), P(None, "tp"),
                        P(None, "tp"), P(), P(), P(), *scale_specs,
                    ),
                    out_specs=P(None, None, "tp", None),
                    check_vma=False,
                )
            fl = kernel(fl)
            if quant:
                out = fl(
                    q, kv_k, kv_v, attn.block_tables, attn.q_pos0,
                    attn.lengths, kv_ks, kv_vs,
                )
            else:
                out = fl(
                    q, kv_k, kv_v, attn.block_tables, attn.q_pos0,
                    attn.lengths,
                )
        else:
            out = kernel(paged_attention)(
                q, kv_k, kv_v, attn.slot_matrix, positions,
                k_scales=kv_ks, v_scales=kv_vs, scale_tp=attn.kv_tp,
                int4_groups=attn.int4_groups or None,
                window=spec.window, sink=sink,
            )
    elif attn.ring and attn.mesh is not None:
        one_kind_only("ring attention")
        # sp-sharded long-context prefill: KV lands in the (sp-replicated)
        # pool for later decode; attention rings the fresh chunk blocks
        # around the sp axis (ops/ring_attention.py). With q_pos0 set the
        # chunk is the UNCACHED TAIL of a prefix-cache hit: the cached
        # rows are gathered from the pool and attended as one extra
        # online-softmax block before the ring spins.
        #
        # int8 KV composes: the ring itself attends the FRESH chunk's
        # bf16 k/v (never the pool), so quantization only touches the
        # pool write (int8 rows + scale scatter, same as the gather
        # path) and the cached-prefix gather (dequantize on the way out)
        from dynamo_tpu.ops.ring_attention import ring_attention_sharded

        kv_k, kv_v, kv_ks, kv_vs = _write_rows(
            kv_k, kv_v, kv_ks, kv_vs,
            k.reshape(b * t, kh * hd), v.reshape(b * t, kh * vd),
        )
        if attn.q_pos0 is not None:
            # bounded gather: only the page bucket that actually holds
            # cached rows — NOT the max-context slot matrix (a 128k
            # config would otherwise materialize ~max_model_len rows per
            # layer for a one-page hit)
            c = min(attn.prefix_cols or attn.slot_matrix.shape[1],
                    attn.slot_matrix.shape[1])
            sm = attn.slot_matrix[:, :c]
            if quant:
                from dynamo_tpu.ops.quant import gather_kv_scales

                flat = sm.reshape(-1)
                if int4:
                    from dynamo_tpu.ops.quant import dequantize_kv_rows_int4

                    pk = dequantize_kv_rows_int4(
                        kv_k[flat],
                        gather_kv_scales(kv_ks, flat, s_ch, attn.kv_tp),
                        kh, out_dtype=x.dtype,
                    ).reshape(b, c, kh, hd)
                    pv = dequantize_kv_rows_int4(
                        kv_v[flat],
                        gather_kv_scales(kv_vs, flat, s_ch, attn.kv_tp),
                        kh, out_dtype=x.dtype,
                    ).reshape(b, c, kh, hd)
                else:
                    pk = dequantize_kv_rows(
                        kv_k[flat],
                        gather_kv_scales(kv_ks, flat, kh, attn.kv_tp),
                        out_dtype=x.dtype,
                    ).reshape(b, c, kh, hd)
                    pv = dequantize_kv_rows(
                        kv_v[flat],
                        gather_kv_scales(kv_vs, flat, kh, attn.kv_tp),
                        out_dtype=x.dtype,
                    ).reshape(b, c, kh, hd)
            else:
                pk = kv_k[sm].reshape(b, c, kh, hd)
                pv = kv_v[sm].reshape(b, c, kh, hd)
            out = kernel(ring_attention_sharded)(
                q, k, v, attn.mesh,
                pos0=attn.q_pos0, prefix_k=pk, prefix_v=pv,
                prefix_len=attn.q_pos0,
            )
        else:
            out = kernel(ring_attention_sharded)(q, k, v, attn.mesh)
    else:
        kv_k, kv_v, kv_ks, kv_vs = _write_rows(
            kv_k, kv_v, kv_ks, kv_vs,
            k.reshape(b * t, kh * hd), v.reshape(b * t, kh * vd),
        )
        if attn.block_tables is not None and attn.q_pos0 is not None:
            # mixed prefill+decode and spec-verify steps on the pallas
            # backend: the WRITE is the row scatter above — decode and
            # verify rows land mid-page, which the page-granular prefill
            # scatter cannot express — and the READ is the ragged flash
            # kernel (per-row q_pos0/q_len; decode rows are q_len=1,
            # verify rows q_len=1+k, chunk rows causal inside the chunk)
            from dynamo_tpu.ops.pallas_attention import ragged_paged_attention

            one_kind_only("the ragged kernel of mixed and verify steps")
            rg = functools.partial(
                ragged_paged_attention,
                page_size=attn.page_size, interpret=attn.interpret,
                int4=int4,
            )
            if attn.mesh is not None:
                P = jax.sharding.PartitionSpec
                scale_specs = (
                    (P(None, "tp", None), P(None, "tp", None)) if quant else ()
                )
                rg = jax.shard_map(
                    rg,
                    mesh=attn.mesh,
                    in_specs=(
                        P(None, None, "tp", None), P(None, "tp"),
                        P(None, "tp"), P(), P(), P(), *scale_specs,
                    ),
                    out_specs=P(None, None, "tp", None),
                    check_vma=False,
                )
            rg = kernel(rg)
            if quant:
                out = rg(
                    q, kv_k, kv_v, attn.block_tables, attn.q_pos0,
                    attn.lengths, kv_ks, kv_vs,
                )
            else:
                out = rg(
                    q, kv_k, kv_v, attn.block_tables, attn.q_pos0,
                    attn.lengths,
                )
        elif attn.block_tables is not None:
            from dynamo_tpu.ops.pallas_attention import paged_decode_attention

            one_kind_only("the read-only decode kernel")
            ro = functools.partial(
                paged_decode_attention,
                page_size=attn.page_size,
                interpret=attn.interpret,
                int4=int4,
            )
            if attn.mesh is not None:
                P = jax.sharding.PartitionSpec
                scale_specs = (
                    (P(None, "tp", None), P(None, "tp", None)) if quant else ()
                )
                ro = jax.shard_map(
                    ro,
                    mesh=attn.mesh,
                    in_specs=(
                        P(None, "tp", None), P(None, "tp"), P(None, "tp"),
                        P(), P(), *scale_specs,
                    ),
                    out_specs=P(None, "tp", None),
                    check_vma=False,
                )
            ro = kernel(ro)
            if quant:
                out = ro(
                    q[:, 0], kv_k, kv_v, attn.block_tables, attn.lengths,
                    kv_ks, kv_vs,
                )[:, None]
            else:
                out = ro(
                    q[:, 0], kv_k, kv_v, attn.block_tables, attn.lengths,
                )[:, None]
        else:
            # `lengths` on a plain gather spec = per-row ragged query
            # lengths (mixed steps); None for the classic single-shape
            # dispatches whose callers slice their own valid columns
            out = kernel(paged_attention)(
                q, kv_k, kv_v, attn.slot_matrix, positions,
                k_scales=kv_ks, v_scales=kv_vs, scale_tp=attn.kv_tp,
                q_lens=attn.lengths,
                int4_groups=attn.int4_groups or None,
                window=spec.window, sink=sink,
            )
    with jax.named_scope("attn.o"):
        if tp_overlap:
            # decomposed psum, half 1: ring reduce-scatter back to the
            # row-scattered residual view (the all-gather half rides the
            # next layer segment's ring matmuls). ring_rs_matmul folds the
            # matmul in so quantized wo keeps its int32 accumulator across
            # the ring (bitwise tp=1 dequant epilogue).
            from dynamo_tpu.parallel import tp_overlap as _ov

            proj = _ov.ring_rs_matmul(
                out.reshape(b * t, h * vd), lp["wo"], tp_axis
            )
        else:
            proj = mm(out.reshape(b, t, h * vd), lp["wo"])
            if tp_axis is not None:
                from dynamo_tpu.parallel.tp_overlap import psum_allreduce

                proj = psum_allreduce(proj, tp_axis)
    return proj, kv_k, kv_v, kv_ks, kv_vs


def _mla_attn_block(
    lp: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,          # [B, T, D]
    cos: jnp.ndarray,        # [B, T, rope width]
    sin: jnp.ndarray,
    pool: jnp.ndarray,       # [N, latent_pool_width] this layer's pool
    write_slots: jnp.ndarray,   # [B*T] int32
    attn: "AttnSpec",
    positions: jnp.ndarray,     # [B, T]
):
    """Latent attention (DeepSeek-V2; with `q_lora_rank` the queries are
    low-rank, `q = RMSNorm(x W_qa) W_qb`) in the ABSORBED form on every path:
    the per-head key expansion W_uk is folded into the query and the
    value expansion W_uv applied after the softmax, so attention is
    multi-query over the cached rows themselves, `score = (q_n W_uk . c
    + q_r . k_r) x s`, `o = (softmax . c) W_uv`. Decode reads the rows
    through the paged kernel (ops/pallas_mla.py, the row written by the
    same kernel); a prefill chunk writes its pages and attends the rows
    gathered through its block table (ops/attention.latent_attention):
    at <= 4,096 tokens the absorbed form's extra flops are a few ms a
    chunk, and one form means prefill and decode cannot disagree about
    the cache. The expanded form is the benchmark's reference.

    Returns (projection [B, T, D], pool)."""
    b, t, _ = x.shape
    h, rank = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    width = pool.shape[1]   # rank + rope, padded to whole lane tiles
    lane_pad = [(0, 0)] * 2 + [(0, width - rank - rope)]
    with jax.named_scope("attn.mla_q"):
        if cfg.q_lora_rank:
            # low-rank queries: both matrices and the norm between them
            c_q = rms_norm(mm(x, lp["w_qa"]), lp["q_norm"], cfg.rms_norm_eps)
            q = mm(c_q, lp["w_qb"]).reshape(b, t, h, nope + rope)
        else:
            q = mm(x, lp["wq"]).reshape(b, t, h, nope + rope)
    with jax.named_scope("attn.mla_kv_a"):
        kva = mm(x, lp["w_kva"])                              # [B, T, W]
        c = rms_norm(kva[..., :rank], lp["kv_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn.rope"):
        q_r = apply_rope(pairs_to_halves(q[..., nope:]), cos, sin)
        k_r = apply_rope(
            pairs_to_halves(kva[..., None, rank:]), cos, sin
        )[..., 0, :]
    with jax.named_scope("attn.mla_absorb"):
        w_kvb = lp["w_kvb"].reshape(rank, h, nope + vd)
        q_abs = jnp.einsum("bthn,rhn->bthr", q[..., :nope], w_kvb[..., :nope])
        # the softmax scale rides the query: the kernel multiplies nothing
        qa = jnp.pad(
            jnp.concatenate([q_abs, q_r], axis=-1) * softmax_scale(cfg),
            [(0, 0)] + lane_pad,
        ).astype(x.dtype)                                     # [B, T, H, W]
        rows = jnp.pad(
            jnp.concatenate([c, k_r], axis=-1), lane_pad
        ).astype(pool.dtype)                                  # [B, T, W]
    kernel = jax.named_scope("attn.mla_kernel")

    if attn.block_tables is not None and attn.write_pos is not None:
        from dynamo_tpu.ops.pallas_mla import mla_paged_decode_attention

        o_lat, pool = kernel(mla_paged_decode_attention)(
            qa[:, 0], rows[:, 0], pool, attn.block_tables, attn.lengths,
            attn.write_pos, rank=rank, page_size=attn.page_size,
            interpret=attn.interpret,
        )
        o_lat = o_lat[:, None]                                # [B, 1, H, rank]
    else:
        ps = attn.page_size
        if attn.write_tables is not None:
            # whole pages through the page writer (chunk starts are
            # page-aligned; the tail of a last page is the sequence's own
            # not-yet-valid positions or the trash page)
            from dynamo_tpu.ops.pallas_mla import latent_page_write

            t_pad = -(-t // ps) * ps
            pages = jnp.pad(rows, ((0, 0), (0, t_pad - t), (0, 0)))
            pool = jax.named_scope("attn.kv_write")(latent_page_write)(
                pool, attn.write_tables,
                pages.reshape(b * (t_pad // ps), ps, width),
                page_size=ps, interpret=attn.interpret,
            )
        else:
            with jax.named_scope("attn.kv_write"):
                pool = pool.at[write_slots].set(rows.reshape(b * t, width))
        if attn.block_tables is not None:
            seen = pool.reshape(-1, ps, width)[attn.block_tables].reshape(
                b, -1, width
            )
        else:
            seen = pool[attn.slot_matrix]                     # [B, C, W]
        q_lens = None if attn.write_tables is not None else attn.lengths
        o_lat = kernel(latent_attention)(
            qa, seen, positions, rank, q_lens=q_lens
        )
    with jax.named_scope("attn.mla_absorb"):
        out = jnp.einsum(
            "bthr,rhv->bthv", o_lat.astype(x.dtype), w_kvb[..., nope:]
        )
    with jax.named_scope("attn.mla_o"):
        proj = mm(out.reshape(b, t, h * vd), lp["wo"])
    return proj, pool


_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


def _mlp_block(
    lp: Params, x: jnp.ndarray, tp_axis=None, act: str = "silu",
    tp_overlap: bool = False,
) -> jnp.ndarray:
    if tp_overlap:
        # x is row-scattered [R/tp, D]; gate/up share one gather ring
        # (chunk i's matmuls run while chunk i+1 is on the wire) and the
        # down projection ends in a ring reduce-scatter, returning the
        # scattered view for the residual add
        from dynamo_tpu.parallel import tp_overlap as _ov

        with jax.named_scope("mlp.gate_up"):
            gate, up = _ov.ring_ag_matmul(
                x, (lp["w_gate"], lp["w_up"]), tp_axis
            )
            hidden = _ACTIVATIONS[act](gate) * up
        with jax.named_scope("mlp.down"):
            return _ov.ring_rs_matmul(hidden, lp["w_down"], tp_axis)
    with jax.named_scope("mlp.gate_up"):
        gate = _ACTIVATIONS[act](mm(x, lp["w_gate"]))
        hidden = gate * mm(x, lp["w_up"])
    with jax.named_scope("mlp.down"):
        out = mm(hidden, lp["w_down"])
        if tp_axis is not None:
            from dynamo_tpu.parallel.tp_overlap import psum_allreduce

            out = psum_allreduce(out, tp_axis)
    return out


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, T] int32
    positions: jnp.ndarray,    # [B, T] int32 absolute positions
    kv: KVCache,
    write_slots: jnp.ndarray,  # [B*T] int32 flat slots for the new tokens (0=trash for pads)
    attn,                      # AttnSpec, or a raw [B, C] slot matrix (gather mode)
    embeds: jnp.ndarray | None = None,       # [B, T, D] multimodal injections
    embeds_mask: jnp.ndarray | None = None,  # [B, T] bool: use embeds row
    moe_stats: list | None = None,  # receives each expert layer's load
    # (models/moe.py `stats`), for a caller that returns it with its tokens
) -> tuple[jnp.ndarray, KVCache]:
    """One model step. Returns (hidden [B, T, D] after final norm, updated kv).

    Logits are computed by `logits()` on the (usually sliced) hidden states
    so prefill only pays the vocab matmul for the last position.
    """
    if not isinstance(attn, AttnSpec):
        attn = AttnSpec.gather(attn)
    # genuine-token mask for the expert layers (padding routes nowhere):
    # fused decode marks inactive rows by write_pos == -1; every other
    # path routes padding's writes to trash slot 0
    real_mask = None
    if cfg.num_experts or cfg.recurrent:
        b_, t_ = tokens.shape
        if attn.write_pos is not None:
            real_mask = (attn.write_pos >= 0)[:, None] & jnp.ones(
                (b_, t_), bool
            )
        else:
            real_mask = write_slots.reshape(b_, t_) != 0
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        # gemma: embedding outputs scaled by sqrt(d)
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if embeds is not None:
        # LLaVA-style injection: image-patch positions take precomputed
        # embeddings instead of the placeholder tokens' lookups
        x = jnp.where(embeds_mask[..., None], embeds.astype(x.dtype), x)
    if cfg.hc_mult > 1:
        # a residual of several streams a token, [B, T, n D]: each starts
        # as the embedding, the layers mix them (`layer_step`), and their
        # sum is the hidden state (models/mhc.py)
        from dynamo_tpu.models import mhc

        x = mhc.expand(x, cfg.hc_mult)

    cos = sin = None
    if cfg.use_rope:
        inv_freq = jnp.asarray(rope_inv_freq(cfg))
        cos, sin = rope_cos_sin(inv_freq, positions)  # [B, T, Hd]
    by_kind = None
    if cfg.hybrid:
        # per kind of layer: its rope base's tables, its AttnSpec (its
        # own page ids) and its write slots
        wcos, wsin = rope_cos_sin(
            jnp.asarray(rope_inv_freq(cfg, cfg.swa_rope_theta)), positions
        )
        by_kind = {
            FULL: (cos, sin, attn, write_slots),
            WINDOW: (wcos, wsin, attn.win, attn.win.write_slots),
        }

    new_k_layers = []
    new_v_layers = []
    new_ks_layers = []
    new_vs_layers = []
    new_ssm, new_conv = [], []
    for l, lp in enumerate(params["layers"]):
        if cfg.layer_kind(l) == MAMBA:
            # the layer's "cache" is its state pools (`layer_step`)
            i = len(new_ssm)
            x, ssm, conv = _mamba_layer(
                lp, cfg, x, kv.ssm[i], kv.conv[i], attn.state_slots,
                positions, real_mask,
            )
            new_ssm.append(ssm)
            new_conv.append(conv)
            continue
        l_cos, l_sin, l_attn, l_slots = (
            by_kind[cfg.layer_kind(l)] if by_kind
            else (cos, sin, attn, write_slots)
        )
        i = len(new_k_layers)  # == l but beside MAMBA layers
        x, layer_k, layer_v, layer_ks, layer_vs = layer_step(
            lp, cfg, x, l_cos, l_sin, kv.k[i],
            None if kv.latent else kv.v[i],
            l_slots, l_attn, positions, real_mask=real_mask,
            kv_ks=kv.ks[i] if kv.quantized else None,
            kv_vs=kv.vs[i] if kv.quantized else None,
            moe_stats=moe_stats, layer=l,
        )
        new_k_layers.append(layer_k)
        new_v_layers.append(layer_v)
        new_ks_layers.append(layer_ks)
        new_vs_layers.append(layer_vs)

    kv = KVCache(
        k=tuple(new_k_layers),
        v=None if kv.latent else tuple(new_v_layers),
        ks=tuple(new_ks_layers) if kv.quantized else None,
        vs=tuple(new_vs_layers) if kv.quantized else None,
        ssm=tuple(new_ssm) if cfg.recurrent else None,
        conv=tuple(new_conv) if cfg.recurrent else None,
    )
    if cfg.hc_mult > 1:
        x = mhc.collapse(x, cfg.hc_mult)
    x = _norm(
        x, params["final_norm"], cfg.rms_norm_eps,
        weight_offset=cfg.norm_weight_offset,
    )
    return x, kv


@functools.partial(jax.jit, static_argnums=(1,))
def _mamba_layer(lp, cfg, x, ssm, conv, state_slots, positions, real_mask):
    """A MAMBA layer of `forward`, a function of its own inside the step
    program. The layers of this kind are alike (36 of granite-4.0-h-micro's
    40), so a step program traces and lowers ONE of them and calls it for
    each: XLA inlines the calls, and the compiled program is the unrolled
    one (state pools updated in place, tests/test_tpu_compile.py). What it
    saves is set-up: a server loads ~35 step programs of 40 layers, and a
    program's tracing and lowering was 3.3-4.3 s of which this kind's
    layers were most (PERF.md section 6, PR 41)."""
    x, ssm, conv, _, _ = layer_step(
        lp, cfg, x, None, None, ssm, conv, None,
        AttnSpec(state_slots=state_slots), positions, real_mask=real_mask,
        layer=cfg.layer_kinds.index(MAMBA),
    )
    return x, ssm, conv


def layer_step(lp, cfg, x, cos, sin, kv_k, kv_v, write_slots, attn,
               positions, real_mask=None, kv_ks=None, kv_vs=None,
               tp_axis=None, tp_overlap: bool = False, bt_shape=None,
               moe_stats=None, *, layer: int):
    """One transformer layer (attention + FFN, pre-norm residuals) over
    the paged pools — shared by `forward` and the manual-tp layer
    executor (parallel/tp_overlap.py). `tp_axis` enables manual-tp
    semantics for use inside a shard_map (explicit psums after the
    row-parallel projections). `tp_overlap` (with `tp_axis` and the
    static `bt_shape=(b, t)`) is the latency-hiding variant: x arrives
    and leaves ROW-SCATTERED [ceil(b*t/tp), D] — norms and residual
    adds run on the scattered view and every collective is a chunked
    `lax.ppermute` ring (parallel/tp_overlap.py). kv_ks/kv_vs are the
    int8-KV scale pools (None in unquantized mode; returned as-is).

    What layer `layer` IS comes from the configuration: its attention
    kind from the pattern (`cfg.layer_kind`; in a hybrid model cos / sin,
    `attn` and `write_slots` are that kind's), latent attention from
    `cfg.latent` (then `kv_k` is its latent pool and `kv_v` None), an
    expert layer from `cfg.is_moe_layer`. The stage executors run dense
    models of one kind, whose layers are all alike, and say 0.

    A MAMBA layer's mixer keeps no pages: `kv_k` / `kv_v` are then its
    state pools (`KVCache.ssm[i]` / `conv[i]`), returned in the same
    places; a row's state slot is `attn.state_slots`, the positions that
    advance it `real_mask`, and a row whose first position is 0 starts
    from a zero state whatever its slot holds (models/mamba2.py).

    With `cfg.hc_mult > 1` `x` is the token's residual STREAMS, [B, T,
    hc_mult x D], in and out, and each sublayer stands inside a boundary
    of models/mhc.py in place of the plain add."""
    if tp_overlap and cfg.num_experts:
        raise ValueError("tp_overlap layer executor covers dense models")
    hc = cfg.hc_mult > 1
    if hc:
        # `x` is the token's streams [B, T, n D]: a boundary around each
        # sublayer, which reads their `pre` mix and whose output `post`
        # writes back (models/mhc.py)
        if tp_axis is not None or tp_overlap:
            raise ValueError(
                f"a residual of {cfg.hc_mult} streams ('{cfg.name}') is "
                "served on one device: the stage executors (manual tp, "
                "tp_overlap) carry one stream [B, T, D] "
                "and have no rule for the boundary's maps"
            )
        from dynamo_tpu.models import mhc

        # the boundary's operations lie under `attn.mhc` / `mlp.mhc` (the
        # family names the benchmark's trace reader admits) and inside it
        # under `mhc.maps` / `mhc.pre` / `mhc.post` (models/mhc.py)
        streams = x
        with jax.named_scope("attn.mhc"):
            h_attn = mhc.maps(lp["hc_attn"], cfg, streams)
            x = mhc.pre(h_attn, streams)
    w_off = cfg.norm_weight_offset
    attn_in = _norm(x, lp["attn_norm"], cfg.rms_norm_eps, weight_offset=w_off)
    if cfg.layer_kind(layer) == MAMBA:
        from dynamo_tpu.models.mamba2 import mamba_mixer

        attn_out, kv_k, kv_v = mamba_mixer(
            lp, cfg, attn_in, kv_k, kv_v, attn.state_slots, real_mask,
            (positions[:, 0] == 0) & real_mask[:, 0],
        )
    elif cfg.latent:
        attn_out, kv_k = _mla_attn_block(
            lp, cfg, attn_in, cos, sin, kv_k, write_slots, attn, positions,
        )
    else:
        attn_out, kv_k, kv_v, kv_ks, kv_vs = _attn_block(
            lp, cfg, attn_in, cos, sin, kv_k, kv_v, write_slots, attn,
            positions, kv_ks=kv_ks, kv_vs=kv_vs, tp_axis=tp_axis,
            tp_overlap=tp_overlap, bt_shape=bt_shape,
            kind=cfg.layer_kind(layer),
        )
    if cfg.residual_multiplier != 1.0:
        attn_out = attn_out * jnp.asarray(
            cfg.residual_multiplier, attn_out.dtype)
    if hc:
        with jax.named_scope("attn.mhc"):
            streams = mhc.post(h_attn, streams, attn_out)
        with jax.named_scope("mlp.mhc"):
            h_mlp = mhc.maps(lp["hc_mlp"], cfg, streams)
            x = mhc.pre(h_mlp, streams)
    else:
        x = x + attn_out
    mlp_in = _norm(x, lp["mlp_norm"], cfg.rms_norm_eps, weight_offset=w_off)
    if cfg.is_moe_layer(layer):
        from dynamo_tpu.models.moe import moe_block

        mlp_out = moe_block(
            lp, cfg, mlp_in, real_mask=real_mask, stats=moe_stats
        )
    else:
        mlp_out = _mlp_block(
            lp, mlp_in, tp_axis=tp_axis, act=cfg.hidden_act,
            tp_overlap=tp_overlap,
        )
        if cfg.residual_multiplier != 1.0:
            mlp_out = mlp_out * jnp.asarray(
                cfg.residual_multiplier, mlp_out.dtype)
    if hc:
        with jax.named_scope("mlp.mhc"):
            x = mhc.post(h_mlp, streams, mlp_out)
    else:
        x = x + mlp_out
    return x, kv_k, kv_v, kv_ks, kv_vs


@jax.named_scope("head")
def logits(params: Params, cfg: ModelConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Vocab projection [..., D] -> [..., V] in float32.

    When the params carry a quantized "lm_head" (ops/quant.py adds one
    even for tied embeddings — the bf16 table stays for the gather), the
    projection runs int8 on the MXU."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    if is_quantized(head):
        return quant_matmul(hidden, head, out_dtype=jnp.float32)
    out = jnp.einsum(
        "...d,dv->...v", hidden, head, preferred_element_type=jnp.float32
    )
    if cfg.logits_scaling != 1.0:
        out = out / cfg.logits_scaling
    return out


def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _dense_init_sharded(key, shape, scale, dtype, sharding):
    """`_dense_init` born under `sharding`: each device computes only
    its own shard. Module-level so one program per distinct (shape,
    scale, dtype, sharding) serves every engine of the process."""
    return jax.lax.with_sharding_constraint(
        _dense_init(key, shape, scale, dtype), sharding
    )


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
    quantize: bool = False, shardings=None,
) -> Params:
    """Random-init params (tests, benchmarks); HF loading lives in
    dynamo_tpu/models/weights.py.

    `quantize=True` quantizes each layer's dense projections to int8 AS
    they are created (ops/quant.py scheme, same result as
    `quantize_params` on the full tree) — peak device memory stays at
    "int8 so far + one bf16 layer", which is what lets an 8B model
    random-init on a 16 GB chip where the bf16 tree alone would OOM.

    `shardings` (the `parallel.mesh.param_shardings` tree) creates every
    dense leaf directly under its target sharding, so no device ever
    holds more than its own shard — without it the whole tree is built
    on the default device first, which for an 8B bf16 model is the whole
    of one chip's HBM. The values do not depend on the sharding
    (jax's partitionable threefry)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    qs, kvs = cfg.q_size, cfg.kv_size
    keys = iter(jax.random.split(key, 4 + 9 * cfg.num_layers))
    if quantize and (cfg.latent or cfg.num_shared_experts or cfg.hybrid
                     or cfg.recurrent):
        raise ValueError(
            f"quantization with '{cfg.name}': int8 weights are not served "
            "for latent attention, shared experts, window beside full "
            "attention or Mamba-2 layers yet"
        )
    if quantize:
        from dynamo_tpu.ops.quant import QUANT_KEYS, quantize_weight

    def dense(k, shape, scale=None, sharding=None):
        scale = scale or (shape[0] ** -0.5)
        if sharding is None:
            return _dense_init(k, shape, scale, dtype)
        return _dense_init_sharded(k, shape, scale, dtype, sharding)

    layers = []
    for i in range(cfg.num_layers):
        sh = shardings["layers"][i] if shardings else {}
        if cfg.layer_kind(i) == MAMBA:
            from dynamo_tpu.models.mamba2 import init_mamba_params

            lp = {
                "attn_norm": jnp.ones((d,), dtype),
                **init_mamba_params(cfg, next(keys), dtype),
                "mlp_norm": jnp.ones((d,), dtype),
            }
        elif cfg.latent:
            rank, hv = cfg.kv_lora_rank, cfg.num_heads * cfg.v_head_dim
            if cfg.q_lora_rank:
                qr = cfg.q_lora_rank
                # w_qa at 4x its fan-in scale: the norm between the two
                # query matrices then ACTS (at fan-in scale x W_qa has unit
                # RMS already, and a program that dropped the norm would
                # pass the benchmark's comparison)
                wq = {
                    "w_qa": dense(next(keys), (d, qr), 4.0 * d ** -0.5),
                    "q_norm": jnp.ones((qr,), dtype),
                    "w_qb": dense(next(keys), (qr, qs)),
                }
            else:
                wq = {"wq": dense(next(keys), (d, qs))}
            lp = {
                "attn_norm": jnp.ones((d,), dtype),
                **wq,
                "w_kva": dense(next(keys), (d, cfg.latent_width)),
                "kv_norm": jnp.ones((rank,), dtype),
                "w_kvb": dense(
                    next(keys),
                    (rank, cfg.num_heads * (cfg.qk_nope_head_dim
                                            + cfg.v_head_dim)),
                ),
                "wo": dense(next(keys), (hv, d)),
                "mlp_norm": jnp.ones((d,), dtype),
            }
        elif cfg.hybrid:
            spec = cfg.attn_kind(cfg.layer_kind(i))
            hv = cfg.num_heads * spec.v_dim
            lp = {
                "attn_norm": jnp.ones((d,), dtype),
                "wq": dense(next(keys), (d, cfg.num_heads * spec.k_dim)),
                "wk": dense(next(keys), (d, spec.k_width)),
                "wv": dense(next(keys), (d, spec.v_width)),
                "wo": dense(next(keys), (hv, d)),
                "mlp_norm": jnp.ones((d,), dtype),
            }
            if spec.sink:
                # N(0, 1) so that the sink is judged (a checkpoint's
                # come from training)
                lp["sink"] = jax.random.normal(
                    jax.random.fold_in(key, 7000 + i), (cfg.num_heads,),
                    jnp.float32,
                )
        else:
            # a caller's score scale (Granite's 1/head for 1/sqrt(head))
            # presumes the q . k a training run grows: seeded q and k are
            # scaled so that the scores have the unit variance they have
            # under 1/sqrt(head), else the softmax is flat and neither the
            # scale nor a position encoding is judged
            qk = (d * cfg.attn_scale * cfg.head_dim ** 0.5) ** -0.5 \
                if cfg.attn_scale else None
            lp = {
                "attn_norm": jnp.ones((d,), dtype),
                "wq": dense(next(keys), (d, qs), qk, sharding=sh.get("wq")),
                "wk": dense(next(keys), (d, kvs), qk, sharding=sh.get("wk")),
                "wv": dense(next(keys), (d, kvs), sharding=sh.get("wv")),
                "wo": dense(next(keys), (qs, d), sharding=sh.get("wo")),
                "mlp_norm": jnp.ones((d,), dtype),
            }
        if cfg.is_moe_layer(i):
            from dynamo_tpu.models.moe import init_moe_params

            lp.update(init_moe_params(cfg, next(keys), dtype=dtype))
        else:
            lp.update({
                "w_gate": dense(next(keys), (d, f), sharding=sh.get("w_gate")),
                "w_up": dense(next(keys), (d, f), sharding=sh.get("w_up")),
                "w_down": dense(next(keys), (f, d), sharding=sh.get("w_down")),
            })
        if cfg.attn_bias:
            lp["bq"] = jnp.zeros((qs,), dtype)
            lp["bk"] = jnp.zeros((kvs,), dtype)
            lp["bv"] = jnp.zeros((kvs,), dtype)
        if cfg.hc_mult > 1:
            from dynamo_tpu.models.mhc import init_mhc_params

            for j, name in enumerate(("hc_attn", "hc_mlp")):
                lp[name] = init_mhc_params(
                    cfg, jax.random.fold_in(key, 9000 + 2 * i + j), dtype)
        if quantize:
            lp = {
                k: (quantize_weight(v) if k in QUANT_KEYS else v)
                for k, v in lp.items()
            }
        layers.append(lp)

    sh = shardings or {}
    params: Params = {
        "embed": dense(next(keys), (cfg.vocab_size, d), scale=0.02,
                       sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (d, cfg.vocab_size),
                                  sharding=sh.get("lm_head"))
    if quantize:
        head = (
            params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
        )
        from dynamo_tpu.ops.quant import quantize_weight as _qw

        params["lm_head"] = _qw(head)
    return params


def param_count(params: Params) -> int:
    """Logical parameter count. On a quantized tree (ops/quant.py) the
    per-channel scales and the duplicate int8 head of tied embeddings
    are bookkeeping, not model parameters — call on the bf16 tree (the
    engine snapshots `param_count` before quantizing)."""
    return sum(int(p.size) for p in jax.tree.leaves(params))
