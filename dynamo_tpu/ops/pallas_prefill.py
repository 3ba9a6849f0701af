"""Pallas flash prefill attention over the paged KV pool.

The jnp gather oracle (`ops.attention.paged_attention`) materializes the
[B, K, G, T, C] f32 logits and probs tensors — ~13 GB of HBM traffic per
layer at a [64, 512] chunk batch, ~500 ms of the ~730 ms prefill step.
Flash attention never materializes them: this kernel streams the
sequence's pages and carries the online-softmax state (running max,
denominator, f32 accumulator) in VMEM, so attention traffic collapses to
the KV pages themselves and prefill becomes MXU-bound.

Layout choices (all forced by Mosaic's "no lane-splitting reshapes"):

- q arrives pre-arranged as [B, KH, T*G, Hd] (the host-side transpose is
  free next to the attention cost), so per kv head the kernel slices a
  2D [T_tile*G, Hd] matrix with static indexing — queries of all G heads
  sharing a kv head are rows of ONE MXU operand. Output leaves the same
  way and is rearranged outside.
- KV pages are fetched PPB at a time through PPB separate BlockSpecs
  (pages are scattered, one index_map each — Pallas pipelines them
  together), and scores land in a [T_tile*G, PPB*page] VMEM scratch
  block, so the online-softmax update runs on wide tiles.
- grid (B, T_tiles, ceil(W/PPB)), page-block dim innermost; the causal
  upper triangle is skipped via pl.when on whole page-blocks.

Reference counterpart: vLLM's prefill attention + block_copy.cu
(reference: lib/llm/src/kernels/block_copy.cu) — there paging is a copy
problem; here the kernel reads pages in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_attention import in_hbm

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(
    # scalar prefetch
    tables_ref,   # [B, Wp] i32 page ids (padded to PPB multiple, 0=trash)
    pos0_ref,     # [B] i32 chunk start position (page-aligned)
    tlen_ref,     # [B] i32 valid query rows in this chunk
    # blocks
    q_ref,        # [1, KH, T_TILE*G, Hd]
    *page_refs,   # PPB x ([1, page, K*Hd] k), PPB x (v), [quant: PPB x
    # ([1, SUBL, page] k-scale tiles), PPB x (v-scale tiles)], then
    # outputs/scratch
    t_tile: int,
    page: int,
    kh: int,
    g: int,
    hd: int,
    wb: int,
    ppb: int,
    quant: bool = False,
    subl: int = 0,
    packed: bool = False,
    int4: bool = False,
    vd: int = 0,
    window: int = 0,
    sink: bool = False,
    mask_block: int = 1,
):
    vd = vd or hd   # values may be narrower than keys (unquantized pools)
    if sink:
        # [T_TILE*G, KH] f32: row r of kv head k holds the sink logit of
        # query head k*G + r % G
        sink_ref, *page_refs = page_refs
    k_refs = page_refs[:ppb]
    v_refs = page_refs[ppb:2 * ppb]
    off = 2 * ppb
    if quant:
        ks_refs = page_refs[off:off + ppb]
        vs_refs = page_refs[off + ppb:off + 2 * ppb]
        off += 2 * ppb
    o_ref = page_refs[off]          # [1, KH, T_TILE*G, Hd]
    m_ref = page_refs[off + 1]      # [T_TILE*G, KH] f32
    l_ref = page_refs[off + 2]
    acc_ref = page_refs[off + 3]    # [KH, T_TILE*G, Hd] f32
    s_ref = page_refs[off + 4]      # [T_TILE*G, PPB*page] f32

    def head_scale(sc_ref, k):
        # one-hot [1, SUBL] @ scale tile [SUBL, page] -> [1, page] lane
        # vector of head k's per-token scales (HIGHEST: default MXU bf16
        # truncation would degrade the scales)
        e_k = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (1, subl), 1) == k, 1.0, 0.0
        )
        return jax.lax.dot_general(
            e_k, sc_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    b, tt, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    scale = hd ** -0.5
    tg = t_tile * g
    blk = ppb * page
    hd2 = hd // 2  # int4: packed bytes per head (planar nibble planes)

    def nibbles(x):
        # packed int4 byte [n, hd2] -> (lo, hi) f32 [n, hd2]: low nibble
        # = features 0..hd2-1 (sign-extend via (x^8)-8), high nibble =
        # features hd2..hd-1 (arithmetic >> sign-extends for free)
        xi = x.astype(jnp.int32)
        lo = (((xi & 15) ^ 8) - 8).astype(jnp.float32)
        hi = (xi >> 4).astype(jnp.float32)
        return lo, hi

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos0_ref[b]
    tlen = tlen_ref[b]
    # absolute positions: query rows (each q position spans G rows) and
    # this page-block's kv rows
    q_pos = pos0 + tt * t_tile + jax.lax.broadcasted_iota(
        jnp.int32, (tg, blk), 0
    ) // g
    k_pos = kb * blk + jax.lax.broadcasted_iota(jnp.int32, (tg, blk), 1)
    if mask_block > 1:
        # causal by BLOCK: a query sees every key up to the end of its own
        # block of `mask_block` positions, and the page-blocks in reach are
        # those up to the end of the tile's last query's block
        valid = (k_pos <= q_pos | (mask_block - 1)) & (q_pos < pos0 + tlen)
        in_reach = kb * blk <= (
            pos0 + (tt + 1) * t_tile - 1) | (mask_block - 1)
    else:
        valid = (k_pos <= q_pos) & (q_pos < pos0 + tlen)  # [TG, BLK]
        in_reach = kb * blk <= pos0 + (tt + 1) * t_tile - 1
    if window:
        # a window: a query sees the `window` positions up to its own;
        # page-blocks wholly behind the tile's first query's window are
        # skipped like those above the causal line (their table entries
        # may name pages the engine has released)
        valid = valid & (k_pos > q_pos - window)
        in_reach = in_reach & (
            (kb + 1) * blk - 1 > pos0 + tt * t_tile - window
        )

    # skip page-blocks entirely above the tile's causal line
    @pl.when(in_reach)
    def _work():
        if packed:
            # int32-packed pages (quant.pack_kv_slots): bitcast each
            # [page//4, K*Hd] int32 block back to int8 once per block,
            # then slice per head as usual
            kbs = [pltpu.bitcast(k_refs[j][0], jnp.int8) for j in range(ppb)]
            vbs = [pltpu.bitcast(v_refs[j][0], jnp.int8) for j in range(ppb)]
        for k in range(kh):
            q_k = q_ref[0, k]                                  # [TG, Hd]
            qf = q_k.astype(jnp.float32) * scale
            for j in range(ppb):
                if int4:
                    # packed int4 page: a head's slice is hd/2 bytes whose
                    # nibble planes are its low/high feature halves —
                    # score with two half-width dots, no unpacked row
                    kp = (kbs[j] if packed else k_refs[j][0])[
                        :, k * hd2:(k + 1) * hd2
                    ]                                          # [page, Hd/2]
                    klo, khi = nibbles(kp)
                    s_j = jax.lax.dot_general(
                        qf[:, :hd2], klo, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) + jax.lax.dot_general(
                        qf[:, hd2:], khi, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                else:
                    if packed:
                        k_j = kbs[j][:, k * hd:(k + 1) * hd]   # [page, Hd]
                    else:
                        k_j = k_refs[j][0, :, k * hd:(k + 1) * hd]
                    s_j = jax.lax.dot_general(
                        qf, k_j.astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                if quant:
                    # int8/int4 pages: K-scales fold into the score lanes
                    s_j = s_j * head_scale(ks_refs[j], k)
                s_ref[:, j * page:(j + 1) * page] = s_j
            s = jnp.where(valid, s_ref[...], _NEG_INF)         # [TG, BLK]
            m_prev = m_ref[:, k]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where(valid, p, 0.0)
            l_ref[:, k] = l_ref[:, k] * alpha + jnp.sum(p, axis=1)
            m_ref[:, k] = m_new
            pv = jnp.zeros((tg, vd), jnp.float32)
            for j in range(ppb):
                p_j = p[:, j * page:(j + 1) * page]
                if quant:
                    # (p * vs) @ v_int == p @ dequant(v)
                    p_j = p_j * head_scale(vs_refs[j], k)
                if int4:
                    # planar PV: [p@lo | p@hi] IS the natural feature
                    # order (lo plane = features 0..hd2-1)
                    vp = (vbs[j] if packed else v_refs[j][0])[
                        :, k * hd2:(k + 1) * hd2
                    ]
                    vlo, vhi = nibbles(vp)
                    pv = pv + jnp.concatenate(
                        [
                            jax.lax.dot_general(
                                p_j, vlo, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            ),
                            jax.lax.dot_general(
                                p_j, vhi, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            ),
                        ],
                        axis=1,
                    )
                else:
                    if packed:
                        v_j = vbs[j][:, k * hd:(k + 1) * hd]   # [page, Hd]
                    else:
                        v_j = v_refs[j][0, :, k * vd:(k + 1) * vd]
                    pv = pv + jax.lax.dot_general(
                        p_j, v_j.astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
            acc_ref[k] = acc_ref[k] * alpha[:, None] + pv

    @pl.when(kb == wb - 1)
    def _emit():
        for k in range(kh):
            l_fin, a_fin = l_ref[:, k], acc_ref[k]
            if sink:
                # the sink's column joins the denominator and is dropped
                m_k, s_k = m_ref[:, k], sink_ref[:, k]
                m_fin = jnp.maximum(m_k, s_k)
                beta = jnp.exp(m_k - m_fin)
                l_fin = l_fin * beta + jnp.exp(s_k - m_fin)
                a_fin = a_fin * beta[:, None]
            denom = jnp.maximum(l_fin, 1e-30)
            o_ref[0, k] = (a_fin / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "page_size", "t_tile", "pages_per_block", "interpret", "int4",
        "window", "mask_block",
    ),
)
def flash_prefill_attention(
    q: jax.Array,             # [B, T, H, Hd] rope applied, unscaled
    k_cache: jax.Array,       # [num_slots, K*Hd] (int8 when scales given)
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, W] i32 position-ordered page ids
    pos0: jax.Array,          # [B] i32 chunk start (NOT required to be
    # page-aligned: alignment is a constraint of the page-scatter WRITE
    # path, never of this read — mixed prefill+decode steps pass decode
    # rows with pos0 mid-page and t_valid == 1)
    t_valid: jax.Array,       # [B] i32 valid rows in the chunk (<= T)
    k_scales: jax.Array = None,  # [num_pages, SUBL, page_size] f32 scale
    # pools (ops/quant pool layout; SUBL >= 8, tokens in lanes)
    v_scales: jax.Array = None,
    sink: jax.Array = None,   # [H] f32 learned sink logits (None = none)
    *,
    page_size: int,
    t_tile: int = 128,
    pages_per_block: int = 4,
    interpret: bool = False,
    int4: bool = False,
    window: int = 0,          # tokens a query sees, itself included (0 = all)
    mask_block: int = 1,      # the causal mask's block, a power of two: a
    # query sees keys up to `q_pos | (mask_block - 1)`; 1 = by position
) -> jax.Array:
    """Causal chunked-prefill attention over gathered pages; rows past
    t_valid produce zeros. With `mask_block` B > 1 the mask is causal by
    block (`k_pos // B <= q_pos // B`): a chunk whose start and valid
    length are multiples of B never reads a row it has not been given,
    and a row of `q_len` B from a block's first position reads the whole
    block (written by the caller beforehand). Returns [B, T, H, Vd] in q.dtype (Vd = Hd
    unless the unquantized value pool is narrower than the key pool). With scale
    pools the pages hold per-token-per-kv-head int8; scale blocks ride
    the same page routing and dequantization happens per head slice in
    VMEM (VPU-cheap next to the halved page DMA traffic).

    Per-row RAGGED query lengths are native: every mask is computed from
    the row's own (pos0, t_valid), so one dispatch may mix full chunks,
    short final chunks and q_len=1 decode rows (the mixed-batching step;
    see ops.pallas_attention.ragged_paged_attention)."""
    b, t, h, hd = q.shape
    quant = k_scales is not None
    # int32-packed pools (quant.pack_kv_slots): same bytes, f32 tiling
    packed = quant and k_cache.dtype == jnp.int32
    num_slots, kw = k_cache.shape
    if packed:
        num_slots *= 4
    page_rows = page_size // 4 if packed else page_size
    # int4: the pool is nibble-packed at HALF width (kw = K*Hd/2), so kh
    # cannot be derived from kw alone — hence the explicit static flag
    kh = (2 * kw if int4 else kw) // hd
    g = h // kh
    vw = v_cache.shape[1]
    vd = hd if quant else vw // kh
    if int4:
        assert quant, "int4 pools require scale pools"
    ppb = pages_per_block
    t_tile = min(t_tile, max(t, 8))

    def vmem_bytes(tt):
        # double-buffered q/out blocks + page blocks, f32 online-softmax
        # scratch; Mosaic's scoped-VMEM stack is ~16 MB — 8B-class dims
        # blow it at the default tile, so shrink until it fits
        tg_ = tt * g
        qo = 2 * kh * tg_ * (hd + vd) * q.dtype.itemsize
        pages = 2 * ppb * page_rows * (kw + vw) * k_cache.dtype.itemsize
        if quant:
            pages += 2 * 2 * ppb * k_scales.shape[1] * page_size * 4
        scratch = (
            kh * tg_ * vd * 4            # acc
            + tg_ * ppb * page_size * 4  # s
            + 2 * tg_ * kh * 4           # m, l
        )
        return qo + pages + scratch

    # budget 9 MB against the 16 MB scoped limit: Mosaic's real footprint
    # runs ~1.6x this estimate (measured: 18.04 MB actual vs 11.3 MB
    # estimated at 8B dims, t_tile 128; the packed bitcast temps fit —
    # validated by the 8B bench)
    while t_tile > 16 and vmem_bytes(t_tile) > 9 * 1024 * 1024:
        t_tile //= 2
    t_pad = -(-t // t_tile) * t_tile
    if t_pad != t:
        q = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    # [B, T, KH, G, Hd] -> [B, KH, T*G, Hd]: all G queries of a kv head
    # become rows of one MXU operand (free vs the attention cost)
    qk = q.reshape(b, t_pad, kh, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, t_pad * g, hd
    )
    w = block_tables.shape[1]
    wp = -(-w // ppb) * ppb
    if wp != w:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, wp - w)))
    num_pages = num_slots // page_size
    k_pages = k_cache.reshape(num_pages, page_rows, kw)
    v_pages = v_cache.reshape(num_pages, page_rows, vw)
    tg = t_tile * g
    wb = wp // ppb

    def page_spec(j, width):
        return pl.BlockSpec(
            (1, page_rows, width),
            lambda bb, tt, kb, tbl, p0, tl, j=j: (tbl[bb, kb * ppb + j], 0, 0),
        )

    scale_inputs = []
    scale_specs = []
    subl = 0
    if quant:
        subl = k_scales.shape[1]
        k_pages, v_pages, k_scales, v_scales = (
            in_hbm(p, interpret)
            for p in (k_pages, v_pages, k_scales, v_scales)
        )
        scale_inputs = [*[k_scales] * ppb, *[v_scales] * ppb]

        def scale_spec(j):
            return pl.BlockSpec(
                (1, subl, page_size),
                lambda bb, tt, kb, tbl, p0, tl, j=j: (
                    tbl[bb, kb * ppb + j], 0, 0
                ),
            )

        scale_specs = [scale_spec(j) for j in range(ppb)] * 2

    sink_inputs, sink_specs = [], []
    if sink is not None:
        # row r of a tile is query head k*G + r % G of kv head k
        sink_inputs = [jnp.tile(
            sink.astype(jnp.float32).reshape(kh, g).T, (t_tile, 1)
        )]                                                    # [TG, KH]
        sink_specs = [pl.BlockSpec((tg, kh), lambda *_: (0, 0))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t_pad // t_tile, wb),
        in_specs=[
            pl.BlockSpec(
                (1, kh, tg, hd), lambda bb, tt, kb, *_: (bb, 0, tt, 0)
            ),
            *sink_specs,
            *[page_spec(j, kw) for j in range(ppb)],
            *[page_spec(j, vw) for j in range(ppb)],
            *scale_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, kh, tg, vd), lambda bb, tt, kb, *_: (bb, 0, tt, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((tg, kh), jnp.float32),
            pltpu.VMEM((tg, kh), jnp.float32),
            pltpu.VMEM((kh, tg, vd), jnp.float32),
            pltpu.VMEM((tg, ppb * page_size), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, t_tile=t_tile, page=page_size, kh=kh, g=g, hd=hd,
            wb=wb, ppb=ppb, quant=quant, subl=subl, packed=packed,
            int4=int4, vd=vd, window=window, sink=sink is not None,
            **({"mask_block": mask_block} if mask_block > 1 else {}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, t_pad * g, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        pos0.astype(jnp.int32),
        t_valid.astype(jnp.int32),
        qk,
        *sink_inputs,
        *[k_pages] * ppb,
        *[v_pages] * ppb,
        *scale_inputs,
    )
    # [B, KH, T*G, Hd] -> [B, T, H, Hd]
    out = out.reshape(b, kh, t_pad, g, vd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t_pad, h, vd)[:, :t]
