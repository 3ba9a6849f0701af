"""Int8 quantized matmul path (W8A8, dynamic per-token activation scales).

The reference's headline baselines serve FP8 models on H100 (reference:
docs/architecture.md:76-83 — "R1-Distill-Llama-70B FP8"); the TPU-native
equivalent is int8 on the MXU, which runs at ~1.4x the bf16 matmul rate on
v5e (measured; spec 2x) and halves the weight bytes the bandwidth-bound
decode phase must stream per step.

Scheme (llm.int8 / SmoothQuant-family, the standard near-lossless recipe):

- weights: symmetric per-output-channel int8, scale = max|w_col| / 127,
  stored as a plain dict leaf {"q": int8 [in, out], "s": f32 [out]} so the
  sharding pytrees in parallel/mesh.py keep working structurally (the
  scale inherits the weight's output-dim partition spec);
- activations: symmetric per-row (per-token) int8 quantized dynamically
  at trace time inside the same jit — no calibration pass;
- the dot runs s8 x s8 -> s32 on the MXU (`preferred_element_type=int32`;
  worst-case accumulation 127*127*K < 2^31 for any real K), dequantized
  as acc * x_scale * w_scale in f32 and cast back to the activation dtype.

Attention itself (QK^T, PV, the paged KV cache) stays bf16: its inputs
are freshly-computed activations, not weights, and the Pallas kernels are
bandwidth- not compute-bound. Embedding lookups stay bf16; the vocab
projection gets its own int8 copy (tied embeddings keep the bf16 table
for the gather).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

# per-layer weight names eligible for quantization (dense Llama family;
# MoE expert tensors and the router stay bf16 — 3-D einsum weights, and
# routing is accuracy-critical)
QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def is_quantized(leaf: Any) -> bool:
    """A quantized-weight leaf is the exact dict {"q", "s"}."""
    return (
        isinstance(leaf, dict)
        and len(leaf) == 2
        and "q" in leaf
        and "s" in leaf
    )


def quantize_weight(w: jnp.ndarray) -> dict:
    """[in, out] float -> {"q": int8 [in, out], "s": f32 [out]}."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def quant_matmul(x: jnp.ndarray, w: dict, out_dtype=None) -> jnp.ndarray:
    """x [..., in] (bf16/f32) @ quantized w -> [..., out] in x.dtype
    (or `out_dtype`; the dequant itself is f32).

    Per-row dynamic activation quantization; s8xs8->s32 on the MXU.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xi = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xi,
        w["q"],
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * xs * w["s"]
    return out.astype(out_dtype or x.dtype)


def quantize_kv_rows(
    rows: jnp.ndarray, num_kv_heads: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """KV rows [..., K*Hd] float -> (int8 [..., K*Hd], scales f32
    [..., K]): symmetric per-row-per-kv-head absmax, the KV analogue of
    the per-token activation scheme above. 8-bit absmax KV is the
    standard near-lossless recipe (the reference's FP8 KV cache plays
    the same role on H100); scales stay f32 — they are ~Hd/4x smaller
    than the data they describe."""
    shape = rows.shape
    hd = shape[-1] // num_kv_heads
    rf = rows.astype(jnp.float32).reshape(*shape[:-1], num_kv_heads, hd)
    amax = jnp.max(jnp.abs(rf), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(rf / scales[..., None]), -127, 127)
    return q.reshape(shape).astype(jnp.int8), scales


def dequantize_kv_rows(
    q: jnp.ndarray, scales: jnp.ndarray, out_dtype=jnp.float32
) -> jnp.ndarray:
    """(int8 [..., K*Hd], scales [..., K]) -> float [..., K*Hd]."""
    shape = q.shape
    kh = scales.shape[-1]
    hd = shape[-1] // kh
    f = q.astype(jnp.float32).reshape(*shape[:-1], kh, hd) * scales[..., None]
    return f.reshape(shape).astype(out_dtype)


# --------------------------------------------------------------------------
# int8-KV scale POOL layout.
#
# Dense per-row scales ([num_slots, K]) cannot be touched by Mosaic: any
# memref slice narrower than the (8, 128) f32 tile fails to compile (probed
# on v5e). The pool layout is therefore page-blocked and TRANSPOSED —
#
#     [num_pages, SUBL, page_size]   f32, tokens in lanes
#
# with SUBL = tp * max(8, K/tp): each tp shard owns a sublane-aligned
# [num_pages, >=8, page_size] block whose rows 0..K/tp-1 are its local
# heads (rows above are padding, scale 1.0). Page slices [1, SUBL, S] are
# tile-aligned for DMA when page_size % 128 == 0, and in-kernel
# dequantization becomes a LANE-side multiply on the score matrix: scale
# tiles [SUBL, S] expand to [H, S] with one static 0/1 replication matmul
# (HIGHEST precision — the MXU's default bf16 truncation would degrade the
# scales). The XLA paths (gather oracle, wire extract/inject) address the
# pool through the helpers below; wire format stays dense [..., K].


def kv_scale_subl(num_kv_heads: int, tp: int = 1) -> int:
    """Sublane rows of the scale pool: 8-aligned per tp shard."""
    return tp * max(8, num_kv_heads // tp)


def init_kv_scale_pool(
    num_pages: int, page_size: int, num_kv_heads: int, tp: int = 1,
    sharding=None,
) -> jnp.ndarray:
    """`sharding` creates the pool shard by shard on its devices (no
    device ever holds the whole array)."""
    return jnp.ones(
        (num_pages, kv_scale_subl(num_kv_heads, tp), page_size), jnp.float32,
        device=sharding,
    )


def _scale_rows(num_kv_heads: int, tp: int) -> jnp.ndarray:
    """Pool row index of each head (head-order [K] vector)."""
    kh_loc = num_kv_heads // tp
    subl_shard = max(8, kh_loc)
    g = jnp.arange(num_kv_heads)
    return (g // kh_loc) * subl_shard + g % kh_loc


def scatter_kv_scales(
    pool: jnp.ndarray,   # [P, SUBL, S]
    slots: jnp.ndarray,  # [M] flat slot ids
    scales: jnp.ndarray,  # [M, K] dense per-row scales
    num_kv_heads: int,
    tp: int = 1,
) -> jnp.ndarray:
    s = pool.shape[2]
    rows = _scale_rows(num_kv_heads, tp)
    return pool.at[
        (slots // s)[:, None], rows[None, :], (slots % s)[:, None]
    ].set(scales.astype(jnp.float32))


def gather_kv_scales(
    pool: jnp.ndarray,
    slots: jnp.ndarray,
    num_kv_heads: int,
    tp: int = 1,
) -> jnp.ndarray:
    """[M, K] dense scales for the given slots."""
    s = pool.shape[2]
    rows = _scale_rows(num_kv_heads, tp)
    return pool[(slots // s)[:, None], rows[None, :], (slots % s)[:, None]]


# --------------------------------------------------------------------------
# int32-PACKED int8 pool format (the pallas serving path).
#
# int8 VMEM tiles are (32, 128): the page DMA writes them ~1.4x slower per
# byte than f32-class (8, 128) tiles (measured via the decode kernel's
# nocompute ablation by a round-4 probe, not in the ledger — the floor was
# 0.72x bf16's where bytes alone say 0.53x). Storing the pools as int32
# [num_slots/4, K*Hd] gets the f32-class tiling; the kernels reinterpret
# with pltpu.bitcast, whose v5e semantics (chip_smoke.py's int8 phase
# holds them) expand the SUBLANE dim 4x with int32 row t holding
# int8 rows 4t..4t+3 as its little-endian bytes. The XLA-side pack must
# therefore interleave groups of 4 consecutive token rows into each int32
# row — exactly what these helpers do (lax.bitcast_convert_type is also
# little-endian, probed to agree with the in-kernel bitcast).


def pack_kv_slots(rows: jnp.ndarray) -> jnp.ndarray:
    """int8 [..., T, K*Hd] -> int32 [..., T//4, K*Hd] (T % 4 == 0):
    int32 row t = token rows 4t..4t+3, little-endian bytes."""
    *lead, t, kw = rows.shape
    x = rows.reshape(*lead, t // 4, 4, kw)
    x = jnp.swapaxes(x, -1, -2)                     # [..., T//4, K*Hd, 4]
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def unpack_kv_slots(packed: jnp.ndarray) -> jnp.ndarray:
    """int32 [..., T4, K*Hd] -> int8 [..., 4*T4, K*Hd] (pack inverse)."""
    *lead, t4, kw = packed.shape
    x = jax.lax.bitcast_convert_type(packed, jnp.int8)   # [..., T4, kw, 4]
    x = jnp.swapaxes(x, -1, -2)
    return x.reshape(*lead, 4 * t4, kw)


def gather_packed_kv(pool: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """Packed pool [num_slots//4, K*Hd] int32 + slot ids [M] -> dense int8
    rows [M, K*Hd] (read-side of the XLA disagg/offload paths)."""
    grp = pool[slots // 4]                               # [M, kw] int32
    b8 = jax.lax.bitcast_convert_type(grp, jnp.int8)     # [M, kw, 4]
    byte = (slots % 4).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(b8, byte, axis=2)[..., 0]


def scatter_packed_kv_rows(
    pool: jnp.ndarray,   # [num_slots//4, W] int32 (pack_kv_slots layout)
    slots: jnp.ndarray,  # [M] flat slot ids (0 = trash)
    rows: jnp.ndarray,   # [M, W] int8 quantized rows (nibble-packed for int4)
) -> jnp.ndarray:
    """Row-scatter dense int8 rows into an int32-PACKED pool (write-side
    sibling of `gather_packed_kv` — the piece that lets mixed/spec-verify
    steps land decode rows MID-PAGE on the pallas+quantized serving path,
    where the page-granular `paged_kv_write` cannot express the write).

    int32 row g holds token rows 4g..4g+3 as its little-endian bytes, so
    a row write is byte-lane surgery: four sequential masked passes, one
    per lane l, each gathering the packed rows of the slots with
    slot % 4 == l, splicing byte lane l with uint32 masks and scattering
    the rows back. Slots outside the pass's lane redirect to packed row 0
    (trash-page slots 0..3, never read) and write their row back
    unmodified, so every pass is one fixed-shape gather + scatter. Passes
    chain sequentially because two slots of one write batch may share a
    packed row (4 tokens per int32 row). Byte-level and width-agnostic,
    so the int4 nibble-packed tier composes unchanged."""
    pool_u = jax.lax.bitcast_convert_type(pool, jnp.uint32)
    byte_u = jax.lax.bitcast_convert_type(
        rows.astype(jnp.int8), jnp.uint8
    ).astype(jnp.uint32)                                 # [M, W]
    lanes = (slots % 4).astype(jnp.int32)
    groups = (slots // 4).astype(jnp.int32)
    for lane in range(4):
        sel = lanes == lane
        g = jnp.where(sel, groups, 0)
        cur = pool_u[g]                                  # [M, W]
        shift = jnp.uint32(8 * lane)
        mask = jnp.uint32(0xFF) << shift
        upd = (cur & ~mask) | (byte_u << shift)
        upd = jnp.where(sel[:, None], upd, cur)
        pool_u = pool_u.at[g].set(upd)
    return jax.lax.bitcast_convert_type(pool_u, jnp.int32)


def scales_to_page_tiles(
    dense: jnp.ndarray, page_size: int, num_kv_heads: int, tp: int = 1
) -> jnp.ndarray:
    """Dense per-row scales [N*page_size, K] -> pool-layout page tiles
    [N, SUBL, page_size] (tokens in lanes, padding rows 1.0) — the source
    format `paged_kv_write`'s quant path scatters."""
    n = dense.shape[0] // page_size
    subl = kv_scale_subl(num_kv_heads, tp)
    rows = _scale_rows(num_kv_heads, tp)
    per_head = dense.reshape(n, page_size, num_kv_heads).transpose(0, 2, 1)
    return jnp.ones((n, subl, page_size), jnp.float32).at[:, rows, :].set(
        per_head
    )


# --------------------------------------------------------------------------
# int4 packed KV tier: two 4-bit values per int8 byte, half the int8
# tier's KV bytes.
#
# Packing is PLANAR per kv head: within one head's Hd features, packed
# byte j holds feature j in its low nibble and feature j + Hd/2 in its
# high nibble, so a packed row is [..., K*Hd/2] int8 and a head's packed
# slice splits into its low/high feature halves by plain slicing — the
# kernels score against the two nibble planes with two half-width dots
# and never materialize the unpacked row in registers. Values are
# clipped to [-7, 7] (symmetric, -8 unused so negation stays exact) with
# grouped absmax scales: `group_size` consecutive features share one f32
# scale. Default group_size = Hd reproduces the int8 tier's per-token-
# per-kv-head granularity (S == K scale channels — the layout the scale
# POOL above and the pallas scale-fold require); finer groups mean more
# scale channels and are gather-path only. The int32 page packing above
# composes unchanged — it is byte-level and width-agnostic — so the
# pallas (8, 128) DMA-tiling story carries over at half width.


def int4_scale_channels(
    num_kv_heads: int, head_dim: int, group_size: int | None = None
) -> int:
    """Scale channels S for an int4 pool (= K * groups-per-head)."""
    g = head_dim if group_size is None else group_size
    if g <= 0 or head_dim % g != 0:
        raise ValueError(
            f"kv_quant_group {g} must divide head_dim {head_dim}"
        )
    return num_kv_heads * (head_dim // g)


def quantize_kv_rows_int4(
    rows: jnp.ndarray, num_kv_heads: int, group_size: int | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """KV rows [..., K*Hd] float -> (packed int8 [..., K*Hd/2], scales
    f32 [..., S]) with S = K * (Hd // group_size); symmetric per-group
    absmax, scale = amax/7 (sentinel 1.0 for zero groups)."""
    shape = rows.shape
    hd = shape[-1] // num_kv_heads
    g = hd if group_size is None else group_size
    s = int4_scale_channels(num_kv_heads, hd, g)
    rf = rows.astype(jnp.float32).reshape(
        *shape[:-1], num_kv_heads, hd // g, g
    )
    amax = jnp.max(jnp.abs(rf), axis=-1)
    scales = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = (
        jnp.clip(jnp.round(rf / scales[..., None]), -7, 7)
        .astype(jnp.int32)
        .reshape(*shape[:-1], num_kv_heads, hd)
    )
    lo, hi = q[..., : hd // 2], q[..., hd // 2 :]
    packed = ((hi << 4) | (lo & 0xF)).astype(jnp.int8)
    return (
        packed.reshape(*shape[:-1], shape[-1] // 2),
        scales.reshape(*shape[:-1], s),
    )


def unpack_int4_kv(packed: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """Packed int8 [..., K*Hd/2] -> int8 [..., K*Hd], values in [-7, 7]
    (planar-pack inverse; low nibbles are each head's first Hd/2
    features). Sign-extends the low nibble via the (x ^ 8) - 8 trick;
    the high nibble sign-extends for free under arithmetic shift."""
    shape = packed.shape
    hd2 = shape[-1] // num_kv_heads
    b = packed.astype(jnp.int32).reshape(*shape[:-1], num_kv_heads, hd2)
    lo = ((b & 15) ^ 8) - 8
    hi = b >> 4
    full = jnp.concatenate([lo, hi], axis=-1)
    return full.reshape(*shape[:-1], 2 * shape[-1]).astype(jnp.int8)


def dequantize_kv_rows_int4(
    packed: jnp.ndarray,
    scales: jnp.ndarray,
    num_kv_heads: int,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """(packed int8 [..., K*Hd/2], scales [..., S]) -> float [..., K*Hd].
    Group size is implied by S (= K * Hd / S features per scale)."""
    shape = packed.shape
    hd = 2 * shape[-1] // num_kv_heads
    gph = scales.shape[-1] // num_kv_heads  # groups per head
    q = unpack_int4_kv(packed, num_kv_heads).astype(jnp.float32)
    qg = q.reshape(*shape[:-1], num_kv_heads, gph, hd // gph)
    sg = scales.reshape(*shape[:-1], num_kv_heads, gph)
    f = qg * sg[..., None]
    return f.reshape(*shape[:-1], 2 * shape[-1]).astype(out_dtype)


def mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """The model's matmul: quantized or plain depending on the leaf."""
    if is_quantized(w):
        return quant_matmul(x, w)
    return x @ w


def logical_param_count(params: dict, cfg) -> int:
    """Model parameter count on a quantized OR plain tree: scales are
    bookkeeping, a tied-embedding int8 head is a duplicate, int8 weights
    count by element like their bf16 originals."""
    total = 0
    for key, sub in params.items():
        if key == "lm_head" and cfg.tie_word_embeddings and is_quantized(sub):
            continue
        for leaf in jax.tree.leaves(sub, is_leaf=is_quantized):
            total += int(leaf["q"].size) if is_quantized(leaf) else int(leaf.size)
    return total


def quantize_params(params: dict, cfg, mode: str = "int8") -> dict:
    """Quantize a llama.init_params-shaped pytree in place of the dense
    projection weights; adds an int8 "lm_head" (from embed.T when tied).

    Norms, biases, embeddings, MoE experts and the router stay bf16.
    """
    if mode != "int8":
        raise ValueError(f"unknown quantization mode {mode!r}; expected 'int8'")
    new = dict(params)
    new["layers"] = [
        {
            k: (quantize_weight(v) if k in QUANT_KEYS else v)
            for k, v in lp.items()
        }
        for lp in params["layers"]
    ]
    head = (
        params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    )
    new["lm_head"] = quantize_weight(head)
    return new
