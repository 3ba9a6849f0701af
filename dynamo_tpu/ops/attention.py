"""Attention over a paged KV cache — one op for prefill, chunked prefill
and decode.

This is the TPU-native replacement for the engine-internal GPU attention the
reference relies on (vLLM paged attention) plus its first-party block-copy
kernel (reference: lib/llm/src/kernels/block_copy.cu — there, paging is a
*copy* problem because attention lives inside vLLM; here paging is native to
the attention op).

KV cache layout (per layer): flat **slot** pools

    k_cache, v_cache : [num_slots, num_kv_heads * head_dim]

where slot = page_id * page_size + offset. Pages exist only in the
allocator; the device sees flat slots, so scatter (write) and gather (read)
are single-index ops and a reshape to [num_pages, page_size, K*Hd] is a
free bitcast when a Pallas kernel wants page-granular DMA (the folded
K*Hd trailing dim keeps XLA's layout row-major — see llama.KVCache).
Slot 0 lives in the reserved trash page: padded positions scatter there,
and it is never allocated.

The unified step: new tokens' KV is **written first**, then queries attend
over the sequence's gathered slots (which now include themselves) under the
mask `slot_position <= query_position`. Prefill (cached_len=0), chunked
prefill / prefix-cache hits (cached_len>0) and decode (T=1) are the same
compiled graph family, bucketed by shape.

Query lengths are per-ROW ragged: nothing ties the rows of one dispatch to
the same chunk size, so a mixed-batching step (engine `_mixed_tick`) packs
q_len=1 decode rows next to chunked-prefill rows in one [B, T] call —
`q_lens` masks each row's padded query columns to exact zeros.

Sharding: the `num_kv_heads` axis is the tensor-parallel axis; gathers and
scatters are shard-local (no collectives on the KV path).

All impls here are pure jax.numpy (run anywhere; the correctness oracle).
Pallas TPU kernels live in `dynamo_tpu.ops.pallas_*` and are selected by the
engine when running on TPU.
"""

from __future__ import annotations

import jax.numpy as jnp

_NEG_INF = -1e30


def write_kv_slots(
    k_cache: jnp.ndarray,  # [N, K*Hd]
    v_cache: jnp.ndarray,
    slots: jnp.ndarray,    # [M] int32 flat slot ids (0 = trash)
    new_k: jnp.ndarray,    # [M, K*Hd]
    new_v: jnp.ndarray,
):
    """Scatter per-token KV into the slot pool; in-place when donated.
    Trash-slot writes (padding) are harmless by construction."""
    return k_cache.at[slots].set(new_k), v_cache.at[slots].set(new_v)


def slots_from_pages(block_tables: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """Expand page-id tables [..., W] into slot matrices [..., W*page_size]."""
    s = block_tables[..., :, None] * page_size + jnp.arange(page_size)
    return s.reshape(*block_tables.shape[:-1], -1)


def _masked_softmax(logits: jnp.ndarray, mask: jnp.ndarray,
                    sink: jnp.ndarray | None = None) -> jnp.ndarray:
    """Softmax over the last axis in f32; fully-masked rows yield zeros.
    `sink` (broadcastable to logits[..., :1]) is one more logit a row
    whose weight is not returned."""
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.exp(logits - m) * mask
    denom = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sink - m)
    return p / (denom + 1e-30)


def paged_attention(
    q: jnp.ndarray,            # [B, T, H, Hd] (rope applied; KV already written)
    k_cache: jnp.ndarray,      # [N, K*Hd] (int8 when scale pools are given)
    v_cache: jnp.ndarray,
    slot_matrix: jnp.ndarray,  # [B, C] int32: the sequence's slots, position-ordered
    positions: jnp.ndarray,    # [B, T] int32 absolute position of each query
    k_scales: jnp.ndarray | None = None,  # [P, SUBL, S] int8-KV scale pools
    v_scales: jnp.ndarray | None = None,  # (ops/quant pool layout)
    scale_tp: int = 1,
    q_lens: jnp.ndarray | None = None,    # [B] valid query rows per row
    int4_groups: int | None = None,       # int4 pools: scale groups per head
    window: int = 0,                      # tokens a query sees (0 = all)
    sink: jnp.ndarray | None = None,      # [H] f32 learned sink logits
    mask_block: int = 1,                  # the causal mask's block (static)
) -> jnp.ndarray:
    """Gathered-slot attention. Gathered slot j holds absolute position j of
    the sequence, so causality is `j <= positions[b, t]`; padded queries and
    0-padded slot-table tails are masked out by the same comparison (their
    garbage KV rides the trash page).

    `mask_block` B > 1 (a power of two) makes the mask causal by BLOCK: a
    query sees every position up to the end of its own block of B, `j <=
    positions[b, t] | (B - 1)`, which is `j // B <= position // B` (a model
    generated by diffusion over blocks; the caller has written the whole
    block's rows). 1 is causal by position, the program it always was.

    `q_lens` makes the per-row RAGGED query contract explicit (mixed
    prefill+decode steps: decode rows q_len=1 beside chunk rows): query
    columns >= q_lens[b] are fully masked and emit exact zeros instead of
    garbage that callers must know to ignore. None keeps the historical
    behavior (callers gather only their valid columns).

    With scale pools the caches hold per-token-per-kv-head symmetric int8
    (ops/quant.quantize_kv_rows; pool layout ops/quant.init_kv_scale_pool);
    rows are dequantized after the gather — this path is the correctness
    oracle for the int8 pallas kernels.

    Keys and values may differ in width (`v_cache` rows are K x Vd): the
    output is [B, T, H, Vd]. `window` adds `j > position - window` to the
    mask (slots behind it may name released pages: never read into a
    weight). `sink` [H] joins each head's softmax as one more column
    whose weight is dropped, so a row of weights sums to less than 1.

    `int4_groups` switches the pools to the nibble-packed int4 tier
    (ops/quant.quantize_kv_rows_int4): the caches hold HALF-width packed
    rows [N, K*Hd/2] and the scale pools carry S = K * int4_groups
    channels; the gather streams the packed bytes and dequantizes after
    — the correctness oracle for the int4 pallas kernels."""
    b, t, h, hd = q.shape
    int4 = int4_groups is not None
    kh = (2 if int4 else 1) * k_cache.shape[1] // hd
    g = h // kh
    scale = hd ** -0.5

    c = slot_matrix.shape[1]
    if int4:
        from dynamo_tpu.ops.quant import (
            dequantize_kv_rows_int4,
            gather_kv_scales,
        )

        flat = slot_matrix.reshape(-1)
        s_ch = kh * int4_groups
        ks = gather_kv_scales(k_scales, flat, s_ch, scale_tp).reshape(b, c, s_ch)
        vs = gather_kv_scales(v_scales, flat, s_ch, scale_tp).reshape(b, c, s_ch)
        k = dequantize_kv_rows_int4(
            k_cache[slot_matrix], ks, kh, q.dtype
        ).reshape(b, c, kh, hd)
        v = dequantize_kv_rows_int4(
            v_cache[slot_matrix], vs, kh, q.dtype
        ).reshape(b, c, kh, hd)
    else:
        k = k_cache[slot_matrix].reshape(b, c, kh, hd)  # [B, C, K, Hd]
        v = v_cache[slot_matrix].reshape(b, c, kh, -1)  # [B, C, K, Vd]
    if not int4 and k_scales is not None:
        from dynamo_tpu.ops.quant import gather_kv_scales

        flat = slot_matrix.reshape(-1)
        ks = gather_kv_scales(k_scales, flat, kh, scale_tp).reshape(b, c, kh)
        vs = gather_kv_scales(v_scales, flat, kh, scale_tp).reshape(b, c, kh)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
    qg = q.reshape(b, t, kh, g, hd)
    logits = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [B, K, G, T, C]

    j = jnp.arange(c)
    reach = positions | (mask_block - 1) if mask_block > 1 else positions
    mask = j[None, None, :] <= reach[:, :, None]  # [B, T, C]
    if window:
        mask = mask & (j[None, None, :] > positions[:, :, None] - window)
    if q_lens is not None:
        mask = mask & (
            jnp.arange(t)[None, :, None] < q_lens[:, None, None]
        )
    mask = mask[:, None, None, :, :]

    probs = _masked_softmax(
        logits, mask,
        None if sink is None else sink.reshape(1, kh, g, 1, 1),
    )
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, v.shape[-1])


def latent_attention(
    qa: jnp.ndarray,         # [B, T, H, W] absorbed, pre-scaled queries
    rows: jnp.ndarray,       # [B, C, W] the sequence's latent rows, by position
    positions: jnp.ndarray,  # [B, T] int32 absolute position of each query
    rank: int,               # the first `rank` columns of a row are its value
    q_lens: jnp.ndarray | None = None,  # [B] valid query rows per row
) -> jnp.ndarray:
    """Absorbed latent attention over gathered rows (models/llama.py
    `_mla_attn_block`): every head scores against the ONE row a token
    keeps, `[c ; k_r]`, and the value is that row's `c`. Row j holds
    position j, so the mask is `paged_attention`'s. Returns the latent
    output [B, T, H, rank] in the rows' dtype; the caller applies W_uv."""
    c = rows.shape[1]
    logits = jnp.einsum(
        "bthw,bcw->bhtc", qa, rows, preferred_element_type=jnp.float32
    )
    mask = jnp.arange(c)[None, None, :] <= positions[:, :, None]  # [B, T, C]
    if q_lens is not None:
        mask = mask & (
            jnp.arange(qa.shape[1])[None, :, None] < q_lens[:, None, None]
        )
    probs = _masked_softmax(logits, mask[:, None])
    return jnp.einsum(
        "bhtc,bcr->bthr", probs.astype(rows.dtype), rows[..., :rank]
    )
