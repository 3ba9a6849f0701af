"""Ring flash attention: causal self-attention over an sp-sharded
sequence axis.

The long-context prefill path (SURVEY §5: the reference scales context
via its engines' context-parallel attention; TPU-native the mechanism is
a ring over the ICI mesh): tokens are sharded [B, T/sp, ...] over the
`sp` axis; each step every shard attends its local queries against the
KV block it currently holds, then rotates the KV block around the ring
with `lax.ppermute`, carrying online-softmax state — after sp steps
every query has seen every key, and no device ever materializes more
than T/sp keys. Peak memory per device is O(T/sp), communication is
sp-1 block rotations riding ICI (the scaling-book recipe for context
parallelism).

Causality works on absolute positions: shard i holds positions
[i*T_local, (i+1)*T_local); a rotated KV block contributes only keys
with position <= the query's. Whole blocks strictly in the future are
skipped arithmetically (their contribution masks to zero — the FLOPs
are spent but the ring stays in lockstep; the standard zig-zag
load-balance optimization trades that for schedule complexity and is
left out deliberately).

Inside each (query-block, kv-block) step the math is plain jnp — XLA
fuses the [T_local, T_local] tile through softmax; the pallas prefill
kernel covers the paged single-device case, this op covers the
multi-device dense case.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_attend(q, k, v, q_pos, k_pos, m, l, acc, scale, k_valid=None):
    """One online-softmax update of local queries against one KV block.
    q [B,Tq,H,Hd], k/v [B,Tk,K,Hd]; m/l [B,H,Tq] f32; acc [B,Tq,H,Hd] f32.
    `q_pos` is [Tq] or per-row [B, Tq]; `k_valid` [B, Tk] optionally
    masks block keys per row (the cached-prefix block's valid length)."""
    b, tq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, tq, kh, g, hd)
    s = jnp.einsum(
        "btkgd,bskd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale  # [B,K,G,Tq,Tk]
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    mask = (k_pos[:, None, :] <= q_pos[:, :, None])  # [B|1,Tq,Tk]
    if k_valid is not None:
        mask = mask & k_valid[:, None, :]
    mask = mask[:, None, None]  # [B|1,1,1,Tq,Tk]
    s = jnp.where(mask, s, _NEG_INF)
    m_blk = jnp.max(s, axis=-1)                      # [B,K,G,Tq]
    m_prev = m.reshape(b, kh, g, tq)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)                  # [B,K,G,Tq]
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask, p, 0.0)
    l_new = l.reshape(b, kh, g, tq) * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bkgts,bskd->btkgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [B,Tq,K,G,Hd]
    acc_new = (
        acc.reshape(b, tq, kh, g, hd)
        * alpha.transpose(0, 3, 1, 2)[..., None]
        + pv
    )
    return (
        m_new.reshape(b, h, tq),
        l_new.reshape(b, h, tq),
        acc_new.reshape(b, tq, h, hd),
    )


def ring_self_attention(
    q: jax.Array,  # [B, T_local, H, Hd] this shard's queries (rope applied)
    k: jax.Array,  # [B, T_local, K, Hd] this shard's keys
    v: jax.Array,
    pos0=None,          # [B] i32 absolute start of the (sharded) chunk
    prefix_k=None,      # [B, C, K, Hd] cached-prefix KV (sp-replicated)
    prefix_v=None,
    prefix_len=None,    # [B] i32 valid prefix rows (= pos0 in the engine)
    *,
    axis_name: str = "sp",
) -> jax.Array:
    """Causal self-attention with sequence sharded over `axis_name`;
    call inside shard_map/jit over a mesh with that axis. Returns the
    local output block [B, T_local, H, Hd] in q.dtype.

    With a cached prefix (prefix-cache hit on a long-context prompt),
    the chunk is the UNCACHED TAIL: `pos0` offsets every position, and
    one extra online-softmax block over the gathered prefix KV
    (replicated across the ring — it is ordinary pool data) seeds the
    state before the ring spins. This is what lets the sp engine keep
    the prefix cache instead of re-prefilling whole prompts."""
    b, tl, h, hd = q.shape
    scale = hd ** -0.5
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    base = idx * tl + jnp.arange(tl, dtype=jnp.int32)
    if pos0 is None:
        q_pos = base
    else:
        q_pos = pos0.astype(jnp.int32)[:, None] + base[None, :]  # [B, Tl]

    m = jnp.full((b, h, tl), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tl), jnp.float32)
    acc = jnp.zeros((b, tl, h, hd), jnp.float32)

    if prefix_k is not None:
        # chunked: a single block over a 100k-token prefix would
        # materialize the [B,K,G,Tq,C] f32 scores ring attention exists
        # to avoid — scan fixed-size prefix blocks with the same online
        # state instead
        c = prefix_k.shape[1]
        blk = min(c, 2048)
        nblk = -(-c // blk)
        c_pad = nblk * blk
        if c_pad != c:
            pad = ((0, 0), (0, c_pad - c), (0, 0), (0, 0))
            prefix_k = jnp.pad(prefix_k, pad)
            prefix_v = jnp.pad(prefix_v, pad)
        pl_len = prefix_len.astype(jnp.int32)[:, None]

        def prefix_body(i, carry):
            m, l, acc = carry
            pk = jax.lax.dynamic_slice_in_dim(prefix_k, i * blk, blk, 1)
            pv = jax.lax.dynamic_slice_in_dim(prefix_v, i * blk, blk, 1)
            kp = i * blk + jnp.arange(blk, dtype=jnp.int32)
            valid = kp[None, :] < pl_len  # [B, blk]
            return _block_attend(
                q, pk, pv, q_pos, kp, m, l, acc, scale, k_valid=valid
            )

        m, l, acc = jax.lax.fori_loop(0, nblk, prefix_body, (m, l, acc))

    # ring: at step s this shard holds the KV block originally on shard
    # (idx - s) mod sp; rotate towards the next rank each step
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(s, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - s) % sp
        k_pos = src * tl + jnp.arange(tl, dtype=jnp.int32)
        if pos0 is not None:
            # ring blocks hold CHUNK positions; shift into absolute ones
            # per row so causality composes with the prefix offset
            k_pos = pos0.astype(jnp.int32)[:, None] + k_pos[None, :]
            m, l, acc = _block_attend(
                q, k_blk, v_blk, q_pos, k_pos, m, l, acc, scale
            )
        else:
            m, l, acc = _block_attend(
                q, k_blk, v_blk, q_pos, k_pos, m, l, acc, scale
            )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, sp, body, (k, v, m, l, acc))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]  # [B,T,H,1]
    return (acc / denom).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                           pos0=None, prefix_k=None, prefix_v=None,
                           prefix_len=None):
    """Convenience wrapper: shard_map over `mesh` with the sequence dim
    sharded on `axis_name` (batch on dp, heads on tp untouched — ring and
    tensor parallel compose). Prefix KV replicates over the ring axis."""
    P = jax.sharding.PartitionSpec
    spec = P("dp", axis_name, "tp", None)
    if prefix_k is None:
        return jax.shard_map(
            functools.partial(ring_self_attention, axis_name=axis_name),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    pspec = P("dp", None, "tp", None)
    return jax.shard_map(
        functools.partial(ring_self_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec, P("dp"), pspec, pspec, P("dp")),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, pos0, prefix_k, prefix_v, prefix_len)
