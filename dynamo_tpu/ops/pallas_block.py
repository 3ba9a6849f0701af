"""Pallas TPU kernel of a BLOCK PASS's attention read: a model generated by
diffusion over blocks (`ModelConfig.dlm`; `engine.py: _dlm_multi`) carries
a whole block of L = `mask_block` positions a row through every pass, and
every position of a block sees the same keys: the mask is `k_pos <= q_pos
| (L - 1)`, and a block starts at a multiple of L (`Sequence.prefill_end`
rounds the prompt down to whole blocks, `_dlm_after` moves a row's first
position by L a commit, a page holds whole blocks), so `q_pos | (L - 1)`
is the block's last position for each of its queries. A row's L x G
queries a KV head (G query heads share it) are therefore ONE multi-query
tile over that head's keys, as the latent kernel's heads are over a latent
row (`ops/pallas_mla.py`), and the kernel is `_decode_kernel`'s design
with that tile, from the pieces `ops/pallas_attention.py` exports:

- ONE grid program over the flat work list of (row, page-block) items
  that `work_list` makes from the attended lengths (`block_lengths`:
  `q_pos0 + L`): a padding row (`q_len` 0) and a page-block past a row's
  last position have no item. The prefill kernel this read went through before
  (`ops/pallas_prefill.py`, still the read of every other ragged call)
  walked a grid step a page-block a row whatever the row held, 2,048 a
  layer at the benchmark's 256 x 32 table columns, at tiles of 8
  positions of which a block fills 4.
- an NBUF-deep `_PageRing` of K and V page copies that stays full across
  rows; an item copies, waits for and computes over its `live_pages` only
  (`switch_live_pages`), READ-ONLY: the block's own rows are in the pool
  already (`_write_rows`, scope `attn.kv_write`), nothing is written back
  and no pool is aliased.
- queries arranged `[B, KH, L*G, Hd]` (row `p*G + g` of KV head `k` is
  position `p`, head `k*G + g`) and outputs `[B, KH, L*G, Vd]`, resident
  in VMEM for the call as both decode kernels hold theirs: 8 MiB each at
  256 rows x 4 KV heads x 32 x 128 bf16, beside two 2 MiB rings; the call
  asks for the scoped VMEM it holds by `ops/pallas_mla.vmem_request`'s
  rule.
- per item, for each KV head (a static loop): `[L*G, Hd] x [t, Hd]`
  scores, the online softmax's float32 state (m, l, acc) carried a KV
  head across a row's items, `[L*G, t] x [t, Vd]` values; the output is
  emitted at the row's last item. Per-head dots, not `_decode_kernel`'s
  block-diagonal operand: that trick exists because G there is 4; at 32
  rows a head the operand is two whole bf16 tiles of sublanes already and
  the block diagonal would quadruple the MXU's work.
- the mask is the formula it always was, `k_pos <= q_pos | (L - 1)` and
  `k_pos < length`, on the item's scores: for a block that starts at a
  multiple of L it cuts only inside the row's last page; a block that
  does not (no caller makes one) is still read as the gather oracle reads
  it, its attended length being what the formula lets its last query
  reach.

Precision: the dots take the pool's dtype (bf16 on the chip) with float32
accumulation, as the latent kernel's and every weight matmul of the model
do; scores, softmax state and accumulators are float32. The `Hd ** -0.5`
scale multiplies the float32 SCORES, not the query: a product of two bf16
values is exact in float32, so the score dot gives what a float32 dot on
converted operands gives up to the order of summation, which a scaled
query rounded back to bf16 would not. The probabilities stay float32 into
the value dot, as two terms in the pool's dtype (the rounding and what it
left: 16 of the 24 bits) stacked into one 2 x L*G-row operand against the
values as they lie, with no converted copy of them: probabilities rounded
to bf16 alone read a served gap 0.0001-0.0003 higher at every seed read
(PERF.md section 6, PR 48), and against the gather oracle at `highest`
precision on the chip the kernel's output is the oracle's rounded to
bf16, to the last digit of the mean (scripts/block_kernel_tpu.py).

Sharding: written for the per-shard view (local KV heads) like its
siblings; a block step under a mesh is refused today (docs/architecture.md
"Block steps").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_attention import (
    _NEG_INF,
    NBUF,
    PAGES_PER_BLOCK,
    _PageRing,
    in_hbm,
    lax_cdiv,
    live_pages,
    switch_live_pages,
    work_list,
)
from dynamo_tpu.ops.pallas_mla import vmem_request


def _block_kernel(
    # scalar prefetch
    lengths_ref,       # [B] i32: attended keys a row (`block_lengths`)
    tables_ref,        # [B, W] i32 page ids (W % pages_per_block == 0)
    pos0_ref,          # [B] i32 the block's first position
    work_seq_ref,      # [MAXW] i32 row of each work item
    work_blk_ref,      # [MAXW] i32 page-block index of each work item
    n_work_ref,        # [1] i32 number of valid work items
    # input (VMEM)
    q_ref,             # [B, KH, L*G, Hd] unscaled queries
    # inputs (HBM)
    k_pages_hbm,       # [num_pages, page_size, KH*Hd]
    v_pages_hbm,       # [num_pages, page_size, KH*Vd]
    # output (VMEM)
    o_ref,             # [B, KH, L*G, Vd]
    # scratch
    k_buf,             # [NBUF, ppb, page_size, KH*Hd] VMEM
    v_buf,             # [NBUF, ppb, page_size, KH*Vd]
    k_sems,            # DMA sems [NBUF]
    v_sems,
    *,
    page_size: int,
    pages_per_block: int,
    nbuf: int,
    mask_block: int,
    group: int,        # G: query heads a KV head
    ablate: str = "",  # perf bisection: "nocompute" | "empty"
):
    ring = _PageRing(k_pages_hbm, v_pages_hbm, None, None,
                     k_buf, v_buf, k_sems, v_sems, None, None)
    t_blk = pages_per_block * page_size
    kh, lg, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    vd = o_ref.shape[3]
    scale = hd ** -0.5
    n_work = n_work_ref[0]

    def start_work_dma(w, slot, settle=False):
        # the item's LIVE pages only: a table entry past the row's last
        # position names the trash page, and nothing reads it (`settle`:
        # wait for those copies, not start them)
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        n_live = live_pages(lengths_ref[seq], blk, page_size, pages_per_block)
        for p in range(pages_per_block):

            @pl.when(p < n_live)
            def _start(p=p):
                if settle:
                    ring.wait(slot, 1)
                    return
                ring.copy_in(
                    tables_ref[seq, blk * pages_per_block + p], slot, p)

    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(nbuf):

        @pl.when(j < n_work)
        def _prologue(j=j):
            start_work_dma(j, j)

    if ablate == "empty":
        # launch, operand copies, prologue: what it started is waited for
        for j in range(nbuf):

            @pl.when(j < n_work)
            def _settle(j=j):
                start_work_dma(j, j, settle=True)

        return

    def body(w, carry):
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        length = lengths_ref[seq]
        slot = jax.lax.rem(w, nbuf)
        n_live = live_pages(length, blk, page_size, pages_per_block)

        # fresh row: reset the flash state of every KV head
        is_first = blk == 0
        ms, ls, accs = carry
        ms = tuple(jnp.where(is_first, _NEG_INF, m) for m in ms)
        ls = tuple(jnp.where(is_first, 0.0, l) for l in ls)
        accs = tuple(jnp.where(is_first, 0.0, a) for a in accs)

        def item(n, ms, ls, accs):
            # the whole item over its first `n` pages (static): sliced
            # with the copies, never masked after them (an uncopied
            # buffer's NaN times a probability of 0 is NaN)
            t = n * page_size
            ring.wait(slot, n)
            if ablate == "nocompute":
                # (times an iota's zeros: a splat accumulator is a layout
                # Mosaic cannot carry into the emit)
                touch = (k_buf[slot, n - 1, :1, :vd].astype(jnp.float32)
                         + v_buf[slot, n - 1, :1, :vd].astype(jnp.float32))
                zeros = (jax.lax.broadcasted_iota(jnp.int32, (lg, vd), 0)
                         < 0).astype(jnp.float32)
                return ms, ls, tuple(a + touch * zeros for a in accs)

            # a query at row r is position `pos0 + r // G`: it sees keys
            # up to the end of its own block of `mask_block` positions,
            # and none past the row's attended length
            q_pos = pos0_ref[seq] + jax.lax.broadcasted_iota(
                jnp.int32, (lg, 1), 0) // group
            reach = jnp.minimum(
                (q_pos | (mask_block - 1)) + 1, length) - blk * t_blk
            valid = jax.lax.broadcasted_iota(jnp.int32, (lg, t), 1) < reach

            out = ([], [], [])
            for k in range(kh):
                kb = k_buf[slot, :n, :, k * hd:(k + 1) * hd].reshape(t, hd)
                vb = v_buf[slot, :n, :, k * vd:(k + 1) * vd].reshape(t, vd)
                s = jax.lax.dot_general(
                    q_ref[seq, k], kb,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                                       # [LG, t]
                s = jnp.where(valid, s, _NEG_INF)
                m_next = jnp.maximum(
                    ms[k], jnp.max(s, axis=-1, keepdims=True))  # [LG, 1]
                p_blk = jnp.exp(s - m_next)
                alpha = jnp.exp(ms[k] - m_next)
                # float32 probabilities against the pool's own values: a
                # probability is its rounding to the pool's dtype and what
                # that rounding left, both rows of ONE operand (the values'
                # tiles are loaded once), their products added in float32
                p_hi = p_blk.astype(vb.dtype)
                p_lo = (p_blk - p_hi.astype(jnp.float32)).astype(vb.dtype)
                o_both = jax.lax.dot_general(
                    jnp.concatenate([p_hi, p_lo], axis=0), vb,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                             # [2 LG, Vd]
                o_curr = o_both[:lg] + o_both[lg:]
                out[0].append(m_next)
                out[1].append(
                    alpha * ls[k] + jnp.sum(p_blk, axis=-1, keepdims=True))
                out[2].append(accs[k] * alpha + o_curr)
            return tuple(map(tuple, out))

        ms, ls, accs = switch_live_pages(
            item, n_live, pages_per_block, ms, ls, accs)

        # last item of this row: emit the normalized output
        @pl.when(blk == lax_cdiv(length, t_blk) - 1)
        def _emit():
            for k in range(kh):
                o_ref[seq, k] = (
                    accs[k] / jnp.maximum(ls[k], 1e-30)).astype(o_ref.dtype)

        # refill the ring with the work item NBUF ahead
        nxt = w + nbuf

        @pl.when(nxt < n_work)
        def _refill():
            start_work_dma(nxt, slot)

        return ms, ls, accs

    def per_head(shape, value):
        return tuple(jnp.full(shape, value, jnp.float32) for _ in range(kh))

    jax.lax.fori_loop(0, n_work, body, (
        per_head((lg, 1), _NEG_INF), per_head((lg, 1), 0.0),
        per_head((lg, vd), 0.0)))


def block_lengths(q_pos0, q_lens, mask_block: int, table_positions: int,
                  xp=jnp):
    """Keys a block pass's rows attend: what the mask's formula lets a
    row's last query reach, `(q_pos0 + L - 1) | (L - 1)`, the end of the
    block that starts at `q_pos0` (a multiple of L from every caller:
    `q_pos0 + L` keys), held to what the block table can name; 0 for a
    padding row (`q_len` 0). The ONE rule the kernel's work list is built
    to and the engine books its `dlm` digests by (`xp=np`)."""
    reach = ((q_pos0 + (mask_block - 1)) | (mask_block - 1)) + 1
    return xp.where(q_lens > 0, xp.minimum(reach, table_positions), 0)


@functools.partial(
    jax.jit,
    static_argnames=["page_size", "mask_block", "pages_per_block", "nbuf",
                     "interpret", "ablate"],
)
def block_paged_attention(
    q: jax.Array,             # [B, L, H, Hd] (rope applied, unscaled)
    k_cache: jax.Array,       # [num_slots, KH*Hd] flat slot pool
    v_cache: jax.Array,       # [num_slots, KH*Vd]
    block_tables: jax.Array,  # [B, W] i32 page ids (0 = trash page)
    q_pos0: jax.Array,        # [B] i32 the block's first position
    q_lens: jax.Array,        # [B] i32 L, or 0 for a padding row
    *,
    page_size: int,
    mask_block: int,
    pages_per_block: int = PAGES_PER_BLOCK,
    nbuf: int = NBUF,
    interpret: bool = False,
    ablate: str = "",  # scripts/block_kernel_tpu.py's timings only
) -> jax.Array:
    """Read-only paged attention of a block pass (module docstring): every
    row carries `L = mask_block` queries from `q_pos0`, keys and values
    already in the pool. Returns [B, L, H, Vd] in q.dtype; a padding row
    gives zeros.

    Under a caller's scope (`attn.block`) the program is the queries' and
    the outputs' rearrangement and ONE custom call, the kernel's: the work
    list's operations carry an inner scope of their own,
    `attn.block_work`, so that a reader of the trace can count passes by
    the kernel's events (benchmark/lib/shapes_dlm.py)."""
    b, t, h, hd = q.shape
    assert t == mask_block, (t, mask_block)
    num_slots, kw = k_cache.shape
    vw = v_cache.shape[1]
    kh = kw // hd
    g = h // kh
    vd = vw // kh
    lg = t * g
    num_pages = num_slots // page_size
    t_blk = pages_per_block * page_size

    with jax.named_scope("attn.block_work"):
        tables = block_tables.astype(jnp.int32)
        w = tables.shape[1]
        if w % pages_per_block:
            tables = jnp.pad(
                tables, ((0, 0), (0, pages_per_block - w % pages_per_block)))
        lengths = block_lengths(
            q_pos0.astype(jnp.int32), q_lens, mask_block, w * page_size)
        work_seq, work_blk, n_work = work_list(
            lengths, t_blk, tables.shape[1] // pages_per_block)
        scalars = (lengths, tables, q_pos0.astype(jnp.int32), work_seq,
                   work_blk, n_work[None])

    # [B, L, KH, G, Hd] -> [B, KH, L*G, Hd]: a block's queries of the G
    # heads that share a KV head are the rows of one MXU operand
    qk = q.astype(k_cache.dtype).reshape(b, t, kh, g, hd).transpose(
        0, 2, 1, 3, 4).reshape(b, kh, lg, hd)
    k_pages = in_hbm(k_cache.reshape(num_pages, page_size, kw), interpret)
    v_pages = in_hbm(v_cache.reshape(num_pages, page_size, vw), interpret)
    hbm = pl.ANY if interpret else pltpu.MemorySpace.HBM
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[vmem, pl.BlockSpec(memory_space=hbm),
                  pl.BlockSpec(memory_space=hbm)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((nbuf, pages_per_block, page_size, kw), k_cache.dtype),
            pltpu.VMEM((nbuf, pages_per_block, page_size, vw), v_cache.dtype),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
    )
    params = None
    if not interpret:
        item = jnp.dtype(k_cache.dtype).itemsize
        limit = vmem_request(
            b * kh * lg * (hd * item + vd * q.dtype.itemsize)
            + nbuf * t_blk * (kw + vw) * item,
            what="block attention kernel")
        if limit:
            params = pltpu.CompilerParams(vmem_limit_bytes=limit)
    out = pl.pallas_call(
        functools.partial(
            _block_kernel, page_size=page_size,
            pages_per_block=pages_per_block, nbuf=nbuf,
            mask_block=mask_block, group=g, ablate=ablate),
        grid_spec=grid_spec,
        compiler_params=params,
        out_shape=jax.ShapeDtypeStruct((b, kh, lg, vd), q.dtype),
        interpret=interpret,
    )(*scalars, qk, k_pages, v_pages)
    # [B, KH, L*G, Vd] -> [B, L, H, Vd]
    return out.reshape(b, kh, t, g, vd).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, vd)
