"""Pallas TPU kernels over the paged LATENT pool (latent attention,
DeepSeek-V2): the decode kernel and the page writer.

A latent pool keeps ONE row a token a layer, `[c ; k_r]` (the normed
latent of `rank` values, then the rotated key slice every head shares:
512 + 64 for DeepSeek-V2), zero-padded to whole 128-lane tiles (`width`:
640; `ModelConfig.latent_pool_width` says why). In the absorbed form (models/llama.py
`_mla_attn_block`) that row is the key of every head AND, in its first
`rank` columns, the value: attention is multi-query over the rows
themselves. So the decode kernel copies a page in ONCE and uses the same
VMEM block for the score dot (all `width` columns) and the value dot (its
first `rank` columns); a K-pool / V-pool kernel would read the same bytes
twice.

Otherwise it is `ops/pallas_attention._decode_kernel`'s design, whose
pieces it imports: one grid program over the flat work list of (sequence,
page-block) items, an NBUF-deep ring of page DMAs that stays full across
sequence boundaries, a work item that copies and computes over only the
pages its sequence holds (`live_pages` / `switch_live_pages`: the engine
books `streamed_pages` by the same rule), and the pool taken through
`in_hbm` / `hbm_out` so that XLA's memory-space assignment has nothing to
decide about a loop-carried pool (KVCache docstring). The dots take the
pool's dtype (bf16 on the chip) with float32 accumulation; the softmax
state is float32.

The fused cache update (no XLA scatter on the decode path) is
`_decode_window_kernel`'s: in the ONE work item that owns the new token's
position, under `pl.when`, the row is merged into the `WRITE_BACK_ROWS`
(16: one packed bf16 tile of sublanes) aligned rows around it where they
lie in VMEM, and those 16 rows go back to the pool: 20,480 B a row a
layer. Every other item reads its block as the copies left it, and no
item may do more to it than that: the kernel runs at the time of its
copies with or without its dots (`scripts/mla_kernel_tpu.py`: `nomerge`,
`nocompute`; PERF.md section 6, PR 45), and one vector pass over an
item's `[512, 640]` block (the row selected into the whole block) costs
it a third more.

Bytes: a token's row is 576 values = 1,152 B in bf16 (1,280 B as it
lies, with the pad lanes); 16 heads do 2 x 16 x (576 + 512) flops on it,
~30 flops a byte, an eighth of the v5e's ridge (197 TFLOP/s over 819
GB/s = 240); 32 heads (Xing4.0) do ~60, a quarter of it. Both are bound
by HBM, and the kernel's share of that roofline is the benchmark's
`mla_decode_attn_roofline` (which counts the 1,152 B; the rows lie in
640 lanes and a sequence's last page is copied whole, so ~85% is what
the copies alone would read).

VMEM (width 640 lanes, page 128, 4 pages an item, ring of 4: 2.6 MB),
with every row's queries (bf16) and outputs (float32 `[B, H, 512]`)
resident for the whole call, at the benchmark's two shapes: decode width
128 x 16 heads (`deepseek-v2-lite-l9`): queries 2.6 MB, outputs 4.2 MB,
new rows 0.2 MB (2.6 MB if Mosaic pads the row to a tile of sublanes):
inside the compiler's default 16 MiB of scoped VMEM, and the call passes
no compiler parameter, as it never has. Decode width 256 x 32 heads
(`xing4.0-29b-a4b-l6`): queries 10.5 MB, outputs 16.8 MB: the call asks
for the scoped VMEM it holds (`vmem_limit_bytes`, of the chip's 128 MiB;
past 96 MiB it refuses with a sentence). What that costs: the queries are
copied in before the loop and the outputs out after it, 27 MB at the HBM
rate, ~33 us a layer that nothing overlaps, beside ~410 us of latent rows
at 256 rows x ~1,030 tokens; blocking both by the work item's sequence
(a ring of `[H, width]` query blocks filled with the pages' ring, the
output written back a sequence at a time) would put them under the loop
and is owed (ROADMAP M3).
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
    WRITE_BACK_ROWS,
    hbm_out,
    in_hbm,
    lax_cdiv,
    live_pages,
    switch_live_pages,
    work_list,
)


# scoped VMEM: the compiler's default limit, the most a call asks for
# (of the v5e's 128 MiB), and the room left for what Mosaic adds
_VMEM_DEFAULT = 16 << 20
_VMEM_MOST = 96 << 20
_VMEM_HEADROOM = 4 << 20


def _mla_decode_kernel(
    # scalar prefetch
    lengths_ref,       # [B] i32: attended rows per sequence (0 = inactive)
    tables_ref,        # [B, W] i32 page ids (W % pages_per_block == 0)
    wpos_ref,          # [B] i32 position whose row this step writes (-1 = none)
    work_seq_ref,      # [MAXW] i32 sequence of each work item
    work_blk_ref,      # [MAXW] i32 page-block index of each work item
    n_work_ref,        # [1] i32 number of valid work items
    # inputs (VMEM)
    q_ref,             # [B, H, width] absorbed, pre-scaled queries
    new_ref,           # [B, 1, width] new-token rows
    # input (HBM)
    pages_hbm,         # [num_pages, page_size, width]
    # outputs
    o_ref,             # [B, H, rank] float32 latent output
    pages_out_hbm,     # aliased pages_hbm
    # scratch
    buf,               # [NBUF, ppb, page_size, width] VMEM
    sems,              # DMA sems [NBUF]
    w_sem,             # DMA sem for the slab write-backs
    wb_pending,        # SMEM [NBUF]: write-back in flight from this slot
    *,
    page_size: int,
    pages_per_block: int,
    nbuf: int,
    rank: int,
    ablate: str = "",  # perf bisection: "nomerge" | "nocompute"
):
    t_blk = pages_per_block * page_size
    wb_rows = min(WRITE_BACK_ROWS, page_size)
    h, width = q_ref.shape[1], q_ref.shape[2]
    n_work = n_work_ref[0]

    def start_work_dma(w, slot):
        # the item's LIVE pages only: a table entry past the sequence's
        # end names the trash page, and nothing reads it
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        n_live = live_pages(lengths_ref[seq], blk, page_size, pages_per_block)
        for p in range(pages_per_block):

            @pl.when(p < n_live)
            def _start(p=p):
                page_id = tables_ref[seq, blk * pages_per_block + p]
                pltpu.make_async_copy(
                    pages_hbm.at[page_id], buf.at[slot, p], sems.at[slot],
                ).start()

    def wait_work_dma(slot, n):
        for _ in range(n):
            pltpu.make_async_copy(
                pages_hbm.at[0], buf.at[slot, 0], sems.at[slot]
            ).wait()

    def drain_wb(slot):
        # a pending write-back reads from buf[slot]; it must land before
        # that slot is reused as a DMA-in target (a wait names a copy of
        # the size that was started: the slab)
        @pl.when(wb_pending[slot] == 1)
        def _():
            rows = pl.ds(0, wb_rows)
            pltpu.make_async_copy(
                buf.at[0, 0, rows], pages_out_hbm.at[0, rows], w_sem
            ).wait()
            wb_pending[slot] = 0

    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(nbuf):
        wb_pending[j] = 0

        @pl.when(j < n_work)
        def _prologue(j=j):
            start_work_dma(j, j)

    def body(w, carry):
        m_prev, l_prev, acc = carry
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        length = lengths_ref[seq]
        wpos = wpos_ref[seq]
        slot = jax.lax.rem(w, nbuf)
        n_live = live_pages(length, blk, page_size, pages_per_block)

        # fresh sequence: reset the flash state
        is_first = blk == 0
        m_prev = jnp.where(is_first, jnp.full_like(m_prev, _NEG_INF), m_prev)
        l_prev = jnp.where(is_first, jnp.zeros_like(l_prev), l_prev)
        acc = jnp.where(is_first, jnp.zeros_like(acc), acc)

        def item(n, m_prev, l_prev, acc):
            # the whole item over its first `n` pages (static): fused
            # write, then one online-softmax step; sliced with the
            # copies, never masked after them (an uncopied buffer's NaN
            # times a probability of 0 is NaN)
            t = n * page_size
            wait_work_dma(slot, n)
            if ablate == "nocompute":
                # (times an iota's zeros: a splat accumulator is a layout
                # Mosaic cannot carry into the emit)
                touch = jnp.sum(
                    buf[slot, :n].reshape(t, width).astype(jnp.float32),
                    axis=0, keepdims=True)
                zeros = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0) < 0
                return m_prev, l_prev, acc + touch[:, :rank] * zeros.astype(
                    jnp.float32)

            # fused cache update, in the one item that owns position
            # `wpos` (attended, so its page is live): the new token's row
            # is merged into the `wb_rows` aligned rows around it where
            # they lie in VMEM, and just those rows go back
            do_write = (
                (wpos >= 0) & (wpos < length)
                & (blk == jax.lax.div(wpos, t_blk))
            )
            if ablate != "nomerge":

                @pl.when(do_write)
                def _merge():
                    off = wpos - blk * t_blk
                    p_local = jax.lax.div(off, page_size)
                    r = jax.lax.rem(off, page_size)
                    row0 = pl.multiple_of(
                        jax.lax.div(r, wb_rows) * wb_rows, wb_rows)
                    slab = pl.ds(row0, wb_rows)
                    row = jax.lax.broadcasted_iota(
                        jnp.int32, (wb_rows, width), 0)
                    buf[slot, p_local, slab] = jnp.where(
                        row == r - row0, new_ref[seq], buf[slot, p_local, slab]
                    )
                    page_id = tables_ref[seq, jax.lax.div(wpos, page_size)]
                    pltpu.make_async_copy(
                        buf.at[slot, p_local, slab],
                        pages_out_hbm.at[page_id, slab], w_sem,
                    ).start()
                    wb_pending[slot] = 1

            kb = buf[slot, :n].reshape(t, width)

            # every head against the ONE row a token keeps: [H, W] x [t, W]
            s = jax.lax.dot_general(
                q_ref[seq], kb,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, t]
            pos = blk * t_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, _NEG_INF)

            m_curr = jnp.max(s, axis=-1, keepdims=True)            # [H, 1]
            m_next = jnp.maximum(m_prev, m_curr)
            p_blk = jnp.exp(s - m_next)                             # [H, t]
            l_next = (
                jnp.exp(m_prev - m_next) * l_prev
                + jnp.sum(p_blk, axis=-1, keepdims=True)
            )
            # the value is the same block's first `rank` columns
            o_curr = jax.lax.dot_general(
                p_blk.astype(kb.dtype), kb[:, :rank],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, rank]
            return m_next, l_next, acc * jnp.exp(m_prev - m_next) + o_curr

        m_prev, l_prev, acc = switch_live_pages(
            item, n_live, pages_per_block, m_prev, l_prev, acc
        )

        @pl.when(blk == lax_cdiv(length, t_blk) - 1)
        def _emit():
            o_ref[seq] = acc / jnp.maximum(l_prev, 1e-30)

        nxt = w + nbuf

        @pl.when(nxt < n_work)
        def _refill():
            drain_wb(slot)
            start_work_dma(nxt, slot)

        return m_prev, l_prev, acc

    m0 = jnp.full((h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    a0 = jnp.zeros((h, rank), jnp.float32)
    jax.lax.fori_loop(0, n_work, body, (m0, l0, a0))
    for j in range(nbuf):
        drain_wb(j)


def resident_vmem_bytes(b: int, h: int, width: int, rank: int, item: int,
                        *, page_size: int,
                        pages_per_block: int = PAGES_PER_BLOCK,
                        nbuf: int = NBUF) -> int:
    """What the decode kernel keeps in VMEM (module docstring): every
    row's queries and float32 outputs, the new rows (a row in 16 sublanes)
    and the ring of page blocks."""
    return (
        b * h * (width * item + rank * 4) + b * 16 * width * item
        + nbuf * pages_per_block * page_size * width * item
    )


def vmem_request(resident: int, what="latent decode kernel") -> int | None:
    """The scoped VMEM the call asks the compiler for: None while what is
    resident fits the compiler's default (16 MiB less headroom: 128 rows x
    16 heads lower as they always have, with no parameter;
    tests/test_tpu_compile.py holds that shape under the line), else what
    it holds and headroom, of the v5e's 128 MiB."""
    if resident <= _VMEM_DEFAULT - _VMEM_HEADROOM:
        return None
    if resident + _VMEM_HEADROOM > _VMEM_MOST:
        raise ValueError(
            f"{what}: {resident / 2**20:.0f} MiB of queries, "
            f"outputs and ring resident, over the {_VMEM_MOST >> 20} MiB "
            "it may ask for"
        )
    return resident + _VMEM_HEADROOM


@functools.partial(
    jax.jit,
    static_argnames=["rank", "page_size", "pages_per_block", "nbuf",
                     "interpret", "ablate"],
)
def mla_paged_decode_attention(
    qa: jax.Array,            # [B, H, width] absorbed queries, pre-scaled
    new_rows: jax.Array,      # [B, width] this step's latent rows
    pool: jax.Array,          # [num_slots, width] flat latent pool
    block_tables: jax.Array,  # [B, W] i32 page ids (0 = trash page)
    lengths: jax.Array,       # [B] i32 attended rows incl. the new token
    write_pos: jax.Array,     # [B] i32 position to store the row (-1 = skip)
    *,
    rank: int,
    page_size: int,
    pages_per_block: int = PAGES_PER_BLOCK,
    nbuf: int = NBUF,
    interpret: bool = False,
    ablate: str = "",  # scripts/mla_kernel_tpu.py's timings only
):
    """Absorbed latent decode attention fused with the pool update.
    Returns (latent output [B, H, rank] float32, pool); the pool is
    updated in place (aliased)."""
    b, h, width = qa.shape
    num_slots = pool.shape[0]
    num_pages = num_slots // page_size
    t_blk = pages_per_block * page_size

    w = block_tables.shape[1]
    if w % pages_per_block:
        block_tables = jnp.pad(
            block_tables, ((0, 0), (0, pages_per_block - w % pages_per_block))
        )
    max_blocks = block_tables.shape[1] // pages_per_block
    lengths = lengths.astype(jnp.int32)
    work_seq, work_blk, n_work = work_list(lengths, t_blk, max_blocks)

    pages = in_hbm(pool.reshape(num_pages, page_size, width), interpret)
    hbm = pl.ANY if interpret else pltpu.MemorySpace.HBM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=hbm),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=hbm),
        ],
        scratch_shapes=[
            pltpu.VMEM((nbuf, pages_per_block, page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((nbuf,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel, page_size=page_size,
        pages_per_block=pages_per_block, nbuf=nbuf, rank=rank, ablate=ablate,
    )
    params = None
    if not interpret:
        limit = vmem_request(resident_vmem_bytes(
            b, h, width, rank, jnp.dtype(pool.dtype).itemsize,
            page_size=page_size, pages_per_block=pages_per_block, nbuf=nbuf))
        if limit:
            params = pltpu.CompilerParams(vmem_limit_bytes=limit)
    out, pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        compiler_params=params,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
            jax.ShapeDtypeStruct(pages.shape, pool.dtype) if interpret
            else hbm_out(pages),
        ],
        # inputs: 0..5 = scalar prefetch, 6 = queries, 7 = new rows,
        # 8 = the pool's pages, aliased onto output 1
        input_output_aliases={8: 1},
        interpret=interpret,
    )(lengths, block_tables.astype(jnp.int32), write_pos.astype(jnp.int32),
      work_seq, work_blk, n_work[None], qa.astype(pool.dtype),
      new_rows.reshape(b, 1, width).astype(pool.dtype), pages)
    return out, pages.reshape(num_slots, width)


def _write_kernel(tbl_ref, pages_ref, src_ref, out_ref):
    del tbl_ref, pages_ref  # aliased through; only the indexed pages change
    out_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def latent_page_write(
    pool: jax.Array,        # [num_slots, width]
    page_table: jax.Array,  # [n_pages] i32 destination page ids (0 = trash)
    new_pages: jax.Array,   # [n_pages, page_size, width] source pages
    *,
    page_size: int,
    interpret: bool = False,
):
    """Write whole pages of latent rows into the pool, in place: the
    prefill-side update (`ops/pallas_kv_write.paged_kv_write` for one
    pool; the same contract: page-aligned chunk starts, a last page's
    tail is the sequence's own not-yet-valid positions or the trash
    page)."""
    num_slots, width = pool.shape
    n = page_table.shape[0]
    pages = in_hbm(pool.reshape(num_slots // page_size, page_size, width),
                   interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, page_size, width), lambda i, tbl: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, page_size, width), lambda i, tbl: (tbl[i], 0, 0)),
        ],
    )
    (out,) = pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pages.shape, pages.dtype) if interpret
            else hbm_out(pages)
        ],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pages, new_pages.astype(pool.dtype))
    return out.reshape(num_slots, width)
