"""Pallas TPU paged-attention decode kernel, fused with the KV-cache write.

The TPU-native answer to the GPU stack's paged-attention + block-copy
kernels (reference: vLLM paged attention and
lib/llm/src/kernels/block_copy.cu:41-731 — there paging is a copy problem
bolted onto a dense kernel; here the kernel reads pages directly and the
cache update happens inside the same kernel).

Decode attention is HBM-bandwidth bound: each step must stream every live
KV page exactly once. Design points (measured on v5e):

- **one grid program over a flat work list**: the host side flattens
  (sequence, page-block) pairs into a work queue; the kernel walks it in
  a single fori loop with an NBUF-deep ring of DMA buffers, so page
  streams stay full across sequence boundaries. A (batch,) grid paid
  ~20 us of pipeline overhead per program; per-program double buffering
  stalled at every sequence switch.
- **a work item covers only the pages its sequence holds**
  (`live_pages`: `pages_per_block` in every block but a sequence's last,
  there what is left of `ceil(length / page_size)`, computed from the
  lengths the kernel already has). The item's copies are started and
  waited for page by page under that count, and its convert / QK /
  softmax / PV run over `live_pages * page_size` positions under a
  `lax.switch` on it: shapes are static, so each count is a branch over
  statically sliced buffers with the same (m, l, acc) carry. A copy
  skipped WITHOUT its compute skipped is wrong, not slow: the ring's
  buffers start uninitialised, the mask is `exp(-inf) = 0`, and a
  float32 scale tile (or a bf16 page) of garbage times 0 is NaN. Before
  this rule a 512-token item read, converted and multiplied up to three
  trash pages past each sequence's end: 1.17x the pages held at the
  benchmark's decode-saturate contexts (PERF.md section 6, PR 32).
  `streamed_pages` is the same rule on the host: the engine's digests
  and the benchmark's `decode_kv_read_amp` count by it.
- **a window layer's work item holds several sequences**
  (`_decode_window_kernel`, taken when the layer kind's static window
  fits one item: `window_grouped`). A window of `window_pages` pages is
  first, last and writing item at once: it needs no carried (m, l, acc),
  no rescale and no branch a page count, so its body is a sibling, not a
  branch of the long-context one. `WINDOW_GROUP` live sequences share a
  loop iteration: one expand dot and one emit dot for all, score and PV
  dots batched over the sequence axis (no cross-sequence products), and
  the fused write sends back `WRITE_BACK_ROWS` rows, not a page. Measured
  (PERF.md section 6, PR 37): such an item was never bound by its chain
  of dots but by its bytes, two whole pages in and one whole page out for
  one new row; the slab took a third of them away, the group a few
  percent more. The ring's helpers (`_PageRing`), `live_pages`, the sink
  and the float32 dots are shared with the per-sequence item.
- **fused cache write**: XLA lowers `pool.at[slots].set(rows)` to a
  scatter the TPU backend serializes (~20 us/row); instead the kernel
  injects the new token's K/V into its page while that page sits in VMEM
  and writes only that page back — no scatter anywhere on the decode path.
- **block-diagonal GQA matmuls**: per page-block the scores for ALL kv
  heads come from ONE `[H, K*Hd] @ [K*Hd, T]` MXU dot — queries are laid
  out block-diagonally (q for kv head k occupies columns [k*Hd,(k+1)*Hd)),
  so cross-head products vanish by construction. The FLOP padding is free
  (the MXU was idle); a per-head loop of [G,Hd] dots + a concat was the
  compute bottleneck. The PV product is one `[H, T] @ [T, K*Hd]` dot whose
  block-diagonal slice is selected outside the kernel.
- pools are `[num_slots, K*Hd]` so pages ([page_size, K*Hd] rows) are
  physically contiguous — XLA lays [N, K, Hd] out slot-minor, which turns
  page DMA into a strided scatter (~15x slower).

VMEM budget: q/out [B, H, K*Hd] + NBUF block buffers. At the deployment
the benchmark runs (Mistral-7B: H=32, K*Hd=1024; int32-packed int8 pages
of 128, decode width 64, ppb 4 = a 512-token item, NBUF 4): two 2 MiB
page rings (4 x 4 x 32 packed rows x 1024 x 4 B, K and V), two 4 MiB
query / output blocks (64 x 32 x 1024 bf16), under 0.3 MiB of scale
rings, staging tiles and new rows. bf16 pools at page 64 (256-token
items): two 4 MiB rings at the same widths. The block was tuned at 256
tokens and doubled with the deployment's page; smaller blocks were timed
again on the chip in PR 32 and are slower (PERF.md section 6): an item's
fixed cost (semaphore waits, the accumulator's rescale, two MXU
fill / drains in a serial QK -> softmax -> PV chain that nothing overlaps
across loop iterations) is paid per item, and the page count per item
takes the saving with no more items. What bounds the kernel there is the
vector unit (int8 -> f32 of every K and V tile), not its copies: with
the convert ablated it runs at its DMA floor.

Sharding: KV heads are the tp axis. The kernel is written for the
per-shard view (local K heads); `shard_map` wrapping happens in the
caller so single-chip runs skip it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def in_hbm(pool: jax.Array, interpret: bool = False) -> jax.Array:
    """A quantized pool as a kernel OPERAND, its memory space stated: HBM.

    Left unstated, XLA's memory-space assignment takes any loop-carried
    buffer that fits (a 3.3 MB f32 scale pool does, a 26 MB K pool does
    not) for a prefetch candidate: it moved each of the 64 scale pools
    into VMEM in four slices before its kernel and copied it back after,
    on every step of the decode scan (3.5-4.5 ms of a 19.4 ms step on
    v5e; KVCache docstring, docs/kv_cache.md). Every pallas_call that
    takes a quantized pool wraps it with this and declares the pool it
    returns with `hbm_out`, so the pass has nothing to decide. The
    interpreter has no memory spaces and refuses an operand that names
    one, so under `interpret` the pool passes through as it is."""
    if interpret:
        return pool
    return pltpu.with_memory_space_constraint(pool, pltpu.HBM)


def hbm_out(pool: jax.Array):
    """The `out_shape` entry of a pool a kernel returns (aliased onto its
    `in_hbm` operand): same shape and dtype, memory space HBM."""
    return pltpu.HBM(pool.shape, pool.dtype)


# the serving call site passes neither: a work item is this many pages
# (512 tokens at the benchmark's page 128, 256 at page 64), the DMA ring
# this many items deep. Timed alone on the v5e at the decode-saturate
# cell's shape (scripts/kernel_check_tpu.py --cell-shape; PERF.md section
# 6, PR 32): blocks of 1 / 2 / 8 pages and rings of 2 / 3 / 6 / 8 items
# are all slower.
PAGES_PER_BLOCK = 4
NBUF = 4
# sequences a WINDOW layer's work item holds (`_decode_window_kernel`),
# and rows of a page its fused write merges and sends back: the aligned
# slab around the new token's row, one packed bf16 tile (the whole page
# where pages are shorter). Timed alone on the v5e at the reason-wide
# cell's shape (scripts/hybrid_kernel_tpu.py: 5 window layers, 192 rows;
# ~1.3 ms of every reading is launch and operand copies; PERF.md section
# 6, PR 37): the per-sequence list 4.30 ms; this item with a whole page
# sent back 3.95 / 3.91 / 3.88 / 3.92 at 1 / 2 / 4 / 8 sequences, with the
# slab 3.08 / 3.01 / 3.01 / 3.06 (ring 2 or 4 deep: the same), its copies
# and waits alone 2.83. The item is bound by the pages it moves, so the
# slab is the gain and the group a small one: 4 fills the MXU's rows in
# the two shared dots, 8 needs a ring of 2 to fit VMEM and is no faster.
WINDOW_GROUP = 4
WRITE_BACK_ROWS = 16
# scoped VMEM the unquantized decode kernel may take (the v5e has 128 MiB):
# at 256 sequences of 64 heads the queries, the output and the two page
# rings of a 1,536 / 1,024-wide layer pass the 16 MiB default together
DECODE_VMEM_LIMIT = 64 << 20


def live_pages(length, blk, page_size: int, pages_per_block: int, xp=jnp):
    """Pages of work item (sequence, block `blk`) that the sequence holds:
    `pages_per_block` in every block but the last, there what is left of
    `ceil(length / page_size)`. The ONE rule of what a work item copies
    in, waits for and computes over; `streamed_pages` sums it (`xp=np`:
    host arrays; the kernels pass traced int32 scalars)."""
    div = np.floor_divide if xp is np else jax.lax.div
    pages = div(length + (page_size - 1), page_size)
    return xp.minimum(pages - blk * pages_per_block, pages_per_block)


def switch_live_pages(item, n_live, pages_per_block: int, *carry):
    """`item(n, *carry)` under a branch on the work item's live page count
    (1..pages_per_block): shapes in a kernel are static, so each count is
    its own branch over statically sliced buffers, all returning the same
    carry."""
    if pages_per_block == 1:
        return item(1, *carry)
    return jax.lax.switch(
        n_live - 1,
        [functools.partial(item, n) for n in range(1, pages_per_block + 1)],
        *carry,
    )


def work_list(lengths: jax.Array, t_blk: int, max_blocks: int):
    """The kernels' flat work list: (sequence, page-block) pairs in
    sequence order, `ceil(length / t_blk)` a sequence, empty rows skipped
    — the DMA ring stays full across sequence boundaries. Returns
    (work_seq [B * max_blocks], work_blk, n_work); entries from `n_work`
    on are 0 and never walked."""
    b = lengths.shape[0]
    bps = (lengths + t_blk - 1) // t_blk                   # blocks per seq
    csum = jnp.cumsum(bps)
    n_work = csum[-1]
    widx = jnp.arange(b * max_blocks, dtype=jnp.int32)
    work_seq = jnp.searchsorted(csum, widx, side="right").astype(jnp.int32)
    safe_seq = jnp.minimum(work_seq, b - 1)
    work_blk = widx - (csum[safe_seq] - bps[safe_seq])
    work_seq = jnp.where(widx < n_work, safe_seq, 0)
    work_blk = jnp.where(widx < n_work, work_blk, 0).astype(jnp.int32)
    return work_seq, work_blk, n_work


def window_pages(window: int, page_size: int) -> int:
    """Pages a window of `window` positions can touch: it may begin
    anywhere in its first page, so one more than it fills."""
    return -(-window // page_size) + 1


def window_grouped(window: int, page_size: int, pages_per_block: int) -> bool:
    """Whether a layer with this (static) window takes the grouped work
    item (`_decode_window_kernel`): a window that fits one item of the
    per-sequence list needs none of that list's carried state. The ONE
    rule of the path, decided from shapes alone: the kernel's wrapper,
    `streamed_pages` and the engine's digest all ask it."""
    return bool(window) and window_pages(window, page_size) <= pages_per_block


def window_items(lengths, group: int = WINDOW_GROUP, xp=jnp):
    """Work items of ONE window layer's grouped kernel for a call's
    attended lengths (last axis: the call's rows, 0 = a row with no
    work): its LIVE rows `group` at a time, the last item partial.
    `window_work_list` walks this many; the engine books it per decode
    dispatch (digest column `kv_win_items`, `xp=np`)."""
    live = xp.sum(lengths > 0, axis=-1)
    return (live + (group - 1)) // group


def window_work_list(lengths: jax.Array, group: int):
    """The grouped kernel's work list: the live rows compacted in row
    order (padding rows of a wide decode program cost nothing), item `w`
    holding entries [w * group, (w + 1) * group). Entries past the last
    live row repeat it: every item copies and computes `group` whole
    windows, and a repeat writes nothing, neither page nor output.
    Returns (rows [ceil(B / group) * group], n [2] = items, live rows)."""
    b = lengths.shape[0]
    live = lengths > 0
    n_live = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    idx = jnp.arange(-(-b // group) * group, dtype=jnp.int32)
    rows = order[jnp.minimum(idx, jnp.maximum(n_live - 1, 0))]
    return rows, jnp.stack([window_items(lengths, group), n_live]).astype(
        jnp.int32)


def streamed_pages(lengths, page_size: int,
                   pages_per_block: int = PAGES_PER_BLOCK, starts=None,
                   window: int = 0, group: int = WINDOW_GROUP) -> int:
    """KV pages ONE layer's decode kernel copies in for these attended
    lengths (any shape; 0 = a row with no work): the work list's
    `ceil(length / block)` items a sequence, each its `live_pages`; with
    `starts` (a window's first attended positions, the same shape) the
    list begins at the page that holds the start, as the kernel's does.
    With a static `window` that takes the grouped item (`window_grouped`;
    lengths [..., rows], a call a row of the leading axes) it is that
    item's rule: `window_pages` copies for each of an item's `group`
    entries, a dead page slot and a partial item's repeats included. The
    engine books it per decode dispatch beside the pages held (digest
    columns `kv_pages_streamed` / `kv_pages_held`), so a change of page
    size or block that makes the kernel read past a sequence's end shows
    as a ratio above 1. Host-side numpy; exact on any backend."""
    if window_grouped(window, page_size, pages_per_block):
        items = window_items(np.asarray(lengths, np.int64), group, xp=np)
        return int(np.sum(items)) * group * window_pages(window, page_size)
    lengths = np.asarray(lengths, np.int64).ravel()
    if starts is not None:
        starts = np.asarray(starts, np.int64).ravel()
    if window:   # a window too long for one item: the per-sequence list
        starts = np.maximum(0 if starts is None else starts, lengths - window)
    if starts is not None:
        starts = np.minimum(starts, lengths)
        lengths = lengths - starts // page_size * page_size
    items = -(-lengths // (page_size * pages_per_block))
    return sum(
        int(live_pages(
            lengths[blk < items], blk, page_size, pages_per_block, xp=np
        ).sum())
        for blk in range(int(items.max(initial=0)))
    )


class _PageRing:
    """The unquantized decode kernels' DMA ring: the ONE set of copy /
    wait / write-back helpers of both item bodies (`_decode_kernel`,
    `_decode_window_kernel`). K and V pages are copied into page `p` of
    ring slot `slot` on the slot's semaphores; a merged page goes back to
    its pool page on `w_sem` under a mark in `wb_pending` (SMEM), which
    is drained before the buffer it reads from is a copy's target again."""

    def __init__(self, k_pages_hbm, v_pages_hbm, ko_pages_hbm, vo_pages_hbm,
                 k_buf, v_buf, k_sems, v_sems, w_sem, wb_pending,
                 wb_rows: int = 0):
        self.k_pages_hbm, self.v_pages_hbm = k_pages_hbm, v_pages_hbm
        self.ko_pages_hbm, self.vo_pages_hbm = ko_pages_hbm, vo_pages_hbm
        self.k_buf, self.v_buf = k_buf, v_buf
        self.k_sems, self.v_sems = k_sems, v_sems
        self.w_sem, self.wb_pending = w_sem, wb_pending
        # rows of a page a write-back moves (0 = the whole page)
        self.wb_rows = wb_rows

    def copy_in(self, page_id, slot, p):
        pltpu.make_async_copy(
            self.k_pages_hbm.at[page_id], self.k_buf.at[slot, p],
            self.k_sems.at[slot],
        ).start()
        pltpu.make_async_copy(
            self.v_pages_hbm.at[page_id], self.v_buf.at[slot, p],
            self.v_sems.at[slot],
        ).start()

    def wait(self, slot, n: int):
        # one wait per started copy: semaphores count completions, so the
        # item's `n` copied pages (static) and no more
        for _ in range(n):
            pltpu.make_async_copy(
                self.k_pages_hbm.at[0], self.k_buf.at[slot, 0],
                self.k_sems.at[slot],
            ).wait()
            pltpu.make_async_copy(
                self.v_pages_hbm.at[0], self.v_buf.at[slot, 0],
                self.v_sems.at[slot],
            ).wait()

    def _wb_copies(self, slot, p, page_id, row0):
        # the page, or its `wb_rows` rows from `row0`, buffer -> pool
        rows = (pl.ds(row0, self.wb_rows),) if self.wb_rows else ()
        return [
            pltpu.make_async_copy(
                buf.at[(slot, p, *rows)], pool.at[(page_id, *rows)],
                self.w_sem,
            )
            for buf, pool in ((self.k_buf, self.ko_pages_hbm),
                              (self.v_buf, self.vo_pages_hbm))
        ]

    def write_back(self, slot, p, page_id, mark, row0=0):
        for copy in self._wb_copies(slot, p, page_id, row0):
            copy.start()
        self.wb_pending[mark] = 1

    def drain(self, mark):
        # a pending page write-back reads from k_buf / v_buf; it must land
        # before that buffer is reused as a DMA-in target
        @pl.when(self.wb_pending[mark] == 1)
        def _():
            for copy in self._wb_copies(0, 0, 0, 0):
                copy.wait()
            self.wb_pending[mark] = 0


def _decode_kernel(
    # scalar prefetch
    lengths_ref,       # [B] i32: attended KV count per sequence (0 = inactive)
    tables_ref,        # [B, W] i32 page ids (W % pages_per_block == 0)
    wpos_ref,          # [B] i32 position whose KV this step writes (-1 = none)
    work_seq_ref,      # [MAXW] i32 sequence of each work item
    work_blk_ref,      # [MAXW] i32 page-block index of each work item
    n_work_ref,        # [1] i32 number of valid work items
    start_ref,         # [B] i32 first position the sequence attends (0 = all)
    # inputs (VMEM)
    q_ref,             # [B, H, Kd] queries (pre-scaled), one row a head
    knew_ref,          # [B, 1, K*Kd] new-token key rows
    vnew_ref,          # [B, 1, K*Vd]
    ek_ref,            # [Kd, K*Kd] 0/1: a head's query tiled over the blocks
    ev_ref,            # [K*Vd, Vd] 0/1: the blocks of an output row added up
    *rest,             # [sink_ref [H, 1] f32,] then HBM inputs, outputs, scratch
    batch: int,
    page_size: int,
    pages_per_block: int,
    nbuf: int,
    sink: bool = False,
    ablate: str = "",   # perf bisection: "nocompute" | "empty"
):
    """Keys `Kd` and values `Vd` wide (equal in every model but one whose
    keys are wider). The block-diagonal operand of the score dot is made
    HERE, once a sequence, from the head's own [H, Kd] rows (a 0/1 matmul
    and a mask: exact), and the output's diagonal blocks are added up here
    too: at 256 sequences of 64 heads over 1,536-wide keys the expanded
    queries would be 50 MB of VMEM, or of HBM traffic, a layer.

    `start_ref` is a window: the work list of a sequence begins at the
    page that holds its first attended position (its items count blocks
    from THAT page) and the mask cuts inside it; pages before it are
    never copied in (the engine has released them). `sink`: a learned
    logit a head that joins the softmax's denominator when a sequence's
    last item is done, so the weights sum to less than 1."""
    if sink:
        sink_ref, *rest = rest
    (k_pages_hbm,      # [num_pages, page_size, K*Kd]
     v_pages_hbm,      # [num_pages, page_size, K*Vd]
     o_ref,            # [B, H, Vd] VMEM
     ko_pages_hbm,     # aliased k_pages_hbm
     vo_pages_hbm,
     k_buf,            # [NBUF, ppb, page_size, K*Kd] VMEM
     v_buf,            # [NBUF, ppb, page_size, K*Vd]
     qb_buf,           # [H, K*Kd] VMEM: this sequence's block-diagonal queries
     k_sems,           # DMA sems [NBUF]
     v_sems,
     w_sem,            # DMA sem for page write-backs
     wb_pending,       # SMEM [NBUF]: write-back in flight from this slot
     ) = rest
    ring = _PageRing(k_pages_hbm, v_pages_hbm, ko_pages_hbm, vo_pages_hbm,
                     k_buf, v_buf, k_sems, v_sems, w_sem, wb_pending)
    t_blk = pages_per_block * page_size
    h, kd = q_ref.shape[1], q_ref.shape[2]
    kw = knew_ref.shape[2]
    vw = vnew_ref.shape[2]
    kh = kw // kd
    vd = vw // kh
    g = h // kh
    n_work = n_work_ref[0]

    def first_page(seq):
        return jax.lax.div(start_ref[seq], page_size)

    def start_work_dma(w, slot, settle=False):
        # the item's LIVE pages only (`live_pages`): a table entry past
        # the sequence's end names the trash page, and nothing reads it
        # (`settle`: wait for those copies, not start them)
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        page0 = first_page(seq)
        n_live = live_pages(
            lengths_ref[seq] - page0 * page_size, blk, page_size,
            pages_per_block,
        )
        for p in range(pages_per_block):

            @pl.when(p < n_live)
            def _start(p=p):
                if settle:
                    ring.wait(slot, 1)
                    return
                ring.copy_in(
                    tables_ref[seq, page0 + blk * pages_per_block + p],
                    slot, p,
                )

    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(nbuf):
        wb_pending[j] = 0

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
        m_prev, l_prev, acc = carry
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        length = lengths_ref[seq]
        wpos = wpos_ref[seq]
        start = start_ref[seq]
        base = first_page(seq) * page_size   # position of the list's page 0
        slot = jax.lax.rem(w, nbuf)
        n_live = live_pages(length - base, blk, page_size, pages_per_block)

        # fresh sequence: reset the flash state
        is_first = blk == 0
        m_prev = jnp.where(is_first, jnp.full_like(m_prev, _NEG_INF), m_prev)
        l_prev = jnp.where(is_first, jnp.zeros_like(l_prev), l_prev)
        acc = jnp.where(is_first, jnp.zeros_like(acc), acc)

        @pl.when(is_first)
        def _expand_queries():
            # row r (a query head) carries its values in its kv head's
            # column block, zeros elsewhere: one MXU dot then computes
            # every head's scores with no cross-head leakage
            tiled = jax.lax.dot_general(
                q_ref[seq], ek_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                  # [H, K*Kd]
            own = (
                jax.lax.broadcasted_iota(jnp.int32, (h, kw), 1) // kd
                == jax.lax.broadcasted_iota(jnp.int32, (h, kw), 0) // g
            )
            qb_buf[...] = jnp.where(own, tiled, 0.0).astype(qb_buf.dtype)

        def item(n, m_prev, l_prev, acc):
            # the whole item over its first `n` pages (static): fused
            # write, then one online-softmax step. Pages past them were
            # never copied in: the ring's buffers start uninitialised,
            # and a NaN there times a masked probability of 0 is NaN, so
            # the compute is sliced with the copies, not masked after
            # them. All of it sits in the branch: a value made outside
            # one and used inside goes through VMEM on its way
            t = n * page_size
            ring.wait(slot, n)
            kb = k_buf[slot, :n].reshape(t, kw)
            vb = v_buf[slot, :n].reshape(t, vw)
            if ablate == "nocompute":
                # (times an iota's zeros: a splat accumulator is a layout
                # Mosaic cannot carry into the emit's mask)
                touch = jnp.sum(kb.astype(jnp.float32), axis=0, keepdims=True)
                zeros = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0) < 0
                return m_prev, l_prev, acc + touch[:, :vw] * zeros.astype(
                    jnp.float32)

            # fused cache update: inject the new token's K/V row into the
            # block that owns position `wpos` (the final block; the
            # position is attended, so its page is live), store the
            # block back and write just that page to HBM
            do_write = (
                (wpos >= 0) & (wpos < length)
                & (blk == jax.lax.div(wpos - base, t_blk))
            )
            off = wpos - base - blk * t_blk
            krow = jax.lax.broadcasted_iota(jnp.int32, (t, kw), 0)
            vrow = jax.lax.broadcasted_iota(jnp.int32, (t, vw), 0)
            kb = jnp.where(do_write & (krow == off), knew_ref[seq], kb)
            vb = jnp.where(do_write & (vrow == off), vnew_ref[seq], vb)

            @pl.when(do_write)
            def _store_back():
                k_buf[slot, :n] = kb.reshape(n, page_size, kw)
                v_buf[slot, :n] = vb.reshape(n, page_size, vw)
                ring.write_back(
                    slot, jax.lax.div(off, page_size),
                    tables_ref[seq, jax.lax.div(wpos, page_size)], slot,
                )

            # ONE MXU dot for all kv heads: qb rows are zero outside their
            # head's column block, so cross-head terms vanish
            s = jax.lax.dot_general(
                qb_buf[...].astype(jnp.float32), kb.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, t]

            pos = base + blk * t_blk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where((pos < length) & (pos >= start), s, _NEG_INF)

            m_curr = jnp.max(s, axis=-1, keepdims=True)            # [H, 1]
            m_next = jnp.maximum(m_prev, m_curr)
            p_blk = jnp.exp(s - m_next)                             # [H, t]
            l_curr = jnp.sum(p_blk, axis=-1, keepdims=True)
            alpha = jnp.exp(m_prev - m_next)
            l_next = alpha * l_prev + l_curr

            # ONE PV dot: [H, t] @ [t, K*Vd]; the emit keeps only each
            # row's own head-column block
            o_curr = jax.lax.dot_general(
                p_blk, vb.astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_next, l_next, acc * alpha + o_curr

        m_prev, l_prev, acc = switch_live_pages(
            item, n_live, pages_per_block, m_prev, l_prev, acc
        )

        # last block of this sequence: emit the normalized output
        n_blocks = lax_cdiv(length - base, t_blk)

        @pl.when(blk == n_blocks - 1)
        def _emit():
            l_fin, a_fin = l_prev, acc
            if sink:
                # the sink's column joins the denominator and is dropped
                m_fin = jnp.maximum(m_prev, sink_ref[...])
                beta = jnp.exp(m_prev - m_fin)
                l_fin = l_prev * beta + jnp.exp(sink_ref[...] - m_fin)
                a_fin = acc * beta
            full = (a_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
            own = (
                jax.lax.broadcasted_iota(jnp.int32, (h, vw), 1) // vd
                == jax.lax.broadcasted_iota(jnp.int32, (h, vw), 0) // g
            )
            # block-diagonal slice: row r keeps its own head's column block
            o_ref[seq] = jax.lax.dot_general(
                jnp.where(own, full, jnp.zeros_like(full)), ev_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)

        # refill the ring with the work item NBUF ahead
        nxt = w + nbuf

        @pl.when(nxt < n_work)
        def _refill():
            ring.drain(slot)
            start_work_dma(nxt, slot)

        return m_prev, l_prev, acc

    m0 = jnp.full((h, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    a0 = jnp.zeros((h, vw), jnp.float32)
    jax.lax.fori_loop(0, n_work, body, (m0, l0, a0))
    for j in range(nbuf):
        ring.drain(j)


def lax_cdiv(a, b: int):
    return jax.lax.div(a + (b - 1), b)


def _decode_window_kernel(
    # scalar prefetch
    lengths_ref,       # [B] i32: attended KV count per sequence (0 = inactive)
    tables_ref,        # [B, W] i32 page ids
    wpos_ref,          # [B] i32 position whose KV this step writes (-1 = none)
    rows_ref,          # [ceil(B / G) * G] i32 `window_work_list`: the live
    # sequences in row order, the last one repeated to the end
    n_ref,             # [2] i32: work items, live sequences
    start_ref,         # [B] i32 first position the sequence attends
    # inputs (VMEM)
    q_ref,             # [B, H, Kd] queries (pre-scaled), one row a head
    knew_ref,          # [B, 1, K*Kd] new-token key rows
    vnew_ref,          # [B, 1, K*Vd]
    ek_ref,            # [Kd, K*Kd] 0/1: a head's query tiled over the blocks
    ev_ref,            # [K*Vd, Vd] 0/1: the blocks of an output row added up
    *rest,             # [sink_ref [H, 1] f32,] then HBM inputs, outputs, scratch
    page_size: int,
    win_pages: int,    # `window_pages`: page slots a sequence takes in an item
    group: int,        # G: sequences an item holds
    nbuf: int,
    sink: bool = False,
    ablate: str = "",   # perf bisection: "nocompute" | "empty"
):
    """The work item of a WINDOW layer: G sequences' whole windows in one
    loop iteration, where `_decode_kernel` walks one (sequence, block) at
    a time. A sibling body, not a branch of that one, and why: the
    long-context item needs a carried (m, l, acc) and a live-page count
    that varies by item (a `lax.switch`); a window of `win_pages` pages
    is first, last and writing item at once, needs neither, and run
    through that chain it pays four serial MXU fill / drains on H query
    rows for every sequence with nothing overlapped across iterations
    (PERF.md section 6, PR 37). Here the G query blocks are expanded in
    ONE dot ([G*H, Kd] x [Kd, K*Kd]), scores and PV are dots batched over
    the sequence axis ([G, H, K*Kd] x [G, T, K*Kd], [G, H, T] x
    [G, T, K*Vd], T = `win_pages` pages: no cross-sequence products, so
    no mask a sequence), the G outputs leave through ONE emit dot, there
    is no rescale, and the G chains are independent.

    Shared with `_decode_kernel`, unchanged: the ring's helpers
    (`_PageRing`), `live_pages` as the rule of which pages a sequence
    holds, the fused write (the new row is merged where it lies in VMEM
    and goes back from there: here the `WRITE_BACK_ROWS` rows around it,
    there its whole page), the sink in the denominator, float32 operands
    in both dots; the arithmetic a sequence is that kernel's with one
    item.

    A copy skipped without its compute skipped is NaN, and a branch a
    page count (`win_pages ** G`) is not an option, so every item copies
    `G * win_pages` pages and computes over all of them: a page slot a
    sequence does not hold (its context is under a page, or its window
    starts on a page's first row) takes the sequence's FIRST page again,
    and a partial last item's spare entries repeat the last live
    sequence. Both are finite and the sequence's own, behind the
    position mask (`exp(-inf) = 0`); a page no row holds is never read.
    `streamed_pages(window=)` counts by the same rule."""
    if sink:
        sink_ref, *rest = rest
    (k_pages_hbm,      # [num_pages, page_size, K*Kd]
     v_pages_hbm,      # [num_pages, page_size, K*Vd]
     o_ref,            # [B, H, Vd] VMEM
     ko_pages_hbm,     # aliased k_pages_hbm
     vo_pages_hbm,
     k_buf,            # [NBUF, G * win_pages, page_size, K*Kd] VMEM
     v_buf,            # [NBUF, G * win_pages, page_size, K*Vd]
     k_sems,           # DMA sems [NBUF]
     v_sems,
     w_sem,            # DMA sem for page write-backs
     wb_pending,       # SMEM [NBUF * G]: write-back in flight, an entry
     ) = rest
    wb_rows = min(WRITE_BACK_ROWS, page_size)
    ring = _PageRing(k_pages_hbm, v_pages_hbm, ko_pages_hbm, vo_pages_hbm,
                     k_buf, v_buf, k_sems, v_sems, w_sem, wb_pending,
                     wb_rows=wb_rows)
    t = win_pages * page_size
    item_pages = group * win_pages
    h, kd = q_ref.shape[1], q_ref.shape[2]
    kw = knew_ref.shape[2]
    vw = vnew_ref.shape[2]
    kh = kw // kd
    vd = vw // kh
    gq = h // kh
    n_items, n_live = n_ref[0], n_ref[1]

    def first_page(seq):
        return jax.lax.div(start_ref[seq], page_size)

    def start_item(w, slot):
        for g in range(group):
            seq = rows_ref[w * group + g]
            page0 = first_page(seq)
            held = live_pages(
                lengths_ref[seq] - page0 * page_size, 0, page_size, win_pages
            )
            for p in range(win_pages):
                # a slot past the pages held: the first page again
                ring.copy_in(
                    tables_ref[seq, page0 + jnp.where(p < held, p, 0)],
                    slot, g * win_pages + p,
                )

    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(nbuf * group):
        wb_pending[j] = 0
    for j in range(nbuf):

        @pl.when(j < n_items)
        def _prologue(j=j):
            start_item(j, j)

    if ablate == "empty":
        for j in range(nbuf):

            @pl.when(j < n_items)
            def _settle(j=j):
                ring.wait(j, item_pages)

        return

    def own_block(width, block):
        # [G*H, width]: row r (query head r % H of sequence r // H) against
        # the columns of its kv head's block
        head = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (group * h, width), 0), h)
        col = jax.lax.broadcasted_iota(jnp.int32, (group * h, width), 1)
        return col // block == head // gq

    def attend(w, slot):
        seqs = [rows_ref[w * group + g] for g in range(group)]
        bases = [first_page(seq) * page_size for seq in seqs]
        if ablate == "nocompute":
            touch = jnp.sum(
                k_buf[slot].astype(jnp.float32).reshape(
                    item_pages * page_size, kw),
                axis=0, keepdims=True,
            )
            o_ref[seqs[0]] = jnp.broadcast_to(
                touch[:, :vd] * 0.0, (h, vd)
            ).astype(o_ref.dtype)
            return

        # fused cache update, a sequence at a time: the new token's K/V
        # row is merged into the `wb_rows` aligned rows around position
        # `wpos` of the page that owns it and just those go back (not for
        # a partial item's repeats)
        for g, (seq, base) in enumerate(zip(seqs, bases)):
            wpos = wpos_ref[seq]
            off = wpos - base
            do_write = (
                (w * group + g < n_live) & (wpos >= 0)
                & (wpos < lengths_ref[seq]) & (off >= 0)
            )

            @pl.when(do_write)
            def _merge(g=g, seq=seq, wpos=wpos, off=off):
                p = g * win_pages + jax.lax.div(off, page_size)
                r = jax.lax.rem(off, page_size)
                row0 = pl.multiple_of(
                    jax.lax.div(r, wb_rows) * wb_rows, wb_rows)
                slab = pl.ds(row0, wb_rows)
                krow = jax.lax.broadcasted_iota(jnp.int32, (wb_rows, kw), 0)
                vrow = jax.lax.broadcasted_iota(jnp.int32, (wb_rows, vw), 0)
                k_buf[slot, p, slab] = jnp.where(
                    krow == r - row0, knew_ref[seq], k_buf[slot, p, slab]
                )
                v_buf[slot, p, slab] = jnp.where(
                    vrow == r - row0, vnew_ref[seq], v_buf[slot, p, slab]
                )
                ring.write_back(
                    slot, p, tables_ref[seq, jax.lax.div(wpos, page_size)],
                    slot * group + g, row0,
                )

        # a row carries its values in its kv head's column block, zeros
        # elsewhere
        tiled = jax.lax.dot_general(
            jnp.concatenate([q_ref[seq] for seq in seqs], axis=0),
            ek_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [G*H, K*Kd]
        qb = jnp.where(own_block(kw, kd), tiled, 0.0).reshape(group, h, kw)
        kb = k_buf[slot].reshape(group, t, kw)
        vb = v_buf[slot].reshape(group, t, vw)
        s = jax.lax.dot_general(
            qb, kb.astype(jnp.float32),
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                  # [G, H, t]

        # the attended positions of each sequence, counted from its page 0
        gi = jax.lax.broadcasted_iota(jnp.int32, (group, 1, t), 0)
        rel = jax.lax.broadcasted_iota(jnp.int32, (group, 1, t), 2)
        lo = jnp.zeros((group, 1, t), jnp.int32)
        hi = jnp.zeros((group, 1, t), jnp.int32)
        for g, (seq, base) in enumerate(zip(seqs, bases)):
            lo = jnp.where(gi == g, start_ref[seq] - base, lo)
            hi = jnp.where(gi == g, lengths_ref[seq] - base, hi)
        s = jnp.where((rel < hi) & (rel >= lo), s, _NEG_INF)

        m = jnp.max(s, axis=-1, keepdims=True)                  # [G, H, 1]
        p_blk = jnp.exp(s - m)
        l_fin = jnp.sum(p_blk, axis=-1, keepdims=True)
        a_fin = jax.lax.dot_general(
            p_blk, vb.astype(jnp.float32),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                  # [G, H, K*Vd]
        if sink:
            # the sink's column joins the denominator and is dropped
            m_fin = jnp.maximum(m, sink_ref[...])
            beta = jnp.exp(m - m_fin)
            l_fin = l_fin * beta + jnp.exp(sink_ref[...] - m_fin)
            a_fin = a_fin * beta
        full = (a_fin / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        full = full.reshape(group * h, vw)
        # block-diagonal slice: row r keeps its own head's column block
        out = jax.lax.dot_general(
            jnp.where(own_block(vw, vd), full, jnp.zeros_like(full)),
            ev_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)                              # [G*H, Vd]
        for g, seq in enumerate(seqs):

            @pl.when(w * group + g < n_live)
            def _emit(g=g, seq=seq):
                o_ref[seq] = out[g * h:(g + 1) * h]

    def body(w, carry):
        slot = jax.lax.rem(w, nbuf)
        ring.wait(slot, item_pages)
        attend(w, slot)

        # refill the ring with the work item NBUF ahead
        nxt = w + nbuf

        @pl.when(nxt < n_items)
        def _refill():
            for g in range(group):
                ring.drain(slot * group + g)
            start_item(nxt, slot)

        return carry

    jax.lax.fori_loop(0, n_items, body, 0)
    for j in range(nbuf * group):
        ring.drain(j)


def _decode_kernel_q(
    # scalar prefetch
    lengths_ref,       # [B] i32: attended KV count per sequence (0 = inactive)
    tables_ref,        # [B, W] i32 page ids (W % pages_per_block == 0)
    wpos_ref,          # [B] i32 position whose KV this step writes (-1 = none)
    work_seq_ref,      # [MAXW] i32 sequence of each work item
    work_blk_ref,      # [MAXW] i32 page-block index of each work item
    n_work_ref,        # [1] i32 number of valid work items
    # inputs (VMEM)
    qb_ref,            # [B, HK, K*Hd] cyclic block-diagonal queries
    # (HK = SUBL*G; row r carries query head (r%SUBL)*G + r//SUBL in kv
    # column block r%SUBL, zero when r%SUBL >= local kv heads)
    knew_ref,          # [B, 1, K*Hd] new-token key rows, int8
    vnew_ref,
    ksnew_ref,         # [B, SUBL] new-token scale columns, f32
    vsnew_ref,
    # inputs (HBM)
    k_pages_hbm,       # [num_pages, page_size, K*Hd] int8
    v_pages_hbm,
    ks_pages_hbm,      # [num_pages, SUBL, page_size] f32 (tokens in lanes)
    vs_pages_hbm,
    # outputs
    o_ref,             # [B, HK, K*Hd] VMEM (valid diag slice taken outside)
    ko_pages_hbm,      # aliased k_pages_hbm
    vo_pages_hbm,
    kso_pages_hbm,     # aliased ks_pages_hbm
    vso_pages_hbm,
    # scratch
    k_buf,             # [NBUF, ppb, page_size, K*Hd] int8 VMEM
    v_buf,
    ks_buf,            # [NBUF, SUBL, ppb*page_size] f32 VMEM (block-wide)
    vs_buf,
    ks_stage,          # [NBUF, SUBL, page_size] f32 write-back staging
    vs_stage,
    k_sems,            # DMA sems [NBUF] (data + scale copies both count)
    v_sems,
    w_sem,             # DMA sem for page write-backs
    wb_pending,        # SMEM [NBUF]: write-back in flight from this slot
    *,
    batch: int,
    page_size: int,
    pages_per_block: int,
    nbuf: int,
    ablate: str = "",  # perf bisection: "noscale_dma" | "noscale_mul"
    packed: bool = False,
    int4: bool = False,
):
    """int8 variant of `_decode_kernel`: pages are int8 plus transposed
    f32 scale pages [SUBL>=8, page_size] (ops/quant.py pool layout — the
    only shape Mosaic can DMA). The streamed-page HBM traffic — most of
    an int8-weights decode step at wide batches (PERF.md section 5) — halves.

    `packed`: the pools arrive int32 [*, page_size//4, K*Hd] (4 token
    rows per int32 row, little-endian — ops/quant.pack_kv_slots). int8's
    (32, 128) VMEM tiles DMA ~1.4x slower per byte than f32-class
    (8, 128) tiles (a round-4 probe, not in the ledger), so the DMA moves
    int32 tiles and the kernel reinterprets with pltpu.bitcast (probed:
    expands sublanes 4x in exactly the pack order). The new token's row
    is injected in the int32 domain — one byte lane of one packed row —
    before the bitcast.

    Dequantization never touches the K*Hd data tiles: scales fold into
    the SCORE matrix lanes instead. Page scale tiles DMA into a
    block-wide [SUBL, t_blk] buffer, and ONE `pltpu.repeat` (a VPU
    sublane tile-repeat — measured much cheaper than per-page MXU
    expansion matmuls) turns it into the [HK, t_blk] multiplier; query
    rows are CYCLIC (row r ↔ kv head r % SUBL) so the tile-repeat's row
    order matches by construction. K-scales multiply the scores;
    V-scales multiply the softmax probs ((p*vs) @ v_int8 == p @
    dequant(v)). Design notes otherwise as in `_decode_kernel`.

    `int4`: the pools are nibble-packed at HALF width (kwp = K*Hd/2,
    ops/quant.quantize_kv_rows_int4 planar layout: a head's packed byte j
    = feature j low nibble | feature j+Hd/2 high nibble). The query
    arrives in PLANAR column order — its lo-half features block-diagonal
    over the first kwp columns, hi-half over the last kwp — so scores
    are TWO half-width dots against the nibble planes and the unpacked
    row never materializes. The PV product accumulates [p@lo | p@hi]
    planar in the same [HK, kw] accumulator; the caller un-permutes.
    Both int8→int32 page packing and the fused-write byte injection are
    byte-level and compose unchanged at half width."""
    t_blk = pages_per_block * page_size
    hk = qb_ref.shape[1]
    kw = qb_ref.shape[2]            # full (planar) width when int4
    kwp = kw // 2 if int4 else kw   # pool row width
    subl = ksnew_ref.shape[1]
    g = hk // subl
    n_work = n_work_ref[0]

    def nibbles(x):
        # packed int4 bytes -> (lo, hi) f32 nibble planes; (x^8)-8
        # sign-extends the low nibble, arithmetic >> the high one
        xi = x.astype(jnp.int32)
        lo = (((xi & 15) ^ 8) - 8).astype(jnp.float32)
        hi = (xi >> 4).astype(jnp.float32)
        return lo, hi

    def start_work_dma(w, slot):
        # the item's LIVE pages only (`live_pages`): data and scale tile
        seq = work_seq_ref[w]
        blk = work_blk_ref[w]
        n_live = live_pages(lengths_ref[seq], blk, page_size, pages_per_block)
        for p in range(pages_per_block):

            @pl.when(p < n_live)
            def _start(p=p):
                page_id = tables_ref[seq, blk * pages_per_block + p]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page_id], k_buf.at[slot, p],
                    k_sems.at[slot],
                ).start()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page_id], v_buf.at[slot, p],
                    v_sems.at[slot],
                ).start()
                if ablate != "noscale_dma":
                    pltpu.make_async_copy(
                        ks_pages_hbm.at[page_id],
                        ks_buf.at[slot, :, p * page_size:(p + 1) * page_size],
                        k_sems.at[slot],
                    ).start()
                    pltpu.make_async_copy(
                        vs_pages_hbm.at[page_id],
                        vs_buf.at[slot, :, p * page_size:(p + 1) * page_size],
                        v_sems.at[slot],
                    ).start()

    def wait_work_dma(slot, n):
        # one wait per started copy — the item's `n` live pages (static:
        # inside its branch) and no more — with a descriptor matching
        # each enqueued copy's SIZE: TPU DMA semaphores count bytes, so a
        # data-page wait cannot stand in for a scale-tile copy
        for _ in range(n):
            pltpu.make_async_copy(
                k_pages_hbm.at[0], k_buf.at[slot, 0], k_sems.at[slot]
            ).wait()
            pltpu.make_async_copy(
                v_pages_hbm.at[0], v_buf.at[slot, 0], v_sems.at[slot]
            ).wait()
            if ablate != "noscale_dma":
                pltpu.make_async_copy(
                    ks_pages_hbm.at[0], ks_buf.at[slot, :, 0:page_size],
                    k_sems.at[slot],
                ).wait()
                pltpu.make_async_copy(
                    vs_pages_hbm.at[0], vs_buf.at[slot, :, 0:page_size],
                    v_sems.at[slot],
                ).wait()

    def drain_wb(slot):
        @pl.when(wb_pending[slot] == 1)
        def _():
            # data + staged scale page per pool, size-matched waits
            pltpu.make_async_copy(
                k_buf.at[0, 0], ko_pages_hbm.at[0], w_sem
            ).wait()
            pltpu.make_async_copy(
                ks_stage.at[0], kso_pages_hbm.at[0], w_sem
            ).wait()
            pltpu.make_async_copy(
                v_buf.at[0, 0], vo_pages_hbm.at[0], w_sem
            ).wait()
            pltpu.make_async_copy(
                vs_stage.at[0], vso_pages_hbm.at[0], w_sem
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

        is_first = blk == 0
        m_prev = jnp.where(is_first, jnp.full_like(m_prev, _NEG_INF), m_prev)
        l_prev = jnp.where(is_first, jnp.zeros_like(l_prev), l_prev)
        acc = jnp.where(is_first, jnp.zeros_like(acc), acc)

        def item(n, m_prev, l_prev, acc):
            # the whole item over its first `n` pages (static): fused
            # write, then one online-softmax step. Pages past them were
            # never copied in: the ring's buffers start uninitialised,
            # and a float32 scale tile of garbage times a masked
            # probability of 0 is NaN, so the compute is sliced with the
            # copies, not masked after them. All of it sits in the
            # branch: a value made outside one and used inside goes
            # through VMEM on its way
            t = n * page_size
            wait_work_dma(slot, n)
            ksb = ks_buf[slot, :, :t]                # [SUBL, t]
            vsb = vs_buf[slot, :, :t]

            # fused cache update: inject the new token's int8 K/V row into
            # its data page and its scale column into the block-wide scale
            # buffer, store both back and write just that page pair to HBM
            # (the position is attended, so its page is live)
            do_write = (
                (wpos >= 0) & (wpos < length)
                & (blk == jax.lax.div(wpos, t_blk))
            )
            off = wpos - blk * t_blk
            if packed:
                # int32 domain: the token's row is byte lane off%4 of
                # packed row off//4; mask-merge the new int8 row's bytes
                kb32 = k_buf[slot, :n].reshape(t // 4, kwp)
                vb32 = v_buf[slot, :n].reshape(t // 4, kwp)
                shift = jax.lax.rem(off, 4) * 8
                mask = 0xFF << shift
                row32 = jax.lax.broadcasted_iota(jnp.int32, (t // 4, kwp), 0)
                inj = do_write & (row32 == jax.lax.div(off, 4))
                nk32 = (knew_ref[seq].astype(jnp.int32) & 0xFF) << shift
                nv32 = (vnew_ref[seq].astype(jnp.int32) & 0xFF) << shift
                kb32 = jnp.where(inj, (kb32 & ~mask) | nk32, kb32)
                vb32 = jnp.where(inj, (vb32 & ~mask) | nv32, vb32)
                kb = pltpu.bitcast(kb32, jnp.int8)   # [t, kwp]
                vb = pltpu.bitcast(vb32, jnp.int8)
            else:
                kb = k_buf[slot, :n].reshape(t, kwp)
                vb = v_buf[slot, :n].reshape(t, kwp)
                row = jax.lax.broadcasted_iota(jnp.int32, (t, kwp), 0)
                kb = jnp.where(do_write & (row == off), knew_ref[seq], kb)
                vb = jnp.where(do_write & (row == off), vnew_ref[seq], vb)
            p_loc = jax.lax.div(off, page_size)
            slane = jax.lax.broadcasted_iota(jnp.int32, (subl, t), 1)
            sc_mask = do_write & (slane == off)
            ksb = jnp.where(sc_mask, ksnew_ref[seq].reshape(subl, 1), ksb)
            vsb = jnp.where(sc_mask, vsnew_ref[seq].reshape(subl, 1), vsb)

            @pl.when(do_write)
            def _store_back():
                if packed:
                    k_buf[slot, :n] = kb32.reshape(n, page_size // 4, kwp)
                    v_buf[slot, :n] = vb32.reshape(n, page_size // 4, kwp)
                else:
                    k_buf[slot, :n] = kb.reshape(n, page_size, kwp)
                    v_buf[slot, :n] = vb.reshape(n, page_size, kwp)
                ks_buf[slot, :, :t] = ksb
                vs_buf[slot, :, :t] = vsb
                # select the written page's [SUBL, S] scale tile (static
                # slices + runtime select: lane offsets must be static)
                kt = jnp.zeros((subl, page_size), jnp.float32)
                vt = jnp.zeros((subl, page_size), jnp.float32)
                for p in range(n):
                    sel = p_loc == p
                    kt = jnp.where(
                        sel, ksb[:, p * page_size:(p + 1) * page_size], kt
                    )
                    vt = jnp.where(
                        sel, vsb[:, p * page_size:(p + 1) * page_size], vt
                    )
                ks_stage[slot] = kt
                vs_stage[slot] = vt
                page_id = tables_ref[seq, jax.lax.div(wpos, page_size)]
                pltpu.make_async_copy(
                    k_buf.at[slot, p_loc], ko_pages_hbm.at[page_id], w_sem
                ).start()
                pltpu.make_async_copy(
                    ks_stage.at[slot], kso_pages_hbm.at[page_id], w_sem
                ).start()
                pltpu.make_async_copy(
                    v_buf.at[slot, p_loc], vo_pages_hbm.at[page_id], w_sem
                ).start()
                pltpu.make_async_copy(
                    vs_stage.at[slot], vso_pages_hbm.at[page_id], w_sem
                ).start()
                wb_pending[slot] = 1

            if ablate in ("nocompute", "noconvert"):
                # DMA + loop floor: "nocompute" converts the full buffers
                # (mirrors the bf16 kernel's ablation), "noconvert"
                # touches 8 rows only — the delta isolates the int8->f32
                # VPU cost
                if ablate == "nocompute":
                    touch = (
                        jnp.sum(kb.astype(jnp.float32))
                        + jnp.sum(vb.astype(jnp.float32))
                    )
                else:
                    touch = (
                        jnp.sum(kb[0:8, :].astype(jnp.float32))
                        + jnp.sum(vb[0:8, :].astype(jnp.float32))
                    )
                return m_prev, l_prev, acc + touch * 0.0

            # int8 values are exact in bf16, so the data dot needs no
            # HIGHEST; K-scales fold into the score lanes afterwards (one
            # VPU repeat). (probed: casting to bf16 instead of f32 here
            # is ~4% SLOWER — int8->bf16 goes through f32 plus a truncate
            # on the VPU)
            if int4:
                klo, khi = nibbles(kb)               # [t, kwp] planes
                qbs = qb_ref[seq].astype(jnp.float32)
                s = jax.lax.dot_general(
                    qbs[:, :kwp], klo,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) + jax.lax.dot_general(
                    qbs[:, kwp:], khi,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [HK, t]
            else:
                s = jax.lax.dot_general(
                    qb_ref[seq].astype(jnp.float32), kb.astype(jnp.float32),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [HK, t]
            if ablate != "noscale_mul":
                s = s * pltpu.repeat(ksb, g, 0)

            pos = blk * t_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, _NEG_INF)

            m_curr = jnp.max(s, axis=-1, keepdims=True)            # [HK, 1]
            m_next = jnp.maximum(m_prev, m_curr)
            p_blk = jnp.exp(s - m_next)                             # [HK, t]
            l_curr = jnp.sum(p_blk, axis=-1, keepdims=True)
            alpha = jnp.exp(m_prev - m_next)
            l_next = alpha * l_prev + l_curr

            # V-scales fold into the probs: (p * vs) @ v_int == p @ dequant(v)
            pv_in = (
                p_blk if ablate == "noscale_mul"
                else p_blk * pltpu.repeat(vsb, g, 0)
            )
            if int4:
                # planar accumulator: lo-plane columns first, hi after —
                # the caller un-permutes to natural feature order
                vlo, vhi = nibbles(vb)
                o_curr = jnp.concatenate(
                    [
                        jax.lax.dot_general(
                            pv_in, vlo,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ),
                        jax.lax.dot_general(
                            pv_in, vhi,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ),
                    ],
                    axis=1,
                )
            else:
                o_curr = jax.lax.dot_general(
                    pv_in, vb.astype(jnp.float32),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            return m_next, l_next, acc * alpha + o_curr

        m_prev, l_prev, acc = switch_live_pages(
            item, n_live, pages_per_block, m_prev, l_prev, acc
        )

        n_blocks = lax_cdiv(length, t_blk)

        @pl.when(blk == n_blocks - 1)
        def _emit():
            o_ref[seq] = (
                acc / jnp.maximum(l_prev, 1e-30)
            ).astype(o_ref.dtype)

        nxt = w + nbuf

        @pl.when(nxt < n_work)
        def _refill():
            drain_wb(slot)
            start_work_dma(nxt, slot)

        return m_prev, l_prev, acc

    m0 = jnp.full((hk, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((hk, 1), jnp.float32)
    a0 = jnp.zeros((hk, kw), jnp.float32)
    jax.lax.fori_loop(0, n_work, body, (m0, l0, a0))
    for j in range(nbuf):
        drain_wb(j)


@functools.partial(
    jax.jit,
    static_argnames=["page_size", "pages_per_block", "nbuf", "interpret",
                     "ablate", "alias_caches", "int4", "window",
                     "window_group"],
)
def fused_paged_decode_attention(
    q: jax.Array,             # [B, H, Hd] (rope applied, unscaled)
    new_k: jax.Array,         # [B, K*Hd] this step's K rows (rope applied;
    # int8 in quantized mode, pre-quantized by the caller)
    new_v: jax.Array,         # [B, K*Hd]
    k_cache: jax.Array,       # [num_slots, K*Hd] flat slot pool
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, W] i32 page ids (0 = trash page)
    lengths: jax.Array,       # [B] i32 attended KV count incl. the new token
    write_pos: jax.Array,     # [B] i32 position to store new_k/new_v (-1 = skip)
    k_scales: jax.Array = None,  # [num_pages, SUBL, page_size] f32 scale
    # pools (ops/quant pool layout; SUBL >= 8, tokens in lanes)
    v_scales: jax.Array = None,
    new_ks: jax.Array = None,    # [B, SUBL] f32 new-row scale columns
    new_vs: jax.Array = None,
    starts: jax.Array = None,    # [B] i32 first attended position (a
    # window; None = every position). Unquantized pools only
    sink: jax.Array = None,      # [H] f32 learned sink logits (the same)
    *,
    page_size: int,
    pages_per_block: int = PAGES_PER_BLOCK,
    nbuf: int = NBUF,
    interpret: bool = False,
    ablate: str = "",
    alias_caches: bool = True,
    int4: bool = False,
    window: int = 0,             # the layer kind's STATIC window: no row
    # attends more than its last `window` positions (`starts` is raised to
    # `lengths - window`; None = exactly that). Unquantized pools only
    window_group: int = WINDOW_GROUP,
):
    """Flash paged decode attention fused with the KV-cache update.

    Returns (out [B, H, Vd], k_cache, v_cache[, k_scales, v_scales]); the
    caches are updated in place (aliased) — the new token's row is
    injected into its page in VMEM and only that page is written back, so
    there is no XLA scatter anywhere on the decode path. With scale pools
    the pages are int8 (`_decode_kernel_q`). Unquantized pools may keep
    values narrower than keys (`v_cache` rows K x Vd), a window start a
    sequence and a sink logit a head (`_decode_kernel`). A static
    `window` short enough for one work item (`window_grouped`: chosen
    from `window`, `page_size` and `pages_per_block` alone) takes the
    grouped item, `window_group` sequences' windows at a time
    (`_decode_window_kernel`); without one the program is the
    per-sequence list's, to the letter."""
    b, h, hd = q.shape
    quant = k_scales is not None
    # int32-PACKED pools (quant.pack_kv_slots layout): 4 token rows per
    # int32 row — f32-class DMA tiling; the kernel bitcasts back to int8
    packed = quant and k_cache.dtype == jnp.int32
    num_slots, kw = k_cache.shape   # kw = pool row width (K*Hd/2 at int4)
    if packed:
        num_slots *= 4
    # int4 pools are nibble-packed at half width, so kh cannot be derived
    # from the pool shape — hence the explicit static flag
    kwf = 2 * kw if int4 else kw    # full logical width K*Hd
    if int4:
        assert quant, "int4 pools require scale pools"
    assert kwf % hd == 0
    kh = kwf // hd
    assert h % kh == 0
    g = h // kh
    num_pages = num_slots // page_size
    t_blk = pages_per_block * page_size

    w = block_tables.shape[1]
    if w % pages_per_block:
        pad = pages_per_block - w % pages_per_block
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    max_blocks = block_tables.shape[1] // pages_per_block

    lengths = lengths.astype(jnp.int32)
    if quant:
        work_seq, work_blk, n_work = work_list(lengths, t_blk, max_blocks)

    # free bitcast: [N, K*Hd] row-major -> page-major view
    page_rows = page_size // 4 if packed else page_size
    k_pages = k_cache.reshape(num_pages, page_rows, kw)
    new_k = new_k.reshape(b, 1, kw)

    scale = hd ** -0.5
    if quant:
        v_pages = v_cache.reshape(num_pages, page_rows, kw)
        new_v = new_v.reshape(b, 1, kw)
        # scale pools arrive page-blocked [P, SUBL, S]
        k_pages, v_pages, ks_pages, vs_pages = (
            in_hbm(p, interpret)
            for p in (k_pages, v_pages, k_scales, v_scales)
        )
        subl = k_scales.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),   # qb
                pl.BlockSpec(memory_space=pltpu.VMEM),   # new_k
                pl.BlockSpec(memory_space=pltpu.VMEM),   # new_v
                pl.BlockSpec(memory_space=pltpu.VMEM),   # new_ks
                pl.BlockSpec(memory_space=pltpu.VMEM),   # new_vs
                # pools pinned to HBM: under pl.ANY Mosaic may place the
                # small scale pools in VMEM, where sub-lane-width (K < 128)
                # memref slices fail to compile
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),  # k_pages
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),  # v_pages
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),  # ks_pages
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),  # vs_pages
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            ],
            scratch_shapes=[
                pltpu.VMEM(
                    (nbuf, pages_per_block, page_rows, kw),
                    jnp.int32 if packed else jnp.int8,
                ),
                pltpu.VMEM(
                    (nbuf, pages_per_block, page_rows, kw),
                    jnp.int32 if packed else jnp.int8,
                ),
                pltpu.VMEM((nbuf, subl, t_blk), jnp.float32),
                pltpu.VMEM((nbuf, subl, t_blk), jnp.float32),
                pltpu.VMEM((nbuf, subl, page_size), jnp.float32),
                pltpu.VMEM((nbuf, subl, page_size), jnp.float32),
                pltpu.SemaphoreType.DMA((nbuf,)),
                pltpu.SemaphoreType.DMA((nbuf,)),
                pltpu.SemaphoreType.DMA,
                pltpu.SMEM((nbuf,), jnp.int32),
            ],
        )
        kernel = functools.partial(
            _decode_kernel_q,
            batch=b,
            page_size=page_size,
            pages_per_block=pages_per_block,
            nbuf=nbuf,
            ablate=ablate,
            packed=packed,
            int4=int4,
        )
        # CYCLIC query-row layout (HK = SUBL*G rows): row r carries query
        # head (r%SUBL)*G + r//SUBL in kv column block r%SUBL — so the
        # kernel's pltpu.repeat of the [SUBL, T] scale tile lines up with
        # the score rows with no expansion matmul. Rows whose kv slot is
        # padding (r%SUBL >= kh) are zero and discarded on the way out.
        hk = subl * g
        r = jnp.arange(hk)
        head_of_row = (r % subl) * g + r // subl
        valid_row = (r % subl) < kh
        q_rows = jnp.where(
            valid_row[None, :, None],
            (q * scale)[:, jnp.where(valid_row, head_of_row, 0), :],
            0,
        ).astype(q.dtype)                                     # [B, HK, Hd]
        rowh = (r % subl).astype(jnp.int32)[None, :, None]
        if int4:
            # PLANAR query layout: the head's lo-half features block-
            # diagonal over the first kw (= K*Hd/2) columns, hi-half over
            # the last kw — matching the pool's nibble planes so the
            # kernel scores with two half-width dots
            hd2 = hd // 2
            colh2 = (jnp.arange(kw, dtype=jnp.int32) // hd2)[None, None, :]

            def _half(qh):                       # [B, HK, Hd/2] -> kw cols
                return jnp.where(colh2 == rowh, jnp.tile(qh, (1, 1, kh)), 0)

            qbq = jnp.concatenate(
                [_half(q_rows[..., :hd2]), _half(q_rows[..., hd2:])],
                axis=2,
            ).astype(q.dtype)                                 # [B, HK, K*Hd]
        else:
            qt = jnp.tile(q_rows, (1, 1, kh))                 # [B, HK, K*Hd]
            colh = (jnp.arange(kw, dtype=jnp.int32) // hd)[None, None, :]
            qbq = jnp.where(colh == rowh, qt, 0).astype(q.dtype)
        # inputs: 0..5 = scalar prefetch, 6 = qb, 7..10 = new rows/scales,
        # 11..14 = page pools — aliased onto outputs 1..4
        aliases = {11: 1, 12: 2, 13: 3, 14: 4} if alias_caches else {}
        out_full, k2, v2, ks2, vs2 = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, hk, kwf), q.dtype),
                *map(hbm_out, (k_pages, v_pages, ks_pages, vs_pages)),
            ],
            input_output_aliases=aliases,
            interpret=interpret,
        )(lengths, block_tables.astype(jnp.int32), write_pos.astype(jnp.int32),
          work_seq, work_blk, n_work[None], qbq,
          new_k.reshape(b, 1, kw), new_v.reshape(b, 1, kw),
          new_ks, new_vs,
          k_pages, v_pages, ks_pages, vs_pages)
        # undo the cyclic layout: row r = j*SUBL + k keeps column block k
        # (kw spans kh blocks; padding rows k >= kh have no block and are
        # dropped); head (k*G + j) <- (j, k)
        out_full = out_full.astype(jnp.float32)
        if int4:
            # planar -> natural feature order first: the accumulator is
            # [lo-plane cols | hi-plane cols]; a head's true features are
            # its lo block then its hi block concatenated
            out_full = (
                out_full.reshape(b, hk, 2, kh, hd // 2)
                .transpose(0, 1, 3, 2, 4)
                .reshape(b, hk, kwf)
            )
        out = out_full.reshape(b, g, subl, kh, hd)
        out = jnp.einsum("bjkkd->bjkd", out[:, :, :kh])       # [B, G, K, Hd]
        out = out.transpose(0, 2, 1, 3).reshape(b, h, hd).astype(q.dtype)
        pool_rows = num_slots // 4 if packed else num_slots
        return (
            out,
            k2.reshape(pool_rows, kw),
            v2.reshape(pool_rows, kw),
            ks2,
            vs2,
        )

    # the kernel makes the block-diagonal operand itself from the heads'
    # own rows: a 0/1 matrix that tiles a row over the K column blocks
    # (and one that adds an output row's blocks up), then a mask
    assert (starts is None and not window) or not quant
    vw = v_cache.shape[1]
    vd = vw // kh
    v_pages = v_cache.reshape(num_pages, page_size, vw)
    new_v = new_v.reshape(b, 1, vw)
    qs = (q * scale).astype(q.dtype)
    ek = (jnp.arange(kw)[None, :] % hd == jnp.arange(hd)[:, None]).astype(
        q.dtype
    )                                                        # [Kd, K*Kd]
    ev = (jnp.arange(vw)[:, None] % vd == jnp.arange(vd)[None, :]).astype(
        q.dtype
    )                                                        # [K*Vd, Vd]
    if window:
        floor = jnp.maximum(lengths - window, 0)
        starts = floor if starts is None else jnp.maximum(
            starts.astype(jnp.int32), floor)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
        from_page0 = lengths
    else:
        starts = jnp.minimum(starts.astype(jnp.int32), lengths)
        from_page0 = lengths - starts // page_size * page_size
    tables = block_tables.astype(jnp.int32)
    if window_grouped(window, page_size, pages_per_block):
        # the work list and the ring of the grouped item: an item's
        # buffer holds `window_group` sequences' page slots
        win_pages = window_pages(window, page_size)
        rows, n = window_work_list(lengths, window_group)
        scalars = (lengths, tables, write_pos.astype(jnp.int32), rows, n,
                   starts)
        item_pages, marks = window_group * win_pages, nbuf * window_group
        qb_scratch = []
        kernel = functools.partial(
            _decode_window_kernel,
            page_size=page_size,
            win_pages=win_pages,
            group=window_group,
            nbuf=nbuf,
            sink=sink is not None,
            ablate=ablate,
        )
    else:
        work_seq, work_blk, n_work = work_list(from_page0, t_blk, max_blocks)
        scalars = (lengths, tables, write_pos.astype(jnp.int32), work_seq,
                   work_blk, n_work[None], starts)
        item_pages, marks = pages_per_block, nbuf
        qb_scratch = [pltpu.VMEM((h, kw), q.dtype)]
        kernel = functools.partial(
            _decode_kernel,
            batch=b,
            page_size=page_size,
            pages_per_block=pages_per_block,
            nbuf=nbuf,
            sink=sink is not None,
            ablate=ablate,
        )
    extra = () if sink is None else (
        sink.astype(jnp.float32).reshape(h, 1),
    )

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[
            *[vmem] * (5 + len(extra)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            vmem,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((nbuf, item_pages, page_size, kw), k_cache.dtype),
            pltpu.VMEM((nbuf, item_pages, page_size, vw), v_cache.dtype),
            *qb_scratch,
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SemaphoreType.DMA((nbuf,)),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((marks,), jnp.int32),
        ],
    )
    # scalar prefetch, VMEM inputs, then pools
    n_in = len(scalars) + 5 + len(extra)
    out, k2, v2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, vd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_cache.dtype),
        ],
        # the pools are aliased onto outputs 1/2 (skipped for read-only
        # callers that keep using their input caches: aliasing would
        # force XLA to defensively copy both pools)
        input_output_aliases=(
            {n_in: 1, n_in + 1: 2} if alias_caches else {}
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=DECODE_VMEM_LIMIT
        ),
        interpret=interpret,
    )(*scalars, qs, new_k, new_v, ek, ev, *extra, k_pages, v_pages)
    return (
        out,
        k2.reshape(num_slots, kw),
        v2.reshape(num_slots, vw),
    )


def paged_decode_attention(
    q: jax.Array,             # [B, H, Hd] (rope applied, unscaled)
    k_cache: jax.Array,       # [num_slots, K*Hd] flat slot pool
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, W] i32 page ids (0 = trash page)
    lengths: jax.Array,       # [B] i32 valid KV positions (0 = inactive row)
    k_scales: jax.Array = None,  # [num_pages, SUBL, S] f32 scale pools
    v_scales: jax.Array = None,
    *,
    page_size: int,
    pages_per_block: int = PAGES_PER_BLOCK,
    interpret: bool = False,
    int4: bool = False,
) -> jax.Array:
    """Read-only flash paged decode attention (KV already written);
    returns [B, H, Hd] in q.dtype."""
    b = q.shape[0]
    kw = k_cache.shape[1]
    quant = k_scales is not None
    subl = k_scales.shape[1] if quant else 0
    # new-token rows are always dense int8 in quant mode, even when the
    # pools themselves are int32-packed (int4: nibble-packed half width,
    # matching the pool row width kw)
    row_dtype = jnp.int8 if quant else k_cache.dtype
    res = fused_paged_decode_attention(
        q,
        jnp.zeros((b, kw), row_dtype),
        jnp.zeros((b, v_cache.shape[1]), row_dtype),
        k_cache,
        v_cache,
        block_tables,
        lengths,
        jnp.full((b,), -1, jnp.int32),
        k_scales,
        v_scales,
        jnp.ones((b, subl), jnp.float32) if quant else None,
        jnp.ones((b, subl), jnp.float32) if quant else None,
        page_size=page_size,
        pages_per_block=pages_per_block,
        interpret=interpret,
        alias_caches=False,
        int4=int4,
    )
    return res[0]


def ragged_paged_attention(
    q: jax.Array,             # [B, T, H, Hd] (rope applied, unscaled)
    k_cache: jax.Array,       # [num_slots, K*Hd] flat slot pool
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, W] i32 page ids (0 = trash page)
    q_pos0: jax.Array,        # [B] i32 first query position per row
    q_lens: jax.Array,        # [B] i32 valid query rows (0 = inactive)
    k_scales: jax.Array = None,  # [num_pages, SUBL, S] f32 scale pools
    v_scales: jax.Array = None,
    *,
    page_size: int,
    interpret: bool = False,
    int4: bool = False,
    mask_block: int = 1,
) -> jax.Array:
    """Read-only paged attention with PER-ROW query lengths — the kernel
    behind the mixed prefill+decode step, the pallas spec-verify path AND
    the block passes of a model generated by diffusion over blocks
    (KV already written, row-scattered by the caller): decode rows are
    q_len=1 at an arbitrary (mid-page) position, speculative verify rows
    span q_len = draft_len+1 from a mid-page q_pos0, chunked-prefill
    rows span [q_pos0, q_pos0+q_len) with the mask causal by position
    inside the chunk (`mask_block` 1) or by blocks of `mask_block`
    positions (a power of two: a query sees keys up to `q_pos |
    (mask_block - 1)`; a block pass is `q_len = mask_block` from the
    block's first position), padding rows (q_len=0) emit zeros.

    Which call takes which kernel is decided by what the call can see in
    its input, both static: a BLOCK PASS (`mask_block > 1`, every row
    `q.shape[1] == mask_block` queries, pools in the model's dtype) takes
    the block kernel (ops/pallas_block.py: a flat work list over the pages
    the rows hold, a block's queries one tile a KV head). Every other call
    (`mask_block` 1: the mixed step, the verify step; a chunk of whole
    blocks: such a model's prefill groups) delegates to the flash prefill
    kernel (ops/pallas_prefill.py), whose online-softmax grid handles
    per-row ragged lengths; unlike the prefill WRITE path, `q_pos0` here
    need not be page-aligned (no page-granular scatter is involved). A
    dedicated kernel that skips the padded query tiles of q_len=1 rows
    would land behind this signature too. Returns [B, T, H, Hd] in
    q.dtype."""
    if mask_block > 1 and q.shape[1] == mask_block and k_scales is None:
        from dynamo_tpu.ops.pallas_block import block_paged_attention

        return block_paged_attention(
            q, k_cache, v_cache, block_tables, q_pos0, q_lens,
            page_size=page_size, mask_block=mask_block, interpret=interpret,
        )

    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

    return flash_prefill_attention(
        q, k_cache, v_cache, block_tables, q_pos0, q_lens,
        k_scales, v_scales, page_size=page_size, interpret=interpret,
        int4=int4, **({"mask_block": mask_block} if mask_block > 1 else {}),
    )
