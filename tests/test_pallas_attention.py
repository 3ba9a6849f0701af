"""Pallas paged decode attention vs the jnp oracle (interpret mode on CPU).

The kernel must agree with `ops.attention.paged_attention` — the pure-jnp
correctness oracle — on mixed-length batches, GQA head groupings, and
inactive (length 0) rows.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_attention, slots_from_pages
from dynamo_tpu.ops.pallas_attention import (
    fused_paged_decode_attention,
    paged_decode_attention,
)

PAGE = 16


def _setup(b, h, kh, hd, w, lengths, seed=0):
    rng = np.random.RandomState(seed)
    num_pages = b * w + 1
    num_slots = num_pages * PAGE
    k_cache = rng.randn(num_slots, kh * hd).astype(np.float32)
    v_cache = rng.randn(num_slots, kh * hd).astype(np.float32)
    q = rng.randn(b, h, hd).astype(np.float32)
    # per-sequence page tables: disjoint pages, 0-padded tails
    tables = np.zeros((b, w), np.int32)
    for i in range(b):
        used = -(-lengths[i] // PAGE)
        tables[i, :used] = 1 + i * w + np.arange(used)
    return (
        jnp.asarray(q),
        jnp.asarray(k_cache),
        jnp.asarray(v_cache),
        jnp.asarray(tables),
        jnp.asarray(np.asarray(lengths, np.int32)),
    )


def _oracle(q, k_cache, v_cache, tables, lengths):
    """jnp gather attention: query at position length-1 over slots."""
    smat = slots_from_pages(tables, PAGE)
    positions = (lengths - 1)[:, None]
    out = paged_attention(q[:, None], k_cache, v_cache, smat, positions)
    return out[:, 0]


@pytest.mark.parametrize(
    "b,h,kh,hd,w,lengths",
    [
        (4, 8, 2, 64, 8, [100, 17, 128, 1]),
        (2, 4, 4, 64, 4, [64, 33]),           # MHA (g=1)
        (3, 16, 2, 128, 6, [5, 96, 41]),      # hd=128
        (4, 8, 2, 64, 8, [100, 0, 128, 0]),   # inactive rows
        (1, 8, 8, 64, 16, [256]),             # long single seq
    ],
)
def test_matches_oracle(b, h, kh, hd, w, lengths):
    q, kc, vc, tables, lens = _setup(b, h, kh, hd, w, lengths)
    got = paged_decode_attention(
        q, kc, vc, tables, lens, page_size=PAGE, pages_per_block=4,
        interpret=True,
    )
    want = _oracle(q, kc, vc, tables, lens)
    active = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(want)[active], rtol=2e-5, atol=2e-5
    )
    # inactive rows produce zeros (the engine discards them)
    np.testing.assert_array_equal(np.asarray(got)[~active], 0.0)


def test_bf16_inputs_close():
    q, kc, vc, tables, lens = _setup(4, 8, 2, 64, 8, [100, 17, 128, 60])
    got = paged_decode_attention(
        q.astype(jnp.bfloat16),
        kc.astype(jnp.bfloat16),
        vc.astype(jnp.bfloat16),
        tables,
        lens,
        page_size=PAGE,
        pages_per_block=4,
        interpret=True,
    )
    want = _oracle(q, kc, vc, tables, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=0.05, atol=0.05
    )


@pytest.mark.parametrize(
    "b,h,kh,hd,w,wpos",
    [
        # mid-page, page-boundary (next write = first slot of its page),
        # inactive, block-boundary (first slot of block 2)
        (4, 8, 2, 64, 16, [37, 47, -1, 128]),
        (2, 32, 8, 64, 16, [0, 200]),   # very first token; long seq
    ],
)
def test_fused_write_matches_scatter_oracle(b, h, kh, hd, w, wpos):
    """The fused kernel must (a) leave the caches exactly as a scatter
    would and (b) attend over the cache *including* the new token."""
    wpos = np.asarray(wpos, np.int32)
    lengths = np.where(wpos >= 0, wpos + 1, 0).astype(np.int32)
    q, kc, vc, tables, lens = _setup(b, h, kh, hd, w, lengths.tolist())
    rng = np.random.RandomState(1)
    new_k = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))
    new_v = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))

    got, k2, v2 = fused_paged_decode_attention(
        q, new_k, new_v, kc, vc, tables, lens, jnp.asarray(wpos),
        page_size=PAGE, pages_per_block=4, interpret=True,
    )

    # oracle: scatter the rows, then gather-attention
    ek, ev = np.asarray(kc).copy(), np.asarray(vc).copy()
    tb = np.asarray(tables)
    for i in range(b):
        if wpos[i] >= 0:
            slot = tb[i, wpos[i] // PAGE] * PAGE + wpos[i] % PAGE
            ek[slot] = np.asarray(new_k)[i]
            ev[slot] = np.asarray(new_v)[i]
    np.testing.assert_array_equal(np.asarray(k2), ek)
    np.testing.assert_array_equal(np.asarray(v2), ev)

    want = _oracle(q, jnp.asarray(ek), jnp.asarray(ev), tables, lens)
    active = lengths > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(want)[active], rtol=2e-5, atol=2e-5
    )


def test_table_width_not_multiple_of_block():
    # W=5 with pages_per_block=4 exercises the pad path
    q, kc, vc, tables, lens = _setup(2, 8, 2, 64, 5, [80, 33])
    got = paged_decode_attention(
        q, kc, vc, tables, lens, page_size=PAGE, pages_per_block=4,
        interpret=True,
    )
    want = _oracle(q, kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ------------------------------------------- a work item's live pages
# (pages_per_block, pages the LAST block of a sequence holds): the first,
# a middle and the last page of a block
LIVE_CASES = [(2, 1), (2, 2), (4, 1), (4, 3), (4, 4)]


def _tail_lengths(ppb, tail):
    """Three sequences whose last block holds `tail` pages: inside the
    first block, one block in and ending exactly on a page's end, two
    blocks in with the newest token the FIRST of its page."""
    t_blk = ppb * PAGE
    return [
        (tail - 1) * PAGE + 5,
        t_blk + tail * PAGE,
        2 * t_blk + (tail - 1) * PAGE + 1,
    ]


def _held_slots(tables, lengths):
    held = np.zeros(0, np.int64)
    for row, n in zip(np.asarray(tables), lengths):
        pages = row[: -(-n // PAGE)]
        held = np.concatenate(
            [held, (pages[:, None] * PAGE + np.arange(PAGE)).ravel()]
        )
    return held


@pytest.mark.parametrize("ppb,tail", LIVE_CASES)
def test_pages_not_held_are_never_touched(ppb, tail):
    """Every pool page no sequence holds, the trash page included, is
    NaN: the output and the written pages must equal the oracle's on
    clean pools. Masking a NaN after the copy gives 0 * NaN = NaN, so
    this holds only if the COMPUTE skips what the copy skipped."""
    lengths = _tail_lengths(ppb, tail)
    b, h, kh, hd, w = 3, 8, 2, 64, 3 * ppb
    q, kc, vc, tables, lens = _setup(b, h, kh, hd, w, lengths)
    wpos = np.asarray(lengths, np.int32) - 1
    rng = np.random.RandomState(2)
    new_k = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))
    new_v = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))

    held = _held_slots(tables, lengths)
    nan_k = np.full(kc.shape, np.nan, np.float32)
    nan_v = np.full(vc.shape, np.nan, np.float32)
    nan_k[held] = np.asarray(kc)[held]
    nan_v[held] = np.asarray(vc)[held]

    got, k2, v2 = fused_paged_decode_attention(
        q, new_k, new_v, jnp.asarray(nan_k), jnp.asarray(nan_v), tables,
        lens, jnp.asarray(wpos), page_size=PAGE, pages_per_block=ppb,
        interpret=True,
    )

    ek, ev = np.asarray(kc).copy(), np.asarray(vc).copy()
    tb = np.asarray(tables)
    for i in range(b):
        slot = tb[i, wpos[i] // PAGE] * PAGE + wpos[i] % PAGE
        ek[slot] = np.asarray(new_k)[i]
        ev[slot] = np.asarray(new_v)[i]
    want = _oracle(q, jnp.asarray(ek), jnp.asarray(ev), tables, lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(k2)[held], ek[held])
    np.testing.assert_array_equal(np.asarray(v2)[held], ev[held])
    unheld = np.setdiff1d(np.arange(kc.shape[0]), held)
    assert np.isnan(np.asarray(k2)[unheld]).all()
    assert np.isnan(np.asarray(v2)[unheld]).all()


@pytest.mark.parametrize("ppb,tail", LIVE_CASES)
def test_fused_write_that_opens_a_page_lands(ppb, tail):
    """`length - 1` a multiple of the page size: the new token is the
    first of a page that held nothing before, the item's LAST live page.
    The write is bit-equal to the scatter oracle."""
    t_blk = ppb * PAGE
    wpos = np.asarray(
        [(tail - 1) * PAGE, t_blk + (tail - 1) * PAGE], np.int32
    )
    lengths = (wpos + 1).tolist()
    b, h, kh, hd, w = 2, 8, 2, 64, 2 * ppb
    q, kc, vc, tables, lens = _setup(b, h, kh, hd, w, lengths)
    rng = np.random.RandomState(3)
    new_k = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))
    new_v = jnp.asarray(rng.randn(b, kh * hd).astype(np.float32))
    got, k2, v2 = fused_paged_decode_attention(
        q, new_k, new_v, kc, vc, tables, lens, jnp.asarray(wpos),
        page_size=PAGE, pages_per_block=ppb, interpret=True,
    )
    ek, ev = np.asarray(kc).copy(), np.asarray(vc).copy()
    tb = np.asarray(tables)
    for i in range(b):
        slot = tb[i, wpos[i] // PAGE] * PAGE
        ek[slot] = np.asarray(new_k)[i]
        ev[slot] = np.asarray(new_v)[i]
    np.testing.assert_array_equal(np.asarray(k2), ek)
    np.testing.assert_array_equal(np.asarray(v2), ev)
    want = _oracle(q, jnp.asarray(ek), jnp.asarray(ev), tables, lens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("page,ppb", [(16, 2), (16, 4), (128, 4), (64, 1)])
def test_streamed_pages_is_the_pages_held(page, ppb):
    """The counter equals sum(ceil(length / page)) and what the work
    list's items copy in by `live_pages`, on random lengths with empty
    rows."""
    from dynamo_tpu.ops.pallas_attention import (
        live_pages,
        streamed_pages,
        work_list,
    )

    rng = np.random.RandomState(page + ppb)
    lengths = rng.randint(0, 9 * page * ppb, size=37)
    lengths[rng.randint(0, 37, size=6)] = 0
    lengths[:3] = [1, page, page * ppb + 1]
    held = int(np.sum(-(-lengths // page)))
    assert streamed_pages(lengths, page, ppb) == held
    assert streamed_pages(lengths.reshape(1, -1), page, ppb) == held
    assert streamed_pages(np.zeros(4, np.int32), page, ppb) == 0
    assert streamed_pages([], page, ppb) == 0

    seq, blk, n_work = work_list(
        jnp.asarray(lengths, jnp.int32), page * ppb, 9
    )
    seq, blk = np.asarray(seq)[: int(n_work)], np.asarray(blk)[: int(n_work)]
    per_item = live_pages(lengths[seq], blk, page, ppb, xp=np)
    assert per_item.min() >= 1 and per_item.max() <= ppb
    assert int(per_item.sum()) == held
    # what the whole-block rule copied in: every item a full block
    assert streamed_pages(lengths, page * ppb, 1) * ppb == len(seq) * ppb
