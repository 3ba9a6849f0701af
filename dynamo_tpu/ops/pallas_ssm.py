"""A Mamba-2 layer's decode step as ONE kernel, from `w_in`'s output to
`w_out`'s input (models/mamba2.py; docs/kv_cache.md "State pools").

A decode row enters as the [1, H P + (H P + 2 N) + H] row `[z | xBC | dt]`
that the `w_in` matmul leaves, with its state and its convolution tail in
the layer's two pools, and leaves as the [1, H P] row that `w_out` reads:

    xBC  <- silu(conv([tail ; xBC]))      tail <- its last d_conv - 1 rows
    dt   <- softplus(dt + dt_bias)        0 where the row does not advance
    h    <- exp(dt A) h + B (outer) dt x  0 h where the row starts a sequence
    y    <- C . h + D x
    out  <- RMSNorm(y silu(z)) w          rounded once, to the model's dtype

Left to XLA that is ~25 device operations a layer beside the state's pass:
slices and relayout copies of `z` and `xBC`, a gather for the new tail,
and `exp(dt A)` and `dt x` (one number a head) written out as float32
[rows, H P] arrays, relaid and read back (PERF.md section 6, PR 42); and
before PR 41 the pool itself was read TWICE and written once. Between the
two matmuls the step program now launches this kernel and nothing else: the
`w_in` matmul writes its [rows, .] result straight into the kernel's
operand and `w_out`'s reads the kernel's [rows, H P] output, the parameters
go in as the model keeps them, and both pools go back to the places they
came from (`input_output_aliases`). Rows the grid does not visit (the
pools' rows past the program's width: the trash row) are never touched. A
row that does not advance (`real` False) gets its state and its tail back
to the bit; a row that starts a sequence (`fresh`) reads zeros whatever its
slot holds.

A GRID STEP IS A ROW, as its state is 1 MB in and 1 MB out (granite-4.0-h-
micro, bf16) and that is what the pipeline can double-buffer; the state's
pass is what the kernel's time is, and everything else hides behind its
DMA. But what is a row's OWN (`z`, `xBC`, `dt`, the tail, the output row)
is one sublane of a tile, and a [rows, 3, CW] pool of tails lies [3, rows,
CW] on the device, 16 slots a bf16 tile. So that part is moved and worked
A GROUP OF 16 ROWS AT A TIME, whole tiles: on a group's first step the
convolution, the new tails and `dt` for its 16 rows, left in VMEM a row a
tile for the rows' own steps; on its last step `D x`, the gate and the norm
of the 16 rows' `y`. A program narrower than a group, or no multiple of it,
is one group.

THE STATE POOL'S LAYOUT IS THE KERNEL'S: `[slots, packs, N, lanes]`, `lanes
= f x P`: the state of `f` = 128 / P heads side by side in the lanes and
the N state values down the sublanes (`pack` / `unpack`). Pack q's `x` and
`y` are lanes [q x lanes, (q + 1) x lanes) of the row as the matmul wrote
it. What is one number a head (`dt`, `A`, `D`) or a state value (B, C) is
made a COLUMN by a 128 x 128 transpose: B and C sublane-varying for the
update, a head's number in every lane of its sublane, from which a pack's
lane-varying [1, lanes] row is a select between its f heads' rows
(`_head_lanes`): never a float32 [rows, H P] array in HBM. `y` is a
reduction over sublanes (adds of whole vregs), not over lanes. Every head
reads the ONE B and the ONE C of its row: `mamba_n_groups` 1, every
published model of the family, and `models/config.py` refuses another by
name, as it refuses a head size that does not pack into the lanes.

`ssm_state_update` lowers to the kernel where the program is lowered for a
TPU and to `reference_update` (plain `jax.numpy`, the same arithmetic in
the same order: float32, one rounding on each write) anywhere else.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 16  # rows worked at a time: a bf16 tile's
_F32 = jnp.float32


def pack_factor(head_dim: int) -> int:
    """Heads side by side in the 128 lanes of a pool row."""
    return LANES // head_dim


def pack(h: jax.Array, f: int) -> jax.Array:
    """[B, H, P, N] -> the pool's [B, H / f, N, f x P]."""
    b, hn, p, n = h.shape
    return h.reshape(b, hn // f, f, p, n).transpose(0, 1, 4, 2, 3).reshape(
        b, hn // f, n, f * p)


def unpack(hq: jax.Array, f: int) -> jax.Array:
    """The pool's [B, H / f, N, f x P] -> [B, H, P, N]."""
    b, q, n, lanes = hq.shape
    return hq.reshape(b, q, n, f, lanes // f).transpose(0, 1, 3, 4, 2).reshape(
        b, q * f, lanes // f, n)


class StepWeights(NamedTuple):
    """What a decode step reads of a mixer's parameters beside its two
    matmuls, as the model keeps them (no operation prepares them)."""
    conv_w: jax.Array    # [d_conv, CW]
    conv_b: jax.Array    # [CW]
    dt_bias: jax.Array   # [H]
    a_log: jax.Array     # [H]
    d: jax.Array         # [H]
    norm: jax.Array      # [H P]


def reference_update(zxbcdt, pool, tails, real, fresh, w: StepWeights, *,
                     eps: float):
    """The step in plain `jax.numpy` on rows [0, B) of `pool` [S, Q, N, L]
    and `tails` [S, d_conv - 1, CW]: `zxbcdt` [B, 1, H P + CW + H] as the
    `w_in` matmul leaves it; `real`, `fresh` [B] bool. Returns (out [B, 1,
    H P] in `zxbcdt`'s dtype, pool, tails)."""
    bsz = zxbcdt.shape[0]
    n, lanes = pool.shape[2:]
    inner = pool.shape[1] * lanes
    taps, cw = tails.shape[1:]
    p = inner // w.d.shape[0]
    z = zxbcdt[:, 0, :inner].astype(_F32)
    xbc = zxbcdt[:, 0, inner:inner + cw]
    dt = zxbcdt[:, 0, inner + cw:].astype(_F32)
    # the convolution over [tail ; xBC], and the tail the row keeps
    tail = jnp.where(fresh[:, None, None], 0, tails[:bsz]).astype(xbc.dtype)
    seq = jnp.concatenate([tail, xbc[:, None]], axis=1)
    cv = w.conv_w.astype(_F32)
    acc = sum(seq[:, j].astype(_F32) * cv[j] for j in range(taps + 1))
    xbc = jax.nn.silu(acc + w.conv_b.astype(_F32)).astype(xbc.dtype)
    tails = tails.at[:bsz].set(jnp.where(
        real[:, None, None], seq[:, 1:], tail).astype(tails.dtype))
    x = xbc[:, :inner].astype(_F32)
    bmat = xbc[:, inner:inner + n].astype(_F32)
    cmat = xbc[:, inner + n:].astype(_F32)
    # the update: what is a head's, over the head's P lanes
    bias, a_log, d = (v.astype(_F32) for v in (w.dt_bias, w.a_log, w.d))
    dt = jnp.where(real[:, None], jax.nn.softplus(dt + bias), 0.0)
    decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt * -jnp.exp(a_log)))
    decay, dt, d = (jnp.repeat(v, p, axis=-1) for v in (decay, dt, d))
    dtx = dt * x
    h = pool[:bsz].astype(_F32)
    h = (h * decay.reshape(bsz, -1, 1, lanes)
         + bmat[:, None, :, None] * dtx.reshape(bsz, -1, 1, lanes))
    y = (h * cmat[:, None, :, None]).sum(axis=2).reshape(bsz, inner)
    pool = pool.at[:bsz].set(h.astype(pool.dtype))
    # D x, the gate, the norm over the row's one group
    y = (y + d * x) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = (y * w.norm.astype(_F32)).astype(zxbcdt.dtype)
    return y[:, None], pool, tails


def _column(row, rows: int, lanes: int):
    """[1, W] -> [rows, lanes]: value i of the row in every lane of
    sublane i (a square transpose of the row spread over the sublanes)."""
    width = row.shape[1]
    side = max(width, rows, lanes)
    if side != width:
        row = jnp.pad(row, ((0, 0), (0, side - width)))
    return jnp.broadcast_to(row, (side, side)).T[:rows, :lanes]


def _head_lanes(col, q: int, f: int, head_dim: int):
    """Pack q's [1, lanes] row of what is one number a head, from `col`
    (head h's number in every lane of sublane h): a select between the
    rows of the pack's f heads."""
    row = col[q * f:q * f + 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    for i in range(1, f):
        row = jnp.where(lane >= i * head_dim, col[q * f + i:q * f + i + 1],
                        row)
    return row


def _kernel(zx_ref, real_ref, fresh_ref, h_ref, tail_ref, cw_ref, cb_ref,
            dtb_ref, alog_ref, d_ref, nw_ref, y_ref, out_ref, tail_out_ref,
            xbc_s, dt_s, keep_s, y_s, a_s, dtcol_s,
            *, head_dim: int, eps: float):
    r = pl.program_id(1)
    rows = zx_ref.shape[0]
    packs, n, lanes = h_ref.shape[1:]
    f = lanes // head_dim
    inner = packs * lanes
    taps, held, cw = tail_ref.shape                        # d_conv - 1
    heads = dtcol_s.shape[0]

    @pl.when(r == 0)
    def _():
        # what is a row's own, for the group's rows at once (whole tiles):
        # the convolution over [tail ; xBC], the tail a row keeps, and dt
        real = real_ref[...] != 0                          # [rows, 1]
        fresh = fresh_ref[...] != 0
        xbc = zx_ref[:, inner:inner + cw]
        seq = [jnp.where(fresh, jnp.zeros_like(xbc), tail_ref[j, :rows])
               for j in range(taps)] + [xbc]
        acc = seq[0].astype(_F32) * cw_ref[0:1].astype(_F32)
        for j in range(1, taps + 1):
            acc = acc + seq[j].astype(_F32) * cw_ref[j:j + 1].astype(_F32)
        acc = jax.nn.silu(acc + cb_ref[...].astype(_F32)[None])
        acc = acc.astype(xbc.dtype).astype(_F32)
        for j in range(taps):
            tail_out_ref[j, :rows] = jnp.where(
                real, seq[j + 1], seq[j]).astype(tail_out_ref.dtype)
            if held > rows:  # slots of the block that are no row's
                tail_out_ref[j, rows:] = tail_ref[j, rows:]
        dt = jax.nn.softplus(
            zx_ref[:, inner + cw:].astype(_F32) + dtb_ref[...].astype(_F32))
        dt = jnp.where(real, dt, jnp.zeros_like(dt))
        keep = jnp.broadcast_to(
            jnp.where(fresh, 0.0, 1.0).astype(_F32), (rows, lanes))
        for i in range(rows):  # a row a tile, for the row's own step
            xbc_s[i] = acc[i:i + 1]
            dt_s[i] = dt[i:i + 1]
            keep_s[i] = keep[i:i + 1]
        acol = _column(-jnp.exp(alog_ref[...].astype(_F32)), heads, lanes)
        for q in range(packs):
            a_s[q:q + 1] = _head_lanes(acol, q, f, head_dim)

    # the row's state: B and C sublane-varying, a head's dt in every lane
    # of sublane h
    bcol = _column(xbc_s[r, :, inner:inner + n], n, lanes)
    ccol = _column(xbc_s[r, :, inner + n:inner + 2 * n], n, lanes)
    dtcol_s[...] = _column(dt_s[r], heads, lanes)
    keep = keep_s[r]
    for q in range(packs):
        at = slice(q * lanes, (q + 1) * lanes)
        dtq = _head_lanes(dtcol_s, q, f, head_dim)
        decay = jnp.exp(dtq * a_s[q:q + 1]) * keep
        h = h_ref[0, q].astype(_F32)                       # [N, lanes]
        h = h * decay + bcol * (dtq * xbc_s[r, :, at])
        out_ref[0, q] = h.astype(out_ref.dtype)
        y_s[r, :, at] = jnp.sum(h * ccol, axis=0, keepdims=True)

    @pl.when(r == rows - 1)
    def _():
        # D x, the gate, the norm over a row's one group: the group's rows
        y, x = (jnp.concatenate([s[i, :, :inner] for i in range(rows)])
                for s in (y_s, xbc_s))
        dcol = _column(d_ref[...].astype(_F32), heads, lanes)
        d = jnp.concatenate(
            [_head_lanes(dcol, q, f, head_dim) for q in range(packs)], axis=1)
        y = (y + d * x) * jax.nn.silu(zx_ref[:, :inner].astype(_F32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y_ref[...] = (y * nw_ref[...].astype(_F32)[None]).astype(y_ref.dtype)



def kernel_update(zxbcdt, pool, tails, real, fresh, w: StepWeights, *,
                  eps: float, interpret: bool = False):
    """The step as one pallas pass over a grid of (groups, rows a group):
    a row's state block a step, the group's blocks of `zxbcdt`, tails and
    output held across its steps."""
    bsz, _, width = zxbcdt.shape
    slots, packs, n, lanes = pool.shape
    taps, cw = tails.shape[1:]
    inner = packs * lanes
    heads = width - inner - cw
    rows = GROUP if bsz % GROUP == 0 else bsz
    # a slot's tail lies [d_conv - 1, slots, CW] on the device (the TPU's
    # own layout of the pool: the transposes are bitcasts there), 16 slots
    # a tile: the block is whole tiles, and slots past the rows go back as
    # they came
    held = min(-(-rows // GROUP) * GROUP, slots)
    group = lambda g, r: (g, 0)           # noqa: E731
    whole = lambda g, r: (0, 0)           # noqa: E731
    state = pl.BlockSpec(
        (1, packs, n, lanes), lambda g, r: (g * rows + r, 0, 0, 0))
    tail = pl.BlockSpec((taps, held, cw), lambda g, r: (0, g, 0))
    flag = pl.BlockSpec((rows, 1), group)
    head = pl.BlockSpec((1, heads), whole)
    out, pool, tails = pl.pallas_call(
        functools.partial(_kernel, head_dim=inner // heads, eps=eps),
        grid=(bsz // rows, rows),
        in_specs=[
            pl.BlockSpec((rows, width), group), flag, flag, state, tail,
            pl.BlockSpec((taps + 1, cw), whole),
            pl.BlockSpec((cw,), lambda g, r: (0,)),
            head, head, head,
            pl.BlockSpec((inner,), lambda g, r: (0,)),
        ],
        out_specs=[pl.BlockSpec((rows, inner), group), state, tail],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, inner), zxbcdt.dtype),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((taps, slots, cw), tails.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1, cw), _F32),               # xBC, convolved
            pltpu.VMEM((rows, 1, heads), _F32),            # dt
            pltpu.VMEM((rows, 1, lanes), _F32),            # 0 on a fresh row
            pltpu.VMEM((rows, 1, inner), _F32),            # y = C . h
            pltpu.VMEM((packs, lanes), _F32),              # -exp(A_log)
            pltpu.VMEM((heads, lanes), _F32),              # the row's dt
        ],
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_state_update",
    )(zxbcdt[:, 0], real.astype(jnp.int32)[:, None],
      fresh.astype(jnp.int32)[:, None], pool, tails.transpose(1, 0, 2),
      w.conv_w, w.conv_b, w.dt_bias[None], w.a_log[None], w.d[None], w.norm)
    return out[:, None], pool, tails.transpose(1, 0, 2)


def ssm_state_update(zxbcdt, pool, tails, real, fresh, w: StepWeights, *,
                     eps: float):
    """(out [B, 1, H P], pool, tails): the kernel on a TPU,
    `reference_update` elsewhere."""
    return jax.lax.platform_dependent(
        zxbcdt, pool, tails, real, fresh, w,
        tpu=functools.partial(kernel_update, eps=eps),
        default=functools.partial(reference_update, eps=eps),
    )
