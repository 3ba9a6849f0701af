"""Probe pltpu.bitcast int32<->int8 semantics on the real chip, and the
page-DMA rate of int32-packed vs int8 pools.

Establishes the ground truth for the packed int8-KV pool format
(docs/quantization.md "recovery plan"): int8 pages DMA ~20% slower per
byte than f32-class dtypes, so the pools store int32 [T/4, C] and the
kernels unpack with pltpu.bitcast. This probe pins down:
 1. forward bitcast row mapping (int32 [T, C] -> int8 [4T, C]);
 2. whether the reverse bitcast (int8 -> int32) compiles + inverts;
 3. measured DMA GB/s for int8 [page, kw] vs int32 [page/4, kw] pages.

Run: python scripts/probe_bitcast.py
"""

import functools
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe_forward():
    T, C = 8, 128
    rng = np.random.RandomState(0)
    x8 = rng.randint(-127, 128, size=(4 * T, C)).astype(np.int8)

    def kernel(x_ref, o_ref):
        o_ref[...] = pltpu.bitcast(x_ref[...], jnp.int8)

    # H1 pack: int32 row t packs int8 rows 4t..4t+3 little-endian
    h1 = (
        x8.reshape(T, 4, C).astype(np.uint8).astype(np.uint32)
    )
    h1 = (h1[:, 0] | (h1[:, 1] << 8) | (h1[:, 2] << 16) | (h1[:, 3] << 24)).view(
        np.int32
    )
    # H2 pack: int32 row t packs int8 rows t, T+t, 2T+t, 3T+t
    h2 = x8.reshape(4, T, C).astype(np.uint8).astype(np.uint32)
    h2 = (h2[0] | (h2[1] << 8) | (h2[2] << 16) | (h2[3] << 24)).view(np.int32)

    out_shape = jax.ShapeDtypeStruct((4 * T, C), jnp.int8)
    f = pl.pallas_call(kernel, out_shape=out_shape)
    for name, packed in (("H1-consecutive", h1), ("H2-strided", h2)):
        y = np.asarray(f(jnp.asarray(packed)))
        print(f"forward {name}: match={np.array_equal(y, x8)}")
        if not np.array_equal(y, x8):
            # where do rows land?
            for r in range(8):
                src = np.where((x8 == y[r]).all(axis=1))[0]
                print(f"  out row {r} == in row(s) {src}")
    return


def probe_reverse():
    T, C = 8, 128
    rng = np.random.RandomState(1)
    x8 = rng.randint(-127, 128, size=(4 * T, C)).astype(np.int8)

    def kernel(x_ref, o_ref):
        o_ref[...] = pltpu.bitcast(x_ref[...], jnp.int32)

    try:
        f = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((T, C), jnp.int32)
        )
        y = np.asarray(f(jnp.asarray(x8)))
    except Exception as e:
        print(f"reverse bitcast FAILED: {type(e).__name__}: {e}")
        return
    h1 = x8.reshape(T, 4, C).astype(np.uint8).astype(np.uint32)
    h1 = (h1[:, 0] | (h1[:, 1] << 8) | (h1[:, 2] << 16) | (h1[:, 3] << 24)).view(
        np.int32
    )
    print(f"reverse bitcast: H1 match={np.array_equal(y, h1)}")


def probe_roundtrip_inject():
    """The decode write path: bitcast to int8, compute, inject a row in
    the int32 domain via shifts, write back."""
    T, C = 32, 128  # int8 rows
    rng = np.random.RandomState(2)
    x8 = rng.randint(-127, 128, size=(T, C)).astype(np.int8)
    new_row = rng.randint(-127, 128, size=(1, C)).astype(np.int8)
    off = 13  # inject at int8 row 13 -> int32 row 3, byte 1

    def kernel(x_ref, new_ref, off_ref, o_ref):
        x32 = x_ref[...]                      # [T//4, C] int32
        off = off_ref[0]
        b = jax.lax.rem(off, 4)
        r32 = jax.lax.div(off, 4)
        shift = b * 8
        nb = (new_ref[...].astype(jnp.int32) & 0xFF) << shift   # [1, C]
        mask = jnp.full_like(x32, 0xFF) << shift
        row = jax.lax.broadcasted_iota(jnp.int32, x32.shape, 0)
        x32 = jnp.where(row == r32, (x32 & ~mask) | nb, x32)
        o_ref[...] = x32

    packed = x8.reshape(T // 4, 4, C).astype(np.uint8).astype(np.uint32)
    packed = (
        packed[:, 0] | (packed[:, 1] << 8) | (packed[:, 2] << 16)
        | (packed[:, 3] << 24)
    ).view(np.int32)

    f = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((T // 4, C), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )
    y = np.asarray(
        f(jnp.asarray(packed), jnp.asarray(new_row), jnp.asarray([off]))
    )
    want = x8.copy()
    want[off] = new_row[0]
    got = np.stack(
        [((y.view(np.uint32) >> (8 * j)) & 0xFF).astype(np.uint8) for j in range(4)],
        axis=1,
    ).reshape(T, C).view(np.int8) if False else None
    # decode H1: int32 row t -> int8 rows 4t..4t+3
    u = y.view(np.uint32)
    dec = np.zeros((T, C), np.uint8)
    for j in range(4):
        dec[j::4] = 0  # placeholder
    dec = np.empty((T // 4, 4, C), np.uint8)
    for j in range(4):
        dec[:, j] = (u >> (8 * j)) & 0xFF
    dec = dec.reshape(T, C).view(np.int8)
    print(f"inject-in-int32-domain: match={np.array_equal(dec, want)}")


def bench_dma(dtype, page, kw, n_pages=8192, nbuf=8, iters=3, reps=8):
    total_pages = 16384
    pool = jnp.zeros((total_pages, page, kw), dtype)
    rng = np.random.RandomState(0)
    # DISTINCT tables per chained rep: identical pallas calls inside the
    # timing scan would be CSE'd into one dispatch (the wall then does
    # not grow with the bytes moved)
    tables = jnp.asarray(
        np.stack([rng.permutation(total_pages)[:n_pages] for _ in range(reps)]),
        jnp.int32,
    )

    def kernel(tables_ref, pages_hbm, out_ref, bufs, sems):
        for j in range(nbuf):
            pltpu.make_async_copy(
                pages_hbm.at[tables_ref[j]], bufs.at[j], sems.at[j]
            ).start()

        def body(i, acc):
            slot = jax.lax.rem(i, nbuf)
            pltpu.make_async_copy(
                pages_hbm.at[0], bufs.at[slot], sems.at[slot]
            ).wait()
            acc = acc + jnp.sum(bufs[slot, 0].astype(jnp.float32)) * 0.0
            nxt = i + nbuf

            @pl.when(nxt < n_pages)
            def _():
                pltpu.make_async_copy(
                    pages_hbm.at[tables_ref[nxt]], bufs.at[slot], sems.at[slot]
                ).start()

            return acc

        acc = jax.lax.fori_loop(0, n_pages, body, 0.0)
        out_ref[0, 0] = acc

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[
            pltpu.VMEM((nbuf, page, kw), dtype),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
    )
    bench = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
    )

    # chain N reps inside one jit (amortizes the per-dispatch cost)
    @jax.jit
    def run(t, p):
        def step(carry, ti):
            o = bench(ti, p)
            return carry + o[0, 0], None

        acc, _ = jax.lax.scan(step, 0.0, t)
        return acc

    _ = np.asarray(run(tables, pool))  # warmup/compile
    best = None
    for _ in range(iters):
        t0 = time.perf_counter()
        _ = np.asarray(run(tables, pool))
        dt = (time.perf_counter() - t0) / reps
        best = dt if best is None else min(best, dt)
    nbytes = n_pages * page * kw * np.dtype(dtype).itemsize
    gbs = nbytes / best / 1e9
    print(
        f"DMA {np.dtype(dtype).name:8s} page=[{page},{kw}] "
        f"{nbytes / 1e6:.0f} MB in {best * 1e3:.2f} ms -> {gbs:.0f} GB/s"
    )
    return gbs


def main():
    print(f"devices: {jax.devices()}")
    probe_forward()
    probe_reverse()
    probe_roundtrip_inject()
    # 8B-class dims: kw=1024, page=128 int8 -> packed [32, 1024] int32
    g8 = bench_dma(jnp.int8, 128, 1024)
    g32 = bench_dma(jnp.int32, 32, 1024)
    gbf = bench_dma(jnp.bfloat16, 64, 1024)  # same 128 KB/page in bf16
    print(f"int32 vs int8 speedup: {g32 / g8:.3f}x ; bf16 ref {gbf:.0f} GB/s")


if __name__ == "__main__":
    main()
