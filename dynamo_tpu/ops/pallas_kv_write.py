"""Pallas page-scatter KV write: the prefill-side cache update.

XLA lowers `pool.at[slots].set(rows)` to a scatter the TPU backend
serializes per row (~0.45 us each) — at a [64, 512] prefill chunk batch
that is 32k rows x 16 layers ~= 390 ms, the single largest prefill cost.
This kernel writes whole pages instead: the grid walks the chunk's page
blocks and an output BlockSpec index_map routed by a scalar-prefetched
page table lands each [page_size, K*Hd] block in place (input/output
aliased pools, no copy): one block DMA per page where the XLA scatter
moves every row by itself (the ratio on this chip is not measured).

The TPU-native counterpart of the reference's block-copy kernel
(reference: lib/llm/src/kernels/block_copy.cu:41-731 — cache-line-chunked
page copies for the same reason: per-element scatter is the enemy).

Correct-use contract (the engine's chunking guarantees both):
- chunk starts are page-aligned (prefill_chunk % page_size == 0; prefix
  cache hits and preemption resumes are page-aligned by construction);
- rows past the chunk tail inside a page may be garbage — they belong to
  the same sequence's not-yet-computed positions (masked out of
  attention) or to the trash page.

Sharding: pools/rows are tp-sharded on the folded K*Hd dim; the caller
wraps in shard_map next to the decode kernel (llama._attn_block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_attention import hbm_out, in_hbm


def _kernel(tbl_ref, kp_ref, vp_ref, src_k_ref, src_v_ref, ok_ref, ov_ref):
    del kp_ref, vp_ref  # aliased through; only the indexed blocks change
    ok_ref[...] = src_k_ref[...]
    ov_ref[...] = src_v_ref[...]


def _kernel_q(tbl_ref, kp_ref, vp_ref, ksp_ref, vsp_ref,
              src_k_ref, src_v_ref, src_ks_ref, src_vs_ref,
              ok_ref, ov_ref, oks_ref, ovs_ref):
    del kp_ref, vp_ref, ksp_ref, vsp_ref  # aliased through
    ok_ref[...] = src_k_ref[...]
    ov_ref[...] = src_v_ref[...]
    oks_ref[...] = src_ks_ref[...]
    ovs_ref[...] = src_vs_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "interpret"),
    donate_argnums=(0, 1, 5, 6),
)
def paged_kv_write(
    k_cache: jax.Array,   # [num_slots, K*Hd] (int8 in quantized mode)
    v_cache: jax.Array,
    page_table: jax.Array,  # [n_pages] i32 destination page ids (0 = trash)
    new_k: jax.Array,     # [n_pages, page_size, K*Hd] source page blocks
    new_v: jax.Array,
    ks_cache: jax.Array = None,  # [num_pages, SUBL, S] f32 scale pools
    vs_cache: jax.Array = None,  # (ops/quant pool layout)
    new_ks: jax.Array = None,    # [n_pages, SUBL, S] source scale tiles
    new_vs: jax.Array = None,
    *,
    page_size: int,
    interpret: bool = False,
):
    """Scatter whole pages into the slot pools, in place (donated).
    In int8-KV mode the scale pools scatter in the same kernel — their
    [SUBL, S] tiles ride the same page-table routing.

    int32-PACKED pools (quant.pack_kv_slots): `k_cache`/`v_cache` arrive
    int32 [num_slots//4, K*Hd] and `new_k`/`new_v` arrive pre-packed
    [n_pages, page_size//4, K*Hd] — the kernel is a pure page copy, so
    only the block shapes change."""
    quant = ks_cache is not None
    packed = quant and k_cache.dtype == jnp.int32
    num_slots, kw = k_cache.shape
    if packed:
        num_slots *= 4
    page_rows = page_size // 4 if packed else page_size
    num_pages = num_slots // page_size
    n = page_table.shape[0]
    kp = k_cache.reshape(num_pages, page_rows, kw)
    if quant:
        vp = v_cache.reshape(num_pages, page_rows, kw)

    def dst(i, tbl):
        return (tbl[i], 0, 0)

    def src(i, tbl):
        return (i, 0, 0)

    if quant:
        subl = ks_cache.shape[1]
        kp, vp, ks_cache, vs_cache = (
            in_hbm(p, interpret) for p in (kp, vp, ks_cache, vs_cache)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, page_rows, kw), src),
                pl.BlockSpec((1, page_rows, kw), src),
                pl.BlockSpec((1, subl, page_size), src),
                pl.BlockSpec((1, subl, page_size), src),
            ],
            out_specs=[
                pl.BlockSpec((1, page_rows, kw), dst),
                pl.BlockSpec((1, page_rows, kw), dst),
                pl.BlockSpec((1, subl, page_size), dst),
                pl.BlockSpec((1, subl, page_size), dst),
            ],
        )
        ok, ov, oks, ovs = pl.pallas_call(
            _kernel_q,
            grid_spec=grid_spec,
            out_shape=[*map(hbm_out, (kp, vp, ks_cache, vs_cache))],
            input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(page_table.astype(jnp.int32), kp, vp, ks_cache, vs_cache,
          new_k, new_v, new_ks, new_vs)
        return (
            ok.reshape(num_slots // 4 if packed else num_slots, kw),
            ov.reshape(num_slots // 4 if packed else num_slots, kw),
            oks,
            ovs,
        )

    vw = v_cache.shape[1]   # values may be narrower than keys
    vp = v_cache.reshape(num_pages, page_rows, vw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, page_size, kw), src),
            pl.BlockSpec((1, page_size, vw), src),
        ],
        out_specs=[
            pl.BlockSpec((1, page_size, kw), dst),
            pl.BlockSpec((1, page_size, vw), dst),
        ],
    )
    ok, ov = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, kp.dtype),
            jax.ShapeDtypeStruct(vp.shape, vp.dtype),
        ],
        input_output_aliases={1: 0, 2: 1},  # kp -> ok, vp -> ov (in place)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kp, vp, new_k, new_v)
    return ok.reshape(num_slots, kw), ov.reshape(num_slots, vw)
