"""Attribute the int8-KV decode step cost: full 1B model scan with the
decode kernel swapped for ablated variants (same dispatch machinery on
both sides, so the deltas isolate the kernel).

Run: python scripts/probe_decode_attrib.py [B]
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import dynamo_tpu.ops.pallas_attention as PA
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.ops.sampling import sample_tokens

B = int(sys.argv[1]) if len(sys.argv) > 1 else 256
CFG = get_config("llama-3.2-1b")
STEPS = 16
KV_LEN = 480
N = 6


def time_scan(b, kv_quant=True, ablate=None, knockout=False,
              nbuf=None, ppb=None, packed=False):
    pg = 128
    w_pages = -(-(KV_LEN + STEPS + pg) // pg)
    num_slots = (b * w_pages + 17) * pg
    tables = jnp.asarray(
        np.stack([np.arange(1 + i * w_pages, 1 + (i + 1) * w_pages)
                  for i in range(b)]), jnp.int32)
    temp = jnp.zeros((b,), jnp.float32)
    topk = jnp.zeros((b,), jnp.int32)
    topp = jnp.ones((b,), jnp.float32)

    def multi(params, kv, tokens, positions, key):
        def body(carry, _):
            tokens, positions, kv, key = carry
            key, sub = jax.random.split(key)
            wslots = (
                jnp.take_along_axis(
                    tables, (positions // pg)[:, None], axis=1
                )[:, 0] * pg + positions % pg
            ).astype(jnp.int32)
            spec = llama.AttnSpec.pallas_decode(
                tables, positions + 1, pg, write_pos=positions
            )
            hidden, kv = llama.forward(
                params, CFG, tokens[:, None], positions[:, None],
                kv, wslots, spec,
            )
            lg = llama.logits(params, CFG, hidden[:, 0])
            toks = sample_tokens(lg, sub, temp, topk, topp, all_greedy=True)
            return (toks, positions + 1, kv, key), toks

        (_, _, kv, _), out = jax.lax.scan(
            body, (tokens, positions, kv, key), None, length=STEPS)
        return out, kv

    from dynamo_tpu.ops.quant import quantize_params

    params = quantize_params(
        llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.bfloat16), CFG
    )
    kv = jax.device_put(llama.init_kv_cache(
        CFG, num_slots, dtype=jnp.bfloat16,
        kv_quant="int8" if kv_quant else None, page_size=pg,
    ))
    if packed:
        from dynamo_tpu.ops.quant import pack_kv_slots

        pk = jax.jit(pack_kv_slots)
        kv = llama.KVCache(
            k=tuple(pk(x) for x in kv.k),
            v=tuple(pk(x) for x in kv.v),
            ks=kv.ks, vs=kv.vs,
        )
    tokens = jnp.ones((b,), jnp.int32)
    positions = jnp.full((b,), KV_LEN, jnp.int32)
    key = jax.random.PRNGKey(0)

    real = PA.fused_paged_decode_attention
    try:
        if knockout:
            PA.fused_paged_decode_attention = (
                lambda q, nk, nv, kc, vc, tb, ln, wp, *a, **kw:
                (q, kc, vc, *a[:2]) if a and a[0] is not None
                else (q, kc, vc)
            )
        elif ablate or nbuf or ppb:
            kw = {}
            if ablate:
                kw["ablate"] = ablate
            if nbuf:
                kw["nbuf"] = nbuf
            if ppb:
                kw["pages_per_block"] = ppb
            PA.fused_paged_decode_attention = functools.partial(real, **kw)
        f = jax.jit(multi, donate_argnums=(1,))
        out, kv = f(params, kv, tokens, positions, key)
        _ = np.asarray(out[-1, :1])
        t0 = time.perf_counter()
        for _ in range(N):
            out, kv = f(params, kv, tokens, positions, key)
        _ = np.asarray(out[-1, :1])
        return (time.perf_counter() - t0) / N / STEPS
    finally:
        PA.fused_paged_decode_attention = real


def main():
    rows = [
        ("PACKED", dict(packed=True)),
        ("PACKED nbuf=16", dict(packed=True, nbuf=16)),
        ("PACKED ppb=8", dict(packed=True, ppb=8)),
        ("PACKED ppb=8 nbuf=16", dict(packed=True, ppb=8, nbuf=16)),
        ("PACKED ppb=2 nbuf=16", dict(packed=True, ppb=2, nbuf=16)),
        ("PACKED noscale_dma", dict(packed=True, ablate="noscale_dma")),
    ]
    for name, kw in rows:
        dt = time_scan(B, **kw)
        print(f"{name:24s} {dt * 1e3:7.3f} ms/step -> {B / dt:6.0f} tok/s",
              flush=True)


if __name__ == "__main__":
    main()
