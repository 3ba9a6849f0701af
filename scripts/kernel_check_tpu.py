"""Compiled-mode validation + microbenchmark of the fused paged-decode
kernel on the real TPU chip (interpret-mode CPU tests cannot validate DMA/
semaphore semantics or VMEM sizing — this runs the Mosaic-compiled kernel).

Writes KERNEL_TPU.json at the repo root:
  { "backend", "agree_max_err", "configs": [ {B, pages, GB/s, ms}, ... ] }

Timing methodology: each config is timed as N chained kernel calls
(each consuming the previous pool) ended by a value fetch, so the fixed
cost of one dispatch is amortized over N and the fetch is the fence.
No copy of KERNEL_TPU.json is kept in the tree (it is gitignored): the
serving path's kernel rates are the ledger's `decode_attn_roofline`.

`--cell-shape [--kernel-file <another tree's ops/pallas_attention.py>]...`
runs only `cell_shape`: the decode kernel ALONE at the shape of the
benchmark's `mistral-7b-int8.decode-saturate` cell, one line per block
size and ablation, written to chiprun_out/kernel_cell_shape.json. It is
how a change to the kernel's block or ring is decided (PERF.md section 6,
PR 32).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops.pallas_attention import fused_paged_decode_attention


def oracle(q, k_cache, v_cache, tables, lengths, page_size):
    b, h, hd = q.shape
    kw = k_cache.shape[1]
    kh = kw // hd
    g = h // kh
    smat = (tables[:, :, None] * page_size + np.arange(page_size)).reshape(b, -1)
    out = np.zeros((b, h, hd), np.float32)
    qf = np.asarray(q, np.float32)
    for i in range(b):
        n = int(lengths[i])
        if n == 0:
            continue
        slots = smat[i, :n]
        k = np.asarray(k_cache, np.float32)[slots].reshape(n, kh, hd)
        v = np.asarray(v_cache, np.float32)[slots].reshape(n, kh, hd)
        for head in range(h):
            kh_i = head // g
            s = (qf[i, head] @ k[:, kh_i].T) / np.sqrt(hd)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, head] = p @ v[:, kh_i]
    return out


def cell_shape(kernel_files: tuple[str, ...] = (), *, b: int = 64,
               layers: int = 32, reps: int = 12, interpret: bool = False,
               ) -> None:
    """The decode kernel alone where the benchmark runs it: 64 rows, a
    row's context a prompt U[256, 768] plus its progress through an
    answer U[768, 1280], Mistral-7B's heads (32 x 128 over 8 kv heads),
    int32-packed int8 pages of 128. Times 32 chained calls (a scan that
    carries the pools, as the 32 layers of a step do) x `reps` and prints
    ms per 32 calls = the kernel's share of one decode step. Each of
    `kernel_files` (another tree's ops/pallas_attention.py) runs first on
    the same inputs, and its outputs are compared with this tree's bit
    for bit. (`interpret` with a small `b`
    and `layers` walks the same code on a CPU: no time means anything.)"""
    import importlib.util

    import dynamo_tpu.ops.pallas_attention as PA
    from dynamo_tpu.ops.quant import init_kv_scale_pool, kv_scale_subl

    if jax.default_backend() != "tpu" and not interpret:
        raise SystemExit("kernel_check_tpu: no TPU")
    rng = np.random.RandomState(32)
    h, kh, hd, page = 32, 8, 128, 128
    kw = kh * hd
    answers = rng.randint(768, 1281, size=b)
    lengths = (rng.randint(256, 769, size=b)
               + (rng.rand(b) * answers).astype(np.int64)).astype(np.int32)
    pages = -(-lengths // page)
    w = 32                                   # max_model_len 4096 / 128
    tables = np.zeros((b, w), np.int32)
    nxt = 1
    for i in range(b):
        tables[i, :pages[i]] = np.arange(nxt, nxt + pages[i])
        nxt += pages[i]
    num_pages = 807                          # the cell's pool
    assert nxt <= num_pages
    pool = lambda seed: jnp.asarray(np.random.RandomState(seed).randint(
        -2 ** 31, 2 ** 31, size=(num_pages * page // 4, kw),
        dtype=np.int64).astype(np.int32))
    scales = lambda seed: init_kv_scale_pool(num_pages, page, kh) * (
        0.01 + 0.01 * seed)
    subl = kv_scale_subl(kh)
    q = jnp.asarray(rng.randn(b, h, hd), jnp.bfloat16)
    new_kv = jnp.asarray(rng.randint(-127, 128, size=(b, kw)), jnp.int8)
    new_sc = jnp.full((b, subl), 0.02, jnp.float32)
    fixed = (jnp.asarray(tables), jnp.asarray(lengths),
             jnp.asarray(lengths - 1))
    held = int(pages.sum())
    need_bytes = int(lengths.sum()) * (2 * kw + 2 * 4 * kh)

    def timed(mod, **kw_static):
        def step(pools, _):
            out, *pools = mod.fused_paged_decode_attention(
                q, new_kv, new_kv, pools[0], pools[1], *fixed,
                pools[2], pools[3], new_sc, new_sc, page_size=page,
                interpret=interpret, **kw_static)
            return tuple(pools), out[:, :, 0]

        f = jax.jit(
            lambda pools: jax.lax.scan(step, pools, None, length=layers),
            donate_argnums=(0,))
        pools = (pool(1), pool(2), scales(1), scales(2))
        pools, out = f(pools)
        first = np.asarray(out, np.float32)
        t0 = time.perf_counter()
        for _ in range(reps):
            pools, out = f(pools)
        _ = np.asarray(out)
        return (time.perf_counter() - t0) / reps * 1e3, first

    def var(ppb, nbuf, ablate=""):
        name = f"ppb{ppb}_nbuf{nbuf}" + (f"_{ablate}" if ablate else "")
        return name, {"pages_per_block": ppb, "nbuf": nbuf, "ablate": ablate}

    variants = [var(4, 4), var(4, 8), var(2, 8), var(1, 8), var(8, 4),
                var(4, 2), var(4, 3), var(4, 6),
                var(4, 8, "nocompute"), var(4, 8, "noconvert")]
    mods = []
    for i, path in enumerate(kernel_files):
        spec = importlib.util.spec_from_file_location(f"pa_other{i}", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        mods.append((path, other))
    mods.append(("this", PA))
    rows, outs = [], {}
    for tree, mod in mods:
        for name, kw_static in variants:
            ms, first = timed(mod, **kw_static)
            outs[tree, name] = first
            ppb = kw_static["pages_per_block"]
            # a tree from before PR 32 has no counter and copied in whole
            # blocks: every item `ppb` pages
            streamed = (
                mod.streamed_pages(lengths, page, ppb)
                if hasattr(mod, "streamed_pages")
                else PA.streamed_pages(lengths, page * ppb, 1) * ppb)
            rows.append({
                "tree": tree, "variant": name, "calls": layers, "ms": ms,
                "pages_streamed": streamed, "pages_held": held,
                "roofline_pct": 100 * need_bytes * layers / 819e9 / (ms / 1e3),
            })
            print(json.dumps(rows[-1]), flush=True)
    record = {"rows": b, "tokens": int(lengths.sum()), "pages_held": held,
              "need_bytes_per_call": need_bytes, "timings": rows}
    if kernel_files:
        record["max_abs_diff"] = {
            f"{tree}:{n}": float(np.abs(outs[tree, n] - outs["this", n]).max())
            for tree in kernel_files for n, _ in variants[:5]}
        record["bit_equal"] = not any(record["max_abs_diff"].values())
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_cell_shape.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({k: v for k, v in record.items() if k != "timings"}))


def main() -> None:
    if "--cell-shape" in sys.argv:
        return cell_shape(tuple(
            sys.argv[i + 1] for i, a in enumerate(sys.argv)
            if a == "--kernel-file"))
    backend = jax.default_backend()
    record: dict = {"backend": backend, "configs": []}
    if backend != "tpu":
        # a kernel check that did not check anything must not read green
        raise SystemExit(f"kernel_check_tpu: no TPU (backend={backend})")

    rng = np.random.RandomState(0)
    page, hd, kh, h = 64, 64, 8, 32
    kw = kh * hd

    # ---- correctness: compiled kernel vs numpy oracle ----------------
    b, w = 8, 8
    num_pages = 128
    k_cache = rng.randn(num_pages * page, kw).astype(np.float32)
    v_cache = rng.randn(num_pages * page, kw).astype(np.float32)
    q = rng.randn(b, h, hd).astype(np.float32)
    tables = rng.permutation(num_pages - 1)[: b * w].reshape(b, w) + 1
    lengths = rng.randint(1, w * page, size=b).astype(np.int32)
    ref = oracle(q, k_cache, v_cache, tables, lengths, page)
    out, _, _ = jax.jit(
        lambda *a: fused_paged_decode_attention(
            *a, jnp.full((b,), -1, jnp.int32), page_size=page, alias_caches=False
        )
    )(
        jnp.asarray(q), jnp.zeros((b, kw), jnp.float32),
        jnp.zeros((b, kw), jnp.float32),
        jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables, jnp.int32), jnp.asarray(lengths),
    )
    err = float(np.abs(np.asarray(out) - ref).max())
    record["agree_max_err"] = err
    assert err < 2e-2, f"compiled kernel disagrees with oracle: {err}"
    print(f"compiled-mode agreement: max err {err:.2e}")

    # ---- int8-KV compiled agreement: quantized pools + scale tiles ----
    # (page 128: the scale-pool layout puts page tokens in lanes)
    from dynamo_tpu.ops.quant import (
        dequantize_kv_rows,
        gather_kv_scales,
        init_kv_scale_pool,
        quantize_kv_rows,
        scatter_kv_scales,
    )

    qpage = 128
    qnum_pages = 64
    qn_slots = qnum_pages * qpage
    kq, ksd = quantize_kv_rows(jnp.asarray(rng.randn(qn_slots, kw), jnp.float32), kh)
    vq, vsd = quantize_kv_rows(jnp.asarray(rng.randn(qn_slots, kw), jnp.float32), kh)
    all_slots = jnp.arange(qn_slots, dtype=jnp.int32)
    ks = scatter_kv_scales(
        init_kv_scale_pool(qnum_pages, qpage, kh), all_slots, ksd, kh)
    vs = scatter_kv_scales(
        init_kv_scale_pool(qnum_pages, qpage, kh), all_slots, vsd, kh)
    subl = ks.shape[1]
    qw = 4
    qtables = rng.permutation(qnum_pages - 1)[: b * qw].reshape(b, qw) + 1
    qlengths = rng.randint(1, qw * qpage, size=b).astype(np.int32)
    ref_q = oracle(
        q, np.asarray(dequantize_kv_rows(kq, ksd)),
        np.asarray(dequantize_kv_rows(vq, vsd)), qtables, qlengths, qpage,
    )
    out_q, *_ = jax.jit(
        lambda *a: fused_paged_decode_attention(
            *a, page_size=qpage, alias_caches=False
        )
    )(
        jnp.asarray(q),
        jnp.zeros((b, kw), jnp.int8), jnp.zeros((b, kw), jnp.int8),
        kq, vq,
        jnp.asarray(qtables, jnp.int32), jnp.asarray(qlengths),
        jnp.full((b,), -1, jnp.int32),
        ks, vs,
        jnp.ones((b, subl), jnp.float32), jnp.ones((b, subl), jnp.float32),
    )
    err_q = float(np.abs(np.asarray(out_q) - ref_q).max())
    record["agree_max_err_int8kv"] = err_q
    assert err_q < 2e-2, f"int8-KV kernel disagrees with oracle: {err_q}"
    print(f"int8-KV compiled-mode agreement: max err {err_q:.2e}")

    # ---- int32-PACKED pools: compiled kernel must be bit-identical ----
    from dynamo_tpu.ops.quant import pack_kv_slots

    out_p, *_ = jax.jit(
        lambda *a: fused_paged_decode_attention(
            *a, page_size=qpage, alias_caches=False
        )
    )(
        jnp.asarray(q),
        jnp.zeros((b, kw), jnp.int8), jnp.zeros((b, kw), jnp.int8),
        pack_kv_slots(kq), pack_kv_slots(vq),
        jnp.asarray(qtables, jnp.int32), jnp.asarray(qlengths),
        jnp.full((b,), -1, jnp.int32),
        ks, vs,
        jnp.ones((b, subl), jnp.float32), jnp.ones((b, subl), jnp.float32),
    )
    err_p = float(np.abs(np.asarray(out_p) - np.asarray(out_q)).max())
    record["packed_vs_dense_max_err"] = err_p
    assert err_p == 0.0, f"packed kernel differs from dense-int8: {err_p}"
    print(f"packed-pool compiled-mode agreement: bit-identical to dense")
    del kq, vq, ks, vs

    # ---- bandwidth: engine-shaped 16-layer decode scan, attention cost
    # isolated by ablation (fused-full minus attention-knocked-out) —
    # (standalone single-kernel timing is dominated by the fixed cost
    # of one dispatch)
    import dynamo_tpu.ops.attention as A
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.ops.sampling import sample_tokens

    cfg = get_config("llama-3.2-1b")
    dtype = jnp.bfloat16
    steps_n = 16
    kv_len = 480

    def time_scan(b, with_attn, quant=False, kv_quant=False, packed=False):
        # int8-KV scale pages put tokens in lanes -> page must be a lane
        # multiple; bf16 runs keep the serving default
        pg = 128 if kv_quant else page
        w_pages = -(-(kv_len + steps_n + pg) // pg)
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
                    params, cfg, tokens[:, None], positions[:, None],
                    kv, wslots, spec,
                )
                lg = llama.logits(params, cfg, hidden[:, 0])
                toks = sample_tokens(lg, sub, temp, topk, topp, all_greedy=True)
                return (toks, positions + 1, kv, key), toks

            (_, _, kv, _), out = jax.lax.scan(
                body, (tokens, positions, kv, key), None, length=steps_n)
            return out, kv

        params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
        if quant:
            from dynamo_tpu.ops.quant import quantize_params

            params = quantize_params(params, cfg)
        kv = jax.device_put(llama.init_kv_cache(
            cfg, num_slots, dtype=dtype,
            kv_quant="int8" if kv_quant else None, page_size=pg,
            packed=packed,
        ))
        tokens = jnp.ones((b,), jnp.int32)
        positions = jnp.full((b,), kv_len, jnp.int32)
        key = jax.random.PRNGKey(0)
        real = (A.write_kv_slots, llama.write_kv_slots,
                llama.fused_paged_decode_attention
                if hasattr(llama, "fused_paged_decode_attention") else None)
        try:
            if not with_attn:
                import dynamo_tpu.ops.pallas_attention as PA

                real_fused = PA.fused_paged_decode_attention
                PA_fake = lambda q, nk, nv, kc, vc, *a, **kw: (q, kc, vc)
                PA.fused_paged_decode_attention = PA_fake
            f = jax.jit(multi, donate_argnums=(1,))
            out, kv = f(params, kv, tokens, positions, key)
            _ = np.asarray(out[-1, :1])
            t0 = time.perf_counter()
            n = 6
            for _ in range(n):
                out, kv = f(params, kv, tokens, positions, key)
            _ = np.asarray(out[-1, :1])
            return (time.perf_counter() - t0) / n / steps_n
        finally:
            if not with_attn:
                PA.fused_paged_decode_attention = real_fused
            del params, kv

    for b in (64, 128, 256):
        full = time_scan(b, with_attn=True)
        no_attn = time_scan(b, with_attn=False)
        full_q = time_scan(b, with_attn=True, quant=True)
        full_qq = time_scan(
            b, with_attn=True, quant=True, kv_quant=True, packed=True
        )
        attn_ms = (full - no_attn) * 1e3
        kv_bytes = b * kv_len * kw * 2 * 2 * cfg.num_layers  # K+V bf16, 16 L
        gbps = kv_bytes / max(full - no_attn, 1e-9) / 1e9
        record["configs"].append(
            {
                "B": b, "kv_len": kv_len, "page": page,
                "full_ms_per_step": round(full * 1e3, 3),
                "attn_ms_per_step": round(attn_ms, 3),
                "attn_GBps": round(gbps, 1),
                "decode_toks_per_s": round(b / full, 0),
                # int8 W8A8 weights (ops/quant.py), attention still bf16
                "full_ms_per_step_int8": round(full_q * 1e3, 3),
                "decode_toks_per_s_int8": round(b / full_q, 0),
                # int8 weights + int8 KV pages (the full quantized stack)
                "full_ms_per_step_int8kv": round(full_qq * 1e3, 3),
                "decode_toks_per_s_int8kv": round(b / full_qq, 0),
            }
        )
        print(f"B={b}: full {full * 1e3:.2f} ms/step, attention "
              f"{attn_ms:.2f} ms -> {gbps:.0f} GB/s, {b / full:.0f} tok/s; "
              f"int8 {full_q * 1e3:.2f} ms -> {b / full_q:.0f} tok/s; "
              f"int8+int8kv {full_qq * 1e3:.2f} ms -> {b / full_qq:.0f} tok/s")

    # ---- flash prefill kernel: compiled agreement + chunk-batch rate --
    from dynamo_tpu.ops.attention import slots_from_pages
    from dynamo_tpu.ops.pallas_prefill import flash_prefill_attention

    b, t_len, w = 8, 512, 10
    num_pages = b * w + 2
    kcf = rng.randn(num_pages * page, kw).astype(np.float32)
    vcf = rng.randn(num_pages * page, kw).astype(np.float32)
    qf3 = rng.randn(b, t_len, h, hd).astype(np.float32)
    tablesf = np.stack(
        [np.arange(1 + i * w, 1 + (i + 1) * w) for i in range(b)]
    ).astype(np.int32)
    pos0 = np.zeros(b, np.int32)
    tlen = np.full(b, t_len, np.int32)
    outf = flash_prefill_attention(
        jnp.asarray(qf3), jnp.asarray(kcf), jnp.asarray(vcf),
        jnp.asarray(tablesf), jnp.asarray(pos0), jnp.asarray(tlen),
        page_size=page,
    )
    from dynamo_tpu.ops.attention import paged_attention

    smat = np.asarray(slots_from_pages(jnp.asarray(tablesf), page))
    reff = np.asarray(paged_attention(
        jnp.asarray(qf3), jnp.asarray(kcf), jnp.asarray(vcf),
        jnp.asarray(smat),
        jnp.asarray(np.tile(np.arange(t_len), (b, 1)), jnp.int32),
    ))
    perr = float(np.abs(np.asarray(outf) - reff).max())
    record["prefill_agree_max_err"] = perr
    assert perr < 2e-2, f"flash prefill disagrees: {perr}"
    print(f"flash prefill compiled-mode agreement: max err {perr:.2e}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "KERNEL_TPU.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
