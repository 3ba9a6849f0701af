"""Kernels: the residual boundaries' share of their HBM roofline. Needed
bytes per decode step = live rows (digests) x 2 x layers boundaries x
(n C read + n C written) x 2 B (lib/shapes_xing.py), the same whether XLA
or a kernel does the work. Least time = bytes / peak HBM bandwidth
(lib/peaks.json); over the step's device time under `attn.mhc` + `mlp.mhc`
(`decode_mhc_ms`). Bandwidth-bound: a row's 24 maps are a matmul of 24
columns and ~2,000 elementwise operations on 16 numbers. Left out where
the configuration carries one stream or the program has no such scope."""
import shapes_xing


def read(art):
    got = art.get("peaks") and shapes_xing.slice_step(art, shapes_xing.MHC)
    rows = got and shapes_xing.decode_rows(art)
    if not rows:
        return None
    need = shapes_xing.mhc_mix_bytes(got[1], rows)
    return need / art["peaks"]["hbm_bytes_per_s"] / got[0] * 100.0
