"""Kernels (ops/pallas_prefill.py through `ragged_paged_attention`, a block
of queries a row under the block-causal mask): the block attention
kernel's share of its HBM roofline. Needed bytes a pass (lib/shapes_dlm.py)
= the resident tokens of the requests decoding during the traced slice x
their keys and values over all layers, plus each row's block of queries
and outputs. Least time = bytes / peak HBM bandwidth (lib/peaks.json). Time
= the KERNEL's own events under `attn.block` (not the scope's waits and
copies), summed, over the passes they ran in. Bandwidth-bound: ~16 flops a
byte at a block of 4 over 8 query heads a KV head. Left out where the
program has no such scope."""
import shapes_dlm
import shapes_mla


def read(art):
    hf = shapes_dlm.config(art)
    if not hf or not art.get("trace") or not art.get("peaks"):
        return None
    kernel_s = shapes_dlm.kernel_pass_seconds(art)
    resident = shapes_mla.resident_tokens(art) if kernel_s else 0.0
    if not resident:
        return None
    need = shapes_dlm.block_attn_bytes(hf, resident, shapes_dlm.rows_mean(art))
    return need / art["peaks"]["hbm_bytes_per_s"] / kernel_s * 100.0
