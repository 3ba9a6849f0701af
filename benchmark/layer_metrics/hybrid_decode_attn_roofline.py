"""Kernels (ops/pallas_attention.py, both kinds of layer of a model with
window beside full attention): the paged decode kernel's share of its HBM
roofline. Needed bytes per decode step (lib/shapes_hybrid.py) = over the
requests decoding during the traced slice, context x 2,560 B x the
full-attention layers + min(context, sliding_window) x 5,120 B x the window
layers. Least time = bytes / peak HBM bandwidth (lib/peaks.json). Kernel
time per step = the share of the decode program's self time under
`attn.kernel` (full layers) and `attn.swa_kernel` (window layers) x its
median execution / `decode_steps`, as `mla_decode_attn_roofline` reads its
scope. Left out where the program has no `attn.swa_kernel` scope."""
import shapes_hybrid
import shapes_mla
import trace_host


def read(art):
    if not art.get("trace") or not art.get("peaks"):
        return None
    scopes = trace_host.scopes(art)
    swa_ms = shapes_mla.step_scope_ms(art, scopes, "attn.swa_kernel")
    full_ms = shapes_mla.step_scope_ms(art, scopes, "attn.kernel")
    if not swa_ms or not full_ms:
        return None
    full, win = shapes_hybrid.resident(art)
    if not full:
        return None
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    need = shapes_hybrid.decode_kv_bytes(hf, full, win)
    return (need / art["peaks"]["hbm_bytes_per_s"]
            / ((swa_ms + full_ms) / 1e3) * 100.0)
