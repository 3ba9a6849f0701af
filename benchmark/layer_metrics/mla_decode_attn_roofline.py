"""Kernels (ops/pallas_mla.py): the paged latent decode kernel's share of
its HBM roofline. Needed bytes per decode step = mean resident context
tokens of the requests decoding during the traced slice (the request log)
x one latent row a layer (lib/shapes_mla.py: 576 bf16 values, read ONCE:
the row is key and value). Least time = bytes / peak HBM bandwidth
(lib/peaks.json). Kernel time per step = the share of the decode program's
self time under `attn.mla_kernel` x its median execution / `decode_steps`
(as `decode_mlp_ms`; never op time over executions). Bandwidth-bound: ~30
flops a byte. Left out where the program has no such scope."""
import shapes_mla
import trace_host


def read(art):
    if not art.get("trace") or not art.get("peaks"):
        return None
    kernel_ms = shapes_mla.step_scope_ms(
        art, trace_host.scopes(art), "attn.mla_kernel")
    resident = shapes_mla.resident_tokens(art) if kernel_ms else 0.0
    if not resident:
        return None
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    need = shapes_mla.decode_latent_bytes(hf, resident)
    return need / art["peaks"]["hbm_bytes_per_s"] / (kernel_ms / 1e3) * 100.0
