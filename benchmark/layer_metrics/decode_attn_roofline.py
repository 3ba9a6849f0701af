"""Kernels (ops/pallas_attention.py): the paged decode-attention kernel's
share of its HBM roofline. Needed bytes per decode step = mean resident
context tokens of the requests that were decoding during the traced slice
(from the benchmark's own request log: prompt + tokens streamed so far) x
KV bytes per token (lib/shapes.py, from the configuration's shapes). Least
time = bytes / peak HBM bandwidth (lib/peaks.json). Kernel time per step =
self time of `fused_paged_decode_attention*` inside the decode program /
(executions x decode_steps). Bandwidth-bound: the kernel reads every
resident K and V once per step and does 2 flops per byte."""


def read(art):
    import shapes
    import trace_reduce

    t = art["trace"]
    if not t or not art.get("peaks"):
        return None
    prog = t["programs"].get("jit__decode_multi")
    ops = t["ops_in_program"].get("jit__decode_multi")
    if not prog or not ops:
        return None
    kernel_s = sum(v["self_s"] for name, v in ops.items()
                   if trace_reduce.op_family(name).startswith(
                       "fused_paged_decode_attention"))
    steps = prog["count"] * art["engine"]["decode_steps"]
    if not kernel_s or not steps:
        return None
    lo, hi = t["slice"]
    # resident tokens of the live requests, averaged over the slice
    marks = [lo + (hi - lo) * (i + 0.5) / 16 for i in range(16)]
    resident = 0.0
    for r in art["requests"]:
        if "t_first" not in r or r["tokens"] < 2:
            continue
        for m in marks:
            if r["t_first"] <= m <= r["t_last"]:
                done = (m - r["t_first"]) / (r["t_last"] - r["t_first"])
                resident += (r["prompt_tokens"] + done * r["tokens"]) / 16
    if not resident:
        return None
    flags = art["config"]["benchmark"]["engine_flags"]
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    need = shapes.decode_attention_bytes(
        hf, shapes.flag(flags, "--kv-quantization"), resident)
    least_s = need / art["peaks"]["hbm_bytes_per_s"]
    return least_s / (kernel_s / steps) * 100.0
