"""Model step: the decode step's share of its HBM roofline, for a model
with window beside full attention whose expert layers hold a share of the
experts. Needed bytes per step (lib/shapes_hybrid.py) = the weights a step
must read (every matrix outside the routed experts, and of the experts the
chip HOLDS the `moe_experts_hit` that a token reached, from the engine's
digests) + the KV of the resident tokens (full layers: the context; window
layers: at most `sliding_window` tokens). Least time = bytes / peak HBM
bandwidth; over `decode_step_ms` (median execution of the decode program /
`decode_steps`). Left out where the digests carry no window-pool column
(a program without two kinds of pool) or no expert load."""
import shapes_hybrid


def read(art):
    t = art.get("trace")
    prog = (t or {}).get("programs", {}).get("jit__decode_multi")
    hits = [d["moe_experts_hit"] for d in art["digests"]
            if d.get("moe_experts_hit")]
    windowed = any(d.get("kv_win_pages_held") for d in art["digests"])
    if not prog or not hits or not windowed or not art.get("peaks"):
        return None
    full, win = shapes_hybrid.resident(art)
    if not full:
        return None
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    need = (shapes_hybrid.decode_weight_bytes(hf, sum(hits) / len(hits))
            + shapes_hybrid.decode_kv_bytes(hf, full, win))
    step_s = prog["median_s"] / art["engine"]["decode_steps"]
    return need / art["peaks"]["hbm_bytes_per_s"] / step_s * 100.0
