"""Model step: the decode step's share of its HBM roofline, for a model
with routed experts and a latent cache. Needed bytes per step = the
weights a step must read (lib/shapes_moe.py: every matrix outside the
routed experts, and of the 64 routed experts a layer the `moe_experts_hit`
that a token reached, from the engine's digests) + the latent rows of the
resident tokens (lib/shapes_mla.py). Least time = bytes / peak HBM
bandwidth; over `decode_step_ms` (median execution of the decode program /
`decode_steps`). No share of the expert block alone: XLA streams the next
layer's experts under the attention before it, so a scope's time does not
bound its bytes. Left out where the digests carry no expert load."""
import shapes_mla
import shapes_moe


def read(art):
    t = art.get("trace")
    prog = (t or {}).get("programs", {}).get("jit__decode_multi")
    hits = [d["moe_experts_hit"] for d in art["digests"]
            if d.get("moe_experts_hit")]
    if not prog or not hits or not art.get("peaks"):
        return None
    resident = shapes_mla.resident_tokens(art)
    if not resident:
        return None
    hf = {k: v for k, v in art["config"].items() if k != "benchmark"}
    need = (shapes_moe.decode_weight_bytes(hf, sum(hits) / len(hits))
            + shapes_mla.decode_latent_bytes(hf, resident))
    step_s = prog["median_s"] / art["engine"]["decode_steps"]
    return need / art["peaks"]["hbm_bytes_per_s"] / step_s * 100.0
