"""Model step: the decode step's share of its HBM roofline, for a model
with low-rank queries, routed experts, a latent cache and a residual of
several streams. Needed bytes per step = the weights a step must read
(lib/shapes_xing.py: every matrix outside the routed experts, and of the
routed experts a layer the `moe_experts_hit` that a token reached, from
the engine's digests) + the latent rows of the resident tokens
(lib/shapes_mla.py) + the streams the boundaries move (lib/shapes_xing.py).
Least time = bytes / peak HBM bandwidth; over the step's device time (the
decode program's self time in the traced slice over the steps it holds).
Left out where the configuration carries one stream."""
import shapes_mla
import shapes_xing


def read(art):
    got = art.get("peaks") and shapes_xing.slice_step(art)
    hits = [d["moe_experts_hit"] for d in art["digests"]
            if d.get("moe_experts_hit")]
    resident = got and hits and shapes_mla.resident_tokens(art)
    if not resident:
        return None
    step_s, hf = got
    need = (shapes_xing.decode_weight_bytes(hf, sum(hits) / len(hits))
            + shapes_mla.decode_latent_bytes(hf, resident)
            + shapes_xing.mhc_mix_bytes(hf, shapes_xing.decode_rows(art)))
    return need / art["peaks"]["hbm_bytes_per_s"] / step_s * 100.0
