"""Model step (a model generated by diffusion over blocks): one pass's
share of its roofline. Least time = the LARGER of needed bytes / peak HBM
bandwidth and needed operations / peak bf16 rate (lib/peaks.json); needed
bytes = the weights a pass must read (lib/shapes_dlm.py: every matrix
outside the experts, and of the experts a layer the `moe_experts_hit` that
a token reached, from the engine's digests) + the resident tokens' keys
and values; needed operations = rows x block_length token rows through the
parameters a token uses + the attention over the resident tokens. Over the
pass's device time (`dlm_pass_ms`). Every sequence brings `block_length`
rows for one stream of the weights, so the two bounds lie close at wide
batches. Left out where the slice holds no block program."""
import shapes_dlm
import shapes_mla


def read(art):
    hf = shapes_dlm.config(art)
    pass_s = hf and art.get("peaks") and shapes_dlm.pass_seconds(art)
    hit = pass_s and shapes_dlm.experts_hit(art)
    resident = hit and shapes_mla.resident_tokens(art)
    if not resident:
        return None
    rows = shapes_dlm.rows_mean(art)
    need_bytes = (shapes_dlm.pass_weight_bytes(hf, hit)
                  + resident * shapes_dlm.kv_bytes_per_token(hf))
    least = max(need_bytes / art["peaks"]["hbm_bytes_per_s"],
                shapes_dlm.pass_ops(hf, rows, resident)
                / art["peaks"]["bf16_flops"])
    return least / pass_s * 100.0
