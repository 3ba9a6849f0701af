"""Engine loop: distinct routed experts that at least one token reached in
a decode step, mean over the expert layers, the steps of a dispatch and
the window's dispatches (digest column `moe_experts_hit`, computed on the
device inside the decode program and fetched with the tokens). It sets the
expert bytes a step must stream (`decode_step_roofline`). Left out where
the digests carry no such column or no expert load."""


def read(art):
    hits = [d["moe_experts_hit"] for d in art["digests"]
            if d.get("moe_experts_hit")]
    return sum(hits) / len(hits) if hits else None
