"""Engine loop (a model generated by diffusion over blocks): tokens a row a
pass over the window: masked positions the landed passes filled over rows x
passes landed, the commit passes among them (digest columns `dlm_filled`,
`dlm_row_passes` of the sync rows). `block_length / (denoising_steps + 1)`
when every row is in step (4/3 at a block of 4 in 2 steps); lower when
rows join on a partial block or end inside one. Left out where the digests
carry no such column."""


def read(art):
    rows = sum(d.get("dlm_row_passes") or 0 for d in art["digests"])
    filled = sum(d.get("dlm_filled") or 0 for d in art["digests"])
    return filled / rows if rows else None
