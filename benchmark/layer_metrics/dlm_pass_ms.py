"""Model step (a model generated by diffusion over blocks): device time of
one PASS of the block step: the block program's device self time in the
traced slice (`jit__dlm_multi`, one `lax.scan` of `decode_steps` passes,
each carrying a whole block a row) over the passes the slice holds, whole
executions and cut ones alike (the block attention kernel's events / the
layers: an execution lasts ~0.3 s and the slice 0.5 s, so a MEDIAN
execution is a cut one). A row takes `denoising_steps + 1` passes a block
of `block_length` tokens. Left out where the slice holds no such program."""
import shapes_dlm


def read(art):
    s = shapes_dlm.pass_seconds(art)
    return s * 1e3 if s else None
