"""Model step (a model generated by diffusion over blocks): device time of
one pass under `head` and `sample`: the head over rows x block_length
token rows of the whole vocabulary, the sampling a position, the
confidence and the transfer: the block program's self time under those
scopes in the traced slice over the passes the slice holds. A pipeline stage that holds
6 of 48 layers holds the whole head: its share of a pass is 8x a
deployment's. Left out where the traced program carries no such scope."""
import shapes_dlm


def read(art):
    s = shapes_dlm.scope_pass_seconds(art, ("head", "sample"))
    return s * 1e3 if s else None
