"""Model step: device time of one decode step under the residual's
boundaries (scopes `attn.mhc` and `mlp.mhc`, which hold `mhc.maps`, `mhc.pre`,
`mhc.post`: models/mhc.py): the decode
program's self time under those scopes in the traced slice over the steps
the slice holds (lib/shapes_xing.py `slice_step`). Left out where the
configuration carries one stream or the program has no such scope."""
import shapes_xing


def read(art):
    got = shapes_xing.slice_step(art, shapes_xing.MHC)
    return got[0] * 1e3 if got else None
