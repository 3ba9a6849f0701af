"""Model step: device time of one decode step — the median duration of the
decode program's executions in the traced slice (`jit__decode_multi`, one
`lax.scan` of `decode_steps` steps) divided by `decode_steps`."""


def read(art):
    t = art["trace"]
    prog = (t or {}).get("programs", {}).get("jit__decode_multi")
    if not prog:
        return None
    return prog["median_s"] / art["engine"]["decode_steps"] * 1e3
