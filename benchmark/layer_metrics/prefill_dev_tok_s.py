"""Model step: prompt tokens per second of DEVICE time in prefill — tokens
the engine dispatched to prefill during the traced slice (flight-recorder
digests) over the device time of the prefill program's executions in the
slice (`jit__model_step`; it also serves mixed steps). The two ends of the
slice can differ by one dispatch (a few percent)."""


def read(art):
    t = art["trace"]
    prog = (t or {}).get("programs", {}).get("jit__model_step")
    if not prog or not prog["total_s"]:
        return None
    lo, hi = t["slice"]
    tokens = sum(d["tokens"] for d in art["digests"]
                 if d["kind"] in ("prefill", "mixed") and lo <= d["t"] < hi)
    return tokens / prog["total_s"] if tokens else None
