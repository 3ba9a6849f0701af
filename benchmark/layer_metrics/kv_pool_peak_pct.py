"""KV manager: peak share of the KV pool's pages in use over the window,
from the flight recorder's digests."""


def read(art):
    fr = [d["kv_frac"] for d in art["digests"]]
    return max(fr) * 100.0 if fr else None
