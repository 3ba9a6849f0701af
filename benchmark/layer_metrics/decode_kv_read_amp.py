"""Kernels: KV pages the paged decode kernel copied in over the window's
decode dispatches / KV pages their rows held (`kv_pages_streamed` /
`kv_pages_held` on the flight-recorder digests, both per layer over the
dispatch's steps). 1 = the kernel reads only what a sequence holds; a
page size or block that makes a work item reach past a sequence's end
reads above 1. A count, not a time: `decode_attn_roofline` counts the
TOKENS' bytes, this says how many more the kernel moves."""


def read(art):
    rows = [d for d in art["digests"]
            if d["kind"] == "decode" and d.get("kv_pages_held")]
    if not rows:
        return None
    return (sum(d["kv_pages_streamed"] for d in rows)
            / sum(d["kv_pages_held"] for d in rows))
