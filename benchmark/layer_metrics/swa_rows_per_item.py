"""Kernels: decode rows a work item of a window layer's decode kernel
holds, over the window's decode dispatches (digest columns `tokens` = rows
x steps, and `kv_win_items` = one window layer's work items over the same
steps). 1 where every sequence is an item of its own (four serial MXU
fill / drains a sequence); near the kernel's group size where an item
holds several sequences' windows, lower when the batch is sparse (the
last item of a step is partial). A count. Left out where the digests
carry no such column."""


def read(art):
    rows = [d for d in art["digests"]
            if d["kind"] == "decode" and d.get("kv_win_items")]
    if not rows:
        return None
    return (sum(d["tokens"] for d in rows)
            / sum(d["kv_win_items"] for d in rows))
