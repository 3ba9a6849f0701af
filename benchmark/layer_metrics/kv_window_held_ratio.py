"""KV manager: window-layer pages held / full-layer pages held by the same
decode rows, over the window's decode dispatches (digest columns
`kv_win_pages_held` / `kv_pages_held_full`, both per layer of their kind
over the dispatch's steps). A window layer keeps the pages its window and
the tokens in flight touch and releases the rest: ~0.2 at contexts of a
thousand tokens over pages of 128, 1.0 if nothing were released. A count.
Left out where the digests carry no such columns."""


def read(art):
    rows = [d for d in art["digests"] if d["kind"] == "decode"
            and d.get("kv_win_pages_held") and d.get("kv_pages_held_full")]
    if not rows:
        return None
    return (sum(d["kv_win_pages_held"] for d in rows)
            / sum(d["kv_pages_held_full"] for d in rows))
