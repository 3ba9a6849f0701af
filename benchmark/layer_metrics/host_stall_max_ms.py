"""Engine loop: the longest single landing inside the window, in ms: the
largest `emit_s` of a `sync` / `overlap` flight digest (the loop's thread
between a fetch's arrival and the end of its token loop, stop checks and
out_queue puts). A landing takes a few ms; one that takes hundreds is the
host stopped (a collector pass over the whole heap: PERF.md section 6,
PR 34), and the device drains behind it. The median hides such a stop
(`host_ms_per_tick`); this is its size. Left out where the digests lack
the column (a program from before PR 24)."""


def read(art):
    rows = [d for d in art["digests"] if d["kind"] in ("sync", "overlap")]
    if not rows or "emit_s" not in rows[0]:
        return None
    return max(d["emit_s"] for d in rows) * 1e3
