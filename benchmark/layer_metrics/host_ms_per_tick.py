"""Engine loop: the loop thread's own work in one decode or mixed tick,
median over the window: building the dispatch (`build_s` on its digest)
+ landing the fetched tokens of the tick's sync (`emit_s`, on the `sync`
/ `overlap` digest that follows). The dispatch call's wall is left out:
behind a running program it blocks for that program's length and is no
work (PERF.md section 5). Flight-recorder digests; left out where they
lack the columns (a program from before PR 24)."""


def read(art):
    import e2e

    ticks, cur = [], None
    for d in art["digests"]:
        if "build_s" not in d:
            return None
        if d["kind"] in ("decode", "mixed"):
            if cur is not None:
                ticks.append(cur)
            cur = d["build_s"]
        elif d["kind"] in ("sync", "overlap") and cur is not None:
            ticks.append(cur + d["emit_s"])
            cur = None
    return e2e.percentile(ticks, 50) * 1e3 if ticks else None
