"""Engine loop / scheduler: submit -> admit wait from the engine's finish
summaries, 95th percentile over requests due in the window."""


def read(art):
    import e2e

    waits = []
    for r in art["requests"]:
        s = art["summaries"].get(r["id"])
        if r.get("in_window") and s and s.get("queue_wait_s") is not None:
            waits.append(s["queue_wait_s"])
    return e2e.percentile(waits, 95) * 1e3 if waits else None
