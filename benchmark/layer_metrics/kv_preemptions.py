"""KV manager: sequences preempted for want of KV pages inside the
window: the rise of the engine's own counter (`preempted` on the
flight-recorder digests, `preemptions_total` in `Engine.metrics()`)
between the window's first and last digest. `preemptions` counts the
same events from log lines."""


def read(art):
    rows = art["digests"]
    if not rows or "preempted" not in rows[0]:
        return None
    return int(rows[-1]["preempted"] - rows[0]["preempted"])
