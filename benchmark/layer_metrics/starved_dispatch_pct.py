"""Engine loop: share of the window's dispatches (prefill, decode, mixed,
spec) at whose jit call the device had already drained everything queued
before (`starved` on the flight-recorder digests: the first output of the
dispatch before was ready). Such a dispatch starts on an idle chip."""

DISPATCH = ("prefill", "decode", "mixed", "spec_verify")


def read(art):
    rows = [d for d in art["digests"] if d["kind"] in DISPATCH]
    if not rows or "starved" not in rows[0]:
        return None
    return 100.0 * sum(d["starved"] for d in rows) / len(rows)
