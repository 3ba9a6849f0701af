"""Device: share of the traced slice in which the chip was idle between programs while a dispatch worker was in eng.lock, eng.upload or eng.enqueue: the next program was not yet queued.
One of five shares that sum to `device_idle_pct`; the rule is at the top
of lib/trace_host.py. Left out where the program writes no `eng.` phase."""
import trace_host


def read(art):
    return trace_host.idle_pct(art, "enqueue")
