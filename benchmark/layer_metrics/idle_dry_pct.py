"""Device: share of the traced slice in which the chip was idle between programs while only eng.fetch or eng.wait was open: nothing was queued behind the program that ended.
One of five shares that sum to `device_idle_pct`; the rule is at the top
of lib/trace_host.py. Left out where the program writes no `eng.` phase."""
import trace_host


def read(art):
    return trace_host.idle_pct(art, "dry")
