"""Device: share of the traced slice in which the chip was idle while a program was executing on the device: gaps between its own operations, which no change to the host can close.
One of five shares that sum to `device_idle_pct`; the rule is at the top
of lib/trace_host.py. Left out where the program writes no `eng.` phase."""
import trace_host


def read(art):
    return trace_host.idle_pct(art, "in_programs")
