"""Device: share of the traced slice in which the chip was idle between programs while the engine was admitting, building a dispatch's inputs or landing tokens (eng.admit, eng.*.build, eng.emit) and no worker was enqueueing.
One of five shares that sum to `device_idle_pct`; the rule is at the top
of lib/trace_host.py. Left out where the program writes no `eng.` phase."""
import trace_host


def read(art):
    return trace_host.idle_pct(art, "host")
