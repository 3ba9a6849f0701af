"""Engine loop: in the window's longest tick, the loop's thread in no
`eng.*` phase (`unphased_s`: the event loop elsewhere, or the process not
running), less its own median over the window's ticks, in ms
(`lib/host_clock.py`). Says which thread and phase a stop sat in; threads
overlap, so the four `tick_max_*_ms` do not sum to the tick, and a steady
run reads ~0 in all. Left out where the digests lack the columns (a
program from before PR 38)."""


def read(art):
    import host_clock

    return host_clock.longest_excess_ms(art, "unphased")
