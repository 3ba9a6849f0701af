"""Engine loop: in the window's longest tick, the larger of a dispatch
worker's `lock_s + upload_s + enqueue_s` (the dispatch rows booked since
the landing before) and the loop's `join_s` awaiting that worker, less its
own median over the window's ticks, in ms (`lib/host_clock.py`). Says which
thread and phase a stop sat in; threads overlap, so the four
`tick_max_*_ms` do not sum to the tick, and a steady run reads ~0 in all.
Left out where the digests lack the columns (a program from before PR 38)."""


def read(art):
    import host_clock

    return host_clock.longest_excess_ms(art, "dispatch")
