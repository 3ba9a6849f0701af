"""Engine loop: seconds of the window lost to stops: over the ticks longer
than twice the median tick, what each took beyond the median (`tick_s` on
the `sync` / `overlap` flight digests; `lib/host_clock.py`). ~0 in a
steady run; a run that reads 3-10% low holds 1.2-4 s. Left out where the
digests lack the column (a program from before PR 38)."""


def read(art):
    import host_clock

    return host_clock.stall_s(art)
