"""Engine loop: the longest tick inside the window, in ms: the longest
time in which no decode row got a token (`tick_s` on the `sync` / `overlap`
flight digests; `lib/host_clock.py`). Left out where the digests lack the
column (a program from before PR 38)."""


def read(art):
    import host_clock

    return host_clock.tick_ms(art, max)
