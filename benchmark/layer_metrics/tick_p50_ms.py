"""Engine loop: the median tick inside the window, in ms: end of one
landing to the end of the next on the loop's thread (`tick_s` on the `sync`
/ `overlap` flight digests; `lib/host_clock.py`). In a closed-loop cell
`decode_rows_mean` x `decode_steps` / this is `out_tok_s`. Left out where
the digests lack the column (a program from before PR 38)."""


def read(art):
    import statistics

    import host_clock

    return host_clock.tick_ms(art, statistics.median)
