"""Set-up: the wall of reading programs back from the persistent compile
cache, from process start to the window's opening (`cache_read_s` of that
`compile_stats()` snapshot; the key is older than PR 38, so a program from
before it reports this one too)."""


def read(art):
    import host_clock

    return host_clock.setup_stat(art, "cache_read_s")
