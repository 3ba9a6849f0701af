"""Set-up: process start to the `compile_stats()` snapshot after the engine
build (`at_s`, `time.monotonic`; `lib/host_clock.py`): imports, the model
directory, weights, pools, the HTTP service, the load generator's start.
`setup_build_s` + `setup_probe_s` + `setup_warm_s` = `setup_s`. Left out
where the snapshots lack the stamp (a program from before PR 38)."""


def read(art):
    import host_clock

    marks = host_clock.setup_marks(art)
    return None if marks is None else marks[1] - marks[0]
