"""Set-up: the `compile_stats()` snapshot after the engine build to the one
after the probes (`at_s`, `time.monotonic`; `lib/host_clock.py`): the check
requests and every program shape compiled or read back. `setup_build_s` +
`setup_probe_s` + `setup_warm_s` = `setup_s`. Left out where the snapshots
lack the stamp (a program from before PR 38)."""


def read(art):
    import host_clock

    marks = host_clock.setup_marks(art)
    return None if marks is None else marks[2] - marks[1]
