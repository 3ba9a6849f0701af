"""Set-up: the `compile_stats()` snapshot after the probes to the window's
opening (`at_s`, `time.monotonic`; `lib/host_clock.py`): the rest of the
reference's forward passes, the ramp and the traffic's own warm-up.
`setup_build_s` + `setup_probe_s` + `setup_warm_s` = `setup_s`. Left out
where the snapshots lack the stamp (a program from before PR 38)."""


def read(art):
    import host_clock

    marks = host_clock.setup_marks(art)
    return None if marks is None else marks[3] - marks[2]
