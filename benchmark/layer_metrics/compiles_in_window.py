"""Engine: XLA backend compiles between window open and close
(telemetry.compile_stats). A proven cell reads 0."""


def read(art):
    c = art["compile"]
    return c["after"]["compile_events"] - c["before"]["compile_events"]
