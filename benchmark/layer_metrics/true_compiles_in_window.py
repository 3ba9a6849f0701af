"""Engine: programs the XLA compiler really built between window open
and close (`telemetry.compile_stats()` `backend_compiles`: compile events
less those served from the persistent cache). `compiles_in_window`
counts both; this one is at most that. A proven cell reads 0."""


def read(art):
    c = art["compile"]
    if "backend_compiles" not in c["after"]:
        return None
    return c["after"]["backend_compiles"] - c["before"]["backend_compiles"]
