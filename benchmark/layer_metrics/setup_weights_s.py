"""Set-up: the engine making or loading, quantizing and placing the
weights, until the leaves are ready (the seconds of the `eng.init.weights`
phase in `phase_s` of the `compile_stats()` snapshot at the window's
opening). Left out where the snapshot lacks the key (a program from before
PR 38)."""


def read(art):
    return art["compile"]["before"].get("phase_s", {}).get("eng.init.weights")
