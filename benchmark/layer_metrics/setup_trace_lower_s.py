"""Set-up: jax's own walls of tracing functions to jaxprs and of lowering
them to MLIR, from process start to the window's opening (`trace_s` +
`lower_s` of that `compile_stats()` snapshot): host work that a warm
compile cache does not save. Left out where the snapshot lacks the keys (a
program from before PR 38)."""


def read(art):
    import host_clock

    return host_clock.setup_stat(art, "trace_s", "lower_s")
