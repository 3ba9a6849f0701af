"""Model step: share of the device's self time in the traced slice that
carries a model scope (`attn.*`, `mlp.*`, `norm`, `head`, `sample`): how
much of the chip's work the naming covers. 0 where the traced programs
were compiled without scopes: a program from before PR 24, or a
program without a pallas kernel read from a compile cache that such a
program filled (scopes are metadata and only a kernel's serialized body
carries them into the cache's key; PERF.md, Findings, PR 24)."""
import trace_host


def read(art):
    got = trace_host.scopes(art)
    if not got:
        return None
    every = sum(s for t in got["times"].values() for s in t.values())
    named = sum(s for t in got["times"].values() for k, s in t.items() if k)
    return 100.0 * named / every if every else None
