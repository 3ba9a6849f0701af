"""Model step: device time of one decode step spent under the attention block (`attn.qkv`, `attn.rope`, `attn.kv_write`, `attn.kernel`, `attn.o`):
`decode_step_ms` (median execution of `jit__decode_multi` / `decode_steps`)
times the share of that program's recorded self time whose operations
carry one of those `jax.named_scope` names in their HLO `op_name`
(lib/trace_host.py). A share, because the slice cuts the executions at
its edges. Left out where the traced programs carry no scope."""
import trace_host


def read(art):
    got = trace_host.scopes(art)
    times = (got or {"times": {}})["times"].get("jit__decode_multi", {})
    own = sum(s for scope, s in times.items() if scope.startswith("attn."))
    prog = art["trace"]["programs"].get("jit__decode_multi") if own else None
    if not prog:
        return None
    step_ms = prog["median_s"] / art["engine"]["decode_steps"] * 1e3
    return own / sum(times.values()) * step_ms
