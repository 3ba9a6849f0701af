"""Engine loop: admit -> first token on the host (`prefill_s` of the
finish summaries: every chunk of the prompt dispatched, computed and the
fetch that carried the sampled token landed), 95th percentile over
requests due in the window. With `queue_wait_s` and `first_emit_s` it
sums to the engine's `ttft_s`. Moves TTFT, which no bounded metric is yet
(PERF.md section 3)."""


def read(art):
    import e2e

    spans = []
    for r in art["requests"]:
        s = art["summaries"].get(r["id"])
        if r.get("in_window") and s and s.get("prefill_s") is not None:
            spans.append(s["prefill_s"])
    return e2e.percentile(spans, 95) * 1e3 if spans else None
