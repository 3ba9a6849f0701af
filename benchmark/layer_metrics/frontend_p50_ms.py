"""HTTP frontend + preprocessor + detokenizer: median over requests due
in the window of (client TTFT from launch) - (engine TTFT from submit,
finish summary joined by x-request-id). Host clocks on both sides."""


def read(art):
    import e2e

    gaps = []
    for r in art["requests"]:
        s = art["summaries"].get(r["id"])
        if (r.get("in_window") and "t_first" in r and s
                and s.get("ttft_s") is not None):
            gaps.append(r["t_first"] - r["launched"] - s["ttft_s"])
    return e2e.percentile(gaps, 50) * 1e3 if gaps else None
