"""Client, whole path: time from when a request was DUE to its first
streamed token, median over the requests due in the window (lib/e2e.py).
What a chat user feels first. Recorded per layer and not bounded: the
~140 requests of a window leave its run-to-run spread at 6% (median) and
27% (95th percentile) of identical work (PERF.md, PR 23)."""
import e2e


def read(art):
    return e2e.metrics(art)["ttft_p50_ms"]
