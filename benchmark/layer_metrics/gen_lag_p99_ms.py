"""Load generator: how late it sent a request, launch - due, 99th
percentile over requests due in the window (benchmark's clock). A
starved generator must not read as a fast server."""


def read(art):
    import e2e

    lags = [r["launched"] - r["due"] for r in art["requests"]
            if r.get("in_window") and "launched" in r]
    return e2e.percentile(lags, 99) * 1e3 if lags else None
