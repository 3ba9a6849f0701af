"""Device: share of the traced slice in which no operation ran on the
chip: 1 - union of device-operation intervals / slice."""


def read(art):
    t = art["trace"]
    if not t or not t["window_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
