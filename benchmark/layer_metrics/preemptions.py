"""KV manager: sequences the engine preempted for want of KV pages inside
the window (the engine logs each; it keeps no counter)."""


def read(art):
    return art["preemptions"]
