"""End-to-end metric `setup_s`: process start to the window opening —
model directory, engine build (weights, KV pool), probes that compile or
read back every program, `correct`, and the traffic's warm-up stretch."""


def read(art):
    return art["setup_s"]
