"""Engine loop: imbalance of the routed experts in a decode step: the most
tokens on one expert (digest column `moe_load_max`, mean over expert
layers and steps) over the mean load of an expert (decoding rows x
`num_experts_per_tok` / `n_routed_experts`, rows from the decode digests).
The grouped matmul's longest group sets its tail. Left out where the
digests carry no expert load."""


def read(art):
    loads = [d["moe_load_max"] for d in art["digests"]
             if d.get("moe_load_max")]
    rows = [d["rows"] for d in art["digests"] if d["kind"] == "decode"]
    hf = art["config"]
    if not loads or not rows or not hf.get("n_routed_experts"):
        return None
    mean = (sum(rows) / len(rows) * hf["num_experts_per_tok"]
            / hf["n_routed_experts"])
    return sum(loads) / len(loads) / mean
