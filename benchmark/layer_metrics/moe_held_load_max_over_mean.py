"""Engine loop: imbalance of the routed experts a chip HOLDS in a decode
step: the most tokens on one held expert (digest column `moe_load_max`,
mean over expert layers and steps) over the mean load of an expert of the
PUBLISHED count (decoding rows x `num_experts_per_tok` / the router's
width: the file's `n_routed_experts` is the share held, not the count the
router spreads tokens over). Left out where the configuration states no
router width apart from the experts held, or the digests no load."""


def read(art):
    loads = [d["moe_load_max"] for d in art["digests"]
             if d.get("moe_load_max")]
    rows = [d["rows"] for d in art["digests"] if d["kind"] == "decode"]
    width = art["config"].get("router_width")
    if not loads or not rows or not width:
        return None
    mean = sum(rows) / len(rows) * art["config"]["num_experts_per_tok"] / width
    return sum(loads) / len(loads) / mean
