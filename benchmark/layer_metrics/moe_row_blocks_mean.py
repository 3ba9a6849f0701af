"""Model step: blocks of sorted (token, expert) pairs an expert layer's
pass ran in a decode step, mean over the expert layers, the steps of a
dispatch and the window's dispatches (digest column `moe_row_blocks`,
counted on the device inside the decode program and fetched with the
tokens). A layer that holds a share of the experts works its held pairs a
block of `models/moe.py: block_rows` rows at a time and stops after the
last block that holds one: 1.00 says every pass ended after its first
block (the glue moved one block's rows, not every pair the router made);
more says the held load ran past twice its even share
(`moe_pairs_held` beside it). A count. Left out where the digests carry
no such column."""


def read(art):
    ran = [d["moe_row_blocks"] for d in art["digests"]
           if d.get("moe_row_blocks")]
    return sum(ran) / len(ran) if ran else None
