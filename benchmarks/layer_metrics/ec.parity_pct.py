"""erasure coding: parity bytes the nodes encoded in the window
(``/metrics`` ``ec.parityBytes``: P and Q of every stripe, before
placement's dedup) as a share of the user bytes acked in it. k data
shards and two of parity a stripe is 2/k, 66.7 % at k = 3, plus what
padding a stripe to its longest chunk adds. Uploads astride an end of
the window are encoded on one side and acked on the other: a few per
cent either way. Nothing on a program without the counter."""

from program_totals import share_pct


def read(w):
    if not any("parityBytes" in n.get("ec", {}) for n in w.nodes_after):
        return None
    return share_pct(w.node_delta("ec", "parityBytes"),
                     w.acked_bytes("put"))
