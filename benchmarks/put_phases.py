"""What PR 38's clocks summed, for the per-layer readers that read them:
the chunk store's put-job phase clock (``/metrics`` ``durability.put``,
``ChunkStore.put_stats``), the CAS pool's lanes (``ingest.cas.lanes``,
``AsyncChunkStore.stats``) and the owner's compile clock (``Health``
``compile``).

Every function returns None where the program serves no such table — a
program older than the clocks, as the parent of PR 38 is — so a reader
built on it leaves its metric out of the line and never raises.
"""

from __future__ import annotations

# the ten phases of a put job; they add up to its ``jobS``
PHASES = ("precheckS", "settleS", "createS", "writeS", "payloadFsyncS",
          "linkWaitS", "linkS", "dirBarrierS", "unlinkS", "flushS")


def put_delta(w, *keys: str) -> float | None:
    """Growth over the window of the sum of these ``durability.put``
    keys, summed over the nodes (seconds of the nodes' write workers,
    or counts)."""
    if not any(isinstance(n.get("durability", {}).get("put"), dict)
               for n in w.nodes_after):
        return None
    return sum(w.node_delta("durability", "put", k) for k in keys)


def lane_delta(w, lane: str, key: str) -> float | None:
    """Growth over the window of ``ingest.cas.lanes[lane][key]``, summed
    over the nodes: ``w`` the write pool, ``r`` the batch reads, ``g``
    the 2-worker latency lane."""
    if not any(isinstance(n.get("ingest", {}).get("cas", {}).get("lanes"),
                          dict) for n in w.nodes_after):
        return None
    return w.node_delta("ingest", "cas", "lanes", lane, key)


def compile_table(w) -> dict | None:
    """The owner's ``Health.compile`` when the window closed: sums since
    the owner started (the compiling is before the window, so not a
    delta)."""
    table = w.owner_after.get("compile")
    return table if isinstance(table, dict) else None
