"""index plane: how many memtables' worth of digests a node's sorted
runs hold as the window closes (``index.lsi.runEntries`` over
``memtableCap``), the least of the nodes. It says whether the cell
measured the index or its memtable: at the source's scale a node holds
thirteen memtables' worth, so nearly every positive lookup is a fenced
``pread`` of a run on disk. 0 where a node has no run yet."""


def read(w):
    ratios = []
    for node in w.nodes_after:
        lsi = (node.get("index") or {}).get("lsi") or {}
        if lsi.get("memtableCap") and "runEntries" in lsi:
            ratios.append(lsi["runEntries"] / lsi["memtableCap"])
    return min(ratios) if ratios else None
