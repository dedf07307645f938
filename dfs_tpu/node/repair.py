"""The repair cycle's pass over this node's manifests.

Every cycle (``StorageNodeServer.repair_once``) asks three questions of
every chunk row of every manifest this node holds: which canonical
copies does THIS node lack (``own_missing``), which digests should each
PEER hold (``need`` — the lists the cycle then probes and pushes), and
which local copies are not canonical here (``stray`` — relocation
candidates). The answers are a pure function of the manifests, the
local chunk listing and the ring maps, so the whole pass runs in a
worker thread (:func:`walk`), and a manifest is read and parsed ONCE
for as long as its file does not change (:class:`ManifestMemo`): a
cycle that finds nothing new costs a ``stat`` a manifest, not a parse.

The aged orphan sweep at the end of the cycle re-uses the pass — its
listing as candidates, its rows as the first live set — and looks again
for manifests saved since, before anything is deleted
(:meth:`ManifestMemo.named_since`).
"""

from __future__ import annotations

import dataclasses
import os
from array import array
from typing import Iterator

from dfs_tpu.meta.manifest import ChunkRef, EcInfo, Manifest
from dfs_tpu.node.placement import ec_placement_map, ec_shard_items
from dfs_tpu.store.cas import NodeStore

# chunk rows remembered between cycles, over all manifests: 40 bytes a
# row (a raw digest and a length) -> 80 MiB at the worst. The source's
# deployment holds ~870 000 digests a node (PERF.md §4), a 1 GiB object
# is 131 072 rows: two million rows are sixteen such objects' manifests
# or some 1 200 of the cells' 16 MiB ones. A manifest past the bound is
# read every cycle, as every manifest was before.
_REMEMBER_ROWS_MAX = 1 << 21

Stamp = tuple[int, int, int]      # the manifest file's mtime_ns, size, inode


class Rows:
    """One manifest in the form the pass needs: raw digests back to
    back, their lengths, and the erasure layout where there is one."""

    __slots__ = ("stamp", "file_id", "digests", "lengths", "ec")

    def __init__(self, stamp: Stamp, m: Manifest) -> None:
        self.stamp = stamp
        self.file_id = m.file_id
        self.digests = bytes.fromhex("".join(c.digest for c in m.chunks))
        if len(self.digests) != 32 * len(m.chunks):
            raise ValueError(f"manifest {m.file_id[:12]} names a chunk "
                             "that is no sha256")
        self.lengths = array("Q", (c.length for c in m.chunks))
        self.ec: EcInfo | None = m.ec

    def __len__(self) -> int:
        return len(self.lengths)

    def items(self) -> Iterator[tuple[bytes, int]]:
        blob = self.digests
        return ((blob[32 * i:32 * i + 32], ln)
                for i, ln in enumerate(self.lengths))

    def parity(self) -> Iterator[str]:
        for st in self.ec.stripes if self.ec is not None else ():
            yield st.p
            yield st.q

    def manifest(self) -> Manifest:
        """What ``ec_placement_map`` / ``ec_shard_items`` take: the
        chunk table and the erasure layout (name and engine are not
        remembered, and neither function reads them)."""
        chunks = []
        offset = 0
        for i, (d, ln) in enumerate(self.items()):
            chunks.append(ChunkRef(index=i, offset=offset, length=ln,
                                   digest=d.hex()))
            offset += ln
        return Manifest(file_id=self.file_id, name="", size=offset,
                        fragmenter="", chunks=tuple(chunks), ec=self.ec)


class ManifestMemo:
    """Manifests remembered between repair cycles, keyed by file id and
    by the manifest file's ``(mtime_ns, size, inode)``: a file rewritten
    in place (tier demotion writes ``ec`` into one; a re-upload after a
    delete) is read again, one that is gone is dropped. Touched by one
    thread at a time — the cycle's, under the node's repair lock."""

    def __init__(self, store: NodeStore) -> None:
        self._manifests = store.manifests
        self._root = os.fspath(store.manifests.root)
        self._kept: dict[str, Rows] = {}
        self._kept_rows = 0
        self.read = 0                  # manifests read and parsed, ever

    @property
    def remembered(self) -> int:
        return len(self._kept)

    def rows(self, unless: dict[str, Stamp] | None = None
             ) -> Iterator[tuple[str, Rows]]:
        """``(file id, rows)`` of every manifest on the disk now, in id
        order — remembered where the file is the one that was read,
        read and parsed otherwise (a corrupt one is skipped, as
        ``ManifestStore.list`` skips it). With ``unless``, only the
        manifests whose file is not the one ``unless`` saw."""
        present = set()
        for fid in self._manifests.ids():
            path = f"{self._root}/{fid}.json"
            try:
                st = os.stat(path)
            except FileNotFoundError:
                continue                       # deleted since the listing
            present.add(fid)
            stamp = (st.st_mtime_ns, st.st_size, st.st_ino)
            if unless is not None and unless.get(fid) == stamp:
                continue
            rows = self._kept.get(fid)
            if rows is None or rows.stamp != stamp:
                rows = self._read(fid, path)
            if rows is not None:
                yield fid, rows
        for fid in self._kept.keys() - present:
            self._drop(fid)

    def _drop(self, fid: str) -> None:
        self._kept_rows -= len(self._kept.pop(fid))

    def _read(self, fid: str, path: str) -> Rows | None:
        if fid in self._kept:
            self._drop(fid)
        try:
            with open(path, "rb") as f:
                # the stamp of the very file the bytes come from
                st = os.fstat(f.fileno())
                raw = f.read()
        except FileNotFoundError:
            return None
        self.read += 1
        try:
            rows = Rows((st.st_mtime_ns, st.st_size, st.st_ino),
                        Manifest.from_json(raw))
        except (ValueError, KeyError):
            return None
        if self._kept_rows + len(rows) <= _REMEMBER_ROWS_MAX:
            self._kept[fid] = rows
            self._kept_rows += len(rows)
        return rows

    def named_since(self, seen: dict[str, Stamp]) -> set[str]:
        """Every digest (parity too) named by a manifest that ``seen``
        — a pass's ``Walk.seen`` — did not read: saved or rewritten
        since. What the orphan sweep asks just before it deletes."""
        live: set[str] = set()
        for _, rows in self.rows(unless=seen):
            live.update(d.hex() for d, _ in rows.items())
            live.update(rows.parity())
        return live


@dataclasses.dataclass
class Walk:
    """One pass's answers (``repair_once`` reads them on the loop)."""

    # peer -> (digest, length) it should hold by the current map
    need: dict[int, list[tuple[str, int]]]
    # every digest any manifest names (parity too) -> its length: the
    # orphan sweep's first live set
    chunk_len: dict[str, int]
    own_missing: dict[str, int]
    # erasure-coded shards this node should hold and lacks, with their
    # manifest (the parity-decode fallback needs it whole)
    own_missing_ec: list[tuple[Manifest, list[ChunkRef]]]
    ec_digests: set[str]
    # previous-epoch holders of EC shards (designated-mover order)
    prev_ec_holders: dict[str, tuple[int, ...]]
    # local copies this node is no canonical holder of -> who is
    stray: dict[str, frozenset[int]]
    local_digests: set[str]            # the chunk listing, once
    seen: dict[str, Stamp]             # manifest -> the file that was read


def walk(store: NodeStore, memo: ManifestMemo, node_id: int, rf: int,
         cur, prev) -> Walk:
    """The pass. ``cur`` / ``prev`` are the ring's current and previous
    maps as the cycle took them, once, before it started (``prev`` None
    outside a migration). One readdir snapshot of the local catalog
    serves both the own-missing checks and the stray detection — local
    copies of chunks this node is NOT a canonical holder of
    (sloppy-quorum handoff leftovers, stale placement), candidates for
    relocation-by-deletion once every canonical holder is confirmed.
    A replicated digest met in a second manifest has the owners it had
    in the first and is walked once."""
    migrating = prev is not None
    w = Walk(need={}, chunk_len={}, own_missing={}, own_missing_ec=[],
             ec_digests=set(), prev_ec_holders={}, stray={},
             local_digests=set(store.chunks.digests()), seen={})
    local = w.local_digests
    walked: set[bytes] = set()
    for fid, rows in memo.rows():
        w.seen[fid] = rows.stamp
        if rows.ec is not None:
            # EC shards live at stripe-derived holders, one copy each; a
            # holder missing its shard regenerates it LOCALLY via parity
            # decode (the push loop only relocates surviving copies — it
            # cannot invent lost bytes)
            m = rows.manifest()
            pl = ec_placement_map(m, cur)
            pl_prev = ec_placement_map(m, prev) if migrating else {}
            miss: dict[str, int] = {}
            for d, ln in ec_shard_items(m):
                w.chunk_len[d] = ln
                w.ec_digests.add(d)
                if migrating:
                    w.prev_ec_holders.setdefault(
                        d, tuple(pl_prev.get(d, ())))
                for target in pl[d]:
                    if target != node_id:
                        w.need.setdefault(target, []).append((d, ln))
                    elif d not in local:
                        miss[d] = ln
            whole = None
            if miss:
                try:
                    whole = store.manifests.load(fid)
                # rewritten since and unreadable now: next cycle's
                except (ValueError, KeyError):
                    pass
            if whole is not None and whole.ec is not None:
                w.own_missing_ec.append(
                    (whole, [ChunkRef(index=0, offset=0, length=ln,
                                      digest=d)
                             for d, ln in miss.items()]))
            continue
        for key, ln in rows.items():
            if key in walked:
                continue
            walked.add(key)
            d = key.hex()
            w.chunk_len[d] = ln
            targets = cur.owners(d, rf)
            for target in targets:
                if target != node_id:
                    w.need.setdefault(target, []).append((d, ln))
                elif d not in local:
                    w.own_missing[d] = ln
            if node_id not in targets and d in local:
                w.stray[d] = frozenset(targets)
    return w
