"""What decides ``correct``: the system's answers against the plain
reference (``reference.py``) and against the guarantees the cell's
configuration states, after the window has closed and every client has
stopped.

Every comparison is a count of answers that differ, with the limit 0 —
they are exact — and each is printed beside its limit. They cover what
the timed path produced at the timed sizes: every operation of the run
(ids, bodies, 404s: through the replay) and, for a seeded sample of the
objects it left, with the newest in it, a read-back through a node that
did not coordinate the upload, the replicas of every chunk on the nodes'
disks, and the 404 of deleted ids on every node. Durability is held as
far as a run can show it: every chunk file an acked upload needed had
its barrier counted by its node (``durability.fsyncs``: one per file the
chunk store made durable) before the ack, and over the whole session.

The replica count looks for each chunk as the file
``node-<i>/chunks/<2 hex>/<digest>`` under the data root (``Stores``):
the one place this file knows the store's layout (a program change that packs chunks
into larger files needs a ``benchmark`` PR to say where a replica is to
be found).
"""

from __future__ import annotations

import hashlib
import os
import sys
from dataclasses import dataclass
from pathlib import Path

import data
import reference


@dataclass
class Comparison:
    name: str
    value: float
    limit: float
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.value <= self.limit

    def line(self) -> str:
        mark = "ok " if self.ok else "BAD"
        return (f"[check] {mark} {self.name} = {self.value:g} "
                f"(limit {self.limit:g})"
                + (f" — {self.detail}" if self.detail else ""))


class Stores:
    """Which chunk files each node's store holds once the clients have
    stopped, listed once by name (a listing costs no stat per file; on
    the check's machine a stat costs some 0.3 ms and a run leaves some
    90 000 files)."""

    def __init__(self, data_root: Path, nodes: int) -> None:
        self.root = data_root
        self.held: list[set[str]] = []
        for i in range(1, nodes + 1):
            names: set[str] = set()
            try:
                subs = [e.path for e in os.scandir(
                    data_root / f"node-{i}" / "chunks") if e.is_dir()]
            except FileNotFoundError:
                subs = []
            for sub in subs:
                names.update(e.name for e in os.scandir(sub)
                             if not e.name.startswith("."))
            self.held.append(names)

    def holders(self, digest: str) -> list[Path]:
        """The chunk's file on every node that has one."""
        return [self.root / f"node-{i + 1}" / "chunks" / digest[:2] / digest
                for i, names in enumerate(self.held) if digest in names]

    def bytes_on_disk(self, digests: set[str]) -> int:
        """Bytes of these chunks' files, every node's copy counted (a
        file gone since the listing — a node evicts what fails its
        verify on a read — holds none)."""
        total = 0
        for d in digests:
            for p in self.holders(d):
                try:
                    total += os.stat(p).st_size
                except FileNotFoundError:
                    pass
        return total


def _cpu_engine_table(body) -> list | None:
    """The chunk table of the program's own CPU engine (C++ walk +
    hashlib, code the device chain shares nothing with) — a cross-check
    between the program's two engines, not part of the reference. None
    where the program no longer has it."""
    repo = str(Path(__file__).resolve().parent.parent)
    if repo not in sys.path:
        sys.path.append(repo)
    try:
        from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter
    except ImportError:
        return None
    return [(c.offset, c.length, c.digest)
            for c in AnchoredCpuFragmenter().chunk(body)]


def unsynced(stores: Stores, digests: set[str],
             node_metrics: list[dict]) -> tuple[int, str]:
    """Chunk files of ``digests`` in the nodes' stores beyond the
    barriers each node says it issued, summed over the nodes; and both
    counts, node by node, to print."""
    files = [len(digests & names) for names in stores.held]
    barriers = [int(m.get("durability", {}).get("fsyncs", 0))
                for m in node_metrics]
    return (sum(max(0, f - b) for f, b in zip(files, barriers)),
            f"files {files} barriers {barriers}")


def run_checks(api, gen, config: dict, traffic: dict, seed: int,
               session_ops: list, manifests: dict, t_close: float,
               stores: Stores, owner_health: dict,
               nodes_after: list[dict], rehearsal: bool) -> list[Comparison]:
    """``manifests``: id -> chunk rows of every acked upload;
    ``nodes_after``: the nodes' ``/metrics`` read at ``t_close``, while
    the clients still ran."""
    dep = config["deployment"]
    n_nodes, rf = int(dep["nodes"]), int(dep["replication_factor"])
    api.phase = "check"
    out: list[Comparison] = []

    # 1. every operation of the run, replayed against the reference
    exp = reference.replay([o for o in session_ops if o.phase != "check"])
    out.append(Comparison(
        "answers the reference store could not have given",
        len(exp.violations), 0, "; ".join(exp.violations[:3])))

    # 2. a seeded sample of live objects, the newest in it
    puts = {o.file_id: o for o in session_ops
            if o.kind == "put" and o.acked and o.file_id in exp.live
            and o.phase != "warm"}
    ids = sorted(puts, key=lambda f: puts[f].t1)
    rng = data.rng(seed, 5)
    rest = ids[:-1]
    picks = rng.choice(len(rest), replace=False, size=min(
        int(traffic["check_sample"]) - 1, len(rest))) if rest else []
    sample = ids[-1:] + [rest[int(i)] for i in picks]
    differ = under = bad_chunk = bad_manifest = 0
    detail = ""
    newest_chunks: list = []
    for fid in sample:
        put = puts[fid]
        want = gen.make(put.key)
        reader = (put.node + 1 + int(rng.integers(n_nodes - 1))) % n_nodes
        op, body = api.get(0, reader, fid, keep=True)
        if not op.acked or memoryview(want) != body:
            differ += 1
            detail = detail or f"{fid[:12]} via node {reader + 1}: " \
                f"status {op.status}, {len(body)} B"
        _, manifest = api.stat(0, reader, fid)
        chunks = manifest.get("chunks", [])
        newest_chunks = newest_chunks or chunks
        end = 0
        for c in chunks:
            if c["offset"] != end or c["length"] > dep["cdc"]["max_chunk"]:
                bad_manifest += 1
            end = c["offset"] + c["length"]
            held = stores.holders(c["digest"])
            under += len(held) < rf
            for path in held[:1] if c["index"] % 16 else held:
                try:
                    with open(path, "rb") as f:
                        raw = f.read()
                except FileNotFoundError:   # a node evicts what fails
                    raw = b""               # its verify on a read
                bad_chunk += hashlib.sha256(raw).hexdigest() != c["digest"]
        bad_manifest += (end != len(want)) \
            + (manifest.get("fragmenter") != dep["engine"])
    out += [
        Comparison(f"objects of {len(sample)} sampled that read back "
                   "different through a non-coordinator", differ, 0, detail),
        Comparison(f"chunks of the sample on fewer than {rf} nodes",
                   under, 0),
        Comparison("chunk files of the sample whose sha256 is not their "
                   "name", bad_chunk, 0),
        Comparison("sampled manifests that do not tile the object, pass "
                   f"max_chunk or name another engine than {dep['engine']}",
                   bad_manifest, 0)]

    # 3. deleted ids answer 404, on every node: those the run deleted
    # and, now that nothing is in flight, ``check_deletes`` of the sample
    gone = sorted(exp.deleted)
    gone = [gone[int(i)] for i in rng.choice(
        len(gone), size=min(16, len(gone)), replace=False)] if gone else []
    unacked = 0
    for fid in sample[1:1 + int(traffic["check_deletes"])]:
        unacked += not api.delete(0, puts[fid].node, fid).acked
        gone.append(fid)
    served = sum(api.get(0, node, fid)[0].status != 404
                 for fid in gone for node in range(n_nodes))
    out.append(Comparison(
        f"deletes not acked, or answers other than 404 for {len(gone)} "
        f"deleted ids on {n_nodes} nodes", served + unacked, 0))

    # 4. the program's CPU engine agrees with the device chain's table
    if sample:
        table = _cpu_engine_table(gen.make(puts[sample[0]].key))
        got = [(c["offset"], c["length"], c["digest"])
               for c in newest_chunks]
        if table is None:
            print("[check] the program has no AnchoredCpuFragmenter: "
                  "engine cross-check skipped")
        else:
            out.append(Comparison(
                "rows of the newest object's chunk table that differ "
                "from the program's CPU engine",
                sum(a != b for a, b in zip(got, table))
                + abs(len(got) - len(table)), 0,
                f"{len(got)} vs {len(table)} chunks"))

    # 5. an ack came after its chunks' barriers: the files of every
    # upload acked by the window's close against the barriers counted at
    # the close (uploads in flight then have issued some more, so a few
    # late barriers can hide), and every file against the final count
    acked = [o for o in session_ops if o.kind == "put" and o.acked
             and o.phase != "check"]
    missing = sum(o.file_id not in manifests for o in acked)

    def named(ops):
        return {c["digest"] for o in ops
                for c in manifests.get(o.file_id, [])}

    nodes_end = [api.node_metrics(i)[0] for i in range(n_nodes)]
    late, late_detail = unsynced(
        stores, named(o for o in acked if o.t1 < t_close), nodes_after)
    never, never_detail = unsynced(stores, named(acked), nodes_end)
    out += [
        Comparison("chunk files of uploads acked by the window's close "
                   "beyond the fsync barriers their nodes had counted by "
                   "then, and acked uploads with no manifest",
                   late + missing, 0, late_detail),
        Comparison("chunk files of all acked uploads beyond the fsync "
                   "barriers their nodes counted in the session",
                   never, 0, never_detail)]

    # 6. the deployment is the one the configuration states
    dev = owner_health.get("device") or {}
    wrong = (0 if rehearsal else dev.get("platform") != dep["owner_platform"]) \
        + sum(n.get("durability", {}).get("mode") != dep["durability"]
              or n.get("frag", {}).get("engine") != dep["engine"]
              for n in nodes_after)
    out.append(Comparison(
        f"processes not as configured (owner on {dep['owner_platform']}, "
        f"nodes on {dep['engine']} with durability {dep['durability']})",
        wrong, 0, f"owner reports {dev.get('platform')!r}"))
    return out
