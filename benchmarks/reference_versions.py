#!/usr/bin/env python3
"""The plain reference of the ``versions`` corpora, and the dedup oracle.

The configuration ``snapshots-3n-rf2-index`` brings this copy: the
objects of a ``versions`` stream rebuilt from ``(corpus_seed, k)`` the
straight way — a version is a list of ``(path, bytes)``, the churn is
applied to it, ``tarfile`` writes it, the archive is sliced — following
the list of draws in ``generators/versions.py``'s docstring and sharing
no code with that module's cached file tables (nor with ``data.py``:
numpy and the standard library only). It holds every byte of a version,
so it is for tests at small sizes and for a by-hand check, never for the
timed path.

``stored_ratio_oracle`` is the byte-granular count ``stored_ratio`` has
to equal: the ``ratio_objects`` objects after the lead, chunked one by
one by the program's CPU engine, the bytes of the chunks that no object
of the lead has, each once, times the replication factor, over the
slice's bytes. (The harness's warm-up object is fresh bytes from
``--seed`` and shares no chunk with a corpus.)

    python3 benchmarks/reference_versions.py --traffic ingest-versions \\
        --config snapshots-3n-rf2-index [--rehearsal]

prints that count for a cell's traffic file, the objects taken from the
generator (a CPU count, minutes at the full size).
"""

from __future__ import annotations

import argparse
import io
import json
import math
import sys
import tarfile
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def _g(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([seed, *tags])


def _sizes(raw, top: int) -> list[int]:
    return [min(top, max(1, int(s))) for s in raw]


class Reference:
    """``object(k)``: object ``k`` of the stream a traffic file
    describes, as bytes."""

    def __init__(self, traffic: dict) -> None:
        self.t = traffic
        self.seed = int(traffic["corpus_seed"])
        self.size = int(traffic["object_bytes"])
        self.pieces = int(traffic["version_objects"])
        self._versions: dict[int, bytes] = {}

    def object(self, k: int) -> bytes:
        version, j = divmod(k, self.pieces)
        if version not in self._versions:
            whole = (self._segments(version)
                     if self.t["corpus"] == "segments"
                     else self._archive(version))
            assert len(whole) == self.size * self.pieces
            self._versions = {version: whole}    # one version held
        return self._versions[version][j * self.size:(j + 1) * self.size]

    # -- corpus ``segments`` ------------------------------------------------
    def _segments(self, version: int) -> bytes:
        period = int(self.t["period_bytes"])
        half = period // 2
        block = _g(self.seed, 0, 0).bytes(half)
        out = []
        for j in range(self.pieces):
            piece = bytearray()
            fresh = _g(self.seed, 1, j).bytes(-(-self.size // period) * half)
            for n in range(-(-self.size // period)):
                piece += fresh[n * half:(n + 1) * half] + block
            piece = piece[:self.size]
            for v in range(1, version + 1):
                r = _g(self.seed, 2, v, j)
                length = int(r.integers(int(self.t["edit_min_bytes"]),
                                        int(self.t["edit_max_bytes"]) + 1))
                at = int(r.integers(0, self.size - length + 1))
                piece[at:at + length] = r.bytes(length)
            out.append(bytes(piece))
        return b"".join(out)

    # -- corpus ``source-tree`` ---------------------------------------------
    def _tree(self, version: int) -> dict[str, tuple[int, bytes]]:
        """path -> (file number, bytes) of every file of a version,
        replayed from version 0."""
        t = self.t
        cap = self.size * self.pieces
        mu, sigma = math.log(float(t["file_median_bytes"])), \
            float(t["file_sigma"])
        top = min(int(t["file_max_bytes"]), cap // 8)
        lo, hi = int(t["edit_min_bytes"]), int(t["edit_max_bytes"])

        def born(f: int) -> str:
            return f"d{f % 97:02d}/s{f // 97 % 13:02d}/f{f:06d}.c"

        def entries(tree) -> int:
            return sum(512 + (len(b) + 511) // 512 * 512
                       for _, b in tree.values())

        tree: dict[str, tuple[int, bytes]] = {}
        used = 0
        for f, s in enumerate(_sizes(
                _g(self.seed, 10).lognormal(mu, sigma, cap // 4096), top)):
            used += 512 + (s + 511) // 512 * 512
            if used > float(t["fill"]) * cap:
                break
            tree[born(f)] = (f, _g(self.seed, 13, f).bytes(s))
        next_f = len(tree)
        for v in range(1, version + 1):
            g = _g(self.seed, 11, v)
            paths = sorted(tree)
            n = len(paths)
            count = {k: max(1, round(float(t["churn"][k]) * n))
                     for k in ("deleted", "renamed", "edited", "added")}
            p = list(g.permutation(n))
            deleted = [p.pop(0) for _ in range(count["deleted"])]
            renamed = sorted(p.pop(0) for _ in range(count["renamed"]))
            edited = sorted(p.pop(0) for _ in range(count["edited"]))
            for i in deleted:
                del tree[paths[i]]
            a = g.integers(0, 97, len(renamed))
            b = g.integers(0, 13, len(renamed))
            for i, da, sb in zip(renamed, a, b):
                f, body = tree.pop(paths[i])
                tree[f"d{da:02d}/s{sb:02d}/f{f:06d}_r{v}.c"] = (f, body)
            for i in edited:
                f, body = tree[paths[i]]
                e = _g(self.seed, 12, f, v)
                op = int(e.integers(0, 3))
                length = int(e.integers(lo, hi + 1))
                at = int(e.integers(0, len(body) + 1))
                if op == 0:
                    body = body[:at] + e.bytes(length) + body[at:]
                elif op == 1:
                    body = body[:at] + body[at + length:]
                else:
                    end = min(len(body), at + length)
                    body = body[:at] + e.bytes(end - at) + body[end:]
                tree[paths[i]] = (f, body)
            for s in _sizes(g.lognormal(mu, sigma, count["added"]), top):
                tree[born(next_f)] = (next_f, _g(self.seed, 13,
                                                 next_f).bytes(s))
                next_f += 1
            while entries(tree) > cap - 1024:
                del tree[max(tree)]
        return tree

    def _archive(self, version: int) -> bytes:
        tree = self._tree(version)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w",
                          format=tarfile.USTAR_FORMAT) as tar:
            for path in sorted(tree):
                info = tarfile.TarInfo(path)
                info.size = len(tree[path][1])
                info.mode, info.mtime = 0o644, 1700000000
                tar.addfile(info, io.BytesIO(tree[path][1]))
        cap = self.size * self.pieces
        whole = buf.getvalue()
        assert not any(whole[cap:])     # only tar's own end blocks go
        return whole[:cap].ljust(cap, b"\0")


def stored_ratio_oracle(make, lead: int, ratio: int, rf: int) -> float:
    """``make(k)``: object ``k`` as a buffer. The count documented at the
    top, through the program's CPU engine."""
    repo = str(HERE.parent)
    if repo not in sys.path:
        sys.path.append(repo)
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    engine = AnchoredCpuFragmenter()
    before: set[str] = set()
    for k in range(lead):
        before.update(c.digest for c in engine.chunk(make(k)))
    new: dict[str, int] = {}
    user = 0
    for k in range(lead, lead + ratio):
        body = make(k)
        user += len(body)
        for c in engine.chunk(body):
            if c.digest not in before:
                new[c.digest] = c.length
    return sum(new.values()) * rf / user


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    from window import load_by_name

    traffic = json.loads(
        (HERE / "traffic" / f"{args.traffic}.json").read_text())
    if args.rehearsal:
        traffic = {**traffic, **traffic["rehearsal"]}
    config = json.loads(
        (HERE / "configs" / f"{args.config}.json").read_text())
    gen = load_by_name("generators", traffic["kind"]).Generator(
        traffic, config, 0)
    print(json.dumps({"stored_ratio_oracle": stored_ratio_oracle(
        lambda k: gen.make(("ver", k)), int(traffic["lead_objects"]),
        int(traffic["ratio_objects"]),
        int(config["deployment"]["replication_factor"]))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
