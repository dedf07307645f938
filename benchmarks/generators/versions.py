"""Traffic kind ``versions``: closed-loop clients streaming version after
version of one corpus, of which version 0 is preloaded — a backup target
taking snapshots in which little changed.

Parameters (the traffic file): ``corpus`` (``segments`` or
``source-tree``), ``clients``, ``object_bytes``, ``version_objects``
(objects a version is cut into), ``block_bytes``, ``corpus_seed``,
``lead_objects`` (= ``version_objects``: version 0, the preload),
``ratio_objects``, and the corpus' own (below).

The stream is ``version 0 ‖ version 1 ‖ ...`` cut every ``object_bytes``;
a key is ``("ver", k)`` with ``k`` the object's number in that stream, so
object ``k`` is piece ``k mod version_objects`` of version ``k div
version_objects``. Every object is exactly ``object_bytes`` long, and its
bytes are a pure function of ``(corpus_seed, k)``: ``make(key)`` rebuilds
any of them for the checks. ``--seed`` only orders: it shuffles the
preload among itself and the ``ratio_objects`` that follow among
themselves (so the bytes stored for that slice are one count for every
seed, ``end_to_end/stored_ratio.py``); from there the stream runs in its
own order. Client ``c`` sends places c, c+clients, ... of that order,
an object of version ``v`` through node ``(c + v) mod nodes`` — a
version never meets its predecessor on the coordinator that chunked it.
The preload goes in through all clients at once, under phase
``preload``. An object is made before its upload's clock starts; what
that costs the closed loop is ``layer_metrics/client.think_s_per_gib``.

An upload a node REFUSES (an HTTP error answer; the index plane's reads
"retry the upload") is sent again at once, same bytes, same node, up to
``_ATTEMPTS`` times in all, as a backup client does — a transport
failure or a timeout is not. Every attempt is an op of the log, so a
refused upload counts as failed, and where nothing is refused the
traffic is what it was. It keeps ``stored_ratio`` the count it is meant
to be on a program that refuses some: the slice and the preload are
whole once each object was taken at last. (Five: the parent of PR 27
refused one object in 13 a second time — the override a refusal leaves
lasts only until that peer filter's next full resync.)

Corpus ``segments`` (``period_bytes``, ``edit_min_bytes``,
``edit_max_bytes``): version 0 is ``data.segment(corpus_seed, j)`` for
each piece ``j``; version ``v`` is version ``v-1`` with, in each piece,
one span of ``edit_min..edit_max`` fresh bytes written over a seeded
offset — in place, nothing shifts.

Corpus ``source-tree`` (``fill``, ``file_median_bytes``, ``file_sigma``,
``file_max_bytes``, ``churn``: the shares of files edited, added,
deleted and renamed a version, ``edit_min_bytes``, ``edit_max_bytes``):
a version is the ustar archive of a tree of many small files in path
order, zero-padded (tar's own end blocks, continued) to
``version_objects`` objects. From one version to the next some files are
edited — a span inserted, deleted or replaced, so every later byte of
the archive SHIFTS — some added, deleted, renamed. What is cached is a
version's file table (path, size, edit history), never its bytes.

The corpus as a list of draws, which ``reference_versions.py`` repeats
in a second, plain implementation (``G(tags)`` is
``numpy.random.default_rng([corpus_seed, *tags])``; C is
``version_objects * object_bytes``):

* file ``f`` is born with ``size0`` bytes ``G(13, f).bytes(size0)`` at
  the path ``d<f mod 97>/s<(f div 97) mod 13>/f<f>.c``; its entry in an
  archive is its ustar header (mode 0644, uid gid 0, mtime 1700000000,
  no names) and its bytes padded to 512;
* version 0: ``sizes = G(10).lognormal(ln median, sigma, C div 4096)``
  cut to whole bytes in ``[1, file_max]``; files 0, 1, ... in that order
  while their entries fit in ``fill * C``;
* version ``v`` from ``v-1`` with ``g = G(11, v)``, ``n`` files in path
  order: counts ``max(1, round(share * n))`` for deleted, renamed,
  edited, added; ``p = g.permutation(n)`` gives, in that order, the
  deleted, the renamed and the edited (by position in path order). The
  renamed, in path order, move to ``d<a>/s<b>/f<f>_r<v>.c`` with
  ``a = g.integers(0, 97, n_ren)``, ``b = g.integers(0, 13, n_ren)``.
  An edited file ``f`` draws from ``e = G(12, f, v)``: ``op =
  e.integers(0, 3)`` (insert, delete, replace), ``length =
  e.integers(edit_min, edit_max + 1)``, ``at = e.integers(0, size + 1)``;
  delete and replace reach at most to the file's end; insert and replace
  take ``e.bytes(that length)``. The added are new files with sizes
  ``g.lognormal(...)`` cut as above. Last, while the entries pass
  ``C - 1024`` the file last in path order is dropped.
"""

from __future__ import annotations

import math
import threading

import numpy as np

import data

_DIRS, _SUBS = 97, 13
_MTIME = 1700000000
_ATTEMPTS = 5                # of one upload, while a node refuses it


class _Segments:
    """Corpus ``segments``: in-place edits on ``data.segment``."""

    def __init__(self, traffic: dict, size: int, pieces: int) -> None:
        self.seed = int(traffic["corpus_seed"])
        self.size, self.pieces = size, pieces
        self.period = int(traffic["period_bytes"])
        self.edit = (int(traffic["edit_min_bytes"]),
                     int(traffic["edit_max_bytes"]))

    def piece(self, version: int, j: int) -> np.ndarray:
        arr = data.segment(self.seed, j, self.size, self.period)
        for v in range(1, version + 1):
            r = data.rng(self.seed, 2, v, j)
            length = int(r.integers(self.edit[0], self.edit[1] + 1))
            at = int(r.integers(0, self.size - length + 1))
            arr[at:at + length] = np.frombuffer(r.bytes(length), np.uint8)
        return arr


def _cut_sizes(raw: np.ndarray, top: int) -> list[int]:
    return [int(s) for s in np.clip(raw.astype(np.int64), 1, top)]


def _entry_bytes(size: int) -> int:
    return 512 + -(-size // 512) * 512


def _header(path: str, size: int) -> bytes:
    """One ustar header block, as ``tarfile`` writes it for a plain
    file with no owner names."""
    name = path.encode()
    head = (name.ljust(100, b"\0") + b"0000644\0" + b"0000000\0"
            + b"0000000\0" + b"%011o\0" % size + b"%011o\0" % _MTIME)
    tail = (b"0" + bytes(100) + b"ustar\x0000" + bytes(32) + bytes(32)
            + bytes(8) + bytes(8) + bytes(155) + bytes(12))
    total = sum(head) + 8 * 32 + sum(tail)
    return head + b"%06o\0 " % total + tail


class _Table:
    """One version of the tree: the files in path order, each with its
    size at birth and the versions that edited it, and where each entry
    starts in the archive."""

    def __init__(self, files: dict[str, tuple[int, int, tuple, int]]
                 ) -> None:
        # path -> (file number, size at birth, edit versions, size now)
        self.files = files
        self.paths = sorted(files)
        sizes = [files[p][3] for p in self.paths]
        self.starts = np.concatenate(
            ([0], np.cumsum([_entry_bytes(s) for s in sizes])))

    @property
    def end(self) -> int:
        return int(self.starts[-1])


class _SourceTree:
    """Corpus ``source-tree``: the module docstring's list of draws, kept
    as file tables; bytes are made for one object at a time."""

    def __init__(self, traffic: dict, size: int, pieces: int) -> None:
        self.seed = int(traffic["corpus_seed"])
        self.size, self.cap = size, size * pieces
        self.mu = math.log(float(traffic["file_median_bytes"]))
        self.sigma = float(traffic["file_sigma"])
        self.top = min(int(traffic["file_max_bytes"]), self.cap // 8)
        self.churn = {k: float(v) for k, v in traffic["churn"].items()}
        self.edit = (int(traffic["edit_min_bytes"]),
                     int(traffic["edit_max_bytes"]))
        self._lock = threading.Lock()
        self._next = 0                   # the next file number to give
        self._tables = [self._first(float(traffic["fill"]))]

    @staticmethod
    def _born(f: int) -> str:
        return f"d{f % _DIRS:02d}/s{f // _DIRS % _SUBS:02d}/f{f:06d}.c"

    def _first(self, fill: float) -> _Table:
        sizes = _cut_sizes(data.rng(self.seed, 10).lognormal(
            self.mu, self.sigma, self.cap // 4096), self.top)
        files, used = {}, 0
        for f, s in enumerate(sizes):
            used += _entry_bytes(s)
            if used > fill * self.cap:
                break
            files[self._born(f)] = (f, s, (), s)
        self._next = len(files)
        return _Table(files)

    def _edit(self, f: int, v: int, size: int):
        """The edit version ``v`` makes to file ``f`` of ``size`` bytes:
        (op, at, bytes removed, bytes written) and the generator left
        where the written bytes come next."""
        e = data.rng(self.seed, 12, f, v)
        op = int(e.integers(0, 3))
        length = int(e.integers(self.edit[0], self.edit[1] + 1))
        at = int(e.integers(0, size + 1))
        gone = 0 if op == 0 else min(length, size - at)
        new = length if op == 0 else (0 if op == 1 else gone)
        return at, gone, new, e

    def _evolve(self, old: _Table, v: int) -> _Table:
        g = data.rng(self.seed, 11, v)
        n = len(old.paths)
        n_del, n_ren, n_edit, n_add = (
            max(1, round(self.churn[k] * n))
            for k in ("deleted", "renamed", "edited", "added"))
        p = [int(i) for i in g.permutation(n)]
        files = dict(old.files)
        for i in p[:n_del]:
            del files[old.paths[i]]
        a = g.integers(0, _DIRS, n_ren)
        b = g.integers(0, _SUBS, n_ren)
        for i, da, sb in zip(sorted(p[n_del:n_del + n_ren]), a, b):
            rec = files.pop(old.paths[i])
            files[f"d{int(da):02d}/s{int(sb):02d}/f{rec[0]:06d}_r{v}.c"] \
                = rec
        for i in sorted(p[n_del + n_ren:n_del + n_ren + n_edit]):
            f, size0, edits, size = files[old.paths[i]]
            _, gone, new, _ = self._edit(f, v, size)
            files[old.paths[i]] = (f, size0, edits + (v,),
                                   size - gone + new)
        for s in _cut_sizes(g.lognormal(self.mu, self.sigma, n_add),
                            self.top):
            files[self._born(self._next)] = (self._next, s, (), s)
            self._next += 1
        table = _Table(files)
        while table.end > self.cap - 1024:
            del files[table.paths[-1]]
            table = _Table(files)
        return table

    def table(self, version: int) -> _Table:
        with self._lock:
            while len(self._tables) <= version:
                self._tables.append(
                    self._evolve(self._tables[-1], len(self._tables)))
            return self._tables[version]

    def _content(self, f: int, size0: int, edits: tuple) -> bytes:
        body = data.rng(self.seed, 13, f).bytes(size0)
        for v in edits:
            at, gone, new, e = self._edit(f, v, len(body))
            body = body[:at] + e.bytes(new) + body[at + gone:]
        return body

    def piece(self, version: int, j: int) -> np.ndarray:
        table = self.table(version)
        lo, hi = j * self.size, (j + 1) * self.size
        out = np.zeros(self.size, dtype=np.uint8)
        first = max(0, int(np.searchsorted(table.starts, lo, "right")) - 1)
        for i in range(first, len(table.paths)):
            start = int(table.starts[i])
            if start >= hi:
                break
            path = table.paths[i]
            f, size0, edits, size = table.files[path]
            entry = _header(path, size) + self._content(f, size0, edits)
            a, b = max(lo, start), min(hi, start + len(entry))
            if a < b:       # an entry's zero padding needs no copy
                out[a - lo:b - lo] = np.frombuffer(
                    entry, np.uint8)[a - start:b - start]
        return out


_CORPORA = {"segments": _Segments, "source-tree": _SourceTree}


class Generator:
    def __init__(self, traffic: dict, config: dict, seed: int) -> None:
        self.clients = int(traffic["clients"])
        self.size = int(traffic["object_bytes"])
        self.pieces = int(traffic["version_objects"])
        self.block = int(traffic["block_bytes"])
        self.nodes = int(config["deployment"]["nodes"])
        self.warm_sizes = [self.size]
        self.corpus = _CORPORA[traffic["corpus"]](
            traffic, self.size, self.pieces)
        self.lead = int(traffic["lead_objects"])
        if self.lead != self.pieces:
            raise ValueError("lead_objects is version 0: it has to equal "
                             "version_objects")
        r = data.rng(seed, 6)
        self.preload_order = [int(k) for k in r.permutation(self.lead)]
        self.order = [self.lead + int(k) for k in
                      r.permutation(int(traffic["ratio_objects"]))]

    def make(self, key: tuple) -> np.ndarray:
        return self.corpus.piece(*divmod(key[1], self.pieces))

    def _put(self, api, client: int, k: int, stop=None):
        key = ("ver", k)
        body = self.make(key)
        want = data.sha256_hex(body)
        node = (client + k // self.pieces) % self.nodes
        for attempt in range(_ATTEMPTS):
            op = api.put(client, node, key, body, want, block=self.block)
            if op.acked or op.status == 0 or (stop and stop.is_set()):
                break
            print(f"[versions] object {k}: node {node + 1} answered "
                  f"{op.status} to attempt {attempt + 1}: "
                  f"{op.error[:100]}", flush=True)
        return op

    def preload(self, api) -> None:
        def send(client: int) -> None:
            for k in self.preload_order[client::self.clients]:
                self._put(api, client, k)

        threads = [threading.Thread(target=send, args=(c,), daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def run_client(self, client: int, api, stop) -> None:
        place = client
        while not stop.is_set():
            self._put(api, client, self.order[place]
                      if place < len(self.order) else self.lead + place,
                      stop)
            place += self.clients
