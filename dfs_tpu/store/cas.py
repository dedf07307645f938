"""Content-addressed local persistence (L0).

Reference layout: ``data/node-<id>/<fileId>/manifest.json`` +
``<fileId>/fragments/<i>.frag`` (StorageNode.java:20,147-149,352-357,463-469).
That keys fragments by *position within one file*, so identical content in two
files is stored twice.

Here chunks are keyed purely by their sha256 digest —
``chunks/<d[:2]>/<digest>`` — which makes cross-file dedup automatic: a chunk
shared by two files (or two versions of one file) is stored once. Manifests
live under ``files/<fileId>.json``. Writes go through a temp file + atomic
rename, upgrading the reference's benign-race story (SURVEY.md §5.2: safety by
idempotent overwrite) to actual atomicity; the manifest-last write ordering on
upload (SURVEY.md §5.4) is preserved by the node runtime.
"""

from __future__ import annotations

import contextlib
import errno
import itertools
import json
import os
import tempfile
import threading
import time
from pathlib import Path

from dfs_tpu.meta.manifest import Manifest
# the delta codec is import-light (numpy + stdlib; dfs_tpu.sim keeps the
# sketch/JAX stack out of its package __init__) — safe at module level
from dfs_tpu.sim.delta import HEADER_BYTES as _DELTA_HEADER_BYTES
from dfs_tpu.sim.delta import apply_delta as _apply_delta
from dfs_tpu.sim.delta import parse_header as _parse_delta_header
from dfs_tpu.utils.hashing import is_hex_digest
from dfs_tpu.utils.hashing import sha256_hex


def _fsync_path(path: str) -> None:
    """fsync a path by name — directories after a create/rename (the
    entry's durability: rename/link atomicity orders the VISIBLE state,
    but the directory block can still sit in the page cache when the
    power goes) and files after a metadata-only change like utime
    (write-time fsyncs don't cover it)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _atomic_write(path: Path | str, data: bytes,
                  fsync: bool = False) -> None:
    parent = os.path.dirname(os.fspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=parent, prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            if fsync:
                # payload durable BEFORE the rename makes it visible —
                # otherwise a crash can leave the new name pointing at
                # zero-filled blocks (rename is atomic, not a barrier)
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, path)
        if fsync:
            _fsync_path(parent)
    except BaseException:
        try:
            os.unlink(tmp)
        # cleanup inside an unwinding write: the original error re-raises
        # below; an unlink failure here only re-leaks a .tmp- the aged
        # sweep reclaims
        except OSError:  # dfslint: ignore[DFS007]
            pass
        raise


_TMP_SWEEP_AGE_S = 3600.0

# bound of ChunkStore's resident set: the source's deployment holds
# ~870 000 digests a node (PERF.md §4) -> 2**20 entries, ~100 MB at the
# worst; past it the set is emptied — and is no longer complete, where
# it was — and refills from what stands behind it (the index where the
# plane is attached, else stats)
_RESIDENT_MAX = 1 << 20


# A look at the disk for a batch (``ChunkStore.has_many`` without
# ``resident_ok``, index off) lists a shard directory once where that
# is cheaper than a ``stat`` for each name the batch asks of it.
# Measured on the file system of the benchmark's machine (9p under
# gVisor; PERF.md §6, PR 35, scripts/fsprice.py), directories of 30 /
# 120 / 500 / 3 400 files of 8 KiB: a ``stat`` 0.08 ms idle and 0.43 ms
# beside eight fsyncing writers; a listing (``os.scandir``, the wanted
# names' ``is_file`` included — the entry's type is known there) 0.24 /
# 0.63 / 2.2 / 15.3 ms idle and 0.7–1.2 / 1.0–3.6 / 6.9–11.1 / 70–94 ms
# beside them: the time of 2.8 / 7.4 / 28 / 193 ``stat``s idle and
# 1.4–2.8 / 2.5–8.2 / 16–25 / 160–219 busy. So a listing costs what
# two to three ``stat``s cost plus one more for every ~17 entries it
# reads, loaded or not: it pays where the batch asks about at least
# ``_LIST_MIN_NAMES`` names of a directory and about more than one in
# ``_LIST_ENTRIES_PER_STAT`` of the names the directory holds (taken as
# the store's chunk count over its 256 directories: sha256 spreads them
# evenly). The repair cycle's probe — 2 048 sorted digests a slice, so
# most of ~20 directories each — lists; a probe of a few names, or of
# one object's chunks in a store of millions, is a ``stat`` a name.
_LIST_MIN_NAMES = 4
_LIST_ENTRIES_PER_STAT = 16
_SHARD_DIRS = 256

# The put job's phase clock (ChunkStore.put_stats, `/metrics`
# `durability.put`): where a put call's seconds went on its calling
# thread. Every instant of a call is in exactly one, so they add up to
# `jobS`.
_PUT_PHASES = ("precheckS", "settleS", "createS", "writeS",
               "payloadFsyncS", "linkWaitS", "linkS", "dirBarrierS",
               "unlinkS", "flushS")


def _stripe(digest: str) -> int:
    """Which of ``ChunkStore._dir_mu`` orders ``digest``: its first
    byte (``key[0]`` of its raw form), the shard directory its file
    lives in."""
    return int(digest[:2], 16)


def _sweep_tmp_files(dirs, max_age_s: float = _TMP_SWEEP_AGE_S) -> int:
    """Unlink ``.tmp-*`` entries older than ``max_age_s`` in the given
    directories; returns the number removed. Shared by the chunk and
    manifest stores — both leak the same class of temp file on a crash
    between create and link/rename."""
    cutoff = time.time() - max_age_s
    n = 0
    for d in dirs:
        for p in d.glob(".tmp-*"):
            try:
                if p.stat().st_mtime <= cutoff:
                    p.unlink()
                    n += 1
            # stat/unlink racing a concurrent sweep or the file's own
            # writer — losing the race is the success case
            except OSError:  # dfslint: ignore[DFS007]
                continue
    return n


class ChunkStore:
    """Flat content-addressed blob store.

    ``fsync=True`` (DurabilityConfig mode "fsync", routed down by the
    node runtime) makes every put crash-durable before it returns: each
    payload file is fsync'd before the link makes it visible, and its
    parent directory is fsync'd after — so an acked upload's chunks
    survive kill -9 / power loss, not just process death. A batch
    (:meth:`put_batch`) keeps that order per chunk and pays the
    directory barrier once per distinct directory, after the last link
    into it. Default False here: standalone/library users opt in; the
    node defaults on.

    ``fault`` is the chaos seam (dfs_tpu.chaos): when set, every
    put/get calls ``fault(op, digest)`` first — on the CALLING thread
    (the bounded CAS workers), so injected ENOSPC/EIO/slow-disk faults
    ride the real I/O paths. None (the default) costs one attribute
    check."""

    def __init__(self, root: Path, fsync: bool = False) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._root_str = os.fspath(self.root)
        self._fsync = bool(fsync)
        self.fault = None                  # chaos hook: fault(op, digest)
        # dedup/index seam (dfs_tpu.index.IndexPlane): when set, every
        # put/delete feeds the log-structured digest index FROM THE
        # CALLING THREAD (the bounded CAS workers — DFS001-clean) and
        # has() answers positive hits from it without a stat. None
        # (the default) keeps the pre-index paths byte-identical.
        self.index = None
        self._count: int | None = None     # lazy; maintained by put/delete
        self._bytes: int | None = None     # lazy; maintained by put/delete
        # chunk files made durable (payload fsync'd, linked, directory
        # fsync'd) — one per file, counted before the put returns
        self._fsyncs = 0
        self._dir_barriers = 0             # directory fsyncs issued
        # digests whose name this store linked (or is about to) and whose
        # directory barrier has not been issued yet: whoever meets such a
        # name as a dedup hit fsyncs the directory itself before answering
        # (_settle). Entered BEFORE the link, so a visible name is always
        # found here until a barrier covers it.
        self._unbarriered: set[str] = set()
        # what is on the disk, remembered — index plane on or off: raw
        # 32-byte digests whose raw file this process linked, or found by
        # a stat or (has(resident_ok=True), the put pre-check) by an index
        # positive, and has not unlinked since. Positives only — no absent
        # name is cached one by one. The put pre-check and
        # has(resident_ok=True) answer from it in front of the index and of
        # the stat; every look at the disk heals it. Entered and
        # discarded-before-the-unlink under the digest's _dir_mu; dies
        # with the process.
        self._resident: set[bytes] = set()
        # is the set COMPLETE — does it hold every raw name on the disk,
        # so that a miss IS the answer "absent"? A fact somebody
        # established, never a default. None: nobody has tried (a bare
        # store: a miss goes on to the index or the stat, as ever). True:
        # a listing seeded the set (digests(complete=True): the node's
        # boot sweep, before anything is in flight) and every link since
        # entered its name, every unlink discarded it first. False: the
        # listing could not (_establish), or it was and the overflow of
        # _RESIDENT_MAX emptied the set; a node lists for it once, so
        # nothing raises it again in that life. What another hand puts
        # into the directory is the one thing a complete set lacks: such
        # a name costs a redundant write that ends as a dedup hit
        # (_write_raw), never a copy counted that is not there.
        self._complete: bool | None = None
        self._res_absent = 0               # misses answered "absent" here
        self._unlinks = 0                  # chunk unlinks ended
        # resident answers / went on (index, stat) / entries found stale
        self._res_hits = self._res_misses = self._res_drops = 0
        # disk-looking batches (has_many without resident_ok): names
        # looked for by a stat / answered from a listing / listings made
        self._look_stats = self._look_listed = self._look_listings = 0
        # the put job's phase clock: calls, items given, names linked,
        # and the calling threads' seconds by phase (put_stats)
        self._put = {"jobs": 0, "items": 0, **self._put_clock()}
        self._count_lock = threading.Lock()   # puts run on CAS pool workers
        # orders the visible link/unlink against its index record and
        # its resident entry: a put racing a delete of the SAME digest
        # could otherwise interleave (link, note_delete, unlink,
        # note_put) and leave a stale "present" — the one divergence the
        # index design forbids. Two digests never needed to exclude each
        # other, so it is a lock a shard directory (``_stripe``: the
        # digest's first byte, which AsyncChunkStore._split cuts a batch
        # by): a node's write workers link side by side. Everything
        # reached under one has a lock of its own; only _put_delta holds
        # two (its digest's and its base's, in ascending order).
        self._dir_mu = tuple(threading.Lock() for _ in range(_SHARD_DIRS))
        self._dirs: set[str] = set()       # subdirs known to exist
        self._tmp_seq = itertools.count()  # cheap unique tmp names
        # similarity seam (dfs_tpu.sim.SimPlane): when set, eligible
        # puts may store a DELTA (base-digest + patch, dfs_tpu.sim.
        # delta) under ``deltas/<d[:2]>/<digest>`` instead of the raw
        # file, and get() reconstructs transparently. None (the
        # default) keeps every pre-sim path byte-identical; the delta
        # tree is consulted ONLY once it exists on disk, so a
        # default-off store never even stats it.
        self.sim = None
        self._deltas_root = f"{self._root_str}/deltas"
        self._delta_mu = threading.Lock()  # guards the two maps below
        self._delta_base: dict[str, str] = {}   # delta digest -> base
        self._delta_refs: dict[str, int] = {}   # base -> live dependents
        self._have_deltas = os.path.isdir(self._deltas_root)
        if self._have_deltas:
            self._prime_delta_maps()

    def _path(self, digest: str) -> Path:
        if not is_hex_digest(digest):
            raise ValueError(f"bad digest {digest!r}")
        return self.root / digest[:2] / digest

    def _path_str(self, digest: str) -> str:
        # the per-chunk access path: plain string joins — pathlib
        # construction measured ~1 s of a 3-download profile (one Path
        # costs ~6 object allocations; reads touch thousands of chunks)
        if not is_hex_digest(digest):
            raise ValueError(f"bad digest {digest!r}")
        return f"{self._root_str}/{digest[:2]}/{digest}"

    # -- delta tree (similarity plane) ---------------------------------
    #
    # A delta-stored chunk lives at deltas/<d[:2]>/<digest> INSIDE the
    # store root. The legacy scans never see it: digests()' inner loop
    # filters on 64-hex names (the 2-hex fan-out dirs under deltas/
    # fail that) and inventory()'s bucket walk filters subdirs on
    # PREFIX_HEX-length names ("deltas" fails that). The raw path
    # always wins when both exist (a crash mid-re-materialize), so
    # there is never an ambiguity about which bytes a digest serves.

    def _delta_path_str(self, digest: str) -> str:
        if not is_hex_digest(digest):
            raise ValueError(f"bad digest {digest!r}")
        return f"{self._deltas_root}/{digest[:2]}/{digest}"

    def _deltas_possible(self) -> bool:
        """Locked read of the deltas-on-disk flag (written under
        ``_delta_mu`` by the first delta put) — False short-circuits
        every delta path, so a plane-less store pays one uncontended
        lock at most and no extra stats."""
        with self._delta_mu:
            return self._have_deltas

    def _prime_delta_maps(self) -> None:
        """Rebuild the delta dependency maps from the on-disk headers
        (one 41-byte read per delta) at open. The maps are the pin
        ground truth for delete/GC refusal and need no separate
        persistence — the delta files ARE the log. A delta whose raw
        twin exists is a crash between re-materialize and unlink: the
        raw copy wins, so the unlink is completed here."""
        droot = Path(self._deltas_root)
        hexdigits = set("0123456789abcdef")
        for sub in sorted(droot.iterdir()) if droot.is_dir() else []:
            if not sub.is_dir():
                continue
            for p in sub.iterdir():
                d = p.name
                if len(d) != 64 or not set(d) <= hexdigits:
                    continue
                if os.path.isfile(self._path_str(d)):
                    try:
                        p.unlink()
                    # completing a previous life's interrupted
                    # re-materialize is best-effort; the raw file keeps
                    # serving either way
                    except OSError:  # dfslint: ignore[DFS007]
                        pass
                    continue
                try:
                    with open(p, "rb") as f:
                        base_d, _ = _parse_delta_header(
                            f.read(_DELTA_HEADER_BYTES))
                # unreadable/corrupt header at boot: leave the file —
                # the read path classifies and drops it with counters
                except (OSError, ValueError):  # dfslint: ignore[DFS007]
                    continue
                self._delta_base[d] = base_d
                self._delta_refs[base_d] = \
                    self._delta_refs.get(base_d, 0) + 1

    def delta_base(self, digest: str) -> str | None:
        """Base digest of a delta-stored chunk, None when raw/absent."""
        with self._delta_mu:
            return self._delta_base.get(digest)

    def delta_pinned(self, digest: str) -> bool:
        """True when resident deltas reconstruct through ``digest`` —
        delete()/GC must refuse it (docs/similarity.md)."""
        with self._delta_mu:
            return self._delta_refs.get(digest, 0) > 0

    def delta_count(self) -> int:
        with self._delta_mu:
            return len(self._delta_base)

    def delta_dependents(self, digest: str) -> list[str]:
        """Resident deltas whose base CHAIN passes through ``digest`` —
        everything a corrupt or lost base invalidates. Ordered deepest
        first, so deleting in order releases each pin before its
        holder is attempted (the scrub cascade rides this)."""
        with self._delta_mu:
            children: dict[str, list[str]] = {}
            for k, v in self._delta_base.items():
                children.setdefault(v, []).append(k)
        out: list[str] = []
        frontier = [digest]
        seen = {digest}
        while frontier:
            nxt = []
            for b in frontier:
                for k in children.get(b, ()):
                    if k not in seen:
                        seen.add(k)
                        out.append(k)
                        nxt.append(k)
            frontier = nxt
        out.reverse()
        return out

    def delta_depth(self, digest: str) -> int:
        """Chain length above ``digest``: 0 = raw-resident, N = a delta
        N hops from raw, -1 = absent or broken chain."""
        depth = 0
        cur = digest
        for _ in range(64):
            with self._delta_mu:
                base = self._delta_base.get(cur)
            if base is None:
                return depth if os.path.isfile(self._path_str(cur)) else -1
            depth += 1
            cur = base
        return -1

    def _chain_resolves(self, digest: str) -> bool:
        """True when ``digest`` reconstructs: its delta chain (possibly
        zero-length) ends at a raw-resident file."""
        cur = digest
        for _ in range(64):
            with self._delta_mu:
                base = self._delta_base.get(cur)
            if base is None:
                return os.path.isfile(self._path_str(cur))
            cur = base
        return False

    # -- the resident set (in front of the index and of the stat) ------

    def _remember(self, key: bytes, seen: int | None = None) -> None:
        """The raw name of ``key`` was just linked or seen. The caller
        holds the digest's ``_dir_mu`` — the lock ``delete`` holds from
        its discard to the end of its unlink — so no entry outlives its
        file. A name seen outside that lock (a ``stat``, a listing, an
        index positive) passes ``seen``, the count of unlinks when its
        look began: if one ended since, it may have been this name's,
        and nothing is entered (the next look does). The same with the
        index plane attached: the index records beside it, under the
        same lock. At ``_RESIDENT_MAX`` the set is emptied, and with it
        goes its completeness: a miss is a question for the disk again."""
        with self._count_lock:
            if seen is not None and seen != self._unlinks:
                return
            if len(self._resident) >= _RESIDENT_MAX:
                self._resident.clear()
                if self._complete:
                    self._complete = False
            self._resident.add(key)

    def _establish(self, raw: list[str], seen: int) -> None:
        """``raw`` is a listing of every raw name on the disk, begun when
        ``seen`` unlinks had ended: seed the set from it and note that it
        is complete. :meth:`_remember`'s rule for a look outside
        ``_dir_mu``, for the listing as a whole: with every directory's
        lock held (ascending, as ``_put_delta`` takes its two) no link
        and no delete is half way, and if an unlink ended while the
        listing was read nothing is entered and nothing declared — as
        where the names would pass ``_RESIDENT_MAX``. A name linked
        meanwhile entered itself."""
        keys = [bytes.fromhex(d) for d in raw]
        with contextlib.ExitStack() as held:
            for mu in self._dir_mu:
                held.enter_context(mu)
            with self._count_lock:
                ok = seen == self._unlinks and \
                    len(self._resident) + len(keys) <= _RESIDENT_MAX
                if ok:
                    self._resident.update(keys)
                # tried and could not: False, no longer None
                self._complete = ok or bool(self._complete)

    def _forget(self, key: bytes) -> None:
        """A look outside ``_dir_mu`` found no raw file of ``key``: its
        entry goes. A complete set has something to lose here that no
        other has — a link that ended since the look entered the name,
        and dropping that would leave a file the set does not hold — so
        it looks again under the directory's lock, where no link is
        half way, before it drops."""
        with self._count_lock:
            if key not in self._resident:
                return
            if not self._complete:
                self._resident.remove(key)
                self._res_drops += 1
                return
        with self._dir_mu[key[0]]:
            if not os.path.isfile(self._path_str(key.hex())):
                with self._count_lock:
                    if key in self._resident:
                        self._resident.remove(key)
                        self._res_drops += 1

    def _look_begins(self, key: bytes,
                     counted: bool) -> tuple[bool, bool, int]:
        """A look for the raw name of ``key`` begins: is it resident; is
        it ABSENT, the look over before it reached the disk; and the
        count of unlinks ended so far — :meth:`_remember`'s ``seen`` for
        what the look finds outside ``_dir_mu``. ``counted``: the caller
        takes a resident answer (the put pre-check, ``resident_ok``), so
        this is a hit or a miss of :meth:`resident_stats` — and a miss
        of a complete set is the answer: no raw file of that name."""
        with self._count_lock:
            known = key in self._resident
            absent = False
            if counted:
                if known:
                    self._res_hits += 1
                else:
                    self._res_misses += 1
                    if self._complete:
                        absent = True
                        self._res_absent += 1
            return known, absent, self._unlinks

    def _saw(self, key: bytes, seen: int) -> None:
        """A look outside ``_dir_mu`` found the raw name — a ``stat``,
        an index positive: entered unless an unlink ended since the
        look began."""
        with self._dir_mu[key[0]]:
            self._remember(key, seen)

    def _stat_raw(self, key: bytes, p: str, known: bool, seen: int) -> bool:
        """One ``stat`` of the raw file, which heals the set both ways:
        "absent" drops the entry, "present" enters it."""
        if not os.path.isfile(p):
            if known:
                self._forget(key)
            return False
        if not known:
            self._saw(key, seen)
        return True

    def resident_stats(self) -> dict:
        """``/metrics`` ``durability.resident*``: existence checks the
        resident set answered "present", those about names it does not
        hold, entries held now, entries dropped because the disk
        disagreed. Counted with the index plane on as with it off. Once
        somebody has established that the set is complete (a node, at
        its boot sweep) two more: ``residentAbsent``, the misses — they
        count in ``residentMisses`` too — answered "absent" there and
        then, where the others went on to the index or to a ``stat``;
        and ``residentComplete``, whether the set is complete now."""
        with self._count_lock:
            out = {"residentHits": self._res_hits,
                   "residentMisses": self._res_misses,
                   "residentEntries": len(self._resident),
                   "residentDrops": self._res_drops}
            if self._complete is not None:
                out["residentAbsent"] = self._res_absent
                out["residentComplete"] = self._complete
            return out

    def has(self, digest: str, resident_ok: bool = False) -> bool:
        """Local existence, asked in the order memory → index → disk.

        **Memory**, only where the caller says a resident answer will do
        (``resident_ok``: placement's probes and pre-ack rounds): the raw
        name is in the resident set — this process linked or saw it and
        has not begun to unlink it since (``delete`` discards before the
        unlink, under the mutex that orders both). The argument is the
        index's below, minus persistence — so minus its crash cases; the
        one caveat is the same external directory mutation, and a look
        at the disk (index off: the repair cycle's, every cycle; index
        on: the backstop below, once scrub has expunged the phantom)
        drops what the disk no longer has. The set stands in front of
        the index as in front of the ``stat``: what either finds for such
        a caller is entered, under :meth:`_remember`'s rule.

        The same caller hears "absent" from memory too, where the set is
        COMPLETE: it holds every raw name on the disk, so a name it
        lacks has no raw file — no ``stat``, no lookup (only the delta
        map is still asked, where a delta tree exists). Complete is a
        fact that is established, not assumed: a node's boot sweep
        seeds the set from the listing it makes anyway, before the
        servers listen (``digests(complete=True)``); from then on every
        link enters its name and every unlink discards it first, under
        ``_dir_mu``. It ends where the set overflows ``_RESIDENT_MAX``
        and is emptied (:meth:`_remember`). A bare store never is, and
        answers a miss from the index or the disk as it always did. The
        caveat's other face: a file ANOTHER hand put there is "absent"
        here until a look at the disk enters it — which costs a
        redundant transfer that ends as a dedup hit (``_write_raw``),
        where the first face costs a copy believed in until the repair
        cycle's look. No "present" is ever answered that was not.

        **Index**, where the plane is attached: a positive
        index answer is final — puts are recorded only AFTER the link
        is visible and deletes BEFORE the unlink (see ``put`` /
        ``delete``), so "present" in the index implies the file was
        durably linked and no delete has begun; the residual caveat is
        external directory mutation, the same class count() documents.
        A NEGATIVE index answer falls through to the stat — the
        negative-confirmation backstop: the index may lag a put (its
        WAL buffers put records; a kill -9 loses the buffer — the safe
        direction), and claiming absence for a present chunk would
        cost a redundant transfer per probe. The backstop is
        SELF-HEALING: a stat that contradicts the index re-records the
        digest (under the same ordering mutex a racing delete takes),
        so a crash-lost record costs one stat, not one per probe
        forever — and the first post-restart repair probe sweep
        re-indexes everything it touches. Without ``resident_ok`` (the
        repair cycle, ``who_has``, relocation, the smart client) this
        is the whole of the path, the set not consulted.

        **Disk**, with the plane off (one ``stat``; a batch:
        :meth:`has_many`)."""
        p = self._path_str(digest)
        key = bytes.fromhex(digest)
        index = self.index
        known = absent = False
        if resident_ok or index is None:
            known, absent, seen = self._look_begins(key, resident_ok)
        if known and resident_ok:
            present = True
        elif absent:
            # no raw file: only a delta of its own can hold it
            present = self._deltas_possible() \
                and self.delta_base(digest) is not None \
                and self._chain_resolves(digest)
        elif index is None:
            present = self._stat_raw(key, p, known, seen) \
                or (self._deltas_possible()
                    and self._chain_resolves(digest))
        elif index.lookup(digest):
            present = True
            # raw names only: an index positive may be a delta-stored
            # chunk once the delta tree exists (the flag never falls)
            if resident_ok and not self._deltas_possible():
                self._saw(key, seen)
        else:
            with self._dir_mu[key[0]]:
                raw = os.path.isfile(p)
                present = raw \
                    or (self._deltas_possible()
                        and self._chain_resolves(digest))
                if present:
                    index.note_put(digest, defer_flush=True)
                if raw:
                    self._remember(key)    # seen under the mutex
            if not raw:
                self._forget(key)
            index.note_stat_fallback(present)
            if present:
                index.maybe_flush()        # outside the ordering mutex
        if present:
            # "present" is what a coordinator counts as a copy before it
            # acks: a name still owed its directory barrier gets it first
            self._settle(digest, p)
        return present

    def has_many(self, digests, resident_ok: bool = False) -> list[bool]:
        """Batched :meth:`has` — one call for a whole probe list, so
        async callers pay one thread-pool job instead of one per
        digest (:meth:`AsyncChunkStore.has_many`).

        A batch that has to look at the disk (index off, no
        ``resident_ok``: the repair cycle's probe of every name a peer
        should hold, every cycle) looks by DIRECTORY: grouped by shard
        directory, and a directory the batch asks enough names of that
        one listing is cheaper than their ``stat``s (``_LIST_MIN_NAMES``
        and ``_LIST_ENTRIES_PER_STAT``, above) is listed once and its
        names answered from the listing (:meth:`_list_present`); the
        others are a :meth:`has` each — a ``stat`` a name, as a small
        batch (``who_has``, a resume probe) always is. The answers are
        those of the :meth:`has` loop, name for name."""
        if resident_ok or self.index is not None:
            return [self.has(d, resident_ok) for d in digests]
        ds = list(digests)
        by_dir: dict[str, set[str]] = {}
        for d in ds:
            if not is_hex_digest(d):
                raise ValueError(f"bad digest {d!r}")
            by_dir.setdefault(d[:2], set()).add(d)
        many = {sub: names for sub, names in by_dir.items()
                if len(names) >= _LIST_MIN_NAMES}
        listed = {}
        if many:
            held = self.count() / _SHARD_DIRS      # names a directory
            listed = {sub: self._list_present(sub, names)
                      for sub, names in many.items()
                      if len(names) * _LIST_ENTRIES_PER_STAT >= held}
        out = []
        stats = 0
        for d in ds:
            present = listed.get(d[:2])
            if present is None:
                out.append(self.has(d))
                stats += 1
            else:
                out.append(d in present)
        with self._count_lock:
            self._look_stats += stats
            self._look_listed += len(ds) - stats
            self._look_listings += len(listed)
        return out

    def _list_present(self, sub: str, wanted: set[str]) -> set[str]:
        """Which of ``wanted`` — digests of shard directory ``sub`` —
        are present, from ONE listing of it: the truth ``os.path.isfile``
        told a name at a time (a regular file of exactly that name, or
        a link to one; ``is_file`` asks the file system only where the
        entry's type is unknown, and only for a wanted name). A name the
        listing lacks may still be delta-stored. Heals the resident set
        as :meth:`_raw_present` does: a listed name is entered under the
        unlink-count rule of a ``stat`` outside ``_dir_mu``; an entry
        that was there BEFORE the listing began and that the listing
        lacks is dropped — so a name linked while the directory was
        being read is never forgotten."""
        keys = {d: bytes.fromhex(d) for d in wanted}
        with self._count_lock:
            seen = self._unlinks
            known = {d for d, k in keys.items() if k in self._resident}
        found: set[str] = set()
        try:
            with os.scandir(f"{self._root_str}/{sub}") as it:
                for e in it:
                    if e.name in wanted:
                        try:
                            if e.is_file():
                                found.add(e.name)
                        # unlinked under the look, or unreadable: what
                        # os.path.isfile calls absent
                        except OSError:  # dfslint: ignore[DFS007]
                            pass
        # no such directory: nothing of it is there
        except (FileNotFoundError, NotADirectoryError):
            pass
        new = found - known
        if new:
            with self._dir_mu[_stripe(sub)]:   # one directory: one lock
                for d in new:
                    self._remember(keys[d], seen)
        for d in known - found:
            self._forget(keys[d])
        if len(found) < len(wanted) and self._deltas_possible():
            found.update(d for d in wanted - found
                         if self._chain_resolves(d))
        if self._fsync:
            for d in found:
                # a name still owed its directory barrier gets it first
                self._settle(d, self._path_str(d))
        return found

    def look_stats(self) -> dict:
        """``/metrics`` ``durability.look*``: over the batches that
        looked at the disk (:meth:`has_many`), the names looked for by
        a ``stat``, the names answered from a listing, and the
        directories listed."""
        with self._count_lock:
            return {"lookStats": self._look_stats,
                    "lookListed": self._look_listed,
                    "lookListings": self._look_listings}

    def put(self, digest: str, data: bytes, verify: bool = True,
            sketch=None) -> bool:
        """Store a chunk. Returns False if it already existed (dedup hit).
        Idempotent and safe under concurrent identical writes: the
        visible write is an os.link of a temp file, which atomically
        FAILS if the chunk appeared meanwhile — so exactly one of two
        racing writers observes True and the cached count cannot
        double-count (content-addressed names make 'it already exists'
        equivalent to 'it holds the right bytes').

        With ``fsync`` on, the payload file is fsync'd before the link
        and the directory after it — the put is crash-durable when it
        returns (the fsync-before-ack contract, docs/chaos.md): payload
        durable → name visible → name durable → return. A dedup hit
        keeps the contract too: a name whose first writer has not issued
        its directory barrier yet is barriered by whoever meets it
        (``_settle``) before the answer.

        This is :meth:`put_batch` of one item — one write path.

        With the similarity plane attached (``self.sim``), an eligible
        new chunk may be stored as a DELTA against a resident similar
        base instead of raw — transparent to every reader via get().
        ``sketch`` optionally carries a precomputed min-hash so the
        plane need not re-sketch."""
        return self._put_items(
            [(digest, data)], verify,
            None if sketch is None else {digest: sketch})[0]

    def put_batch(self, items, verify: bool = True) -> list[bool]:
        """Store a batch of ``(digest, data)``; per item True = newly
        stored, False = dedup hit — the results of a :meth:`put` loop,
        written as a batch:

        a. per item, on the calling thread: fault hook, dedup hit on an
           existing file or delta, ``verify``, the similarity plane's
           delta path (one sketch launch for the whole batch); a digest
           met twice is written once;
        b. per new file: temp (``O_EXCL``), write, **payload fsync**,
           close — one descriptor at a time;
        c. every link temp → final name (a lost race is a dedup hit);
        d. **one fsync per distinct parent directory**, after the last
           link into it;
        e. the temps unlinked (on every error path too), the gauges.

        It returns only after (d): per chunk the order is ``put``'s —
        payload durable → name visible → name durable → return — and the
        directory barrier is shared by the files of one directory
        instead of issued per file. With durability off the same phases
        run without barriers. An exception fails the whole call; names
        already linked stay (content-addressed, fully written) and are
        barriered by the next put that meets them.

        With the similarity plane attached each raw write is a batch of
        its own: a later item may encode against an earlier one, which
        has to be resident by then."""
        items = list(items)
        sketches = self.sim.sketch_for_batch(self, items) \
            if self.sim is not None else None
        return self._put_items(items, verify, sketches)

    def _put_items(self, items, verify: bool, sketches) -> list[bool]:
        results = [False] * len(items)
        fresh: list[tuple[str, str, bytes]] = []   # raw writes owed
        fresh_at: list[int] = []                   # their place in items
        queued: set[str] = set()
        index = self.index
        hits = known = 0    # dedup hits on a raw name; known to the index
        # this call's phase clock: local floats, added to the store's
        # table once, when the call returns. The pre-check loop is ONE
        # pair of clock reads, less what _settle's barriers and the
        # loop's own raw writes (similarity plane) took inside it.
        ph = self._put_clock()
        in_loop = 0.0
        t_job = time.perf_counter()
        for i, (digest, data) in enumerate(items):
            if self.fault is not None:
                self.fault("put", digest)
            p = self._path_str(digest)
            if digest in queued:
                continue           # twice in one batch: written once
            # resident → a dedup hit, plane on or off; a miss of a
            # complete set → no raw file, nothing asked; else the look of
            # the mode: one stat (index off), isfile then the index
            key = bytes.fromhex(digest)
            resident, absent, seen = self._look_begins(key, True)
            if resident or (not absent and (
                    self._stat_raw(key, p, False, seen)
                    if index is None else os.path.isfile(p))):
                self._settle(digest, p, ph)
                hits += 1
                if resident:
                    known += 1     # linked or seen this life: recorded
                elif index is not None:
                    if index.lookup(digest):
                        known += 1
                        self._saw(key, seen)
                    else:
                        # dedup hit on a chunk the index forgot
                        # (crash-lost WAL buffer): heal here too — a
                        # repair push re-sending a restarted node its
                        # own chunks is exactly how that node's catalog
                        # re-enters the index (same ordering mutex
                        # discipline as has()'s backstop)
                        with self._dir_mu[key[0]]:
                            if os.path.isfile(p):
                                index.note_put(digest, defer_flush=True)
                                self._remember(key)
                        index.maybe_flush()
                continue
            if self._deltas_possible():
                with self._delta_mu:
                    if digest in self._delta_base:
                        continue   # present (as a delta): dedup hit
            if verify and sha256_hex(data) != digest:
                raise ValueError(
                    f"data does not match digest {digest[:12]}…")
            if self.sim is not None:
                enc = self.sim.encode_for_put(
                    self, digest, data,
                    sketch=sketches.get(digest) if sketches else None)
                stored = None
                if enc is not None:
                    stored = self._put_delta(digest, enc[0], enc[1],
                                             raw_len=len(data))
                if stored is None:
                    # no delta, or rolled back (base vanished
                    # mid-write): raw, and now — the next item may
                    # encode against this one
                    t_raw = time.perf_counter()
                    stored = self._write_raw([(digest, p, data)], ph)[0]
                    in_loop += time.perf_counter() - t_raw
                results[i] = stored
                continue
            queued.add(digest)
            fresh.append((digest, p, data))
            fresh_at.append(i)
        ph["precheckS"] = time.perf_counter() - t_job - in_loop \
            - ph["settleS"]
        if hits and index is not None:
            index.note_put_dedup(hits, known)
        if fresh:
            for i, new in zip(fresh_at, self._write_raw(fresh, ph)):
                results[i] = new
        ph["jobS"] = time.perf_counter() - t_job
        self._note_put(ph, len(items))
        return results

    @staticmethod
    def _put_clock() -> dict:
        """One put call's phase clock, at zero."""
        return {"newFiles": 0, "linkContended": 0, "jobS": 0.0,
                **dict.fromkeys(_PUT_PHASES, 0.0)}

    def _note_put(self, ph: dict, items: int) -> None:
        """A returned call's clock into the store's table: one lock
        take a call, not a file."""
        with self._count_lock:
            put = self._put
            put["jobs"] += 1
            put["items"] += items
            for key, v in ph.items():
                put[key] += v

    def put_stats(self) -> dict:
        """``/metrics`` ``durability.put``, the put job's phase clock
        (docs/observability.md "Unified metrics" has the table).
        ``jobs`` counts the calls that returned — a placement batch is
        up to four, one a write worker; a raw write from outside a
        batch (re-materialisation) is one of its own — ``items`` what
        they were given, ``newFiles`` the names they linked,
        ``linkContended`` the links among them whose directory's lock
        (``_dir_mu``) was held when asked for, ``jobS`` their wall time
        on the calling threads, and the ten ``_PUT_PHASES`` add up to
        it. ``linkS`` holds the book-keeping between two links too; the
        similarity plane's encode and delta write have no phase of
        their own and count in ``precheckS``. A phase's seconds include
        the thread's wait to take the interpreter lock back after its
        system call returned."""
        with self._count_lock:
            return {k: round(v, 6) if isinstance(v, float) else v
                    for k, v in self._put.items()}

    def _settle(self, digest: str, p: str, ph: dict | None = None) -> None:
        """Before a dedup hit on ``p`` is answered: if the name was
        linked by a put whose directory barrier is still owed (the
        window between phases (c) and (d) of another thread's batch),
        issue that barrier here. No waiting on the other thread; the
        fsync runs outside every lock. A put's pre-check hands its
        phase clock: the barrier's seconds are its ``settleS``."""
        if not self._fsync:
            return
        with self._count_lock:
            if digest not in self._unbarriered:
                return
        t0 = time.perf_counter()
        _fsync_path(os.path.dirname(p))
        with self._count_lock:
            self._dir_barriers += 1
            self._unbarriered.discard(digest)
        if ph is not None:
            ph["settleS"] += time.perf_counter() - t0

    def _write_raw(self, batch, ph: dict | None = None) -> list[bool]:
        """The raw-file write mechanics for ``(digest, path, data)``
        items, phases (b)–(e) of :meth:`put_batch` — shared by every
        put and by re-materialization, which must bypass the sim seam
        (re-encoding what it just reconstructed would loop).

        Timed into ``ph``, the calling put's phase clock
        (:meth:`put_stats`): a clock read at every switch of phase —
        around the system calls of a file, never inside them — so the
        phases add up to the call. Reached from outside a put, the call
        is a job of its own."""
        new = [False] * len(batch)
        temps: list[str] = []
        parents: dict[str, None] = {}   # owed a barrier, in link order
        nlinked = nbytes = barriers = 0
        own = ph is None
        if own:
            ph = self._put_clock()
        clock = time.perf_counter
        t_job = t = clock()
        try:
            for _, p, data in batch:
                parent = os.path.dirname(p)
                if parent not in self._dirs:   # one mkdir per subdir lifetime
                    os.makedirs(parent, exist_ok=True)
                    self._dirs.add(parent)
                # pid+sequence tmp names instead of mkstemp: uniqueness
                # within this store is all that is needed, and mkstemp's
                # random-name search measured real time at thousands of
                # puts per upload. O_EXCL collisions (a crash-leaked temp
                # from a previous run of the same pid — routine for PID-1
                # containers) just advance the sequence; the loop touches
                # nothing it did not create, so a concurrent writer's live
                # temp is never deleted.
                while True:
                    tmp = f"{parent}/.tmp-{os.getpid()}-{next(self._tmp_seq)}"
                    try:
                        fd = os.open(
                            tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
                        break
                    except FileExistsError:
                        continue
                temps.append(tmp)
                t, t0 = clock(), t
                ph["createS"] += t - t0
                try:
                    view = memoryview(data)
                    done = os.write(fd, view)
                    while done < len(view):    # short write: disk nearly full
                        done += os.write(fd, view[done:])
                    if self._fsync:
                        t, t0 = clock(), t
                        ph["writeS"] += t - t0
                        os.fsync(fd)           # payload durable BEFORE the name
                        t, t0 = clock(), t
                        ph["payloadFsyncS"] += t - t0
                finally:
                    os.close(fd)
                    t, t0 = clock(), t
                    ph["writeS"] += t - t0
            for k, (digest, p, data) in enumerate(batch):
                if self._fsync:
                    # entered BEFORE the link: whoever sees the name finds
                    # it owed a barrier (_settle). A lost race below is
                    # owed one as well — the winner's may not be out yet.
                    with self._count_lock:
                        self._unbarriered.add(digest)
                    parents[os.path.dirname(p)] = None
                key = bytes.fromhex(digest)
                mu = self._dir_mu[key[0]]
                t, t0 = clock(), t
                ph["linkS"] += t - t0      # the book-keeping between links
                if not mu.acquire(blocking=False):
                    # the directory's lock was held when asked for: a
                    # link, a delete or a look of the same directory
                    ph["linkContended"] += 1
                    mu.acquire()
                try:
                    t, t0 = clock(), t
                    ph["linkWaitS"] += t - t0
                    try:
                        os.link(temps[k], p)
                    except FileExistsError:
                        # dedup hit: the name is there, as a stat would say
                        self._remember(key)
                        continue
                    except OSError as e:
                        # filesystem without hard links: fall back to
                        # atomic rename. Loses the exactly-one-True race
                        # guarantee (both racers see True, count drifts by
                        # one until restart) but never loses data — rename
                        # is still atomic and content-addressed names make
                        # the overwrite idempotent. Only the no-hardlink
                        # errnos take the fallback; anything else (vanished
                        # tmp, EIO, and EXDEV — tmp is created in the
                        # target's OWN directory, so a cross-device link
                        # error means something anomalous that os.replace
                        # would also fail on, just with a less accurate
                        # traceback) stays loud with its real cause.
                        if e.errno not in (errno.EPERM, errno.EOPNOTSUPP,
                                           errno.ENOTSUP, errno.EMLINK):
                            raise
                        os.replace(temps[k], p)
                    if self.index is not None:
                        # recorded AFTER the link is visible (inside the
                        # ordering lock): a crash between the two leaves a
                        # false NEGATIVE — has()'s stat backstop covers
                        # it. The flush/compaction threshold runs AFTER
                        # the mutex drops (below) — a multi-second merge
                        # inside it would freeze every link of the
                        # directory.
                        self.index.note_put(digest, defer_flush=True)
                    self._remember(key)
                finally:
                    mu.release()
                new[k] = True
                nlinked += 1
                nbytes += len(data)
            t, t0 = clock(), t
            ph["linkS"] += t - t0
            if self._fsync:
                # a NAME is durable only once its directory block is:
                # link/rename ordered the visible state, the dirfd fsync
                # makes it survive power loss (payloads fsync'd above).
                # Once per directory, after the last link into it.
                for parent in parents:
                    _fsync_path(parent)
                    barriers += 1
                with self._count_lock:
                    self._fsyncs += nlinked
                    self._unbarriered.difference_update(
                        d for d, _, _ in batch)
                t, t0 = clock(), t
                ph["dirBarrierS"] += t - t0
        finally:
            for tmp in temps:
                try:
                    os.unlink(tmp)       # ours: the O_EXCL open succeeded
                # already consumed by os.replace on the no-hardlink path,
                # or re-leaked to the aged sweep — non-fatal cleanup
                except OSError:  # dfslint: ignore[DFS007]
                    pass
            with self._count_lock:
                self._dir_barriers += barriers
                if self._count is not None:
                    self._count += nlinked
                if self._bytes is not None:
                    self._bytes += nbytes
        t, t0 = clock(), t
        ph["unlinkS"] += t - t0
        if self.index is not None:
            self.index.maybe_flush()   # outside the ordering mutex
            ph["flushS"] += clock() - t
        ph["newFiles"] += nlinked
        if own:
            ph["jobS"] = clock() - t_job
            self._note_put(ph, len(batch))
        return new

    def _put_delta(self, digest: str, base_digest: str, blob: bytes,
                   raw_len: int) -> bool | None:
        """Store ``digest`` as a delta blob against ``base_digest``,
        with the same tmp + O_EXCL + link + fsync discipline as raw
        puts. Returns True (stored), False (lost the link race — the
        chunk is present), or None: the base vanished between the
        encoder's read and the pin registration below (a delete/GC
        completing in that window), so the write was rolled back and
        the caller must store raw. Once the pin IS registered (inside
        the ordering mutex delete() of the base takes: this is the one
        place that orders two digests, so it holds the locks of both
        directories, in ascending order), no later delete can remove
        the base."""
        parent = f"{self._deltas_root}/{digest[:2]}"
        if parent not in self._dirs:
            os.makedirs(parent, exist_ok=True)
            self._dirs.add(parent)
        while True:
            tmp = f"{parent}/.tmp-{os.getpid()}-{next(self._tmp_seq)}"
            try:
                fd = os.open(tmp,
                             os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
                break
            except FileExistsError:
                continue
        dp = f"{parent}/{digest}"
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
                if self._fsync:
                    f.flush()
                    os.fsync(f.fileno())
            lo, hi = sorted((_stripe(digest), _stripe(base_digest)))
            with self._dir_mu[lo], (self._dir_mu[hi] if hi != lo
                                    else contextlib.nullcontext()):
                try:
                    os.link(tmp, dp)
                except FileExistsError:
                    return False   # racing identical delta: present
                except OSError as e:
                    # same no-hardlink fallback story as _write_raw
                    if e.errno not in (errno.EPERM, errno.EOPNOTSUPP,
                                       errno.ENOTSUP, errno.EMLINK):
                        raise
                    os.replace(tmp, dp)
                with self._delta_mu:
                    self._delta_base[digest] = base_digest
                    self._delta_refs[base_digest] = \
                        self._delta_refs.get(base_digest, 0) + 1
                    self._have_deltas = True
                if self.index is not None:
                    self.index.note_put(digest, defer_flush=True)
            if self._fsync:
                _fsync_path(parent)
                with self._count_lock:
                    self._fsyncs += 1
                    self._dir_barriers += 1
        finally:
            try:
                os.unlink(tmp)       # ours: the O_EXCL open succeeded
            # already consumed by os.replace on the no-hardlink path, or
            # re-leaked to the aged sweep — either way non-fatal cleanup
            except OSError:  # dfslint: ignore[DFS007]
                pass
        with self._count_lock:
            if self._count is not None:
                self._count += 1
            if self._bytes is not None:
                self._bytes += len(blob)
        if not self._chain_resolves(base_digest):
            # the base was deleted between the encoder reading it and
            # the pin above becoming visible: roll back and store raw
            self._drop_delta(digest)
            return None
        if self.sim is not None:
            # crash seam: delta linked + durable, index record still in
            # the WAL buffer and the band-log append unfsynced — the
            # false-NEGATIVE window chaos must prove harmless
            self.sim.maybe_crash("sim.after_delta_write")
            self.sim.note_delta_stored(raw_len, len(blob))
        if self.index is not None:
            self.index.maybe_flush()   # outside the ordering mutex
        return True

    def _drop_delta(self, digest: str) -> bool:
        """Unlink a delta file and release its base pin (rollback,
        corruption, re-materialize completion, or delete of a dead
        delta). The index delete-record is skipped when the digest is
        still raw-resident — re-materialize leaves the chunk present."""
        dp = self._delta_path_str(digest)
        blob_len = 0
        with self._dir_mu[_stripe(digest)]:
            with self._delta_mu:
                base = self._delta_base.pop(digest, None)
                if base is not None:
                    n = self._delta_refs.get(base, 0) - 1
                    if n > 0:
                        self._delta_refs[base] = n
                    else:
                        self._delta_refs.pop(base, None)
            try:
                blob_len = os.path.getsize(dp)
            # already gone (a racing drop): map cleanup above is all
            # that was left to do
            except OSError:  # dfslint: ignore[DFS007]
                return False
            if self.index is not None \
                    and not os.path.isfile(self._path_str(digest)):
                self.index.note_delete(digest, defer_flush=True)
            try:
                os.unlink(dp)
            except FileNotFoundError:
                return False
        with self._count_lock:
            if self._count is not None:
                self._count -= 1
            if self._bytes is not None:
                self._bytes -= blob_len
        if self.sim is not None:
            self.sim.note_delta_dropped(blob_len)
        if self.index is not None:
            self.index.maybe_flush()
        return True

    def _rematerialize(self, digest: str, data: bytes) -> None:
        """Promote a hot delta back to a raw file (read-count policy in
        SimPlane.note_delta_read). Raw is written FIRST, the delta
        unlinked after — a crash between the two leaves both, raw wins
        on read, and _prime_delta_maps completes the unlink next boot."""
        p = self._path_str(digest)
        if not os.path.isfile(p):
            self._write_raw([(digest, p, data)])
        if self.sim is not None:
            self.sim.maybe_crash("sim.after_rematerialize")
        self._drop_delta(digest)

    def fsync_count(self) -> int:
        """Chunk files made durable so far — payload fsync'd, linked,
        directory fsync'd; one per file, counted before its put returns
        (``/metrics`` ``durability.fsyncs``)."""
        with self._count_lock:
            return self._fsyncs

    def barrier_dirs(self) -> int:
        """fsync every chunk directory once; returns how many. The BOOT
        barrier (``NodeStore.boot_sweep``): ``_unbarriered`` dies with
        the process, so a life killed between phases (c) and (d) of a
        batch leaves names this life would answer as dedup hits with no
        directory barrier ever issued. Whatever names survived to this
        life are made durable here, before the servers listen. A no-op
        with durability off."""
        if not self._fsync:
            return 0
        dirs = [os.fspath(sub) for sub in self.root.iterdir()
                if sub.is_dir() and len(sub.name) == 2]
        for d in dirs:
            _fsync_path(d)
        with self._count_lock:
            self._dir_barriers += len(dirs)
        return len(dirs)

    def dir_barrier_count(self) -> int:
        """Directory fsyncs issued so far (``durability.dirBarriers``):
        one per distinct directory per batch, plus the few a dedup hit
        issues for a name whose first writer has not yet."""
        with self._count_lock:
            return self._dir_barriers

    def get(self, digest: str) -> bytes | None:
        if self.fault is not None:
            self.fault("get", digest)
        try:
            with open(self._path_str(digest), "rb") as f:
                return f.read()
        except FileNotFoundError:
            self._forget(bytes.fromhex(digest))    # no raw file: no entry
            if not self._deltas_possible():
                return None
            return self._get_delta(digest, 0)

    def _get_delta(self, digest: str, depth: int) -> bytes | None:
        """Transparent delta reconstruction: read the delta blob,
        resolve the base (recursively — bases may themselves be
        deltas, bounded), apply, and verify sha256 == digest before
        serving (DFS004: the boundary check rides sha256_hex).
        Structural damage or a digest mismatch drops the delta exactly
        like a corrupt raw chunk — scrub/repair re-fetches from
        replicas. A missing base is reported ABSENT (not corrupt):
        scrub heals it from replicas first (docs/similarity.md)."""
        if depth > 64:
            return None
        try:
            with open(self._delta_path_str(digest), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        try:
            base_d, _out_len = _parse_delta_header(blob)
        except ValueError:
            self._drop_delta(digest)
            return None
        try:
            with open(self._path_str(base_d), "rb") as f:
                base = f.read()
        except FileNotFoundError:
            base = self._get_delta(base_d, depth + 1)
        if base is None:
            if self.sim is not None:
                self.sim.note_missing_base()
            return None
        try:
            out = _apply_delta(blob, base)
        except ValueError:
            self._drop_delta(digest)
            return None
        if sha256_hex(out) != digest:
            self._drop_delta(digest)
            return None
        if depth == 0 and self.sim is not None \
                and self.sim.note_delta_read(digest):
            self._rematerialize(digest, out)
        return out

    def delete(self, digest: str) -> bool:
        p = self._path_str(digest)
        key = bytes.fromhex(digest)
        try:
            # size BEFORE unlink, for the cached byte gauge; losing the
            # stat→unlink race to a concurrent delete means the unlink
            # raises and neither gauge moves — same story as put's
            # exactly-one-True link race
            with self._dir_mu[key[0]]:
                if self._deltas_possible():
                    # pinned base: resident deltas reconstruct through
                    # this digest — refused until the dependents die or
                    # re-materialize. Checked INSIDE the ordering mutex:
                    # _put_delta registers its pin under the same lock
                    # (it holds its base's beside its own), so a racing
                    # delta write either sees the base survive or rolls
                    # itself back, never a broken chain
                    with self._delta_mu:
                        if self._delta_refs.get(digest, 0) > 0:
                            return False
                size = os.path.getsize(p)
                if self.index is not None:
                    # recorded BEFORE the unlink (written through, not
                    # buffered): a crash between the two leaves a false
                    # negative for a present chunk — the safe
                    # direction; the reverse order could persist a
                    # stale "present" for vanished bytes
                    self.index.note_delete(digest, defer_flush=True)
                # out of the resident set BEFORE the unlink, and the
                # unlink counted once it ended: a stat that saw the name
                # just before enters nothing after this (_raw_present)
                with self._count_lock:
                    self._resident.discard(key)
                try:
                    os.unlink(p)
                finally:
                    with self._count_lock:
                        self._unlinks += 1
            with self._count_lock:
                if self._count is not None:
                    self._count -= 1
                if self._bytes is not None:
                    self._bytes -= size
            if self.index is not None:
                self.index.maybe_flush()   # outside the ordering mutex
            return True
        except FileNotFoundError:
            self._forget(key)              # no raw file: no entry
            if self._deltas_possible():
                return self._drop_delta(digest)
            return False

    def count(self) -> int:
        """Number of stored chunks, O(1) after the first call. The full
        ``digests()`` scan behind the naive count made the internal
        ``health`` op scale with store size — every peer probes it every
        few seconds, which measured ~40% of a single-core cluster's read
        throughput at a 175K-chunk store. Initialized by one scan, then
        maintained by put/delete (external writes to the directory, or
        puts racing the very first scan, can skew it by a few until
        restart — acceptable for a diagnostics field). The priming scan
        runs OUTSIDE the lock so a big store's first probe cannot stall
        concurrent put/delete workers behind it."""
        with self._count_lock:
            primed = self._count
        if primed is None:
            # priming scan stays OUTSIDE the lock (a big store's first
            # probe must not stall put/delete workers behind it); the
            # peek above runs under it — an unlocked peek raced the
            # worker-side writes (dfslint DFS008)
            n = len(self.digests())
            with self._count_lock:
                if self._count is None:
                    self._count = n
        with self._count_lock:
            return self._count

    def digests(self, complete: bool = False) -> list[str]:
        """Every digest the store holds, raw names first, by a listing of
        every shard directory. ``complete`` (``NodeStore.boot_sweep``:
        before the servers listen, so nothing is in flight): the raw
        names of this listing — not the delta tree's — seed the resident
        set, which is complete from here on (:meth:`_establish`)."""
        with self._count_lock:
            seen = self._unlinks
        out = []
        hexdigits = set("0123456789abcdef")
        for sub in sorted(self.root.iterdir()) if self.root.is_dir() else []:
            if sub.is_dir():
                # filter strays (e.g. crash-leaked .tmp-* from _atomic_write)
                # — which also skips the deltas/ fan-out (2-hex names)
                out.extend(sorted(
                    p.name for p in sub.iterdir()
                    if len(p.name) == 64 and set(p.name) <= hexdigits))
        if complete:
            self._establish(out, seen)
        if self._deltas_possible():
            raw = set(out)
            droot = Path(self._deltas_root)
            for sub in sorted(droot.iterdir()) if droot.is_dir() else []:
                if sub.is_dir():
                    out.extend(sorted(
                        p.name for p in sub.iterdir()
                        if len(p.name) == 64 and set(p.name) <= hexdigits
                        and p.name not in raw))
        return out

    def total_bytes(self) -> int:
        total = 0
        for d in self.digests():
            try:
                total += os.path.getsize(self._path_str(d))
            # delta-stored (or deleted mid-scan): count the delta
            # file's on-disk bytes instead — this gauge measures
            # footprint, not logical size
            except OSError:  # dfslint: ignore[DFS007]
                try:
                    total += os.path.getsize(self._delta_path_str(d))
                # vanished between the listing and the stat: a racing
                # delete won — the ordinary census-race outcome
                except OSError:  # dfslint: ignore[DFS007]
                    pass
        return total

    def bytes_total(self) -> int:
        """CAS payload bytes, O(1) after the first call — the capacity
        gauge the census history sampler reads every ~10 s, which must
        never re-pay ``total_bytes()``'s stat-per-chunk scan (the same
        scaling trap ``count()`` already documents). Primed by one
        ``inventory()`` pass outside the lock, then maintained by
        put/delete; the same external-writes skew caveat as the count
        applies (re-primed on restart)."""
        with self._count_lock:
            primed = self._bytes
        if primed is None:
            # same locked-peek/unlocked-scan split as count()
            n = self.inventory()["bytes"]   # primes both gauges
            with self._count_lock:
                if self._bytes is None:
                    self._bytes = n
        with self._count_lock:
            return self._bytes

    # digest-prefix census buckets: 2 hex chars = 256 buckets, matching
    # the on-disk fan-out (chunks/<d[:2]>/<digest>); the bucket hash is
    # the XOR of each member digest's leading 64 bits — order-free,
    # incremental, and computable from a manifest walk alone, so a
    # coordinator can compare EXPECTED bucket membership against this
    # observed summary without moving any digest list (obs/census.py)
    PREFIX_HEX = 2
    STAMP_HEX = 16

    @staticmethod
    def digest_stamp(digest: str) -> int:
        return int(digest[:ChunkStore.STAMP_HEX], 16)

    def inventory(self, list_prefixes=None, list_cap: int = 4096) -> dict:
        """Bounded, bucketed CAS census: per digest-prefix bucket
        ``[count, bytes, xor-hash]`` plus store totals — one readdir +
        stat pass, run OFF the event loop via the async CAS tier
        (:meth:`AsyncChunkStore.inventory`). Also primes the
        count/bytes gauges.

        With ``list_prefixes`` the walk is RESTRICTED to exactly those
        buckets and returns only their sorted member-digest lists
        (capped at ``list_cap`` each, ``listTruncated`` set when a cap
        bit) — the census drill-down, which already has the full
        summaries from its first pass and must not re-pay a whole-store
        scan (or re-pay stat: names need readdir alone). Summary keys
        stay present but zero in that mode; the gauges are untouched."""
        hexdigits = set("0123456789abcdef")
        if list_prefixes is not None:
            listed: dict[str, list[str]] = {}
            truncated = False
            for prefix in sorted(set(list_prefixes)):
                sub = self.root / prefix
                pool = {
                    d for d in (os.listdir(sub) if sub.is_dir() else [])
                    if len(d) == 64 and set(d) <= hexdigits}
                if self._deltas_possible():
                    dsub = Path(self._deltas_root) / prefix
                    pool.update(
                        d for d in
                        (os.listdir(dsub) if dsub.is_dir() else [])
                        if len(d) == 64 and set(d) <= hexdigits)
                names = sorted(pool)
                if len(names) > list_cap:
                    names = names[:list_cap]
                    truncated = True
                listed[prefix] = names
            return {"buckets": {}, "chunks": 0, "bytes": 0,
                    "listed": listed, "listTruncated": truncated}
        buckets: dict[str, list] = {}
        total_n = total_b = 0
        for sub in sorted(self.root.iterdir()) if self.root.is_dir() else []:
            if not sub.is_dir() or len(sub.name) != self.PREFIX_HEX \
                    or not set(sub.name) <= hexdigits:
                continue
            b = [0, 0, 0]
            for p in sub.iterdir():
                d = p.name
                if len(d) != 64 or not set(d) <= hexdigits:
                    continue   # crash-leaked .tmp-* and strays
                try:
                    size = p.stat().st_size
                # stat racing a concurrent delete/GC: the vanished chunk
                # is simply not in this census pass — losing the race is
                # the ordinary case, not a failure to surface
                except OSError:  # dfslint: ignore[DFS007]
                    continue
                b[0] += 1
                b[1] += size
                b[2] ^= self.digest_stamp(d)
            if b[0]:
                buckets[sub.name] = b
                total_n += b[0]
                total_b += b[1]
        if self._deltas_possible():
            droot = Path(self._deltas_root)
            for sub in sorted(droot.iterdir()) if droot.is_dir() else []:
                if not sub.is_dir() or len(sub.name) != self.PREFIX_HEX \
                        or not set(sub.name) <= hexdigits:
                    continue
                for p in sub.iterdir():
                    d = p.name
                    if len(d) != 64 or not set(d) <= hexdigits:
                        continue
                    if os.path.isfile(self._path_str(d)):
                        continue   # mid-re-materialize: raw pass counted it
                    if not self._chain_resolves(d):
                        continue   # broken chain: not reconstructible —
                        # absent for census purposes (scrub heals first)
                    try:
                        size = p.stat().st_size
                    # same stat-vs-delete race as the raw pass
                    except OSError:  # dfslint: ignore[DFS007]
                        continue
                    b = buckets.setdefault(sub.name, [0, 0, 0])
                    b[0] += 1
                    b[1] += size
                    b[2] ^= self.digest_stamp(d)
                    total_n += 1
                    total_b += size
        with self._count_lock:
            # unconditional: the full scan is ground truth at scan time,
            # so every census/df heals whatever skew the gauges carried
            # (the count()-documented priming race, external writes) —
            # at worst re-introducing the same bounded concurrent-put
            # window instead of drifting until restart
            self._count = total_n
            self._bytes = total_b
        return {"buckets": buckets, "chunks": total_n, "bytes": total_b}

    def sweep_tmp(self, max_age_s: float = _TMP_SWEEP_AGE_S) -> int:
        """Reclaim crash-leaked ``.tmp-*`` files. ``put()`` only ever
        unlinks temps it created in THIS process; a crash between open
        and unlink leaks one, and the pid+sequence naming never revisits
        it. The hour age gate is load-bearing at RUNTIME: delete-
        triggered GC runs while puts run in thread workers, and sweeping
        a live temp between its open and os.link would fail that upload
        — a leaked temp older than an hour cannot belong to any
        in-flight put. The only caller allowed to lower ``max_age_s``
        is the BOOT sweep (``NodeStore.boot_sweep``), which runs before
        the servers start, when no put can be in flight — every temp on
        disk then belongs to the previous (crashed) life."""
        dirs = [sub for sub in
                (self.root.iterdir() if self.root.is_dir() else [])
                if sub.is_dir()]
        if self._deltas_possible():
            droot = Path(self._deltas_root)
            dirs.extend(sub for sub in
                        (droot.iterdir() if droot.is_dir() else [])
                        if sub.is_dir())
        return _sweep_tmp_files(dirs, max_age_s)


class ManifestStore:
    """Per-node manifest directory; every node holds every manifest, exactly
    like the reference's announce-to-all model (StorageNode.java:313-350).

    ``fsync=True``: manifest saves and tombstone writes are fsync'd
    (file + directory) before returning — the manifest write is what
    ACKS an upload, so it must be crash-durable exactly like the chunks
    it references (fsync-before-ack, docs/chaos.md)."""

    def __init__(self, root: Path, fsync: bool = False) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._fsync = bool(fsync)
        # serializes save() against delete() PER FILE ID: since r13
        # both run on to_thread workers (fsync barriers must not block
        # the event loop), so the loop no longer serializes save's
        # is_tombstoned-check-then-write against a concurrent tombstone
        # write — without this a delete landing inside that window
        # would be resurrected by the late save. STRIPED, not global:
        # the lock is held across the save's fsync barriers, and
        # announce-to-all means every node saves every upload's
        # manifest — one global mutex would queue every concurrent
        # ack's disk barrier behind one file's.
        self._mu = tuple(threading.Lock() for _ in range(16))
        self._tmp_seq = itertools.count()  # cheap unique tmp names

    def _lock(self, file_id: str) -> threading.Lock:
        return self._mu[int(file_id[:2], 16) & 15]

    def _path(self, file_id: str) -> Path:
        if not is_hex_digest(file_id):
            raise ValueError(f"bad file_id {file_id!r}")
        return self.root / f"{file_id}.json"

    def _tomb_path(self, file_id: str) -> Path:
        if not is_hex_digest(file_id):
            raise ValueError(f"bad file_id {file_id!r}")
        return self.root / f"{file_id}.tomb"

    def is_tombstoned(self, file_id: str) -> bool:
        return self._tomb_path(file_id).exists()

    def clear_tombstone(self, file_id: str) -> None:
        """One unlink (``save(fresh=True)`` makes it under the id's
        lock, in its worker thread — never an event loop)."""
        self._tomb_path(file_id).unlink(missing_ok=True)

    def tombstones(self) -> list[str]:
        """File ids known deleted (hex-validated — a stray file in the
        manifests dir must not poison peers' anti-entropy). Tombstones
        persist (and replicate via repair anti-entropy) so a node that
        slept through a delete cannot resurrect the file from its stale
        manifest — the reference's announce-to-all model has exactly that
        hole for *creates* already (SURVEY.md §3.4: best-effort, no
        anti-entropy) and no delete at all (§2.5(5))."""
        return sorted(p.stem for p in self.root.glob("*.tomb")
                      if is_hex_digest(p.stem))

    def save(self, m: Manifest, mtime: float | None = None, *,
             fresh: bool = False, text: str | None = None) -> bool:
        """Persist a manifest; refused (False) when the file is
        tombstoned, so late announces cannot resurrect a deleted file.

        ``fresh`` marks the save of an upload in progress (the
        coordinator's commit, a peer's ``announce`` with ``fresh``): a
        new upload resurrects deleted content on purpose — without this
        a content-derived file id would be unuploadable after one
        delete — so the tombstone is cleared HERE, under the id's lock
        and in the caller's worker thread, and a save that has just
        cleared it does not ask whether one exists. ``text`` is
        ``m.to_json()`` where the caller has made it already (the
        coordinator announces the same text).

        ``mtime`` carries the ORIGIN write time when a manifest is being
        ADOPTED from a peer (anti-entropy / download fallback): the file
        mtime is the LWW ordering side against tombstone timestamps, and
        stamping adoption time instead would make an adopted stale
        manifest look newer than a legitimate delete."""
        with self._lock(m.file_id):   # atomic vs delete() — __init__
            if fresh:
                self.clear_tombstone(m.file_id)
            elif self.is_tombstoned(m.file_id):
                return False
            p = os.fspath(self._path(m.file_id))
            self._replace(p, (m.to_json() if text is None
                              else text).encode())
            if mtime is not None:
                os.utime(p, (mtime, mtime))
                if self._fsync:
                    # the mtime IS the LWW ordering side against
                    # tombstones — a crash reverting it to the (newer)
                    # write time would make this adopted manifest beat
                    # a legitimate delete; utime is metadata the write
                    # fsync above did not cover
                    _fsync_path(p)
            return True

    def _replace(self, path: str, data: bytes) -> None:
        """A manifest's durable replace in the calls it needs and no
        other — temp (``O_EXCL``) → write → fsync → close → replace →
        the directory's barrier: payload durable, name visible, name
        durable, return. ``_atomic_write`` beside it pays a ``makedirs``
        of a root ``__init__`` made, ``mkstemp``'s name search and
        ``fdopen``'s ``fstat`` for every save, and every node saves
        every upload's manifest. Temp names as ``ChunkStore._write_raw``
        makes them (the comment there says why not ``mkstemp``);
        ``sweep_tmp`` reclaims one a crash leaves."""
        root = os.fspath(self.root)
        while True:
            tmp = f"{root}/.tmp-{os.getpid()}-{next(self._tmp_seq)}"
            try:
                fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL,
                             0o600)
                break
            except FileExistsError:
                continue
        try:
            try:
                view = memoryview(data)
                done = os.write(fd, view)
                while done < len(view):   # short write: disk nearly full
                    done += os.write(fd, view[done:])
                if self._fsync:
                    os.fsync(fd)          # payload durable BEFORE the name
            finally:
                os.close(fd)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            # cleanup inside an unwinding write: the original error
            # re-raises below; a temp left here the aged sweep reclaims
            except OSError:  # dfslint: ignore[DFS007]
                pass
            raise
        if self._fsync:
            _fsync_path(root)

    def ids(self) -> list[str]:
        """File ids present, from filenames alone — no reads/parses (the
        anti-entropy exchange runs every repair cycle on every node)."""
        return sorted(p.stem for p in self.root.glob("*.json")
                      if is_hex_digest(p.stem))

    def load(self, file_id: str) -> Manifest | None:
        try:
            return Manifest.from_json(self._path(file_id).read_bytes())
        except FileNotFoundError:
            return None

    def list(self) -> list[Manifest]:
        """All known files — backs ``GET /files`` the way the reference's
        manifest-dir scan does (StorageNode.java:364-393)."""
        out = []
        for p in sorted(self.root.glob("*.json")):
            try:
                out.append(Manifest.from_json(p.read_bytes()))
            except (ValueError, KeyError):
                continue  # skip corrupt manifest rather than failing the listing
        return out

    def delete(self, file_id: str, ts: float | None = None) -> bool:
        """Remove a manifest, leaving a persistent timestamped tombstone
        (written first — crash between the two steps errs toward delete).
        The timestamp orders deletes against re-uploads in anti-entropy
        (last-writer-wins; wall clocks, the usual LWW skew caveat).
        ``ts`` carries the ORIGIN deletion time when a tombstone is being
        propagated — re-stamping with the local apply time would advance
        the timestamp as it gossips until it postdates (and destroys) a
        legitimate re-upload."""
        with self._lock(file_id):   # atomic vs save() — see __init__
            # two-step sequence without a crash point: the tombstone
            # lands BEFORE the manifest unlink precisely so a kill -9
            # between them errs toward delete (the acked operation) —
            # the stale manifest is masked by is_tombstoned and swept
            # by anti-entropy; no window loses an ack
            # dfslint: ignore[DFS013]
            _atomic_write(self._tomb_path(file_id),
                          json.dumps({"ts": time.time() if ts is None
                                      else float(ts)}).encode(),
                          fsync=self._fsync)
            try:
                self._path(file_id).unlink()
                return True
            except FileNotFoundError:
                return False

    def tombstone_ts(self, file_id: str) -> float | None:
        """Deletion timestamp of a tombstone, or None if not tombstoned
        (falls back to file mtime for unreadable tombstone bodies)."""
        p = self._tomb_path(file_id)
        try:
            return float(json.loads(p.read_bytes())["ts"])
        except FileNotFoundError:
            return None
        except (ValueError, KeyError, TypeError):
            try:
                return p.stat().st_mtime
            except FileNotFoundError:
                return None

    def sweep_tmp(self, max_age_s: float = _TMP_SWEEP_AGE_S) -> int:
        """Reclaim crash-leaked temps (a crash between a save's or a
        tombstone write's create and its replace) — same hour age gate
        as the chunk store (and the same boot-sweep exception)."""
        return _sweep_tmp_files([self.root], max_age_s)

    def mtime(self, file_id: str) -> float | None:
        """Manifest file mtime — the 'written at' ordering side of
        last-writer-wins against tombstone timestamps."""
        try:
            return self._path(file_id).stat().st_mtime
        except FileNotFoundError:
            return None


class NodeStore:
    """A node's complete on-disk state: ``<root>/chunks`` + ``<root>/manifests``.
    Survives restarts, matching the reference's durability claim
    (README.md:179)."""

    def __init__(self, data_root: Path, node_id: int,
                 fsync: bool = False) -> None:
        self.root = Path(data_root) / f"node-{node_id}"
        self.chunks = ChunkStore(self.root / "chunks", fsync=fsync)
        self.manifests = ManifestStore(self.root / "manifests",
                                       fsync=fsync)

    def boot_sweep(self) -> dict:
        """Crash-recovery reconciliation, run ONCE at node start before
        the servers listen (so nothing is in flight): reclaim every
        crash-leaked temp regardless of age (they all belong to the
        previous life), and run the AGED orphan GC — a crash between
        CAS put and manifest write leaves durable chunks no manifest
        references, which are exactly the aborted-stream orphans the
        aged path already reclaims. The 1h age is kept even at boot:
        a young orphan may belong to a manifest announced while this
        node was down, which manifest anti-entropy adopts on the first
        repair cycle — deleting it here would force a re-fetch.

        With durability on, every chunk directory is fsync'd once
        (``ChunkStore.barrier_dirs``): a previous life killed between a
        batch's links and its directory barriers left names that no
        barrier covers and that this life cannot tell from durable
        ones — after this sweep every name on disk is durable, so a
        dedup hit on it may be acked.

        The listing the orphan GC makes is the one moment at which this
        process knows every name in its chunk directory and nothing can
        change it: the chunk store's resident set is seeded from it and
        is COMPLETE from here on (``ChunkStore.digests``), so the put
        pre-check and placement's probes hear "absent" from memory. The
        orphans deleted next leave the set through ``delete``, as any
        name does."""
        tmps = self.chunks.sweep_tmp(max_age_s=0.0) \
            + self.manifests.sweep_tmp(max_age_s=0.0)
        barriers = self.chunks.barrier_dirs()
        orphans = self.gc(min_age_s=3600.0, complete=True)
        return {"tmps": tmps, "orphans": len(orphans),
                "dirBarriers": barriers}

    def gc(self, min_age_s: float = 0.0,
           complete: bool = False) -> list[str]:
        """Delete chunks referenced by no manifest (the reference has no
        delete/GC at all — SURVEY.md §2.5(5)). Returns deleted digests.

        ``min_age_s`` spares recently-written chunks: uploads are
        manifest-LAST, so an in-flight upload's chunks are unreferenced
        until it commits — the periodic orphan sweep (repair loop) passes
        a generous age so it only reclaims chunks from genuinely
        abandoned streams (aborted chunked uploads), never from a live
        one. Delete-triggered GC keeps age 0: explicit user intent.
        ``complete``: :meth:`boot_sweep`'s word that nothing is in
        flight, handed to the listing (``ChunkStore.digests``)."""
        live: set[str] = set()
        for m in self.manifests.list():
            live.update(m.all_digests())   # incl. erasure parity chunks
        return self.sweep_orphans(self.chunks.digests(complete), live,
                                  min_age_s)

    def _with_delta_bases(self, live: set[str]) -> set[str]:
        """Delta-base pinning (similarity plane): a live delta-stored
        chunk reconstructs through its base chain, so every base under
        a live delta is live too — GC'ing one would break reads of a
        still-referenced file. chunks.delete()'s pin refusal backs this
        up; expanding the live set keeps the dead list honest instead
        of relying on refusals."""
        if self.chunks.delta_count():
            for d in list(live):
                cur = d
                for _ in range(64):
                    base = self.chunks.delta_base(cur)
                    if base is None:
                        break
                    live.add(base)
                    cur = base
        return live

    def sweep_orphans(self, listing, live: set[str], min_age_s: float,
                      named_since=None) -> list[str]:
        """:meth:`gc` over a listing and a live set the caller already
        has (the repair cycle's: both as old as its pass): delete the
        digests of ``listing`` that ``live`` does not name and that
        pass the age gate. ``named_since()`` is asked once there is
        something to delete and just before it is deleted: the digests
        named by manifests saved after ``live`` was taken — a manifest
        committed meanwhile protects its chunks as it would from a
        :meth:`gc` that began now."""
        live = self._with_delta_bases(live)
        cutoff = time.time() - min_age_s
        dead = []
        for d in listing:
            if d in live:
                continue
            if min_age_s > 0:
                try:
                    st = self.chunks._path(d).stat()
                except FileNotFoundError:
                    try:   # delta-stored: age-gate on the delta file
                        st = os.stat(self.chunks._delta_path_str(d))
                    except FileNotFoundError:
                        continue
                if st.st_mtime > cutoff:
                    continue
            dead.append(d)
        if dead and named_since is not None:
            fresh = self._with_delta_bases(named_since())
            dead = [d for d in dead if d not in fresh]
        # dead deltas first: deleting one releases its base pin, so a
        # dead base in the SAME pass is reclaimable instead of being
        # refused until the next cycle
        dead.sort(key=lambda d: self.chunks.delta_base(d) is None)
        sim = self.chunks.sim
        if sim is not None and dead:
            # crash seam: live + pinned sets computed, nothing deleted
            # yet — a kill here must lose no reconstructible chunk
            sim.maybe_crash("sim.before_base_gc")
        deleted: list[str] = []
        pending = dead
        while pending:
            # fixpoint over pin refusals: a chain of dead deltas
            # releases its pins one link per sweep — retry until a
            # sweep frees nothing (then the survivors are pinned by
            # LIVE deltas, i.e. not actually dead)
            nxt = []
            for d in pending:
                if self.chunks.delete(d):
                    deleted.append(d)
                elif self.chunks.delta_pinned(d):
                    nxt.append(d)
            if len(nxt) == len(pending):
                break
            pending = nxt
        # hour-gated: never races a live put or manifest write
        self.chunks.sweep_tmp()
        self.manifests.sweep_tmp()
        return deleted
