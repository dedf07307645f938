"""Content-addressed store + manifest v2 + fixed fragmenter unit tests."""

import hashlib

import pytest

from dfs_tpu.fragmenter.fixed import FixedFragmenter
from dfs_tpu.meta.manifest import ChunkRef, Manifest
from dfs_tpu.store.cas import ChunkStore, NodeStore
from dfs_tpu.utils.hashing import sha256_hex


def test_fixed_fragmenter_reference_semantics():
    """Split rule from StorageNode.java:140-155: base = total/parts, first
    total%parts fragments get +1 byte."""
    data = bytes(range(23))
    chunks = FixedFragmenter(parts=5).chunk(data)
    assert [c.length for c in chunks] == [5, 5, 5, 4, 4]
    assert [c.offset for c in chunks] == [0, 5, 10, 15, 19]
    for c in chunks:
        assert c.digest == hashlib.sha256(
            data[c.offset:c.offset + c.length]).hexdigest()


def test_fixed_fragmenter_tiny_and_empty(example_files):
    """Zero-byte fragments for tiny files (SURVEY.md §2.5(8))."""
    chunks = FixedFragmenter(parts=5).chunk(b"ab")
    assert [c.length for c in chunks] == [1, 1, 0, 0, 0]
    chunks = FixedFragmenter(parts=5).chunk(b"")
    assert [c.length for c in chunks] == [0] * 5
    assert all(c.digest == sha256_hex(b"") for c in chunks)


def test_manifest_roundtrip(example_files):
    data = example_files["id.jpg"]
    m = FixedFragmenter(parts=5).manifest(data, name="id.jpg")
    m2 = Manifest.from_json(m.to_json())
    assert m2 == m
    assert m2.file_id == sha256_hex(data)
    assert m2.total_chunks == 5


def test_manifest_validates_coverage():
    with pytest.raises(ValueError):
        Manifest(file_id="0" * 64, name="x", size=10, fragmenter="fixed",
                 chunks=(ChunkRef(0, 0, 5, "a" * 64),))


def test_chunk_store_put_get_dedup(tmp_path):
    cs = ChunkStore(tmp_path / "chunks")
    data = b"hello chunk"
    d = sha256_hex(data)
    assert cs.put(d, data) is True
    assert cs.put(d, data) is False  # dedup hit
    assert cs.get(d) == data
    assert cs.has(d)
    assert cs.get("f" * 64) is None
    with pytest.raises(ValueError):
        cs.put("a" * 64, b"mismatched")
    with pytest.raises(ValueError):
        cs.get("not-a-digest")


def test_node_store_gc(tmp_path, example_files):
    ns = NodeStore(tmp_path, node_id=1)
    data = example_files["pag1.html"]
    m = FixedFragmenter(parts=3).manifest(data, name="pag1.html")
    for c in m.chunks:
        ns.chunks.put(c.digest, data[c.offset:c.offset + c.length])
    ns.manifests.save(m)
    orphan = sha256_hex(b"orphan")
    ns.chunks.put(orphan, b"orphan")
    dead = ns.gc()
    assert dead == [orphan]
    assert all(ns.chunks.has(c.digest) for c in m.chunks)

    # restart durability (reference claim README.md:179)
    ns2 = NodeStore(tmp_path, node_id=1)
    assert ns2.manifests.load(m.file_id) == m
    got = b"".join(ns2.chunks.get(c.digest) for c in m.chunks)
    assert got == data


def test_manifest_listing(tmp_path, example_files):
    ns = NodeStore(tmp_path, node_id=2)
    names = ["teste.txt", "pag1.html"]
    for n in names:
        ns.manifests.save(FixedFragmenter(parts=2).manifest(
            example_files[n], name=n))
    listed = {m.name for m in ns.manifests.list()}
    assert listed == set(names)


def test_sweep_tmp_reclaims_only_aged_leaks(tmp_path):
    """Crash-leaked .tmp-* files (put: open->crash before link;
    _atomic_write: mkstemp->crash before replace) are reclaimed by the
    hour-gated sweep; anything younger — a live put's temp — is not."""
    import os
    import time as _time
    ns = NodeStore(tmp_path, node_id=3)
    d = sha256_hex(b"x")
    ns.chunks.put(d, b"x")          # creates chunks/<d[:2]>/
    sub = ns.chunks.root / d[:2]
    old_c = sub / ".tmp-999-0"
    new_c = sub / ".tmp-999-1"
    old_m = ns.manifests.root / ".tmp-leak"
    for p in (old_c, new_c, old_m):
        p.write_bytes(b"leak")
    past = _time.time() - 7200
    os.utime(old_c, (past, past))
    os.utime(old_m, (past, past))
    assert ns.chunks.sweep_tmp() == 1
    assert ns.manifests.sweep_tmp() == 1
    assert not old_c.exists() and not old_m.exists()
    assert new_c.exists()           # younger than the gate: untouched
    assert ns.chunks.get(d) == b"x"
    new_c.unlink()


def test_put_falls_back_to_replace_without_hardlinks(tmp_path, monkeypatch):
    """Filesystems without hard links take the os.replace fallback; a
    link failure that is NOT a no-hardlink errno stays loud."""
    import errno as _errno
    import os
    from dfs_tpu.store.cas import ChunkStore
    cs = ChunkStore(tmp_path / "c")
    real_link = os.link

    def no_links(src, dst, **kw):
        raise OSError(_errno.EOPNOTSUPP, "no hard links here")

    monkeypatch.setattr(os, "link", no_links)
    d = sha256_hex(b"payload")
    assert cs.put(d, b"payload") is True
    assert cs.get(d) == b"payload"
    assert cs.put(d, b"payload") is False     # dedup via exists-check

    def vanishing(src, dst, **kw):
        raise FileNotFoundError(_errno.ENOENT, "tmp vanished", src)

    monkeypatch.setattr(os, "link", vanishing)
    d2 = sha256_hex(b"other")
    try:
        cs.put(d2, b"other")
    except FileNotFoundError:
        pass
    else:
        raise AssertionError("non-hardlink errno must propagate")
    monkeypatch.setattr(os, "link", real_link)


def test_count_gauge_primes_once_and_tracks_put_delete(tmp_path):
    """r17 DFS008 regression: count()'s lazily-primed gauge peek moved
    under the lock (it raced the worker-side put/delete updates); the
    prime-once-then-maintain contract — and the priming scan staying
    OUTSIDE the lock — must survive the restructure."""
    store = ChunkStore(tmp_path / "chunks")
    payloads = [b"a" * 10, b"b" * 20, b"c" * 30]
    digests = [sha256_hex(p) for p in payloads]
    for d, p in zip(digests, payloads):
        store.put(d, p)
    assert store.count() == 3                  # priming scan
    store.delete(digests[0])
    assert store.count() == 2                  # maintained, no rescan
    d_new = sha256_hex(b"d" * 5)
    store.put(d_new, b"d" * 5)
    store.put(d_new, b"d" * 5)                 # dedup hit: no double count
    assert store.count() == 3
    assert store.bytes_total() == 20 + 30 + 5

    # the gauges stay coherent when hammered from worker threads while
    # a reader polls — the cross-context shape DFS008 flagged
    import threading

    extra = [(sha256_hex(bytes([i]) * 8), bytes([i]) * 8)
             for i in range(32)]
    seen = []

    def writer(items):
        for d, p in items:
            store.put(d, p)

    def reader():
        for _ in range(64):
            seen.append((store.count(), store.bytes_total()))

    threads = [threading.Thread(target=writer, args=(extra[:16],)),
               threading.Thread(target=writer, args=(extra[16:],)),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert store.count() == 3 + 32
    assert store.bytes_total() == 20 + 30 + 5 + 32 * 8
    assert all(c >= 3 and b >= 55 for c, b in seen)


# ---------------------------------------------------------------------- #
# the batch put: payload barriers, links, one directory barrier per
# directory (PR 25) — put is put_batch of one item
# ---------------------------------------------------------------------- #

class _Calls:
    """Record the file-system calls the store issues (name, path), in
    order, and how many descriptors it holds at once."""

    NAMES = ("stat", "fstat", "open", "write", "fsync", "close", "link",
             "replace", "unlink")

    def __init__(self, monkeypatch, root):
        import os
        import threading
        self.root = str(root)
        self.events = []        # (call, path or None)
        self.paths = {}         # fd -> path
        self.max_open = 0
        self.fail = None        # (call, predicate(path)) -> OSError once
        self.on_call = None     # hook(call, path), before the real call
        self.mu = threading.Lock()
        real = {n: getattr(os, n) for n in self.NAMES}
        self.real = real

        def path_of(call, args):
            if call in ("fstat", "write", "fsync", "close"):
                return self.paths.get(args[0])
            if call in ("link", "replace"):
                return str(args[1])
            return str(args[0]) if isinstance(args[0], (str, bytes)) \
                or hasattr(args[0], "__fspath__") else self.paths.get(args[0])

        def wrap(call):
            def fn(*args, **kw):
                path = path_of(call, args)
                mine = path is not None and path.startswith(self.root)
                if mine:
                    with self.mu:
                        self.events.append((call, path))
                    if self.on_call is not None:
                        self.on_call(call, path)
                    if self.fail is not None and self.fail[0] == call \
                            and self.fail[1](path):
                        self.fail = None
                        raise OSError(5, f"injected {call} failure", path)
                out = real[call](*args, **kw)
                if call == "open" and mine:
                    with self.mu:
                        self.paths[out] = path
                        self.max_open = max(self.max_open, len(self.paths))
                if call == "close":
                    with self.mu:
                        self.paths.pop(args[0], None)
                return out
            return fn

        for n in self.NAMES:
            monkeypatch.setattr(os, n, wrap(n))

    def of(self, *calls):
        return [(c, p) for c, p in self.events if c in calls]


def _batch(n, seed=0, size=64):
    import random
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        data = rng.randbytes(size)
        out.append((sha256_hex(data), data))
    return out


def _temps(root):
    return sorted(p.name for p in root.rglob(".tmp-*"))


def _is_temp(path):
    return "/.tmp-" in path


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_put_batch_barriers_once_per_directory(tmp_path, monkeypatch, fsync):
    """A batch of 600: one payload fsync per new file before that file's
    link, one directory fsync per distinct parent after the last link
    into it and before put_batch returns, never more than two
    descriptors, and the counters say so."""
    import os
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    items = _batch(600)
    for d, _ in items:                      # directories made beforehand
        os.makedirs(cs.root / d[:2], exist_ok=True)
    calls = _Calls(monkeypatch, cs.root)
    assert cs.put_batch(items, verify=True) == [True] * 600
    ev = calls.events
    assert calls.max_open <= 2
    assert calls.of("fstat") == []
    parents = {os.path.dirname(str(cs._path(d))) for d, _ in items}
    assert 1 < len(parents) <= 256
    payload_syncs = [p for c, p in calls.of("fsync") if _is_temp(p)]
    dir_syncs = [p for c, p in calls.of("fsync") if p in parents]
    links = calls.of("link")
    assert len(links) == 600
    if not fsync:
        assert calls.of("fsync") == []
        assert cs.fsync_count() == 0 and cs.dir_barrier_count() == 0
    else:
        assert len(payload_syncs) == 600 and len(set(payload_syncs)) == 600
        assert sorted(dir_syncs) == sorted(parents)     # once each
        assert len(calls.of("fsync")) == 600 + len(parents)
        # payload durable before its name: link k publishes the k-th temp
        # (same order), whose fsync lies before the link
        link_at = [i for i, (c, _) in enumerate(ev) if c == "link"]
        sync_at = {p: i for i, (c, p) in enumerate(ev)
                   if c == "fsync" and _is_temp(p)}
        opened = [p for c, p in calls.of("open") if _is_temp(p)]
        assert len(opened) == 600
        for tmp, at in zip(opened, link_at):
            assert sync_at[tmp] < at
        # name durable after the LAST link into its directory
        last_link = {}
        for i, (c, p) in enumerate(ev):
            if c == "link":
                last_link[os.path.dirname(p)] = i
        for i, (c, p) in enumerate(ev):
            if c == "fsync" and p in parents:
                assert i > last_link[p]
        assert cs.fsync_count() == 600
        assert cs.dir_barrier_count() == len(parents)
    assert _temps(cs.root) == []
    assert len(calls.of("unlink")) == 600
    # a second, overlapping batch: only the new files are written
    more = items[:50] + _batch(25, seed=1)
    before = len(calls.of("link"))
    assert cs.put_batch(more) == [False] * 50 + [True] * 25
    assert len(calls.of("link")) - before == 25
    assert cs.fsync_count() == (625 if fsync else 0)


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_put_batch_equals_the_put_loop(tmp_path, fsync):
    """Dedup hits, a digest twice in the batch, and the gauges: what a
    loop of put answers, put_batch answers."""
    a = ChunkStore(tmp_path / "a", fsync=fsync)
    b = ChunkStore(tmp_path / "b", fsync=fsync)
    old = _batch(8, seed=2)
    for cs in (a, b):
        for d, data in old[:5]:
            cs.put(d, data)
        assert cs.count() == 5            # primes the gauges
    fresh = _batch(40, seed=3)
    batch = old[3:8] + fresh[:20] + fresh[5:10] + fresh[20:] + old[:2]
    want = [a.put(d, data) for d, data in batch]
    got = b.put_batch(batch)
    assert got == want
    assert want.count(True) == 3 + 40
    assert sorted(a.digests()) == sorted(b.digests())
    assert (a.count(), a.bytes_total()) == (b.count(), b.bytes_total()) \
        == (48, 48 * 64)
    assert a.fsync_count() == b.fsync_count() == (48 if fsync else 0)
    for d, data in batch:
        assert b.get(d) == data
    assert _temps(b.root) == []
    assert b.put_batch([]) == []


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_put_batch_verify_mismatch_leaves_nothing(tmp_path, fsync):
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    items = _batch(30, seed=4)
    bad = ("e" * 64, b"not what the digest says")
    with pytest.raises(ValueError):
        cs.put_batch(items[:20] + [bad] + items[20:], verify=True)
    assert not cs.has(bad[0])
    assert not (cs.root / "ee").exists() or \
        list((cs.root / "ee").iterdir()) == []
    assert _temps(cs.root) == []
    # the call failed as a whole; what it may have left is sound, and a
    # repeat stores the rest
    for d in cs.digests():
        assert sha256_hex(cs.get(d)) == d
    left = len(cs.digests())
    again = cs.put_batch(items)
    assert again.count(True) == 30 - left
    assert len(cs.digests()) == 30 and cs.count() == 30


@pytest.mark.parametrize("fsync,phase", [
    (True, "open"), (True, "write"), (True, "payload-fsync"),
    (True, "link"), (True, "dir-fsync"),
    # with durability off no barrier is issued: three phases can fail
    (False, "open"), (False, "write"), (False, "link")])
def test_put_batch_oserror_in_each_phase(tmp_path, monkeypatch, fsync,
                                         phase):
    """An OSError in any phase fails the call, leaves no temp, keeps the
    gauges exact, and leaves no name that a later dedup hit would answer
    for without a directory barrier."""
    import os
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    first = _batch(4, seed=5)
    cs.put_batch(first)
    assert cs.count() == 4
    items = _batch(120, seed=6)
    calls = _Calls(monkeypatch, cs.root)
    seen = {"n": 0}

    def nth(k, want):
        def pred(path):
            if not want(path):
                return False
            seen["n"] += 1
            return seen["n"] == k
        return pred

    parents = {os.path.dirname(str(cs._path(d))) for d, _ in items}
    calls.fail = {
        "open": ("open", nth(60, _is_temp)),
        "write": ("write", nth(60, _is_temp)),
        "payload-fsync": ("fsync", nth(60, _is_temp)),
        "link": ("link", nth(60, lambda p: True)),
        "dir-fsync": ("fsync", nth(len(parents) // 2,
                                   lambda p: p in parents)),
    }[phase]
    with pytest.raises(OSError):
        cs.put_batch(items)
    assert calls.fail is None               # the fault fired
    assert _temps(cs.root) == []
    on_disk = cs.digests()
    assert cs.count() == len(on_disk)
    assert cs.bytes_total() == sum(len(cs.get(d)) for d in on_disk)
    for d in on_disk:                       # every name holds its payload
        assert sha256_hex(cs.get(d)) == d
    linked = len(on_disk) - 4
    assert linked == {"open": 0, "write": 0, "payload-fsync": 0,
                      "link": 59, "dir-fsync": 120}[phase]
    # the failed call counted no file as made durable ...
    assert cs.fsync_count() == (4 if fsync else 0)
    # ... and a repeat answers a dedup hit for what is linked only after
    # a directory barrier covering it was issued
    before = len([1 for c, p in calls.of("fsync") if p in parents])
    got = cs.put_batch(items)
    assert got.count(False) == linked
    assert len(cs.digests()) == 124 and cs.count() == 124
    if fsync:
        owed = {os.path.dirname(str(cs._path(d)))
                for (d, _), new in zip(items, got) if not new}
        synced = [p for c, p in calls.of("fsync") if p in parents][before:]
        assert owed <= set(synced)
        assert cs.fsync_count() == 4 + got.count(True)
    assert _temps(cs.root) == []


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("race", ["precheck", "link"])
def test_same_digest_from_two_threads(tmp_path, monkeypatch, fsync, race):
    """Exactly one True; and the loser — whether it meets the name at the
    dedup pre-check or loses the link — does not return before a
    directory barrier covering the name was issued, although the
    winner's own is still held back."""
    import os
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    data = b"raced chunk"
    d = sha256_hex(data)
    parent = os.path.dirname(str(cs._path(d)))
    os.makedirs(parent)
    calls = _Calls(monkeypatch, cs.root)
    winner = {}
    linked = threading.Event()
    release = threading.Event()
    both = threading.Barrier(2)
    out = {}

    def on_call(call, path):
        me = threading.get_ident()
        if call == "write" and race == "link":
            both.wait(timeout=10)       # both are past the pre-check
        if call == "fsync" and path == parent and winner.get("id") == me:
            release.wait(timeout=10)    # the winner's barrier is held back

    real_link = os.link

    def link(src, dst, **kw):
        real_link(src, dst, **kw)       # raises FileExistsError for the loser
        winner["id"] = threading.get_ident()
        linked.set()

    monkeypatch.setattr(os, "link", link)
    calls.on_call = on_call

    def run(name, wait_for_link):
        if wait_for_link:
            assert linked.wait(timeout=10)
        out[name] = cs.put(d, data)
        out[name + "_syncs"] = len(
            [1 for c, p in calls.of("fsync") if p == parent])

    ta = threading.Thread(target=run, args=("a", False))
    tb = threading.Thread(target=run, args=("b", race == "precheck"))
    ta.start()
    tb.start()
    # the loser returns while the winner's directory barrier is held back
    deadline = 10.0
    loser = None
    import time as _time
    t0 = _time.time()
    while _time.time() - t0 < deadline:
        done = [n for n in ("a", "b") if n in out]
        if fsync and done:
            loser = done[0]
            break
        if not fsync and len(done) == 2:
            break
        _time.sleep(0.005)
    if fsync:
        assert loser is not None and out[loser] is False
        # a directory barrier was issued — by the loser — before it returned
        assert out[loser + "_syncs"] >= 1
    release.set()
    ta.join(10)
    tb.join(10)
    assert sorted([out["a"], out["b"]]) == [False, True]
    assert cs.get(d) == data and _temps(cs.root) == []
    assert cs.fsync_count() == (1 if fsync else 0)
    if fsync:
        assert cs.dir_barrier_count() == 2
        # settled: a third put answers without another barrier
        assert cs.put(d, data) is False
        assert cs.dir_barrier_count() == 2


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_put_of_one_item_call_sequence(tmp_path, monkeypatch, fsync):
    """put is put_batch of one: the old sequence less the fstat."""
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    seen = {}
    pair = None
    for i in range(100000):                 # two chunks of one directory
        data = b"chunk-%d" % i
        d = sha256_hex(data)
        if d[:2] in seen:
            pair = (seen[d[:2]], (d, data))
            break
        seen[d[:2]] = (d, data)
    (d0, data0), (d1, data1) = pair
    assert cs.put(d0, data0) is True        # makes the directory
    calls = _Calls(monkeypatch, cs.root)
    assert cs.put(d1, data1) is True
    final = str(cs._path(d1))
    parent = str(cs._path(d1).parent)
    tmp = [p for c, p in calls.events if c == "open" and _is_temp(p)][0]
    want = [("stat", final), ("open", tmp), ("write", tmp)]
    if fsync:
        want.append(("fsync", tmp))
    want += [("close", tmp), ("link", final)]
    if fsync:
        want += [("open", parent), ("fsync", parent), ("close", parent)]
    want.append(("unlink", tmp))
    assert calls.events == want
    calls.events.clear()
    assert cs.put(d1, data1) is False       # a dedup hit on a name this
    assert calls.events == []               # store linked: resident (PR 28)
    other = ChunkStore(cs.root, fsync=fsync)    # another life: one stat,
    calls.events.clear()                        # and then none
    assert other.put(d1, data1) is False
    assert other.put(d1, data1) is False
    assert calls.events == [("stat", final)]


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_overlapping_batches_from_many_threads(tmp_path, fsync):
    """More writers than cores, a short switch interval, batches that
    overlap: every digest is newly stored by exactly one of them, the
    gauges and counters are exact, and no name is left owed a barrier."""
    import os
    import sys
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    assert cs.count() == 0
    pool = _batch(240, seed=9, size=32)
    n_threads = 2 * (os.cpu_count() or 4)
    wins = [None] * n_threads
    errors = []

    def writer(k):
        try:
            mine = pool[(k * 17) % 120:][:120] + pool[:40]
            wins[k] = [d for (d, _), new in zip(mine, cs.put_batch(mine))
                       if new]
        except BaseException as e:      # reported by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    won = [d for w in wins for d in w]
    assert len(won) == len(set(won)) == len(cs.digests())
    assert cs.count() == len(won) and cs.bytes_total() == 32 * len(won)
    assert cs.fsync_count() == (len(won) if fsync else 0)
    assert cs._unbarriered == set()
    assert _temps(cs.root) == []
    for d, data in pool[:40]:
        assert cs.get(d) == data


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_barrier_dirs_covers_what_a_dead_life_left(tmp_path, monkeypatch,
                                                    fsync):
    """Names linked by a life that never reached its directory barriers
    (``_unbarriered`` died with it): ``barrier_dirs`` — the boot sweep's
    — fsyncs every chunk directory once, and nothing with durability
    off; the next put of such a name is a plain dedup hit."""
    dead = ChunkStore(tmp_path / "chunks", fsync=False)   # no barrier issued
    items = _batch(80, seed=3)
    assert all(dead.put_batch(items))
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    calls = _Calls(monkeypatch, cs.root)
    n = cs.barrier_dirs()
    parents = {str(cs.root / d[:2]) for d, _ in items}
    if fsync:
        assert n == len(parents) == cs.dir_barrier_count()
        assert {p for _, p in calls.of("fsync")} == parents
        assert len(calls.of("fsync")) == len(parents)
    else:
        assert n == 0 == cs.dir_barrier_count()
        assert calls.of("fsync") == []
    assert calls.max_open <= 1
    before = len(calls.of("fsync"))
    assert cs.put_batch(items[:5]) == [False] * 5
    assert len(calls.of("fsync")) == before and cs.fsync_count() == 0


# ---------------------------------------------------------------------- #
# the resident set: what is on the disk, remembered (PR 28) — since PR 39
# with the index plane attached too, in front of the index
# ---------------------------------------------------------------------- #

def _key(d):
    return bytes.fromhex(d)


def _plane(root, cs):
    """An index plane over ``cs``'s chunks, attached to it."""
    from dfs_tpu.config import IndexConfig
    from dfs_tpu.index import IndexPlane
    plane = IndexPlane(IndexConfig(enabled=True), root)
    plane.open_or_rebuild(cs.digests)
    cs.index = plane
    return plane


def _lookups(plane):
    return plane.lsi.stats()["lookups"]


_INDEX = pytest.mark.parametrize("index", [False, True],
                                 ids=["index-off", "index-on"])


def _stale(cs):
    """Resident entries whose raw file is not on the disk."""
    import os
    return [k.hex() for k in cs._resident
            if not os.path.isfile(cs._path_str(k.hex()))]


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_resident_after_put_and_after_a_positive_look(tmp_path, fsync):
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    items = _batch(30)
    assert all(cs.put_batch(items))
    assert cs._resident == {_key(d) for d, _ in items}
    assert cs.resident_stats() == {
        "residentHits": 0, "residentMisses": 30, "residentEntries": 30,
        "residentDrops": 0}                 # the pre-check's 30 stats
    # another life knows nothing; each positive look at the disk enters
    # one, with the caller's leave to answer from the set or without it
    other = ChunkStore(cs.root, fsync=fsync)
    assert other._resident == set()
    assert other.has(items[0][0]) is True
    assert other.has(items[1][0], resident_ok=True) is True
    assert other._resident == {_key(items[0][0]), _key(items[1][0])}
    assert other.has_many([d for d, _ in items], resident_ok=True) \
        == [True] * 30
    assert other._resident == cs._resident
    assert other.resident_stats() == {
        "residentHits": 2, "residentMisses": 29, "residentEntries": 30,
        "residentDrops": 0}
    # a dedup hit that loses the link enters the name as a stat would
    late = ChunkStore(cs.root, fsync=fsync)
    d, data = items[2]
    assert late._write_raw([(d, late._path_str(d), data)]) == [False]
    assert late._resident == {_key(d)}


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_an_absent_answer_is_never_cached(tmp_path, fsync):
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    (d, data), = _batch(1, seed=4)
    for ok in (True, False, True):
        assert cs.has(d, resident_ok=ok) is False
    assert cs._resident == set()
    t = threading.Thread(target=cs.put, args=(d, data))
    t.start()
    t.join(10)
    assert cs.has(d, resident_ok=True) is True      # the very next call
    assert cs.delete(d) is True
    assert cs.has(d, resident_ok=True) is False and cs._resident == set()
    assert cs.put(d, data) is True                  # written again
    assert cs.has(d, resident_ok=True) is True and cs.get(d) == data


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("via", ["delete", "gc"])
def test_entries_leave_before_the_unlink(tmp_path, monkeypatch, fsync, via):
    ns = NodeStore(tmp_path, 1, fsync=fsync)
    cs = ns.chunks
    items = _batch(12, seed=5)
    assert all(cs.put_batch(items))
    calls = _Calls(monkeypatch, cs.root)
    seen = []

    def on_call(call, path):
        if call == "unlink" and not _is_temp(path):
            d = path.rsplit("/", 1)[1]
            # held: its own directory's lock, and no other
            seen.append((d, _key(d) in cs._resident,
                         [k for k, mu in enumerate(cs._dir_mu)
                          if mu.locked()] == [_key(d)[0]]))

    calls.on_call = on_call
    if via == "delete":
        assert all(cs.delete(d) for d, _ in items)
    else:
        assert sorted(ns.gc()) == sorted(d for d, _ in items)
    assert sorted(d for d, _, _ in seen) == sorted(d for d, _ in items)
    assert all(not resident and locked for _, resident, locked in seen)
    assert cs._resident == set() and cs._unlinks == 12
    assert cs.has_many([d for d, _ in items], resident_ok=True) \
        == [False] * 12


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("when", ["after_the_stat", "inside_the_delete"])
def test_a_stat_positive_racing_a_delete_enters_nothing(
        tmp_path, monkeypatch, fsync, when):
    """The look saw the name; the unlink ended before the look could
    enter it: nothing is entered, whichever side of the delete's discard
    the stat fell on."""
    import os
    import threading
    first = ChunkStore(tmp_path / "chunks", fsync=fsync)
    (d, data), = _batch(1, seed=6)
    assert first.put(d, data)
    cs = ChunkStore(first.root, fsync=fsync)    # knows nothing yet
    final = cs._path_str(d)
    real_stat = os.stat
    if when == "after_the_stat":
        once = []

        def stat(path, *a, **kw):
            out = real_stat(path, *a, **kw)
            if str(path) == final and not once:
                once.append(1)
                assert cs.delete(d) is True     # between stat and entry
            return out
        monkeypatch.setattr(os, "stat", stat)
        assert cs.has(d) is True                # true when it looked
    else:
        statted = threading.Event()
        go = threading.Event()
        looker = threading.Thread(
            target=lambda: (go.wait(10), cs.has(d, resident_ok=True)))

        def stat(path, *a, **kw):
            out = real_stat(path, *a, **kw)
            if str(path) == final \
                    and threading.current_thread() is looker:
                statted.set()
            return out

        real_unlink = os.unlink

        def unlink(path, *a, **kw):
            if str(path) == final:              # inside delete's mutex,
                go.set()                        # past the discard
                assert statted.wait(10)
            return real_unlink(path, *a, **kw)

        monkeypatch.setattr(os, "stat", stat)
        monkeypatch.setattr(os, "unlink", unlink)
        looker.start()
        assert cs.delete(d) is True
        looker.join(10)
        assert not looker.is_alive()
    monkeypatch.undo()
    assert cs._resident == set() and cs._unlinks == 1
    assert cs.has(d, resident_ok=True) is False
    assert cs.put(d, data) is True              # the pre-check missed


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_deletes_racing_looks_from_many_threads_leave_no_stale_entry(
        tmp_path, fsync, index):
    """More threads than cores at a 10 µs switch interval: lookers enter
    names from stats — with the index plane attached, from index
    positives — while deleters unlink and writers put them back: an
    entry never outlives its file, and every answer that says "present"
    from the set is backed by the disk at the end."""
    import os
    import sys
    import threading
    pool = _batch(96, seed=7, size=32)
    ChunkStore(tmp_path / "chunks", fsync=fsync).put_batch(pool)
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)   # learns by looking
    plane = _plane(tmp_path / "plane", cs) if index else None
    digests = [d for d, _ in pool]
    n_threads = 2 * (os.cpu_count() or 4)
    errors = []
    stop = threading.Event()

    def looker(k):
        try:
            while not stop.is_set():
                cs.has_many(digests[k % 7::3], resident_ok=bool(k & 1))
        except BaseException as e:
            errors.append(e)

    def churner(k):
        try:
            for _ in range(3):
                for d, data in pool[k % 5::5]:
                    cs.delete(d)
                    if (int(d[:2], 16) + k) & 1:
                        cs.put(d, data)
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        lookers = [threading.Thread(target=looker, args=(k,))
                   for k in range(n_threads)]
        churners = [threading.Thread(target=churner, args=(k,))
                    for k in range(n_threads)]
        for t in lookers + churners:
            t.start()
        for t in churners:
            t.join(120)
        stop.set()
        for t in lookers:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert not any(t.is_alive() for t in lookers + churners)
    assert _stale(cs) == []
    on_disk = set(cs.digests())
    assert 0 < len(on_disk) < 96
    assert cs.has_many(digests, resident_ok=True) \
        == [d in on_disk for d in digests]
    assert cs._unbarriered == set()
    if plane is not None:
        assert cs.has_many(digests) == [d in on_disk for d in digests]
        plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_known_names_cost_no_stat_index_off_and_no_lookup_index_on(
        tmp_path, monkeypatch, fsync, index):
    """A leg's list of 1 700 digests the store already holds: the second
    ``has_many`` and a ``put_batch`` of 1 700 dedup hits issue no
    file-system call at all — and, with the index plane attached, no
    ``index.lookup`` either: the store that linked the names never asks,
    a store of another life pays one lookup a name once (index off: one
    ``stat``) and none after. Without the caller's leave the path is the
    parent's in both modes."""
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    plane = _plane(tmp_path / "plane", cs) if index else None
    items = _batch(1700, seed=8)
    digests = [d for d, _ in items]
    assert all(cs.put_batch(items))
    life = ChunkStore(cs.root, fsync=fsync)     # a peer that never wrote
    life.index = plane
    calls = _Calls(monkeypatch, cs.root)
    finals = [("stat", life._path_str(d)) for d in digests]
    asked = [0]

    def looked_up():
        n = _lookups(plane) if index else 0
        asked[0], d = n, n - asked[0]
        return d

    try:
        for store in (cs, life):
            calls.events.clear()
            looked_up()
            assert store.has_many(digests, resident_ok=True) \
                == [True] * 1700                    # the first list
            first = list(calls.events)
            first_lookups = looked_up()
            calls.events.clear()
            assert store.has_many(digests, resident_ok=True) \
                == [True] * 1700                    # the second
            assert store.put_batch(items) == [False] * 1700
            assert calls.events == [] and looked_up() == 0
            assert len(store._resident) == 1700
            if index:
                assert first == []      # linked here, or index positives
                assert first_lookups == (0 if store is cs else 1700)
            else:
                assert first == ([] if store is cs else finals)
            # without the caller's leave: index on, the parent's lookup a
            # name and no set; index off, a look at the disk for every
            # name — since PR 35 by a listing of its directory where the
            # batch asks four names of it or more (nearly all of a store
            # asked whole), a stat a name in the others
            calls.events.clear()
            before = store.look_stats()
            set_before = store.resident_stats()
            assert store.has_many(digests) == [True] * 1700
            looked = {k: v - before[k]
                      for k, v in store.look_stats().items()}
            if index:
                assert calls.events == [] and looked_up() == 1700
                assert looked == {"lookStats": 0, "lookListed": 0,
                                  "lookListings": 0}
                assert store.resident_stats() == set_before
            else:
                few = {d[:2] for d in digests
                       if sum(e[:2] == d[:2] for e in digests) < 4}
                assert [e for e in calls.events if e in set(finals)] \
                    == [f for d, f in zip(digests, finals) if d[:2] in few]
                assert looked["lookStats"] + looked["lookListed"] == 1700
                assert looked["lookStats"] \
                    == sum(d[:2] in few for d in digests) < 170
                assert looked["lookListings"] \
                    == len({d[:2] for d in digests} - few)
        assert life.resident_stats() == {
            "residentHits": 2 * 1700, "residentMisses": 1700,
            "residentEntries": 1700, "residentDrops": 0}
        assert cs.resident_stats() == {     # its own 1 700 fresh names
            "residentHits": 3 * 1700, "residentMisses": 1700,
            "residentEntries": 1700, "residentDrops": 0}
        if index:
            # a resident dedup hit is a hit the index knows
            seam = plane.stats()
            assert seam["putDedupHits"] == seam["putDedupIndexKnown"] \
                == 2 * 1700
            assert seam["statFallbacks"] == 0
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("when", ["after_the_lookup", "inside_the_delete",
                                  "a_stat_past_the_discard"])
def test_an_index_positive_racing_a_delete_enters_nothing(
        tmp_path, monkeypatch, fsync, when):
    """The plane attached: the index said "present"; the unlink ended
    before the look could enter the name. Nothing is entered — whether
    the whole delete fell between the lookup and the entry, or the
    lookup fell inside the delete before its record and its discard and
    the entry waited for the mutex; nor by the put pre-check whose
    ``stat`` saw the name past the discard (the index says no by then,
    the heal looks again under the mutex)."""
    import os
    import threading
    first = ChunkStore(tmp_path / "chunks", fsync=fsync)
    plane = _plane(tmp_path / "plane", first)
    (d, data), = _batch(1, seed=13)
    assert first.put(d, data)
    cs = ChunkStore(first.root, fsync=fsync)    # knows nothing yet
    cs.index = plane
    final = cs._path_str(d)
    real_lookup = plane.lookup
    try:
        if when == "after_the_lookup":
            once = []

            def lookup(digest):
                out = real_lookup(digest)
                if digest == d and not once:
                    once.append(out)
                    assert cs.delete(d) is True     # between lookup and entry
                return out
            monkeypatch.setattr(plane, "lookup", lookup)
            assert cs.has(d, resident_ok=True) is True  # true when it looked
            assert once == [True]
        else:
            answered = threading.Event()
            go = threading.Event()
            real_stat, real_unlink = os.stat, os.unlink
            if when == "inside_the_delete":
                answers = []
                looker = threading.Thread(target=lambda: (
                    go.wait(10), answers.append(cs.has(d, resident_ok=True))))

                def lookup(digest):
                    out = real_lookup(digest)
                    if threading.current_thread() is looker:
                        answers.append(out)
                        answered.set()
                    return out
                monkeypatch.setattr(plane, "lookup", lookup)

                def stat(path, *a, **kw):
                    out = real_stat(path, *a, **kw)
                    if str(path) == final \
                            and cs._dir_mu[_key(d)[0]].locked() \
                            and threading.current_thread() is not looker \
                            and not go.is_set():
                        go.set()                # delete's getsize: inside
                        assert answered.wait(10)    # its mutex, before its
                    return out                  # record and its discard
                monkeypatch.setattr(os, "stat", stat)
            else:
                answers = []
                looker = threading.Thread(target=lambda: (
                    go.wait(10), answers.append(cs.put(d, data))))

                def stat(path, *a, **kw):
                    out = real_stat(path, *a, **kw)
                    if str(path) == final and not answered.is_set() \
                            and threading.current_thread() is looker:
                        answered.set()          # the pre-check's isfile
                    return out

                def unlink(path, *a, **kw):
                    if str(path) == final:      # inside delete's mutex,
                        go.set()                # past record and discard
                        assert answered.wait(10)
                    return real_unlink(path, *a, **kw)
                monkeypatch.setattr(os, "stat", stat)
                monkeypatch.setattr(os, "unlink", unlink)
            looker.start()
            assert cs.delete(d) is True
            looker.join(10)
            assert not looker.is_alive()
            # an index positive and the answer of the moment it looked;
            # the pre-check's stat-positive is the parent's dedup hit
            assert answers == ([True, True] if when == "inside_the_delete"
                               else [False])
        monkeypatch.undo()
        assert cs._resident == set() and cs._unlinks == 1
        assert cs.has(d, resident_ok=True) is False
        assert cs.has(d) is False
        assert cs.put(d, data) is True              # the pre-check missed
        assert cs._resident == {_key(d)} and cs.get(d) == data
    finally:
        plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_has_without_leave_index_on_issues_the_parents_calls(
        tmp_path, monkeypatch, fsync):
    """``has`` without ``resident_ok`` — the repair cycle, ``who_has``,
    relocation, the smart client — with the plane attached: one lookup;
    a ``stat`` only behind a negative (the backstop, which re-records a
    name the index forgot). The resident set is not consulted, and is
    healed only by what the backstop's ``stat`` says."""
    import os
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    (held, dh), (forgot, df), (absent, _), (gone, dg) = _batch(4, seed=14)
    plain = ChunkStore(cs.root, fsync=fsync)    # no plane: the index never
    assert plain.put(forgot, df)                # hears of this one
    plane = _plane(tmp_path / "plane", ChunkStore(tmp_path / "empty"))
    cs.index = plane
    assert cs.put(held, dh) and cs.put(gone, dg)
    os.unlink(cs._path_str(gone))               # behind its back, and
    plane.note_delete(gone)                     # scrub expunged the phantom
    calls = _Calls(monkeypatch, cs.root)
    order = []
    real_lookup = plane.lookup

    def lookup(digest):
        order.append(("lookup", digest))
        return real_lookup(digest)
    monkeypatch.setattr(plane, "lookup", lookup)
    calls.on_call = lambda call, path: order.append((call, path))
    try:
        before = cs.resident_stats()
        assert cs._resident == {_key(held), _key(gone)}
        assert cs.has_many([held, forgot, absent, gone, forgot, held]) \
            == [True, True, False, False, True, True]
        assert order == [
            ("lookup", held),
            ("lookup", forgot), ("stat", cs._path_str(forgot)),
            ("lookup", absent), ("stat", cs._path_str(absent)),
            ("lookup", gone), ("stat", cs._path_str(gone)),
            ("lookup", forgot),                 # re-recorded: no stat
            ("lookup", held)]
        assert plane.stats()["statFallbacks"] == 3
        assert plane.stats()["statFallbackHits"] == 1
        # the backstop's looks healed the set: a hit entered, a phantom
        # dropped; no probe of the set was counted
        assert cs._resident == {_key(held), _key(forgot)}
        assert cs.resident_stats() == {
            **before, "residentDrops": 1}
        assert cs.has(gone, resident_ok=True) is False
    finally:
        plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_index_off_call_sequences_are_the_parents(tmp_path, monkeypatch,
                                                  fsync):
    """The plane off: a put batch over a resident name, a name of
    another life, a fresh one and a repeat, then the looks with and
    without the caller's leave, issue the parent's file-system calls in
    the parent's order (PR 28's: this list is the parent's recorder on
    the same script, to the letter)."""
    import os
    other = ChunkStore(tmp_path / "chunks", fsync=fsync)
    (mine, dm), (theirs, dt), (fresh, dn), (absent, _) = _batch(4, seed=15)
    assert other.put(theirs, dt)
    cs = ChunkStore(other.root, fsync=fsync)
    assert cs.put(mine, dm)
    path = cs._path_str
    parent_dir = os.path.dirname(path(fresh))
    calls = _Calls(monkeypatch, cs.root)
    assert cs.put_batch([(mine, dm), (theirs, dt), (fresh, dn),
                         (fresh, dn)]) == [False, False, True, False]
    put = [(c, p) for c, p in calls.events
           if not _is_temp(p) and p != calls.root]      # makedirs' look
    temps = [(c, p) for c, p in calls.events if _is_temp(p)]
    barrier = [("open", parent_dir), ("fsync", parent_dir),
               ("close", parent_dir)] if fsync else []
    assert put == [("stat", path(theirs)), ("stat", path(fresh)),
                   ("link", path(fresh))] + barrier
    assert [c for c, _ in temps] \
        == ["open", "write"] + (["fsync"] if fsync else []) \
        + ["close", "unlink"]
    calls.events.clear()
    assert cs.has_many([mine, theirs, fresh, absent], resident_ok=True) \
        == [True, True, True, False]
    assert calls.events == [("stat", path(absent))]
    calls.events.clear()
    assert [cs.has(d) for d in (mine, absent)] == [True, False]
    assert calls.events == [("stat", path(mine)), ("stat", path(absent))]
    assert cs.resident_stats() == {
        "residentHits": 4, "residentMisses": 4, "residentEntries": 3,
        "residentDrops": 0}


@_INDEX
def test_a_resident_hit_still_settles_a_name_owed_its_barrier(
        tmp_path, monkeypatch, index):
    """Between another thread's link and its directory barrier the name
    is resident AND owed a barrier: ``has`` and the put pre-check issue
    it before they answer, as a stat-positive did at the parent — and,
    with the index plane attached, as an index positive did."""
    import os
    cs = ChunkStore(tmp_path / "chunks", fsync=True)
    plane = _plane(tmp_path / "plane", cs) if index else None
    (d, data), = _batch(1, seed=10)
    parent = os.path.dirname(cs._path_str(d))
    os.makedirs(parent)
    calls = _Calls(monkeypatch, cs.root)
    answers = []

    def on_call(call, path):
        # the writer's own directory barrier is about to be issued: the
        # name is linked, resident, and still owed
        if call == "fsync" and path == parent and not answers:
            assert _key(d) in cs._resident and d in cs._unbarriered
            answers.append(None)                # re-entrancy guard
            before = cs.dir_barrier_count()
            answers[:] = [cs.has(d, resident_ok=True),
                          cs.dir_barrier_count() - before]
            assert d not in cs._unbarriered
            assert cs.put(d, data) is False     # settled: no second one
            answers.append(cs.dir_barrier_count() - before)

    calls.on_call = on_call
    assert cs.put(d, data) is True
    assert answers == [True, 1, 1]
    assert calls.of("stat").count(("stat", cs._path_str(d))) == 1   # the
    #                                             writer's own pre-check
    assert cs.dir_barrier_count() == 2 and cs.fsync_count() == 1
    assert cs.resident_stats()["residentHits"] == 2
    if plane is not None:
        assert _lookups(plane) == 0
        plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_overflow_empties_the_set_and_answers_stay_right(
        tmp_path, monkeypatch, fsync, index):
    """Beyond ``_RESIDENT_MAX`` the set is emptied and refilled by what
    stands behind it — ``stat``s, or with the plane attached the index
    (no ``stat`` for a ``has``) — never a wrong answer."""
    import dfs_tpu.store.cas as cas
    monkeypatch.setattr(cas, "_RESIDENT_MAX", 64)
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    plane = _plane(tmp_path / "plane", cs) if index else None
    items = _batch(150, seed=11)
    digests = [d for d, _ in items]
    assert all(cs.put_batch(items))
    assert len(cs._resident) == 150 - 128       # emptied twice on the way
    assert cs._resident == {_key(d) for d in digests[128:]}
    calls = _Calls(monkeypatch, cs.root)
    assert cs.has_many(digests, resident_ok=True) == [True] * 150
    # what the set held went out with the refill's first overflow: every
    # name is asked of what stands behind the set, once
    if index:
        assert calls.events == [] and _lookups(plane) == 150
    else:
        assert calls.events == [("stat", cs._path_str(d)) for d in digests]
    assert len(cs._resident) <= 64 and _stale(cs) == []
    assert cs.put_batch(items) == [False] * 150
    assert cs.delete(digests[0]) and cs.delete(digests[149])
    assert cs.has_many(digests, resident_ok=True) \
        == [False] + [True] * 148 + [False]
    assert cs.count() == 148 and _stale(cs) == []
    if plane is not None:
        assert cs.has_many(digests) == [False] + [True] * 148 + [False]
        plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_a_look_at_the_disk_and_a_get_miss_heal_the_set(tmp_path, fsync):
    """A file removed behind the store's back: a resident answer still
    says present (the one caveat); the audit look, a ``get`` and a
    ``delete`` each drop the entry, and the next put writes the file."""
    import os
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    items = _batch(4, seed=12)
    assert all(cs.put_batch(items))
    for d, _ in items:
        os.unlink(cs._path_str(d))              # behind its back
    (a, da), (b, db), (c, dc), (e, de) = items
    assert cs.has_many([a, b, c, e], resident_ok=True) == [True] * 4
    assert cs.has(a) is False                   # the repair cycle's look
    assert cs.get(b) is None
    assert cs.delete(c) is False
    assert cs._resident == {_key(e)}
    assert cs.resident_stats()["residentDrops"] == 3
    assert cs.has_many([a, b, c], resident_ok=True) == [False] * 3
    assert cs.put_batch([(a, da), (b, db), (c, dc)]) == [True] * 3
    assert cs.get(a) == da and cs.get(b) == db and cs.get(c) == dc
    assert cs.put(e, de) is False               # still believed: the caveat
    assert cs.has(e) is False and cs.put(e, de) is True
    assert cs.get(e) == de and _stale(cs) == []


# ---------------------------------------------------------------------- #
# the COMPLETE set (PR 44): a node's boot sweep seeds the resident set
# from the listing it makes anyway; from then on a miss is the answer
# "absent" — no stat, no isfile, no index lookup. A bare store (every
# case above) never is.
# ---------------------------------------------------------------------- #

def _booted(tmp_path, fsync, index=False, held=()):
    """A node's store over what a previous life left (``held``), its
    boot sweep done — the index plane opened before it, as the node
    does."""
    ns = NodeStore(tmp_path, 1, fsync=fsync)
    if held:
        assert all(ChunkStore(ns.chunks.root, fsync=fsync).put_batch(held))
    plane = _plane(tmp_path / "plane", ns.chunks) if index else None
    ns.boot_sweep()
    return ns, ns.chunks, plane


def _name_stats(calls):
    """The recorded ``stat``s of chunk names (``isfile`` and ``getsize``
    are one each), temps and directories left out."""
    return [(c, p) for c, p in calls.of("stat")
            if len(p.rsplit("/", 1)[1]) == 64]


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_a_booted_store_answers_absent_and_present_from_memory(
        tmp_path, monkeypatch, fsync, index):
    """After the boot sweep: the names of a previous life are present
    and the names nobody has are absent — for ``has_many(resident_ok)``
    and for the put pre-check — with no ``stat`` of any chunk name and,
    the plane attached, no lookup; the fresh names are then written and
    present. ``residentAbsent`` counts inside ``residentMisses``."""
    held = _batch(40, seed=20)
    fresh = _batch(60, seed=21)
    ns, cs, plane = _booted(tmp_path, fsync, index, held)
    try:
        assert cs._resident == {_key(d) for d, _ in held}
        assert cs.resident_stats() == {
            "residentHits": 0, "residentMisses": 0, "residentEntries": 40,
            "residentDrops": 0, "residentAbsent": 0,
            "residentComplete": True}
        calls = _Calls(monkeypatch, cs.root)
        asked = _lookups(plane) if index else 0
        names = [d for d, _ in held + fresh]
        assert cs.has_many(names, resident_ok=True) \
            == [True] * 40 + [False] * 60
        assert calls.events == []
        assert cs.put_batch(held) == [False] * 40
        assert calls.events == []
        assert cs.put_batch(fresh) == [True] * 60       # written, and
        assert _name_stats(calls) == []                 # never asked for
        assert len(calls.of("link")) == 60
        calls.events.clear()
        assert cs.has_many(names, resident_ok=True) == [True] * 100
        assert calls.events == []
        assert (_lookups(plane) if index else 0) == asked
        assert cs.resident_stats() == {
            "residentHits": 40 + 40 + 100, "residentMisses": 60 + 60,
            "residentEntries": 100, "residentDrops": 0,
            "residentAbsent": 120, "residentComplete": True}
        assert sorted(cs.digests()) == sorted(names) and _stale(cs) == []
        assert all(cs.get(d) == data for d, data in held + fresh)
        if index:
            assert plane.stats()["statFallbacks"] == 0
            assert cs.has_many(names) == [True] * 100   # recorded at the link
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_has_without_leave_still_looks_at_the_disk_of_a_booted_store(
        tmp_path, monkeypatch, fsync, index):
    """No ``resident_ok`` — the repair cycle, ``who_has``, relocation,
    the smart client: a complete set changes nothing for them. The look
    is the parent's (index off: a ``stat``; index on: a lookup, a
    ``stat`` behind its negative), counts in neither ``residentMisses``
    nor ``residentAbsent``, and heals the set both ways."""
    held = _batch(3, seed=22)
    (absent, _), = _batch(1, seed=23)
    ns, cs, plane = _booted(tmp_path, fsync, index, held)
    try:
        calls = _Calls(monkeypatch, cs.root)
        before = cs.resident_stats()
        asked = _lookups(plane) if index else 0
        assert cs.has(absent) is False
        assert calls.events == [("stat", cs._path_str(absent))]
        assert (_lookups(plane) - asked if index else 1) == 1
        calls.events.clear()
        # (index on: the previous life ran no plane, so the lookup's
        # negative falls to the backstop's stat, which re-records it)
        assert cs.has(held[0][0]) is True
        assert calls.events == [("stat", cs._path_str(held[0][0]))]
        assert (_lookups(plane) - asked if index else 2) == 2
        assert cs.resident_stats() == before
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_complete_put_then_delete_then_put_across_threads(
        tmp_path, monkeypatch, fsync):
    """put → present, delete → absent, put again → written, each step on
    a thread of its own, each answer from memory: the one ``stat`` of
    the name is ``delete``'s ``getsize``."""
    import threading
    ns, cs, _ = _booted(tmp_path, fsync)
    (d, data), = _batch(1, seed=24)
    calls = _Calls(monkeypatch, cs.root)
    out = []

    def on_thread(fn, *args):
        t = threading.Thread(target=lambda: out.append(fn(*args)))
        t.start()
        t.join(10)
        assert not t.is_alive()
        return out.pop()

    assert cs.has(d, resident_ok=True) is False
    assert on_thread(cs.put, d, data) is True
    assert cs.has(d, resident_ok=True) is True
    assert on_thread(cs.put, d, data) is False
    assert _name_stats(calls) == []
    assert on_thread(cs.delete, d) is True
    assert _name_stats(calls) == [("stat", cs._path_str(d))]
    assert on_thread(cs.has, d, True) is False and cs._resident == set()
    assert on_thread(cs.put, d, data) is True           # written again
    assert cs.has(d, resident_ok=True) is True and cs.get(d) == data
    assert len(_name_stats(calls)) == 1 and len(calls.of("link")) == 2
    assert cs.resident_stats()["residentAbsent"] == 4   # 2 has, 2 pre-checks
    assert cs.resident_stats()["residentComplete"] is True


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_a_file_added_by_another_hand_costs_a_redundant_write(
        tmp_path, monkeypatch, fsync, index):
    """The one thing a complete set lacks: a file another hand put into
    the directory is "absent" from memory, so it is written again — a
    write that loses its link (``[False]``: a dedup hit), leaves one
    file with its bytes, and enters the name. A look at the disk enters
    such a name too."""
    ns, cs, plane = _booted(tmp_path, fsync, index)
    try:
        (a, da), (b, db) = _batch(2, seed=25)
        other = ChunkStore(cs.root, fsync=fsync)        # another hand
        assert other.put(a, da) and other.put(b, db)
        assert cs.has_many([a, b], resident_ok=True) == [False, False]
        calls = _Calls(monkeypatch, cs.root)
        assert cs.put_batch([(a, da)]) == [False]
        assert _name_stats(calls) == [] and len(calls.of("link")) == 1
        assert sorted(cs.digests()) == sorted([a, b])
        assert _temps(cs.root) == [] and cs.get(a) == da
        assert _key(a) in cs._resident
        assert cs.has(b) is True                        # a look at the disk
        calls.events.clear()
        assert cs.has_many([a, b], resident_ok=True) == [True, True]
        assert calls.events == []
        assert cs.resident_stats()["residentAbsent"] == 3
        assert cs.count() == 2
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
def test_a_file_removed_by_another_hand_is_dropped_by_the_repair_look(
        tmp_path, monkeypatch, fsync):
    """The caveat's old face, unchanged: believed in until a look at the
    disk (the repair cycle's ``has_many``, no ``resident_ok``) — which
    drops it, after which "absent" comes from memory. The boot sweep's
    aged orphans left the set through ``delete``."""
    import os
    import time
    held = _batch(12, seed=26)
    old = [d for d, _ in held[:2]]
    ns = NodeStore(tmp_path, 1, fsync=fsync)
    assert all(ns.chunks.put_batch(held))
    for d in old:                                   # aborted streams' chunks
        t = time.time() - 7200
        os.utime(ns.chunks._path_str(d), (t, t))
    ns, cs, _ = _booted(tmp_path, fsync)
    names = [d for d, _ in held]
    assert cs._resident == {_key(d) for d in names[2:]}
    assert cs._unlinks == 2
    gone = names[5]
    os.unlink(cs._path_str(gone))                   # behind its back
    assert cs.has_many(names, resident_ok=True) \
        == [False] * 2 + [True] * 10                # the caveat
    assert cs.has_many(names) \
        == [d not in (*old, gone) for d in names]   # the repair cycle's look
    calls = _Calls(monkeypatch, cs.root)
    assert cs.has_many(names, resident_ok=True) \
        == [d not in (*old, gone) for d in names]
    assert calls.events == []
    assert cs.resident_stats() == {
        "residentHits": 10 + 9, "residentMisses": 2 + 3,
        "residentEntries": 9, "residentDrops": 1, "residentAbsent": 5,
        "residentComplete": True}


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_overflow_ends_completeness_and_the_disk_answers_again(
        tmp_path, monkeypatch, fsync, index):
    """At ``_RESIDENT_MAX`` the set is emptied and is complete no more:
    a miss goes on to what stands behind the set, as in a bare store,
    and the answers stay right."""
    import dfs_tpu.store.cas as cas
    monkeypatch.setattr(cas, "_RESIDENT_MAX", 64)
    held = _batch(40, seed=27)
    more = _batch(30, seed=28)
    absent = [d for d, _ in _batch(20, seed=29)]
    ns, cs, plane = _booted(tmp_path, fsync, index, held)
    try:
        assert cs.resident_stats()["residentComplete"] is True
        assert cs.put_batch(more[:24]) == [True] * 24   # 64 held: full
        calls = _Calls(monkeypatch, cs.root)
        assert cs.has_many(absent, resident_ok=True) == [False] * 20
        assert calls.events == []
        assert cs.put_batch(more[24:]) == [True] * 6    # the 65th empties it
        stats = cs.resident_stats()
        assert stats["residentComplete"] is False
        assert stats["residentEntries"] == 6
        # (a batch's pre-checks all come before its first link)
        assert stats["residentAbsent"] == 24 + 20 + 6
        calls.events.clear()
        asked = _lookups(plane) if index else 0
        names = [d for d, _ in held] + absent
        assert cs.has_many(names, resident_ok=True) \
            == [True] * 40 + [False] * 20
        # index on: a lookup a name, and a stat behind each negative (the
        # absent ones; the previous life's too — it ran no plane)
        assert (_lookups(plane) - asked if index else 60) == 60
        assert calls.events == [("stat", cs._path_str(d)) for d in names]
        assert cs.resident_stats()["residentAbsent"] == 50
        assert cs.put_batch(held[:3]) == [False] * 3
        assert _stale(cs) == []
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("why", ["an_unlink_ended_during_the_listing",
                                 "more_names_than_the_bound"])
def test_a_listing_that_cannot_vouch_declares_nothing(
        tmp_path, monkeypatch, fsync, why):
    """``_remember``'s rule for a look outside ``_dir_mu``, for the
    listing as a whole: an unlink that ended while it was read, or more
    names than ``_RESIDENT_MAX``, and nothing is entered and nothing
    declared — the store answers from the disk, and says so."""
    import dfs_tpu.store.cas as cas
    held = _batch(30, seed=30)
    ns = NodeStore(tmp_path, 1, fsync=fsync)
    cs = ns.chunks
    assert all(ChunkStore(cs.root, fsync=fsync).put_batch(held))
    if why == "more_names_than_the_bound":
        monkeypatch.setattr(cas, "_RESIDENT_MAX", 29)
        ns.boot_sweep()
        on_disk = [d for d, _ in held]
    else:
        real = cs._establish

        def establish(raw, seen):
            assert cs.delete(raw[0]) is True    # ended before the seeding
            return real(raw, seen)
        monkeypatch.setattr(cs, "_establish", establish)
        ns.boot_sweep()
        on_disk = sorted(d for d, _ in held)[1:]
    assert cs._resident == set()
    assert cs.resident_stats()["residentComplete"] is False
    (absent, _), = _batch(1, seed=31)
    calls = _Calls(monkeypatch, cs.root)
    assert cs.has_many([absent, *on_disk], resident_ok=True) \
        == [False] + [True] * len(on_disk)
    assert calls.events == [("stat", cs._path_str(d))
                            for d in [absent, *on_disk]]
    assert cs.resident_stats()["residentAbsent"] == 0
    monkeypatch.undo()
    assert sorted(cs.digests(complete=True)) == sorted(on_disk)  # a quiet one
    assert cs.resident_stats()["residentComplete"] is True


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_a_delta_stored_digest_is_present_to_a_complete_set(
        tmp_path, monkeypatch, fsync, index):
    """With a ``deltas/`` tree the set holds raw names only, and the
    delta map is still asked behind a miss: a delta-stored digest is
    present (and a dedup hit for a put), a name nobody has absent with
    no ``stat`` of its raw name."""
    from dfs_tpu.sim.delta import make_delta
    (base_d, base), (delta, target), (absent, _) = _batch(3, seed=32)
    ns = NodeStore(tmp_path, 1, fsync=fsync)
    first = ns.chunks
    assert first.put(base_d, base)
    assert first._put_delta(delta, base_d, make_delta(base_d, base, target),
                            raw_len=len(target)) is True
    ns, cs, plane = _booted(tmp_path, fsync, index)
    try:
        assert cs._deltas_possible() and cs.delta_count() == 1
        assert cs._resident == {_key(base_d)}
        assert sorted(cs.digests()) == sorted([base_d, delta])
        calls = _Calls(monkeypatch, cs.root)
        assert cs.has_many([base_d, delta, absent], resident_ok=True) \
            == [True, True, False]
        assert cs.put_batch([(delta, target), (base_d, base)]) \
            == [False, False]
        # the chain's end is looked at (the base's raw file); the raw
        # names of the delta and of the absent one never
        assert set(_name_stats(calls)) <= {("stat", cs._path_str(base_d))}
        assert cs.get(delta) == target
        assert cs._resident == {_key(base_d)}
    finally:
        if plane is not None:
            plane.close()


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_a_complete_set_stays_the_disk_under_many_threads(
        tmp_path, fsync, index):
    """More threads than cores at a 10 µs switch interval over a booted
    store: probes and pre-checks answered from memory while deleters
    unlink and writers put back. At the end the set IS the directory —
    no entry without its file, no file without its entry — still
    complete, and every file holds its bytes."""
    import os
    import sys
    import threading
    pool = _batch(96, seed=33, size=32)
    ns, cs, plane = _booted(tmp_path, fsync, index, pool[:48])
    digests = [d for d, _ in pool]
    n_threads = 2 * (os.cpu_count() or 4)
    errors = []
    stop = threading.Event()

    def looker(k):
        try:
            while not stop.is_set():
                cs.has_many(digests[k % 7::3], resident_ok=True)
        except BaseException as e:
            errors.append(e)

    def churner(k):
        try:
            for _ in range(3):
                for d, data in pool[k % 5::5]:
                    cs.delete(d)
                    if (int(d[:2], 16) + k) & 1:
                        cs.put(d, data)
        except BaseException as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        lookers = [threading.Thread(target=looker, args=(k,))
                   for k in range(n_threads)]
        churners = [threading.Thread(target=churner, args=(k,))
                    for k in range(n_threads)]
        for t in lookers + churners:
            t.start()
        for t in churners:
            t.join(120)
        stop.set()
        for t in lookers:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    try:
        assert not errors
        assert not any(t.is_alive() for t in lookers + churners)
        on_disk = set(cs.digests())
        assert 0 < len(on_disk) < 96
        assert cs._resident == {_key(d) for d in on_disk}
        stats = cs.resident_stats()
        assert stats["residentComplete"] is True
        assert stats["residentAbsent"] == stats["residentMisses"] > 0
        assert stats["residentDrops"] == 0
        assert cs.has_many(digests, resident_ok=True) \
            == [d in on_disk for d in digests]
        assert all(cs.get(d) == data for d, data in pool if d in on_disk)
        assert cs._unbarriered == set() and _temps(cs.root) == []
        if plane is not None:
            assert cs.has_many(digests) == [d in on_disk for d in digests]
    finally:
        if plane is not None:
            plane.close()


# ---------------------------------------------------------------------- #
# a look at the disk for a batch lists the directory (PR 35) — index off,
# no resident_ok: the repair cycle's probe
# ---------------------------------------------------------------------- #

def _named(sub, n, seed=0):
    """``n`` (digest, data) whose digests all begin with ``sub``: names
    of one shard directory (put with ``verify=False``)."""
    import random
    rng = random.Random(seed)
    return [(sub + "%062x" % rng.getrandbits(248), rng.randbytes(48))
            for _ in range(n)]


def _look_tree(root, fsync):
    """A store whose directory ``ab`` holds every kind of entry a look
    can meet, and the names to ask about (present and absent)."""
    import os

    from dfs_tpu.sim.delta import make_delta
    cs = ChunkStore(root, fsync=fsync)
    raw = _named("ab", 40, seed=1)
    assert all(cs.put_batch(raw, verify=False))
    absent = [d for d, _ in _named("ab", 12, seed=2)]
    other = _named("cd", 2, seed=3)         # a directory asked little of
    assert all(cs.put_batch(other, verify=False))
    sub = os.path.join(cs.root, "ab")
    open(os.path.join(sub, ".tmp-123-7"), "wb").close()     # a stray temp
    (adir, _), (alink, _), (dangling, _) = _named("ab", 3, seed=4)
    os.mkdir(os.path.join(sub, adir))               # a name, not a file
    os.symlink(cs._path_str(raw[0][0]), os.path.join(sub, alink))
    os.symlink(os.path.join(sub, "nowhere"), os.path.join(sub, dangling))
    (delta, target), = _named("ab", 1, seed=5)      # delta-stored
    base_d, base = raw[1]
    assert cs._put_delta(delta, base_d, make_delta(base_d, base, target),
                         raw_len=len(target)) is True
    (broken, _), (gone, gone_data) = _named("ab", 2, seed=6)
    assert cs.put(gone, gone_data, verify=False)    # a delta whose base
    assert cs._put_delta(broken, gone,              # is then removed
                         make_delta(gone, gone_data, b"x"), raw_len=1)
    os.unlink(cs._path_str(gone))
    # every kind, and two names asked twice
    names = [d for d, _ in raw] + absent + [adir, alink, dangling, delta,
                                            broken, gone, absent[1],
                                            raw[0][0]]
    names += [d for d, _ in other] + [_named("cd", 1, seed=7)[0][0]]
    return cs, names, [d for d, _ in raw], alink, delta


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@pytest.mark.parametrize("case", [
    "as_found", "known_before", "unlinked_behind", "under_the_threshold",
    "little_of_a_large_directory", "linked_while_listed"])
def test_a_batch_look_lists_the_directory_and_answers_as_the_stats_do(
        tmp_path, monkeypatch, fsync, case):
    """``has_many`` without ``resident_ok`` (index off), the listing
    engaged, against the ``has`` loop — a ``stat`` a name — over two
    lives of one tree: the same answer name for name, the same entries
    entered and dropped, the same ``residentDrops``; under the threshold
    — fewer names than ``_LIST_MIN_NAMES``, or fewer than one in
    ``_LIST_ENTRIES_PER_STAT`` of what a directory of the store holds —
    no directory is listed at all; a name linked while its directory is
    being read stays remembered."""
    import os

    import dfs_tpu.store.cas as cas
    first, names, raw, alink, delta = _look_tree(tmp_path / "chunks", fsync)
    by_list = ChunkStore(first.root, fsync=fsync)       # two lives that
    by_stat = ChunkStore(first.root, fsync=fsync)       # know nothing
    assert by_list._deltas_possible() and by_stat._deltas_possible()
    if case in ("known_before", "unlinked_behind"):
        for cs in (by_list, by_stat):
            cs.has_many(names, resident_ok=True)        # learn the names
            assert len(cs._resident) == 40 + 1 + 2      # raw, link, cd's
    lost = []
    if case == "unlinked_behind":
        lost = [raw[3], raw[17], alink]
        for d in lost:
            os.unlink(first._path_str(d))               # behind both
    if case == "under_the_threshold":
        monkeypatch.setattr(cas, "_LIST_MIN_NAMES", len(names) + 1)
    if case == "little_of_a_large_directory":
        # 60 names of a directory of a store of 1 000 a directory
        by_list._count = cas._SHARD_DIRS * 1000
        assert 60 * cas._LIST_ENTRIES_PER_STAT < 1000
    real_scandir = os.scandir
    listed = []
    late = _named("ab", 1, seed=9)[0]

    def scandir(path):
        listed.append(os.fspath(path))
        it = real_scandir(path)
        if case == "linked_while_listed" and path.endswith("/ab"):
            entries = list(it)      # the directory is read: now a put
            it.close()              # links a name the listing lacks
            assert by_list.put(late[0], late[1], verify=False) is True
            assert _key(late[0]) in by_list._resident
            import contextlib
            return contextlib.nullcontext(iter(entries))
        return it

    monkeypatch.setattr(os, "scandir", scandir)
    if case == "linked_while_listed":
        names = names + [late[0]]
    got = by_list.has_many(names)
    monkeypatch.undo()
    if case == "linked_while_listed":
        # the listing lacks the name (where deltas may be, the chain's
        # end is looked for after it and finds the file): whatever the
        # answer, the entry its put made stays
        assert _key(late[0]) in by_list._resident
        assert by_list.resident_stats()["residentDrops"] == 0
        assert by_list.has(late[0], resident_ok=True) is True
        by_stat.has(late[0])
    want = [by_stat.has(d) for d in names[:len(got)]]
    if case == "linked_while_listed":
        want[-1] = got[-1]
    assert got == want
    assert sum(got) >= 40 + 2 + 2 - len(lost)   # raw, link, delta, cd's
    assert by_list._resident == by_stat._resident
    assert _stale(by_list) == []
    assert by_list.resident_stats()["residentDrops"] \
        == by_stat.resident_stats()["residentDrops"] \
        == (len(lost) if case == "unlinked_behind" else 0)
    look = by_list.look_stats()
    asked_ab = sum(d[:2] == "ab" for d in names)
    if case in ("under_the_threshold", "little_of_a_large_directory"):
        assert listed == []
        assert look == {"lookStats": len(names), "lookListed": 0,
                        "lookListings": 0}
    else:
        # `ab` listed once; `cd`, asked three names of, a stat a name
        assert listed == [os.path.join(first._root_str, "ab")]
        assert look == {"lookStats": len(names) - asked_ab,
                        "lookListed": asked_ab, "lookListings": 1}
    assert by_stat.look_stats() == {"lookStats": 0, "lookListed": 0,
                                    "lookListings": 0}
    # has() of one name, a resident_ok batch and a second life's answers
    # are what they were: no listing, no look counted
    before = by_list.look_stats()
    assert by_list.has_many(names, resident_ok=True) \
        == [by_stat.has(d, resident_ok=True) for d in names]
    assert by_list.look_stats() == before


# -- a lock a shard directory (PR 42): the ordering rule a digest ----------

def _on_stripe(stripe, tag, size=600):
    """``(digest, data)`` whose digest's first byte — its shard
    directory, the lock that orders it — is ``stripe``."""
    for i in range(1 << 16):
        data = f"{tag}-{i}-".encode() * (size // 8)
        d = sha256_hex(data)
        if _key(d)[0] == stripe:
            return d, data
    raise AssertionError("no such digest")


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_INDEX
def test_puts_racing_deletes_of_the_same_digests_end_agreeing(
        tmp_path, fsync, index):
    """Threads putting and deleting the same few digests — two of them
    in one shard directory — with looks beside them: when all have
    ended, the resident set holds no name the disk lacks, the index says
    of every digest what the disk says, and the gauge counts the files."""
    import os
    import sys
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    plane = _plane(tmp_path / "plane", cs) if index else None
    items = [_on_stripe(7, "a"), _on_stripe(7, "b"), _on_stripe(200, "c"),
             *_batch(3, seed=42)]
    assert cs.count() == 0
    errors = []
    start = threading.Barrier(6)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)         # threads change places mid-call

    def worker(k):
        try:
            start.wait(10)
            for i in range(60):
                d, data = items[(i + k) % len(items)]
                if k % 3 == 0:
                    cs.delete(d)
                elif k % 3 == 1:
                    cs.put_batch([(d, data), items[(i + k + 1) % len(items)]])
                else:
                    cs.put(d, data)
                    cs.has_many([x for x, _ in items], resident_ok=bool(i % 2))
        except BaseException as e:      # noqa: BLE001 - shown by the assert
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads) and errors == []
        assert not any(mu.locked() for mu in cs._dir_mu)
        on_disk = {d for d, _ in items if os.path.isfile(cs._path_str(d))}
        assert _stale(cs) == []
        assert {k.hex() for k in cs._resident} <= on_disk
        if plane is not None:
            assert {d for d, _ in items if plane.lookup(d)} == on_disk
        assert cs.count() == len(on_disk) == len(cs.digests())
        assert _temps(cs.root) == []
        for d, data in items:
            assert cs.has(d, resident_ok=True) is (d in on_disk)
            assert cs.get(d) == (data if d in on_disk else None)
    finally:
        sys.setswitchinterval(interval)
        if plane is not None:
            plane.close()


class _Recorded:
    """A lock of ``ChunkStore._dir_mu`` that writes down who took it."""

    def __init__(self, stripe, log):
        import threading
        self.stripe, self.log, self.mu = stripe, log, threading.Lock()

    def acquire(self, blocking=True):
        got = self.mu.acquire(blocking)
        if got:
            self.log.append(self.stripe)
        return got

    def release(self):
        self.mu.release()

    def locked(self):
        return self.mu.locked()

    def __enter__(self):
        self.acquire()

    def __exit__(self, *exc):
        self.release()


def _delta_of(cs, stripe, base, tag):
    """A delta blob against ``base`` (stored raw in ``cs``) for a target
    whose digest lives in shard directory ``stripe``."""
    from dfs_tpu.sim.delta import make_delta
    bd, bdata = base
    for i in range(1 << 16):
        target = bdata + f"{tag}-{i}".encode()
        d = sha256_hex(target)
        if _key(d)[0] == stripe:
            return d, target, make_delta(bd, bdata, target)
    raise AssertionError("no such digest")


_STRIPES = pytest.mark.parametrize(
    "delta_stripe,base_stripe", [(200, 3), (3, 200), (9, 9)],
    ids=["base-below", "base-above", "same-directory"])


@_STRIPES
def test_put_delta_holds_its_own_lock_and_its_bases_in_ascending_order(
        tmp_path, delta_stripe, base_stripe):
    """The one place that orders two digests: the delta's link, its pin
    and its index record happen under the locks of BOTH directories,
    the lower taken first (one lock where they are the same); a delete
    and a drop take their own digest's alone."""
    cs = ChunkStore(tmp_path / "chunks", fsync=False)
    log = []
    cs._dir_mu = tuple(_Recorded(k, log) for k in range(256))
    base = _on_stripe(base_stripe, "base")
    assert cs.put(*base) and log == [base_stripe]
    d, target, blob = _delta_of(cs, delta_stripe, base, "t")
    del log[:]
    assert cs._put_delta(d, base[0], blob, raw_len=len(target)) is True
    assert log == sorted({delta_stripe, base_stripe})
    assert cs.get(d) == target and cs.delta_pinned(base[0])
    del log[:]
    assert cs.delete(base[0]) is False          # pinned: refused
    assert log == [base_stripe]
    del log[:]
    assert cs.delete(d) is True                 # the delta: dropped
    assert set(log) == {delta_stripe}
    assert cs.delete(base[0]) is True and not cs.delta_pinned(base[0])
    assert not any(mu.locked() for mu in cs._dir_mu)


@_STRIPES
def test_a_delete_of_the_base_waits_for_the_delta_that_pins_it(
        tmp_path, monkeypatch, delta_stripe, base_stripe):
    """``delete(base)`` arriving while ``_put_delta`` is between its link
    and its pin — inside both locks — waits, then finds the pin and
    refuses: never a broken chain."""
    import os
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=False)
    base = _on_stripe(base_stripe, "base")
    assert cs.put(*base)
    d, target, blob = _delta_of(cs, delta_stripe, base, "t")
    dp = cs._delta_path_str(d)
    answers = []
    deleter = threading.Thread(
        target=lambda: answers.append(cs.delete(base[0])))
    real_link = os.link

    def link(src, dst, *a, **kw):
        out = real_link(src, dst, *a, **kw)
        if str(dst) == dp:
            deleter.start()                     # linked, not yet pinned
            deleter.join(0.3)
            assert deleter.is_alive() and answers == []
        return out

    monkeypatch.setattr(os, "link", link)
    assert cs._put_delta(d, base[0], blob, raw_len=len(target)) is True
    deleter.join(10)
    assert answers == [False]
    assert cs.get(d) == target and cs.get(base[0]) == base[1]


@pytest.mark.parametrize("fsync", [True, False], ids=["fsync", "none"])
@_STRIPES
def test_a_delta_put_racing_a_delete_of_its_base_pins_or_rolls_back(
        tmp_path, fsync, delta_stripe, base_stripe):
    """Under threads, round after round: either the delete came first and
    the delta rolled itself back (``None``: the caller stores raw), or
    the pin came first and the delete was refused — one of the two, the
    chain never broken; beside them a second ``_put_delta`` whose
    directories are the reverse pair, which must not deadlock."""
    import threading
    cs = ChunkStore(tmp_path / "chunks", fsync=fsync)
    other = _on_stripe(delta_stripe, "other-base")
    assert cs.put(*other)
    for rnd in range(25):
        base = _on_stripe(base_stripe, f"base{rnd}")
        assert cs.put(*base)
        d, target, blob = _delta_of(cs, delta_stripe, base, f"t{rnd}")
        # the reverse pair: a delta in the base's directory against a
        # base in the delta's
        rd, rtarget, rblob = _delta_of(cs, base_stripe, other, f"r{rnd}")
        got = {}
        start = threading.Barrier(3)

        def run(name, fn):
            start.wait(10)
            got[name] = fn()

        threads = [
            threading.Thread(target=run, args=("stored", lambda: cs._put_delta(
                d, base[0], blob, raw_len=len(target)))),
            threading.Thread(target=run, args=("deleted", lambda: cs.delete(
                base[0]))),
            threading.Thread(target=run, args=("reverse", lambda: cs._put_delta(
                rd, other[0], rblob, raw_len=len(rtarget))))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads), "deadlock"
        assert not any(mu.locked() for mu in cs._dir_mu)
        assert got["reverse"] is True and cs.get(rd) == rtarget
        assert got["stored"] in (True, None)
        assert got["deleted"] is (got["stored"] is None), got
        if got["stored"]:
            assert cs.delta_base(d) == base[0] and cs.delta_pinned(base[0])
            assert cs.get(d) == target and cs.get(base[0]) == base[1]
        else:
            assert cs.delta_base(d) is None and not cs.delta_pinned(base[0])
            assert cs.has(d) is False and cs.get(d) is None
            assert cs.get(base[0]) is None
        assert cs.delta_depth(rd) == 1 and cs.delta_pinned(other[0])
    assert cs.delete(other[0]) is False         # 25 deltas lean on it
