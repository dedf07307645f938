"""The commit (``Ingest._finalize``, docs/ingest.md "The commit"): ONE
pass in a worker thread on each node — tombstone clear and durable
replace under the id's lock — and the coordinator starts every node's
pass at once, so an ack waits for the slower of {its own save, the
peers' saves} and not for their sum.

Order is shown with events held at gates, never with clocks: a save is
stopped inside its worker thread and the test looks at what the other
nodes have done meanwhile. Every entry point ends in the same
``_finalize`` (streamed, whole-body, erasure-coded), so the cases that
are cheap to repeat run through all three.
"""

import asyncio
import errno
import logging
import os
import sys
import threading
import time

import numpy as np
import pytest

from dfs_tpu.config import ChaosConfig
from dfs_tpu.fragmenter.fixed import FixedFragmenter
from dfs_tpu.meta.manifest import Manifest
from dfs_tpu.node.runtime import UploadError
from dfs_tpu.store.cas import ManifestStore
from dfs_tpu.utils.hashing import sha256_hex
from tests.test_node_cluster import make_cluster_cfg, start_nodes, stop_nodes

WAIT_S = 20.0       # every wait in this file is bounded

# entry point -> nodes it needs (a P+Q stripe of 3 wants five)
ENTRIES = {"stream": 3, "body": 3, "ec": 5}
entries = pytest.mark.parametrize("entry", sorted(ENTRIES))


def _payload(rng, n=40_000) -> bytes:
    return rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()


def _upload(node, entry: str, data: bytes, name: str):
    if entry == "stream":
        async def blocks():
            for i in range(0, len(data), 8192):
                yield data[i:i + 8192]
        return node.upload_stream(blocks(), name)
    return node.upload(data, name, ec_k=3 if entry == "ec" else 0)


async def _cluster(entry, tmp_path, **kw):
    cluster = make_cluster_cfg(ENTRIES[entry])
    return await start_nodes(cluster, tmp_path, **kw)


async def _seen(ev: threading.Event) -> None:
    assert await asyncio.to_thread(ev.wait, WAIT_S), "never happened"


class _Gate:
    """Stands in a node's ``ManifestStore.save``: says when the pass was
    ``entered`` (in its worker thread), holds it until ``open`` is set,
    runs the real save, says it ``returned``."""

    def __init__(self, node, held: bool) -> None:
        self.real = node.store.manifests.save
        self.entered = threading.Event()
        self.returned = threading.Event()
        self.open = threading.Event()
        if not held:
            self.open.set()
        self.thread = None
        node.store.manifests.save = self

    def __call__(self, m, *a, **kw):
        self.thread = threading.get_ident()
        self.entered.set()
        assert self.open.wait(WAIT_S), "gate never opened"
        try:
            return self.real(m, *a, **kw)
        finally:
            self.returned.set()


def _finalize_tasks() -> list:
    return [t for t in asyncio.all_tasks()
            if "_finalize" in getattr(t.get_coro(), "__qualname__", "")]


# --------------------------------------------------------------------- #
# (a) side by side
# --------------------------------------------------------------------- #

@entries
def test_every_peer_is_told_while_the_local_save_is_held(
        entry, tmp_path, rng):
    data = _payload(rng)

    async def run():
        nodes = await _cluster(entry, tmp_path)
        try:
            gates = {i: _Gate(n, held=(i == 1)) for i, n in nodes.items()}
            up = asyncio.ensure_future(_upload(nodes[1], entry, data, "a"))
            await _seen(gates[1].entered)
            # the local pass stands at its gate: every peer's announce
            # arm has been entered — and has SAVED — meanwhile
            for i in sorted(nodes):
                if i != 1:
                    await _seen(gates[i].returned)
            fid = sha256_hex(data)
            assert nodes[1].store.manifests.load(fid) is None
            assert all(nodes[i].store.manifests.load(fid) is not None
                       for i in nodes if i != 1)
            await asyncio.sleep(0.05)
            assert not up.done()            # no ack without the local one
            before = nodes[1].counters.snapshot()
            assert before.get("uploads", 0) == 0
            gates[1].open.set()
            m, _ = await asyncio.wait_for(up, WAIT_S)
            assert m.file_id == fid
            assert nodes[1].store.manifests.load(fid) is not None
            assert nodes[1].counters.snapshot()["uploads"] == 1
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


@entries
def test_the_ack_waits_for_the_slower_peer(entry, tmp_path, rng):
    data = _payload(rng)

    async def run():
        nodes = await _cluster(entry, tmp_path)
        try:
            slow = max(nodes)
            gates = {i: _Gate(n, held=(i == slow))
                     for i, n in nodes.items()}
            up = asyncio.ensure_future(_upload(nodes[1], entry, data, "a"))
            await _seen(gates[slow].entered)
            await _seen(gates[1].returned)      # the local save is done
            await asyncio.sleep(0.05)
            assert not up.done()                # ... and the ack waits
            gates[slow].open.set()
            m, _ = await asyncio.wait_for(up, WAIT_S)
            for n in nodes.values():
                assert n.store.manifests.load(m.file_id) is not None
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (b) the local save refused, or raising ENOSPC
# --------------------------------------------------------------------- #

def _fail_local_save(node, how: str, tomb_ts=None):
    """``refused``: the store answers False with a tombstone on the
    disk. (With the clear inside the save's own pass, under the lock a
    DELETE takes, the store as it stands never refuses a FRESH save;
    ``_finalize`` is held to its contract for a store that does — here
    one that writes the tombstone a DELETE in the window would have and
    answers as a save that is not fresh does.) ``enospc``: the save
    raises as a full disk makes it raise."""
    ms = node.store.manifests
    real = ms.save

    def save(m, *a, **kw):
        if how == "enospc":
            raise OSError(errno.ENOSPC, "No space left on device")
        ms.delete(m.file_id, ts=tomb_ts)
        return False

    ms.save = save
    return real


@pytest.mark.parametrize("how", ["refused", "enospc"])
@entries
def test_a_failed_local_save_fails_the_upload_after_the_announces(
        entry, how, tmp_path, rng):
    data = _payload(rng)

    async def run():
        nodes = await _cluster(entry, tmp_path)
        try:
            real = _fail_local_save(nodes[1], how)
            gates = {i: _Gate(n, held=True)
                     for i, n in nodes.items() if i != 1}
            up = asyncio.ensure_future(_upload(nodes[1], entry, data, "b"))
            for g in gates.values():
                await _seen(g.entered)
            # the local save has failed by now or will in a moment; the
            # upload does not end while an announce is in flight
            await asyncio.sleep(0.1)
            assert not up.done()
            for g in gates.values():
                g.open.set()
            with pytest.raises(UploadError) as ei:
                await asyncio.wait_for(up, WAIT_S)
            if how == "enospc":
                assert ei.value.status == 507
                assert "nsufficient storage" in str(ei.value)
                assert nodes[1].counters.snapshot()[
                    "disk_full_rejects"] == 1
            else:
                assert "manifest save refused" in str(ei.value)
            # every announce ran to its end: nothing is left behind
            assert all(g.returned.is_set() for g in gates.values())
            assert _finalize_tasks() == []
            c = nodes[1].counters.snapshot()
            for key in ("uploads", "manifests_saved", "manifest_bytes",
                        "manifest_chunks", "upload_bytes"):
                assert c.get(key, 0) == 0, key
            # a retry of the same upload acks
            nodes[1].store.manifests.save = real
            m, _ = await asyncio.wait_for(
                _upload(nodes[1], entry, data, "b"), WAIT_S)
            c = nodes[1].counters.snapshot()
            assert c["uploads"] == 1 and c["manifests_saved"] == 1
            for n in nodes.values():
                assert n.store.manifests.load(m.file_id) is not None
                assert not n.store.manifests.is_tombstoned(m.file_id)
            _, got = await nodes[max(nodes)].download(m.file_id)
            assert bytes(got) == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (c) ... and the cluster converges to ONE state for that id
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("how,tomb_age_s,alive", [
    ("enospc", None, True),          # the pull gives node 1 the manifest
    ("refused", 3600.0, True),       # the tombstone is older: resurrected
    ("refused", -3600.0, False),     # ... newer: the delete wins
    ("refused", 0.0, None),          # a tie in time: either, but ONE
])
def test_a_failed_commit_converges_to_one_state(
        how, tomb_age_s, alive, tmp_path, rng):
    data = _payload(rng)

    async def run():
        nodes = await _cluster("body", tmp_path)
        try:
            ts = None if not tomb_age_s else time.time() - tomb_age_s
            real = _fail_local_save(nodes[1], how, tomb_ts=ts)
            with pytest.raises(UploadError):
                await _upload(nodes[1], "body", data, "c")
            nodes[1].store.manifests.save = real
            fid = sha256_hex(data)
            # the state this adds: unacked, on the peers, not on node 1
            assert nodes[1].store.manifests.load(fid) is None
            assert nodes[2].store.manifests.load(fid) is not None
            assert nodes[3].store.manifests.load(fid) is not None
            for _ in range(2):              # its anti-entropy passes
                for n in nodes.values():
                    await n.repair_once()
            states = {(n.store.manifests.load(fid) is not None,
                       n.store.manifests.is_tombstoned(fid))
                      for n in nodes.values()}
            assert len(states) == 1, states
            (has, tomb), = states
            assert has != tomb              # a manifest or a tombstone
            if alive is not None:
                assert has == alive
            if has:
                _, got = await nodes[1].download(fid)
                assert bytes(got) == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (d) an announce that fails
# --------------------------------------------------------------------- #

@entries
def test_a_failed_announce_is_logged_and_counted_and_the_upload_acks(
        entry, tmp_path, rng):
    from dfs_tpu.comm.rpc import RpcError
    data = _payload(rng)
    warned: list[str] = []

    class Heard(logging.Handler):
        def emit(self, record):
            warned.append(record.getMessage())

    async def run():
        nodes = await _cluster(entry, tmp_path)
        try:
            client = nodes[1].ingest.client
            real = client.announce

            async def announce(peer, mj, fresh=False):
                if peer.node_id == 2:
                    raise RpcError("node 2 unreachable")
                return await real(peer, mj, fresh=fresh)

            client.announce = announce
            heard = Heard(logging.WARNING)
            nodes[1].ingest.log.addHandler(heard)
            try:
                m, _ = await _upload(nodes[1], entry, data, "d")
            finally:
                nodes[1].ingest.log.removeHandler(heard)
            c = nodes[1].counters.snapshot()
            assert c["announce_failures"] == 1 and c["uploads"] == 1
            assert warned == [
                "announce to node 2 failed: node 2 unreachable"]
            assert nodes[2].store.manifests.load(m.file_id) is None
            for i in nodes:
                if i != 2:
                    assert nodes[i].store.manifests.load(
                        m.file_id) is not None
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (e) where the two crash points stand
# --------------------------------------------------------------------- #

@entries
def test_crash_points_stand_before_any_manifest_and_after_the_local_one(
        entry, tmp_path, rng):
    data = _payload(rng)
    fid = sha256_hex(data)

    async def run():
        nodes = await _cluster(entry, tmp_path,
                               chaos=ChaosConfig(enabled=True))
        try:
            gate = _Gate(nodes[1], held=False)
            seen = []

            def maybe_crash(point):
                if point.startswith("upload."):
                    seen.append((point, gate.returned.is_set(), [
                        i for i in sorted(nodes)
                        if nodes[i].store.manifests.load(fid)
                        is not None]))

            nodes[1].chaos.maybe_crash = maybe_crash
            await _upload(nodes[1], entry, data, "e")
            (p0, saved0, held0), (p1, saved1, held1) = seen
            assert (p0, saved0, held0) == (
                "upload.before_manifest", False, [])
            assert p1 == "upload.after_manifest" and saved1
            assert 1 in held1
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (f) no commit call on a loop
# --------------------------------------------------------------------- #

@entries
def test_no_file_system_call_of_a_commit_runs_on_the_event_loop(
        entry, tmp_path, rng, monkeypatch):
    data = _payload(rng)

    async def run():
        loop_thread = threading.get_ident()
        nodes = await _cluster(entry, tmp_path)
        try:
            gates = {i: _Gate(n, held=False) for i, n in nodes.items()}
            unlinks, real_unlink = [], os.unlink

            def unlink(path, *a, **kw):
                if os.fspath(path).endswith(".tomb"):
                    unlinks.append((os.fspath(path),
                                    threading.get_ident()))
                return real_unlink(path, *a, **kw)

            monkeypatch.setattr(os, "unlink", unlink)
            m, _ = await _upload(nodes[1], entry, data, "f")
            monkeypatch.setattr(os, "unlink", real_unlink)
            # one tombstone clear a node, each inside that node's pass:
            # the save's thread, never the loop's
            assert sorted(p for p, _ in unlinks) == sorted(
                os.fspath(n.store.manifests._tomb_path(m.file_id))
                for n in nodes.values())
            assert all(t != loop_thread for _, t in unlinks)
            for i, g in gates.items():
                assert g.thread is not None and g.thread != loop_thread
                root = os.fspath(nodes[i].store.manifests.root)
                assert [t for p, t in unlinks
                        if p.startswith(root + os.sep)] == [g.thread]
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


# --------------------------------------------------------------------- #
# (g) the store's pass: nine calls, the lock, the refusal
# --------------------------------------------------------------------- #

_OS_CALLS = ("unlink", "open", "write", "fsync", "close", "replace",
             "rename", "stat", "lstat", "fstat", "mkdir", "makedirs",
             "link", "utime", "scandir", "listdir", "access")


def _manifest(name="g.bin") -> Manifest:
    return FixedFragmenter(parts=5).manifest(bytes(range(256)) * 12,
                                             name=name)


def _count_os_calls(monkeypatch) -> list:
    calls: list[str] = []
    for name in _OS_CALLS:
        real = getattr(os, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls.append(_name)
            return _real(*a, **kw)

        monkeypatch.setattr(os, name, counted)
    return calls


@pytest.mark.parametrize("fsync,expected", [
    (True, ["unlink", "open", "write", "fsync", "close", "replace",
            "open", "fsync", "close"]),
    (False, ["unlink", "open", "write", "close", "replace"]),
])
def test_a_fresh_save_makes_only_the_calls_a_durable_replace_needs(
        fsync, expected, tmp_path, monkeypatch):
    ms = ManifestStore(tmp_path / "manifests", fsync=fsync)
    m = _manifest()
    text = m.to_json()
    with monkeypatch.context() as mp:
        calls = _count_os_calls(mp)
        assert ms.save(m, fresh=True, text=text)
    assert calls == expected
    assert ms._path(m.file_id).read_bytes() == text.encode()
    assert ms.load(m.file_id) == m
    assert ms.sweep_tmp(max_age_s=0.0) == 0     # no temp left behind
    # a second save replaces the first, in the same nine
    with monkeypatch.context() as mp:
        calls = _count_os_calls(mp)
        assert ms.save(m, fresh=True)
    assert calls == expected


def test_a_save_that_is_not_fresh_asks_and_is_refused_as_before(tmp_path):
    ms = ManifestStore(tmp_path / "manifests", fsync=True)
    m = _manifest()
    assert ms.save(m)                       # nothing in its way
    assert ms.delete(m.file_id)
    assert ms.is_tombstoned(m.file_id) and ms.load(m.file_id) is None
    assert ms.save(m) is False              # adoption, anti-entropy
    assert ms.save(m, time.time() - 5.0) is False
    assert ms.load(m.file_id) is None and ms.is_tombstoned(m.file_id)
    assert ms.save(m, fresh=True)           # a new upload resurrects
    assert not ms.is_tombstoned(m.file_id) and ms.load(m.file_id) == m
    # adoption keeps the origin's write time
    then = time.time() - 100.0
    assert ms.save(m, then)
    assert abs(ms.mtime(m.file_id) - then) < 1e-3


def test_a_temp_name_a_crash_left_is_stepped_over(tmp_path):
    ms = ManifestStore(tmp_path / "manifests", fsync=True)
    m = _manifest()
    leaked = ms.root / f".tmp-{os.getpid()}-0"
    leaked.write_bytes(b"a previous life of this pid")
    assert ms.save(m, fresh=True) and ms.load(m.file_id) == m
    assert leaked.read_bytes() == b"a previous life of this pid"
    assert ms.ids() == [m.file_id] and ms.sweep_tmp(0.0) == 1


def test_a_failed_write_leaves_no_temp_and_no_manifest(
        tmp_path, monkeypatch):
    ms = ManifestStore(tmp_path / "manifests", fsync=True)
    m = _manifest()

    def full(fd):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(os, "fsync", full)
    with pytest.raises(OSError) as ei:
        ms.save(m, fresh=True)
    monkeypatch.undo()
    assert ei.value.errno == errno.ENOSPC
    assert list(ms.root.iterdir()) == []


def test_a_delete_racing_a_fresh_save_leaves_one_of_the_two(tmp_path):
    """Under the id's lock the two passes serialise: manifest or
    tombstone, never both, never a tombstone the save thought cleared
    beside the manifest it then wrote."""
    ms = ManifestStore(tmp_path / "manifests", fsync=False)
    m = _manifest()
    text = m.to_json()
    rounds, workers = 60, 4
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        deadline = time.monotonic() + WAIT_S
        for _ in range(rounds):
            start = threading.Barrier(2 * workers)
            errors = []

            def go(fn):
                try:
                    start.wait(WAIT_S)
                    fn()
                except BaseException as e:     # noqa: BLE001 - reported
                    errors.append(e)

            ts = [threading.Thread(target=go, args=(
                      lambda: ms.save(m, fresh=True, text=text),))
                  for _ in range(workers)]
            ts += [threading.Thread(target=go, args=(
                       lambda: ms.delete(m.file_id),))
                   for _ in range(workers)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(WAIT_S)
                assert not t.is_alive()
            assert errors == []
            has = ms._path(m.file_id).exists()
            tomb = ms.is_tombstoned(m.file_id)
            assert has != tomb, (has, tomb)
            assert time.monotonic() < deadline
    finally:
        sys.setswitchinterval(old)


# --------------------------------------------------------------------- #
# (h) one serialisation a commit on the coordinator
# --------------------------------------------------------------------- #

@entries
def test_the_coordinator_serialises_once_and_saves_what_it_announced(
        entry, tmp_path, rng, monkeypatch):
    data = _payload(rng)

    async def run():
        nodes = await _cluster(entry, tmp_path)
        try:
            encoded, real_to_json = [], Manifest.to_json

            def to_json(self):
                encoded.append(self)        # held: ids are not reused
                return real_to_json(self)

            monkeypatch.setattr(Manifest, "to_json", to_json)
            client = nodes[1].ingest.client
            real = client.announce
            announced = []

            async def announce(peer, mj, fresh=False):
                announced.append((peer.node_id, mj, fresh))
                return await real(peer, mj, fresh=fresh)

            client.announce = announce
            m, _ = await _upload(nodes[1], entry, data, "h")
            monkeypatch.setattr(Manifest, "to_json", real_to_json)
            assert sum(1 for x in encoded if x is m) == 1
            assert sorted(i for i, _, _ in announced) == [
                i for i in sorted(nodes) if i != 1]
            assert all(fresh for _, _, fresh in announced)
            (text,) = {mj for _, mj, _ in announced}
            on_disk = nodes[1].store.manifests._path(
                m.file_id).read_bytes()
            assert on_disk == text.encode()
            c = nodes[1].counters.snapshot()
            assert c["manifest_bytes"] == len(on_disk)
            assert c["manifest_chunks"] == len(m.chunks)
            # what a peer wrote parses to the same manifest
            assert nodes[2].store.manifests.load(m.file_id) == m
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())
