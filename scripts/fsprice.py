#!/usr/bin/env python3
"""What a ``stat`` and a directory listing cost on THIS file system, idle
and beside fsyncing writers — the measurement the chunk store's
``_LIST_MIN_NAMES`` / ``_LIST_ENTRIES_PER_STAT`` are derived from
(``dfs_tpu/store/cas.py``; PERF.md §6, PR 35).

    python scripts/fsprice.py            # here: a place to look, not a price
    chiprun -- python scripts/fsprice.py # the benchmark's machine: the price

Directories of 30 / 120 / 500 / 3 400 files of 8 KiB (a shard directory
of a cell's node, and of the source's deployment) under ``$TMPDIR``:
per directory size, the median ``os.path.isfile`` of a present and of an
absent name, one ``os.scandir`` with the wanted names' ``is_file``, and
how many ``stat``s that listing is worth — then the same beside eight
threads that create, fsync and link files as the store's writers do.
And what each call of a chunk file's put costs one thread (``price_put``,
PR 38): the prices the put job's phase clock is read against. And how
links scale (``price_links``, PR 39; alone: ``fsprice.py links``): P
processes of four writer threads, with a lock a process around the link
(the chunk store until PR 42), with a lock a directory (the store since)
and with none — whether the store's one mutex or the file system below
it serialised the links of several nodes.
Jax-free, stdlib only; removes what it made.
"""

from __future__ import annotations

import multiprocessing
import os
import random
import shutil
import statistics
import sys
import tempfile
import threading
import time

SIZES = (30, 120, 500, 3400)
WRITERS = 8


def _names(seed: str, n: int) -> list[str]:
    rng = random.Random(seed)       # 64 hex digits: a chunk file's name
    return ["%064x" % rng.getrandbits(256) for _ in range(n)]


def _fill(d: str, names: list[str], size: int = 8192) -> None:
    os.makedirs(d, exist_ok=True)
    for n in names:
        fd = os.open(f"{d}/{n}", os.O_WRONLY | os.O_CREAT, 0o600)
        os.write(fd, b"x" * size)
        os.close(fd)


def measure(tag: str, dirs: dict[str, list[str]]) -> None:
    for size in SIZES:
        stat_s, absent_s, list_s = [], [], []
        for d, names in dirs.items():
            if f"/s{size}-" not in d:
                continue
            asked = names[:200]
            t = time.perf_counter()
            for n in asked:
                os.path.isfile(f"{d}/{n}")
            stat_s.append((time.perf_counter() - t) / len(asked))
            t = time.perf_counter()
            for n in asked:
                os.path.isfile(f"{d}/{n[::-1]}")
            absent_s.append((time.perf_counter() - t) / len(asked))
            want = set(names)
            t = time.perf_counter()
            with os.scandir(d) as it:
                got = {e.name for e in it if e.name in want and e.is_file()}
            list_s.append(time.perf_counter() - t)
            assert len(got) == size
        m = statistics.median
        print(f"{tag} dir={size}: stat present {m(stat_s) * 1e3:.4f} ms, "
              f"absent {m(absent_s) * 1e3:.4f} ms; listing "
              f"{m(list_s) * 1e3:.3f} ms ({m(list_s) / size * 1e6:.2f} "
              f"us/entry) = {m(list_s) / m(stat_s):.1f} stats; worst "
              f"listing {max(list_s) * 1e3:.3f} ms", flush=True)


def price_put(tag: str, root: str, n: int = 1500) -> None:
    """What each call of a chunk file's put costs one thread — the
    prices the put job's phase clock is held against (``/metrics``
    ``durability.put``; PERF.md §5, PR 38): ``n`` files of 8 KiB over 64
    directories, each created (``O_EXCL``), written, fsynced, closed,
    linked to its name, its directory fsynced, its temp unlinked, as
    ``ChunkStore._write_raw`` does a file — but for the directory
    barrier, which a batch pays once a directory."""
    dirs = [f"{root}/put-{tag.split()[0]}-{k:02x}" for k in range(64)]
    for d in dirs:
        os.makedirs(d)
    s = dict.fromkeys(("create", "write", "payload fsync", "close",
                       "link", "dir fsync", "unlink", "stat"), 0.0)
    clock = time.perf_counter
    payload = b"z" * 8192
    for i in range(n):
        d = dirs[i % len(dirs)]
        tmp, p = f"{d}/.tmp-{i}", f"{d}/{i:064x}"
        t0 = clock()
        os.path.isfile(p)
        t1 = clock()
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
        t2 = clock()
        os.write(fd, payload)
        t3 = clock()
        os.fsync(fd)
        t4 = clock()
        os.close(fd)
        t5 = clock()
        # a price list, not a store: nothing here is read back
        # dfslint: ignore[DFS013]
        os.link(tmp, p)
        t6 = clock()
        dfd = os.open(d, os.O_RDONLY)
        os.fsync(dfd)
        os.close(dfd)
        t7 = clock()
        os.unlink(tmp)
        t8 = clock()
        for key, dt in zip(s, (t2 - t1, t3 - t2, t4 - t3, t5 - t4,
                               t6 - t5, t7 - t6, t8 - t7, t1 - t0)):
            s[key] += dt
    print(f"{tag} put of {n} files, ms a call: "
          + ", ".join(f"{k} {v / n * 1e3:.4f}" for k, v in s.items())
          + f"; a file {sum(s.values()) / n * 1e3:.3f}", flush=True)


_LOCKINGS = ("a lock a process", "a lock a directory", "no lock")


def _link_process(root: str, locking: str, threads: int, seconds: float,
                  start, out) -> None:
    """One node's write workers: ``threads`` threads that each make a
    file as ``ChunkStore._write_raw`` does (create ``O_EXCL``, write
    8 KiB, fsync, close), link it to its name — under this process's
    one lock, under the lock of the name's directory (what the store
    does since PR 42: ``ChunkStore._dir_mu``), or under none — and
    unlink the temp."""
    dirs = [f"{root}/{k:02x}" for k in range(64)]
    for d in dirs:
        os.makedirs(d)
    one = threading.Lock()
    mus = {d: one if locking == "a lock a process" else threading.Lock()
           for d in dirs}
    locked = locking != "no lock"
    tally = [[0, 0.0, 0.0] for _ in range(threads)]  # links, wait s, link s
    payload = b"z" * 8192
    clock = time.perf_counter

    def worker(k: int) -> None:
        mine = tally[k]
        start.wait()
        end = clock() + seconds
        i = 0
        while clock() < end:
            d = dirs[(i * threads + k) % len(dirs)]
            tmp, p = f"{d}/.tmp-{k}-{i}", f"{d}/{k:02x}{i:062x}"
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
            os.write(fd, payload)
            os.fsync(fd)
            os.close(fd)
            mu = mus[d]
            t0 = clock()
            if locked:
                mu.acquire()
            t1 = clock()
            # a price list, not a store: nothing here is read back
            # dfslint: ignore[DFS013]
            os.link(tmp, p)
            t2 = clock()
            if locked:
                mu.release()
            os.unlink(tmp)
            mine[0] += 1
            mine[1] += t1 - t0
            mine[2] += t2 - t1
            i += 1

    ws = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    out.put([sum(col) for col in zip(*tally)])


def price_links(root: str, procs=(1, 3, 5), threads: int = 4,
                seconds: float = 4.0) -> None:
    """Do links run side by side? ``P`` processes (a node each) of
    ``threads`` write workers make files for ``seconds``; once every
    process holds ONE lock around its link (the store until PR 42:
    ``ChunkStore._index_mu``), once a lock a directory (the store since:
    ``_dir_mu``), once none. If the lock is the ceiling, the other two
    make more files a second and a link stays near its idle price; if
    the file system serialises links machine-wide, the files a second
    are the same and the wait moves from the lock into the link, whose
    time then grows with ``P`` in every column (PERF.md §7, PR 39, 42)."""
    ctx = multiprocessing.get_context("spawn")
    for p in procs:
        for col, locking in enumerate(_LOCKINGS):
            base = f"{root}/links-{p}-{col}"
            start, out = ctx.Barrier(p * threads), ctx.Queue()
            children = [ctx.Process(
                target=_link_process,
                args=(f"{base}/n{k}", locking, threads, seconds, start, out))
                for k in range(p)]
            for c in children:
                c.start()
            links, wait_s, link_s = (
                sum(col) for col in zip(*(out.get() for _ in children)))
            for c in children:
                c.join()
            print(f"links P={p} x {threads} threads, {locking}: "
                  f"{links / seconds:.0f} links/s, a link "
                  f"{link_s / links * 1e3:.3f} ms, waited for the lock "
                  f"{wait_s / links * 1e3:.3f} ms, the rest of a file "
                  f"{(p * threads * seconds - wait_s - link_s) / links * 1e3:.3f}"
                  f" ms", flush=True)
            shutil.rmtree(base, ignore_errors=True)


def main() -> None:
    root = tempfile.mkdtemp(prefix="fsprice_")
    try:
        if sys.argv[1:] == ["links"]:
            price_links(root)
            return
        t = time.perf_counter()
        dirs: dict[str, list[str]] = {}
        for size in SIZES:
            for k in range(8 if size < 3400 else 2):
                d = f"{root}/s{size}-{k}"
                dirs[d] = _names(d, size)
                _fill(d, dirs[d])
        print(f"made {sum(map(len, dirs.values()))} files in "
              f"{time.perf_counter() - t:.2f}s on {root}", flush=True)
        measure("idle", dirs)
        price_put("idle", root)  # dfslint: ignore[DFS013] - as above
        stop = threading.Event()
        made = [0] * WRITERS

        def writer(k: int) -> None:
            d = f"{root}/w{k}"
            os.makedirs(d)
            while not stop.is_set():
                p = f"{d}/{made[k]}"
                fd = os.open(p + ".tmp",
                             os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
                os.write(fd, b"y" * 8192)
                os.fsync(fd)
                os.close(fd)
                # load beside the measurement, not a store: nothing
                # here is read back  # dfslint: ignore[DFS013]
                os.link(p + ".tmp", p)
                os.unlink(p + ".tmp")
                dfd = os.open(d, os.O_RDONLY)
                os.fsync(dfd)
                os.close(dfd)
                made[k] += 1

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(WRITERS)]
        t = time.perf_counter()
        for w in threads:
            w.start()
        time.sleep(0.5)
        measure(f"beside {WRITERS} writers", dirs)
        measure(f"beside {WRITERS} writers", dirs)
        price_put(f"beside {WRITERS} writers", root)
        stop.set()
        for w in threads:
            w.join()
        print(f"the writers made {sum(made)} files in "
              f"{time.perf_counter() - t:.2f}s")
        price_links(root)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
