"""Async front of the local CAS (the write-path "disk tier").

Every ``ChunkStore`` operation is blocking file I/O; called inline from
the node's asyncio runtime it occupies the event loop for the syscall's
duration — under writeback pressure that measured multi-second stalls
during which the node answered nothing (the store_chunks receive path
learned this first, runtime._dispatch). This wrapper runs chunk
put/get through a small dedicated thread pool so

- the event loop never blocks on chunk file I/O, and
- disk concurrency is BOUNDED (``IngestConfig.cas_io_threads``) instead
  of riding the unbounded default ``asyncio.to_thread`` executor, which
  let a burst of concurrent reads stack arbitrary many file descriptors
  and seeks.

Batch variants (:meth:`put_many` / :meth:`get_many`) never dispatch per
chunk — a lock+wakeup per item is real time at CDC chunk sizes
(thousands of chunks per batch) on the 1-core CI host. ``get_many`` is
ONE worker job; ``put_many`` cuts a large batch by directory into at
most ``workers`` jobs, so the batch's barriers are in flight on every
worker the pool has instead of in series on one.

The wrapper also attributes time: ``queue_s`` (submitted jobs waiting
for a free worker — the disk tier is saturated) vs ``busy_s`` (actual
I/O), surfaced under ``/metrics`` ``ingest.cas`` for the write-path
stall breakdown (docs/ingest.md).
"""

from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Sequence, TypeVar

from dfs_tpu.store.cas import ChunkStore

T = TypeVar("T")

# a batch smaller than this stays one job: its directories are met once
# each anyway, and a handful of files is not worth four dispatches
_SPLIT_MIN_ITEMS = 64


class AsyncChunkStore:
    """Bounded-thread-pool async wrapper over one node's :class:`ChunkStore`.

    Three lanes, because a batch job pins a worker for its whole list
    or part (hundreds of chunk files — seconds under writeback pressure)
    and FIFO queueing behind one would blow a peer RPC's budget, making
    a merely BUSY node look dead to its callers — the same
    probe-starvation failure the internal admission gate exempts health
    ops to avoid:

    - ``cas-w``: puts (ingest batches, handoff);
    - ``cas-r``: batched reads (``get_many`` — degraded-read gathers);
    - ``cas-g``: SINGLE-chunk gets (the peer-facing ``get_chunk``
      dispatch and ``_fetch_chunk``), so the latency-critical path
      never queues behind either batch lane.
    """

    def __init__(self, store: ChunkStore, workers: int = 4,
                 obs=None) -> None:
        self.store = store
        # Observability hook: when set, each op records a `cas.<op>`
        # span under the caller's trace context (the await happens on
        # the event-loop side, so ContextVar inheritance is free even
        # though run_in_executor itself does not copy contexts).
        self._obs = obs
        self._workers = max(1, int(workers))
        self._wpool = ThreadPoolExecutor(
            max_workers=self._workers, thread_name_prefix="cas-w")
        self._rpool = ThreadPoolExecutor(
            max_workers=self._workers, thread_name_prefix="cas-r")
        self._gpool = ThreadPoolExecutor(
            max_workers=max(2, self._workers // 2),
            thread_name_prefix="cas-g")
        self._lock = threading.Lock()
        # per lane, in the order w, r, g: [jobs ended, their seconds
        # queued, their seconds on a worker]; stats() serves them, and
        # their sums as the totals
        self._lanes = {self._wpool: [0, 0.0, 0.0],
                       self._rpool: [0, 0.0, 0.0],
                       self._gpool: [0, 0.0, 0.0]}
        self._pending = 0   # submitted, not yet finished — the backlog
        # gauge the runtime sentinel samples (obs/sentinel.py): a value
        # persistently above the worker count means the disk tier is
        # saturated and callers are queueing

    async def _run(self, pool: ThreadPoolExecutor,
                   fn: Callable[[], T], opname: str | None = None) -> T:
        import asyncio

        t_submit = time.perf_counter()
        lane = self._lanes[pool]
        with self._lock:
            self._pending += 1

        def job() -> T:
            t_start = time.perf_counter()
            try:
                return fn()
            finally:
                t_end = time.perf_counter()
                with self._lock:
                    self._pending -= 1
                    lane[0] += 1
                    lane[1] += t_start - t_submit
                    lane[2] += t_end - t_start

        def recalled(cfut) -> None:
            # a caller cancelled while the job still waited for a worker
            # (an aborted upload's batch): the pool drops it unrun, its
            # finally never runs, so the gauge is unwound here
            if cfut.cancelled():
                with self._lock:
                    self._pending -= 1

        try:
            # run_in_executor, by hand: the pool's own future is needed
            cfut = pool.submit(job)
        except BaseException:
            # submit failed (pool shut down): the job will never run its
            # finally, so the backlog gauge must be unwound here
            with self._lock:
                self._pending -= 1
            raise
        cfut.add_done_callback(recalled)
        fut = asyncio.wrap_future(cfut)
        if self._obs is None or opname is None:
            return await fut
        with self._obs.span(opname):
            return await fut

    async def get(self, digest: str) -> bytes | None:
        return await self._run(self._gpool,
                               lambda: self.store.get(digest), "cas.get")

    async def put(self, digest: str, data: bytes,
                  verify: bool = False) -> bool:
        return await self._run(
            self._wpool,
            lambda: self.store.put(digest, data, verify=verify), "cas.put")

    async def has_many(self, digests: Sequence[str],
                       resident_ok: bool = False) -> list[bool]:
        """Batched local existence — ONE worker job for the whole
        probe list. The ``has_chunks`` server path and the resume
        probe used to pay a per-digest job (or, worse, inline loop
        stats); a hot probe service must cost one worker dispatch per
        LIST. A caller that says a resident answer will do
        (``resident_ok``: placement's probes and pre-ack rounds) is
        answered from the store's resident set, dedup plane on or off —
        microseconds a list once the store has linked or seen the
        names, and on a booted node (the set complete) for the names
        nobody has as well. Everyone else rides the index when the plane is on
        (store/cas.py: ~0.15 ms a lookup) and with it off pays a
        ``stat`` a digest, which on a busy file system measured 0.67 ms
        each (PERF.md §6, PR 28): a repair slice of 2 048 digests is
        over a second of one worker.
        On the LATENCY lane (``cas-g``), not the batch-read lane:
        peers time budget a probe like a metadata op, so it must never
        queue behind a multi-second ``get_many`` gather."""
        if not digests:
            return []
        ds = list(digests)
        return await self._run(
            self._gpool, lambda: self.store.has_many(ds, resident_ok),
            "cas.has_many")

    async def get_many(self, digests: Sequence[str]
                       ) -> list[tuple[str, bytes]]:
        """(digest, bytes) for every digest present locally — one worker
        job for the whole list; absent digests are simply missing."""
        if not digests:
            return []
        ds = list(digests)
        return await self._run(
            self._rpool,
            lambda: [(d, b) for d in ds
                     if (b := self.store.get(d)) is not None],
            "cas.get_many")

    async def put_many(self, items: Sequence[tuple[str, bytes]],
                       verify: bool = False) -> list[bool]:
        """Store a batch; per-item True = newly stored (False = dedup
        hit), same contract as :meth:`ChunkStore.put`, in the items'
        order.

        A batch is written as a batch (:meth:`ChunkStore.put_batch`:
        payload barriers, links, one directory barrier per directory)
        and spread over the write pool's workers: cut by directory
        (``digest[:2]``, contiguous ranges) into at most ``workers``
        parts, one ``cas-w`` job each, awaited together inside the one
        ``cas.put_many`` span. A directory belongs to one part, so a
        batch still pays one barrier per directory. A batch under
        ``_SPLIT_MIN_ITEMS``, and any batch when the similarity plane is
        attached (its sketch pass is one launch per batch), is one job.
        An exception in any part fails the call once every part has
        ended (no job is left writing behind a failed call)."""
        if not items:
            return []
        import asyncio

        its = list(items)
        parts = self._split(its)

        def job(idx: list[int]) -> Callable[[], list[bool]]:
            return lambda: self.store.put_batch(
                [its[i] for i in idx], verify=verify)

        with (self._obs.span("cas.put_many") if self._obs is not None
              else contextlib.nullcontext()):
            done = await asyncio.gather(
                *(self._run(self._wpool, job(idx)) for idx in parts),
                return_exceptions=True)
        results = [False] * len(its)
        for idx, got in zip(parts, done):
            if isinstance(got, BaseException):
                raise got
            for i, newly in zip(idx, got):
                results[i] = newly
        return results

    def _split(self, its: list[tuple[str, bytes]]) -> list[list[int]]:
        """Indexes of ``its`` cut into at most ``workers`` contiguous
        ranges of its directory order, a directory never in two."""
        if (self._workers == 1 or len(its) < _SPLIT_MIN_ITEMS
                or self.store.sim is not None):
            return [list(range(len(its)))]
        order = sorted(range(len(its)), key=lambda i: its[i][0][:2])
        parts: list[list[int]] = []
        start = 0
        for k in range(1, self._workers + 1):
            end = max(start, len(order) * k // self._workers)
            while 0 < end < len(order) and \
                    its[order[end]][0][:2] == its[order[end - 1]][0][:2]:
                end += 1
            if end > start:
                parts.append(order[start:end])
            start = end
        return parts

    async def inventory(self, list_prefixes=None,
                        list_cap: int = 4096) -> dict:
        """Bucketed CAS census scan (:meth:`ChunkStore.inventory`) as
        ONE read-pool job — a readdir+stat pass over the whole store
        (or, with ``list_prefixes``, a readdir of exactly those
        buckets — the drill-down never re-pays the full scan), which
        must ride the bounded batch lane like every other store-wide
        touch (a census fan-out must never occupy the event loop or
        stack unbounded executor jobs)."""
        lp = list(list_prefixes) if list_prefixes else None
        return await self._run(
            self._rpool,
            lambda: self.store.inventory(lp, list_cap=list_cap),
            "cas.inventory")

    @property
    def pending(self) -> int:
        """Jobs submitted but not yet finished (queued + running)."""
        with self._lock:
            return self._pending

    def stats(self) -> dict:
        """``/metrics`` ``ingest.cas``: jobs ended, their seconds queued
        for a worker and their seconds on one — as totals, and by lane
        under ``lanes``: ``w`` the write pool (puts), ``r`` the batch
        reads (``get_many``, the census scan), ``g`` the 2-worker
        latency lane (single gets and every ``has_many``: placement's
        probes, the verify round, the repair cycle's). The totals are
        the lanes' sums."""
        with self._lock:
            lanes = {name: {"ops": lane[0], "queueS": round(lane[1], 6),
                            "busyS": round(lane[2], 6)}
                     for name, lane in zip("wrg", self._lanes.values())}
            pending = self._pending
        total = {key: sum(lane[key] for lane in lanes.values())
                 for key in ("ops", "queueS", "busyS")}
        return {"workers": self._workers, "ops": total["ops"],
                "pending": pending,
                "queueS": round(total["queueS"], 6),
                "busyS": round(total["busyS"], 6),
                "lanes": lanes}

    def close(self) -> None:
        # wait=False: in-flight jobs finish on their worker threads, but
        # an async stop() must not block its loop on the drain
        self._wpool.shutdown(wait=False)
        self._rpool.shutdown(wait=False)
        self._gpool.shutdown(wait=False)
