"""Small asyncio helpers shared across layers (jax-free)."""

from __future__ import annotations

import asyncio
import time


def create_logged_task(coro, log, what: str) -> asyncio.Task:
    """``asyncio.create_task`` + an exception-logging done-callback.

    The loop holds only WEAK task references, and a task nobody awaits
    reports its exception (at best) at interpreter exit, attributed to
    nothing — so a long-lived background loop (health probes, periodic
    repair/scrub) that dies unexpectedly goes dark in silence. This
    helper is the dfslint-DFS002-clean way to spawn one: the caller
    still must RETAIN the returned task (the done-callback does not keep
    it alive), but an unexpected death is logged the moment it happens.
    Cancellation is not logged — it is how these loops are stopped.
    """
    task = asyncio.create_task(coro)

    def _done(t: asyncio.Task) -> None:
        if t.cancelled():
            return
        exc = t.exception()   # marks it retrieved either way
        if exc is not None:
            log.error("background task %r died unexpectedly: %s: %s",
                      what, type(exc).__name__, exc)

    task.add_done_callback(_done)
    return task


async def gather_abort_siblings(*coros):
    """gather() that CANCELS the surviving coroutines when one raises.

    A bare gather propagates the first exception but leaves its siblings
    running detached — an error aborting one leg of concurrent work
    (e.g. a local-disk failure in a placement batch) must also stop the
    traffic it was gathered with, and must not leak pending tasks into a
    closing loop. Shared by the node runtime's placement gathers and the
    RPC layer's windowed slice sender — one copy of the idiom, not two
    drifting ones.
    """
    tasks = [asyncio.ensure_future(c) for c in coros]
    try:
        return await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        raise


class on_loop_seconds:
    """``await on_loop_seconds(coro, add)`` is ``await coro`` — with
    every step the coroutine runs between two suspensions timed, and
    the seconds handed to ``add``: the time it HELD the event loop, as
    opposed to the time it took. Coroutines it awaits count (they run
    inside its steps); tasks it spawns do not."""

    def __init__(self, coro, add) -> None:
        self._coro = coro
        self._add = add

    def __await__(self):
        coro, add = self._coro, self._add
        exc = None
        try:
            while True:
                t = time.perf_counter()
                try:
                    # a task resumes its coroutine with None, or throws
                    # (cancellation): a future's result is read by the
                    # innermost await, not sent from here
                    waits_on = coro.send(None) if exc is None \
                        else coro.throw(exc)
                except StopIteration as done:
                    return done.value
                finally:
                    add(time.perf_counter() - t)
                exc = None
                try:
                    yield waits_on
                except BaseException as e:  # noqa: BLE001 — thrown on
                    exc = e
        finally:
            coro.close()
