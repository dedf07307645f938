"""Hedged-read policy: latency-derived hedge delay + a token-bucket
hedge budget (docs/serve.md §hedged reads).

"The Tail at Scale" (Dean & Barroso, CACM 2013) observation: with
replicated immutable chunks, the read tail is set by the SLOWEST
replica a request happens to hit — one 250 ms-slow node makes every
read that routes to it a p99 outlier, while a perfectly good copy sits
idle one ring step away. The fix is the hedged request: if the primary
replica has not answered within a delay derived from its own recent
latency, issue the same fetch to the next replica and take the first
verified answer.

Two disciplines keep hedging from becoming its own overload:

- **Latency-derived delay.** The hedge fires only after
  ``clamp(HEDGE_MEAN_FACTOR x the BEST replica's windowed mean RPC
  latency, floor, cap)`` (RpcStats ``recentSeconds/recentCount``, the
  same 60 s window the doctor's slow_peer rule reads). The best
  replica's mean — "what a healthy copy currently takes" — and NOT the
  primary's own: seeding from the primary is self-referential (its
  slow samples walk its own hedge delay up past its slowness until
  hedging disables itself exactly when it is needed — observed live,
  RpcStats.recent_best_mean docstring). A healthy primary answers well
  inside the healthy mean x factor, so steady-state hedge traffic is
  ~0; the floor stops a microsecond-fast history from hedging every
  call, the cap bounds how long a read waits before trying elsewhere.
- **Token-bucket budget.** Every fired hedge consumes a token
  (``ServeConfig.hedge_budget_per_s`` refill, bounded burst — the r13
  RetryBudget shape). An empty bucket means the primary is waited out
  instead: cluster-wide hedge load is bounded by the refill rate, so
  hedging can never double the fleet's fetch traffic no matter how
  sick a replica gets. Denials are counted and windowed — the doctor's
  ``hedge_storm`` rule reads them.

Loop-affine like the RPC client that drives it: touched only from the
owning event loop, no locks.
"""

from __future__ import annotations

import asyncio
import collections
import time
from typing import Awaitable, Callable

from dfs_tpu.comm.rpc import RpcError, RpcUnreachable
from dfs_tpu.comm.wire import WireError

# hedge delay = clamp(factor x windowed mean, floor, cap): 3x the mean
# approximates "slower than this call usually is, by enough margin that
# healthy jitter does not hedge" without keeping per-peer histograms
HEDGE_MEAN_FACTOR = 3.0


def _ignore(*args, **kwargs) -> None:
    """The journal and the health registry of a caller with neither."""


class HedgePolicy:
    """One node's hedged-read state: delay derivation, the token
    bucket, and the fired/won/denied counters (60 s recency windows for
    the doctor's ``hedge_storm`` rule — the shed_storm no-latch
    discipline)."""

    BURST_CAP = 8.0       # bucket capacity: bounded hedge burst
    RECENT_WINDOW_S = 60.0
    _RECENT_MAX = 512

    def __init__(self, floor_s: float, cap_s: float,
                 budget_per_s: float) -> None:
        self.floor_s = float(floor_s)
        self.cap_s = float(cap_s)
        self.budget_per_s = float(budget_per_s)
        self._tokens = min(self.BURST_CAP, max(1.0, budget_per_s))
        self._last = time.monotonic()
        self.fired = 0
        self.won = 0
        self.denied = 0
        self._fired_ts: collections.deque[float] = \
            collections.deque(maxlen=self._RECENT_MAX)
        self._denied_ts: collections.deque[float] = \
            collections.deque(maxlen=self._RECENT_MAX)

    def delay_s(self, recent_mean_s: float | None) -> float:
        """Hedge delay given the best replica's windowed mean RPC
        latency (None = no recent sample anywhere: use the floor — a
        cluster we know nothing about is assumed healthy)."""
        if recent_mean_s is None:
            return self.floor_s
        return min(self.cap_s,
                   max(self.floor_s, HEDGE_MEAN_FACTOR * recent_mean_s))

    def take(self) -> bool:
        """Consume one hedge token; False = budget empty (the caller
        waits the primary out — denial counted for hedge_storm)."""
        now = time.monotonic()
        self._tokens = min(self.BURST_CAP,
                           self._tokens + (now - self._last)
                           * self.budget_per_s)
        self._last = now
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        self.denied += 1
        self._denied_ts.append(now)
        return False

    def note_fired(self) -> None:
        self.fired += 1
        self._fired_ts.append(time.monotonic())

    def note_won(self) -> None:
        self.won += 1

    async def race(self, issue: Callable[[int], Awaitable], primary_id: int,
                   backup_id: int, *, op: str, delay_s: float,
                   event: Callable[..., None] = _ignore,
                   mark_dead: Callable[[int], None] = _ignore,
                   **detail) -> tuple[object, int]:
        """The hedged call every plane makes (docs/serve.md): ``issue``
        the RPC to the primary; if it outlives ``delay_s`` (see
        :meth:`delay_s`) and the token bucket allows, race the SAME
        call against the backup — first completed reply wins, loser
        cancelled. The node's read path and the smart client race a
        batched ``get_chunks``; the write path a ``store_chunks`` slice
        train, where content-addressed puts make the duplicate
        harmless: every hash-echo-verified slice is a real durable copy
        wherever it landed, and a loser cancelled mid-flight keeps the
        slices it already landed. Returns ``(result, winner_id)``;
        exceptions propagate only when BOTH sides fail (attributed to
        the primary — the caller's health/error handling stays aimed at
        the peer it chose), so a hedge can only ever improve on the
        unhedged call. ``event`` is the node's journal (``detail`` goes
        into its ``hedge_fired``), ``mark_dead`` its health registry;
        the client has neither."""
        task = asyncio.create_task(issue(primary_id))
        btask: asyncio.Task | None = None

        async def reap_on_cancel() -> None:
            """OUR caller was cancelled: the racers must die with it —
            shield/asyncio.wait leave their tasks running detached
            otherwise (up to two ~32 MiB transfers for a caller that
            is gone), and an unretrieved RpcError would log 'exception
            was never retrieved' at GC."""
            racers = [t for t in (task, btask) if t is not None]
            for t in racers:
                t.cancel()
            await asyncio.gather(*racers, return_exceptions=True)

        try:
            return await asyncio.wait_for(asyncio.shield(task),
                                          delay_s), primary_id
        # absence-as-result: the timeout IS the hedge trigger — the
        # shielded primary keeps running and is raced below
        except asyncio.TimeoutError:  # dfslint: ignore[DFS007]
            pass                        # primary still in flight: hedge
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        # (a primary that failed fast raises through: the caller's
        # RpcUnreachable/RpcError handling applies as-is)
        if not self.take():
            try:
                return await task, primary_id
            except asyncio.CancelledError:
                await reap_on_cancel()   # awaiting a Task does not
                raise                    # cancel it — reap explicitly
        self.note_fired()
        event("hedge_fired", op=op, primary=primary_id,
              backup=backup_id, **detail, delayS=round(delay_s, 4))
        btask = asyncio.create_task(issue(backup_id))
        try:
            done, _ = await asyncio.wait(
                {task, btask}, return_when=asyncio.FIRST_COMPLETED)
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        first, other = (task, btask) if task in done else (btask, task)
        first_id, other_id = (primary_id, backup_id) if first is task \
            else (backup_id, primary_id)
        ferr = first.exception()
        if ferr is None:
            # loser cancelled; if it had already failed unreachable,
            # keep the evidence (the health registry would learn it
            # from the next probe anyway — this is just sooner)
            other.cancel()
            try:
                await other
            except (asyncio.CancelledError, RpcError, WireError):  # dfslint: ignore[DFS007]
                pass    # reaped: the winner's reply is the result
            if not other.cancelled() \
                    and isinstance(other.exception(), RpcUnreachable):
                mark_dead(other_id)
            winner, result = first_id, first.result()
        else:
            # first finisher failed: fall to the other side — no third RPC
            if isinstance(ferr, RpcUnreachable):
                mark_dead(first_id)
            try:
                result = await other
            except asyncio.CancelledError:
                await reap_on_cancel()       # the racer must die with us
                raise
            except (RpcError, WireError) as e:
                # both failed: surface the PRIMARY's failure class so the
                # caller's diagnosis targets the peer it actually chose
                raise (ferr if first_id == primary_id else e) from None
            winner = other_id
        if winner == backup_id:
            self.note_won()
            event("hedge_won", op=op, primary=primary_id,
                  backup=backup_id)
        return result, winner

    @staticmethod
    def _recent(ts: collections.deque, cutoff: float) -> int:
        return sum(1 for t in ts if t >= cutoff)

    def stats(self) -> dict:
        """``/metrics`` serve ``hedge`` section. floorS/capS/budgetPerS
        mirror the ServeConfig fields (dfslint DFS005 checks the
        mapping); fired/won/denied are since-boot, the *Recent pair
        covers RECENT_WINDOW_S. The deques are bounded (memory under a
        storm), so the windowed counts SATURATE at ``windowCap`` —
        published so the doctor's hedge_storm rule can clamp its
        fired-at-refill-rate bar to what the window can actually show
        (with a 20/s budget the un-clamped bar would be 1200, a number
        a 512-cap window can never reach — the rule would be dead code
        exactly for generous budgets)."""
        cutoff = time.monotonic() - self.RECENT_WINDOW_S
        return {"enabled": True,
                "floorS": self.floor_s,
                "capS": self.cap_s,
                "budgetPerS": self.budget_per_s,
                "tokens": round(self._tokens, 2),
                "fired": self.fired,
                "won": self.won,
                "denied": self.denied,
                "firedRecent": self._recent(self._fired_ts, cutoff),
                "deniedRecent": self._recent(self._denied_ts, cutoff),
                "windowCap": self._RECENT_MAX}


__all__ = ["HEDGE_MEAN_FACTOR", "HedgePolicy"]
