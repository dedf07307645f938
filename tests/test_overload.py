"""Overload-survival plane (r18): end-to-end deadlines, hedged reads,
and the overload/compound-fault bench (docs/serve.md, docs/chaos.md).

Layers of coverage:

- UNIT: the deadline contextvar (activation, expiry, header/wire
  parsing, task inheritance), the hedge policy (delay clamp + token
  bucket + recency windows), the harness Retry-After decorrelated
  jitter, and the doctor's hedge_storm rule.
- DEFAULT-OFF IDENTITY: no X-Dfs-Deadline header + default config =
  no deadline context, no `deadline` wire field, no hedge policy —
  the pre-r18 read/write paths byte-identical (the chaos/index-plane
  discipline).
- ADMISSION: a request arriving expired sheds at the gate (counted
  ``deadlineShed``, never plain ``shed``); a QUEUED waiter is evicted
  the moment its deadline passes; a queued waiter whose client hangs
  up frees its position and never consumes a slot at the head (the
  r18 disconnect satellite's regression).
- RPC + DISPATCH: the client refuses to send (and to keep retrying)
  expired work; ``_dispatch`` refuses it server-side before any CAS
  touch — with the counter/journal evidence the bench gates on.
- HEDGED READS: a 3-node in-process cluster with one slow replica —
  the hedge fires, the backup wins, the read returns fast, and the
  journal carries hedge_fired/hedge_won.
- The ``bench_overload.py --tiny`` subprocess smoke gating all five
  scripted scenarios end to end + the OVERLOAD_r29.json schema lock.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from dfs_tpu.comm.rpc import DeadlineExpired, InternalClient
from dfs_tpu.config import (CDCParams, CensusConfig, ChaosConfig,
                            ClusterConfig, NodeConfig, PeerAddr,
                            ServeConfig)
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.obs.doctor import diagnose
from dfs_tpu.serve.admission import (AdmissionGate, ClientDisconnected,
                                     ShedError)
from dfs_tpu.serve.hedge import HedgePolicy
from dfs_tpu.utils import deadline

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=2048, avg_size=8192, max_size=65536)
CENSUS_OFF = CensusConfig(history_interval_s=0)


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _mk_cluster(n: int, rf: int) -> ClusterConfig:
    ports = _free_ports(2 * n)
    peers = tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                           port=ports[2 * i],
                           internal_port=ports[2 * i + 1])
                  for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def _start_nodes(cluster: ClusterConfig, root: Path,
                       overrides: dict[int, dict] | None = None
                       ) -> dict[int, StorageNodeServer]:
    nodes = {}
    for p in cluster.peers:
        kw = dict((overrides or {}).get(p.node_id, {}))
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", cdc=CDC,
                         health_probe_s=0, census=CENSUS_OFF, **kw)
        n = StorageNodeServer(cfg)
        await n.start()
        nodes[p.node_id] = n
    return nodes


async def _stop_all(nodes) -> None:
    for n in nodes.values():
        await n.stop()


# ------------------------------------------------------------------ #
# unit: deadline contextvar
# ------------------------------------------------------------------ #

def test_deadline_context_basics():
    assert deadline.remaining() is None
    assert not deadline.expired()
    tok = deadline.activate(30.0)
    try:
        rem = deadline.remaining()
        assert rem is not None and 29.0 < rem <= 30.0
        assert not deadline.expired()
    finally:
        deadline.restore(tok)
    assert deadline.remaining() is None
    # non-positive budget activates ALREADY expired (the drop paths
    # are exactly what must fire for a dead-on-arrival request)
    tok = deadline.activate(-1.0)
    try:
        assert deadline.expired()
    finally:
        deadline.restore(tok)
    # absurd budgets are clamped
    tok = deadline.activate(10 ** 9)
    try:
        assert deadline.remaining() <= deadline.MAX_DEADLINE_S
    finally:
        deadline.restore(tok)


def test_deadline_header_and_wire_parsing():
    assert deadline.parse_header("2.5") == 2.5
    assert deadline.parse_header(" 0.25 ") == 0.25
    assert deadline.parse_header(None) is None
    assert deadline.parse_header("") is None
    assert deadline.parse_header("soon") is None
    assert deadline.parse_header("inf") is None
    assert deadline.parse_wire(1.5) == 1.5
    assert deadline.parse_wire(2) == 2.0
    assert deadline.parse_wire(None) is None
    assert deadline.parse_wire("1.5") is None
    assert deadline.parse_wire(True) is None
    assert deadline.parse_wire(float("nan")) is None


def test_deadline_inherited_by_tasks_and_threads():
    async def run() -> None:
        tok = deadline.activate(60.0)
        try:
            async def child() -> float | None:
                return deadline.remaining()

            got = await asyncio.create_task(child())
            assert got is not None and got > 50.0
            got = await asyncio.to_thread(deadline.remaining)
            assert got is not None and got > 50.0
        finally:
            deadline.restore(tok)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# unit: hedge policy
# ------------------------------------------------------------------ #

def test_hedge_policy_delay_clamp():
    h = HedgePolicy(floor_s=0.05, cap_s=0.5, budget_per_s=10.0)
    assert h.delay_s(None) == 0.05            # no sample: floor
    assert h.delay_s(0.001) == 0.05           # below floor: floor
    assert h.delay_s(0.06) == pytest.approx(0.18)   # 3x mean
    assert h.delay_s(10.0) == 0.5             # above cap: cap


def test_hedge_policy_token_bucket_and_windows():
    h = HedgePolicy(floor_s=0.0, cap_s=1.0, budget_per_s=0.0)
    h._tokens = 2.0
    assert h.take() and h.take()
    assert not h.take()                       # empty, no refill
    assert h.denied == 1
    h.note_fired()
    h.note_fired()
    h.note_won()
    s = h.stats()
    assert s["fired"] == 2 and s["won"] == 1 and s["denied"] == 1
    assert s["firedRecent"] == 2 and s["deniedRecent"] == 1
    # refill restores tokens over time
    h2 = HedgePolicy(floor_s=0.0, cap_s=1.0, budget_per_s=1000.0)
    while h2.take():
        pass
    time.sleep(0.01)                          # ~10 tokens of refill
    assert h2.take()


class _Racers:
    """Two RPCs whose ends the test orders: ``issue(n)`` waits for its
    gate, then returns or raises what ``outcome[n]`` holds."""

    def __init__(self, outcome: dict) -> None:
        self.outcome = outcome
        self.gate = {n: asyncio.Event() for n in outcome}
        self.ended = {n: asyncio.Event() for n in outcome}

    async def issue(self, n: int):
        try:
            await self.gate[n].wait()
            if isinstance(self.outcome[n], Exception):
                raise self.outcome[n]
            return self.outcome[n]
        finally:
            self.ended[n].set()

    async def end_in_order(self, *order: int) -> None:
        for n in order:
            self.gate[n].set()
            await self.ended[n].wait()
            for _ in range(3):      # let race() see this end first
                await asyncio.sleep(0)


PRIMARY, BACKUP = 2, 3


@pytest.mark.parametrize("order", [(PRIMARY, BACKUP), (BACKUP, PRIMARY)],
                         ids=["primary fails first", "backup fails first"])
def test_race_both_sides_failed_raises_the_primarys_error(order):
    """Every plane's hedged call (node read, node write, smart client):
    when BOTH sides fail the caller sees the failure of the peer it
    chose, whichever side failed first — its health/error handling is
    aimed at that peer. (Until PR 29 the smart client's own copy of the
    race surfaced whichever side failed LAST.)"""
    from dfs_tpu.comm.rpc import RpcError, RpcUnreachable
    from dfs_tpu.comm.wire import WireError

    async def run() -> None:
        h = HedgePolicy(floor_s=0.0, cap_s=0.0, budget_per_s=100.0)
        r = _Racers({PRIMARY: RpcUnreachable("primary down"),
                     BACKUP: WireError("backup garbled")})
        dead: list[int] = []
        events: list[str] = []
        racing = asyncio.ensure_future(h.race(
            r.issue, PRIMARY, BACKUP, op="get_chunks", delay_s=0.0,
            event=lambda t, **kw: events.append(t),
            mark_dead=dead.append))
        await r.end_in_order(*order)
        with pytest.raises(RpcUnreachable, match="primary down"):
            await racing
        assert (h.fired, h.won) == (1, 0)
        assert events == ["hedge_fired"]
        # unreachable is evidence only from the side that failed FIRST
        # (the other's failure is the error being raised)
        assert dead == ([PRIMARY] if order[0] == PRIMARY else [])
        assert issubclass(RpcUnreachable, RpcError)

    asyncio.run(run())


def test_race_winner_survives_a_loser_that_fails_garbled():
    """The loser is cancelled and reaped; if it had ALREADY failed —
    with a transport error or a garbled frame (``WireError``, which the
    smart client's copy let through until PR 29) — the winner's reply
    is still the result."""
    from dfs_tpu.comm.wire import WireError

    async def run() -> None:
        h = HedgePolicy(floor_s=0.0, cap_s=0.0, budget_per_s=100.0)
        r = _Racers({PRIMARY: "pairs", BACKUP: WireError("garbled")})
        racing = asyncio.ensure_future(h.race(
            r.issue, PRIMARY, BACKUP, op="get_chunks", delay_s=0.0))
        while h.fired == 0:             # both in flight, at their gates
            await asyncio.sleep(0)
        await asyncio.sleep(0)
        # both end in the SAME loop turn: the primary's reply is taken
        # first, the backup is found already failed when it is reaped
        r.gate[BACKUP].set()
        r.gate[PRIMARY].set()
        assert await racing == ("pairs", PRIMARY)
        assert r.ended[BACKUP].is_set()
        assert (h.fired, h.won) == (1, 0)

    asyncio.run(run())


def test_serve_config_validates_deadline_hedge_fields():
    with pytest.raises(ValueError):
        ServeConfig(default_deadline_s=-1)
    with pytest.raises(ValueError):
        ServeConfig(hedge_floor_s=0.5, hedge_cap_s=0.1)
    with pytest.raises(ValueError):
        ServeConfig(hedge_budget_per_s=-1)
    # hedge master switch: no budget, no policy
    from dfs_tpu.serve import ServingTier

    tier = ServingTier(ServeConfig())
    assert tier.hedge is None
    assert tier.stats()["hedge"]["enabled"] is False
    assert tier.stats()["defaultDeadlineS"] == 0.0
    tier_on = ServingTier(ServeConfig(hedge_budget_per_s=5.0))
    assert tier_on.hedge is not None
    assert tier_on.stats()["hedge"]["enabled"] is True


# ------------------------------------------------------------------ #
# unit: harness Retry-After decorrelated jitter
# ------------------------------------------------------------------ #

def test_loadgen_honors_retry_after_with_jitter(tmp_path):
    """A 503 with Retry-After is retried AFTER a decorrelated-jitter
    sleep bounded below by the advertised budget — never immediately
    (the retry-storm regression this satellite fixes)."""
    from scripts.chaos_harness import ClusterHarness, LoadGen

    h = ClusterHarness(1, tmp_path, chaos=False)
    answers = [(503, b"busy", {"retry-after": "2"}),
               (503, b"busy", {"retry-after": "2"}),
               (201, json.dumps({"fileId": "x"}).encode(), {})]
    calls: list = []

    def fake_http_h(node, method, path, body=None, headers=None,
                    timeout=60.0):
        calls.append(path)
        return answers[min(len(calls) - 1, len(answers) - 1)]

    h.http_h = fake_http_h
    load = LoadGen(h, payload_bytes=64, retry_503=2)
    sleeps: list[float] = []
    load._sleep = sleeps.append
    status, _ = load._request_with_503_retry(1, "POST", "/upload")
    assert status == 201
    assert len(calls) == 3 and len(sleeps) == 2
    # sleep 1: uniform(retry_after, 3*retry_after) — never below the
    # advertised budget, never an immediate retry
    assert 2.0 <= sleeps[0] <= 6.0
    # sleep 2 decorrelates off sleep 1 (uniform(base, 3*prev), capped)
    assert 2.0 <= sleeps[1] <= min(10.0, 3.0 * sleeps[0])
    assert load.snapshot()["retries_503"] == 2
    # retries exhausted: the final 503 is returned, not retried forever
    calls.clear()
    sleeps.clear()
    answers[:] = [(503, b"busy", {"retry-after": "1"})] * 5
    status, _ = load._request_with_503_retry(1, "GET", "/download")
    assert status == 503 and len(calls) == 3 and len(sleeps) == 2


# ------------------------------------------------------------------ #
# unit: doctor hedge_storm rule
# ------------------------------------------------------------------ #

def _snap(nid: int, hedge: dict | None) -> dict:
    return {"nodeId": nid, "now": time.time(),
            "hedge": hedge if hedge is not None else {"enabled": False}}


def test_doctor_hedge_storm_rule():
    now = time.time()
    # sustained at-refill hedging -> storm
    sick = {1: _snap(1, {"enabled": True, "budgetPerS": 0.5,
                         "firedRecent": 30, "deniedRecent": 0}),
            2: _snap(2, None)}
    for s in sick.values():
        s["receivedAt"] = now
    findings = diagnose(sick, coordinator_now=now)
    rules = [f["rule"] for f in findings]
    assert "hedge_storm" in rules
    f = next(f for f in findings if f["rule"] == "hedge_storm")
    assert f["peers"] == [1]
    # SUSTAINED denials count as storm evidence even below the
    # refill-rate bar; a single blip's denial (the plane absorbing a
    # burst as designed) does not
    denied = {1: _snap(1, {"enabled": True, "budgetPerS": 5.0,
                           "firedRecent": 10, "deniedRecent": 9,
                           "receivedAt": now})}
    assert any(f["rule"] == "hedge_storm"
               for f in diagnose(denied, coordinator_now=now))
    blip = {1: _snap(1, {"enabled": True, "budgetPerS": 5.0,
                         "firedRecent": 10, "deniedRecent": 1,
                         "receivedAt": now})}
    assert not any(f["rule"] == "hedge_storm"
                   for f in diagnose(blip, coordinator_now=now))
    # a handful of hedges is the plane WORKING, not a storm
    quiet = {1: _snap(1, {"enabled": True, "budgetPerS": 0.05,
                          "firedRecent": 3, "deniedRecent": 0,
                          "receivedAt": now})}
    assert not any(f["rule"] == "hedge_storm"
                   for f in diagnose(quiet, coordinator_now=now))
    # malformed cross-version fields cost nothing
    bad = {1: _snap(1, {"enabled": True, "budgetPerS": "lots",
                        "firedRecent": "many", "receivedAt": now})}
    assert not any(f["rule"] == "hedge_storm"
                   for f in diagnose(bad, coordinator_now=now))
    # a generous budget's at-refill bar clamps to the producer's
    # bounded window (hedge.py windowCap): a SATURATED window is a
    # storm even though refill*60 (=1200 here) is a count the 512-cap
    # deque can never show — without the clamp the rule was dead code
    # exactly for generous budgets (r18 review finding)
    saturated = {1: _snap(1, {"enabled": True, "budgetPerS": 20.0,
                              "firedRecent": 512, "deniedRecent": 0,
                              "windowCap": 512, "receivedAt": now})}
    assert any(f["rule"] == "hedge_storm"
               for f in diagnose(saturated, coordinator_now=now))


# ------------------------------------------------------------------ #
# admission: deadline eviction + disconnect
# ------------------------------------------------------------------ #

def test_gate_sheds_expired_on_arrival_counted_separately():
    async def run() -> None:
        gate = AdmissionGate("download", slots=2, queue_depth=4)
        tok = deadline.activate(-1.0)
        try:
            with pytest.raises(ShedError):
                await gate.acquire()
        finally:
            deadline.restore(tok)
        s = gate.stats()
        assert s["deadlineShed"] == 1
        assert s["shed"] == 0          # NOT a capacity shed
        assert s["active"] == 0        # no slot consumed
        # without a deadline the gate admits normally
        await gate.acquire()
        assert gate.stats()["active"] == 1
        gate.release()

    asyncio.run(run())


def test_gate_evicts_queued_waiter_on_deadline_expiry():
    async def run() -> None:
        gate = AdmissionGate("download", slots=1, queue_depth=4)
        await gate.acquire()               # hold the only slot
        tok = deadline.activate(0.05)
        try:
            t0 = time.monotonic()
            with pytest.raises(ShedError):
                await gate.acquire()
            took = time.monotonic() - t0
            assert took < 2.0              # evicted AT expiry, not at
            # slot-release time (the holder never releases here)
        finally:
            deadline.restore(tok)
        s = gate.stats()
        assert s["deadlineShed"] == 1 and s["waiting"] == 0
        # the slot is intact: release hands it to a live waiter
        waiter = asyncio.create_task(gate.acquire())
        await asyncio.sleep(0.01)
        gate.release()
        await asyncio.wait_for(waiter, timeout=2)
        assert gate.stats()["active"] == 1
        gate.release()
        assert gate.stats()["active"] == 0

    asyncio.run(run())


def test_gate_frees_slot_of_hung_up_queued_waiter():
    """THE disconnect regression: a queued download whose client hangs
    up must free its queue position — when the head of the queue is
    reached the slot passes to a LIVE waiter, and the dead request
    never holds it."""

    async def run() -> None:
        gate = AdmissionGate("download", slots=1, queue_depth=8)
        await gate.acquire()               # hold the only slot
        gone = asyncio.get_running_loop().create_future()

        async def disconnected():
            return await gone              # resolves to b"" = EOF

        dead = asyncio.create_task(gate.acquire(
            disconnected=lambda: disconnected()))
        await asyncio.sleep(0.01)
        live = asyncio.create_task(gate.acquire())   # queued behind it
        await asyncio.sleep(0.01)
        assert gate.stats()["waiting"] == 2
        gone.set_result(b"")               # the dead client hangs up
        with pytest.raises(ClientDisconnected):
            await dead
        assert gate.stats()["disconnects"] == 1
        assert gate.stats()["waiting"] == 1
        # slot release skips the ghost and admits the live waiter
        gate.release()
        await asyncio.wait_for(live, timeout=2)
        assert gate.stats()["active"] == 1
        gate.release()
        assert gate.stats()["active"] == 0
        # stray non-EOF bytes are NOT a hangup: the waiter stays
        # queued and the watcher RE-ARMS (a one-shot watcher would go
        # blind after the first byte)
        await gate.acquire()
        calls: list[int] = []

        async def noisy():
            calls.append(1)
            if len(calls) == 1:
                return b"x"            # a pipelined stray byte
            # then quiet: a watcher that never resolves again
            return await asyncio.get_running_loop().create_future()

        waiter = asyncio.create_task(gate.acquire(
            disconnected=lambda: noisy()))
        await asyncio.sleep(0.01)
        assert not waiter.done()
        assert len(calls) >= 2         # re-armed after the stray byte
        gate.release()
        await asyncio.wait_for(waiter, timeout=2)
        gate.release()
        # stray byte FOLLOWED by a real EOF: the re-armed watcher must
        # still catch the hangup (one-shot disarming missed exactly
        # this — the dead request consumed a slot at the head)
        await gate.acquire()
        seq = [b"x", b""]

        async def stray_then_eof():
            if seq:
                return seq.pop(0)
            return await asyncio.get_running_loop().create_future()

        dead2 = asyncio.create_task(gate.acquire(
            disconnected=lambda: stray_then_eof()))
        with pytest.raises(ClientDisconnected):
            await dead2
        assert gate.stats()["disconnects"] == 2
        gate.release()
        assert gate.stats()["active"] == 0

    asyncio.run(run())


# ------------------------------------------------------------------ #
# RPC client + dispatch: expired work never runs
# ------------------------------------------------------------------ #

def test_rpc_client_refuses_expired_work():
    async def run() -> None:
        client = InternalClient()
        called = []

        async def boom(*a, **kw):
            called.append(1)
            raise AssertionError("expired call must never reach the "
                                 "wire")

        client._request = boom
        peer = PeerAddr(node_id=2, host="127.0.0.1", port=1,
                        internal_port=1)
        tok = deadline.activate(-1.0)
        try:
            with pytest.raises(DeadlineExpired):
                await client.call(peer, {"op": "health"})
        finally:
            deadline.restore(tok)
        assert not called

    asyncio.run(run())


def test_rpc_client_stops_retrying_when_budget_cannot_cover():
    """First attempt fails at the transport; the remaining deadline
    cannot cover backoff + connect — the client gives up with
    DeadlineExpired instead of burning retries on a dead caller."""

    async def run() -> None:
        client = InternalClient(connect_timeout_s=2.0, retries=3)
        attempts = []

        async def fail_once(peer, header, body, timeout_s=None,
                            acct=None):
            attempts.append(1)
            raise ConnectionRefusedError("nope")

        client._call_once = fail_once
        peer = PeerAddr(node_id=2, host="127.0.0.1", port=1,
                        internal_port=1)
        tok = deadline.activate(0.5)    # < backoff + connect_timeout
        try:
            with pytest.raises(DeadlineExpired):
                await client.call(peer, {"op": "health"})
        finally:
            deadline.restore(tok)
        assert len(attempts) == 1       # no second attempt
        # without a deadline the same failure retries the full envelope
        with pytest.raises(Exception) as ei:
            await client.call(peer, {"op": "health"})
        assert "unreachable" in str(ei.value)
        assert len(attempts) == 1 + client.retries

    asyncio.run(run())


def test_dispatch_drops_expired_and_wire_carries_remaining(tmp_path):
    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            n1, n2 = nodes[1], nodes[2]
            # live deadline rides the wire and the op is served
            tok = deadline.activate(30.0)
            try:
                resp, _ = await n1.client.call(cluster.peer(2),
                                               {"op": "health"})
                assert resp["ok"]
            finally:
                deadline.restore(tok)
            # expired context server-side: _dispatch refuses before any
            # CAS touch, with the counter + journal evidence
            tok = deadline.activate(0.000001)
            await asyncio.sleep(0.002)
            try:
                resp, _ = await n2._dispatch({"op": "get_chunk",
                                              "digest": "0" * 64}, b"")
            finally:
                deadline.restore(tok)
            assert resp["ok"] is False
            assert "deadline" in resp["error"]
            assert n2.counters.snapshot()["deadline_drops"] >= 1
            # the journal writes on its own thread; wait for the emit
            # to reach disk before reading the file back
            await asyncio.to_thread(n2.obs.journal.flush)
            tail = await asyncio.to_thread(n2.obs.journal.tail, 0.0,
                                           256)
            assert any(e.get("type") == "deadline_shed"
                       for e in tail["events"])
            # DEFAULT-OFF IDENTITY: no deadline context -> no wire
            # field, full service (the pre-r18 header exactly)
            sent: list[dict] = []
            real = n1.client._call_once

            async def spy(peer, header, body, timeout_s=None,
                          acct=None):
                sent.append(dict(header))
                return await real(peer, header, body, timeout_s, acct)

            n1.client._call_once = spy
            resp, _ = await n1.client.call(cluster.peer(2),
                                           {"op": "health"})
            assert resp["ok"]
            assert "deadline" not in sent[-1]
            n1.client._call_once = real
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_http_deadline_header_sheds_expired_request(tmp_path):
    """The HTTP edge births the deadline; an expired budget is shed at
    the download gate as a 503 (deadlineShed), and the downloads
    counter proves the read path never ran. Absent header + default
    config = no deadline at all."""

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={1: {"serve": ServeConfig(download_slots=2)}})
        node = nodes[1]
        try:
            data = os.urandom(30000)
            m, _ = await node.upload(data, "f.bin")
            addr = cluster.peer(1)

            async def http(path: str, extra: str = "") -> bytes:
                reader, writer = await asyncio.open_connection(
                    addr.host, addr.port)
                writer.write((f"GET {path} HTTP/1.1\r\n"
                              f"Host: x\r\n{extra}"
                              "Connection: close\r\n\r\n").encode())
                await writer.drain()
                out = await reader.read(-1)
                writer.close()
                return out

            before = node.counters.snapshot().get("downloads", 0)
            out = await http(f"/download?fileId={m.file_id}",
                             "X-Dfs-Deadline: 0.000001\r\n")
            assert out.startswith(b"HTTP/1.1 503")
            assert b"Retry-After" in out
            adm = node.serve.admission.download.stats()
            assert adm["deadlineShed"] == 1 and adm["shed"] == 0
            assert node.counters.snapshot().get("downloads", 0) \
                == before
            # no header: served in full, byte-identical
            out = await http(f"/download?fileId={m.file_id}")
            assert out.startswith(b"HTTP/1.1 200")
            assert out.endswith(data)
            # malformed header: ignored, never an error
            out = await http(f"/download?fileId={m.file_id}",
                             "X-Dfs-Deadline: soon\r\n")
            assert out.startswith(b"HTTP/1.1 200")
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# hedged reads on a live in-process cluster
# ------------------------------------------------------------------ #

def test_hedged_read_beats_slow_replica(tmp_path):
    """3-node rf=2 cluster, node 3 serving every inbound op 250 ms
    late: node 2's remote digests are the {3,1}-owned ones (primary
    node 3), so an unhedged read from node 2 eats the delay while the
    hedged read races node 1 and wins fast — with the
    hedge_fired/hedge_won journal + counter evidence."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        hedged = ServeConfig(hedge_budget_per_s=50.0,
                             hedge_floor_s=0.05, hedge_cap_s=0.3)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={2: {"serve": hedged},
                       3: {"chaos": ChaosConfig(enabled=True)}})
        try:
            # ~25 chunks: the odds that NONE lands in the {3,1} owner
            # set (i.e. node 2 never routes a fetch at node 3 and no
            # hedge can fire) are (2/3)^25 ~ 4e-5 — a 60 KB corpus
            # flaked on exactly that
            data = os.urandom(200000)
            m, _ = await nodes[1].upload(data, "t.bin")
            # healthy warm read (seeds the windowed means)
            _, body = await nodes[2].download(m.file_id)
            assert bytes(body) == data
            nodes[3].chaos.set(serve_delay_s=0.25)
            lats = []
            for _ in range(3):
                t0 = time.monotonic()
                _, body = await nodes[2].download(m.file_id)
                assert bytes(body) == data
                lats.append(time.monotonic() - t0)
            hs = nodes[2].serve.hedge.stats()
            assert hs["fired"] >= 1 and hs["won"] >= 1
            # the hedge must beat the injected delay by a wide margin
            # (~55 ms observed vs 250+ ms unhedged); 0.2 s keeps the
            # assertion robust on a loaded host
            assert min(lats) < 0.2, lats
            await asyncio.to_thread(nodes[2].obs.journal.flush)
            tail = await asyncio.to_thread(nodes[2].obs.journal.tail,
                                           0.0, 512)
            kinds = {e.get("type") for e in tail["events"]}
            assert "hedge_fired" in kinds and "hedge_won" in kinds
            nodes[3].chaos.set(serve_delay_s=0.0)
            # default-off identity: the un-hedged nodes built no policy
            assert nodes[1].serve.hedge is None
            assert nodes[3].serve.hedge is None
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_hedge_budget_empty_waits_primary_out(tmp_path):
    """An exhausted hedge budget must mean NO second RPC — the read
    waits the slow primary out (hedging can never double load)."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        hedged = ServeConfig(hedge_budget_per_s=0.000001,
                             hedge_floor_s=0.01, hedge_cap_s=0.1)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={2: {"serve": hedged},
                       3: {"chaos": ChaosConfig(enabled=True)}})
        try:
            # ~25 chunks, like the sibling test: the denial needs at
            # least one {3,1}-owned digest so a hedge is WANTED —
            # a 40 KB corpus flaked on none existing (~20% of runs)
            data = os.urandom(200000)
            m, _ = await nodes[1].upload(data, "t.bin")
            hedge = nodes[2].serve.hedge
            hedge._tokens = 0.0            # bucket drained
            nodes[3].chaos.set(serve_delay_s=0.2)
            _, body = await nodes[2].download(m.file_id)
            assert bytes(body) == data     # correct, just slow
            hs = hedge.stats()
            assert hs["fired"] == 0 and hs["denied"] >= 1
            nodes[3].chaos.set(serve_delay_s=0.0)
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_streamed_download_stops_at_mid_stream_deadline_expiry(tmp_path):
    """The deadline must keep counting THROUGH a streamed body: the
    HTTP edge deliberately leaves the context armed for the handler's
    body iteration (r18 review finding — restoring it at the response
    head silently disarmed every batch after the first), so a
    mid-download expiry truncates the stream instead of fetching the
    remaining batches for a caller that gave up."""

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={1: {"chaos": ChaosConfig(enabled=True)}})
        node = nodes[1]
        try:
            node._FETCH_BATCH_BYTES = 8192   # many tiny batches
            # geometry chosen so the outcome is deterministic at BOTH
            # extremes of CDC chunking variance: >= 16 batches minimum
            # (1 MB / 64 KiB max chunk) x 50 ms/batch = > 0.8 s total,
            # so a 0.5 s deadline can never serve the full body; and
            # batch 0 costs at most ~5 chunk reads x 50 ms ~ 0.25 s,
            # so the head always commits first (a 120 KB corpus flaked
            # both ways on chunk-count luck)
            data = os.urandom(1_000_000)
            m, _ = await node.upload(data, "f.bin")
            # slow disk makes each batch cost ~50 ms SERVER-side, so
            # the deadline expires mid-stream regardless of how fast
            # the client drains the socket
            node.chaos.set(disk_delay_s=0.05)
            addr = cluster.peer(1)
            reader, writer = await asyncio.open_connection(
                addr.host, addr.port)
            writer.write((f"GET /download?fileId={m.file_id} "
                          "HTTP/1.1\r\nHost: x\r\n"
                          "X-Dfs-Deadline: 0.5\r\n"
                          "Connection: close\r\n\r\n").encode())
            await writer.drain()
            out = await reader.read(-1)
            writer.close()
            node.chaos.set(disk_delay_s=0.0)
            head, _, body = out.partition(b"\r\n\r\n")
            assert head.startswith(b"HTTP/1.1 200")   # head committed
            # before the expiry — truncation is the only honest signal
            assert len(body) < len(data), (
                "expired mid-stream but the full body was served")
            assert node.counters.snapshot()["deadline_drops"] >= 1
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_hedged_fetch_cancellation_reaps_racers(tmp_path):
    """A cancelled caller (client hung up mid-read) must take its
    in-flight hedge racers down with it — asyncio.shield/wait leave
    them running detached otherwise, still transferring bytes for a
    reader that is gone (r18 review finding)."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        hedged = ServeConfig(hedge_budget_per_s=50.0,
                             hedge_floor_s=0.05, hedge_cap_s=0.3)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={1: {"chaos": ChaosConfig(enabled=True)},
                       2: {"serve": hedged},
                       3: {"chaos": ChaosConfig(enabled=True)}})
        try:
            data = os.urandom(200000)
            m, _ = await nodes[1].upload(data, "t.bin")
            _, body = await nodes[2].download(m.file_id)   # warm
            # BOTH replicas slow: the hedge fires at ~50 ms and the
            # race then provably stays in flight past the cancel point
            # (a fast backup resolves it in ~60 ms total — the first
            # cut of this test cancelled a download that had already
            # finished)
            nodes[3].chaos.set(serve_delay_s=0.4)
            nodes[1].chaos.set(serve_delay_s=0.4)
            before = set(asyncio.all_tasks())
            dl = asyncio.create_task(nodes[2].download(m.file_id))
            await asyncio.sleep(0.15)   # hedge fired, both in flight
            dl.cancel()
            with pytest.raises(asyncio.CancelledError):
                await dl
            await asyncio.sleep(0.1)   # reaping settles
            # exclude the SERVER-side frame-service tasks: an
            # in-service op is deliberately never cancelled on peer
            # hangup (pre-r10 semantics, wire.py _on_broken) — they
            # finish their injected delay and fail at the reply write.
            # The CLIENT-side racers are what must not survive.
            leaked = [
                t for t in asyncio.all_tasks() - before
                if not t.done() and t is not asyncio.current_task()
                and t.get_coro().__qualname__
                != "FrameServerProtocol._serve"]
            assert not leaked, (
                f"cancelled download leaked racers: {leaked}")
            nodes[3].chaos.set(serve_delay_s=0.0)
            nodes[1].chaos.set(serve_delay_s=0.0)
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# the bench smoke + artifact schema lock
# ------------------------------------------------------------------ #

def test_bench_overload_tiny_smoke(tmp_path):
    """``bench_overload.py --tiny`` end to end: overload against armed
    gates (shed curve + Retry-After + goodput SLO + the deadline
    never-executed proof), compound faults, a membership change during
    a partition, EC reconstruction under a killed shard holder, and
    the hedged-read held-count/RPC gates — all green, plus the
    OVERLOAD_r29.json schema lock against the committed artifact
    (OVERLOAD_r18.json is the same bench as recorded with its
    timer-driven pulse, before PR 29; nothing produces it any more)."""
    out_path = tmp_path / "overload_tiny.json"
    res = subprocess.run(
        [sys.executable, str(REPO / "bench_overload.py"), "--tiny",
         "--out", str(out_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=540,
        # TMPDIR: the bench's three clusters live and die in this
        # test's own directory
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO), "TMPDIR": str(tmp_path)})
    os.sync()   # drain our writeback before the next test's fsyncs
    assert res.returncode == 0, (
        f"bench_overload --tiny failed:\n{res.stdout[-2000:]}"
        f"\n{res.stderr[-4000:]}")
    out = json.loads(out_path.read_text())
    assert out["metric"] == "overload_survival" and out["round"] == 29
    assert out["ok"] is True
    scenarios = out["scenarios"]
    assert set(scenarios) == {"overload", "compound", "ring_partition",
                              "ec_faults", "hedged_reads"}
    for name, s in scenarios.items():
        assert s["ok"] is True, name
    ov = scenarios["overload"]
    assert ov["shed_curve_engaged"] and ov["retry_after_present"]
    assert ov["zero_acked_loss"] and ov["byte_identical"]
    assert ov["goodput_within_slo"]
    assert ov["deadline_never_executed"]
    assert ov["offered_x_capacity"] == 5.0
    assert scenarios["compound"]["full_node_answers_507"]
    assert scenarios["compound"]["zero_acked_loss"]
    assert scenarios["ring_partition"]["epochs_converged"]
    assert scenarios["ec_faults"]["reconstruction_exercised"]
    assert scenarios["ec_faults"]["background_read_corruptions"] == 0
    hd = scenarios["hedged_reads"]
    # the tail cut as counts (bench_overload.py: a HELD read took at
    # least half the injected delay; the p99 read is held with hedging
    # off and is not with it on), not as a ratio of two p99s that are
    # each the maximum of 48 wall-clock samples
    tail = -(-hd["reads_per_arm"] // 100)
    assert hd["held_reads_off"] > tail >= hd["held_reads_on"]
    assert hd["rpc_ratio"] <= 1.2
    assert hd["hedge_fired"] > 0 and hd["hedge_won"] > 0

    # schema lock against the COMMITTED artifact: same keys, so the
    # bench cannot drift away from what OVERLOAD_r29.json claims
    committed = json.loads((REPO / "OVERLOAD_r29.json").read_text())
    assert set(committed) == set(out)
    assert set(committed["workload"]) == set(out["workload"])
    assert set(committed["scenarios"]) == set(out["scenarios"])
    for name in scenarios:
        assert set(committed["scenarios"][name]) \
            == set(out["scenarios"][name]), name
    assert committed["ok"] is True
