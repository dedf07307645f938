"""The seam between the node's write layers (docs/ingest.md): the arrows
point one way — api/http → node/ingest → node/placement → store + rpc —
and placement's plan and ``filter_credits`` steps are plain functions of
(ring map, batch, filter states, echo cache), exercised here without a
cluster. The cluster tests (test_index.py, test_node_cluster.py, …) are
the proof that behaviour did not move; these pin the decisions."""

from __future__ import annotations

import ast
import asyncio
import types
from pathlib import Path

import pytest

from dfs_tpu.comm.rpc import RpcError
from dfs_tpu.index import EchoCache
from dfs_tpu.index.filter import LocalFilter, PeerFilterSet
from dfs_tpu.node.placement import (BatchPlacement, filter_credits,
                                    plan_batch)
from dfs_tpu.ring import RingMap
from dfs_tpu.serve.hedge import HedgePolicy
from dfs_tpu.utils.hashing import sha256_hex

NODE = Path(__file__).resolve().parent.parent / "dfs_tpu" / "node"


def _imports(path: Path) -> set[str]:
    """Every module ``path`` imports, at any depth of the file."""
    out: set[str] = set()
    for n in ast.walk(ast.parse(path.read_text())):
        if isinstance(n, ast.Import):
            out.update(a.name for a in n.names)
        elif isinstance(n, ast.ImportFrom) and n.module:
            out.add(n.module)
            out.update(f"{n.module}.{a.name}" for a in n.names)
    return out


@pytest.mark.parametrize("module,forbidden", [
    ("placement.py", ("dfs_tpu.node.runtime", "dfs_tpu.api",
                      "dfs_tpu.node.ingest")),
    ("ingest.py", ("dfs_tpu.node.runtime", "dfs_tpu.api")),
    ("errors.py", ("dfs_tpu",)),
    # the external client and the RPC layer are nobody's upper layer
    # in the node: what both need (``slice_payloads``) lives in comm/
    ("../client/smart.py", ("dfs_tpu.node", "dfs_tpu.api")),
    ("../comm/rpc.py", ("dfs_tpu.node", "dfs_tpu.api", "dfs_tpu.client")),
])
def test_layers_import_downward_only(module, forbidden):
    src = NODE / module
    bad = {m for m in _imports(src) for f in forbidden
           if m == f or m.startswith(f + ".")}
    assert not bad, f"{module} imports upward: {sorted(bad)}"
    # ... and names the server nowhere, not even in a comment
    assert "StorageNodeServer" not in src.read_text()


# --------------------------------------------------------------------- #
# plan and filter_credits as plain functions
# --------------------------------------------------------------------- #

RING = RingMap.static([1, 2, 3])
SELF, RF = 1, 2


def _digest_owned_by(*owners: int) -> str:
    """A digest whose rf=2 owner list on the static ring is ``owners``."""
    for i in range(10_000):
        d = sha256_hex(f"seam-{i}".encode())
        if tuple(RING.owners(d, RF)) == owners:
            return d
    raise AssertionError(f"no digest owned by {owners}")


MINE_2 = _digest_owned_by(1, 2)      # this node and peer 2
MINE_3 = _digest_owned_by(3, 1)      # peer 3 and this node
THEIRS = _digest_owned_by(2, 3)      # both owners are peers
BATCH = [(d, d.encode()) for d in (MINE_2, MINE_3, THEIRS)]


def _plane(claims: dict[int, list[str]], echoed=()):
    """An index plane as ``filter_credits`` sees it: a replica of each
    listed peer's filter holding that peer's claimed digests, and an
    echo cache holding the ``echoed`` (peer, digest) confirmations."""
    peer_filters = PeerFilterSet()
    for peer, digests in claims.items():
        theirs = LocalFilter()
        for d in digests:
            theirs.add(d)
        peer_filters.apply_full(peer, *theirs.snapshot())
    cache = EchoCache(16)
    for peer, d in echoed:
        cache.confirm(peer, d)
    return types.SimpleNamespace(local_filter=LocalFilter(),
                                 peer_filters=peer_filters,
                                 echo_cache=cache)


EVERYTHING = [MINE_2, MINE_3, THEIRS]


def test_plan_splits_a_batch_by_owner():
    plan = plan_batch(RING, SELF, BATCH, RF, {})
    assert [d for d, _ in plan.local_puts] == [MINE_2, MINE_3]
    assert {n: [d for d, _ in w] for n, w in plan.per_node.items()} \
        == {2: [MINE_2, THEIRS], 3: [MINE_3, THEIRS]}
    # a local target is a copy already; a peer's is not, until it answers
    assert plan.copies == {MINE_2: 1, MINE_3: 1, THEIRS: 0}
    assert plan.payload_of[THEIRS] == THEIRS.encode()


def test_plan_sends_a_pinned_digest_to_its_holder_only():
    """EC stripe placement: the pinned holder replaces the ring owners."""
    plan = plan_batch(RING, SELF, BATCH, 1, {THEIRS: (3,), MINE_2: (1,)})
    assert [d for d, _ in plan.local_puts] == [MINE_2]
    assert [d for d, _ in plan.per_node[3]] == [MINE_3, THEIRS]
    assert 2 not in plan.per_node
    assert plan.copies == {MINE_2: 1, MINE_3: 0, THEIRS: 0}


@pytest.mark.parametrize("case,plane,dead,expected", [
    # no plane, or a plane whose filter exchange is off: every leg asks
    ("index off", None, (), {}),
    ("filter exchange off",
     types.SimpleNamespace(local_filter=None), (), {}),
    # peer 2 sent no filter yet: its leg probes everything, so THEIRS
    # is vouched for there and peer 3's positive may be credited
    ("no replica of one peer's filter", _plane({3: EVERYTHING}), (),
     {3: {MINE_3, THEIRS}}),
    # PR 27's case: both owners of THEIRS are peers and both filters
    # claim it — credited twice it would be stored nowhere this node can
    # vouch for. The first leg asks; the second may credit.
    ("every filter claims a chunk this node does not own",
     _plane({2: EVERYTHING, 3: EVERYTHING}), (),
     {2: {MINE_2}, 3: {MINE_3, THEIRS}}),
    # a corpse backs nothing: with peer 2 dead nobody else vouches for
    # THEIRS, so the one live leg asks about it
    ("a dead peer", _plane({2: EVERYTHING, 3: EVERYTHING}), (2,),
     {3: {MINE_3}}),
    # a hash echo on record from peer 2 is evidence: peer 3's positive
    # may be credited, and peer 2's leg needs no credit for it
    ("the echo cache vouches for one leg",
     _plane({2: EVERYTHING, 3: EVERYTHING}, echoed=[(2, THEIRS)]), (),
     {2: {MINE_2}, 3: {MINE_3, THEIRS}}),
    # a filter that rules a chunk out never credits it (it is sent)
    ("filters that rule the batch out", _plane({2: [], 3: []}), (), {}),
])
def test_filter_credits(case, plane, dead, expected):
    plan = plan_batch(RING, SELF, BATCH, RF, {})
    credits = filter_credits(plan, plane, lambda nid: nid not in dead)
    assert {n: ds for n, ds in credits.items() if ds} == expected, case
    # whatever the filters say, a chunk this node does not hold is
    # sent to, or asked of, at least one live owner
    crediting = [n for n, ds in credits.items() if THEIRS in ds]
    live_legs = [n for n in plan.per_node if n not in dead]
    assert len(crediting) < len(live_legs), case


# --------------------------------------------------------------------- #
# the hedged slice train: whose in-flight peak is recorded
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("backup,peaks,won", [
    # the backup train fails, the primary's then lands: its peak counts
    # (until PR 29 it was dropped on this one path of four)
    (RpcError("backup refused"), [("sliceInflight", 4)], 0),
    # the backup lands first: the primary is cancelled, and the peak of
    # a train to a non-canonical holder is nobody's window to tune
    (2, [], 1),
], ids=["primary wins after the backup failed", "backup wins"])
def test_hedged_transfer_records_the_primarys_peak_only(backup, peaks,
                                                        won):
    """``_transfer`` under a hedge policy, without a cluster: leg 2's
    train outlives the hedge delay, a second train goes to node 3 (the
    next holder of MINE_2 that is neither a primary nor this node), and
    the test ends the two in the order of its case."""
    gate = {2: asyncio.Event(), 3: asyncio.Event()}
    ended = {2: asyncio.Event(), 3: asyncio.Event()}
    outcome = {2: 4, 3: backup}

    class Client:
        async def store_chunks_windowed(self, peer, file_id, slices,
                                        window, on_slice):
            try:
                await gate[peer].wait()
                if isinstance(outcome[peer], Exception):
                    raise outcome[peer]
                return outcome[peer]
            finally:
                ended[peer].set()

    recorded: list[tuple[str, int]] = []
    hedge = HedgePolicy(floor_s=0.0, cap_s=0.0, budget_per_s=100.0)
    env = types.SimpleNamespace(
        cfg=types.SimpleNamespace(
            node_id=SELF,
            cluster=types.SimpleNamespace(replication_factor=RF,
                                          peer=lambda nid: nid),
            ingest=types.SimpleNamespace(slice_inflight=2)),
        ring=types.SimpleNamespace(current=RING), echo_cache=None,
        client=Client(), hedge=hedge,
        health=types.SimpleNamespace(is_alive=lambda nid: True,
                                     mark_dead=lambda nid: None),
        obs=types.SimpleNamespace(
            event=lambda *a, **kw: None,
            rpc_client=types.SimpleNamespace(
                recent_best_mean=lambda op: None)),
        stalls=types.SimpleNamespace(
            peak=lambda name, v: recorded.append((name, v))),
        under_replicated=set())
    missing = [(MINE_2, MINE_2.encode())]

    async def run() -> None:
        leg = BatchPlacement(env, "f" * 64, missing, {}, None, {}, None)
        sending = asyncio.ensure_future(
            leg._transfer(2, missing, [missing]))
        for nid in (3, 2):              # the backup's train ends first
            gate[nid].set()
            await ended[nid].wait()
            for _ in range(3):
                await asyncio.sleep(0)
        await sending

    asyncio.run(run())
    assert recorded == peaks
    assert (hedge.fired, hedge.won) == (1, won)
