"""Span totals and the owner inside the trace (dfs_tpu/obs, the chip
owner's phase clock): self time under an injected clock, the bounded
per-name table, ``m0`` in ``/trace``, and a streamed upload through a
delegating node whose stitched tree holds the owner's spans.

The arithmetic runs on an injected clock (no sleeps, exact numbers);
the end-to-end cases run the anchored device engine on JAX's CPU
backend behind a real ``SidecarServer``, as tests/test_sidecar.py does.
"""

import asyncio
import contextvars
import http.client
import json
import sys
import threading

import numpy as np
import pytest

grpc = pytest.importorskip("grpc")

from dfs_tpu.config import NodeConfig, ObsConfig  # noqa: E402
from dfs_tpu.node.runtime import StorageNodeServer  # noqa: E402
from dfs_tpu.obs import (Observability, _Open, current,  # noqa: E402
                         new_span_id, new_trace_id)
from dfs_tpu.obs.prom import render_node_metrics  # noqa: E402
from dfs_tpu.obs.stitch import render_tree  # noqa: E402
from dfs_tpu.sidecar.service import (SidecarClient,  # noqa: E402
                                     SidecarFragmenter, SidecarServer)
from tests.test_obs import make_cluster_cfg, parse_prom  # noqa: E402

S = 1_000_000_000       # the injected clock counts ns


class Clock:
    def __init__(self) -> None:
        self.t = 5 * S

    def __call__(self) -> int:
        return self.t

    def tick(self, seconds: float) -> None:
        self.t += int(seconds * S)


def make_obs(**cfg):
    clock = Clock()
    return Observability(ObsConfig(**cfg), node_id=1, clock_ns=clock), clock


class Opened:
    """One span held open by hand in a context of its own, so that
    siblings can overlap as they do under ``gather``: a root, or a
    child of the span open in ``under``'s context."""

    def __init__(self, obs, name, under: "Opened | None" = None) -> None:
        self.obs = obs
        if under is None:
            self.ctx = contextvars.copy_context()
            self._cm = obs.request_span(name)
        else:
            self.ctx = under.ctx.run(contextvars.copy_context)
            self._cm = obs.span(name)
        self.ctx.run(self._cm.__enter__)
        self.sid = self.ctx.run(current)[1]

    def child(self, name) -> "Opened":
        return Opened(self.obs, name, under=self)

    def close(self) -> None:
        self.ctx.run(self._cm.__exit__, None, None, None)


# --------------------------------------------------------------------- #
# self time
# --------------------------------------------------------------------- #

def test_self_time_serial_children():
    obs, clock = make_obs()
    with obs.request_span("root"):
        clock.tick(1.0)
        with obs.span("a"):
            clock.tick(2.0)
        clock.tick(0.5)
        with obs.span("b"):
            clock.tick(3.0)
            with obs.span("b.inner"):   # a grandchild is b's, not root's
                clock.tick(1.0)
        clock.tick(0.25)
    t = obs.span_totals()
    assert t["root"] == {"count": 1, "seconds": 7.75, "selfSeconds": 1.75}
    assert t["a"] == {"count": 1, "seconds": 2.0, "selfSeconds": 2.0}
    assert t["b"] == {"count": 1, "seconds": 4.0, "selfSeconds": 3.0}
    assert t["b.inner"]["selfSeconds"] == 1.0


def test_self_time_overlapping_children_is_the_union_never_negative():
    """Children under ``gather`` overlap: [1, 4) and [2, 6) cover 5 s of
    the parent's 7, not 7 (a plain sum would leave self time at 0, and
    three such children would take it negative)."""
    obs, clock = make_obs()
    r = Opened(obs, "root")
    clock.tick(1.0)
    a = r.child("kid")
    clock.tick(1.0)
    b = r.child("kid")
    c = r.child("kid")         # [2, 6) twice
    clock.tick(2.0)
    a.close()
    clock.tick(2.0)
    b.close()
    c.close()
    clock.tick(1.0)
    r.close()
    t = obs.span_totals()
    assert t["kid"] == {"count": 3, "seconds": 11.0, "selfSeconds": 11.0}
    assert t["root"] == {"count": 1, "seconds": 7.0, "selfSeconds": 2.0}


def test_child_that_outlives_its_parent_is_clipped():
    obs, clock = make_obs()
    r = Opened(obs, "root")
    clock.tick(1.0)
    late = r.child("late")
    clock.tick(2.0)
    r.close()                       # the child still runs
    clock.tick(5.0)
    late.close()
    t = obs.span_totals()
    assert t["root"] == {"count": 1, "seconds": 3.0, "selfSeconds": 1.0}
    assert t["late"] == {"count": 1, "seconds": 7.0, "selfSeconds": 7.0}


def test_self_seconds_of_a_serial_trace_sum_to_the_root():
    obs, clock = make_obs()

    def walk(depth: int) -> None:
        with obs.span(f"d{depth}"):
            clock.tick(0.125 * (depth + 1))
            for _ in range(2 if depth < 3 else 0):
                walk(depth + 1)
                clock.tick(0.0625)

    with obs.request_span("root"):
        walk(0)
    t = obs.span_totals()
    assert sum(r["selfSeconds"] for r in t.values()) \
        == pytest.approx(t["root"]["seconds"], abs=1e-6)
    assert all(r["selfSeconds"] <= r["seconds"] for r in t.values())


@pytest.mark.parametrize("n", [10, _Open._FOLD_AT * 3 + 7])
def test_many_children_fold_to_the_same_self_time(n):
    """A span with thousands of short children (a per-chunk read under
    a streamed download) folds what is final into one number: same self
    time as with every interval kept, one long-lived sibling open
    throughout or not, and only the open children held."""
    obs, clock = make_obs(trace_ring=8)
    r = Opened(obs, "root")
    clock.tick(1.0)
    for i in range(n):
        kid = r.child("kid")
        clock.tick(0.002)
        kid.close()
        clock.tick(0.001)
        if i == n // 2:
            long = r.child("long")     # open over the second half
    assert len(obs._open[r.sid].kids) <= 2 * _Open._FOLD_AT
    long.close()
    r.close()
    t = obs.span_totals()
    first_half = n // 2 + 1
    want = 1.0 + 0.001 * first_half
    assert t["root"]["selfSeconds"] == pytest.approx(want, abs=1e-6)
    assert t["kid"]["count"] == n


def test_concurrent_children_never_lose_an_update():
    """More threads than cores open children under one parent with a
    short switch interval: every span is counted once, self time stays
    within its span, and nothing is left open."""
    obs = Observability(ObsConfig(trace_ring=64), node_id=1)
    n_threads, per = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with obs.request_span("root"):
            ctxs = [contextvars.copy_context() for _ in range(n_threads)]

            def work(ctx):
                def body():
                    for _ in range(per):
                        with obs.span("kid"):
                            with obs.span("leaf"):
                                pass
                ctx.run(body)

            threads = [threading.Thread(target=work, args=(c,))
                       for c in ctxs]
            for th in threads:
                th.start()
            for th in threads:
                th.join(60)
            assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    t = obs.span_totals()
    assert t["kid"]["count"] == t["leaf"]["count"] == n_threads * per
    assert all(0.0 <= r["selfSeconds"] <= r["seconds"] + 1e-9
               for r in t.values())
    assert obs._open == {}


# --------------------------------------------------------------------- #
# the table: lives with the ring, bounded
# --------------------------------------------------------------------- #

def test_totals_absent_and_free_with_tracing_off():
    obs, clock = make_obs(trace_ring=0)
    with obs.request_span("http./x", latency=True):
        with obs.span("upload.place"):
            clock.tick(1.0)
    assert "spans" not in obs.stats()
    assert obs.span_totals() == {} and obs._open == {}
    assert clock.t == 6 * S and obs.stats()["ringSpans"] == 0
    # ... and the clock was never read: tracing off is one branch
    obs._now = None
    with obs.request_span("http./x"):
        with obs.span("upload.place"):
            pass


def test_span_name_cardinality_is_capped():
    obs, _ = make_obs()
    for i in range(obs._MAX_SPAN_NAMES + 40):
        with obs.request_span(f"n{i}"):
            pass
    t = obs.span_totals()
    assert len(t) == obs._MAX_SPAN_NAMES + 1
    assert t["_overflow"]["count"] == 40
    with obs.request_span("n0"):    # a known name still counts as itself
        pass
    assert obs.span_totals()["n0"]["count"] == 2


def test_prom_page_carries_the_span_totals():
    class FakeNode:
        def __init__(self, obs):
            from dfs_tpu.utils.logging import Counters, Stopwatches
            self.obs, self.latency = obs, obs.latency
            self.counters, self.ingest_stalls = Counters(), Stopwatches()
            self.under_replicated = []

    obs, clock = make_obs()
    with obs.request_span("http./upload"):
        clock.tick(1.0)
        with obs.span("upload.place"):
            clock.tick(0.5)
    samples, types, _ = parse_prom(render_node_metrics(FakeNode(obs)))

    def sample(metric, name):
        return samples[(metric, (("name", name),))]

    assert sample("dfs_span_total", "http./upload") == 1
    assert sample("dfs_span_seconds_total", "http./upload") == 1.5
    assert sample("dfs_span_self_seconds_total", "http./upload") == 1.0
    assert sample("dfs_span_seconds_total", "upload.place") == 0.5
    assert types["dfs_span_self_seconds"] == "counter"
    off, _ = make_obs(trace_ring=0)
    assert "dfs_span_" not in render_node_metrics(FakeNode(off))


def test_spans_between_selects_by_the_shared_clock():
    obs, clock = make_obs()
    with obs.request_span("early"):
        clock.tick(1.0)             # [5, 6)
    clock.tick(1.0)
    with obs.request_span("mid"):
        clock.tick(2.0)             # [7, 9)
    clock.tick(1.0)
    with obs.request_span("late"):
        clock.tick(1.0)             # [10, 11)
    got = obs.spans_between(int(6.5 * S), int(9.5 * S))
    assert [s["name"] for s in got] == ["mid"]
    assert got[0]["m0"] == 7 * S
    assert [s["name"] for s in obs.spans_between(8 * S, 10 * S)] \
        == ["mid", "late"]


# --------------------------------------------------------------------- #
# end to end: the owner inside the trace
# --------------------------------------------------------------------- #

PHASES = ("inputWaitS", "dispatchS", "collectS", "replyS")
DEVICE_KEYS = {"platform", "device_kind", "count", "regions",
               "overflow_redos", "segments", "strong_cuts", "window_cuts",
               "forced_cuts", "deviceWaitS", "streams", "streamS",
               "openS", "bytes", *PHASES,
               # the packed regions' own (PR 41); none is packed here
               "packedRegions", "packedStreams", "packedBytes",
               "packedCapacityBytes", "packWaitS", "packRoundS",
               # a streamed walk's windows (PR 43)
               "windows", "windowBytes", "tailWindows", "stagedTimed",
               "stagedTimedBytes", "stagedTimedS", "pendingAtDispatch",
               "bufferPeakBytes"}


@pytest.fixture(scope="module")
def owner():
    """The chip owner as a deployment runs it — the anchored device
    engine, here on JAX's CPU backend — and one stream of a little
    over 4 MiB through it already (more than a packed region holds, so
    it walks a window of its own, tests/test_packed_region.py has the
    packed ones; the first stream pays the compile)."""
    srv = SidecarServer(port=0, fragmenter="cdc-anchored-tpu")
    srv.start()
    client = SidecarClient(srv.port)
    data = np.random.default_rng(24).integers(
        0, 256, size=4 * 2**20 + 17, dtype=np.uint8).tobytes()
    list(client.chunk_hash_duplex(
        data[i:i + 2**20] for i in range(0, len(data), 2**20)))
    yield srv, client, data
    client.close()
    srv.stop()


def test_owner_phases_sum_to_stream_time(owner):
    _, client, data = owner
    h = client.health()
    dev = h["device"]
    assert set(dev) == DEVICE_KEYS
    assert dev["streams"] >= 1 and dev["regions"] >= 1
    assert dev["bytes"] >= len(data)
    assert sum(dev[p] for p in PHASES) \
        == pytest.approx(dev["streamS"], rel=0.02)
    assert 0.0 < dev["deviceWaitS"] <= dev["collectS"]
    # one stream at a time here: open time is the streams' time
    assert dev["openS"] == pytest.approx(dev["streamS"], rel=0.02)
    # the owner's own totals ride along
    for name in ("owner.stream", "owner.dispatch", "owner.collect"):
        assert h["spans"][name]["count"] >= 1
    assert h["spans"]["owner.stream"]["selfSeconds"] \
        <= h["spans"]["owner.stream"]["seconds"]


def test_owner_trace_answers_by_id_and_by_interval(owner):
    srv, client, data = owner
    frag = SidecarFragmenter(srv.port)
    node_obs = Observability(ObsConfig(), node_id=1)
    try:
        with node_obs.request_span("upload.fragment"):
            tid, parent = current()
            frag.manifest_stream(
                (data[i:i + 2**20] for i in range(0, len(data), 2**20)),
                name="t", store=lambda d, b: None)
        by_id = client.trace(traceId=tid)
        assert {s["name"] for s in by_id} \
            == {"owner.stream", "owner.dispatch", "owner.collect"}
        stream = next(s for s in by_id if s["name"] == "owner.stream")
        assert stream["p"] == parent and stream["node"] == 0
        assert stream["bytes"] == len(data)
        assert all(s["p"] == stream["s"] for s in by_id if s is not stream)
        lo, hi = stream["m0"], stream["m0"] + int(stream["d"] * 1e9)
        by_time = client.trace(sinceMonoNs=lo, untilMonoNs=hi)
        assert {s["s"] for s in by_id} <= {s["s"] for s in by_time}
        assert client.trace(sinceMonoNs=hi + 10**12,
                            untilMonoNs=hi + 2 * 10**12) == []
        assert client.trace(traceId=new_trace_id()) == []
        with pytest.raises(grpc.RpcError) as ei:
            client.trace(sinceMonoNs=lo)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        frag.close()


def _post_chunked(port: int, path: str, data: bytes, headers: dict) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", path, headers=headers, body=(
            data[i:i + 2**20] for i in range(0, len(data), 2**20)))
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status in (200, 201), (resp.status, body)
        return json.loads(body)
    finally:
        conn.close()


def _get(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def test_streamed_upload_stitches_the_owner_into_one_tree(tmp_path, owner):
    """A chunked-transfer upload through a delegating node: ``/trace``
    returns ONE tree in which the owner's spans hang under the node's
    ``upload.fragment``, every span carries ``m0``, and ``m0`` never
    runs backwards along a parent chain (one clock for both processes).
    The node's ``/metrics`` serves the totals and the body's two
    stopwatches."""
    srv, _, data = owner
    tid = new_trace_id()
    hdr = {"X-Dfs-Trace": f"{tid}-{new_span_id()}"}

    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        cfg = NodeConfig(node_id=1, cluster=cluster, data_root=tmp_path,
                         sidecar_port=srv.port, health_probe_s=0)
        node = StorageNodeServer(cfg)
        await node.start()
        try:
            port = cluster.peers[0].port
            up = await asyncio.to_thread(
                _post_chunked, port, "/upload?name=s.bin", data, hdr)
            assert up["size"] == len(data)
            return (await asyncio.to_thread(
                        _get, port, f"/trace?traceId={tid}"),
                    await asyncio.to_thread(_get, port, "/metrics"))
        finally:
            await node.stop()

    trace, metrics = asyncio.run(run())
    spans = trace["spans"]
    by_id = {s["s"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert {"http./upload", "upload.body", "upload.fragment",
            "owner.stream", "owner.dispatch", "owner.collect",
            "upload.place", "upload.commit", "commit.save",
            "commit.announce"} <= names
    assert all(isinstance(s.get("m0"), int) for s in spans)

    def one(name):
        return next(s for s in spans if s["name"] == name)

    assert by_id[one("owner.stream")["p"]]["name"] == "upload.fragment"
    assert by_id[one("owner.collect")["p"]]["name"] == "owner.stream"
    assert one("owner.stream")["node"] == 0
    for name in ("upload.body", "upload.fragment", "upload.place",
                 "upload.commit"):
        assert by_id[one(name)["p"]]["name"] == "http./upload"
    assert by_id[one("upload.replicate")["p"]]["name"] == "upload.place"
    # the commit's two sides, started together under the one span
    for name in ("commit.save", "commit.announce"):
        assert by_id[one(name)["p"]]["name"] == "upload.commit"
    # one tree: a single root line, nothing orphaned but the client's
    # own root span (which lives in no ring)
    roots = [s for s in spans if s["p"] not in by_id]
    assert [s["name"] for s in roots] == ["http./upload"]
    assert "owner.stream" in render_tree(spans)
    for s in spans:
        if s["p"] in by_id:
            assert by_id[s["p"]]["m0"] <= s["m0"]
    totals = metrics["obs"]["spans"]
    for name in ("http./upload", "upload.body", "upload.fragment",
                 "upload.place", "upload.commit", "commit.save",
                 "commit.announce", "cas.put_many"):
        assert totals[name]["count"] >= 1
        assert totals[name]["selfSeconds"] <= totals[name]["seconds"]
    stalls = metrics["ingest"]["stalls"]
    assert stalls["bodyWaitS"] >= 0.0 and stalls["feedWaitS"] >= 0.0
    assert stalls["bodyWaitS"] + stalls["feedWaitS"] \
        <= totals["upload.body"]["seconds"] + 1e-3
    assert metrics["obs"]["ringSpans"] >= len(
        [s for s in spans if s["node"] == 1])
