"""PR 36: the erasure-coded archive (``archive-5n-ec3``) on the program's
side — the batched P+Q encode (``ops.ec.encode_pq_batch``, both twins),
``Ingest.ec_extend`` packing an object's stripes into a few fixed
widths, which twin a process runs (``utils.device.holds_tpu``, never an
engine's name), and the spans and counters the cell's readers take.

The oracle is the benchmark's plain reference, ``benchmarks/
reference_ec.py`` (table-based, imports nothing of the program). Small
sizes, CPU: what the bytes ARE and what the program COUNTS, never a
speed.
"""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import reference_ec  # noqa: E402

from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter  # noqa: E402
from dfs_tpu.meta.manifest import (StripeRef, ec_stripe_groups,  # noqa: E402
                                   stripe_shard_len)
from dfs_tpu.ops import ec as ec_ops  # noqa: E402
from dfs_tpu.utils import device  # noqa: E402
from dfs_tpu.utils.hashing import sha256_hex  # noqa: E402
from tests.test_node_cluster import (make_cluster_cfg, start_nodes,  # noqa: E402
                                     stop_nodes)

EDGES = [2048 << i for i in range(6)]          # 2 KiB .. the default max_chunk
MIB = 1 << 20


# -- the batched encode against the plain reference -------------------------

def test_bucket_widths_are_powers_of_two_from_2k():
    assert [ec_ops.batch_width(n) for n in (0, 4, 2044, 2048)] == [2048] * 4
    for edge in EDGES[1:]:
        assert ec_ops.batch_width(edge - 4) == edge
        assert ec_ops.batch_width(edge) == edge
        assert ec_ops.batch_width(edge // 2 + 4) == edge
    assert ec_ops.batch_width(65540) == 131072      # past max_chunk: no cap
    # a call's shards stay within 64 MiB, its rows a power of two
    for k in (1, 3, 10):
        for w in (*EDGES, 1 << 27):
            rows = ec_ops.batch_rows(k, w)
            assert rows & (rows - 1) == 0 and rows >= 1
            assert rows == 1 or rows * k * w <= 64 * MIB


@pytest.mark.parametrize("twin", ["numpy", "jit"])
@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_batch_encode_is_the_reference_at_every_bucket_edge(k, twin):
    """Seeded shards of ragged lengths, packed as ``ec_extend`` packs
    them: a stripe's padded length at and beside every bucket edge, the
    rest of its row zero; the row's first ``shard_len`` bytes are the
    reference's P and Q of the stripe padded to ``shard_len`` alone."""
    rng = np.random.default_rng([2147483659, k])
    for edge in EDGES:
        lens = sorted({max(4, edge // 2 + 4), edge - 4, edge,
                       int(rng.integers(edge // 2 + 8, edge - 8)) // 4 * 4})
        assert all(ec_ops.batch_width(n) == edge for n in lens)
        sh = np.zeros((len(lens), k, edge), dtype=np.uint8)
        want = []
        for row, pad in zip(sh, lens):
            ragged = [int(rng.integers(1, pad + 1)) for _ in range(k - 1)]
            data = [reference_ec.padded(rng.bytes(n), pad)
                    for n in (*ragged, pad)]
            for shard, d in zip(row, data):
                shard[:pad] = d
            want.append(reference_ec.encode(data))
        p, q = ec_ops.encode_pq_batch(sh, device=twin == "jit")
        for prow, qrow, pad, (rp, rq) in zip(p, q, lens, want):
            assert prow[:pad].tobytes() == rp.tobytes()
            assert qrow[:pad].tobytes() == rq.tobytes()
            assert not prow[pad:].any() and not qrow[pad:].any()


@pytest.mark.parametrize("twin", ["numpy", "jit"])
def test_a_short_stripe_fills_the_last_slots(twin):
    """n < k shards: its weights follow its own n (g^(n-1-i)), which
    Horner gives with the shards in the LAST n of the k slots."""
    rng = np.random.default_rng(2147483693)
    k, pad = 3, 2048
    for n in (1, 2):
        data = [reference_ec.padded(rng.bytes(pad - 4 * i), pad)
                for i in range(n)]
        sh = np.zeros((1, k, pad), dtype=np.uint8)
        sh[0, k - n:] = data
        p, q = ec_ops.encode_pq_batch(sh, device=twin == "jit")
        rp, rq = reference_ec.encode(data)
        assert p[0].tobytes() == rp.tobytes()
        assert q[0].tobytes() == rq.tobytes()
    with pytest.raises(ValueError):
        ec_ops.encode_pq_batch(np.zeros((1, 3, 6), dtype=np.uint8))


# -- Ingest.ec_extend: the manifest, byte for byte ---------------------------

def _object(seed: int, size: int = MIB):
    """A seeded object as the CPU engine cuts it (the deployed 2 / 8 / 64
    KiB), and its digest -> payload map as ``Ingest.upload`` hands it."""
    data = np.random.default_rng([2147483659, seed]).bytes(size)
    manifest = AnchoredCpuFragmenter().manifest(
        data, name=f"obj-{seed}", file_id=sha256_hex(data))
    view = memoryview(data)
    return data, manifest, {c.digest: view[c.offset:c.offset + c.length]
                            for c in manifest.chunks}


def _per_stripe(manifest, chunks, k):
    """The parent's ``ec_extend``: a stripe at a time at its own padded
    length through ``encode_pq_np``."""
    stripes, parity = [], []
    for grp in ec_stripe_groups(manifest.chunks, k):
        pad = stripe_shard_len(grp)
        sh = np.zeros((len(grp), pad), dtype=np.uint8)
        for j, c in enumerate(grp):
            sh[j, :c.length] = np.frombuffer(chunks[c.digest],
                                             dtype=np.uint8)
        p, q = ec_ops.encode_pq_np(sh)
        stripes.append(StripeRef(p=sha256_hex(p.tobytes()),
                                 q=sha256_hex(q.tobytes()), shard_len=pad))
        parity += [(stripes[-1].p, p.tobytes()), (stripes[-1].q, q.tobytes())]
    return stripes, parity


async def _one_node(tmp_path):
    return await start_nodes(make_cluster_cfg(1, rf=1), tmp_path)


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_ec_extend_is_the_reference_and_the_parents_manifest(tmp_path, k):
    data, manifest, chunks = _object(k)

    async def run():
        nodes = await _one_node(tmp_path)
        try:
            return nodes[1].ingest.ec_extend(manifest, chunks, k)
        finally:
            await stop_nodes(nodes)

    got, parity = asyncio.run(run())
    rows = [{"index": c.index, "offset": c.offset, "length": c.length}
            for c in manifest.chunks]
    groups = reference_ec.stripe_groups(rows, k)
    assert got.ec.k == k and len(got.ec.stripes) == len(groups)
    assert len(groups[-1]) == (len(rows) % k or k)
    for s, (stripe, group) in enumerate(zip(got.ec.stripes, groups)):
        pad = reference_ec.shard_len(group)
        rp, rq = reference_ec.encode([reference_ec.padded(
            data[c["offset"]:c["offset"] + c["length"]], pad)
            for c in group])
        assert stripe.shard_len == pad
        assert parity[2 * s] == (sha256_hex(rp.tobytes()), rp.tobytes())
        assert parity[2 * s + 1] == (sha256_hex(rq.tobytes()), rq.tobytes())
        assert (stripe.p, stripe.q) == (parity[2 * s][0],
                                        parity[2 * s + 1][0])
    stripes, parent_parity = _per_stripe(manifest, chunks, k)
    assert list(got.ec.stripes) == stripes and parity == parent_parity
    assert got.chunks == manifest.chunks and got.file_id == manifest.file_id


# -- which twin runs: this process's device, never the engine's name ---------

class _Compiles:
    """Backend compilations JAX reports while the block runs."""

    def __enter__(self):
        import jax.monitoring

        self.n = 0
        self._cb = lambda name, *a, **kw: self._note(name)
        jax.monitoring.register_event_duration_secs_listener(self._cb)
        return self

    def _note(self, name: str) -> None:
        self.n += "backend_compile" in name

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._cb)


def test_compile_listener_sees_a_compilation():
    import jax
    import jax.numpy as jnp

    with _Compiles() as seen:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert seen.n >= 1


def test_a_node_behind_an_owner_encodes_without_compiling(tmp_path,
                                                          monkeypatch):
    """``sidecar:cdc-anchored-tpu`` names the OWNER's engine; the node
    was started ``JAX_PLATFORMS=cpu`` and holds no chip, so its parity is
    NumPy's: not one jit compilation, whatever the shapes."""
    import os

    assert os.environ["JAX_PLATFORMS"] == "cpu" and not device.holds_tpu()
    _, manifest, chunks = _object(11)

    async def run():
        nodes = await _one_node(tmp_path)
        try:
            ingest = nodes[1].ingest
            monkeypatch.setattr(ingest.fragmenter, "name",
                                "sidecar:cdc-anchored-tpu", raising=False)
            with _Compiles() as seen:
                got, _ = ingest.ec_extend(manifest, chunks, 3)
            return seen.n, got
        finally:
            await stop_nodes(nodes)

    compiled, got = asyncio.run(run())
    assert compiled == 0
    assert list(got.ec.stripes) == _per_stripe(manifest, chunks, 3)[0]
    src = (REPO / "dfs_tpu" / "node" / "ingest.py").read_text()
    assert "fragmenter.name" not in src.split("def ec_extend")[1].split(
        "async def upload_stream")[0]


def test_holds_tpu_is_what_require_tpu_took(monkeypatch):
    monkeypatch.setattr(device, "_took_tpu", False)
    assert not device.holds_tpu()
    monkeypatch.setattr(device, "device_info", lambda: {
        "platform": "cpu", "device_kind": "cpu", "count": 1})
    with pytest.raises(device.DeviceError):
        device.require_tpu("a test")
    assert not device.holds_tpu()           # asked for and not got
    monkeypatch.setattr(device, "device_info", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1})
    device.require_tpu("a test")
    assert device.holds_tpu()


def test_the_jitted_twin_compiles_a_bounded_set_of_shapes(tmp_path,
                                                          monkeypatch):
    """A chip holder's ``ec_extend`` over 8 different objects: the same
    manifests as NumPy's, and no more compiled shapes than widths times
    row counts allow — where a shape a distinct padded length was
    hundreds."""
    monkeypatch.setattr(device, "holds_tpu", lambda: True)
    ec_ops._make_batch_encode_fn.cache_clear()
    objects = [_object(20 + i, MIB // 2 + i * 77_777) for i in range(8)]

    async def run():
        nodes = await _one_node(tmp_path)
        try:
            return [nodes[1].ingest.ec_extend(m, chunks, 3)
                    for _, m, chunks in objects]
        finally:
            await stop_nodes(nodes)

    out = asyncio.run(run())
    lengths = set()
    for (got, parity), (_, manifest, chunks) in zip(out, objects):
        stripes, parent_parity = _per_stripe(manifest, chunks, 3)
        assert list(got.ec.stripes) == stripes and parity == parent_parity
        lengths |= {s.shard_len for s in stripes}
    shapes = ec_ops._make_batch_encode_fn(3)._cache_size()
    most_rows = max(len(got.ec.stripes) for got, _ in out)
    bound = len(EDGES) * (most_rows - 1).bit_length() + len(EDGES)
    assert 1 <= shapes <= bound < len(lengths)
    assert shapes <= sum(ec_ops.batch_rows(3, w).bit_length() for w in EDGES)


# -- spans and counters, through a real upload -------------------------------

def test_spans_nest_and_counters_add_up(tmp_path):
    """Three whole-body EC uploads through a 5-node cluster: ``ec.pack``,
    ``ec.math`` and ``ec.hash`` are children of ``upload.ec_encode`` in
    the upload's trace, and /metrics ``ec`` adds up to the manifests."""
    bodies = [np.random.default_rng([2147483659, 40 + i]).bytes(60_000 + i)
              for i in range(3)]

    async def run():
        nodes = await start_nodes(make_cluster_cfg(5, rf=1), tmp_path)
        try:
            node = nodes[2]
            done = []
            for i, body in enumerate(bodies):
                with node.obs.request_span("http./upload"):
                    trace = node.obs.wire_trace()["t"]
                    done.append((trace, *await node.upload(
                        body, f"arch-{i}", ec_k=3)))
            return (done, [node.obs.spans_for(t) for t, _, _ in done],
                    node.ec_stats(), node.obs.span_totals(),
                    nodes[1].ec_stats())
        finally:
            await stop_nodes(nodes)

    done, traces, ec, totals, idle = asyncio.run(run())
    for spans in traces:
        by_id = {s["s"]: s for s in spans}
        encode = [s for s in spans if s["name"] == "upload.ec_encode"]
        assert len(encode) == 1
        kids = [s for s in spans if s["name"].startswith("ec.")]
        assert {s["name"] for s in kids} == {"ec.pack", "ec.math", "ec.hash"}
        assert all(by_id[s["p"]] is encode[0] for s in kids)
        assert sum(s["d"] for s in kids) <= encode[0]["d"] + 1e-3
    manifests = [m for _, m, _ in done]
    assert ec["objects"] == 3
    assert ec["stripes"] == sum(len(m.ec.stripes) for m in manifests)
    assert ec["parityBytes"] == sum(s["ecParityBytes"] for _, _, s in done)
    assert ec["parityBytes"] == sum(2 * st.shard_len for m in manifests
                                    for st in m.ec.stripes)
    assert ec["encodeCalls"] == totals["ec.math"]["count"]
    assert 3 <= ec["encodeCalls"] <= 3 * len(EDGES)
    assert idle == {"objects": 0, "stripes": 0, "encodeCalls": 0,
                    "parityBytes": 0}


def test_metrics_serves_the_ec_section(tmp_path):
    """GET /metrics carries ``ec`` beside ``ingest``: what the four
    ``ec.*`` readers of the benchmark take deltas of."""
    from dfs_tpu.cli.client import NodeClient

    body = np.random.default_rng(2147483701).bytes(50_000)

    async def run():
        cluster = make_cluster_cfg(5, rf=1)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(body, "m", ec_k=3)
            client = NodeClient("127.0.0.1", cluster.peer(1).port)
            page = await asyncio.to_thread(client.metrics)
            return manifest, page
        finally:
            await stop_nodes(nodes)

    manifest, page = asyncio.run(run())
    assert page["ec"] == {"objects": 1, "stripes": len(manifest.ec.stripes),
                          "encodeCalls": page["ec"]["encodeCalls"],
                          "parityBytes": sum(2 * s.shard_len
                                             for s in manifest.ec.stripes)}
    assert 1 <= page["ec"]["encodeCalls"] <= len(EDGES)
    assert "ingest" in page


# -- the benchmark's side of the contract -----------------------------------

def test_the_cell_is_data_and_the_fixture_to_the_letter():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(c for c in bench["workloads"]
                if c["name"] == "archive.ingest-ec")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("archive-5n-ec3", "ingest-ec", 1)
    config = json.loads((REPO / "benchmarks" / "configs"
                         / "archive-5n-ec3.json").read_text())
    fixture = json.loads((REPO / "benchmarks" / "tests" / "fixtures"
                          / "archive-5n-ec3.json").read_text())
    for key in ("deployment", "guarantees", "controls", "reduced"):
        assert config[key] == fixture[key]
    assert "fixture" not in config
    entry = next(c for c in bench["configs"] if c["name"] == "archive-5n-ec3")
    assert entry["source"] == fixture["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == ["object_bytes",
                                                     "corpus_bytes"]
    traffic = json.loads((REPO / "benchmarks" / "traffic"
                          / "ingest-ec.json").read_text())
    assert (traffic["block_bytes"], traffic["clients"],
            traffic["object_bytes"], traffic["period_bytes"]) \
        == (0, 3, 16 * MIB, 32 * MIB)
    assert (traffic["lead_objects"], traffic["ratio_objects"]) == (3, 12)
    new = [m for m in bench["per_layer"] if m["name"].startswith("ec.")]
    assert sorted(m["name"] for m in new) == [
        "ec.calls_per_object", "ec.encode_s_per_gib", "ec.math_s_per_gib",
        "ec.parity_pct"]
    assert all(m["workloads"] == ["archive.ingest-ec"]
               and m["moves"] == "ingest_mibps" for m in new)
