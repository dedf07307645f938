"""Sharded streaming CDC as an INGEST option: the
``FragmenterConfig.devices`` knob routes the ANCHORED chunker's region
walk through the window-batched anchor/region passes with
double-buffered staging (round 15), and the resulting chunk boundaries
and digests must be BYTE-IDENTICAL to the single-device path — on
smooth streams, ragged tails, carries crossing region and device
borders, empty and one-chunk streams, and through a real node's
streaming upload."""

import asyncio

import numpy as np
import pytest

from dfs_tpu.config import FragmenterConfig
from dfs_tpu.fragmenter.base import get_fragmenter
from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter
from dfs_tpu.fragmenter.cdc_anchored_sharded import \
    ShardedAnchoredCdcFragmenter
from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
from dfs_tpu.ops.cdc_v2 import AlignedCdcParams
from tests.test_cdc_anchored import CASES

# anchored geometry: the anchored_sharded_parity_check shapes — 4 KiB
# lanes, 2-4 KiB segments; region = 4 device spans of one seg_max each
APARAMS = AnchoredCdcParams(
    chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                           strip_blocks=64),
    seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
AREGION = 4 * 4096


def _blocks(data: bytes, n: int):
    for off in range(0, len(data), n):
        yield data[off:off + n]


# ------------------------------------------------------------------ #
# the sharded walk (round 15): the anchored chunker's streaming
# region walk over the mesh — sharded pass A, host select with the
# threaded carry, sharded region step (repack/scan/digest per lane
# shard), double-buffered staging
# ------------------------------------------------------------------ #

def _afrag(devices: int = 4, region: int = AREGION,
           **kw) -> ShardedAnchoredCdcFragmenter:
    return ShardedAnchoredCdcFragmenter(
        APARAMS, FragmenterConfig(devices=devices, region_bytes=region),
        **kw)


@pytest.mark.parametrize("size", [0, 1, 100, 5000, AREGION, AREGION + 1,
                                  3 * AREGION - 7, 4 * AREGION,
                                  6 * AREGION + 12345])
def test_anchored_sharded_byte_identical(size):
    """manifest_stream through the sharded anchored walk == the host
    engine: same spans, same digests (device SHA vs host SHA-NI), same
    file id — for empty, one-chunk, sub-region, exact-region,
    multi-region and ragged-tail stream lengths."""
    rng = np.random.default_rng(4321)
    data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    cpu = AnchoredCpuFragmenter(APARAMS, region_bytes=AREGION) \
        .manifest_stream(_blocks(data, 1 << 13), name="x")
    shd = _afrag().manifest_stream(_blocks(data, 1 << 13), name="x")
    assert [(c.offset, c.length, c.digest) for c in shd.chunks] \
        == [(c.offset, c.length, c.digest) for c in cpu.chunks]
    assert shd.file_id == cpu.file_id and shd.size == cpu.size


@pytest.mark.parametrize("case", sorted(CASES))
def test_anchored_sharded_cuts_alike_on_the_rule_s_cases(case):
    """The window-batched walk (device pass A's three planes pulled to
    the host, the carry threaded through ``select_segments``) against
    the host engine on the streams that drive each arm of the rule:
    random, anchor-free (forced cuts), anchor-dense, strong anchors
    every eight bytes (every cut at strong_min: the lane tables' full
    bound) and anchors at the windows' edges."""
    data = CASES[case]().tobytes()
    cpu = AnchoredCpuFragmenter(APARAMS).chunk(data)
    shd = _afrag().manifest_stream(_blocks(data, 1 << 13), name="x")
    assert [(c.offset, c.length, c.digest) for c in shd.chunks] \
        == [(c.offset, c.length, c.digest) for c in cpu]


def test_anchored_sharded_carry_crosses_device_boundary():
    """The inter-region carry is NONZERO while consecutive windows of
    one batch live on DIFFERENT devices (windows ride the dp axis, one
    per device) — so the carried tail segment's bytes were staged to
    one device and its selection threads into the next device's window.
    The oracle (region_spans_np) derives the carry independently; the
    walk must reproduce the host engine exactly through that handoff."""
    from dfs_tpu.ops.cdc_anchored import region_spans_np

    rng = np.random.default_rng(4321)
    data = rng.integers(0, 256, size=3 * AREGION, dtype=np.uint8)
    _, consumed0 = region_spans_np(
        data[:AREGION], np.zeros((8,), np.uint8), 0, False, APARAMS)
    frag = _afrag()
    carry = consumed0 - frag.stride
    assert carry > 0, "chosen stream must leave a nonzero carry"
    # >1 device and >1 full window in the stream: windows 0 and 1 sit
    # on different mesh devices, and the carry crosses between them
    assert frag.devices > 1
    assert 3 * AREGION - frag.stride >= AREGION
    cpu = AnchoredCpuFragmenter(APARAMS, region_bytes=AREGION) \
        .manifest_stream(_blocks(data.tobytes(), 1 << 13), name="x")
    shd = frag.manifest_stream(_blocks(data.tobytes(), 1 << 13), name="x")
    assert [(c.offset, c.length, c.digest) for c in shd.chunks] \
        == [(c.offset, c.length, c.digest) for c in cpu.chunks]


def test_anchored_sharded_region_too_small_rejected():
    """A region that cannot hold two segments is a config error — the
    same two-segment floor the single-device walk enforces."""
    with pytest.raises(ValueError, match="two segments"):
        ShardedAnchoredCdcFragmenter(
            APARAMS, FragmenterConfig(devices=4, region_bytes=4096))


def test_anchored_sharded_stores_identical_payloads():
    rng = np.random.default_rng(4321)
    data = rng.integers(0, 256, size=2 * AREGION + 333,
                        dtype=np.uint8).tobytes()
    got: dict[str, bytes] = {}
    m = _afrag().manifest_stream(_blocks(data, 8192), name="x",
                                 store=lambda d, b: got.setdefault(d, b))
    assert b"".join(got[c.digest] for c in m.chunks) == data


def test_anchored_factory_returns_sharded_only_when_asked():
    frag = get_fragmenter("cdc-anchored", cdc_params=APARAMS,
                          frag=FragmenterConfig(devices=4,
                                                region_bytes=AREGION))
    assert isinstance(frag, ShardedAnchoredCdcFragmenter)
    # describe() (the resume protocol) is the host engine's — same
    # strategy, same boundaries, no new kind
    assert frag.describe()["kind"] == "cdc-anchored"
    single = get_fragmenter("cdc-anchored", cdc_params=APARAMS,
                            frag=FragmenterConfig())
    assert isinstance(single, AnchoredCpuFragmenter)
    assert not isinstance(single, ShardedAnchoredCdcFragmenter)


def test_anchored_sharded_degraded_environment_falls_back():
    """More devices configured than visible: ingest must still work,
    through the host region oracle, with identical output."""
    rng = np.random.default_rng(4321)
    frag = ShardedAnchoredCdcFragmenter(
        APARAMS, FragmenterConfig(devices=64, region_bytes=64 * 512))
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()
    cpu = AnchoredCpuFragmenter(APARAMS).manifest_stream(
        _blocks(data, 8192), name="x")
    shd = frag.manifest_stream(_blocks(data, 8192), name="x")
    assert frag._unavailable
    assert [(c.offset, c.length, c.digest) for c in shd.chunks] \
        == [(c.offset, c.length, c.digest) for c in cpu.chunks]


def test_anchored_sharded_first_staging_sample_not_outlier():
    """r06 regression, sharded edition: the probe/step jits are warmed
    at step-build time, so the FIRST staging-bandwidth sample must not
    eat a trace/compile and read as an outlier vs the run's median.
    ``overlap_min_bw=inf`` keeps staging serial so EVERY window is
    timed (benches read the public surface; the raw samples are
    test-only)."""
    rng = np.random.default_rng(4321)
    frag = _afrag(overlap_min_bw=float("inf"))
    n_windows = 10
    total = AREGION + (n_windows - 1) * frag.stride
    data = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()
    assert frag.staging_timed_windows() == 0
    for _ in frag.chunks_stream(_blocks(data, 1 << 14)):
        pass
    assert frag.staging_timed_windows() >= n_windows - 1
    samples = list(frag._staging_samples)
    bws = [b / t for b, t in samples]
    med = sorted(bws)[len(bws) // 2]
    assert bws[0] >= med / 8, \
        f"first staging sample {bws[0]:.0f} B/s is an outlier vs " \
        f"median {med:.0f} B/s — a jit compile leaked into it"
    assert frag.reset_staging_samples() == len(samples)
    assert frag.staging_timed_windows() == 0


def test_node_streaming_upload_via_sharded_anchored(tmp_path):
    """End to end: a single-node cluster configured with
    fragmenter='cdc-anchored' + frag.devices selects the sharded walk
    (the config->factory path), and upload_stream through it serves
    back byte-identical data. The node's fragmenter is then swapped to
    the TEST geometry for the actual transfer — NodeConfig.cdc pins
    anchored strips to the production default, whose compile is the
    bench's job (CDC_SHARD_r15.json runs the real config geometry)."""
    from dfs_tpu.config import ClusterConfig, NodeConfig, PeerAddr
    from dfs_tpu.node.runtime import StorageNodeServer

    rng = np.random.default_rng(4321)
    data = rng.integers(0, 256, size=3 * AREGION + 123,
                        dtype=np.uint8).tobytes()

    async def run():
        import socket

        socks = [socket.socket() for _ in range(2)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        cluster = ClusterConfig(
            peers=(PeerAddr(node_id=1, host="127.0.0.1", port=ports[0],
                            internal_port=ports[1]),),
            replication_factor=1)
        cfg = NodeConfig(
            node_id=1, cluster=cluster, data_root=tmp_path,
            fragmenter="cdc-anchored",
            frag=FragmenterConfig(devices=4),
            health_probe_s=0)
        node = StorageNodeServer(cfg)
        assert isinstance(node.fragmenter, ShardedAnchoredCdcFragmenter)
        node.fragmenter = ShardedAnchoredCdcFragmenter(
            APARAMS, FragmenterConfig(devices=4, region_bytes=AREGION))
        await node.start()
        try:
            async def blocks():
                for off in range(0, len(data), 8192):
                    yield data[off:off + 8192]

            manifest, _ = await node.upload_stream(blocks(), "s.bin")
            oracle = AnchoredCpuFragmenter(
                APARAMS, region_bytes=AREGION).manifest_stream(
                _blocks(data, 8192), name="s.bin")
            assert [(c.offset, c.length, c.digest)
                    for c in manifest.chunks] \
                == [(c.offset, c.length, c.digest)
                    for c in oracle.chunks]
            assert not node.fragmenter._unavailable
            _, got = await node.download(manifest.file_id)
            assert bytes(got) == data
        finally:
            await node.stop()

    asyncio.run(run())


# ------------------------------------------------------------------ #
# tier-1 smoke: bench_cdc_sharded --tiny runs the sharded anchored walk
# at 1-2 devices + the full-node path and emits the CDC_SHARD_r15.json
# schema, locked against the committed artifact
# ------------------------------------------------------------------ #

def test_bench_cdc_sharded_tiny(tmp_path):
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    out_path = tmp_path / "CDC_SHARD_tiny.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(repo)}
    proc = subprocess.run(
        [sys.executable, str(repo / "bench_cdc_sharded.py"),
         "--tiny", "--out", str(out_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    art = json.loads(out_path.read_text())
    committed = json.loads((repo / "CDC_SHARD_r15.json").read_text())
    # schema lock: the tiny artifact carries every top-level and
    # per-phase key the committed full-mode artifact commits to
    assert set(committed) <= set(art)
    assert set(committed["stream"]) <= set(art["stream"])
    assert set(committed["node"]) <= set(art["node"])
    assert art["metric"] == committed["metric"] == \
        "anchored_sharded_ingest"
    assert art["mode"] == "tiny" and art["ok"] is True
    s = art["stream"]
    assert len(s["devices"]) == len(s["gibps"]) == len(s["staging_gibps"])
    assert s["identical"] is True and s["reconstruction_ok"] is True
    assert art["node"]["byte_identical"] is True
    # perf is NOT gated in tiny mode (CI hosts stall unpredictably; the
    # committed artifact carries the >=1.7x scaling claim) — but the
    # committed FULL artifact must itself hold the gate
    assert committed["mode"] == "full" and committed["ok"] is True
    assert committed["stream"]["scale_max_devices"] >= 1.7
