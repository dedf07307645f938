"""The Fragmenter plugin interface (north star, BASELINE.json).

The reference hard-codes one strategy — split into ``TOTAL_NODES = 5``
positional fragments (StorageNode.java:15,138-171). Here fragmentation is a
plugin: the node runtime calls ``chunk(data)`` and gets back content-addressed
chunk metadata; everything downstream (manifest, placement, replication,
download, dedup) is strategy-agnostic.

The kinds (:data:`FRAGMENTER_KINDS`, :func:`get_fragmenter`):
- ``fixed``            — reference-equivalent positional split (host).
- ``cdc``              — byte-granular Gear content-defined chunking on the
                         host (NumPy / C++), no JAX: the source's CPU CDC
                         plugin.
- ``cdc-anchored``     — the anchored two-level chunker on the host.
- ``cdc-anchored-tpu`` — the same chunker as ONE chunk+hash chain on the
                         device (ops/cdc_anchored.py): what the chip runs.
- ``auto``             — the anchored pair, by what the machine has.
"""

from __future__ import annotations

import abc

from dfs_tpu.config import FRAGMENTER_KINDS
from dfs_tpu.meta.manifest import ChunkRef, Manifest
from dfs_tpu.utils.hashing import sha256_hex


class Fragmenter(abc.ABC):
    """Splits a byte stream into content-addressed chunks."""

    name: str = "abstract"
    # the Observability of the process that serves this engine, set by a
    # chip owner (sidecar/service.py); an engine with a window walk
    # opens its per-window spans through it
    obs = None

    @abc.abstractmethod
    def chunk(self, data: bytes) -> list[ChunkRef]:
        """Return the chunk list covering ``data`` exactly, in order, with
        per-chunk sha256 digests."""

    def manifest(self, data: bytes, name: str,
                 file_id: str | None = None) -> Manifest:
        """Build the manifest for ``data``: file_id = sha256(bytes) exactly as
        the reference (StorageNode.java:127), chunks from this strategy."""
        return Manifest(
            file_id=file_id or sha256_hex(data),
            name=name,
            size=len(data),
            fragmenter=self.name,
            chunks=tuple(self.chunk(data)),
        )

    def manifest_stream(self, blocks, name: str, store=None) -> Manifest:
        """Chunk a block stream. CDC backends override with true
        bounded-memory streaming (fragmenter/stream.py); this fallback
        materializes (FixedFragmenter needs the total size upfront — its
        split rule depends on it, StorageNode.java:140)."""
        data = b"".join(blocks)
        m = self.manifest(data, name=name)
        if store is not None:
            for c in m.chunks:
                store(c.digest, data[c.offset:c.offset + c.length])
        return m

    def describe(self) -> dict:
        """JSON-able description sufficient for ANOTHER process to
        reproduce this fragmenter's chunk boundaries bit-exactly (the
        resumable-upload protocol: the client chunks locally with the
        node's advertised parameters, probes which digests the cluster
        already holds, and transfers only the missing payloads).
        Subclasses override; kinds map back via
        :func:`fragmenter_from_description`."""
        raise NotImplementedError(f"{self.name} is not resume-describable")

    def _manifest_via_chunks_stream(self, blocks, name: str,
                                    store) -> Manifest:
        """Shared manifest assembly for backends whose streaming surface
        is chunks_stream: drain it, size = last chunk end, file_id
        derived from the digests (callers that need fileId=sha256(body)
        — the node runtime — compute it themselves and override)."""
        from dfs_tpu.ops.cdc_v2 import file_id_from_digests

        chunks: list[ChunkRef] = []
        for batch in self.chunks_stream(blocks, store=store):
            chunks.extend(batch)
        size = chunks[-1].offset + chunks[-1].length if chunks else 0
        return Manifest(
            file_id=file_id_from_digests([c.digest for c in chunks]),
            name=name, size=size, fragmenter=self.name,
            chunks=tuple(chunks))

    # wall time of the first region a device engine ran, from the start
    # of its dispatch (staging, tracing and compiling included) to its
    # results on the host; None until then, and for a host engine
    first_region_s: float | None = None

    def device_stats(self) -> dict | None:
        """What this engine computes on, for the chip owner's Health
        answer: ``{platform, device_kind, count}`` as JAX reports it,
        ``regions`` / ``overflow_redos`` and the streams' phase clock
        (docs/sidecar_wire.md), or None for an engine that runs on the
        host (asking must not initialise a backend there)."""
        return None

    def tee_stats(self) -> dict:
        """What a delegating engine's tee did at this node, for
        ``/metrics`` ``ingest.seam`` (``SidecarFragmenter``:
        ``teePeakBytes``, ``teeWaitS``); an engine that chunks in the
        node's own process has no tee and adds nothing."""
        return {}

    def stream_span(self) -> int | None:
        """Upper bound on how far chunks_stream's reporting can lag the
        bytes it has consumed (the sidecar advertises this so a teeing
        client can cap its buffer without risking deadlock). None =
        unbounded (this base implementation materializes)."""
        return None

    def chunks_stream(self, blocks, store=None):
        """Generator of ChunkRef batches in stream order, yielded AS the
        stream is consumed — the incremental surface the sidecar's
        stream-stream method serves from. Backends with a true streaming
        walk (anchored CPU/TPU) override with bounded-memory
        implementations; this fallback materializes for the same reason
        manifest_stream's does."""
        data = b"".join(blocks)
        m = self.manifest(data, name="stream")
        if store is not None:
            for c in m.chunks:
                store(c.digest, data[c.offset:c.offset + c.length])
        if m.chunks:
            yield list(m.chunks)


def _aligned_from_cdc(cdc_params):
    """CDCParams byte sizes -> 64-byte block units (quantized); grow the
    strip to fit large --max-chunk values (strips must hold at least one
    max-size chunk, and stay 128-block-aligned for the device compaction
    tiling): CLI values that are legal for ``cdc`` must not crash the
    start-up of a node on an anchored kind."""
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

    max_blocks = max(1, cdc_params.max_size // 64)
    default_strip = AlignedCdcParams.__dataclass_fields__[
        "strip_blocks"].default
    strip_blocks = default_strip
    while strip_blocks < max_blocks:
        strip_blocks *= 2
    return AlignedCdcParams(
        min_blocks=max(1, cdc_params.min_size // 64),
        avg_blocks=max(1, cdc_params.avg_size // 64),
        max_blocks=max_blocks,
        strip_blocks=strip_blocks)


def fragmenter_from_description(desc: dict) -> Fragmenter:
    """Rebuild a chunk-compatible fragmenter from ``describe()`` output.
    Always returns the CPU engine of the described strategy — chunk
    boundaries and digests are bit-identical across CPU/TPU/sidecar by
    construction (tests enforce it), which is exactly what resume
    needs."""
    from dfs_tpu.config import CDCParams

    kind = desc.get("kind")
    if kind == "fixed":
        from dfs_tpu.fragmenter.fixed import FixedFragmenter

        return FixedFragmenter(parts=int(desc["parts"]))
    if kind == "cdc":
        from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter

        return CpuCdcFragmenter(CDCParams(
            min_size=int(desc["min_size"]), avg_size=int(desc["avg_size"]),
            max_size=int(desc["max_size"]), seed=int(desc["seed"])))
    if kind == "cdc-anchored":
        from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter
        from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
        from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

        if "strong_min" not in desc:
            raise ValueError(
                "cdc-anchored description without strong_min: written "
                "before the strong-anchor segment rule, whose cuts this "
                "build does not reproduce")
        c = desc["chunk"]
        return AnchoredCpuFragmenter(AnchoredCdcParams(
            chunk=AlignedCdcParams(
                min_blocks=int(c["min_blocks"]),
                avg_blocks=int(c["avg_blocks"]),
                max_blocks=int(c["max_blocks"]),
                strip_blocks=int(c["strip_blocks"]),
                seed=int(c["seed"])),
            seg_min=int(desc["seg_min"]), seg_max=int(desc["seg_max"]),
            seg_mask=int(desc["seg_mask"]), seed=int(desc["seed"]),
            strong_min=int(desc["strong_min"]),
            strong_bits=int(desc["strong_bits"])))
    raise ValueError(f"undescribable fragmenter kind {kind!r}")


def _anchored_params(cdc_params):
    from dfs_tpu.ops.cdc_anchored import TILE_BYTES, AnchoredCdcParams

    if isinstance(cdc_params, AnchoredCdcParams):
        return cdc_params
    if cdc_params is not None:
        # operator chunk sizing (NodeConfig.cdc is always a CDCParams)
        # must reach the nested aligned grid — the segment level scales
        # with it: seg_max is pinned to one lane (strip bytes) and
        # seg_min keeps the default 3:4 ratio, tile-aligned.
        chunk = _aligned_from_cdc(cdc_params)
        seg_max = chunk.strip_blocks * 64
        seg_min = max(TILE_BYTES,
                      (3 * seg_max // 4) // TILE_BYTES * TILE_BYTES)
        return AnchoredCdcParams(chunk=chunk, seg_min=seg_min,
                                 seg_max=seg_max)
    return AnchoredCdcParams()


def get_fragmenter(kind: str, *, cdc_params=None, fixed_parts: int = 5,
                   frag=None) -> Fragmenter:
    """Factory keyed by NodeConfig.fragmenter. ``"auto"`` (the serve
    default) resolves ONCE, here, to the anchored pair: the TPU device
    engine iff this machine has a TPU platform (utils.device.wants_tpu),
    its CPU engine otherwise — and says which in the log. A TPU that is
    present but cannot be taken is an error (DeviceError), never a quiet
    CPU engine; so is ``cdc-anchored-tpu`` coming up on another platform,
    unless ``JAX_PLATFORMS=cpu`` asked for the CPU by name.

    ``frag`` (a FragmenterConfig) carries execution knobs: with
    ``frag.devices > 1`` the ``"cdc-anchored"`` region walk shards over
    that many JAX devices with double-buffered staging
    (fragmenter/cdc_anchored_sharded.py) — byte-identical chunk
    boundaries, multi-chip throughput. No other kind shards."""
    import logging

    log = logging.getLogger("dfs_tpu.fragmenter")
    if kind not in FRAGMENTER_KINDS:
        raise ValueError(f"unknown fragmenter {kind!r}: the kinds are "
                         + ", ".join(FRAGMENTER_KINDS))
    if frag is not None and frag.devices > 1 and kind != "cdc-anchored":
        # silence would be indistinguishable from sharding working
        # (/metrics frag reports the configured device count either way)
        log.warning(
            "--cdc-devices is ignored by fragmenter=%r; use "
            "fragmenter='cdc-anchored' for multi-device ingest", kind)
        frag = None
    if kind == "auto":
        from dfs_tpu.utils.device import wants_tpu

        kind = "cdc-anchored-tpu" if wants_tpu() else "cdc-anchored"
        log.info("fragmenter 'auto' -> %s", kind)
    sharded = frag is not None and frag.devices > 1
    if kind == "cdc-anchored-tpu" or sharded:
        from dfs_tpu.utils.device import (DeviceError, cpu_on_purpose,
                                          require_tpu)

        if not cpu_on_purpose():
            what = f"fragmenter {kind!r}" + (
                f" over {frag.devices} devices" if sharded else "")
            info = require_tpu(what)
            if sharded and info["count"] < frag.devices:
                raise DeviceError(
                    f"{what}: only {info['count']} TPU device(s) visible")
    if kind == "fixed":
        from dfs_tpu.fragmenter.fixed import FixedFragmenter

        return FixedFragmenter(parts=fixed_parts)
    if kind == "cdc":
        from dfs_tpu.config import CDCParams
        from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter

        return CpuCdcFragmenter(cdc_params or CDCParams())
    params = _anchored_params(cdc_params)
    if sharded:
        from dfs_tpu.fragmenter.cdc_anchored_sharded import \
            ShardedAnchoredCdcFragmenter

        return ShardedAnchoredCdcFragmenter(params, frag)
    from dfs_tpu.fragmenter.cdc_anchored import (AnchoredCpuFragmenter,
                                                 AnchoredTpuFragmenter)

    cls = AnchoredCpuFragmenter if kind == "cdc-anchored" \
        else AnchoredTpuFragmenter
    return cls(params)
