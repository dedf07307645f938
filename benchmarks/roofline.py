"""What the chip must at least move to chunk and hash one region, and how
long that takes at the table's peak.

The chain (``ops/cdc_anchored.py`` ``make_chain_fn``) reads a region's
bytes, finds content-defined cuts and hashes every chunk with SHA-256.
Whatever the kernels do in between, every byte of the region has to come
out of HBM once, and the chunk table (offset, length, 32-byte digest for
each chunk) has to go back. That is the floor on bytes. There is no
floor on operations here: SHA-256's rounds are 32-bit integer work on the
vector unit, for which no peak is published — so the share this gives is
of a bound by bytes alone: a floor on the chain's time, not a target.
"""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for this ``device_kind``; a device that
    is not in the table is an error, never a default."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}")
    return table[device_kind]


def region_min_hbm_bytes(region_bytes: int, avg_chunk: int) -> float:
    """Least HBM traffic for one region: its bytes read once, plus one
    table row (two 4-byte words and a 32-byte digest) written per chunk
    at the configured average chunk size."""
    return region_bytes + (region_bytes / avg_chunk) * (4 + 4 + 32)


def hbm_roofline_pct(region_bytes: int, avg_chunk: int,
                     busy_s_per_region: float, device_kind: str) -> float:
    floor_s = region_min_hbm_bytes(region_bytes, avg_chunk) \
        / peaks_for(device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / busy_s_per_region
