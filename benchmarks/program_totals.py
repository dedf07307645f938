"""What the program itself summed over the window, for the per-layer
readers: span totals (``/metrics`` ``obs.spans``: per span name the
count, the seconds and the self seconds), the ingest stopwatches
(``ingest.stalls``) and the owner's phase clock (``Health.device``).

Every function returns None where the program serves no such table or
key — a program older than the span totals — so a reader built on it
leaves its metric out of the line and never raises. A span name that
has not closed yet on a node that does serve the table counts as 0.
"""

from __future__ import annotations


def span_s(w, name: str, field: str = "seconds") -> float | None:
    """Growth over the window of ``obs.spans[name][field]``, summed
    over the nodes (span-seconds: spans of one name may overlap)."""
    if not any(isinstance(n.get("obs", {}).get("spans"), dict)
               for n in w.nodes_after):
        return None
    return w.node_delta("obs", "spans", name, field)


def stall_s(w, key: str) -> float | None:
    """Growth of the stopwatch ``ingest.stalls[key]``, summed over the
    nodes."""
    if not any(key in n.get("ingest", {}).get("stalls", {})
               for n in w.nodes_after):
        return None
    return w.node_delta("ingest", "stalls", key)


def owner_s(w, *keys: str) -> float | None:
    """Growth of the sum of these ``Health.device`` counters."""
    after = w.owner_after.get("device") or {}
    before = w.owner_before.get("device") or {}
    if not all(k in after for k in keys):
        return None
    return sum(float(after[k]) - float(before.get(k, 0.0)) for k in keys)


def per_gib(w, amount: float | None) -> float | None:
    return None if amount is None else w.per_gib_put(amount)


def share_pct(part: float | None, whole: float | None) -> float | None:
    if part is None or not whole:
        return None
    return 100.0 * part / whole
