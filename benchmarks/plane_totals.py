"""What the dedup/index plane and the probes of placement left in the
nodes' ``/metrics`` over the window, for the per-layer readers that PR 27
brought — beside ``program_totals.py``, with the same rule: None where
the program serves no such span or counter (a program older than it, or
a node started without ``--index``), so the reader leaves its metric out
of the line and never raises.
"""

from __future__ import annotations

from program_totals import span_s


def closed_span_s(w, name: str) -> float | None:
    """``program_totals.span_s``, but None as well where no node has
    ever closed a span of that name: a program that has no such span is
    then told from one whose span took no time."""
    if not any(name in (n.get("obs", {}).get("spans") or {})
               for n in w.nodes_after):
        return None
    return span_s(w, name)


def index_delta(w, *path: str) -> float | None:
    """Growth over the window of ``index.<path>``, summed over the
    nodes."""
    def has(doc) -> bool:
        for key in ("index", *path):
            if not isinstance(doc, dict) or key not in doc:
                return False
            doc = doc[key]
        return True

    if not any(has(n) for n in w.nodes_after):
        return None
    return w.node_delta("index", *path)
