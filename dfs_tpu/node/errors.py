"""What the node's layers raise and the HTTP edge maps to a status.
Below every layer of the node, so placement, ingest and the runtime
share one set of classes without importing each other."""

from __future__ import annotations


class UploadError(RuntimeError):
    """Maps to HTTP 500 'Replication failed' (StorageNode.java:176) by
    default; raisers may pin a different code via ``status`` (resume
    validation -> 400, resume-missing-chunks -> 409) so the HTTP layer
    never classifies by matching message text."""

    def __init__(self, msg: str, status: int = 500) -> None:
        super().__init__(msg)
        self.status = status


class NotFoundError(KeyError):
    """Maps to HTTP 404 (StorageNode.java:408-411)."""


class DownloadError(RuntimeError):
    """Maps to HTTP 500 'Could not retrieve fragment…' / 'File corrupted'
    (StorageNode.java:443-446, 453-458)."""


class RangeNotSatisfiable(DownloadError):
    """A byte range past EOF — maps to HTTP 416 with the file size."""

    def __init__(self, size: int) -> None:
        super().__init__(f"range not satisfiable (size {size})")
        self.size = size


class DeadlineExceeded(DownloadError):
    """The caller's end-to-end deadline expired during a read — maps to
    HTTP 503 + Retry-After (the same answer the admission gate gives an
    expired arrival), never a 500: the cluster is healthy, the budget
    is gone, and a 500 would invite the immediate no-backoff retry the
    Retry-After discipline exists to prevent. Also distinct so the
    fetch walks can STOP at expiry instead of touring every remaining
    candidate and counting each refusal as a remote miss."""
