"""The client side of a cell: the four operations over the nodes' HTTP
surface, each timed on this host's clock and written to one op log.

One TCP connection per operation (the nodes answer ``Connection:
close``). A record's clock starts before the connect and stops when the
last byte of the answer has been read and, for a GET, hashed — what a
user waits for. ``status`` is the HTTP status, or 0 when the transport
failed or timed out.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import threading
import time
from dataclasses import dataclass, field
from urllib.parse import urlencode


@dataclass
class Op:
    kind: str                 # put | get | stat | delete
    client: int
    node: int                 # index of the node asked
    file_id: str              # the id asked for, or (put) the id expected
    key: tuple | None = None  # put: what the generator made the bytes from
    nbytes: int = 0           # user bytes carried
    t0: float = 0.0           # time.monotonic() before the connect
    t1: float = 0.0           # ... after the last byte was verified
    status: int = 0
    got_id: str = ""          # put/stat: the id the node answered
    body_sha: str = ""        # get: sha256 of the body as received
    body_len: int = 0
    error: str = ""
    phase: str = "run"        # preload | run | check

    @property
    def acked(self) -> bool:
        return self.status in (200, 201)

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0


@dataclass
class OpLog:
    ops: list[Op] = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def add(self, op: Op) -> Op:
        with self._lock:
            self.ops.append(op)
        return op

    def snapshot(self) -> list[Op]:
        with self._lock:
            return list(self.ops)


class Api:
    def __init__(self, ports: list[int], timeout_s: float,
                 log: OpLog) -> None:
        self.ports = ports
        self.timeout_s = timeout_s
        self.log = log
        self.phase = "run"

    def _do(self, op: Op, method: str, path: str, body=None,
            chunked: bool = False, sink=None) -> bytes:
        """Run one request, fill ``op``'s clock, status and error; the
        body comes back whole, or goes block by block to ``sink``."""
        op.phase = self.phase
        out = b""
        op.t0 = time.monotonic()
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.ports[op.node], timeout=self.timeout_s)
        try:
            headers = {"Transfer-Encoding": "chunked"} if chunked else {}
            conn.request(method, path, body=body, headers=headers,
                         encode_chunked=chunked)
            resp = conn.getresponse()
            op.status = resp.status
            if sink is None or resp.status != 200:
                out = resp.read()
            else:
                while True:
                    block = resp.read(1 << 20)
                    if not block:
                        break
                    sink(block)
        except (OSError, http.client.HTTPException) as e:
            op.status = 0
            op.error = f"{type(e).__name__}: {e}"
        finally:
            conn.close()
        op.t1 = time.monotonic()
        if not op.acked and not op.error:
            op.error = out[:200].decode("utf-8", "replace")
        return out

    def put(self, client: int, node: int, key: tuple, data, want_id: str,
            block: int = 0) -> Op:
        """POST /upload: with ``block`` the body streams in pieces of
        that size (chunked transfer, as a backup stream arrives), else it
        goes with its Content-Length (as an object PUT does)."""
        mv = memoryview(data)
        op = Op("put", client, node, want_id, key=key, nbytes=len(mv))
        path = "/upload?" + urlencode({"name": "-".join(map(str, key))})
        body = (bytes(mv[i:i + block]) for i in range(0, len(mv), block)) \
            if block else mv
        out = self._do(op, "POST", path, body=body, chunked=bool(block))
        if op.acked:
            op.got_id = str(json.loads(out).get("fileId", ""))
        return self.log.add(op)

    def get(self, client: int, node: int, file_id: str,
            keep: bool = False) -> tuple[Op, bytes]:
        """GET /download, hashed as it arrives (``keep`` also returns
        the bytes, for a check against the generator's)."""
        op = Op("get", client, node, file_id)
        h = hashlib.sha256()
        kept: list[bytes] = []

        def sink(block: bytes) -> None:
            h.update(block)
            op.body_len += len(block)
            if keep:
                kept.append(block)

        self._do(op, "GET", "/download?" + urlencode({"fileId": file_id}),
                 sink=sink)
        op.body_sha = h.hexdigest()
        op.nbytes = op.body_len if op.acked else 0
        return self.log.add(op), b"".join(kept)

    def stat(self, client: int, node: int, file_id: str
             ) -> tuple[Op, dict]:
        """GET /manifest: what the cluster knows of an object."""
        op = Op("stat", client, node, file_id)
        out = self._do(op, "GET",
                       "/manifest?" + urlencode({"fileId": file_id}))
        manifest = json.loads(out) if op.acked else {}
        op.got_id = str(manifest.get("fileId", ""))
        op.body_len = int(manifest.get("size", 0))
        return self.log.add(op), manifest

    def delete(self, client: int, node: int, file_id: str) -> Op:
        op = Op("delete", client, node, file_id)
        self._do(op, "DELETE", "/files?" + urlencode({"fileId": file_id}))
        return self.log.add(op)

    def node_metrics(self, node: int) -> tuple[dict, str]:
        """A node's ``/metrics`` as JSON and as the Prometheus page."""
        pages = []
        for path in ("/metrics", "/metrics?format=prom"):
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.ports[node], timeout=30.0)
            try:
                conn.request("GET", path)
                pages.append(conn.getresponse().read())
            finally:
                conn.close()
        return json.loads(pages[0]), pages[1].decode("utf-8", "replace")
