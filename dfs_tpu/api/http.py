"""External HTTP/1.1 API (L5) over asyncio streams.

Route parity with the reference's external surface (StorageNode.java:71-89):

    GET  /status            → 200 "OK"                  (:71-74)
    GET  /files             → JSON file list             (:364-393)
    POST /upload?name=…     → 201 JSON {fileId,…}        (:118-189)
    GET  /download?fileId=… → bytes + Content-Disposition (:399-461)

plus new surface the reference lacks: GET /metrics (counters), GET
/manifest?fileId=… and DELETE /files?fileId=… (SURVEY.md §2.5(5)).

Fixed reference defects: query strings are URL-decoded (the reference's
parseQuery never decodes, StorageNode.java:521-533, while its client encodes —
§2.5(3)); status lines carry real reason phrases (the reference always says
"OK", even on errors, :562); missing Content-Length on POST → 411 (:118-189).
"""

from __future__ import annotations

import asyncio
import json
from typing import TYPE_CHECKING
from urllib.parse import parse_qs, unquote, urlsplit

from dfs_tpu.utils import deadline

if TYPE_CHECKING:
    from dfs_tpu.node.runtime import StorageNodeServer

_REASONS = {200: "OK", 201: "Created", 206: "Partial Content",
            400: "Bad Request", 404: "Not Found",
            405: "Method Not Allowed", 411: "Length Required",
            413: "Payload Too Large",
            416: "Range Not Satisfiable", 500: "Internal Server Error",
            503: "Service Unavailable", 507: "Insufficient Storage"}
MAX_BODY = 4 * 1024 * 1024 * 1024
# plain (Content-Length) uploads above this stream through the
# bounded-memory ingest instead of materializing the body in node RAM
STREAM_BODY_BYTES = 64 * 1024 * 1024


def _head(status: int, length: int, content_type: str,
          extra: dict[str, str] | None = None) -> bytes:
    head = [f"HTTP/1.1 {status} {_REASONS.get(status, 'Unknown')}",
            f"Content-Type: {content_type}",
            f"Content-Length: {length}",
            "Connection: close"]
    for k, v in (extra or {}).items():
        head.append(f"{k}: {v}")
    return ("\r\n".join(head) + "\r\n\r\n").encode()


def _resp(status: int, body: bytes, content_type: str,
          extra: dict[str, str] | None = None) -> bytes:
    return _head(status, len(body), content_type, extra) + body


def _bad_id(file_id: str) -> bool:
    """Malformed fileId -> 400 up front, so a ValueError later in the
    pipeline (e.g. a corrupt peer manifest) still surfaces as a 500."""
    from dfs_tpu.utils.hashing import is_hex_digest

    return not is_hex_digest(file_id)


def plain(status: int, text: str) -> bytes:
    return _resp(status, text.encode(), "text/plain; charset=utf-8")


def as_json(status: int, obj) -> bytes:
    return _resp(status, json.dumps(obj).encode(), "application/json")


def resp_parts(status: int, parts: list, content_type: str,
               extra: dict[str, str] | None = None) -> list:
    """Vectored response: ``[head bytes, *payload buffers]``. The handler
    writes each element to the socket as-is — payload buffers (read-only
    chunk views from the store/cache/wire) are never joined into one
    body (docs/wire.md zero-copy discipline). Content-Length is the
    buffer-length sum, so the on-wire response is byte-identical to the
    joined form."""
    length = sum(len(p) for p in parts)
    return [_head(status, length, content_type, extra), *parts]


def binary_head(status: int, length: int, filename: str) -> bytes:
    """Content-Disposition download head (reference StorageNode.java:460,
    592-601; Content-Length known upfront from the manifest) — the body
    streams behind it buffer by buffer. Strip control characters (CR/LF
    would split the header — injection) and quotes before interpolating
    the user-supplied name into a header."""
    safe = "".join(c for c in filename if c >= " " and c != '"') or "download"
    return _head(status, length, "application/octet-stream",
                 {"Content-Disposition": f'attachment; filename="{safe}"'})


def _shed(node: "StorageNodeServer", e) -> bytes:
    """503 + Retry-After: admission control refused the request — the
    explicit alternative to unbounded queuing (every queued request
    degrades every other one; a shed request costs one cheap retry)."""
    import math as _math

    node.counters.inc("http_shed")
    return _resp(503, str(e).encode(), "text/plain; charset=utf-8",
                 {"Retry-After": str(max(1, _math.ceil(e.retry_after_s)))})


def _deadline_503(node: "StorageNodeServer", e) -> bytes:
    """503 + Retry-After for a deadline that died AFTER admission: the
    same answer the gate gives an expired arrival — never a 500, which
    would invite exactly the immediate no-backoff retry the Retry-After
    discipline exists to prevent (the cluster is healthy; the caller's
    budget is not)."""
    import math as _math

    node.counters.inc("http_shed")
    return _resp(503, str(e).encode(), "text/plain; charset=utf-8",
                 {"Retry-After": str(max(1, _math.ceil(
                     node.cfg.serve.retry_after_s)))})


class _GatedBody:
    """Streamed-body wrapper holding a download admission slot for the
    LIFETIME of the body — gating that released at the first byte would
    bound nothing. An explicit class, not a wrapper generator: closing a
    never-started generator skips its ``finally`` entirely (the head
    write can fail before the first iteration), which would leak the
    slot forever."""

    def __init__(self, gate, gen) -> None:
        self._gate = gate
        self._gen = gen
        self._released = False

    def __aiter__(self) -> "_GatedBody":
        return self

    async def __anext__(self):
        try:
            return await self._gen.__anext__()
        except BaseException:       # incl. StopAsyncIteration
            await self.aclose()
            raise

    async def aclose(self) -> None:
        if self._released:
            return
        self._released = True
        try:
            await self._gen.aclose()
        finally:
            self._gate.release()


def _parse_range(value: str) -> tuple[int | None, int | None] | None:
    """Parse a single-range ``bytes=`` header into (first, last) with
    either side possibly open: 'bytes=a-b' -> (a, b), 'bytes=a-' ->
    (a, None), 'bytes=-n' -> (None, n). Multi-range and malformed ->
    None (caller answers 400)."""
    if not value.startswith("bytes=") or "," in value:
        return None
    spec = value[len("bytes="):].strip()
    first, _, last = spec.partition("-")
    if _ != "-" or (not first and not last):
        return None
    # digits only (RFC 9110: first-byte-pos / suffix-length = 1*DIGIT) —
    # int() would accept signs, turning 'bytes=--5' into a bogus negative
    # suffix that read as satisfiability instead of malformed syntax
    if (first and not first.isdigit()) or (last and not last.isdigit()):
        return None
    return (int(first) if first else None,
            int(last) if last else None)


async def _chunked_body(reader: asyncio.StreamReader, limit: int = MAX_BODY):
    """Async generator over an HTTP/1.1 chunked-transfer body. Raises
    ValueError on malformed framing; enforces a cumulative size cap."""
    total = 0
    while True:
        line = (await reader.readline()).decode("latin-1").strip()
        if not line:
            raise ValueError("missing chunk size")
        try:
            size = int(line.split(";", 1)[0], 16)  # ignore extensions
        except ValueError as e:
            raise ValueError(f"bad chunk size {line!r}") from e
        if size == 0:
            # consume trailer section up to the blank line
            while True:
                t = await reader.readline()
                if t in (b"\r\n", b"\n", b""):
                    return
        total += size
        if total > limit:
            raise ValueError("chunked body exceeds size cap")
        data = await reader.readexactly(size)
        crlf = await reader.readexactly(2)
        if crlf != b"\r\n":
            raise ValueError("missing chunk terminator")
        yield data


def make_http_handler(node: "StorageNodeServer"):
    import time

    async def handler(reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        t0 = time.perf_counter()
        body_gen = None
        try:
            out = await _serve_one(node, reader)
            if isinstance(out, tuple):          # streamed body
                out, body_gen = out
        except Exception as e:  # noqa: BLE001
            node.log.warning("http error: %s", e)
            out = plain(500, f"Internal error: {e}")
        node.latency.record("http.request", time.perf_counter() - t0)
        try:
            if isinstance(out, list):
                # vectored response (resp_parts): head + payload views
                # written individually — no join anywhere on the way out
                for part in out:
                    writer.write(part)
            else:
                writer.write(out)
            await writer.drain()
            if body_gen is not None:
                try:
                    async for part in body_gen:
                        writer.write(part)
                        await writer.drain()    # socket backpressure
                except Exception as e:  # noqa: BLE001
                    # head already sent: the only honest signal left is
                    # truncation (close before Content-Length is met) —
                    # never pad a corrupt/incomplete body to completion
                    node.log.warning("download stream aborted: %s", e)
        except (ConnectionError, OSError):
            pass
        finally:
            if body_gen is not None:
                try:
                    await body_gen.aclose()
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    return handler


# routes whose (fixed) path may become a span name; anything else is
# "http.other" so an attacker-chosen path can never mint span names
_TRACED_ROUTES = frozenset({
    "/status", "/files", "/metrics", "/manifest", "/chunking", "/missing",
    "/upload_resume", "/upload", "/download", "/scrub", "/repair",
    "/trace", "/events", "/doctor", "/census", "/metrics/history",
    "/chaos", "/ring", "/dataplane", "/commit"})

# routes the CONFIGURED default deadline applies to: the client-facing
# data plane. Maintenance/diagnosis endpoints (/repair, /scrub,
# /census, /doctor …) are deliberately exempt — an operator-requested
# healing pass capped at the traffic deadline would abort partway
# through exactly the backlog it was asked to clear. An EXPLICIT
# X-Dfs-Deadline header is honored on any route (the caller asked).
_DEADLINE_DEFAULT_ROUTES = frozenset({
    "/download", "/upload", "/upload_resume", "/missing", "/chunking",
    "/manifest", "/files", "/commit"})


async def _serve_one(node: "StorageNodeServer",
                     reader: asyncio.StreamReader) -> bytes:
    from dfs_tpu.obs import parse_http_trace

    request_line = (await reader.readline()).decode("latin-1").strip()
    if not request_line:
        return plain(400, "Empty request")
    parts = request_line.split(" ")
    if len(parts) != 3:
        return plain(400, "Malformed request line")
    method, target, _version = parts
    split = urlsplit(target)
    path = unquote(split.path)
    query = {k: v[0] for k, v in parse_qs(split.query).items()}

    content_length: int | None = None
    range_header: str | None = None
    trace_header: str | None = None
    deadline_header: str | None = None
    chunked = False
    while True:
        line = (await reader.readline()).decode("latin-1")
        if line in ("\r\n", "\n", ""):
            break
        if ":" in line:
            k, v = line.split(":", 1)
            key = k.strip().lower()
            if key == "content-length":
                try:
                    content_length = int(v.strip())
                except ValueError:
                    return plain(400, "Bad Content-Length")
                if content_length < 0:
                    # int() accepts signs; a negative length would reach
                    # readexactly() and 500 instead of being rejected
                    return plain(400, "Bad Content-Length")
            elif key == "range":
                range_header = v.strip()
            elif key == "x-dfs-trace":
                # distributed-tracing carrier (docs/observability.md):
                # "<trace32hex>-<span16hex>"; absent or malformed simply
                # roots a fresh trace — a bad header never fails a request
                trace_header = v.strip()
            elif key == "x-dfs-deadline":
                # end-to-end deadline carrier (docs/serve.md §deadlines):
                # remaining budget in seconds; absent or malformed means
                # no client deadline — the default-deadline config (or
                # nothing) applies, never an error
                deadline_header = v.strip()
            elif key == "transfer-encoding":
                chunked = "chunked" in v.strip().lower()

    node.counters.inc("http_requests")

    # deadline born at the edge: the client's X-Dfs-Deadline budget, or
    # the configured default for clients that sent none. Carried in a
    # contextvar exactly like the trace context, so every downstream hop
    # (admission waits, RPC calls, CAS pool jobs) inherits it. Both
    # absent (the default config) = no deadline = pre-r18 behavior.
    budget = deadline.parse_header(deadline_header)
    if budget is None and path in _DEADLINE_DEFAULT_ROUTES \
            and node.cfg.serve.default_deadline_s > 0:
        budget = node.cfg.serve.default_deadline_s
    dl_token = deadline.activate(budget) if budget is not None else None

    # the request span: every downstream hop (rpc calls, CAS pool jobs,
    # admission waits) inherits its context via contextvars and parents
    # to it. Streamed-download bodies outlive the span (it covers work
    # up to the response head + first batch) — docs/observability.md.
    name = f"http.{path}" if path in _TRACED_ROUTES else "http.other"
    # latency=True: per-route histograms (bounded: allowlisted routes +
    # http.other) whose buckets carry the request's trace id as an
    # OpenMetrics exemplar — /metrics links a slow bucket to `trace <id>`
    streamed = False
    try:
        with node.obs.request_span(name, parse_http_trace(trace_header),
                                   latency=True) as sp:
            out = await _route(node, reader, method, path, query,
                               content_length, range_header, chunked)
            # a (head, body_gen) tuple is a streamed download: the
            # handler iterates the body in THIS task after we return,
            # and the generator's per-batch _fetch_verified deadline
            # checks must keep seeing the countdown — so the context
            # is deliberately NOT restored (it dies with the handler
            # task; the connection serves exactly one request).
            # Restoring here silently disarmed mid-download expiry for
            # every batch after the first (r18 review finding).
            streamed = isinstance(out, tuple)
            if isinstance(out, (bytes, bytearray)):
                sp.bytes = len(out)
            elif isinstance(out, list):             # vectored response
                sp.bytes = sum(len(p) for p in out)
            return out
    finally:
        if dl_token is not None and not streamed:
            deadline.restore(dl_token)


async def _route(node: "StorageNodeServer", reader: asyncio.StreamReader,
                 method: str, path: str, query: dict,
                 content_length: int | None, range_header: str | None,
                 chunked: bool):
    from dfs_tpu.comm.rpc import DeadlineExpired
    from dfs_tpu.node.runtime import (DeadlineExceeded, DownloadError,
                                      NotFoundError, RangeNotSatisfiable,
                                      UploadError)
    from dfs_tpu.serve import ClientDisconnected, ShedError

    if method == "GET" and path == "/status":
        return plain(200, "OK")  # exact reference reply, StorageNode.java:73

    if method == "GET" and path == "/files":
        return as_json(200, node.list_files())

    if method == "GET" and path == "/metrics":
        if query.get("format") == "prom":
            # unified Prometheus exposition: counters + stopwatches +
            # latency HISTOGRAM BUCKETS + per-peer/op RPC series
            from dfs_tpu.obs.prom import render_node_metrics

            # OpenMetrics content type, NOT text/plain 0.0.4: the bucket
            # lines carry exemplar suffixes, which classic-format
            # parsers reject — the Content-Type tells Prometheus which
            # parser to use (obs/prom.py module docstring)
            return _resp(200, render_node_metrics(node).encode(),
                         "application/openmetrics-text; version=1.0.0; "
                         "charset=utf-8")
        snap = node.counters.snapshot()
        snap["nodeId"] = node.cfg.node_id
        snap["underReplicated"] = len(node.under_replicated)
        snap["latency"] = node.latency.snapshot()
        snap["peersAlive"] = node.health.snapshot()
        snap["serve"] = node.serve.stats()   # cache/flight/admission
        snap["ingest"] = node.ingest_stats()  # write-path pipeline:
        # window/credit bounds, stall attribution, CAS-tier queue/busy
        snap["ec"] = node.ec_stats()   # erasure coding: objects,
        # stripes, encode calls, parity bytes (additive)
        snap["frag"] = node.frag_stats()  # fragmenter execution knobs
        # (device sharding / staging depth) + the live engine name
        snap["obs"] = node.obs.stats()   # trace ring + RPC tables —
        # ADDITIVE: the pre-r09 JSON schema stays a strict subset
        snap["census"] = node.census_stats()  # capacity gauges +
        # history-sampler config/state (r12, additive like "obs")
        snap["durability"] = node.durability_stats()  # fsync mode +
        # barrier count (r13, additive)
        snap["repair"] = node.repair_stats()  # the repair cycle:
        # cycles ended, manifests read vs remembered, seconds on the loop
        snap["chaos"] = node.chaos_stats()  # fault-injection knobs +
        # injected counters; {"enabled": false} on a chaos-less node
        snap["retryBudget"] = node.client.retry_budget.stats()
        snap["ring"] = node.ring_stats()  # membership epoch + rebalance
        # progress (r14, additive like "obs"/"census")
        snap["index"] = node.index_stats()  # dedup/index plane: LSI
        # gauges + filter bytes + probe-skip counters (r16, additive);
        # {"enabled": false, ...config echo} on a plane-less node
        snap["tier"] = node.tier_stats()  # hot/cold tiering: ledger +
        # demotion/promotion counters (r20, additive);
        # {"enabled": false} on a tier-less node
        snap["sim"] = node.sim_stats()  # similarity compression:
        # sketch/delta counters (r21, additive);
        # {"enabled": false} on a sim-less node
        return as_json(200, snap)

    if method == "GET" and path == "/metrics/history":
        # embedded metrics history (docs/observability.md): downsampled
        # multi-resolution series the census sampler maintains. No name
        # -> the series directory; sampler off -> enabled:false, never
        # an error (the /events discipline).
        history = node.history
        if history is None:
            return as_json(200, {"enabled": False, "series": []})
        name = query.get("name")
        if not name:
            return as_json(200, {"enabled": True,
                                 "series": history.names()})
        snap = history.snapshot(name)
        if snap is None:
            return plain(404, "Unknown series")
        snap["enabled"] = True
        return as_json(200, snap)

    if method == "GET" and path == "/census":
        # replication-health census + cluster capacity (df): fan out
        # bucketed inventories (partial on dead peers), cross-reference
        # manifests, answer with the replication histogram + bounded
        # finding lists. &cluster=0 = this node's inventory only.
        return as_json(200, await node.census_report(
            cluster=query.get("cluster", "1") != "0"))

    if method == "GET" and path == "/trace":
        from dfs_tpu.obs import TRACE_HEX, is_id

        tid = query.get("traceId")
        if not tid or not is_id(tid, TRACE_HEX):
            return plain(400, "Bad traceId")
        # cluster-wide stitch by default; &cluster=0 = this ring only
        return as_json(200, await node.trace_spans(
            tid, cluster=query.get("cluster", "1") != "0"))

    if method == "GET" and path == "/events":
        # flight-recorder query (docs/observability.md): recent journal
        # events, oldest first. `since` is a unix-seconds float, `limit`
        # caps the newest events returned. Journal off -> empty list
        # with enabled:false, never an error.
        journal = node.obs.journal
        if journal is None:
            return as_json(200, {"enabled": False, "events": []})
        try:
            since = float(query.get("since", 0.0))
            limit = int(query.get("limit", 256))
        except ValueError:
            return plain(400, "Bad since/limit")
        if limit < 1 or limit > 4096:
            return plain(400, "limit out of range (1..4096)")
        # segment reads are file I/O — off the event loop like every
        # other disk touch (dfslint DFS001)
        out = await asyncio.to_thread(journal.tail, since, limit)
        out["enabled"] = True
        return as_json(200, out)

    if path == "/chaos" and method in ("GET", "POST"):
        # fault-injection control plane (docs/chaos.md): GET = active
        # knobs + injected-fault counters; POST {knob: value, ...} =
        # atomically swap the mutable knobs (the harness scripts
        # inject → observe → heal scenarios this way). Hard 404 when
        # the node was not booted with chaos enabled — the master
        # switch is boot-only on purpose: a production node must not
        # be fault-injectable by anyone who can reach its HTTP port.
        if node.chaos is None:
            return plain(404, "Chaos disabled (boot with --chaos)")
        if method == "GET":
            return as_json(200, node.chaos.stats())
        if content_length is None:
            return plain(411, "Length Required")
        if content_length > 64 * 1024:
            return plain(413, "Payload Too Large")
        try:
            knobs = json.loads(await reader.readexactly(content_length))
            if not isinstance(knobs, dict):
                raise ValueError("want a JSON object of chaos knobs")
            return as_json(200, node.chaos.set(**knobs))
        # AttributeError: a wrong-typed knob value (e.g. partition: 5)
        # failing inside ChaosConfig validation is still a bad request
        except (ValueError, TypeError, AttributeError,
                UnicodeDecodeError) as e:
            return plain(400, f"Bad chaos knobs: {e}")

    if path == "/tier" and method in ("GET", "POST"):
        # hot/cold tiering control plane (docs/tiering.md): GET = the
        # /metrics "tier" section standalone; POST (empty body) = run
        # one demotion scan NOW and answer its summary — the
        # deterministic path tests and operators use instead of waiting
        # out --tier-scan-interval. 404 when the plane is off: tiering
        # is a boot decision, like /chaos.
        if node.tier is None:
            return plain(404, "Tiering disabled (boot with --tier)")
        if method == "GET":
            return as_json(200, node.tier_stats())
        try:
            return as_json(200, await node.tier_scan_once())
        except ShedError as e:
            return _shed(node, e)

    if path == "/ring" and method in ("GET", "POST"):
        # elastic membership admin plane (docs/membership.md): GET =
        # epoch/members/migration status (+ every peer's epoch view);
        # POST {"action": "add"|"drain"|"remove"|"reweight",
        # "nodeId": N[, "weight": W]} = bump the epoch, install the new
        # map locally, push it to every peer, and kick the rebalancer.
        if method == "GET":
            return as_json(200, await node.ring_status(
                cluster=query.get("cluster", "1") != "0"))
        if content_length is None:
            return plain(411, "Length Required")
        if content_length > 64 * 1024:
            return plain(413, "Payload Too Large")
        try:
            body = json.loads(await reader.readexactly(content_length))
            if not isinstance(body, dict):
                raise ValueError("want a JSON object")
            action = str(body.get("action", ""))
            node_id = body.get("nodeId")
            weight = body.get("weight")
            return as_json(200, await node.ring_admin(
                action,
                node_id=int(node_id) if node_id is not None else None,
                weight=float(weight) if weight is not None else None))
        except (ValueError, TypeError, UnicodeDecodeError) as e:
            return plain(400, f"Bad ring change: {e}")

    if method == "GET" and path == "/doctor":
        # cluster doctor: fan out per-peer snapshots (partial on dead
        # peers) + run the pathology rule table. &cluster=0 = this node
        # only (still runs single-node rules).
        return as_json(200, await node.doctor_report(
            cluster=query.get("cluster", "1") != "0"))

    if method == "GET" and path == "/manifest":
        file_id = query.get("fileId")
        if not file_id:
            return plain(400, "Missing fileId")
        if _bad_id(file_id):
            return plain(400, "Bad fileId")
        m = node.store.manifests.load(file_id)
        if m is None:
            return plain(404, "File not found")
        return _resp(200, m.to_json().encode(), "application/json")

    if method == "GET" and path == "/chunking":
        # resumable-upload probe step 1: parameters sufficient for the
        # client to reproduce chunk boundaries bit-exactly (CPU/TPU/
        # sidecar engines chunk identically by construction)
        try:
            desc = node.fragmenter.describe()
        except NotImplementedError:
            return plain(404, "Fragmenter not resume-describable")
        return as_json(200, {"fragmenter": node.fragmenter.name,
                             "describe": desc})

    if method == "GET" and path == "/dataplane":
        # smart-client bootstrap (docs/client.md): ring map + peer
        # address book + chunking description + filter state in one
        # call. Old servers 404 this path — the client's cue to fall
        # back to the coordinator data plane.
        return as_json(200, node.dataplane_info())

    if method == "POST" and path == "/commit":
        # single-hop ingest commit (docs/client.md): the client striped
        # payloads straight to the ring owners; this call carries ONLY
        # the chunk table. body: [u32 json_len][json {fileId,size,
        # chunks}] — same framing family as /upload_resume, zero
        # payload section.
        if content_length is None:
            return plain(411, "Length Required")
        if content_length > 64 * 1024 * 1024:
            return plain(413, "Payload Too Large")
        gate = node.serve.admission.upload
        try:
            await gate.acquire()   # shed BEFORE buffering the body
        except ShedError as e:
            return _shed(node, e)
        try:
            raw = await reader.readexactly(content_length)
            try:
                jlen = int.from_bytes(raw[:4], "big")
                meta = json.loads(raw[4:4 + jlen])
                if 4 + jlen != len(raw):
                    raise ValueError("trailing bytes after table")
                table = [(int(o), int(ln), str(dg))
                         for o, ln, dg in meta["chunks"]]
                file_id, size = str(meta["fileId"]), int(meta["size"])
            except (KeyError, ValueError, TypeError) as e:
                return plain(400, f"Bad commit frame: {e}")
            if _bad_id(file_id):
                return plain(400, "Bad fileId")
            try:
                manifest, stats = await node.commit_manifest(
                    table, query.get("name", ""), file_id, size)
            except (DeadlineExpired, DeadlineExceeded) as e:
                return _deadline_503(node, e)
            except UploadError as e:
                # 409 = chunks not durably present (client falls back
                # to a full upload); 400 = bad table; 500 = placement
                return plain(e.status, str(e))
            return as_json(201, {"fileId": manifest.file_id,
                                 "name": manifest.name,
                                 "size": manifest.size,
                                 "chunks": manifest.total_chunks,
                                 **stats})
        finally:
            gate.release()

    if method == "POST" and path == "/missing":
        if content_length is None:
            return plain(411, "Length Required")
        if content_length > 64 * 1024 * 1024:
            return plain(413, "Payload Too Large")
        try:
            digests = json.loads(await reader.readexactly(content_length))
            if (not isinstance(digests, list)
                    or not all(isinstance(d, str) for d in digests)):
                raise ValueError("want a JSON list of digest strings")
        except (ValueError, UnicodeDecodeError) as e:
            return plain(400, f"Bad digest list: {e}")
        return as_json(200,
                       {"missing": await node.missing_digests(digests)})

    if method == "POST" and path == "/upload_resume":
        # body: [u32 json_len][json {fileId,size,chunks,provided}]
        # [provided payloads concatenated in listed order]
        if content_length is None:
            return plain(411, "Length Required")
        if content_length > MAX_BODY:
            return plain(413, "Payload Too Large")
        gate = node.serve.admission.upload
        try:
            await gate.acquire()   # shed BEFORE buffering the body
        except ShedError as e:
            return _shed(node, e)
        try:
            raw = await reader.readexactly(content_length)
            try:
                jlen = int.from_bytes(raw[:4], "big")
                meta = json.loads(raw[4:4 + jlen])
                table = [(int(o), int(ln), str(dg))
                         for o, ln, dg in meta["chunks"]]
                lengths = {dg: ln for _, ln, dg in table}
                provided: dict[str, bytes] = {}
                off = 4 + jlen
                for dg in meta["provided"]:
                    ln = lengths[dg]
                    provided[dg] = raw[off:off + ln]
                    off += ln
                if off != len(raw):
                    raise ValueError("payload section length mismatch")
                file_id, size = str(meta["fileId"]), int(meta["size"])
            except (KeyError, ValueError, TypeError) as e:
                return plain(400, f"Bad resume frame: {e}")
            if _bad_id(file_id):
                return plain(400, "Bad fileId")
            try:
                manifest, stats = await node.upload_resume(
                    table, query.get("name", ""), file_id, size, provided)
            except (DeadlineExpired, DeadlineExceeded) as e:
                return _deadline_503(node, e)
            except UploadError as e:
                # 409 = resume no longer possible (client falls back to a
                # full upload); 400 = bad frame/table; 500 = placement
                # failed
                return plain(e.status, str(e))
            return as_json(201, {"fileId": manifest.file_id,
                                 "name": manifest.name,
                                 "size": manifest.size,
                                 "chunks": manifest.total_chunks, **stats})
        finally:
            gate.release()

    if method == "POST" and path == "/upload":
        ec_k = 0
        if query.get("ec"):
            # isdecimal, not isdigit: the latter passes non-ASCII digits
            # (e.g. '²') that int() then rejects — a 500 instead of 400
            if not query["ec"].isdecimal() or int(query["ec"]) < 1:
                return plain(400, "Bad ec parameter")
            ec_k = int(query["ec"])
            if chunked:
                return plain(400, "ec requires a whole-body upload "
                                  "(parity stripes span chunk groups)")
        if not chunked:
            if content_length is None:
                return plain(411, "Length Required")  # reference parity
            if content_length > MAX_BODY:
                return plain(413, "Payload Too Large")
        gate = node.serve.admission.upload
        try:
            await gate.acquire()   # shed BEFORE consuming the body
        except ShedError as e:
            return _shed(node, e)
        try:
            return await _handle_upload(node, reader, query, chunked,
                                        content_length, ec_k)
        finally:
            gate.release()

    if method == "GET" and path == "/download":
        file_id = query.get("fileId")
        if not file_id:
            return plain(400, "Missing fileId")
        if _bad_id(file_id):
            return plain(400, "Bad fileId")
        rng = None
        if range_header is not None:
            # partial read: chunk-granular manifests make byte ranges
            # cheap (only overlapping chunks are gathered) — surface
            # the reference never had (no range requests anywhere,
            # SURVEY.md §2.5(5)); satisfiability is resolved in ONE
            # place (download_range), this layer only parses/formats
            rng = _parse_range(range_header)
            if rng is None:
                return plain(400, "Bad Range")
            if (rng[0] is not None and rng[1] is not None
                    and rng[0] > rng[1]):
                # 'bytes=5-2' is syntactically invalid per RFC 9110
                # §14.1.1: the Range header MUST be ignored (full 200
                # body), not answered 416.
                rng = None
        gate = node.serve.admission.download
        try:
            # disconnect watcher: a GET has no body, so the only thing
            # this read can ever return is b"" (EOF — the client hung
            # up) or stray garbage; the gate frees our queue position
            # on EOF so an abandoned download never consumes a slot
            # when it reaches the head (docs/serve.md)
            await gate.acquire(disconnected=lambda: reader.read(1))
        except ShedError as e:
            return _shed(node, e)
        except ClientDisconnected:
            # nobody left to answer; the handler's write of b"" is a
            # no-op on the dead socket
            node.counters.inc("http_client_gone")
            return b""
        streaming = None
        try:
            if rng is not None:
                try:
                    manifest, parts, start, end = await node.download_range(
                        file_id, *rng)
                except RangeNotSatisfiable as e:
                    return _resp(416, b"", "text/plain",
                                 {"Content-Range": f"bytes */{e.size}"})
                # vectored 206: the range's chunk views go to the socket
                # one by one — never joined into a body (docs/wire.md)
                return resp_parts(
                    206, parts, "application/octet-stream",
                    {"Content-Range":
                     f"bytes {start}-{end - 1}/{manifest.size}",
                     "Accept-Ranges": "bytes"})
            # STREAMING read: chunks go to the socket as they verify —
            # node memory stays ~one fetch batch for any file size (the
            # reference assembles the whole file in RAM before replying,
            # StorageNode.java:419,448; its heap bounds usable file
            # size). The first batch is fetched before the head is
            # written, so the common failures still answer 404/500.
            manifest, body_gen = await node.download_stream(file_id)
            # the admission slot stays held until the body fully drains
            # (or the client disconnects) — see _GatedBody
            streaming = _GatedBody(gate, body_gen)
            return binary_head(200, manifest.size, manifest.name), streaming
        except NotFoundError:
            return plain(404, "File not found")
        except DeadlineExceeded as e:
            # the budget died post-admission, pre-head: same answer as
            # an expired arrival at the gate
            return _deadline_503(node, e)
        except DownloadError as e:
            return plain(500, str(e))
        finally:
            if streaming is None:
                gate.release()

    if method == "POST" and path == "/scrub":
        # verify every local chunk against its content address; corrupt
        # ones are evicted and queued for repair (reference has no
        # integrity scanning at all — read-time whole-file check only)
        return as_json(200, await node.scrub_once())

    if method == "POST" and path == "/repair":
        # Operator-triggered re-replication (the serve loop also runs this
        # periodically; the reference has no repair at all — SURVEY.md §5.3).
        repaired = await node.repair_once()
        return as_json(200, {"repaired": repaired,
                             "underReplicated": len(node.under_replicated)})

    if method == "DELETE" and path == "/files":
        file_id = query.get("fileId")
        if not file_id:
            return plain(400, "Missing fileId")
        if _bad_id(file_id):
            return plain(400, "Bad fileId")
        found = await node.delete(file_id)
        return plain(200 if found else 404,
                     "Deleted" if found else "File not found")

    return plain(404, "Not found")  # reference: unknown routes → 404, :107


async def _handle_upload(node: "StorageNodeServer",
                         reader: asyncio.StreamReader, query: dict,
                         chunked: bool, content_length: int | None,
                         ec_k: int) -> bytes:
    """POST /upload body handling (factored out so the admission gate
    wraps it in one try/finally)."""
    from dfs_tpu.comm.rpc import DeadlineExpired
    from dfs_tpu.node.runtime import DeadlineExceeded, UploadError

    if chunked or (content_length > STREAM_BODY_BYTES and not ec_k):
        # streaming ingest: the body feeds the fragmenter's
        # bounded-memory pipeline as it arrives — the whole payload
        # never exists in node memory (the reference reads the
        # entire body into one array, StorageNode.java:124). Since
        # round 4 large PLAIN bodies take this path too, read off
        # the socket in ~1 MiB pieces; EC uploads still materialize
        # (parity stripes group chunks across the whole file).
        async def _plain_body():
            left = content_length
            while left:
                b = await reader.read(min(1 << 20, left))
                if not b:
                    raise asyncio.IncompleteReadError(b"", left)
                left -= len(b)
                yield b

        body = _chunked_body(reader) if chunked else _plain_body()
        try:
            manifest, stats = await node.upload_stream(
                body, query.get("name", ""))
        except (DeadlineExpired, DeadlineExceeded) as e:
            # the caller's budget died mid-placement: a 503-class
            # refusal (already-placed chunks age out via GC; a later
            # retry dedups them) — see _deadline_503
            return _deadline_503(node, e)
        except UploadError as e:
            return plain(getattr(e, "status", 500), str(e))
        except ValueError as e:
            return plain(400, f"Bad request body: {e}")
    else:
        data = await reader.readexactly(content_length)
        try:
            manifest, stats = await node.upload(
                data, query.get("name", ""), ec_k=ec_k)
        except (DeadlineExpired, DeadlineExceeded) as e:
            return _deadline_503(node, e)
        except UploadError as e:
            # "Replication failed" -> 500 (:176); ec validation -> 400
            return plain(getattr(e, "status", 500), str(e))
    return as_json(201, {"fileId": manifest.file_id,
                         "name": manifest.name,
                         "size": manifest.size,
                         "chunks": manifest.total_chunks, **stats})
