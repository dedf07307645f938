"""gRPC sidecar: the accelerator pipeline as a local service (north star,
BASELINE.json: "The Java StorageNode calls the TPU backend over a local gRPC
sidecar during upload").

Any host process — a storage node written in another language, or a Python
node that wants the TPU in a separate process so device init/compile never
blocks the serving loop — streams bytes in and gets chunk boundaries +
per-chunk SHA-256 digests back.

The wire contract uses gRPC *generic* handlers with identity (bytes)
serialization: the environment ships grpcio but not grpc_tools/protoc-gen-py,
and the payloads are length-delimited binary anyway (protobuf would Base64
nothing, buy nothing). Methods (all under service ``dfs.Sidecar``):

- ``ChunkHashStream`` **stream-unary — the production path**. Request: a
  stream of raw byte blocks (any blocking; 4 MiB is typical). Response:
  JSON chunk table. No payload ceiling: blocks feed the fragmenter's
  bounded-memory pipelined streaming walk (fragmenter/cdc_anchored.py), so
  a multi-GiB upload holds ~(max_inflight+1) regions in memory, never the
  whole stream.
- ``ChunkHash``  unary-unary compatibility path (whole payload in one
  message, 1 GiB gRPC message cap applies).
- ``Health``     unary-unary. Request: empty. Response: JSON status,
  including ``device`` — what the engine computes on, as JAX reports it,
  how many regions it has dispatched there and where its streams' wall
  time went (null for host engines) — ``spans``, the owner's span
  totals, and ``compile``, what JAX spent tracing, lowering and
  compiling in this process since it started (``_CompileClock``).
  Counters only: it is polled.
- ``Trace``      unary-unary. Request: JSON ``{"traceId"}`` or
  ``{"sinceMonoNs", "untilMonoNs"}``. Response: ``{"spans": [...]}``
  from the owner's span ring (dfs_tpu/obs): the owner is a node of the
  trace like any other. A caller names its current span in the gRPC
  metadata key ``x-dfs-trace`` (the ``X-Dfs-Trace`` header's value
  format); every chunking handler opens ``owner.stream`` under it.

The sidecar is the deployment's CHIP OWNER (utils/device.py): a chip
belongs to one process, so N nodes on a host share it through this one.
It accepts a ``fragmenter`` name at startup — default ``auto`` (the
anchored flagship: TPU device engine iff the machine has a TPU platform,
decided once, fragmenter/base.py). ``SidecarFragmenter`` is the node-side
adapter: a drop-in Fragmenter that delegates chunk+hash to a sidecar
process (NodeConfig.sidecar_port wires it into the node runtime).
"""

from __future__ import annotations

import json
import threading
import time
from concurrent import futures

import grpc

from dfs_tpu import obs as obs_mod
from dfs_tpu.fragmenter.base import Fragmenter

_SERVICE = "dfs.Sidecar"
STREAM_BLOCK = 4 * 1024 * 1024
_TRACE_KEY = "x-dfs-trace"      # gRPC metadata keys are lower case


def _trace_metadata() -> tuple | None:
    """The calling thread's current span as call metadata, or None when
    it is untraced (or tracing is off: the context is then never set)."""
    cur = obs_mod.current()
    return ((_TRACE_KEY, f"{cur[0]}-{cur[1]}"),) if cur else None


def _identity(x: bytes) -> bytes:
    return x


class _CompileClock:
    """What JAX spent making programs in this process (``Health``
    ``compile``): listeners on ``jax.monitoring``, registered before
    the engine is built so that its first compile is in. ``traceS``,
    ``lowerS`` and ``backendCompileS`` sum the three
    ``/jax/core/compile/*_duration`` events (the last is a module's way
    through the backend, a persistent-cache read included), ``modules``
    counts the last; ``cacheRequests`` / ``cacheHits`` the modules that
    asked the persistent compile cache and those it answered. An event
    of those two families that this table does not know is kept under
    its own name — seconds summed, occurrences counted — and not
    dropped.

    Events of one name NEST: a jit traced inside a jit reports its own
    duration and the outer one reports both (the 16 MiB chain's plain
    sum read 35 s of tracing inside a first region of 39 s that also
    held 27 s of lowering and compiling; PERF.md §6, PR 38). A sum is
    the seconds of the OUTERMOST events: what ended on this thread
    inside an event's interval was counted already and is taken out
    again."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "traceS",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerS",
        "/jax/core/compile/backend_compile_duration": "backendCompileS"}
    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache":
            "cacheRequests",
        "/jax/compilation_cache/cache_hits": "cacheHits"}
    _KEPT = ("/jax/core/compile/", "/jax/compilation_cache/")
    _PILE = 1 << 18     # events remembered per thread and name

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        # per (thread, name): (when it ended, seconds) of the events
        # that no later event has enclosed yet
        self._ended: dict[tuple, list] = {}
        self._t: dict[str, float] = {
            **dict.fromkeys(self._DURATIONS.values(), 0.0), "modules": 0,
            **dict.fromkeys(self._EVENTS.values(), 0)}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if not event.startswith(self._KEPT):
            return
        now = time.monotonic()      # an event reports as it ends
        with self._lock:
            ended = self._ended.setdefault(
                (threading.get_ident(), event), [])
            inside = 0.0
            while ended and ended[-1][0] >= now - secs:
                inside += ended.pop()[1]
            ended.append((now, secs))
            # one after the other they pile up until their parent ends:
            # one kernel of the 16 MiB chain traces 31 369 ops, each an
            # event of its own. Past the bound the oldest go, which are
            # the likeliest to be enclosed by nothing any more.
            del ended[:-self._PILE]
            key = self._DURATIONS.get(event, event)
            self._t[key] = self._t.get(key, 0.0) + secs - inside
            if key == "backendCompileS":
                self._t["modules"] += 1

    def _event(self, event: str, **_) -> None:
        if event.startswith(self._KEPT):
            key = self._EVENTS.get(event, event)
            with self._lock:
                self._t[key] = self._t.get(key, 0) + 1

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        with self._lock:
            return {k: round(v, 6) if isinstance(v, float) else v
                    for k, v in self._t.items()}


class SidecarServer:
    # A chunking call holds its pool thread from first byte to reply. A
    # small stream's thread spends that time asleep, waiting for the
    # packed region that carries its stream (fragmenter/cdc_anchored.py
    # ``_Packer``): the pool is what bounds how many streams one region
    # can gather, so it is sized for waiters — one thread at a time
    # stages and collects, whatever the pool holds. (Four, until PR 41:
    # a region could then never hold more than four streams.)
    def __init__(self, port: int = 0, fragmenter: str = "auto",
                 cdc_params=None, max_workers: int = 32) -> None:
        from dfs_tpu.config import ObsConfig
        from dfs_tpu.fragmenter.base import get_fragmenter

        self.compile_clock = _CompileClock()
        self.fragmenter = get_fragmenter(fragmenter, cdc_params=cdc_params)
        # ring and totals only (no journal, no sentinel): node id 0 is
        # the owner in a stitched tree. The engine opens its per-window
        # spans through it.
        self.obs = obs_mod.Observability(ObsConfig(), node_id=0)
        self.fragmenter.obs = self.obs
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[("grpc.max_receive_message_length", 1 << 30),
                     ("grpc.max_send_message_length", 1 << 30)])
        self._server.add_generic_rpc_handlers((self._handlers(),))
        self.port = self._server.add_insecure_port(f"127.0.0.1:{port}")

    def _chunk_table(self, chunks, size: int) -> bytes:
        from dfs_tpu.ops.cdc_v2 import file_id_from_digests

        return json.dumps({
            "fragmenter": self.fragmenter.name,
            # digest-derived, NOT sha256(payload): re-hashing the whole
            # payload to label the response would double the hash work of
            # the very service whose job is fast hashing
            "fileId": file_id_from_digests([c.digest for c in chunks]),
            "size": size,
            "chunks": [{"index": c.index, "offset": c.offset,
                        "length": c.length, "digest": c.digest}
                       for c in chunks],
        }).encode()

    def _stream_span(self, ctx):
        """``owner.stream`` under the span the caller named in its
        metadata (a fresh root when it named none)."""
        carrier = dict(ctx.invocation_metadata()).get(_TRACE_KEY)
        return self.obs.request_span(
            "owner.stream", obs_mod.parse_http_trace(carrier))

    def _handlers(self) -> grpc.GenericRpcHandler:
        def chunk_hash(request: bytes, ctx) -> bytes:
            with self._stream_span(ctx) as sp:
                sp.bytes = len(request)
                return self._chunk_table(self.fragmenter.chunk(request),
                                         len(request))

        def chunk_hash_stream(request_iterator, ctx) -> bytes:
            with self._stream_span(ctx) as sp:
                m = self.fragmenter.manifest_stream(request_iterator,
                                                    name="stream")
                sp.bytes = m.size
                return self._chunk_table(list(m.chunks), m.size)

        def chunk_hash_duplex(request_iterator, ctx):
            """stream-stream: chunk batches flow back AS the fragmenter's
            walk finalizes them, instead of one table at stream end — the
            node tees its body buffer and trims it against these replies,
            which is what makes sidecar-delegated chunked uploads
            bounded-memory on the node side (round-2 advisor finding: the
            stream-unary path forced the node to hold the whole body)."""
            from dfs_tpu.ops.cdc_v2 import file_id_from_digests

            digests: list[str] = []
            size = 0
            # the span stays open across the yields: gRPC runs one call
            # in one thread and one context from first reply to last
            with self._stream_span(ctx) as sp:
                for batch in self.fragmenter.chunks_stream(
                        request_iterator):
                    if not batch:
                        continue
                    size = batch[-1].offset + batch[-1].length
                    digests.extend(c.digest for c in batch)
                    yield json.dumps({
                        "chunks": [{"index": c.index, "offset": c.offset,
                                    "length": c.length, "digest": c.digest}
                                   for c in batch]}).encode()
                sp.bytes = size
            yield json.dumps({
                "done": True, "size": size,
                "fileId": file_id_from_digests(digests),
                "fragmenter": self.fragmenter.name}).encode()

        def health(request: bytes, ctx) -> bytes:
            # "window" = the fragmenter's reporting-lag bound (0 when the
            # backend materializes): teeing duplex clients size their
            # buffer cap from it — see SidecarFragmenter.chunks_stream
            span = self.fragmenter.stream_span()
            try:
                desc = self.fragmenter.describe()
            except NotImplementedError:
                desc = None
            return json.dumps({"ok": True,
                               "fragmenter": self.fragmenter.name,
                               "window": span or 0,
                               "describe": desc,
                               "device": self.fragmenter.device_stats(),
                               "spans": self.obs.span_totals(),
                               "compile": {
                                   **self.compile_clock.snapshot(),
                                   "firstRegionS":
                                       self.fragmenter.first_region_s},
                               }).encode()

        def trace(request: bytes, ctx) -> bytes:
            try:
                q = json.loads(request or b"{}")
                if "traceId" in q:
                    spans = self.obs.spans_for(str(q["traceId"]))
                else:
                    spans = self.obs.spans_between(
                        int(q["sinceMonoNs"]), int(q["untilMonoNs"]))
            except (ValueError, KeyError, TypeError) as e:
                ctx.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          f"Trace wants traceId or sinceMonoNs and "
                          f"untilMonoNs: {e!r}")
            return json.dumps({"spans": spans}).encode()

        methods = {
            f"/{_SERVICE}/ChunkHash": grpc.unary_unary_rpc_method_handler(
                chunk_hash, request_deserializer=_identity,
                response_serializer=_identity),
            f"/{_SERVICE}/ChunkHashStream":
                grpc.stream_unary_rpc_method_handler(
                    chunk_hash_stream, request_deserializer=_identity,
                    response_serializer=_identity),
            f"/{_SERVICE}/ChunkHashDuplex":
                grpc.stream_stream_rpc_method_handler(
                    chunk_hash_duplex, request_deserializer=_identity,
                    response_serializer=_identity),
            f"/{_SERVICE}/Health": grpc.unary_unary_rpc_method_handler(
                health, request_deserializer=_identity,
                response_serializer=_identity),
            f"/{_SERVICE}/Trace": grpc.unary_unary_rpc_method_handler(
                trace, request_deserializer=_identity,
                response_serializer=_identity),
        }

        class Handler(grpc.GenericRpcHandler):
            def service(self, call_details):
                return methods.get(call_details.method)

        return Handler()

    def start(self) -> None:
        self._server.start()

    def stop(self, grace: float = 0.5) -> None:
        self._server.stop(grace)
        self.compile_clock.close()


class SidecarClient:
    """Deadlines are mandatory: an un-deadlined blocking call from the
    node would freeze its entire event loop if the sidecar wedged (a cold
    compile inside a stream runs against ``timeout_s``; warm the owner
    before serving, as chip_smoke.py does)."""

    def __init__(self, port: int, host: str = "127.0.0.1",
                 timeout_s: float = 600.0,
                 health_timeout_s: float = 30.0) -> None:
        self.timeout_s = timeout_s
        self.health_timeout_s = health_timeout_s
        self._channel = grpc.insecure_channel(
            f"{host}:{port}",
            options=[("grpc.max_receive_message_length", 1 << 30),
                     ("grpc.max_send_message_length", 1 << 30)])
        self._chunk_hash = self._channel.unary_unary(
            f"/{_SERVICE}/ChunkHash", request_serializer=_identity,
            response_deserializer=_identity)
        self._chunk_hash_stream = self._channel.stream_unary(
            f"/{_SERVICE}/ChunkHashStream", request_serializer=_identity,
            response_deserializer=_identity)
        self._chunk_hash_duplex = self._channel.stream_stream(
            f"/{_SERVICE}/ChunkHashDuplex", request_serializer=_identity,
            response_deserializer=_identity)
        self._health = self._channel.unary_unary(
            f"/{_SERVICE}/Health", request_serializer=_identity,
            response_deserializer=_identity)
        self._trace = self._channel.unary_unary(
            f"/{_SERVICE}/Trace", request_serializer=_identity,
            response_deserializer=_identity)

    # every chunking call names the caller's current span (if it has
    # one) in its metadata: the owner's spans hang under it

    def chunk_hash(self, data: bytes) -> dict:
        return json.loads(self._chunk_hash(
            data, timeout=self.timeout_s, metadata=_trace_metadata()))

    def chunk_hash_stream(self, blocks) -> dict:
        """Stream byte blocks (any iterable of bytes) — no size ceiling."""
        return json.loads(self._chunk_hash_stream(
            iter(blocks), timeout=self.timeout_s,
            metadata=_trace_metadata()))

    def chunk_hash_duplex(self, blocks):
        """Stream blocks in, iterate chunk-batch dicts out as the sidecar
        finalizes them; the last message is {'done': True, ...}."""
        for msg in self._chunk_hash_duplex(iter(blocks),
                                           timeout=self.timeout_s,
                                           metadata=_trace_metadata()):
            yield json.loads(msg)

    def health(self) -> dict:
        return json.loads(self._health(b"", timeout=self.health_timeout_s))

    def trace(self, **query) -> list[dict]:
        """The owner's ring spans for ``traceId=`` or for
        ``sinceMonoNs=, untilMonoNs=`` (CLOCK_MONOTONIC ns)."""
        return json.loads(self._trace(
            json.dumps(query).encode(),
            timeout=self.health_timeout_s))["spans"]

    def close(self) -> None:
        self._channel.close()


class SidecarFragmenter(Fragmenter):
    """Drop-in Fragmenter that delegates chunk+hash to a sidecar process.

    Keeps device init, XLA compiles, and the GIL-heavy hashing out of the
    node's serving process — the north-star deployment shape ("the
    StorageNode calls the TPU backend over a local gRPC sidecar"). Streams
    in STREAM_BLOCK pieces, so payload size is unbounded on this side too.
    Store-callback streaming (the node's upload_stream path) rides the
    duplex method with a capped tee buffer — bounded node memory; see
    chunks_stream. manifest() comes from the base class (the node runtime
    passes file_id explicitly, so no extra hashing happens there).
    """

    def __init__(self, port: int, host: str = "127.0.0.1") -> None:
        self.client = SidecarClient(port, host=host)
        h = self.client.health()
        self.name = f"sidecar:{h['fragmenter']}"
        # reporting-lag bound of the sidecar's walk; 0 = materializing
        # backend (fixed split) — then the tee cannot be safely capped
        self.stream_window = int(h.get("window") or 0)
        self._describe = h.get("describe")
        # what the tees of this node's streams did, over its life
        # (``tee_stats``): concurrent uploads share one adapter
        self._tee_lock = threading.Lock()
        self._tee_peak = 0
        self._tee_wait_s = 0.0
        self.last_peak_buffer = 0

    def tee_stats(self) -> dict:
        """``/metrics`` ``ingest.seam``: the most bytes any stream's tee
        held (a peak; the cap is 2 x ``stream_window``) and the seconds
        tees waited at the cap — each counted when it happens, not at a
        stream's end."""
        with self._tee_lock:
            return {"teePeakBytes": self._tee_peak,
                    "teeWaitS": round(self._tee_wait_s, 6)}

    def describe(self) -> dict:
        if not self._describe:
            raise NotImplementedError(f"{self.name} is not describable")
        return self._describe

    def _refs(self, resp: dict):
        from dfs_tpu.meta.manifest import ChunkRef

        return tuple(ChunkRef(index=c["index"], offset=c["offset"],
                              length=c["length"], digest=c["digest"])
                     for c in resp["chunks"])

    def chunk(self, data: bytes):
        blocks = (data[i:i + STREAM_BLOCK]
                  for i in range(0, len(data), STREAM_BLOCK))
        return list(self._refs(self.client.chunk_hash_stream(blocks)))

    def chunks_stream(self, blocks, store=None):
        """True streaming delegation over the duplex method: blocks are
        TEED into a local rolling buffer while gRPC's sender thread
        forwards them; each chunk batch the sidecar streams back is
        sliced out of the tee (satisfying ``store``) and the buffer is
        trimmed to the last reported chunk end. Peak node memory is
        therefore ~the sidecar's in-flight window span plus transport
        slack — never the whole body (``last_peak_buffer`` records the
        newest stream's high-water mark, ``tee_stats`` the largest of
        all; tests assert the bound). gRPC flow control
        paces the sender off the sidecar's walk, so TCP backpressure
        still reaches the uploading client end to end."""
        cond = threading.Condition()
        buf = bytearray()
        base = 0                      # absolute offset of buf[0]
        dead = False
        peak = self.last_peak_buffer = 0
        # cap the un-trimmed tee at 2x the sidecar's advertised
        # reporting-lag bound (gRPC's own flow control buffers multiple
        # MB, so without this the tee grows to ~the whole body). 2x the
        # lag bound can never deadlock: the sidecar always makes progress
        # with at most `window` bytes outstanding past the last reported
        # chunk end. A materializing backend advertises 0 -> uncapped.
        budget = 2 * self.stream_window if self.stream_window else None

        def tee():
            nonlocal peak
            for b in blocks:
                bb = bytes(b)
                with cond:
                    t0 = None
                    while (budget is not None and not dead
                           and len(buf) + len(bb) > budget + 2 * len(bb)):
                        t0 = t0 or time.perf_counter()
                        cond.wait(0.2)
                    if t0 is not None:
                        with self._tee_lock:
                            self._tee_wait_s += time.perf_counter() - t0
                    if dead:
                        return
                    buf.extend(bb)
                    if len(buf) > peak:
                        peak = self.last_peak_buffer = len(buf)
                        with self._tee_lock:
                            self._tee_peak = max(self._tee_peak, peak)
                yield bb

        try:
            for msg in self.client.chunk_hash_duplex(tee()):
                if msg.get("done"):
                    return
                refs = self._refs(msg)
                end = refs[-1].offset + refs[-1].length
                payloads = ()
                # a reply is sliced and the tee trimmed under ONE lock:
                # one view over the tee, one copy a chunk, the view
                # released before the trim resizes what it exported
                with cond:
                    if store is not None:
                        with memoryview(buf) as mv:
                            payloads = [
                                bytes(mv[(lo := r.offset - base):
                                         lo + r.length]) for r in refs]
                    if end > base:
                        del buf[:end - base]
                        base = end
                    cond.notify_all()
                for ref, payload in zip(refs, payloads):
                    if len(payload) != ref.length:
                        raise RuntimeError(
                            "sidecar chunk reply outran the teed stream")
                    store(ref.digest, payload)
                yield refs
        finally:
            with cond:
                dead = True           # unblock a tee stuck at the cap
                cond.notify_all()

    def manifest_stream(self, blocks, name: str, store=None):
        from dfs_tpu.meta.manifest import Manifest

        if store is None:
            # metadata-only callers skip the tee copy entirely
            resp = self.client.chunk_hash_stream(blocks)
            return Manifest(file_id=resp["fileId"], name=name,
                            size=resp["size"], fragmenter=self.name,
                            chunks=self._refs(resp))
        return self._manifest_via_chunks_stream(blocks, name, store)

    def close(self) -> None:
        self.client.close()
