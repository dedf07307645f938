"""Cluster chaos harness: real-process nodes + open-loop load + fault
scripting (docs/chaos.md).

The library behind ``bench_chaos.py`` and ``tests/test_chaos.py``:

- :class:`ClusterHarness` — spins N separate ``dfs-tpu serve``
  processes (the reference's operating mode, the same shape
  tests/test_process_cluster.py runs), each booted with ``--chaos`` so
  scenarios can re-script fault knobs live via ``POST /chaos``; knows
  how to ``kill -9`` a node mid-flight and restart it (optionally with
  different flags — e.g. a crash point armed).
- :class:`LoadGen` — open-loop multi-tenant load: a scheduler thread
  issues uploads/downloads at a fixed rate REGARDLESS of completion
  (closed-loop generators throttle themselves exactly when the system
  degrades — hiding the overload the harness exists to provoke), with
  Zipf-distributed read popularity over the acked catalog. Every acked
  upload lands in a ledger keyed by its content hash; ``verify_all``
  later downloads every acked file and checks byte-identity (fileId IS
  sha256(body), so hash equality is byte equality) — the zero
  acked-write-loss invariant, mechanically checked.

Invariant doctrine (ROADMAP item 4): an upload that never acked may
vanish — its chunks are aged-GC orphans. An upload that ACKED (HTTP
201 whose fileId matches the locally computed content hash) must read
back byte-identical from any live node, through every fault this
harness can inject. That asymmetry is what fsync-before-ack buys.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _sha256_hex(data: bytes) -> str:
    from dfs_tpu.utils.hashing import sha256_hex

    return sha256_hex(data)


def _probe_free(port: int) -> bool:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", port))
        return True
    except OSError:
        return False
    finally:
        s.close()


# where a harness looks for its ports: BELOW the kernel's ephemeral
# range (32768-60999 here). A node process takes seconds to reach its
# bind, and a port the kernel handed out as "free" from that range was
# taken meanwhile as the source port of some other test's outgoing
# connection — "address already in use", a node dead at start-up, 2 of
# 361 tests in one parallel run. Each process walks its own lane of the
# range (by pid), so concurrent harnesses do not probe the same run.
_PORT_LO, _PORT_HI, _PORT_LANES = 10240, 32768, 64
_next_port = 0


def contiguous_free_ports(n: int) -> int:
    """cmd_serve derives peer ports as base+i; find a free run of n."""
    global _next_port
    lane = (_PORT_HI - _PORT_LO) // _PORT_LANES
    lo = _PORT_LO + os.getpid() % _PORT_LANES * lane
    for _ in range(lane):
        base = lo + _next_port % (lane - n)
        _next_port += n
        if all(_probe_free(base + i) for i in range(n)):
            return base
    raise RuntimeError("no contiguous free port run found")


class HarnessError(AssertionError):
    """A scenario precondition/invariant the harness could not meet."""


class ClusterHarness:
    """N real ``dfs-tpu serve`` processes with the chaos plane armed."""

    def __init__(self, n: int, workdir: Path, rf: int = 2,
                 repair_interval_s: float = 1.0,
                 extra_flags: list[str] | None = None,
                 chaos: bool = True, env: dict | None = None) -> None:
        self.n = n
        self.rf = rf
        self.workdir = Path(workdir)
        self.workdir.mkdir(parents=True, exist_ok=True)
        base = contiguous_free_ports(2 * n)
        self.base_http = base
        self.base_internal = base + n
        self.repair_interval_s = repair_interval_s
        self.extra_flags = list(extra_flags or [])
        self.chaos = chaos
        self.env = {**os.environ, "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": str(REPO), **(env or {})}
        self.procs: dict[int, subprocess.Popen] = {}
        # per-node flag overrides applied at (re)start — scenarios arm
        # crash points by restarting a node with different flags
        self._node_flags: dict[int, list[str]] = {}

    # ---- lifecycle --------------------------------------------------- #

    def http_port(self, node_id: int) -> int:
        return self.base_http + node_id - 1

    def _argv(self, node_id: int) -> list[str]:
        argv = [sys.executable, "-m", "dfs_tpu.cli.main", "serve",
                "--node-id", str(node_id), "--nodes", str(self.n),
                "--base-port", str(self.base_http),
                "--base-internal-port", str(self.base_internal),
                "--replication-factor", str(self.rf),
                "--fragmenter", "cdc",
                "--data-root", str(self.workdir / "data"),
                "--repair-interval", str(self.repair_interval_s),
                "--probe-interval", "2"]
        if self.chaos:
            argv += ["--chaos"]
        argv += self.extra_flags
        argv += self._node_flags.get(node_id, [])
        return argv

    def start(self, node_id: int,
              extra_flags: list[str] | None = None) -> None:
        if extra_flags is not None:
            self._node_flags[node_id] = list(extra_flags)
        log = (self.workdir / f"node{node_id}.log").open("ab")
        self.procs[node_id] = subprocess.Popen(
            self._argv(node_id), cwd=self.workdir, env=self.env,
            stdout=log, stderr=subprocess.STDOUT)

    def start_all(self) -> None:
        for i in range(1, self.n + 1):
            self.start(i)

    def wait_ready(self, node_ids=None, timeout: float = 90.0,
                   respawns: int = 2) -> None:
        deadline = time.time() + timeout
        for i in (node_ids or range(1, self.n + 1)):
            tries = 0
            while True:
                p = self.procs.get(i)
                if p is not None and p.poll() is not None:
                    tail = self.node_log(i)[-2000:]
                    if "address already in use" in tail \
                            and tries < respawns:
                        # something took the port between the probe
                        # and the node's bind (harness ports lie below
                        # the ephemeral range, so not an outbound
                        # connection: another harness on this lane).
                        # Re-spawn after a beat (same flags).
                        tries += 1
                        time.sleep(1.5)
                        self.start(i)
                        continue
                    raise HarnessError(
                        f"node {i} died during startup: " + tail)
                try:
                    status, body = self.http(i, "GET", "/status",
                                             timeout=2)
                    if status == 200 and body == b"OK":
                        break
                except OSError:
                    pass
                if time.time() > deadline:
                    raise HarnessError(f"node {i} never came up: "
                                       + self.node_log(i)[-2000:])
                time.sleep(0.2)

    def kill9(self, node_id: int) -> None:
        """kill -9: no shutdown path runs — what fsync-before-ack must
        survive. Idempotent on an already-dead node."""
        p = self.procs.get(node_id)
        if p is None or p.poll() is not None:
            return
        os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=10)

    def wait_dead(self, node_id: int, timeout: float = 30.0) -> int:
        """Block until the node process exits (a crash point firing);
        returns the negative signal number / exit code."""
        p = self.procs[node_id]
        return p.wait(timeout=timeout)

    def restart(self, node_id: int,
                extra_flags: list[str] | None = None,
                timeout: float = 90.0, attempts: int = 3) -> None:
        flags = extra_flags if extra_flags is not None else []
        for a in range(attempts):
            self.kill9(node_id)
            self.start(node_id, extra_flags=flags)
            try:
                self.wait_ready([node_id], timeout=timeout)
                return
            except HarnessError:
                # the reborn node died at start-up (its port taken
                # while it was dead, or a slow host): wait a beat and
                # re-spawn.
                if a + 1 >= attempts:
                    raise
                time.sleep(1.5)

    def stop_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    def node_log(self, node_id: int) -> str:
        try:
            return (self.workdir / f"node{node_id}.log").read_text(
                errors="replace")
        except OSError:
            return ""

    # ---- HTTP -------------------------------------------------------- #

    def http(self, node_id: int, method: str, path: str,
             body: bytes | None = None, headers: dict | None = None,
             timeout: float = 60.0) -> tuple[int, bytes]:
        """One HTTP request to a node; HTTP errors return (status,
        body) instead of raising — a 503/507 is scenario DATA, not a
        harness failure. Transport errors (dead node) raise OSError."""
        status, data, _ = self.http_h(node_id, method, path, body=body,
                                      headers=headers, timeout=timeout)
        return status, data

    def http_h(self, node_id: int, method: str, path: str,
               body: bytes | None = None, headers: dict | None = None,
               timeout: float = 60.0) -> tuple[int, bytes, dict]:
        """:meth:`http` plus the response headers (lower-cased keys) —
        scenarios that honor ``Retry-After`` need them."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http_port(node_id)}{path}",
            data=body, method=method, headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read(), \
                    {k.lower(): v for k, v in r.headers.items()}
        except urllib.error.HTTPError as e:
            return e.code, e.read(), \
                {k.lower(): v for k, v in e.headers.items()}

    def get_json(self, node_id: int, path: str,
                 timeout: float = 60.0) -> dict:
        status, body = self.http(node_id, "GET", path, timeout=timeout)
        if status != 200:
            raise HarnessError(f"GET {path} on node {node_id} -> "
                               f"{status}: {body[:200]!r}")
        return json.loads(body)

    def set_chaos(self, node_id: int, **knobs) -> dict:
        status, body = self.http(
            node_id, "POST", "/chaos",
            body=json.dumps(knobs).encode(),
            headers={"Content-Type": "application/json"}, timeout=30)
        if status != 200:
            raise HarnessError(f"POST /chaos on node {node_id} -> "
                               f"{status}: {body[:200]!r}")
        return json.loads(body)

    def metrics(self, node_id: int) -> dict:
        return self.get_json(node_id, "/metrics")

    # ---- membership ring (docs/membership.md) ------------------------ #

    def ring_status(self, node_id: int, cluster: bool = False) -> dict:
        return self.get_json(
            node_id, f"/ring?cluster={'1' if cluster else '0'}")

    def ring_post(self, node_id: int, **body) -> dict:
        """POST /ring membership change on one node (it pushes the new
        epoch cluster-wide and kicks the rebalancer)."""
        status, resp = self.http(
            node_id, "POST", "/ring", body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, timeout=60)
        if status != 200:
            raise HarnessError(f"POST /ring on node {node_id} -> "
                               f"{status}: {resp[:200]!r}")
        return json.loads(resp)

    def wait_ring_converged(self, epoch: int, node_ids=None,
                            timeout: float = 90.0) -> None:
        """Block until every named node reports the epoch AND has
        closed its migration window (rebalance_done) — the moment
        dual-read ends and placement is steady-state again."""
        deadline = time.time() + timeout
        pending = list(node_ids or range(1, self.n + 1))
        while pending and time.time() < deadline:
            still = []
            for i in pending:
                try:
                    st = self.ring_status(i)
                    if st.get("epoch") != epoch or st.get("migrating"):
                        still.append(i)
                except (OSError, HarnessError):
                    still.append(i)
            pending = still
            if pending:
                time.sleep(0.5)
        if pending:
            raise HarnessError(
                f"nodes {pending} never converged to ring epoch "
                f"{epoch} within {timeout}s: "
                + "; ".join(self.node_log(i)[-500:] for i in pending))

    def census(self, node_id: int) -> dict:
        return self.get_json(node_id, "/census", timeout=120)

    def doctor(self, node_id: int) -> dict:
        return self.get_json(node_id, "/doctor", timeout=120)

    def trace(self, node_id: int, trace_id: str) -> dict:
        return self.get_json(node_id, f"/trace?traceId={trace_id}")

    def wait_census_clean(self, node_id: int, timeout: float = 60.0,
                          require_no_orphans: bool = True) -> dict:
        """Poll /census until the repair loop has converged the data
        plane: no under-/over-replication, all peers answering (and,
        unless the scenario aborted uploads, no orphans). Returns the
        final report either way — the caller gates on it."""
        deadline = time.time() + timeout
        rep: dict = {}
        while time.time() < deadline:
            try:
                rep = self.census(node_id)
            except (OSError, HarnessError):
                time.sleep(1.0)
                continue
            clean = (rep.get("peersFailed", 1) == 0
                     and rep.get("underReplicatedTotal", 1) == 0
                     and rep.get("overReplicatedTotal", 1) == 0
                     and (not require_no_orphans
                          or rep.get("orphanedTotal", 1) == 0))
            if clean:
                return rep
            time.sleep(1.0)
        return rep


class LoadGen:
    """Open-loop, multi-tenant Zipf load against a ClusterHarness.

    A scheduler thread fires one operation every ``1/rate_per_s``
    seconds into a worker pool, never waiting for completions (open
    loop: offered load is independent of system health). Uploads carry
    fresh pseudo-random payloads; the ack ledger records
    ``fileId == sha256(payload)`` — an ack whose fileId does NOT match
    the locally computed hash is counted as a corruption, not an ack.
    Downloads pick a ledger entry with Zipf(popularity by recency) and
    verify the body hashes to its fileId. Status-code counts are kept
    per class so a scenario can assert e.g. "zero 503s" or "507s only
    on the disk-full node"."""

    # Retry-After discipline (docs/chaos.md): a 503-shed op is retried
    # AFTER the server-advertised budget with DECORRELATED JITTER —
    # sleep_n = min(CAP, uniform(retry_after, 3 x sleep_{n-1})). An
    # immediate retry would turn one shed into a synchronized retry
    # storm: every shed client re-arriving together is exactly the
    # thundering herd the 503 was trying to disperse.
    RETRY_503_MAX = 2          # retries per op beyond the first attempt
    RETRY_503_CAP_S = 10.0     # worst-case single backoff sleep

    def __init__(self, harness: ClusterHarness, payload_bytes: int,
                 rate_per_s: float = 6.0, tenants: int = 3,
                 upload_fraction: float = 0.5, seed: int = 1234,
                 upload_nodes=None, download_nodes=None,
                 op_timeout_s: float = 60.0,
                 retry_503: int | None = None) -> None:
        import random as _random

        self.h = harness
        self.payload_bytes = payload_bytes
        self.interval = 1.0 / rate_per_s
        self.tenants = tenants
        self.upload_fraction = upload_fraction
        self.op_timeout_s = op_timeout_s
        self.retry_503 = self.RETRY_503_MAX if retry_503 is None \
            else int(retry_503)
        self._rng = _random.Random(seed)
        # injectable for tests: the Retry-After backoff sleeps through
        # this, so a unit test can record delays instead of waiting
        self._sleep = time.sleep
        self._nodes_up = list(upload_nodes
                              or range(1, harness.n + 1))
        self._nodes_down = list(download_nodes
                                or range(1, harness.n + 1))
        self._lock = threading.Lock()
        self.ledger: list[dict] = []      # acked: {fileId, size, node}
        self.stats = {"uploads_attempted": 0, "uploads_acked": 0,
                      "uploads_failed": 0, "ack_hash_mismatch": 0,
                      "downloads_attempted": 0, "downloads_ok": 0,
                      "downloads_failed": 0, "download_mismatch": 0,
                      "retries_503": 0,
                      "status": {}}
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        self._seq = 0

    def _jitter_503(self, retry_after_s: float,
                    prev_s: float | None) -> float:
        """Next decorrelated-jitter sleep after a 503 — delegates to
        the ONE module-level rule (:func:`_decorrelated_503_sleep`) so
        the threaded and multi-process generators cannot silently
        diverge on the retry discipline."""
        with self._lock:
            return _decorrelated_503_sleep(self._rng, retry_after_s,
                                           prev_s,
                                           cap_s=self.RETRY_503_CAP_S)

    def _request_with_503_retry(self, node: int, method: str, path: str,
                                body: bytes | None = None,
                                headers: dict | None = None
                                ) -> tuple[int, bytes]:
        """One op with Retry-After-honoring 503 retries. Raises OSError
        on transport failure exactly like :meth:`ClusterHarness.http`."""
        prev: float | None = None
        for attempt in range(1 + self.retry_503):
            status, data, hdrs = self.h.http_h(
                node, method, path, body=body, headers=headers,
                timeout=self.op_timeout_s)
            if status != 503 or attempt >= self.retry_503:
                return status, data
            self._count_status(503)   # retried sheds still show in the
            # per-status table; the caller counts the FINAL status
            try:
                ra = float(hdrs.get("retry-after", 1.0))
            except ValueError:
                ra = 1.0
            with self._lock:
                self.stats["retries_503"] += 1
            prev = self._jitter_503(ra, prev)
            self._sleep(prev)
        return status, data

    # ---- ops --------------------------------------------------------- #

    def _payload(self, tenant: int, seq: int) -> bytes:
        import numpy as np

        rng = np.random.default_rng((tenant << 32) ^ seq ^ 0xC4A05)
        return rng.integers(0, 256, size=self.payload_bytes,
                            dtype=np.uint8).tobytes()

    def _count_status(self, status: int) -> None:
        with self._lock:
            key = str(status)
            self.stats["status"][key] = \
                self.stats["status"].get(key, 0) + 1

    def _upload_once(self, tenant: int, seq: int, node: int,
                     trace_id: str | None = None) -> dict | None:
        data = self._payload(tenant, seq)
        want = _sha256_hex(data)
        with self._lock:
            self.stats["uploads_attempted"] += 1
        headers = {"Content-Type": "application/octet-stream"}
        if trace_id is not None:
            headers["X-Dfs-Trace"] = f"{trace_id}-{os.urandom(8).hex()}"
        try:
            status, body = self._request_with_503_retry(
                node, "POST", f"/upload?name=t{tenant}%2Ff{seq}.bin",
                body=data, headers=headers)
        except OSError:
            with self._lock:
                self.stats["uploads_failed"] += 1
            return None
        self._count_status(status)
        if status != 201:
            with self._lock:
                self.stats["uploads_failed"] += 1
            return None
        info = json.loads(body)
        if info.get("fileId") != want:
            # the server acked bytes OTHER than what was sent — a
            # corruption-class failure, never a mere op error
            with self._lock:
                self.stats["ack_hash_mismatch"] += 1
            return None
        entry = {"fileId": want, "size": len(data), "node": node,
                 "tenant": tenant}
        with self._lock:
            self.stats["uploads_acked"] += 1
            self.ledger.append(entry)
        return entry

    def _download_once(self, entry: dict, node: int) -> bool:
        with self._lock:
            self.stats["downloads_attempted"] += 1
        try:
            status, body = self._request_with_503_retry(
                node, "GET", f"/download?fileId={entry['fileId']}")
        except OSError:
            with self._lock:
                self.stats["downloads_failed"] += 1
            return False
        self._count_status(status)
        if status != 200:
            with self._lock:
                self.stats["downloads_failed"] += 1
            return False
        if len(body) != entry["size"] \
                or _sha256_hex(body) != entry["fileId"]:
            with self._lock:
                self.stats["download_mismatch"] += 1
            return False
        with self._lock:
            self.stats["downloads_ok"] += 1
        return True

    def _pick_zipf(self) -> dict | None:
        """Zipf-by-recency over the acked catalog: rank 1 = newest,
        p(rank) ∝ 1/rank^1.2 — the hot-head/long-tail read mix."""
        with self._lock:
            n = len(self.ledger)
            if n == 0:
                return None
            weights = [1.0 / (r ** 1.2) for r in range(1, n + 1)]
            total = sum(weights)
            x = self._rng.random() * total
            acc = 0.0
            for rank, w in enumerate(weights, 1):
                acc += w
                if x <= acc:
                    return self.ledger[n - rank]
            return self.ledger[0]

    # ---- open loop --------------------------------------------------- #

    def _one_op(self) -> None:
        if self._rng.random() < self.upload_fraction or not self.ledger:
            tenant = self._rng.randrange(self.tenants)
            with self._lock:
                self._seq += 1
                seq = self._seq
            self._upload_once(tenant, seq,
                              self._rng.choice(self._nodes_up))
        else:
            entry = self._pick_zipf()
            if entry is not None:
                self._download_once(entry,
                                    self._rng.choice(self._nodes_down))

    def run_for(self, seconds: float) -> None:
        """Open-loop burst: fire ops on schedule for ``seconds``, then
        wait for the in-flight stragglers."""
        deadline = time.time() + seconds
        while time.time() < deadline and not self._stop.is_set():
            t = threading.Thread(target=self._one_op, daemon=True)
            t.start()
            self._threads.append(t)
            time.sleep(self.interval)
        self.drain()

    def drain(self, timeout: float = 120.0) -> None:
        deadline = time.time() + timeout
        for t in self._threads:
            t.join(timeout=max(0.1, deadline - time.time()))
        self._threads = [t for t in self._threads if t.is_alive()]

    # ---- invariants -------------------------------------------------- #

    def verify_all(self, nodes=None, timeout_per_file: float = 60.0
                   ) -> dict:
        """THE invariant: every acked upload downloads byte-identical
        (sha256(body) == fileId) from a live node. Returns
        {checked, ok, lost: [fileIds]}. Verification reads go through
        ``_download_once`` so they keep counting into this generator's
        stats (the r13 artifact shape)."""
        with self._lock:
            entries = list(self.ledger)
        return verify_ledger(self.h, entries, nodes=nodes,
                             timeout_per_file=timeout_per_file,
                             download=self._download_once)

    def snapshot(self) -> dict:
        with self._lock:
            out = json.loads(json.dumps(self.stats))
            out["acked"] = len(self.ledger)
        return out


def verify_ledger(harness: ClusterHarness, ledger: list[dict],
                  nodes=None, timeout_per_file: float = 60.0,
                  download=None) -> dict:
    """THE acked-write invariant, in ONE place for every generator:
    each ledger entry must download byte-identical (status 200, exact
    size, sha256(body) == fileId) from a live node, with one retry on
    a different node before declaring loss — readable from the
    CLUSTER, not from the first node asked. ``download(entry, node) ->
    bool`` overrides the check (the threaded LoadGen counts its
    verification reads into its own stats); the default is a
    stats-neutral direct probe."""
    node_list = list(nodes or range(1, harness.n + 1))

    def direct(entry: dict, node: int) -> bool:
        try:
            status, body = harness.http(
                node, "GET", f"/download?fileId={entry['fileId']}",
                timeout=timeout_per_file)
        except OSError:
            return False
        return (status == 200 and len(body) == entry["size"]
                and _sha256_hex(body) == entry["fileId"])

    check = download if download is not None else direct
    lost: list[str] = []
    for i, entry in enumerate(ledger):
        if not (check(entry, node_list[i % len(node_list)])
                or check(entry, node_list[(i + 1) % len(node_list)])):
            lost.append(entry["fileId"])
    return {"checked": len(ledger),
            "ok": len(ledger) - len(lost), "lost": lost}


# ------------------------------------------------------------------ #
# multi-process open-loop overload generator (docs/chaos.md §overload)
# ------------------------------------------------------------------ #

def _decorrelated_503_sleep(rng, retry_after_s: float,
                            prev_s: float | None,
                            cap_s: float = 10.0) -> float:
    """THE Retry-After jitter rule, shared by the threaded LoadGen and
    the open-loop worker processes: at least the advertised budget, at
    most 3x the previous sleep (Brooker, "Exponential Backoff And
    Jitter"), capped — an immediate retry would re-arrive exactly with
    every other shed client."""
    base = max(0.0, retry_after_s)
    hi = 3.0 * (prev_s if prev_s is not None else base)
    return min(cap_s, rng.uniform(base, max(base, hi)))


async def _aio_http(port: int, method: str, path: str,
                    body: bytes | None = None,
                    headers: dict | None = None,
                    timeout: float = 60.0) -> tuple[int, bytes, dict]:
    """Minimal asyncio HTTP/1.1 client for the open-loop worker: one
    connection per request (the node answers ``Connection: close``),
    thousands may be in flight as coroutines — the thread-per-op
    LoadGen topped out orders of magnitude below genuine overload."""
    import asyncio

    async def go() -> tuple[int, bytes, dict]:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            head = [f"{method} {path} HTTP/1.1",
                    "Host: 127.0.0.1", "Connection: close"]
            for k, v in (headers or {}).items():
                head.append(f"{k}: {v}")
            if body is not None:
                head.append(f"Content-Length: {len(body)}")
            writer.write(("\r\n".join(head) + "\r\n\r\n").encode())
            if body:
                writer.write(body)
            await writer.drain()
            status_line = await reader.readline()
            parts = status_line.split()
            if len(parts) < 2:
                raise ConnectionResetError("bad status line")
            status = int(parts[1])
            hdrs: dict[str, str] = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                if b":" in line:
                    k, v = line.split(b":", 1)
                    hdrs[k.strip().lower().decode("latin-1")] = \
                        v.strip().decode("latin-1")
            cl = hdrs.get("content-length")
            data = await reader.readexactly(int(cl)) if cl \
                else await reader.read(-1)
            return status, data, hdrs
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    return await asyncio.wait_for(go(), timeout)


def _worker_payload(payload_bytes: int, tenant: int, seq: int) -> bytes:
    import numpy as np

    rng = np.random.default_rng((tenant << 32) ^ seq ^ 0xC4A05)
    return rng.integers(0, 256, size=payload_bytes,
                        dtype=np.uint8).tobytes()


async def _load_worker(spec: dict) -> dict:
    """One open-loop worker process: ops are SCHEDULED at the offered
    rate regardless of completions (the open-loop contract — a
    closed-loop generator throttles itself exactly when the system
    degrades, hiding the overload this exists to provoke), each op a
    coroutine, thousands concurrently in flight. 503s are retried
    after the advertised Retry-After with decorrelated jitter. Returns
    {stats, ledger, latencies} — the parent aggregates across workers."""
    import asyncio
    import random as _random

    rng = _random.Random(spec["seed"])
    rate = float(spec["rate_per_s"])
    interval = 1.0 / rate
    payload_bytes = int(spec["payload_bytes"])
    tenants = int(spec["tenants"])
    upload_fraction = float(spec["upload_fraction"])
    ports = {int(k): int(v) for k, v in spec["ports"].items()}
    up_nodes = [int(n) for n in spec["upload_nodes"]]
    down_nodes = [int(n) for n in spec["download_nodes"]]
    op_timeout = float(spec["op_timeout_s"])
    deadline_s = spec.get("deadline_s")
    retry_503 = int(spec.get("retry_503", 2))
    max_inflight = int(spec.get("max_inflight", 2000))
    worker_id = int(spec.get("worker_id", 0))

    stats = {"uploads_attempted": 0, "uploads_acked": 0,
             "uploads_failed": 0, "ack_hash_mismatch": 0,
             "downloads_attempted": 0, "downloads_ok": 0,
             "downloads_failed": 0, "download_mismatch": 0,
             "retries_503": 0, "transport_errors": 0,
             "overflow_dropped": 0, "abandoned": 0,
             "inflight_peak": 0, "status": {}}
    ledger: list[dict] = []
    # latency of the SUCCESSFUL attempt only (per-attempt clock reset):
    # the goodput-SLO gate judges what ADMITTED requests experienced —
    # shed-and-retried time is the client's backoff, not server goodput
    latencies: dict[str, list[float]] = {"upload": [], "download": []}

    def count_status(status) -> None:
        key = str(status)
        stats["status"][key] = stats["status"].get(key, 0) + 1

    async def request(node: int, method: str, path: str,
                      body: bytes | None = None) -> tuple[int, bytes,
                                                          float]:
        """-> (status, body, last_attempt_seconds); honors Retry-After
        on 503 with decorrelated jitter. OSError-class on transport
        failure, like the threaded LoadGen."""
        headers = {}
        if deadline_s is not None:
            headers["X-Dfs-Deadline"] = f"{deadline_s:g}"
        prev: float | None = None
        for attempt in range(1 + retry_503):
            t0 = time.monotonic()
            status, data, hdrs = await _aio_http(
                ports[node], method, path, body=body, headers=headers,
                timeout=op_timeout)
            took = time.monotonic() - t0
            if status != 503 or attempt >= retry_503:
                return status, data, took
            count_status(503)
            try:
                ra = float(hdrs.get("retry-after", 1.0))
            except ValueError:
                ra = 1.0
            stats["retries_503"] += 1
            prev = _decorrelated_503_sleep(rng, ra, prev)
            await asyncio.sleep(prev)
        return status, data, took

    async def upload_once(tenant: int, seq: int, node: int) -> None:
        data = _worker_payload(payload_bytes, tenant, seq)
        want = _sha256_hex(data)
        stats["uploads_attempted"] += 1
        try:
            status, body, took = await request(
                node, "POST", f"/upload?name=t{tenant}%2Ff{seq}.bin",
                body=data)
        except (OSError, asyncio.TimeoutError, EOFError,
                asyncio.IncompleteReadError):
            stats["uploads_failed"] += 1
            stats["transport_errors"] += 1
            return
        count_status(status)
        if status != 201:
            stats["uploads_failed"] += 1
            return
        info = json.loads(body)
        if info.get("fileId") != want:
            stats["ack_hash_mismatch"] += 1
            return
        stats["uploads_acked"] += 1
        ledger.append({"fileId": want, "size": len(data),
                       "node": node, "tenant": tenant})
        latencies["upload"].append(took)

    async def download_once(entry: dict, node: int) -> None:
        stats["downloads_attempted"] += 1
        try:
            status, body, took = await request(
                node, "GET", f"/download?fileId={entry['fileId']}")
        except (OSError, asyncio.TimeoutError, EOFError,
                asyncio.IncompleteReadError):
            stats["downloads_failed"] += 1
            stats["transport_errors"] += 1
            return
        count_status(status)
        if status != 200:
            stats["downloads_failed"] += 1
            return
        if len(body) != entry["size"] \
                or _sha256_hex(body) != entry["fileId"]:
            stats["download_mismatch"] += 1
            return
        stats["downloads_ok"] += 1
        latencies["download"].append(took)

    def pick_zipf() -> dict | None:
        n = len(ledger)
        if n == 0:
            return None
        # rank 1 = newest; p(rank) ∝ 1/rank^1.2 — the LoadGen mix, but
        # sampled in O(1) via the continuous Pareto inverse (the
        # threaded LoadGen builds an O(acked) weight table per op,
        # which an open loop firing thousands of ops/s cannot afford)
        rank = min(n, int(rng.paretovariate(0.2)))
        return ledger[n - max(1, rank)]

    inflight: set = set()
    seq = worker_id << 24   # distinct payload/tenant space per worker

    async def one_op() -> None:
        nonlocal seq
        if rng.random() < upload_fraction or not ledger:
            seq += 1
            await upload_once(rng.randrange(tenants) + worker_id * 1000,
                              seq, rng.choice(up_nodes))
        else:
            entry = pick_zipf()
            if entry is not None:
                await download_once(entry, rng.choice(down_nodes))

    loop_end = time.monotonic() + float(spec["seconds"])
    next_fire = time.monotonic()
    while time.monotonic() < loop_end:
        # offered-rate pacing: the next op fires on the SCHEDULE, not
        # on completions — in-flight count grows with server latency
        if len(inflight) >= max_inflight:
            stats["overflow_dropped"] += 1   # honest accounting: an
            # offered op the bounded generator could not carry
        else:
            t = asyncio.ensure_future(one_op())
            inflight.add(t)
            t.add_done_callback(inflight.discard)
            stats["inflight_peak"] = max(stats["inflight_peak"],
                                         len(inflight))
        # behind schedule: fire the NEXT op immediately but never
        # "catch up" by bursting the backlog — a 2 s loop stall at
        # 500 ops/s would otherwise discharge ~1000 ops in one tick,
        # a synthetic thundering herd the offered-rate contract (and
        # the shed/latency artifacts gated on it) must not contain
        next_fire = max(next_fire + interval, time.monotonic())
        delay = next_fire - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)

    drain_end = time.monotonic() + float(spec.get("drain_s", 30.0))
    while inflight and time.monotonic() < drain_end:
        await asyncio.wait(set(inflight), timeout=1.0)
    for t in list(inflight):
        t.cancel()
        stats["abandoned"] += 1
    if inflight:
        await asyncio.gather(*inflight, return_exceptions=True)
    latencies["upload"].sort()
    latencies["download"].sort()
    # bounded artifact: the percentile math needs the sorted sample,
    # not every point — cap what crosses the process boundary
    cap = 20000
    return {"stats": stats, "ledger": ledger,
            "latencies": {k: v[:: max(1, len(v) // cap)]
                          for k, v in latencies.items()}}


def load_worker_main(spec_path: str) -> int:
    """CLI entry for one worker process:
    ``python -m scripts.chaos_harness --load-worker <spec.json>``."""
    import asyncio

    spec = json.loads(Path(spec_path).read_text())
    result = asyncio.run(_load_worker(spec))
    Path(spec["out"]).write_text(json.dumps(result))
    return 0


def percentile(sorted_xs: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted sample (0 when
    empty — callers gate on sample size first)."""
    if not sorted_xs:
        return 0.0
    i = min(len(sorted_xs) - 1, max(0, int(q * len(sorted_xs))))
    return sorted_xs[i]


class ProcLoadGen:
    """Multi-PROCESS open-loop load: K worker processes, each an
    asyncio open loop firing ops at ``rate_per_s / K`` with thousands
    of in-flight simulated tenants, paced by OFFERED RATE, never by
    completions. This is what drives genuine overload: the threaded
    LoadGen's thread-per-op model exhausts a small host's threads right
    when the system slows down — exactly when offered load must keep
    coming. Same ack-ledger/byte-identity doctrine as LoadGen; the
    parent aggregates worker ledgers and runs verify_all itself."""

    def __init__(self, harness: ClusterHarness, payload_bytes: int,
                 rate_per_s: float, procs: int = 3, tenants: int = 64,
                 upload_fraction: float = 0.5, seed: int = 77,
                 upload_nodes=None, download_nodes=None,
                 op_timeout_s: float = 30.0,
                 deadline_s: float | None = None, retry_503: int = 2,
                 max_inflight: int = 2000,
                 workdir: Path | None = None) -> None:
        self.h = harness
        self.procs = max(1, int(procs))
        self.spec = {
            "payload_bytes": payload_bytes,
            "rate_per_s": rate_per_s / self.procs,
            "tenants": tenants, "upload_fraction": upload_fraction,
            "ports": {i: harness.http_port(i)
                      for i in range(1, harness.n + 1)},
            "upload_nodes": list(upload_nodes
                                 or range(1, harness.n + 1)),
            "download_nodes": list(download_nodes
                                   or range(1, harness.n + 1)),
            "op_timeout_s": op_timeout_s, "deadline_s": deadline_s,
            "retry_503": retry_503, "max_inflight": max_inflight,
        }
        self.seed = seed
        self.workdir = Path(workdir or harness.workdir) / "loadgen"
        self.workdir.mkdir(parents=True, exist_ok=True)
        self.stats: dict = {}
        self.ledger: list[dict] = []
        self.latencies: dict[str, list[float]] = {"upload": [],
                                                  "download": []}

    def run_for(self, seconds: float, drain_s: float = 30.0) -> dict:
        """Run the fleet for ``seconds`` of offered load (plus drain),
        blocking; aggregates worker results into self.stats/ledger/
        latencies and returns the merged stats."""
        procs: list[tuple[subprocess.Popen, Path]] = []
        for w in range(self.procs):
            spec = dict(self.spec)
            spec.update(seconds=seconds, drain_s=drain_s,
                        seed=self.seed + 1000 * w, worker_id=w,
                        out=str(self.workdir / f"worker{w}.out.json"))
            spec_path = self.workdir / f"worker{w}.spec.json"
            spec_path.write_text(json.dumps(spec))
            log = (self.workdir / f"worker{w}.log").open("ab")
            procs.append((subprocess.Popen(
                [sys.executable, "-m", "scripts.chaos_harness",
                 "--load-worker", str(spec_path)],
                cwd=REPO, env={**os.environ, "PYTHONPATH": str(REPO)},
                stdout=log, stderr=subprocess.STDOUT), Path(spec["out"])))
        merged: dict = {"status": {}}
        deadline_t = time.time() + seconds + drain_s + 60.0
        for w, (p, out_path) in enumerate(procs):
            try:
                p.wait(timeout=max(5.0, deadline_t - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
            if not out_path.is_file():
                raise HarnessError(
                    f"load worker {w} died without a result: "
                    + (self.workdir / f"worker{w}.log").read_text(
                        errors="replace")[-2000:])
            res = json.loads(out_path.read_text())
            for k, v in res["stats"].items():
                if k == "status":
                    for s, n in v.items():
                        merged["status"][s] = \
                            merged["status"].get(s, 0) + n
                elif k == "inflight_peak":
                    merged[k] = max(merged.get(k, 0), v)
                else:
                    merged[k] = merged.get(k, 0) + v
            self.ledger.extend(res["ledger"])
            for k in self.latencies:
                self.latencies[k].extend(res["latencies"].get(k, []))
        for k in self.latencies:
            self.latencies[k].sort()
        merged["acked"] = len(self.ledger)
        self.stats = merged
        return merged

    def latency_percentiles(self, kind: str) -> dict:
        xs = self.latencies.get(kind, [])
        return {"n": len(xs),
                "p50": round(percentile(xs, 0.50), 4),
                "p95": round(percentile(xs, 0.95), 4),
                "p99": round(percentile(xs, 0.99), 4)}

    def verify_all(self, nodes=None) -> dict:
        """THE invariant, the one :func:`verify_ledger` rule: every
        acked upload downloads byte-identical from a live node (one
        retry on a second node)."""
        return verify_ledger(self.h, self.ledger, nodes=nodes,
                             timeout_per_file=120.0)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--load-worker":
        sys.exit(load_worker_main(sys.argv[2]))
    print("usage: python -m scripts.chaos_harness --load-worker "
          "<spec.json>", file=sys.stderr)
    sys.exit(2)
