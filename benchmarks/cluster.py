"""The deployment a cell runs on: one chip owner and N storage nodes on
this host, started through the program's real command line, tracked by
``Popen`` object and stopped in ``finally``.

Child handling, the free-port search and the readiness polling are
copied from ``chip_smoke.py`` (PR 21), which stays as it is. Nothing
here imports the program or JAX: the owner holds the chip, and a parent
that touched JAX would hold it instead.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


class BenchFailure(Exception):
    """The run cannot give a result: no result line, non-zero exit."""


class Child:
    def __init__(self, name: str, argv: list[str], env: dict,
                 log_dir: Path, stdin=None) -> None:
        self.name = name
        self.log_path = log_dir / f"{name}.log"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdin=stdin or subprocess.DEVNULL,
            stdout=self._log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def tail(self, lines: int = 30) -> str:
        return "\n".join(self.log_text().splitlines()[-lines:])

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(f"{self.name} exited with code {rc}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        self._log.close()


def compile_cache_dir() -> Path:
    """Where the owner keeps compiled programs: the environment's choice,
    else the fixed ``.jax_cache/`` of the checkout — the same rule as
    ``dfs_tpu.utils.device.compile_cache_dir`` (the path is part of the
    cache's key, so it is never made from a temp name or a pid)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else REPO / ".jax_cache"


def child_env(jax_platforms: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = jax_platforms
    env["JAX_COMPILATION_CACHE_DIR"] = str(compile_cache_dir())
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def wait_for(what: str, probe, children: list[Child], limit_s: float):
    """Poll ``probe()`` (a value, or None for "not yet") until it
    answers, a child dies, or ``limit_s`` passes."""
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        for c in children:
            c.check_alive()
        got = probe()
        if got is not None:
            return got
        time.sleep(0.1)
    raise BenchFailure(f"timed out after {limit_s:.0f}s waiting for {what}")


def free_port_base(n: int) -> int:
    """A base port with ``n`` consecutive free ports (``serve`` derives
    its HTTP and internal ports from a base + node id)."""
    start = 17100 + (os.getpid() * 7) % 20000
    for base in range(start, 60000, 97):
        socks = []
        try:
            for p in range(base, base + n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", p))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise BenchFailure("no free port range")


class OwnerHealth:
    """The owner's ``Health`` over its documented wire (gRPC generic
    method ``/dfs.Sidecar/Health``, empty request, JSON reply —
    docs/sidecar_wire.md), without the program's client."""

    def __init__(self, port: int) -> None:
        import grpc

        self._channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        self._call = self._channel.unary_unary(
            "/dfs.Sidecar/Health", request_serializer=lambda b: b,
            response_deserializer=lambda b: b)

    def __call__(self) -> dict:
        return json.loads(self._call(b"", timeout=30.0))


class Owner:
    """The chip owner, started through ``benchmarks/owner.py`` — the
    program's ``sidecar`` command unchanged, plus a control thread that
    answers ``memstats`` and ``trace`` requests written to its stdin."""

    def __init__(self, args: list[str], jax_platforms: str, work: Path,
                 children: list[Child]) -> None:
        self.ctl_dir = work / "ctl"
        self.ctl_dir.mkdir()
        self._n = 0
        self.child = Child(
            "owner",
            [sys.executable, str(HERE / "owner.py"), *args],
            child_env(jax_platforms), work, stdin=subprocess.PIPE)
        self._children = children
        children.append(self.child)
        self.port = 0
        self.health: OwnerHealth | None = None

    def wait_listening(self, limit_s: float) -> None:
        def listening():
            for line in self.child.log_text().splitlines():
                if line.startswith("sidecar listening on "):
                    return int(line.split()[3].rsplit(":", 1)[1])
            return None

        self.port = wait_for("the owner's `sidecar listening` line",
                             listening, [self.child], limit_s)
        self.health = OwnerHealth(self.port)

    def send(self, verb: str, *args: str) -> Path:
        """Write one control line; returns the file its answer lands in."""
        self._n += 1
        out = self.ctl_dir / f"{self._n:04d}.{verb}.json"
        line = " ".join([verb, str(out), *args]) + "\n"
        self.child.proc.stdin.write(line.encode())
        self.child.proc.stdin.flush()
        return out

    def answer(self, out: Path, limit_s: float) -> dict:
        def ready():
            return json.loads(out.read_text()) if out.exists() else None

        got = wait_for(f"the owner's answer {out.name}", ready,
                       self._children, limit_s)
        if "error" in got:
            raise BenchFailure(f"owner control {out.name}: {got['error']}")
        return got


def start_nodes(deployment: dict, owner_port: int, data_root: Path,
                work: Path, children: list[Child]) -> list[int]:
    """``serve`` for every node, as the configuration's ``deployment``
    spells it; returns the nodes' HTTP ports once all answer /status."""
    n = int(deployment["nodes"])
    base = free_port_base(2 * n)
    ports = [base + i for i in range(n)]
    for i in range(1, n + 1):
        argv = [sys.executable, "-m", "dfs_tpu.cli.main", "serve",
                "--node-id", str(i), "--nodes", str(n),
                "--sidecar-port", str(owner_port),
                "--base-port", str(base),
                "--base-internal-port", str(base + n),
                "--data-root", str(data_root),
                *deployment["node_args"]]
        children.append(Child(f"node{i}", argv, child_env("cpu"), work))

    def all_up():
        for p in ports:
            try:
                with socket.create_connection(("127.0.0.1", p), 1.0) as s:
                    s.sendall(b"GET /status HTTP/1.1\r\nHost: x\r\n\r\n")
                    if b" 200 " not in s.recv(256):
                        return None
            except OSError:
                return None
        return True

    wait_for(f"{n} nodes to answer /status", all_up, children, 120)
    return ports
