"""The plain reference: an object store as a dict, replaying the op log.

It imports nothing of the program and takes nothing the program made.
An object is named by the sha256 of its bytes (hashlib, over bytes the
benchmark's generator made from the seed); ``put`` makes it live,
``delete`` makes it gone, and a read of a live id gives those bytes.
The dict keeps each object's generator key, not its bytes: ``make(key)``
regenerates them where a comparison needs them, so a window's GiB of
objects need not be held.

``replay`` walks the acknowledged operations in the order their answers
arrived and returns what the system owes: the live ids, the deleted
ids, and every answer that a store with these semantics could not have
given.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Expected:
    live: dict[str, tuple] = field(default_factory=dict)   # id -> key
    deleted: set[str] = field(default_factory=set)
    violations: list[str] = field(default_factory=list)


def replay(ops) -> Expected:
    """``ops``: records with kind, file_id, got_id, key, status, t0, t1,
    body_sha, body_len, nbytes (``benchmarks/ops.py`` ``Op``). The
    generators never read or delete an id while a delete of it is in
    flight, so the order of answers is the order of effects."""
    exp = Expected()
    known: dict[str, int] = {}          # id -> size, of every id ever put
    for op in sorted(ops, key=lambda o: o.t1):
        fid = op.file_id
        if op.kind == "put":
            if op.status == 201:
                if op.got_id != fid:
                    exp.violations.append(
                        f"put {op.key}: acked id {op.got_id[:12]} is not "
                        f"sha256(body) {fid[:12]}")
                exp.live[fid] = op.key
                exp.deleted.discard(fid)
                known[fid] = op.nbytes
        elif op.kind == "delete":
            if op.status == 200:
                if fid not in exp.live:
                    exp.violations.append(
                        f"delete {fid[:12]}: acked for an id not live")
                exp.live.pop(fid, None)
                exp.deleted.add(fid)
        elif op.kind in ("get", "stat"):
            if fid in exp.live:
                if op.status == 404:
                    exp.violations.append(
                        f"{op.kind} {fid[:12]}: 404 for a live id")
                elif op.status == 200 and op.kind == "get" and (
                        op.body_sha != fid or op.body_len != known[fid]):
                    exp.violations.append(
                        f"get {fid[:12]}: body is not the bytes put "
                        f"({op.body_len} B, sha {op.body_sha[:12]})")
                elif op.status == 200 and op.kind == "stat" and (
                        op.got_id != fid or op.body_len != known[fid]):
                    exp.violations.append(
                        f"stat {fid[:12]}: manifest says id "
                        f"{op.got_id[:12]}, size {op.body_len}")
            elif op.status == 200:
                exp.violations.append(
                    f"{op.kind} {fid[:12]}: answered 200 for an id "
                    + ("deleted" if fid in exp.deleted else "never put"))
    return exp
