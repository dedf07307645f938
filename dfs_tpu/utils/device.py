"""Which device this process computes on — decided once, at start, out loud.

A TPU chip belongs to ONE process. A deployment on a chip machine is
therefore one chip owner (``cli.main sidecar``, started with
``JAX_PLATFORMS=tpu``) plus N node processes that delegate to it
(``serve --sidecar-port``, started with ``JAX_PLATFORMS=cpu``). Nothing
in here falls back: JAX, left alone, answers a failed TPU init with
``[CpuDevice(id=0)]`` and exit 0, so "it ran" proves nothing about the
chip unless the process that owns it says which platform it got and
fails when that is not the one it was started for.

- ``JAX_PLATFORMS=cpu`` is the one way to say "CPU on purpose" (tests,
  non-owner nodes, rehearsals).
- A machine "has a TPU" when the PCI bus shows a TPU chip — read from
  sysfs, so the question never initialises a backend (and never takes
  the chip from the process that should own it).
- The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says, else in one fixed git-ignored directory inside the checkout; the
  path is part of the cache key, so it never derives from a pid, a
  temp dir or the clock.
"""

from __future__ import annotations

import glob
import os
from pathlib import Path

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# PCI ids of TPU chips (vendor: Google). The same table JAX consults to
# decide whether to try libtpu; kept here so the question needs no JAX.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset({
    "0x0027",   # v2 / v3
    "0x0056",
    "0x005e",   # v4
    "0x0062",   # v5p
    "0x0063",   # v5e
    "0x006f",   # v6e
    "0x0076",   # 7x
})

ONE_OWNER_HINT = (
    "a chip belongs to one process: start ONE owner with "
    "`JAX_PLATFORMS=tpu python -m dfs_tpu.cli.main sidecar`, and every "
    "node with `JAX_PLATFORMS=cpu ... serve --sidecar-port PORT`")


class DeviceError(RuntimeError):
    """The process was started for a device it did not get."""


_took_tpu = False      # require_tpu came back in THIS process


def compile_cache_dir() -> Path:
    """Where compiled executables persist: the environment's choice, else
    the fixed in-checkout directory."""
    env = os.environ.get(_CACHE_ENV)
    return Path(env) if env else _DEFAULT_CACHE_DIR


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and keep every executable (no size or
    compile-time floor: the chain is a handful of large kernels, and the
    small ones are what a warm start otherwise re-pays one by one).
    Called by every entry point that may initialise a backend, before
    its first compile. When the environment names the directory JAX has
    already read it, and no other is set in code."""
    import jax

    if not os.environ.get(_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(_DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return compile_cache_dir()


def tpu_chips() -> int:
    """TPU chips on this machine's PCI bus (0 = none). sysfs only: asking
    must not initialise a backend, because ``jax.devices()`` cannot tell
    "no TPU" from "a TPU another process holds" — both come back CPU."""
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            if Path(vendor).read_text().strip() != _GOOGLE_PCI_VENDOR:
                continue
            dev = Path(vendor).with_name("device").read_text().strip()
        except OSError:
            continue
        n += dev in _TPU_PCI_DEVICES
    return n


def _platforms_env() -> list[str]:
    raw = os.environ.get("JAX_PLATFORMS", "")
    return [p.strip().lower() for p in raw.split(",") if p.strip()]


def cpu_on_purpose() -> bool:
    """True iff the environment pins JAX to the CPU."""
    return _platforms_env() == ["cpu"]


def wants_tpu() -> bool:
    """The ``auto`` rule: the device engine iff this machine has a TPU
    platform. ``JAX_PLATFORMS`` decides when set (``cpu`` = CPU on
    purpose); otherwise the PCI bus does. No threshold, no timeout, no
    re-probe."""
    env = _platforms_env()
    if env:
        return "tpu" in env
    return tpu_chips() > 0


def device_info() -> dict:
    """The device as JAX reports it (initialises the backend)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(what: str) -> dict:
    """Take the TPU for this process or raise :class:`DeviceError` naming
    what was found instead. With no ``JAX_PLATFORMS`` the platform is
    pinned to ``tpu`` first, so a chip that is present but cannot be
    taken (held by another process, init error) raises in JAX rather
    than coming back as a CPU device. Returns :func:`device_info`."""
    import jax

    if not _platforms_env():
        jax.config.update("jax_platforms", "tpu")
    try:
        info = device_info()
    except RuntimeError as e:
        raise DeviceError(
            f"{what} needs the TPU backend and could not take it: {e} — "
            f"{ONE_OWNER_HINT} (JAX_PLATFORMS=cpu runs the engine on the "
            "CPU on purpose)") from e
    if info["platform"] != "tpu":
        raise DeviceError(
            f"{what} needs the TPU backend, but JAX came up on "
            f"platform {info['platform']!r} ({info['device_kind']}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}) — "
            f"{ONE_OWNER_HINT}")
    global _took_tpu
    _took_tpu = True
    return info


def holds_tpu() -> bool:
    """True iff THIS process took the TPU (:func:`require_tpu` came
    back): the chip owner, a node whose own engine runs on the chip, a
    bench. A node that only talks to an owner holds none, whatever its
    engine is called (``sidecar:cdc-anchored-tpu``), and neither does
    anything started ``JAX_PLATFORMS=cpu``. Host code with a jitted twin
    (the erasure-coding encode) asks here, never an engine's name; the
    question initialises nothing."""
    return _took_tpu


def bench_device(what: str) -> str:
    """The gate every bench script opens with: place the compile cache,
    then require the platform to be ``tpu`` unless the CPU was asked for
    by name — there the XLA twins run as a correctness rehearsal, never
    as a speed. Returns the line to print: platform, device_kind and
    device count as JAX reports them."""
    enable_compile_cache()
    dev = device_info() if cpu_on_purpose() else require_tpu(what)
    return (f"device: platform={dev['platform']} "
            f"device_kind={dev['device_kind']} count={dev['count']}")
