"""Shared plumbing of the multi-device walks.

The sharded anchored walk (cdc_anchored_sharded.py) and the sharded
sketcher (dfs_tpu/sim/sketch.py) need the same two pieces, and they must
not drift apart:

- **one compile-shape policy** (:func:`fixed_region_bytes`): streaming
  input is re-blocked to a FIXED region size so the sharded step
  traces/compiles exactly once for the whole stream. The size must be a
  multiple of the caller's granule (the anchor tile / the sketch window's)
  and at least one granule; the anchored walk then enforces its own
  two-segment floor.

- **one fallback predicate** (:class:`ShardedSteps`): building the mesh
  + steps is LAZY (jax untouched until the first stream). Where the
  devices were asked for — any process not started with
  ``JAX_PLATFORMS=cpu`` — a failure is an ERROR: no TPU, fewer chips
  than configured, a kernel the compiler refuses. Only the CPU-on-purpose
  rehearsal (virtual devices, tests) degrades to the single-device
  kernel with one logged warning; output is identical either way (the
  sharded steps compute the same boundaries, which tests pin
  byte-identical).
"""

from __future__ import annotations

import logging
from typing import Callable


def fixed_region_bytes(requested: int, default: int, granule: int) -> int:
    """The single compile-shape policy: the fixed per-stream region size
    in bytes — ``requested`` (or ``default`` when 0) floored to a whole
    multiple of ``granule``, never below one granule. Every region of a
    stream except the ragged tail has exactly this size, so the sharded
    step compiles once."""
    rb = int(requested) or int(default)
    return max(int(granule), rb // int(granule) * int(granule))


class ShardedSteps:
    """Lazy mesh + step construction behind the single fallback
    predicate. ``build(mesh)`` runs at most once, on the first
    :meth:`get`; it may return any strategy-specific step bundle. On the
    device (anything but ``JAX_PLATFORMS=cpu``) a failure raises. On the
    CPU on purpose it marks the instance unavailable, logs one warning,
    and every later ``get()`` returns None — callers fall back to their
    single-device kernel."""

    def __init__(self, devices: int, build: Callable) -> None:
        self.devices = int(devices)
        self._build = build
        self._steps = None
        self.mesh = None
        self.unavailable = False

    def _make(self) -> None:
        import jax

        from dfs_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < self.devices:
            raise RuntimeError(
                f"{self.devices} devices configured, "
                f"{len(jax.devices())} visible")
        # windows (chunks to sketch) ride the dp axis, one whole
        # window per device
        mesh = make_mesh(self.devices, dp=self.devices)
        self._steps = self._build(mesh)
        self.mesh = mesh

    def get(self):
        if self._steps is not None or self.unavailable:
            return self._steps
        from dfs_tpu.utils.device import cpu_on_purpose, require_tpu

        if not cpu_on_purpose():
            require_tpu(f"a sharded walk over {self.devices} devices")
            self._make()
            return self._steps
        try:
            self._make()
        except Exception as e:  # noqa: BLE001 - CPU rehearsal: degrade
            self.unavailable = True
            logging.getLogger("dfs_tpu.fragmenter").warning(
                "sharded CDC unavailable (%s); running single-device", e)
        return self._steps
