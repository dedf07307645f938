"""Traffic kind ``files``: closed-loop clients uploading a directory of
many small files of mixed kinds in one batch, half of which the store
already holds — a site deploy or a photo-library sync into a
content-addressed store.

Parameters (the traffic file): ``clients``, ``kinds`` (a kind's name ->
the median of its sizes in bytes; the kinds come in equal shares),
``size_sigma`` (of ln size, every kind), ``size_min_bytes`` /
``size_max_bytes`` (the clip), ``repeat_share``, ``preload_objects``,
``lead_objects``, ``ratio_objects``, ``block_bytes`` (0: a whole body
with its Content-Length), ``corpus_seed``, ``warm_sizes`` (one object a
packed-region shape the owner compiles: one).

The stream's files are the traffic file's own: file ``k`` derives from
``(corpus_seed, k)``, so the stream never ends. The preload — the
directory as the last sync left it — is ``preload_objects`` files with
numbers below zero (``-1 - i``), sent through all clients at once under
phase ``preload``. A key is ``("file", k)``; an upload is named by it,
so a file sent again goes under a new name. ``--seed`` only orders: it
shuffles the first ``lead_objects`` places of the stream among
themselves and the next ``ratio_objects`` among themselves (so the
bytes stored for that slice are one count for every seed,
``end_to_end/stored_ratio.py``); from there the stream runs in its own
order. Client ``c`` sends places c, c+clients, ... of that order to
node ``c mod nodes``, a new connection an upload.

The corpus as a list of draws, which ``reference_files.py`` repeats in
a second, plain implementation (``G(tags)`` is
``numpy.random.default_rng([corpus_seed, *tags])``; the kinds in the
traffic file's order):

* file ``k`` of the stream draws from ``g = G(14, k)``, preload file
  ``-1 - i`` from ``g = G(15, i)``, in this order: its kind
  ``g.integers(0, number of kinds)``; its size ``g.lognormal(ln of the
  kind's median, size_sigma)`` cut to whole bytes and clipped to
  ``[size_min_bytes, size_max_bytes]``; its bytes ``g.bytes(size)`` (a
  kind sets the size, not the content);
* with ``period = round(1 / repeat_share)``, a place ``k >= 0`` that is
  a multiple of ``period`` (at 0.5: the even places) sends no file of
  its own: it re-sends, byte for byte, preload file ``-1 - j`` with
  ``j = G(16, k).integers(0, preload_objects)``. ``repeat_share`` 0:
  every place is new.
"""

from __future__ import annotations

import math
import sys
import threading

import numpy as np

import data
from cluster import REPO, BenchFailure


def _refuse_a_host_cutoff() -> None:
    """A cell drives the device, and this deployment's streams are all
    small. Until PR 41 the TPU engine handed every stream of 2 MiB or
    less to the host oracle (``_CPU_CUTOFF``), and such a program cannot
    run this as a cell: without ``--trace`` it runs to its end with the
    chip idle, with it the run ends "the traced slice holds no device
    operation" after the whole window (my chip run, PR 41: 127 s) —
    and a failed traced run refuses the PR that added the cell. So a
    program that still has the cutoff is refused here, at once and
    whatever ``--trace`` is. The test is for the OLD name: nothing the
    program renames later can trip it."""
    if str(REPO) not in sys.path:
        sys.path.append(str(REPO))
    from dfs_tpu.fragmenter import cdc_anchored

    if hasattr(cdc_anchored, "_CPU_CUTOFF"):
        raise BenchFailure(
            "this program chunks a stream of 2 MiB or less on the host "
            "(dfs_tpu/fragmenter/cdc_anchored.py _CPU_CUTOFF): a cell of "
            "small files would hold no device operation")


class Generator:
    def __init__(self, traffic: dict, config: dict, seed: int) -> None:
        _refuse_a_host_cutoff()
        self.corpus_seed = int(traffic["corpus_seed"])
        self.clients = int(traffic["clients"])
        self.block = int(traffic["block_bytes"])
        self.nodes = int(config["deployment"]["nodes"])
        self.medians = [float(m) for m in traffic["kinds"].values()]
        self.sigma = float(traffic["size_sigma"])
        self.clip = (int(traffic["size_min_bytes"]),
                     int(traffic["size_max_bytes"]))
        share = float(traffic["repeat_share"])
        self.period = round(1 / share) if share > 0 else 0
        self.preload_objects = int(traffic["preload_objects"])
        self.warm_sizes = [int(s) for s in traffic["warm_sizes"]]
        lead = int(traffic["lead_objects"])
        ratio = int(traffic["ratio_objects"])
        r = data.rng(seed, 6)
        self.order = [int(k) for k in r.permutation(lead)] \
            + [lead + int(k) for k in r.permutation(ratio)]

    def resent(self, k: int) -> int | None:
        """The number (below zero) of the preload file that place ``k``
        sends again, or None where it sends file ``k``."""
        if k < 0 or not self.period or k % self.period:
            return None
        return -1 - int(data.rng(self.corpus_seed, 16, k).integers(
            0, self.preload_objects))

    def size_of(self, k: int) -> tuple[int, int]:
        """(kind, size) of file ``k`` — ``make`` without the bytes."""
        return self._draw(k)[:2]

    def _draw(self, k: int):
        g = data.rng(self.corpus_seed, 14, k) if k >= 0 \
            else data.rng(self.corpus_seed, 15, -1 - k)
        kind = int(g.integers(0, len(self.medians)))
        size = int(g.lognormal(math.log(self.medians[kind]), self.sigma))
        return kind, min(self.clip[1], max(self.clip[0], size)), g

    def make(self, key: tuple) -> np.ndarray:
        k = key[1]
        again = self.resent(k)
        _, size, g = self._draw(k if again is None else again)
        return np.frombuffer(g.bytes(size), dtype=np.uint8)

    def _put(self, api, client: int, k: int):
        key = ("file", k)
        body = self.make(key)
        return api.put(client, client % self.nodes, key, body,
                       data.sha256_hex(body), block=self.block)

    def preload(self, api) -> None:
        def send(client: int) -> None:
            for i in range(client, self.preload_objects, self.clients):
                self._put(api, client, -1 - i)

        threads = [threading.Thread(target=send, args=(c,), daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def run_client(self, client: int, api, stop) -> None:
        place = client
        while not stop.is_set():
            self._put(api, client, self.order[place]
                      if place < len(self.order) else place)
            place += self.clients
