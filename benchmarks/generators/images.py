"""Traffic kind ``images``: closed-loop clients, each sending night
after night the full image of one machine — a backup server's nightly
fulls of VM images or database dumps into a content-addressed store.
Every upload is ONE long stream: ``object_bytes`` in one chunked
transfer, many device windows at the chip owner.

Parameters (the traffic file): ``clients``, ``images`` (lineages cloned
from one base: as many as clients), ``object_bytes``, ``period_bytes``
(the base's: ``data.segment``), ``extents_per_night``,
``extent_min_bytes``, ``extent_max_bytes``, ``block_bytes`` (the
chunked-transfer block), ``corpus_seed``, ``lead_objects`` (1: the
base), ``ratio_objects`` (= ``images``: night 1 of each),
``warm_sizes``.

A key is ``("img", k)``. Object 0 is the base image, preloaded once,
under phase ``preload``. Object ``k >= 1`` is image ``(k-1) mod images``
on night ``(k-1) div images + 1``: the base with that image's nights
1 ... n applied, in place — nothing shifts, as in a disk image or a
database file. The lineages share only the base, so two uploads in
flight never race to store the same new chunk, and the bytes stored for
night 1 of every image (``end_to_end/stored_ratio.py``: the
``ratio_objects`` after the lead) are one count for every seed. Every
object is exactly ``object_bytes`` long and a pure function of
``(corpus_seed, k)``: ``make(key)`` rebuilds any of them for the checks.

``--seed`` only orders: it draws which image each client takes (a
permutation) and a rotation ``r``; client ``c`` sends the nights of ITS
image one after the other, night ``n`` through node ``(image + n + r)
mod nodes`` — a night never meets its predecessor on the coordinator
that chunked it. A client keeps its image as one array and writes the
next night's extents into it before the upload's clock starts; what
that and the hash of the whole image cost the closed loop is
``layer_metrics/client.think_s_per_gib``.

The corpus as a list of draws, which ``reference_images.py`` repeats in
a second, plain implementation (``G(tags)`` is
``numpy.random.default_rng([corpus_seed, *tags])``; ``half`` is
``period_bytes / 2``):

* the base: ``block = G(0, 0).bytes(half)``; ``new = G(1, 0).bytes(
  ceil(object_bytes / period_bytes) * half)``; period ``p`` of the image
  is ``new[p * half:(p + 1) * half]`` then ``block``; the whole cut to
  ``object_bytes`` (``data.segment(corpus_seed, 0, ...)``: the source's
  synthetic tarball);
* night ``n`` of image ``i`` draws from ``g = G(20, i, n)``, extent
  after extent, ``extents_per_night`` times, in this order: its length
  ``int(exp(g.uniform(ln extent_min_bytes, ln extent_max_bytes)))``
  (log-uniform), its offset ``g.integers(0, object_bytes - length +
  1)``, its bytes ``g.bytes(length)``, written over the image at that
  offset (a later extent lies over an earlier one where they meet).
"""

from __future__ import annotations

import math
import threading

import numpy as np

import data


class Generator:
    def __init__(self, traffic: dict, config: dict, seed: int) -> None:
        self.corpus_seed = int(traffic["corpus_seed"])
        self.clients = int(traffic["clients"])
        self.images = int(traffic["images"])
        self.size = int(traffic["object_bytes"])
        self.period = int(traffic["period_bytes"])
        self.extents = int(traffic["extents_per_night"])
        self.extent = (int(traffic["extent_min_bytes"]),
                       int(traffic["extent_max_bytes"]))
        self.block = int(traffic["block_bytes"])
        self.nodes = int(config["deployment"]["nodes"])
        self.warm_sizes = [int(s) for s in traffic["warm_sizes"]]
        if int(traffic["lead_objects"]) != 1 \
                or int(traffic["ratio_objects"]) != self.images \
                or self.clients != self.images:
            raise ValueError(
                "the lead is the base image (lead_objects 1), the slice "
                "night 1 of every image (ratio_objects = images), and a "
                "client sends one image (clients = images)")
        r = data.rng(seed, 6)
        self.image_of = [int(i) for i in r.permutation(self.images)]
        self.rotation = int(r.integers(self.nodes))
        self._lock = threading.Lock()
        self._base: np.ndarray | None = None

    def base(self) -> np.ndarray:
        """Object 0, made once and kept (read-only)."""
        with self._lock:
            if self._base is None:
                arr = data.segment(self.corpus_seed, 0, self.size,
                                   self.period)
                arr.setflags(write=False)
                self._base = arr
            return self._base

    def night(self, arr: np.ndarray, image: int, n: int) -> None:
        """Write night ``n`` of ``image`` over ``arr``."""
        g = data.rng(self.corpus_seed, 20, image, n)
        lo, hi = (math.log(b) for b in self.extent)
        for _ in range(self.extents):
            length = int(math.exp(g.uniform(lo, hi)))
            at = int(g.integers(0, self.size - length + 1))
            arr[at:at + length] = np.frombuffer(g.bytes(length), np.uint8)

    def key_of(self, image: int, n: int) -> tuple:
        return ("img", (n - 1) * self.images + image + 1)

    def node_of(self, image: int, n: int) -> int:
        return (image + n + self.rotation) % self.nodes

    def make(self, key: tuple) -> np.ndarray:
        k = key[1]
        if k == 0:
            return self.base()
        n, image = divmod(k - 1, self.images)
        arr = self.base().copy()
        for night in range(1, n + 2):
            self.night(arr, image, night)
        return arr

    def preload(self, api) -> None:
        body = self.base()
        api.put(0, self.rotation % self.nodes, ("img", 0), body,
                data.sha256_hex(body), block=self.block)

    def run_client(self, client: int, api, stop) -> None:
        image = self.image_of[client]
        arr = self.base().copy()
        n = 0
        while not stop.is_set():
            n += 1
            self.night(arr, image, n)
            api.put(client, self.node_of(image, n), self.key_of(image, n),
                    arr, data.sha256_hex(arr), block=self.block)
