"""Traffic kind ``segments``: closed-loop clients, each streaming the
next segment of one stream through its own coordinator.

Parameters (the traffic file): ``clients``, ``object_bytes``,
``period_bytes`` (half of every period is fresh, half the tiled block —
``data.segment``), ``block_bytes`` (the chunked-transfer block),
``preload_objects``, ``corpus_seed``, ``lead_objects``,
``ratio_objects``.

The stream's bytes are the traffic file's own: segment ``k`` derives
from ``(corpus_seed, k)``, so it never ends and a faster system never
runs dry. ``--seed`` gives the order: it shuffles the first
``lead_objects`` segments among themselves and the next
``ratio_objects`` among themselves; from there on the stream runs in its
own order. What a chunker finds to share between segments depends on
their bytes and swings by a few per cent of the stored bytes from one
corpus to another, so every seed sends the same segments in another
order: the bytes stored for those ``ratio_objects`` segments, once the
lead is in the stores, are then the same count for every seed
(``end_to_end/stored_ratio.py``). Client ``c`` sends places c,
c+clients, ... of that order to node ``c mod nodes``. A key is
``("seg", k)``: ``k`` is the segment's number in the stream.
"""

from __future__ import annotations

import data


class Generator:
    def __init__(self, traffic: dict, config: dict, seed: int) -> None:
        self.corpus_seed = int(traffic["corpus_seed"])
        self.clients = int(traffic["clients"])
        self.size = int(traffic["object_bytes"])
        self.period = int(traffic["period_bytes"])
        self.block = int(traffic["block_bytes"])
        self.preload_objects = int(traffic.get("preload_objects", 0))
        self.nodes = int(config["deployment"]["nodes"])
        self.warm_sizes = [self.size]
        lead, ratio = int(traffic["lead_objects"]), int(traffic["ratio_objects"])
        r = data.rng(seed, 6)
        self.order = [int(k) for k in r.permutation(lead)] \
            + [lead + int(k) for k in r.permutation(ratio)]

    def make(self, key: tuple):
        return data.segment(self.corpus_seed, key[1], self.size, self.period)

    def _put(self, api, client: int, k: int):
        key = ("seg", k)
        body = self.make(key)
        return api.put(client, client % self.nodes, key, body,
                       data.sha256_hex(body), block=self.block)

    def preload(self, api) -> None:
        # preloaded segments take numbers below zero, clear of the run's
        for i in range(self.preload_objects):
            self._put(api, i % self.clients, -1 - i)

    def run_client(self, client: int, api, stop) -> None:
        place = client
        while not stop.is_set():
            self._put(api, client, self.order[place]
                      if place < len(self.order) else place)
            place += self.clients
