"""A lossy in-process packet network whose losses are a pure function of
each packet's place in the run.

Endpoints are sockets (``send_to``, ``receive_all``, ``local_addr``) by
name; ``deliver()`` ages every queued packet one hop, and a packet arrives
``latency_hops`` hops after it was sent.  Whether a packet is lost depends
on the network's seed, its sender and receiver, the hop it was sent in, its
first ``KIND_BYTES`` bytes (where the P2P protocol keeps the packet's type)
and how many packets that begin alike the sender sent to that receiver in
that hop before it.  A packet whose sending time depends on the device,
such as a checksum report that goes out when its readback lands, therefore
never changes which other packets are lost, and every run of a seed loses
the same packets.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Any, Dict, List, Tuple

KIND_BYTES = 3
_KEY = struct.Struct("<QIIQI")


class LossyNetwork:
    def __init__(self, latency_hops: int, loss: float, seed: int):
        self.latency_hops = int(latency_hops)
        self.loss = float(loss)
        self.seed = int(seed) & (2**64 - 1)
        self._queues: Dict[Any, List[Tuple[int, Any, bytes]]] = {}
        self._index: Dict[Any, int] = {}
        self._sent: Dict[tuple, int] = {}
        self._hop = 0

    def endpoint(self, name: Any) -> "Socket":
        self._queues.setdefault(name, [])
        self._index.setdefault(name, len(self._index))
        return Socket(self, name)

    def deliver(self) -> None:
        self._hop += 1
        self._sent.clear()

    def lost(self, src: Any, dst: Any, data: bytes) -> bool:
        if self.loss <= 0.0:
            return False
        kind = (src, dst, data[:KIND_BYTES])
        n = self._sent.get(kind, 0)
        self._sent[kind] = n + 1
        key = _KEY.pack(self.seed, self._index[src], self._index[dst],
                        self._hop, n)
        h = hashlib.blake2b(data[:KIND_BYTES] + key, digest_size=8).digest()
        return int.from_bytes(h, "little") < self.loss * 2.0**64

    def send(self, src: Any, dst: Any, data: bytes) -> None:
        if not self.lost(src, dst, data):
            self._queues[dst].append((self._hop + self.latency_hops, src,
                                      data))

    def receive(self, name: Any) -> List[Tuple[Any, bytes]]:
        q = self._queues[name]
        due = [(src, d) for t, src, d in q if t <= self._hop]
        if due:
            q[:] = [p for p in q if p[0] > self._hop]
        return due


class Socket:
    """One endpoint of a :class:`LossyNetwork`."""

    def __init__(self, net: LossyNetwork, name: Any):
        self.net = net
        self.name = name

    @property
    def local_addr(self) -> Any:
        return self.name

    def send_to(self, data: bytes, addr: Any) -> None:
        self.net.send(self.name, addr, data)

    def receive_all(self) -> List[Tuple[Any, bytes]]:
        return self.net.receive(self.name)
