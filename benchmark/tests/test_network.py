"""The benchmark's lossy network, and the P2P driver's work being a pure
function of the seed."""

import time

import pytest

from harness.network import LossyNetwork


def fates(net, extra_at=None):
    """Send 2,000 input-like packets a hop for 20 hops; return which arrived.
    ``extra_at`` adds a packet of another kind in that hop first."""
    a, b = net.endpoint("a"), net.endpoint("b")
    got = []
    for hop in range(20):
        if hop == extra_at:
            a.send_to(b"\x47\xa7\x08checksum", "b")
        for i in range(2000):
            a.send_to(b"\x47\xa7\x03" + i.to_bytes(4, "little"), "b")
        for _ in range(3):
            net.deliver()
        got.append({d for _src, d in b.receive_all() if d[2] == 3})
    return got


def test_losses_follow_the_seed_not_other_packets():
    base = fates(LossyNetwork(latency_hops=3, loss=0.05, seed=7))
    assert fates(LossyNetwork(latency_hops=3, loss=0.05, seed=7), 5) == base
    assert fates(LossyNetwork(latency_hops=3, loss=0.05, seed=8)) != base
    kept = sum(len(g) for g in base) / (20 * 2000)
    assert 0.94 < kept < 0.96


def test_packets_arrive_after_the_latency():
    net = LossyNetwork(latency_hops=3, loss=0.0, seed=1)
    a, b = net.endpoint("a"), net.endpoint("b")
    a.send_to(b"xyz", "b")
    for _ in range(2):
        net.deliver()
        assert b.receive_all() == []
    net.deliver()
    assert b.receive_all() == [("a", b"xyz")]


@pytest.mark.parametrize("seed", [2**33 + 11])
def test_p2p_work_is_the_same_in_every_run_of_a_seed(seed):
    from harness import spec
    from harness.apps import build_app
    from harness.measure import Marks

    def work():
        cell = spec.load_cell("boxgame_p2p_pair")
        cell.traffic = dict(cell.traffic, hold_sweeps=1, toggle_frames=260,
                            warm_frames=270)
        drv = cell.driver().Driver(build_app(cell.config, seed), cell.config,
                                   cell.traffic, seed, Marks())
        try:
            drv.warm()
            ticks = []
            for _ in range(400):
                drv.step(ticks, time.perf_counter)
            return ([r.frame for r in drv.runners],
                    [r.stats()["rollbacks"] for r in drv.runners],
                    drv.counters()["device_dispatches"])
        finally:
            drv.close()

    assert work() == work()
