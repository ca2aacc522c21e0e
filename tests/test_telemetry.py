"""Telemetry subsystem tests — registry semantics, timeline ordering across
a forced rollback, desync forensics reports, the Prometheus exporter, and
the two hardening satellites that ride along (room same-addr rejoin, sync
handshake protocol versioning)."""

import dataclasses
import glob
import json
import struct
import time
import urllib.request

import pytest

from bevy_ggrs_tpu import telemetry
from tests.test_synctest import make_counter_app, make_runner


@pytest.fixture(autouse=True)
def _clean_telemetry():
    # the registry/timeline are process globals: isolate every test
    telemetry.disable()
    telemetry.reset()
    telemetry.configure_forensics(None)
    yield
    telemetry.disable()
    telemetry.reset()
    telemetry.configure_forensics(None)


# ---------------------------------------------------------------- registry


def test_counter_semantics_with_labels():
    telemetry.enable()
    telemetry.count("widgets_total", help="widgets")
    telemetry.count("widgets_total", 4, kind="blue")
    telemetry.count("widgets_total", kind="blue")
    c = telemetry.registry().counter("widgets_total", "widgets")
    assert c.value() == 1
    assert c.value(kind="blue") == 5
    snap = telemetry.registry().snapshot()
    assert snap["widgets_total"]["kind"] == "counter"
    assert snap["widgets_total"]["series"]["kind=blue"] == 5


def test_histogram_buckets_and_sum():
    telemetry.enable()
    for v in (0, 1, 1, 5, 100):
        telemetry.observe("depth", v, help="d", buckets=(0, 1, 4, 8))
    h = telemetry.registry().histogram("depth", "d", buckets=(0, 1, 4, 8))
    s = h.snapshot()
    assert s["count"] == 5
    assert s["sum"] == 107
    # per-bucket (non-cumulative); 100 overflows every bucket -> count only
    assert s["buckets"] == [1, 2, 0, 1]


def test_gauge_and_kind_conflict():
    telemetry.enable()
    telemetry.gauge_set("depth_now", 3, help="g")
    assert telemetry.registry().gauge("depth_now", "g").value() == 3
    with pytest.raises(TypeError):
        telemetry.registry().counter("depth_now", "not a gauge")


def test_disabled_is_noop():
    assert not telemetry.enabled()
    telemetry.count("never_total")
    telemetry.observe("never_hist", 1)
    telemetry.gauge_set("never_gauge", 1)
    telemetry.record("never_event")
    assert telemetry.registry().snapshot() == {}
    assert telemetry.timeline().tail(10) == []


def test_prometheus_rendering_cumulative():
    telemetry.enable()
    telemetry.count("ticks_total", 3, help="ticks")
    for v in (0, 2, 9):
        telemetry.observe("lat", v, help="lat", buckets=(1, 4))
    text = telemetry.registry().render_prometheus()
    assert "# TYPE ticks_total counter" in text
    assert "ticks_total 3" in text
    # cumulative le buckets ending in +Inf, plus _sum/_count
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="4"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_sum 11" in text
    assert "lat_count 3" in text


# ---------------------------------------------- timeline across a rollback


def test_timeline_orders_rollbacks_and_spans():
    telemetry.enable()
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=2)
    for _ in range(8):
        runner.tick()
    assert not mismatches
    events = telemetry.timeline().tail(10_000)
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    rollbacks = telemetry.timeline().events("rollback")
    # check_distance=2 forces a load+resim every tick after warmup
    assert rollbacks, "synctest check_distance=2 must roll back"
    for ev in rollbacks:
        assert ev["to_frame"] < ev["from_frame"]
        assert ev["depth"] == ev["from_frame"] - ev["to_frame"]
    # the tick phases are the one span system: chrome_trace() draws each
    # flight tick as a slice with one child slice per timed phase, inside
    # its tick and after the rollbacks' timeline order
    trace = telemetry.chrome_trace()
    assert telemetry.validate_chrome_trace(trace) == []
    slices = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    ticks = [e for e in slices if e["name"] == "tick"]
    phase_slices = [e for e in slices if e["name"] in telemetry.PHASES]
    assert len(ticks) == len(telemetry.flight_recorder().snapshot("tick"))
    assert {"store_save", "rollback_load", "wave_dispatch",
            "session_step"} <= {e["name"] for e in phase_slices}
    for e in phase_slices:
        assert any(t["tid"] == e["tid"] and t["ts"] <= e["ts"] + 1e-3
                   and e["ts"] + e["dur"] <= t["ts"] + t["dur"] + 1e-3
                   for t in ticks), e
    assert not [e for e in slices
                if e["name"] not in telemetry.PHASES and e["name"] != "tick"]
    # summary() derives the headline numbers from the same run
    s = telemetry.summary()
    assert s["enabled"] and s["derived"]["rollbacks_total"] == len(rollbacks)


def test_export_jsonl_round_trips(tmp_path):
    telemetry.enable()
    telemetry.record("alpha", x=1)
    telemetry.record("beta", y="z")
    out = tmp_path / "tl.jsonl"
    n = telemetry.export_jsonl(str(out))
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert n == len(lines) == 2
    assert [l["kind"] for l in lines] == ["alpha", "beta"]


# -------------------------------------------------------------- forensics


def test_desync_report_on_injected_mismatch(tmp_path):
    telemetry.enable()
    telemetry.configure_forensics(str(tmp_path))
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=2)
    for _ in range(4):
        runner.tick()
    # corrupt checksummed state behind the session's back (negative control
    # pattern from test_synctest.py) -> re-simulated frames must disagree
    w = runner.world
    runner.world = dataclasses.replace(
        w, comps={**w.comps, "counter": w.comps["counter"] + 1000}
    )
    runner._world_checksum = app.checksum_fn(runner.world)
    for _ in range(6):
        runner.tick()
    assert mismatches
    reports = glob.glob(str(tmp_path / "desync_synctest_mismatch_*.json"))
    assert reports, "forensics dir configured -> a report must be written"
    rep = json.loads(open(reports[0]).read())
    assert rep["kind"] == "synctest_mismatch"
    assert rep["frames"]
    assert "counter" in rep["component_checksums"]
    assert "__entities__" in rep["component_checksums"]
    assert rep["timeline_tail"], "report embeds the recent timeline"
    assert telemetry.registry().counter(
        "checksum_mismatch_total", ""
    ).value(kind="synctest") > 0


def test_no_report_without_forensics_dir(tmp_path):
    telemetry.enable()
    assert telemetry.forensics_dir() is None
    assert telemetry.write_desync_report("synctest_mismatch") is None
    assert not list(tmp_path.iterdir())


# ------------------------------------------------------------- prometheus


def test_http_exporter_scrape():
    telemetry.enable()
    telemetry.count("scraped_total", 7, help="scrape me")
    exporter = telemetry.start_http_exporter(port=0)
    try:
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = resp.read().decode()
            ctype = resp.headers["Content-Type"]
        assert ctype.startswith("text/plain; version=0.0.4")
        assert "scraped_total 7" in body
    finally:
        exporter.close()


# ------------------------------------------------- satellite: room rejoin


def test_same_addr_rejoin_into_full_room():
    """A socket that already holds a slot in a full room may re-join it
    under a new peer id: its own membership must not count against capacity."""
    from bevy_ggrs_tpu import RoomServer, RoomSocket, wait_for_players
    from bevy_ggrs_tpu.session import room as room_mod

    old_cap = room_mod.MAX_ROOM_MEMBERS
    room_mod.MAX_ROOM_MEMBERS = 1
    try:
        server = RoomServer(host="127.0.0.1")
        a = RoomSocket(server.local_addr, "solo", peer_id="old-name",
                       host="127.0.0.1")
        wait_for_players(a, 1, timeout_s=5.0, server=server)
        a.peer_id = "new-name"
        a._join()
        deadline = time.monotonic() + 3.0
        while (time.monotonic() < deadline
               and sorted(server.rooms.get("solo", {})) != ["new-name"]):
            server.poll()
            time.sleep(0.002)
        assert sorted(server.rooms["solo"]) == ["new-name"]
        assert len(server.rooms["solo"]) <= 1
        server.close()
        a.close()
    finally:
        room_mod.MAX_ROOM_MEMBERS = old_cap


# -------------------------------------- satellite: handshake versioning


def test_sync_handshake_rejects_versionless_peer():
    """A peer speaking the pre-version wire format (4-byte sync bodies) must
    stall in SYNCHRONIZING instead of mis-parsing — and a versioned REQ from
    it gets a versioned REP."""
    from bevy_ggrs_tpu import (
        GgrsRunner, PlayerType, SessionBuilder, SessionState,
        UdpNonBlockingSocket,
    )
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.session.protocol import (
        HDR, MAGIC, PROTOCOL_VERSION, S_SYNC_REP, S_SYNC_REQ,
        T_SYNC_REQ, T_SYNC_REP,
    )

    telemetry.enable()
    old_body = struct.Struct("<I")  # the pre-version sync body
    socks = [UdpNonBlockingSocket(0, host="127.0.0.1") for _ in range(2)]
    addrs = [("127.0.0.1", s.local_addr[1]) for s in socks]
    app = box_game.make_app(num_players=2)
    b = (SessionBuilder.for_app(app)
         .add_player(PlayerType.LOCAL, 0)
         .add_player(PlayerType.REMOTE, 1, addrs[1]))
    session = b.start_p2p_session(socks[0])
    runner = GgrsRunner(app, session)
    versioned_reps = []
    for _ in range(60):
        runner.update(0.0)
        for addr, data in socks[1].receive_all():
            magic, t = HDR.unpack_from(data)
            if t == T_SYNC_REQ:
                (nonce,) = old_body.unpack_from(data[HDR.size:])
                # reply in the OLD format: no version byte
                socks[1].send_to(
                    HDR.pack(MAGIC, T_SYNC_REP) + old_body.pack(nonce), addr
                )
            elif t == T_SYNC_REP:
                versioned_reps.append(S_SYNC_REP.unpack_from(data[HDR.size:]))
        time.sleep(0.001)
    # version-less REPs were dropped -> never synchronized
    assert session.current_state() == SessionState.SYNCHRONIZING
    assert telemetry.registry().counter(
        "handshake_version_mismatch_total", ""
    ).value(remote_version="none") > 0
    # a properly versioned REQ from the old peer's socket gets a versioned REP
    socks[1].send_to(
        HDR.pack(MAGIC, T_SYNC_REQ) + S_SYNC_REQ.pack(99, PROTOCOL_VERSION),
        addrs[0],
    )
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline and not any(
        n == 99 for n, _ in versioned_reps
    ):
        runner.update(0.0)
        for addr, data in socks[1].receive_all():
            magic, t = HDR.unpack_from(data)
            if t == T_SYNC_REP:
                versioned_reps.append(S_SYNC_REP.unpack_from(data[HDR.size:]))
        time.sleep(0.001)
    assert (99, PROTOCOL_VERSION) in versioned_reps
    for s in socks:
        s.close()
