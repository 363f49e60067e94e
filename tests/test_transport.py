"""repro.fleet.transport: wire framing, LocalTransport/SocketTransport
equivalence, and the failure modes that must degrade cleanly — a worker
killed mid-batch, truncated frames, request timeouts — instead of
hanging the fleet."""
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from repro.codecs import get_codec
from repro.fleet import (
    FleetFrontend,
    LocalTransport,
    RemoteError,
    SocketTransport,
    TransportError,
    rebalance,
)
from repro.fleet.transport import (
    ProtocolError,
    Reader,
    Writer,
    pack_ownership,
    parse_address,
    recv_frame,
    send_frame,
    unpack_ownership,
)
from repro.serve.codec_service import CodecService, Ownership
from repro.stream import write_chunked

SHAPE = (16, 16, 8)


@pytest.fixture(scope="module")
def payload_path(tmp_path_factory):
    rng = np.random.default_rng(0)
    x = rng.random(SHAPE).astype(np.float32)
    enc = get_codec("ttd").fit(x, max_rank=4)
    path = str(tmp_path_factory.mktemp("transport") / "p.tcdc")
    write_chunked(path, enc, chunk_bytes=1024)
    return path


def _idx(n=100, seed=1):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, s, n) for s in SHAPE], axis=1)


def _spawn(iid, **kw):
    kw.setdefault("timeout", 10.0)
    return SocketTransport.spawn(iid, **kw)


# ---------------------------------------------------------------------------
# wire framing
# ---------------------------------------------------------------------------
def test_writer_reader_roundtrip():
    arr = np.arange(24, dtype=np.float64).reshape(4, 6)
    body = (
        Writer().u8(7).u16(300).u32(1 << 20).u64(1 << 40).i64(-5)
        .str("payload/α").blob(b"raw bytes").array(arr).bytes()
    )
    r = Reader(body)
    assert (r.u8(), r.u16(), r.u32(), r.u64(), r.i64()) == (
        7, 300, 1 << 20, 1 << 40, -5
    )
    assert r.str() == "payload/α"
    assert r.blob() == b"raw bytes"
    np.testing.assert_array_equal(r.array(), arr)  # bit-exact


def test_reader_rejects_truncated_body():
    body = Writer().u64(1).bytes()
    with pytest.raises(ProtocolError, match="truncated"):
        Reader(body[:3]).u64()
    with pytest.raises(ProtocolError, match="truncated"):
        Reader(Writer().str("hello").bytes()[:4]).str()


@pytest.mark.parametrize(
    "ownership",
    [
        None,
        Ownership(),
        Ownership(chunk_ids=frozenset({1, 5}), tile_ids=None),
        Ownership(chunk_ids=frozenset(), tile_ids=frozenset({0, 2, 9})),
    ],
)
def test_ownership_roundtrip(ownership):
    w = Writer()
    pack_ownership(w, ownership)
    got = unpack_ownership(Reader(w.bytes()))
    if ownership is None:
        assert got is None
    else:
        assert got.chunk_ids == ownership.chunk_ids
        assert got.tile_ids == ownership.tile_ids


def test_parse_address():
    assert parse_address("unix:/tmp/x.sock") == (socket.AF_UNIX, "/tmp/x.sock")
    assert parse_address("tcp:127.0.0.1:7070") == (
        socket.AF_INET, ("127.0.0.1", 7070)
    )
    with pytest.raises(ValueError, match="bad"):
        parse_address("http://nope")
    with pytest.raises(ValueError, match="bad tcp"):
        parse_address("tcp:missing-port")


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    with a, b:
        send_frame(a, b"hello frame")
        assert recv_frame(b) == b"hello frame"
        a.close()
        assert recv_frame(b) is None  # clean EOF at a frame boundary


def test_truncated_frame_is_protocol_error_not_hang():
    a, b = socket.socketpair()
    with a, b:
        a.sendall(struct.pack("<I", 100) + b"only a little")
        a.close()
        with pytest.raises(ProtocolError, match="truncated frame"):
            recv_frame(b)


# ---------------------------------------------------------------------------
# LocalTransport semantics
# ---------------------------------------------------------------------------
def test_local_transport_defers_submit_errors_to_flush(payload_path):
    t = LocalTransport("l0")
    t.load_stream("t", payload_path)
    bad = t.submit("nope", _idx(4))  # unknown payload: deferred, not raised
    good = t.submit("t", _idx(4))
    results, failures = t.flush()
    assert good in results and bad in failures
    assert isinstance(failures[bad], KeyError)
    assert t.flush() == ({}, {})  # reported exactly once


def test_local_transport_full_surface(payload_path):
    t = LocalTransport("l0")
    t.load_stream("t", payload_path, tile_entries=64)
    assert t.payloads() == ["t"]
    assert t.shape_of("t") == SHAPE
    rid = t.submit("t", _idx(10))
    results, failures = t.flush()
    assert not failures and results[rid].shape == (10,)
    stats = t.stats()
    assert stats["misses"] > 0 and "t" in stats["per_payload"]
    t.set_ownership("t", Ownership(tile_ids=frozenset()))
    assert t.drop_unowned("t") > 0
    t.unload("t")
    assert t.payloads() == []


# ---------------------------------------------------------------------------
# socket transport vs local: bit-identical round trip (satellite)
# ---------------------------------------------------------------------------
def test_socket_and_local_transport_bit_identical(payload_path):
    local = LocalTransport("l0")
    local.load_stream("t", payload_path, tile_entries=64)
    remote = _spawn("w0")
    try:
        remote.load_stream("t", payload_path, tile_entries=64)
        assert remote.payloads() == ["t"]
        assert remote.shape_of("t") == SHAPE
        batches = [_idx(n, seed=n) for n in (3, 57, 200)]
        l_tickets = [local.submit("t", b) for b in batches]
        r_tickets = [remote.submit("t", b) for b in batches]
        l_res, l_fail = local.flush()
        r_res, r_fail = remote.flush()
        assert not l_fail and not r_fail
        for lt, rt in zip(l_tickets, r_tickets):
            np.testing.assert_array_equal(l_res[lt], r_res[rt])
            assert l_res[lt].dtype == r_res[rt].dtype
        # ownership verbs round-trip: export tiles, drop, re-admit
        tiles = remote.export_tiles("t")
        assert tiles and all(isinstance(v, np.ndarray) for v in tiles.values())
        assert tiles.keys() == local.export_tiles("t").keys()
        tid, values = next(iter(tiles.items()))
        np.testing.assert_array_equal(values, local.export_tiles("t")[tid])
        remote.set_ownership("t", Ownership(tile_ids=frozenset()))
        assert remote.drop_unowned("t") > 0
        remote.set_ownership("t", None)
        assert remote.admit_tile("t", tid, values)
        # stats snapshots share one schema
        assert set(remote.stats()) == set(local.stats())
        # a remote service error comes back as RemoteError, not a hang
        bad = remote.submit("nope", _idx(2))
        _, fail = remote.flush()
        assert isinstance(fail[bad], RemoteError)
        assert "nope" in str(fail[bad])
        with pytest.raises(RemoteError, match="no payload"):
            remote.shape_of("ghost")
        # ...and the transport is still healthy afterwards
        rid = remote.submit("t", batches[0])
        res, fail = remote.flush()
        assert not fail
        np.testing.assert_array_equal(res[rid], l_res[l_tickets[0]])
    finally:
        remote.close()
    with pytest.raises(TransportError):  # closed transports fail fast
        remote.submit("t", batches[0])


def test_spawn_refused_on_tpu_host(monkeypatch):
    """On a TPU host a spawned worker would reach for the chip this
    process holds: spawn refuses before starting any child."""
    import subprocess

    import jax

    def no_child(*a, **kw):
        raise AssertionError("spawn started a child process")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen", no_child)
    with pytest.raises(RuntimeError, match="device of its own"):
        SocketTransport.spawn("w0")


def test_spawned_socket_dir_removed_on_close(payload_path):
    remote = _spawn("w0")
    sock_dir = remote._owned_dir
    assert sock_dir is not None and os.path.isdir(sock_dir)
    remote.close()
    assert not os.path.exists(sock_dir)  # no /tmp litter per spawn


def test_spawn_instance_replay_failure_closes_transport(payload_path, tmp_path):
    """A joiner whose payload replay fails must be closed (its worker
    process reaped), not leaked outside fleet.transports."""

    class FailingTransport(LocalTransport):
        closed = False

        def load_stream(self, name, path, *, tile_entries=None):
            raise ValueError("replay boom")

        def close(self):
            FailingTransport.closed = True
            super().close()

    fleet = FleetFrontend(2)
    fleet.load_stream("t", payload_path)
    fleet._transport_factory = FailingTransport
    with pytest.raises(ValueError, match="replay boom"):
        rebalance(fleet, add=["i9"])
    assert "i9" not in fleet.transports
    assert FailingTransport.closed


def test_worker_closes_on_garbage_frame(payload_path):
    remote = _spawn("w0")
    try:
        remote.load_stream("t", payload_path)
        # a length prefix promising more bytes than ever arrive: the worker
        # must treat it as a protocol error and close — not hang waiting
        remote._sock.sendall(struct.pack("<I", 64) + b"garbage")
        remote._sock.shutdown(socket.SHUT_WR)
        assert remote._proc.wait(timeout=10) == 0  # exited, no hang
    finally:
        remote.close()


# ---------------------------------------------------------------------------
# multi-process fleet: bit-identical + live rebalance (tentpole acceptance)
# ---------------------------------------------------------------------------
def test_socket_fleet_bit_identical_with_rebalance(payload_path):
    single = CodecService()
    single.load_stream("t", payload_path, tile_entries=64)
    fleet = FleetFrontend(
        ["w0", "w1", "w2"], transport_factory=lambda iid: _spawn(iid)
    )
    try:
        fleet.load_stream("t", payload_path, tile_entries=64)
        batches = [_idx(80, seed=s) for s in range(4)]
        refs = [single.decode_at("t", b) for b in batches]
        for b, ref in zip(batches, refs):
            np.testing.assert_array_equal(fleet.decode_at("t", b), ref)
        # live rebalance mid-query-stream: a real worker process retires
        pending = [fleet.submit("t", b) for b in batches[:2]]
        report = rebalance(fleet, remove=["w2"])
        out = fleet.flush()
        assert not fleet.failed  # ZERO failed tickets across the change
        assert report.removed == ["w2"]
        for t, ref in zip(pending, refs[:2]):
            np.testing.assert_array_equal(out[t], ref)
        assert fleet.instances() == ["w0", "w1"]
        for b, ref in zip(batches, refs):
            np.testing.assert_array_equal(fleet.decode_at("t", b), ref)
    finally:
        fleet.close()


def test_worker_killed_mid_batch_fails_cleanly_then_replica_serves(payload_path):
    """Kill a worker with tickets in flight: those tickets fail cleanly
    (no hang), the instance lands in ``excluded``, and with replication=2
    the very next query is served bit-identically by the survivor."""
    single = CodecService()
    single.load_stream("t", payload_path, tile_entries=64)
    fleet = FleetFrontend(
        ["w0", "w1"],
        replication=2,
        transport_factory=lambda iid: _spawn(iid),
    )
    try:
        fleet.load_stream("t", payload_path, tile_entries=64)
        idx = _idx(300)
        ref = single.decode_at("t", idx)
        np.testing.assert_array_equal(fleet.decode_at("t", idx), ref)
        victim = "w1"
        fleet.transports[victim]._proc.kill()
        tickets = [fleet.submit("t", _idx(40, seed=s)) for s in range(3)]
        t0 = time.monotonic()
        out = fleet.flush()  # must not hang on the dead socket
        assert time.monotonic() - t0 < 10
        assert victim in fleet.excluded
        assert isinstance(fleet.exclusion_errors[victim], TransportError)
        for t in tickets:  # every ticket resolved: result or clean failure
            assert (t in out) != (t in fleet.failed)
        # replication=2: every group still has a live owner -> full answers
        np.testing.assert_array_equal(fleet.decode_at("t", idx), ref)
        # the fleet still registers NEW payloads while a member is dead —
        # survivors load it; the corpse catches up at rebalance (never: it
        # is being removed below)
        fleet.load_stream("u", payload_path, tile_entries=64)
        single.load_stream("u", payload_path, tile_entries=64)
        np.testing.assert_array_equal(
            fleet.decode_at("u", idx), single.decode_at("u", idx)
        )
        # removing the dead member for real must not hang either
        report = rebalance(fleet, remove=[victim])
        assert report.removed == [victim]
        assert fleet.instances() == ["w0"] and not fleet.excluded
        np.testing.assert_array_equal(fleet.decode_at("t", idx), ref)
    finally:
        fleet.close()


def test_dead_worker_without_replicas_is_unroutable_error(payload_path):
    fleet = FleetFrontend(["w0"], transport_factory=lambda iid: _spawn(iid))
    try:
        fleet.load_stream("t", payload_path, tile_entries=64)
        fleet.transports["w0"]._proc.kill()
        with pytest.raises(TransportError):
            fleet.decode_at("t", _idx(10))  # the death itself, reported cleanly
        assert fleet.excluded == {"w0"}
        with pytest.raises(TransportError, match="every replica is excluded"):
            fleet.decode_at("t", _idx(10))  # now routed around — and empty
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# client-side failure modes against a fake server (no worker spawn)
# ---------------------------------------------------------------------------
def _fake_server(behavior):
    """A one-connection TCP server running ``behavior(conn)`` in a thread."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def run():
        conn, _ = srv.accept()
        with conn:
            behavior(conn)
        srv.close()

    threading.Thread(target=run, daemon=True).start()
    return f"tcp:127.0.0.1:{port}"


def test_truncated_response_is_transport_error_not_hang():
    def truncate(conn):
        conn.recv(1 << 16)  # swallow the request
        conn.sendall(struct.pack("<I", 500) + b"half a frame")
        # close without sending the rest

    addr = _fake_server(truncate)
    t = SocketTransport("fake", addr, timeout=5.0, connect_timeout=5.0)
    with pytest.raises(TransportError, match="truncated"):
        t.ping()
    with pytest.raises(TransportError):  # dead from then on, fails fast
        t.stats()


def test_unresponsive_server_hits_request_timeout():
    def stall(conn):
        conn.recv(1 << 16)
        time.sleep(5)  # never answer

    addr = _fake_server(stall)
    t = SocketTransport("fake", addr, timeout=0.5, connect_timeout=5.0)
    t0 = time.monotonic()
    with pytest.raises(TransportError, match="timed out"):
        t.ping()
    assert time.monotonic() - t0 < 3  # the timeout bounded the wait


def test_out_of_order_response_id_is_protocol_error():
    def wrong_rid(conn):
        payload = recv_frame(conn)
        (_, rid) = struct.unpack("<BQ", payload[:9])
        send_frame(conn, struct.pack("<BQ", 0, rid + 999))

    addr = _fake_server(wrong_rid)
    t = SocketTransport("fake", addr, timeout=5.0, connect_timeout=5.0)
    with pytest.raises(ProtocolError, match="response id"):
        t.ping()


def test_connect_retry_gives_up_with_clear_error():
    with pytest.raises(TransportError, match="could not connect"):
        SocketTransport(
            "ghost", "unix:/tmp/definitely-not-a-socket-xyz.sock",
            connect_timeout=0.5, retry_delay=0.1,
        )


# ---------------------------------------------------------------------------
# tracing is observational only: answers + counters identical on or off
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["local", "socket"])
def test_tracing_on_off_bit_identical(payload_path, kind, monkeypatch):
    from repro import obs

    def run(traced: bool):
        monkeypatch.setenv("REPRO_TRACE", "1" if traced else "0")
        if traced:
            obs.enable_tracing()
            obs.get_recorder().clear()
        else:
            obs.disable_tracing()
        t = (
            LocalTransport("l0")
            if kind == "local"
            else _spawn("w0")  # worker inherits REPRO_TRACE from the env
        )
        try:
            t.load_stream("t", payload_path, tile_entries=64)
            tickets = [t.submit("t", _idx(n, seed=n)) for n in (3, 57, 200)]
            results, failures = t.flush()
            assert not failures
            return [results[k] for k in tickets], t.stats()
        finally:
            t.close()

    try:
        res_off, stats_off = run(traced=False)
        res_on, stats_on = run(traced=True)
    finally:
        obs.disable_tracing()
        obs.get_recorder().clear()
    for a, b in zip(res_off, res_on):
        np.testing.assert_array_equal(a, b)  # bit-exact
        assert a.dtype == b.dtype
    assert stats_off == stats_on  # every cache counter identical
