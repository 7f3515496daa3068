"""Gateway: the volunteer protocol over a real loopback socket.

The same engine-free volunteer loop (``run_volunteer`` on a
``VolunteerSession``) must complete a training run over a TCP socket exactly
as it does over direct in-process calls — the end-to-end proof that the
sans-IO protocol layer owns ALL the rules and the transport is swappable.
"""
from __future__ import annotations

import socket
import struct
import threading
import time

import pytest

from repro.core import gateway
from repro.core.gateway import (GatewayServer, SocketTransport,
                                WsClientTransport, _recv_frame, _send_frame,
                                run_volunteer)
from repro.core.protocol import Hello
from repro.core.simulator import SyntheticProblem
from repro.core.transport import InProcessTransport

N_VERSIONS, N_MB = 3, 4
N_TASKS = N_VERSIONS * (N_MB + 1)


def _problem():
    return SyntheticProblem(n_versions=N_VERSIONS, n_mb=N_MB)


@pytest.fixture
def server():
    s = GatewayServer(_problem(), n_versions=N_VERSIONS)
    s.start()
    yield s
    s.close()


def test_single_volunteer_over_socket(server):
    transport = SocketTransport("127.0.0.1", server.port, "sock0")
    final, tasks = run_volunteer(transport, "sock0", N_VERSIONS)
    transport.close()
    assert final == N_VERSIONS
    assert tasks == N_TASKS
    assert server.ds.latest_version == N_VERSIONS
    assert server.done.is_set()


def test_socket_run_matches_inprocess_run(server):
    ref_server = GatewayServer(_problem(), n_versions=N_VERSIONS)
    ref = run_volunteer(InProcessTransport(ref_server.endpoint), "ref",
                        N_VERSIONS)
    ref_server.close()
    transport = SocketTransport("127.0.0.1", server.port, "sock0")
    out = run_volunteer(transport, "sock0", N_VERSIONS)
    transport.close()
    assert out == ref == (N_VERSIONS, N_TASKS)


def test_barrierless_policy_over_socket_uses_server_applier():
    """Under staleness:<s> the gateway hosts a ServerApplier: the socket
    volunteer commits every update with one SubmitUpdate and never sends a
    PublishModel or an admission-time FetchModel pair."""
    s = GatewayServer(_problem(), n_versions=N_VERSIONS, policy="staleness:1")
    s.start()
    try:
        n_updates = s.n_updates
        assert n_updates == N_VERSIONS * N_MB      # one version per gradient
        transport = SocketTransport("127.0.0.1", s.port, "thin0")
        final, tasks = run_volunteer(transport, "thin0", n_updates,
                                     policy="staleness:1")
        sent = dict(transport.sent)
        transport.close()
        assert final == n_updates
        assert tasks == n_updates
        assert sent.get("SubmitUpdate") == n_updates
        assert "PublishModel" not in sent
        assert s.endpoint.applier.applied == n_updates
        assert s.done.is_set()
    finally:
        s.close()


def test_two_volunteers_share_the_run(server):
    """Cross-client coordination over the socket: pushed Wake/VersionReady
    frames must wake the volunteer blocked on the other one's progress."""
    results = {}

    def worker(vid):
        transport = SocketTransport("127.0.0.1", server.port, vid)
        results[vid] = run_volunteer(transport, vid, N_VERSIONS)
        transport.close()

    threads = [threading.Thread(target=worker, args=(f"gw{i}",), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "volunteer deadlocked over the socket"
    finals = [results[v][0] for v in sorted(results)]
    tasks = [results[v][1] for v in sorted(results)]
    assert finals == [N_VERSIONS, N_VERSIONS]
    assert sum(tasks) == N_TASKS          # every task done exactly once
    assert server.ds.latest_version == N_VERSIONS


# ---------------------------------------------------------------------------
# dual dialect: the same run over WebSocket framing
# ---------------------------------------------------------------------------

def test_ws_volunteer_matches_tcp_run(server):
    """The tentpole equivalence: a WebSocket-framed volunteer finishes the
    identical run a native-TCP volunteer does, on the same server port."""
    ref_server = GatewayServer(_problem(), n_versions=N_VERSIONS)
    ref_server.start()
    ref_tr = SocketTransport("127.0.0.1", ref_server.port, "tcp0")
    ref = run_volunteer(ref_tr, "tcp0", N_VERSIONS)
    ref_tr.close()
    ref_server.close()
    transport = WsClientTransport("127.0.0.1", server.port, "ws0")
    out = run_volunteer(transport, "ws0", N_VERSIONS)
    transport.close()
    assert out == ref == (N_VERSIONS, N_TASKS)
    assert server.ds.latest_version == N_VERSIONS


def test_ws_and_tcp_volunteers_share_one_run(server):
    """One port, both dialects, one run: cross-dialect Wake/VersionReady
    pushes must coordinate a WS volunteer with a TCP volunteer."""
    results = {}

    def worker(vid, cls):
        tr = cls("127.0.0.1", server.port, vid)
        results[vid] = run_volunteer(tr, vid, N_VERSIONS)
        tr.close()

    threads = [
        threading.Thread(target=worker, args=("ws0", WsClientTransport),
                         daemon=True),
        threading.Thread(target=worker, args=("tcp0", SocketTransport),
                         daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "volunteer deadlocked across dialects"
    assert [results[v][0] for v in sorted(results)] == [N_VERSIONS] * 2
    assert sum(results[v][1] for v in results) == N_TASKS


def test_non_ws_http_request_is_rejected_cleanly(server):
    """A GET that is not a well-formed WS upgrade gets a 400 and a close,
    and the server stays healthy for the next volunteer."""
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    sock.sendall(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
    sock.settimeout(5)
    data = b""
    while b"\r\n\r\n" not in data:
        chunk = sock.recv(4096)
        if not chunk:
            break
        data += chunk
    sock.close()
    assert data.startswith(b"HTTP/1.1 400")
    tr = SocketTransport("127.0.0.1", server.port, "after400")
    assert run_volunteer(tr, "after400", N_VERSIONS) == (N_VERSIONS, N_TASKS)
    tr.close()


# ---------------------------------------------------------------------------
# satellite regressions: the socket framing bugfix pass
# ---------------------------------------------------------------------------

def test_sock_timeout_restored_when_exception_escapes():
    """Regression (timeout leak): an exception raised inside a timed
    section must not leak the scoped timeout onto the socket — the next
    frame read would get a surprise socket.timeout and desync the stream."""
    a, b = socket.socketpair()
    try:
        a.settimeout(7.5)
        with pytest.raises(RuntimeError):
            with gateway._sock_timeout(a, 0.01):
                assert a.gettimeout() == 0.01
                raise RuntimeError("injected fault mid-section")
        assert a.gettimeout() == 7.5          # restored despite the raise
        # nesting restores the OUTER scope's value, not the default
        with gateway._sock_timeout(a, 1.0):
            with gateway._sock_timeout(a, 2.0):
                assert a.gettimeout() == 2.0
            assert a.gettimeout() == 1.0
        assert a.gettimeout() == 7.5
    finally:
        a.close()
        b.close()


def test_wait_notification_fault_does_not_leak_timeout(server, monkeypatch):
    """The integration face of the same bug: a decode fault inside a timed
    wait_notification must leave the socket back at blocking (None), so the
    transport is still usable for aligned reads afterwards."""
    tr = SocketTransport("127.0.0.1", server.port, "leak0")
    assert tr.sock.gettimeout() is None
    assert tr.wait_notification(0.2) is None      # clean idle timeout
    assert tr.sock.gettimeout() is None

    def boom(sock):
        raise RuntimeError("injected decode fault")

    monkeypatch.setattr(gateway, "_recv_frame", boom)
    with pytest.raises(RuntimeError, match="injected"):
        tr.wait_notification(0.2)
    monkeypatch.undo()
    assert tr.sock.gettimeout() is None           # no stale 0.2 s timeout
    # the stream is still aligned: a real call round-trips fine
    from repro.core.protocol import LatestReq
    assert tr.call(LatestReq()).version == 0
    tr.close()


def test_oversize_length_prefix_closes_connection_server_side(server):
    """Regression (MAX_FRAME): a hostile u32 length prefix must close the
    connection with a logged protocol error — never drive an allocation —
    and the server must stay healthy for the next volunteer."""
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    _send_frame(sock, Hello("big0"))
    assert _recv_frame(sock) is not None          # bound normally first
    sock.sendall(struct.pack(">I", gateway.MAX_FRAME + 1))
    sock.settimeout(5)
    assert sock.recv(4096) == b""                 # server closed on us
    sock.close()
    tr = SocketTransport("127.0.0.1", server.port, "afterbig")
    assert run_volunteer(tr, "afterbig", N_VERSIONS) == (N_VERSIONS, N_TASKS)
    tr.close()


def test_oversize_length_prefix_closes_connection_client_side():
    """Same cap on the client: a corrupt length prefix from the server side
    surfaces as a clean ConnectionError, not a multi-GB recv loop."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def fake_server():
        conn, _ = lsock.accept()
        conn.recv(1 << 16)                        # swallow the Hello frame
        conn.sendall(struct.pack(">I", gateway.MAX_FRAME + 1) + b"junk")
        try:
            conn.recv(1)                          # hold open until client acts
        except OSError:
            pass                                  # client reset us — expected

    t = threading.Thread(target=fake_server, daemon=True)
    t.start()
    with pytest.raises(ConnectionError):
        SocketTransport("127.0.0.1", port, "dupe0", connect_timeout=5)
    lsock.close()


def test_mid_frame_stall_tears_down_via_endpoint_disconnect(
        server, monkeypatch):
    """Regression (half-open teardown): a client that sends a length header
    and then goes silent must be torn down through endpoint.disconnect —
    not a bare close — so its waiters/subscriptions are dropped."""
    monkeypatch.setattr(gateway, "FRAME_STALL_TIMEOUT", 0.3)
    dropped = []
    orig = server.endpoint.disconnect
    monkeypatch.setattr(server.endpoint, "disconnect",
                        lambda c: (dropped.append(c), orig(c))[1])
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    _send_frame(sock, Hello("stall0"))
    assert _recv_frame(sock) is not None          # registered as a consumer
    sock.sendall(struct.pack(">I", 64))           # header, then... nothing
    deadline = time.monotonic() + 5.0
    while "stall0" not in dropped:
        assert time.monotonic() < deadline, \
            "server never disconnected the mid-frame staller"
        time.sleep(0.02)
    sock.settimeout(5)
    assert sock.recv(4096) == b""                 # connection torn down
    sock.close()


def test_volunteer_killed_between_header_and_body(server, monkeypatch):
    """The abrupt-death variant: the socket dies (not stalls) between the
    length header and the body — same teardown path, same disconnect."""
    monkeypatch.setattr(gateway, "FRAME_STALL_TIMEOUT", 0.3)
    dropped = []
    orig = server.endpoint.disconnect
    monkeypatch.setattr(server.endpoint, "disconnect",
                        lambda c: (dropped.append(c), orig(c))[1])
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    _send_frame(sock, Hello("corpse0"))
    assert _recv_frame(sock) is not None
    sock.sendall(struct.pack(">I", 64))           # header only...
    sock.close()                                  # ...then the tab closes
    deadline = time.monotonic() + 5.0
    while "corpse0" not in dropped:
        assert time.monotonic() < deadline, \
            "server never disconnected the dead half-frame client"
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# torn writes: byte-level delivery, both dialects
# ---------------------------------------------------------------------------

def _dribble(sock, data: bytes, chunk: int = 1) -> None:
    for i in range(0, len(data), chunk):
        sock.sendall(data[i:i + chunk])
        time.sleep(0.001)


def test_torn_tcp_writes_reassemble_cleanly(server):
    """A native frame arriving one byte at a time must dispatch exactly
    once, intact; a partial frame must get NO reply until completed."""
    from repro.core.protocol import LatestReq, encode_message
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    body = encode_message(Hello("torn0"))
    _dribble(sock, struct.pack(">I", len(body)) + body)
    assert _recv_frame(sock) is not None          # one intact dispatch
    # now leave a frame half-written: no reply may arrive for it
    body2 = encode_message(LatestReq())
    frame2 = struct.pack(">I", len(body2)) + body2
    sock.sendall(frame2[:len(frame2) // 2])
    sock.settimeout(0.5)
    with pytest.raises(socket.timeout):
        sock.recv(4096)                           # half a frame, no dispatch
    sock.settimeout(5)
    sock.sendall(frame2[len(frame2) // 2:])       # complete it
    reply = _recv_frame(sock)
    assert reply is not None and reply.version == 0
    sock.close()


def test_torn_ws_writes_reassemble_cleanly(server):
    """The WS equivalent, harder: the upgrade, then a Hello fragmented into
    WS continuation frames AND dribbled byte-by-byte. The server must
    dispatch the one reassembled message and reply with one WS message."""
    from repro.core import wsframing as wf
    from repro.core.protocol import decode_message, encode_message
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    request, key = wf.client_handshake_request(f"127.0.0.1:{server.port}")
    _dribble(sock, request, chunk=3)
    handshake = wf.ClientHandshake(key)
    sock.settimeout(5)
    while not handshake.done:
        handshake.feed(sock.recv(4096))
    framer = wf.client_framer()
    if handshake.leftover:
        framer.feed(handshake.leftover)
    wire = framer.send_message(encode_message(Hello("wstorn0")),
                               fragment_size=5)
    _dribble(sock, wire)                          # fragments, byte by byte
    events = []
    while not events:
        events = framer.feed(sock.recv(4096))
    assert len(events) == 1 and isinstance(events[0], wf.Message)
    assert decode_message(events[0].data) is not None
    sock.close()
