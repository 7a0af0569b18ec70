"""The reactor core (ISSUE 9): loop mechanics, incremental frame
assembly, slow-reader backpressure, the N=8 echo micro-bench against
the thread-per-connection baseline, inline probe serving, and the
master:reactor readiness/status surfaces.

The chaos suite (tests/test_chaos.py) is the regression harness for
the PORT itself — fencing, reconnect-through-kill, trace propagation
and 2-slave convergence under none/int8/topk all run over the reactor
now, unchanged.
"""

import socket
import socketserver
import struct
import threading
import time

import pytest

from veles import reactor
from veles.server import (MasterServer, framed_server, recv_frame,
                          send_frame)
from tests.test_service import make_wf


@pytest.fixture(autouse=True)
def _mnist_config_guard():
    """make_wf (tests/test_service.py) mutates root.mnist without
    restoring; tests here must not leak that config into later files
    (the same guard idiom as tests/test_health.py)."""
    from veles.config import root
    # the sample's module-level defaults must be in root BEFORE the
    # snapshot, or a never-touched key restores as an explicit None
    from veles.znicz_tpu.models import mnist  # noqa: F401
    saved_loader = {k: root.mnist.loader.get(k)
                    for k in ("minibatch_size", "n_train", "n_valid")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    yield
    root.mnist.loader.update(saved_loader)
    root.mnist.decision.max_epochs = saved_epochs


def _drain(sock):
    try:
        sock.close()
    except OSError:
        pass


# -- loop mechanics ----------------------------------------------------


def test_call_soon_crosses_threads_and_timers_fire_in_order():
    loop = reactor.get_reactor()
    seen = []
    done = threading.Event()
    loop.call_soon(seen.append, "soon")
    loop.call_later(0.02, seen.append, "later-20ms")
    loop.call_later(0.001, seen.append, "later-1ms")
    loop.call_later(0.05, lambda: (seen.append("last"), done.set()))
    assert done.wait(5.0), seen
    assert seen == ["soon", "later-1ms", "later-20ms", "last"]
    assert not loop.in_loop()           # we are the test thread


def test_every_rearms_until_cancelled():
    loop = reactor.get_reactor()
    hits = []
    timer = loop.every(0.01, lambda: hits.append(1))
    deadline = time.monotonic() + 5.0
    while len(hits) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(hits) >= 3
    timer.cancel()
    time.sleep(0.05)
    frozen = len(hits)
    time.sleep(0.1)
    assert len(hits) <= frozen + 1      # at most one in-flight firing


def test_loop_lag_gauge_updates():
    loop = reactor.get_reactor()
    from veles import telemetry
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        fams = {f.name for f in telemetry.get_registry().families()}
        if "veles_reactor_loop_lag_seconds" in fams:
            break
        time.sleep(0.05)
    assert "veles_reactor_loop_lag_seconds" in fams
    # a healthy idle loop lags microseconds, never seconds
    assert loop.loop_lag_s < 1.0


# -- framed assembly over the reactor ----------------------------------


def _echo_server():
    done = threading.Event()
    server = framed_server(("127.0.0.1", 0), lambda req: req, done,
                           lambda sid, clean=False: None)
    return server


def test_framed_echo_assembles_fragmented_frames():
    """A frame dripped one byte at a time (header, tag and payload
    all fragmented) must assemble incrementally and echo back whole —
    the blocking-recv-loop behavior, reproduced by the state
    machine."""
    server = _echo_server()
    try:
        sock = socket.create_connection(server.server_address,
                                        timeout=10)
        payload = ("echo", 42, b"z" * 257)
        import hashlib
        import hmac as hmac_mod
        import pickle
        from veles.server import _secret
        blob = pickle.dumps(payload, protocol=5)
        tag = hmac_mod.new(_secret(), blob, hashlib.sha256).digest()
        frame = struct.pack(">I", len(blob)) + tag + blob
        for i in range(0, len(frame), 7):      # 7-byte drip
            sock.sendall(frame[i:i + 7])
            if i < 64:
                time.sleep(0.001)              # force tiny reads
        assert recv_frame(sock) == payload
        # a second, normally-sent frame still works on the same
        # connection (no leftover assembly state)
        send_frame(sock, ("echo", 2))
        assert recv_frame(sock) == ("echo", 2)
        _drain(sock)
    finally:
        server.server_close()


def test_framed_rejects_tampered_hmac_and_oversized_header():
    server = _echo_server()
    try:
        # tampered byte -> the server refuses to deserialize and
        # severs the connection
        sock = socket.create_connection(server.server_address,
                                        timeout=10)
        import hashlib
        import hmac as hmac_mod
        import pickle
        from veles.server import _secret
        blob = pickle.dumps(("echo", 1), protocol=5)
        tag = hmac_mod.new(_secret(), blob, hashlib.sha256).digest()
        bad = bytearray(blob)
        bad[-1] ^= 1
        sock.sendall(struct.pack(">I", len(bad)) + tag + bytes(bad))
        assert recv_frame(sock) is None        # server hung up
        _drain(sock)

        # oversized length header -> dropped before any allocation
        sock = socket.create_connection(server.server_address,
                                        timeout=10)
        sock.sendall(struct.pack(">I", (1 << 30) + 1) + b"\0" * 32)
        assert recv_frame(sock) is None
        _drain(sock)

        # and the server is still alive for a healthy peer
        sock = socket.create_connection(server.server_address,
                                        timeout=10)
        send_frame(sock, ("echo", 3))
        assert recv_frame(sock) == ("echo", 3)
        _drain(sock)
    finally:
        server.server_close()


# -- slow-reader backpressure (ISSUE 9 satellite) ----------------------


def test_slow_reader_drops_at_write_queue_cap():
    """A stalled slave connection accumulates a BOUNDED reply queue
    and is dropped at the cap with a counted fault
    (``backpressure_drops``); its lease revokes, its jobs requeue,
    and a healthy slave then finishes the run — the stall never
    blocks the merge path."""
    from veles.client import SlaveClient
    wf = make_wf("BackpressureMaster", max_epochs=None)
    wf.decision.max_epochs = 2
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=30.0,
                          max_write_buffer=1 << 16)
    server.start_background()

    # shrink BOTH kernel buffers (client receive before connect —
    # loopback autotune can otherwise swallow megabytes of replies
    # in flight and starve the server-side queue of growth)
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8192)
    sock.settimeout(10)
    sock.connect(server.bound_address)
    send_frame(sock, ("hello", "stall", "none"))
    _, sid, lease = recv_frame(sock)[:3]
    # ... and the server side's send buffer, so queued replies land
    # in the reactor's write queue, not the kernel's
    deadline = time.time() + 10
    conn = None
    while time.time() < deadline and conn is None:
        for c in server._server.connections():
            if c.slave_id == sid:
                conn = c
        time.sleep(0.01)
    assert conn is not None
    conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)

    # flood job requests and NEVER read a reply: each response is a
    # weight-carrying payload, so the reply queue must hit the cap
    deadline = time.time() + 30
    while time.time() < deadline \
            and server.faults["backpressure_drops"] < 1:
        try:
            send_frame(sock, ("job", sid, lease))
        except OSError:
            break                       # server dropped us: done
        time.sleep(0.002)
    deadline = time.time() + 10
    while time.time() < deadline \
            and server.faults["backpressure_drops"] < 1:
        time.sleep(0.02)
    st = server.status()
    assert st["faults"]["backpressure_drops"] >= 1, st
    assert st["faults"]["drops"] >= 1, st       # lease revoked too
    assert str(sid) not in st["slaves"], st
    _drain(sock)

    # the merge path was never blocked: a healthy slave completes
    healthy = make_wf("BackpressureHealthy")
    healthy.is_slave = True
    SlaveClient(healthy, "127.0.0.1:%d" % server.bound_address[1],
                name="healthy", io_timeout=10.0).run_forever()
    assert server.done.is_set()


def test_status_reports_per_slave_write_queue_depth():
    wf = make_wf("DepthMaster", max_epochs=None)
    wf.decision.max_epochs = 2
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    try:
        sock = socket.create_connection(server.bound_address,
                                        timeout=10)
        send_frame(sock, ("hello", "depth", "none"))
        _, sid, _lease = recv_frame(sock)[:3]
        row = server.status()["slaves"][str(sid)]
        # a healthy, fully-drained connection queues nothing
        assert row["write_queue_bytes"] == 0
        _drain(sock)
    finally:
        server.kill()


# -- acceptance: N=8 echo micro-bench ----------------------------------


def _run_echo_clients(port, n=8, duration=0.5, payload=b"x" * 512):
    counts = [0] * n
    stop = time.perf_counter() + duration
    errors = []

    def client(i):
        try:
            s = socket.create_connection(("127.0.0.1", port),
                                         timeout=10)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            frame = ("echo", i, payload)
            while time.perf_counter() < stop:
                send_frame(s, frame)
                if recv_frame(s)[0] != "echo":
                    raise AssertionError("bad echo")
                counts[i] += 1
            _drain(s)
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return sum(counts) / duration


def _threaded_echo_baseline():
    """The pre-ISSUE-9 shape: one blocking thread per connection."""

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            self.request.setsockopt(socket.IPPROTO_TCP,
                                    socket.TCP_NODELAY, 1)
            try:
                while True:
                    req = recv_frame(self.request)
                    if req is None:
                        break
                    send_frame(self.request, req)
            except (ConnectionError, OSError):
                pass

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    return Server(("127.0.0.1", 0), Handler)


def test_echo_reactor_at_least_threaded_throughput_8_conns():
    """Acceptance (ISSUE 9): with 8 concurrent connections hammering
    framed echo round-trips, the single-threaded reactor must be no
    slower than the thread-per-connection baseline (measured ~3x
    faster here — no GIL-contended thread wakeup per frame). Retried
    to keep CI scheduling noise from flaking an honest >= bound."""
    last = None
    for _ in range(3):
        baseline = _threaded_echo_baseline()
        threading.Thread(target=baseline.serve_forever,
                         daemon=True).start()
        threaded = _run_echo_clients(baseline.server_address[1])
        baseline.shutdown()
        baseline.server_close()

        server = _echo_server()
        try:
            looped = _run_echo_clients(server.server_address[1])
        finally:
            server.server_close()
        last = (looped, threaded)
        if looped >= threaded:
            return
    pytest.fail("reactor echo slower than threaded baseline across "
                "3 attempts: reactor %.0f rt/s vs threaded %.0f rt/s"
                % last)


# -- HTTP plane on the loop --------------------------------------------


def test_probes_answer_inline_without_thread_per_request():
    """/healthz and /metrics on web-status are served ON the loop:
    50 sequential probe requests spawn zero worker threads (only the
    provider-pulling routes defer)."""
    import urllib.request
    from veles.web_status import WebStatus
    status = WebStatus(port=0)
    try:
        base = "http://127.0.0.1:%d" % status.port
        urllib.request.urlopen(base + "/healthz", timeout=10).read()
        before = threading.active_count()
        for _ in range(50):
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as resp:
                assert resp.status == 200
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as resp:
                assert resp.status == 200
        assert threading.active_count() <= before + 1
        # the deferred route still works (worker-thread handoff)
        with urllib.request.urlopen(base + "/status.json",
                                    timeout=10) as resp:
            assert resp.status == 200
    finally:
        status.close()


def test_fleet_scrape_reports_reactor_lag():
    """velescli top's scraper surfaces the per-target reactor loop
    lag once the lag probe has ticked into the registry."""
    from veles.fleet import scrape_target
    from veles.web_status import WebStatus
    status = WebStatus(port=0)
    try:
        deadline = time.monotonic() + 5.0
        row = {}
        while time.monotonic() < deadline:
            row = scrape_target("http://127.0.0.1:%d" % status.port,
                                timeout=10)
            if "reactor_lag_s" in row.get("metrics", {}):
                break
            time.sleep(0.1)
        assert "reactor_lag_s" in row["metrics"], row
        assert row["metrics"]["reactor_lag_s"] < 1.0
    finally:
        status.close()


def test_current_lag_observes_a_wedged_loop():
    """loop_lag_s is the loop's SELF-measurement — a wedged loop
    freezes it near zero. current_lag() must instead grow while the
    loop is parked behind a blocking callback (what the
    master:reactor readiness check reads)."""
    loop = reactor.get_reactor()
    started = threading.Event()
    release = threading.Event()

    def wedge():
        started.set()
        release.wait(5.0)           # deliberately blocks the loop

    loop.call_soon(wedge)
    assert started.wait(5.0)
    time.sleep(0.8)                 # probe now overdue by ~0.5s
    try:
        assert loop.current_lag() > 0.3, loop.current_lag()
    finally:
        release.set()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and loop.current_lag() > 0.3:
        time.sleep(0.05)
    assert loop.current_lag() < 0.3     # recovered


def test_accept_factory_failure_keeps_listener_alive(monkeypatch):
    """One failing connection construction must cost THAT connection
    only — never tear down the acceptor (which would silently stop
    the listener forever while `accepting` stayed True)."""
    server = _echo_server()
    try:
        boom = {"n": 1}
        real = server.build_connection

        def flaky(sock, addr):
            if boom["n"]:
                boom["n"] -= 1
                raise RuntimeError("transient factory failure")
            return real(sock, addr)

        monkeypatch.setattr(server, "build_connection", flaky)
        victim = socket.create_connection(server.server_address,
                                          timeout=10)
        # the victim's connection dies...
        assert recv_frame(victim) is None
        _drain(victim)
        # ...but the listener survives and still accepts
        sock = socket.create_connection(server.server_address,
                                        timeout=10)
        send_frame(sock, ("echo", 1))
        assert recv_frame(sock) == ("echo", 1)
        assert server.accepting
        _drain(sock)
    finally:
        server.server_close()


def test_http_bad_content_length_answers_400():
    """A garbled or negative Content-Length must answer 400 like the
    old threaded frontend did, not drop the connection replyless."""
    from veles.web_status import WebStatus
    status = WebStatus(port=0)
    try:
        for value in ("abc", "-5"):
            sock = socket.create_connection(("127.0.0.1",
                                             status.port), timeout=10)
            sock.sendall(("POST /update HTTP/1.1\r\n"
                          "Host: x\r\nContent-Length: %s\r\n\r\n"
                          % value).encode())
            reply = sock.recv(4096)
            assert reply.startswith(b"HTTP/1.1 400"), (value, reply)
            _drain(sock)
    finally:
        status.close()


def test_http_reply_larger_than_the_write_queue_arrives_whole(
        monkeypatch):
    """A Content-Length body bigger than ``max_write_buffer`` (a
    full-vocabulary logits reply is) is fed to the queue slice by
    slice as the socket drains and arrives intact — it used to
    overflow the queue and drop the connection mid-reply."""
    import urllib.request
    from veles import reactor
    monkeypatch.setattr(reactor, "DEFAULT_MAX_WRITE_BUFFER", 1 << 16)
    monkeypatch.setattr(reactor.HttpConnection, "BODY_SLICE", 1 << 14)
    # well past what the kernel's socket buffers swallow in one send
    body = bytes(range(256)) * (64 << 10)           # 16 MiB
    server = reactor.HttpServer(
        "127.0.0.1", 0,
        lambda request: request.reply(200, body,
                                      "application/octet-stream"))
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/" % server.port,
                timeout=30) as resp:
            assert resp.read() == body
    finally:
        server.close()


def test_http_connections_untracked_without_a_request():
    """TCP-only health checks (open, close, no HTTP request) must not
    accumulate connection objects in the server's tracking set."""
    from veles.web_status import WebStatus
    status = WebStatus(port=0)
    try:
        for _ in range(20):
            sock = socket.create_connection(("127.0.0.1",
                                             status.port), timeout=10)
            sock.close()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and status._server.connections():
            time.sleep(0.05)
        assert status._server.connections() == []
    finally:
        status.close()


def test_fenced_ping_severs_so_zombie_heartbeat_counts_once():
    """The send-only heartbeat cannot read the ("stale",) a fenced
    ping earns, so the server severs the connection after the reply
    drains — a zombie slave deep in a long compute stops beating at
    the first fence instead of inflating stale_pings once per
    ping_interval until its next round-trip."""
    wf = make_wf("StalePingMaster", max_epochs=None)
    wf.decision.max_epochs = 50
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=50)
    server.start_background()
    try:
        sock = socket.create_connection(server.bound_address,
                                        timeout=10)
        send_frame(sock, ("hello", "zombie", "none"))
        _, sid, lease = recv_frame(sock)[:3]
        server.drop_slave(sid)          # revoke out from under it
        send_frame(sock, ("ping", sid, lease))
        assert recv_frame(sock) == ("stale",)
        # the connection is severed after the fence: further beats
        # die at the socket, not at the fault counters
        assert recv_frame(sock) is None
        assert server.faults["stale_pings"] == 1
        _drain(sock)
    finally:
        server.kill()


# -- master:reactor readiness ------------------------------------------


def test_master_reactor_readiness_check():
    from veles import health
    from veles.health import HealthMonitor
    wf = make_wf("ReactorReadyMaster", max_epochs=None)
    wf.decision.max_epochs = 50
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=50)
    server.start_background()
    try:
        with health.scoped(HealthMonitor(interval=30.0)) as mon:
            server.register_health(mon)
            ok, reasons = mon.ready_state()
            assert ok is True, reasons
            doc = mon.probe("/readyz")[1]
            assert doc["checks"]["master:reactor"]["ok"] is True
            # an impossible lag threshold flips the check with a
            # reason naming the lag
            server.reactor_lag_ready_s = -1.0
            mon.tick()
            ok, reasons = mon.ready_state()
            assert ok is False
            assert any("reactor loop lag" in r for r in reasons), \
                reasons
    finally:
        server.kill()
