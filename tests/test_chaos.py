"""Fault-tolerance layer under injected faults (ISSUE 2): leases +
fencing, drop→requeue, slave auto-reconnect, the ChaosProxy harness,
and the snapshot store's retry/circuit-breaker degradation.

Everything here is seeded/deterministic in its DECISIONS (what gets
dropped/duplicated is a fixed plan or a seeded PRNG, never wall-clock
luck); assertions are on convergence and counters, not on timing.
"""

import socket
import struct
import threading
import time

import numpy
import pytest

import veles.prng as prng
from veles.chaos import (C2S, S2C, DELAY, DROP, DUP, PASS, TRUNCATE,
                         ChaosEvent, ChaosProxy)
from veles.client import SlaveClient
from veles.distributable import DistributionRegistry
from veles.loader.base import CLASS_TRAIN
from veles.server import MasterServer, recv_frame, send_frame
from tests.test_service import make_wf


def run_iteration(wf):
    """What SlaveClient._run_iteration does on the numpy backend."""
    for u in wf.forwards:
        u.run()
    wf.evaluator.run()
    if wf.loader.minibatch_class == CLASS_TRAIN:
        for gd in reversed(wf.gds):
            gd.run()


def sequential_reference(max_epochs=2):
    """Fault-free single-process run over the exact master job order
    (shuffling disabled on both sides for parity), as in
    test_service.test_single_slave_matches_standalone."""
    ref = make_wf("ChaosRef")
    ref.loader.shuffle_enabled = False
    ref.loader._start_epoch(first=True)
    loader = ref.loader
    for _ in range(max_epochs * loader.effective_batches_per_epoch):
        loader.run()
        run_iteration(ref)
    return numpy.array(ref.forwards[0].weights.map_read().mem)


# -- lease fencing (deterministic, handle-level) -----------------------


def test_unknown_or_revoked_slave_is_fenced():
    """Satellite: job/update/ping from ids not in self.slaves (never
    helloed, or dropped) are rejected, not served/merged."""
    wf = make_wf("FenceUnknown", max_epochs=None)
    wf.decision.max_epochs = 2
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=2)

    assert server.handle(("job", 999, "bogus")) == ("stale",)
    assert server.handle(("ping", 999, "bogus")) == ("stale",)
    assert server.handle(
        ("update", 999, "bogus", 1, 0, {})) == ("stale",)
    assert server.faults["stale_jobs"] == 1
    assert server.faults["stale_pings"] == 1
    assert server.faults["fenced_updates"] == 1

    # a real hello with a WRONG lease id is equally dead (a slave
    # from a previous master incarnation whose id got re-minted)
    kind, sid, lease = server.handle(("hello", "zombie"))
    assert kind == "welcome" and lease
    assert server.handle(("job", sid, "not-the-lease")) == ("stale",)
    assert server.handle(("ping", sid, lease)) == ("pong", 0)

    # dropping the slave revokes the lease outright
    server.drop_slave(sid)
    assert server.faults["drops"] == 1
    assert server.handle(("job", sid, lease)) == ("stale",)


def test_duplicate_update_fenced_weights_identical():
    """Satellite: replaying an already-applied update must leave the
    master weights BITWISE identical — the job_id was consumed, the
    duplicate is fenced instead of double-counted."""
    master_wf = make_wf("FenceMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    _, sid, lease = server.handle(("hello", "fence-slave"))

    slave_wf = make_wf("FenceSlave")
    slave_wf.is_slave = True
    sreg = DistributionRegistry(slave_wf)

    # pull jobs until a TRAIN minibatch (valid/test jobs carry no
    # weight delta, so a double-apply of them would prove nothing)
    loader_name = master_wf.loader.name
    for _ in range(64):
        resp = server.handle(("job", sid, lease))
        assert resp[0] == "job", resp
        _, payload, job_id, epoch = resp[:4]
        if payload[loader_name][0] == CLASS_TRAIN:
            break
    else:
        pytest.fail("no train job served")

    sreg.apply_job(payload)
    run_iteration(slave_wf)
    update = sreg.generate_update()

    assert server.handle(
        ("update", sid, lease, job_id, epoch, update)) == ("ok",)
    w_once = numpy.array(master_wf.forwards[0].weights.map_read().mem)
    # the replay: same lease, same job_id, same bytes
    assert server.handle(
        ("update", sid, lease, job_id, epoch, update)) == ("stale",)
    assert server.faults["fenced_updates"] == 1
    numpy.testing.assert_array_equal(
        master_wf.forwards[0].weights.map_read().mem, w_once)

    # stale-epoch fencing: a job minted now, acknowledged with a wrong
    # epoch tag, is refused too
    resp = server.handle(("job", sid, lease))
    if resp[0] == "job":
        _, payload2, job2, epoch2 = resp[:4]
        assert server.handle(
            ("update", sid, lease, job2, epoch2 + 1, {})) == ("stale",)


def test_mid_job_kill_requeues_and_completes():
    """Satellite: kill a slave mid-job (socket severed, no update) —
    the master requeues its minibatch within the timeout bound and a
    healthy slave finishes the run."""
    master_wf = make_wf("KillMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0)
    server.start_background()
    addr = server.bound_address

    # raw-frame slave: hello, take a job, die without updating
    sock = socket.create_connection(addr, timeout=10)
    send_frame(sock, ("hello", "doomed"))
    _, sid, lease = recv_frame(sock)
    send_frame(sock, ("job", sid, lease))
    resp = recv_frame(sock)
    assert resp[0] == "job"
    stolen_job = resp[1][master_wf.loader.name]
    # impolite death: RST, not FIN (SO_LINGER 0)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
    sock.close()

    deadline = time.time() + 10
    while time.time() < deadline and server.faults["drops"] < 1:
        time.sleep(0.02)
    st = server.status()
    assert st["faults"]["drops"] >= 1, st
    assert st["faults"]["requeued_jobs"] >= 1, st
    # the stolen minibatch is back at the head of the queue
    assert master_wf.loader._pending_jobs[0] == stolen_job

    healthy = make_wf("KillHealthy")
    healthy.is_slave = True
    client = SlaveClient(healthy, "127.0.0.1:%d" % addr[1],
                         name="healthy", io_timeout=10.0)
    client.run_forever()
    assert server.done.is_set()
    assert server.status()["faults"]["drops"] >= 1


def test_slave_reconnects_through_connection_kill():
    """Auto-reconnect: sever the slave's connection mid-run (via the
    proxy) — run_forever re-hellos on a fresh lease and finishes. The
    kill is an EVENT of the run, not a moment on the clock: the
    slave's third job request dies with every live connection, from
    inside the proxy's own pump."""
    master_wf = make_wf("ReconMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0)
    server.start_background()
    severed = []

    def plan(evt):
        if evt.direction == C2S and evt.kind == "job" \
                and evt.nth == 3 and not severed:
            severed.append(proxy.kill_all())
            return DROP
        return None

    with ChaosProxy(("127.0.0.1", server.bound_address[1]),
                    plan=plan) as proxy:
        slave_wf = make_wf("ReconSlave")
        slave_wf.is_slave = True
        client = SlaveClient(slave_wf, proxy.address, name="recon",
                             io_timeout=2.0, retry_base=0.02,
                             retry_max=0.2, max_retries=20)
        done = []
        t = threading.Thread(
            target=lambda: done.append(client.run_forever()))
        t.start()
        t.join(timeout=120)
        assert done, "slave did not survive the kill"
    assert severed == [1], "the kill never happened"
    assert server.done.is_set()
    assert client.reconnects >= 1
    # the master saw the connection END (the proxy hangs up on both
    # peers), long before the run's remaining jobs were served: no
    # waiting for its silent-peer sweep
    assert server.status()["faults"]["drops"] >= 1


def test_clean_completion_counts_no_faults():
    """A fault-free run must report ZERO drops/fenced updates — the
    counters measure degradation, and a polite bye is not a fault."""
    master_wf = make_wf("CleanMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    slave_wf = make_wf("CleanSlave")
    slave_wf.is_slave = True
    SlaveClient(slave_wf, "127.0.0.1:%d" % server.bound_address[1],
                name="clean").run_forever()
    assert server.done.is_set()
    st = server.status()
    assert st["faults"]["drops"] == 0, st
    assert st["faults"]["fenced_updates"] == 0, st
    assert st["faults"]["requeued_jobs"] == 0, st


# -- the acceptance chaos run ------------------------------------------


def _chaos_convergence_two_slaves(codec="none", topk_percent=25.0):
    """2 slaves through a ChaosProxy injecting seeded drops/delays,
    one duplicated update and one mid-job kill — training finishes,
    status() shows >=1 drop and >=1 fenced update, and the final
    master weights match the fault-free single-process UNCOMPRESSED
    run within tolerance (every minibatch merged exactly once;
    under a lossy ``codec``, error feedback must survive retries,
    re-hellos and fencing)."""
    w_ref = sequential_reference(max_epochs=2)

    master_wf = make_wf("ChaosMaster-%s" % codec, max_epochs=None)
    master_wf.loader.shuffle_enabled = False
    master_wf.loader._start_epoch(first=True)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0, grad_codec=codec,
                          grad_topk_percent=topk_percent)
    server.start_background()

    lock = threading.Lock()
    seen = {"updates": 0, "jobs": 0, "dup_done": False,
            "kill_done": False}

    def plan(evt):
        with lock:
            if evt.direction == C2S and evt.kind == "update":
                seen["updates"] += 1
                # exactly one duplicated update frame: the fence must
                # keep it from double-counting
                if seen["updates"] == 3 and not seen["dup_done"]:
                    seen["dup_done"] = True
                    return DUP
            if evt.direction == S2C and evt.kind == "job":
                seen["jobs"] += 1
                # exactly one mid-job kill: the job payload dies on
                # the wire, the connection is severed, the master must
                # requeue
                if seen["jobs"] == 5 and not seen["kill_done"]:
                    seen["kill_done"] = True
                    return TRUNCATE
        return None                   # fall through to seeded rates

    with ChaosProxy(("127.0.0.1", server.bound_address[1]), seed=1337,
                    plan=plan, drop_rate=0.01, delay_rate=0.10,
                    delay_s=0.01) as proxy:
        slaves = [make_wf("ChaosSlave%s%d" % (codec, i))
                  for i in range(2)]
        clients = []
        for wf in slaves:
            wf.is_slave = True
        errors = []

        def run_slave(wf, idx):
            client = SlaveClient(
                wf, proxy.address, name="chaos-%d" % idx,
                io_timeout=2.0, retry_base=0.02, retry_max=0.25,
                max_retries=25, grad_codec=codec,
                grad_topk_percent=topk_percent)
            clients.append(client)
            try:
                client.run_forever()
            except ConnectionError:
                # the master tears down after done: a slave caught
                # mid-reconnect is allowed to give up THEN, never
                # before
                if not server.done.is_set():
                    errors.append("gave up before done")

        threads = [threading.Thread(target=run_slave, args=(wf, i))
                   for i, wf in enumerate(slaves)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert server.done.is_set(), server.status()
        stats = proxy.stats()

    st = server.status()
    assert st["faults"]["drops"] >= 1, (st, stats)
    assert st["faults"]["fenced_updates"] >= 1, (st, stats)
    assert st["faults"]["codec_fallbacks"] == 0, st
    assert seen["dup_done"] and seen["kill_done"], (seen, stats)

    w_master = numpy.asarray(
        master_wf.forwards[0].weights.map_read().mem)
    assert numpy.isfinite(w_master).all()
    # exactly-once merge per minibatch: only slave-interleaving (and,
    # under a lossy codec, the bounded residual tail) keeps this from
    # being bitwise
    numpy.testing.assert_allclose(
        w_master, w_ref, atol=0.02,
        err_msg=str({"status": st, "proxy": stats}))
    if codec != "none":
        # the compression REALLY ran through the chaos: every re-
        # hello re-negotiated the codec and the tensor payloads
        # shrank (falsifiable: a silent fallback to 'none' would
        # leave encoded == raw)
        from veles import telemetry
        reg = telemetry.get_registry()
        raw = reg.counter_total("veles_grad_codec_raw_bytes_total",
                                codec=codec)
        enc = reg.counter_total(
            "veles_grad_codec_encoded_bytes_total", codec=codec)
        assert raw > 0, "codec never encoded a tensor"
        assert enc < raw * 0.55, (enc, raw)


def test_chaos_convergence_two_slaves():
    """Acceptance (ISSUE 2): the uncompressed chaos convergence run."""
    _chaos_convergence_two_slaves("none")


@pytest.mark.parametrize("codec", ["int8", "topk"])
def test_chaos_convergence_two_slaves_compressed(codec):
    """Acceptance (ISSUE 7): the same seeded drops/dups/mid-job-kill
    chaos run under a LOSSY gradient codec still lands within the
    existing 2e-2 atol of the fault-free uncompressed run — error
    feedback survives retries, duplicated updates and fencing."""
    _chaos_convergence_two_slaves(codec)


def test_trace_context_propagation_under_chaos():
    """Satellite (ISSUE 6): run 2 slaves through a ChaosProxy with one
    duplicated update and one mid-job kill, tracing enabled on the
    master — the merged trace must stay coherent: every traced span's
    trace_id roots at a ``job.dispatch`` span (no orphans), there is
    exactly ONE ``job.merge`` span per job_id (the duplicated update
    was fenced, not double-merged), and at least one job shows the
    full dispatch → wire → slave-compute → merge causal chain across
    both sides of the wire."""
    from veles import telemetry
    telemetry.tracer.start()
    master_wf = make_wf("TraceChaosMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0)
    server.start_background()

    lock = threading.Lock()
    seen = {"updates": 0, "jobs": 0, "dup_done": False,
            "kill_done": False}

    def plan(evt):
        with lock:
            if evt.direction == C2S and evt.kind == "update":
                seen["updates"] += 1
                if seen["updates"] == 3 and not seen["dup_done"]:
                    seen["dup_done"] = True
                    return DUP
            if evt.direction == S2C and evt.kind == "job":
                seen["jobs"] += 1
                if seen["jobs"] == 5 and not seen["kill_done"]:
                    seen["kill_done"] = True
                    return TRUNCATE
        return None

    with ChaosProxy(("127.0.0.1", server.bound_address[1]), seed=4242,
                    plan=plan) as proxy:
        slaves = [make_wf("TraceChaosSlave%d" % i) for i in range(2)]
        for wf in slaves:
            wf.is_slave = True

        def run_slave(wf, idx):
            try:
                SlaveClient(wf, proxy.address, name="trace-%d" % idx,
                            io_timeout=2.0, retry_base=0.02,
                            retry_max=0.25,
                            max_retries=25).run_forever()
            except ConnectionError:
                pass

        threads = [threading.Thread(target=run_slave, args=(wf, i))
                   for i, wf in enumerate(slaves)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert server.done.is_set(), server.status()
    telemetry.tracer.stop()
    assert seen["dup_done"] and seen["kill_done"], seen

    events = telemetry.tracer.events()
    traced = [e for e in events
              if e.get("args", {}).get("trace_id")]
    assert traced, "no trace-context spans recorded"
    roots = {e["args"]["trace_id"] for e in traced
             if e["name"] == "job.dispatch"}
    orphans = [e for e in traced
               if e["args"]["trace_id"] not in roots]
    assert not orphans, orphans[:3]

    merges = [e for e in events if e["name"] == "job.merge"]
    assert merges, "no merge spans"
    merge_jobs = [e["args"]["job_id"] for e in merges]
    assert len(merge_jobs) == len(set(merge_jobs)), \
        "a job_id was merged twice: %s" % sorted(merge_jobs)

    names_by_trace = {}
    for e in traced:
        names_by_trace.setdefault(
            e["args"]["trace_id"], set()).add(e["name"])
    want = {"job.dispatch", "job.wire", "slave.compute", "job.merge"}
    assert any(want <= names for names in names_by_trace.values()), \
        sorted(names_by_trace.values(), key=len)[-1]

    # the wire accounting rode along: both directions moved bytes
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_wire_bytes_total",
                             direction="tx") > 0
    assert reg.counter_total("veles_wire_bytes_total",
                             direction="rx") > 0

    # per-slave latency attribution reached the journal: the merge
    # path filled last-rtt/job/wire for the slaves it heard from
    # (slaves may have deregistered by now, so check via the trace's
    # wire spans instead of status())
    assert any(e["name"] == "job.wire" for e in traced)


def test_status_reports_per_slave_last_job_timing():
    """Satellite: one served+merged job fills the per-slave
    last_rtt_s/last_job_s/last_wire_s fields surfaced by status() —
    slow-slave skew is visible on the dashboard without a trace
    fetch."""
    wf = make_wf("TimingMaster", max_epochs=None)
    wf.decision.max_epochs = 2
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=2)
    _, sid, lease = server.handle(("hello", "timed"))
    st0 = server.status()["slaves"][str(sid)]
    assert st0["last_rtt_s"] is None and st0["last_job_s"] is None

    slave_wf = make_wf("TimingSlave")
    slave_wf.is_slave = True
    sreg = DistributionRegistry(slave_wf)
    resp = server.handle(("job", sid, lease))
    assert resp[0] == "job" and len(resp) >= 5
    # the job frame carries a trace context for the slave's spans
    from veles.telemetry import TraceContext
    assert TraceContext.from_wire(resp[4]) is not None
    _, payload, job_id, epoch = resp[:4]
    sreg.apply_job(payload)
    run_iteration(slave_wf)
    update = sreg.generate_update()
    update["__telemetry__"] = {"token": "t-timing",
                               "job_seconds": 0.004}
    assert server.handle(
        ("update", sid, lease, job_id, epoch, update)) == ("ok",)
    st = server.status()["slaves"][str(sid)]
    assert st["last_rtt_s"] is not None and st["last_rtt_s"] >= 0
    assert st["last_job_s"] == 0.004
    assert st["last_wire_s"] is not None
    assert abs(st["last_wire_s"]
               - max(st["last_rtt_s"] - 0.004, 0)) < 0.002


@pytest.mark.slow
def test_chaos_soak_heavy_rates():
    """Soak variant: sustained seeded drop/dup/delay rates over more
    epochs; completion + exactly-once accounting only (no weight
    parity — requeue reordering compounds)."""
    master_wf = make_wf("SoakMaster", max_epochs=None)
    master_wf.decision.max_epochs = 4
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=4,
                          slave_timeout=5.0)
    server.start_background()
    with ChaosProxy(("127.0.0.1", server.bound_address[1]), seed=99,
                    drop_rate=0.03, dup_rate=0.02, delay_rate=0.2,
                    delay_s=0.02) as proxy:
        slaves = [make_wf("SoakSlave%d" % i) for i in range(3)]
        for wf in slaves:
            wf.is_slave = True

        def run_slave(wf, idx):
            try:
                SlaveClient(wf, proxy.address, name="soak-%d" % idx,
                            io_timeout=2.0, retry_base=0.02,
                            retry_max=0.25,
                            max_retries=50).run_forever()
            except ConnectionError:
                pass
        threads = [threading.Thread(target=run_slave, args=(wf, i))
                   for i, wf in enumerate(slaves)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert server.done.is_set()
        assert proxy.faults_injected() > 0
    w = master_wf.forwards[0].weights.map_read().mem
    assert numpy.isfinite(w).all()


# -- client robustness -------------------------------------------------


def test_connect_rejects_bad_welcome():
    """Satellite: a malformed handshake raises ConnectionError (not a
    bare assert that vanishes under python -O), and a server that
    hangs up mid-handshake does too."""
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen()
    port = listener.getsockname()[1]
    wf = make_wf("BadWelcome")
    wf.is_slave = True

    def serve_one(frame):
        conn, _ = listener.accept()
        recv_frame(conn)
        if frame is not None:
            send_frame(conn, frame)
        conn.close()

    for frame in [("hello", "i-am-not-a-master"), ("welcome", 1),
                  None]:
        t = threading.Thread(target=serve_one, args=(frame,))
        t.start()
        client = SlaveClient(wf, "127.0.0.1:%d" % port,
                             io_timeout=5.0)
        with pytest.raises(ConnectionError):
            client.connect()
        t.join(timeout=10)
    listener.close()


def test_heartbeat_hammers_update_path_without_desync():
    """Satellite (ISSUE 9): the heartbeat thread is SEND-ONLY and
    whole-frame sends are serialized, so pings hammered at ~1kHz
    against a live job/update loop can never interleave bytes
    mid-frame or steal the main reader's responses. A run at this
    ping rate completes with zero reconnects, zero fenced updates and
    zero protocol desyncs — and the pongs owed to the pings are all
    drained by the main reader."""
    master_wf = make_wf("HbHammerMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=10.0)
    server.start_background()
    slave_wf = make_wf("HbHammerSlave")
    slave_wf.is_slave = True
    client = SlaveClient(slave_wf,
                         "127.0.0.1:%d" % server.bound_address[1],
                         name="hb-hammer", io_timeout=10.0,
                         ping_interval=0.001)
    jobs = client.run_forever()
    assert server.done.is_set()
    assert jobs > 0
    assert client.pings_sent > 0, \
        "the hammer never hammered — ping_interval not honored"
    # no desync, no reconnect, no fencing: byte-interleaving or a
    # stolen response would show up in every one of these
    assert client.reconnects == 0
    assert client.stale_resyncs == 0
    st = server.status()
    assert st["faults"]["fenced_updates"] == 0, st
    assert st["faults"]["drops"] == 0, st
    # every pong was either drained or is still owed for a ping the
    # final bye cut off — never negative, never unsolicited
    assert client._pending_pongs >= 0


def test_backoff_is_capped_with_jitter():
    wf = make_wf("BackoffWf")
    wf.is_slave = True
    client = SlaveClient(wf, "127.0.0.1:1", retry_base=0.05,
                         retry_max=2.0)
    for attempt in range(1, 12):
        d = client._backoff(attempt)
        assert 0.0 < d <= 2.0 * 1.25
    assert client._backoff(1) <= 0.05 * 1.25
    # retry-forever mode (max_retries=None) runs attempt into the
    # thousands: the exponent must be clamped, not overflow float
    for attempt in (1030, 10 ** 6):
        assert 0.0 < client._backoff(attempt) <= 2.0 * 1.25


def test_slave_request_stop_exits_retry_forever_loop():
    """Preemption relay: request_stop() must break run_forever even
    with max_retries=None and nothing listening (the slave is deep in
    reconnect backoff when SIGTERM arrives)."""
    wf = make_wf("StopWf")
    wf.is_slave = True
    client = SlaveClient(wf, "127.0.0.1:1", io_timeout=0.5,
                         retry_base=0.05, retry_max=5.0,
                         max_retries=None)
    t = threading.Thread(target=client.run_forever, daemon=True)
    t.start()
    time.sleep(0.3)               # let it enter the backoff loop
    client.request_stop()
    t.join(timeout=5)
    assert not t.is_alive()


def test_completed_master_drains_byes_to_stragglers():
    """A run that completes while a slave is disconnected must not
    strand it: the master keeps its listener up for drain_timeout
    answering ("bye",), so a retry-forever slave reconnecting just
    after done still hears the goodbye instead of retrying a dead
    address forever."""
    wf = make_wf("DrainMaster")
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=3,
                          slave_timeout=5.0, drain_timeout=3.0)
    server.start_background()
    server.done.set()
    time.sleep(0.15)              # serve loop enters the drain window
    swf = make_wf("DrainSlave")
    swf.is_slave = True
    client = SlaveClient(swf, "127.0.0.1:%d" % server.bound_address[1],
                         io_timeout=1.0, retry_base=0.02,
                         retry_max=0.2, max_retries=None)
    t = threading.Thread(target=client.run_forever, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()


def test_client_gives_up_after_max_retries():
    """Capped retries: with nothing listening, run_forever raises
    after max_retries consecutive failures instead of spinning."""
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()                     # nothing listens here now
    wf = make_wf("GiveUpWf")
    wf.is_slave = True
    client = SlaveClient(wf, "127.0.0.1:%d" % dead_port,
                         io_timeout=0.5, retry_base=0.01,
                         retry_max=0.05, max_retries=3)
    with pytest.raises(ConnectionError, match="giving up"):
        client.run_forever()
    assert client.reconnects == 3


# -- ChaosProxy mechanics ----------------------------------------------


def test_chaos_decide_plan_beats_rates_and_is_seeded():
    import random
    proxy = ChaosProxy.__new__(ChaosProxy)    # no sockets needed
    proxy.plan = None
    proxy.drop_rate, proxy.dup_rate = 0.5, 0.5
    proxy.delay_rate = proxy.truncate_rate = 0.0
    evt = ChaosEvent(C2S, 0, 0, "update", 1)
    # seeded rates: same rng seed -> same decision sequence
    a = [proxy._decide(evt, random.Random(7)) for _ in range(5)]
    b = [proxy._decide(evt, random.Random(7)) for _ in range(5)]
    assert a == b and set(a) <= {DROP, DUP}
    # cumulative thresholds exhaust to PASS
    proxy.drop_rate = proxy.dup_rate = 0.0
    assert proxy._decide(evt, random.Random(7)) == PASS
    # an explicit plan wins over any rates
    proxy.plan = lambda e: DELAY
    proxy.drop_rate = 1.0
    assert proxy._decide(evt, random.Random(7)) == DELAY
    proxy.plan = lambda e: "explode"
    with pytest.raises(ValueError):
        proxy._decide(evt, random.Random(7))


def test_chaos_proxy_counts_and_passes_frames():
    """A plain proxied hello/ping round-trip works and is counted."""
    wf = make_wf("ProxyCount", max_epochs=None)
    wf.decision.max_epochs = 2
    server = MasterServer(wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0)
    server.start_background()
    with ChaosProxy(("127.0.0.1", server.bound_address[1])) as proxy:
        sock = socket.create_connection(("127.0.0.1", proxy.port),
                                        timeout=10)
        send_frame(sock, ("hello", "count-me"))
        kind, sid, lease = recv_frame(sock)
        assert kind == "welcome"
        send_frame(sock, ("ping", sid, lease))
        assert recv_frame(sock) == ("pong", 0)
        sock.close()
        stats = proxy.stats()
    assert stats["connections"] == 1
    assert stats[C2S][PASS] >= 2 and stats[S2C][PASS] >= 2
    server.done.set()


# -- master restart recovery (ISSUE 4 acceptance) ----------------------


def test_persist_degrades_never_dies(tmp_path, monkeypatch):
    """The 'persistence must degrade, never kill the cluster'
    contract covers STATE BUILD failures too: an exception out of
    checkpoint_state (bad slave-pushed telemetry entry, transient
    device error) must be swallowed into a warning + None, or it
    silently kills the persist thread / crashes the shutdown path."""
    from veles.snapshotter import FileSnapshotStore
    wf = make_wf("PersistWf")
    server = MasterServer(
        wf, "127.0.0.1:0", max_epochs=3,
        checkpoint_store=FileSnapshotStore(str(tmp_path)),
        checkpoint_every=0.05)
    def boom():
        raise RuntimeError("boom")
    monkeypatch.setattr(server, "checkpoint_state", boom)
    assert server.persist_state("test") is None
    assert server.persist_count == 0
    server.done.set()


def test_master_restart_recovery(tmp_path):
    """Acceptance: kill the master mid-run (SIGKILL semantics: no
    goodbye, no final persist), restart it from the store with the
    auto-resume path — slaves reconnect UNAIDED (re-hello against the
    fresh lease table, re-sync via the job payloads) and the final
    weights match the fault-free sequential run within the usual
    tolerance: every minibatch merged exactly once relative to the
    restored state."""
    from veles.snapshotter import FileSnapshotStore, resolve_auto
    w_ref = sequential_reference(max_epochs=3)
    store = FileSnapshotStore(str(tmp_path))

    def spawn_master(resume):
        wf = make_wf("RestartMaster", max_epochs=None)
        wf.loader.shuffle_enabled = False
        wf.loader._start_epoch(first=True)
        wf.decision.max_epochs = 3
        resume_state = None
        if resume:
            resolved = resolve_auto(store)
            assert resolved, "no persisted master state to resume"
            tree, name, _ = resolved
            assert "master" in tree, tree.keys()
            wf.restore_state(tree["workflow"])
            resume_state = tree["master"]
        server = MasterServer(wf, "127.0.0.1:0", max_epochs=3,
                              slave_timeout=5.0,
                              checkpoint_store=store,
                              checkpoint_every=0.02,
                              resume_state=resume_state)
        if resume:
            # the journal actually landed (falsifiable: a restore that
            # silently fell back to construction defaults would not
            # track the persisted counters — which may legitimately
            # still be at 1/0 if the newest persist predates serving,
            # so "made progress" is NOT assertable here)
            assert server.epoch == resume_state["epoch"]
            assert server._next_job == resume_state["next_job"]
        server.start_background()
        return wf, server

    wf1, server1 = spawn_master(resume=False)

    def pace(evt):
        # pace the cluster: ~40ms per served job, so the synthetic
        # workload cannot race from start to done before the test
        # thread (GIL-starved by the in-process cluster) gets to kill
        # the master mid-run (was 20ms; the PR-7 zero-copy framing
        # made the wire fast enough to flake that window)
        if evt.direction == S2C and evt.kind == "job":
            return DELAY
        return None

    with ChaosProxy(("127.0.0.1", server1.bound_address[1]),
                    plan=pace, delay_s=0.04) as proxy:
        clients, errors = [], []

        def run_slave(idx):
            wf = make_wf("RestartSlave%d" % idx)
            wf.is_slave = True
            client = SlaveClient(
                wf, proxy.address, name="restart-%d" % idx,
                io_timeout=1.0, retry_base=0.02, retry_max=0.25,
                max_retries=None)     # a preemptible master's setting
            clients.append(client)
            try:
                client.run_forever()
            except ConnectionError as exc:
                errors.append(str(exc))

        # daemons: these clients retry FOREVER (max_retries=None), so
        # any assertion failing mid-test must not leave pytest waiting
        # on a spinning non-daemon thread for the rest of time
        threads = [threading.Thread(target=run_slave, args=(i,),
                                    daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()

        # let the cluster make SOME progress and persist at least
        # once, then kill EARLY (most of the run still ahead) so the
        # recovery is substantial, not a formality
        deadline = time.time() + 60
        while time.time() < deadline:
            if server1.persist_count >= 1 \
                    and sum(c.jobs_done for c in clients) >= 4:
                break
            time.sleep(0.005)
        assert server1.persist_count >= 1, "master never persisted"
        assert not server1.done.is_set(), \
            "run finished before the kill — nothing was recovered"

        # SIGKILL: stop serving with NO final persist, sever sockets
        server1.kill()
        proxy.kill_all()

        wf2, server2 = spawn_master(resume=True)
        proxy.target = ("127.0.0.1", server2.bound_address[1])

        assert server2.done.wait(timeout=120), server2.status()
        # slaves caught mid-reconnect when the run completes would
        # retry forever (max_retries=None): cap them so threads exit
        for c in clients:
            c.max_retries = 10
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    # at least one slave re-helloed the restarted master UNAIDED and
    # drove the recovered run to completion (whether the second one
    # makes it back before the work runs out is a scheduling race on
    # this fast synthetic workload, not a robustness property)
    assert server2.faults["joins"] >= 1, server2.status()

    w_master = numpy.asarray(
        wf2.forwards[0].weights.map_read().mem)
    assert numpy.isfinite(w_master).all()
    # weight parity with the fault-free sequential run: the replayed
    # post-persist minibatches run at the restored weights, so a tiny
    # tail of elements drifts marginally past the usual 2e-2 chaos
    # tolerance (measured: <0.004 % of elements, max ~0.023 over 30+
    # runs). Keep 2e-2 as the BULK criterion and cap the tail hard —
    # an accounting bug (lost epoch, double merge) diverges broadly
    # and blows both.
    diff = numpy.abs(w_master - w_ref)
    ctx = str({"status": server2.status(), "errors": errors,
               "max": float(diff.max()),
               "frac>2e-2": float((diff > 0.02).mean())})
    assert diff.max() < 0.05, ctx
    assert (diff > 0.02).mean() < 1e-3, ctx


def test_master_resume_state_fences_old_leases():
    """A restored master must fence every pre-restart identity: the
    lease table starts empty even though slave/job counters continue,
    so a zombie frame can never merge into the recovered weights."""
    wf1 = make_wf("FencePersist", max_epochs=None)
    wf1.decision.max_epochs = 2
    server1 = MasterServer(wf1, "127.0.0.1:0", max_epochs=2)
    _, sid, lease = server1.handle(("hello", "old-slave"))
    resp = server1.handle(("job", sid, lease))
    assert resp[0] == "job"
    state = server1.checkpoint_state()

    wf2 = make_wf("FenceRestored", max_epochs=None)
    wf2.decision.max_epochs = 2
    wf2.restore_state(state["workflow"])
    server2 = MasterServer(wf2, "127.0.0.1:0", max_epochs=2,
                           resume_state=state["master"])
    # the in-flight job was folded back into pending on persist
    assert wf2.loader._pending_jobs[0] == resp[1][wf1.loader.name]
    # the old lease is dead on arrival
    assert server2.handle(("job", sid, lease)) == ("stale",)
    assert server2.handle(
        ("update", sid, lease, resp[2], resp[3], {})) == ("stale",)
    # and a fresh hello mints an id the old incarnation never used
    _, sid2, _ = server2.handle(("hello", "new-slave"))
    assert sid2 > sid


def test_master_resume_empty_queue_does_not_replay_epoch():
    """A persist can land in the window where an epoch is FULLY merged
    (pending and in-flight both empty) but the counter not yet
    advanced (that happens lazily on the next job poll). A restore
    from that state must leave the queue empty — refilling it at the
    stale counter would replay a whole already-merged epoch into the
    restored weights."""
    wf1 = make_wf("EmptyQPersist", max_epochs=None)
    wf1.decision.max_epochs = 3
    server1 = MasterServer(wf1, "127.0.0.1:0", max_epochs=3)
    _, sid, lease = server1.handle(("hello", "sl"))
    while wf1.loader._pending_jobs:
        resp = server1.handle(("job", sid, lease))
        assert resp[0] == "job", resp
        # the payload names the loader, so the in-flight entry clears:
        # a fully MERGED epoch, not just a fully served one
        server1.handle(("update", sid, lease, resp[2], resp[3],
                        {wf1.loader.name: None}))
    state = server1.checkpoint_state()
    assert not state["master"]["pending"]
    assert state["master"]["epoch"] == 0

    wf2 = make_wf("EmptyQRestored", max_epochs=None)
    wf2.decision.max_epochs = 3
    wf2.restore_state(state["workflow"])
    server2 = MasterServer(wf2, "127.0.0.1:0", max_epochs=3,
                           resume_state=state["master"])
    assert server2.epoch == 0
    assert not wf2.loader._pending_jobs   # no refill at the stale counter
    _, sid2, lease2 = server2.handle(("hello", "sl2"))
    assert server2.handle(("job", sid2, lease2)) == ("wait",)
    assert server2.epoch == 1             # advanced, not replayed
    resp = server2.handle(("job", sid2, lease2))
    assert resp[0] == "job" and resp[3] == 1


@pytest.mark.slow
def test_master_sigkill_soak_subprocess(tmp_path):
    """Soak: the full CLI stack — master and slaves as real
    processes, the master SIGKILLed and restarted TWICE with
    ``--snapshot auto`` on the same port; slaves (--slave-retries 0 =
    unbounded) ride through both restarts and the run completes."""
    import os
    import subprocess
    import sys
    from tests.test_service import REPO

    port = _dead_port()
    snapdir = str(tmp_path / "snaps")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    overrides = ["root.mnist.decision.max_epochs=6",
                 "root.mnist.loader.n_train=500",
                 "root.mnist.loader.n_valid=100",
                 "root.mnist.loader.minibatch_size=50"]
    base = [sys.executable, "-m", "veles",
            os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
            "--seed", "11", "-d", "numpy", "--no-stats"] + overrides
    master_cmd = base + ["--listen-address", "127.0.0.1:%d" % port,
                         "--snapshots", snapdir,
                         "--checkpoint-every", "0.2",
                         "--slave-timeout", "5"]

    def master_files():
        try:
            return {n for n in os.listdir(snapdir) if "_master-" in n}
        except OSError:
            return set()

    def wait_new_master_file(before, proc, timeout=120):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if master_files() - before:
                return True
            if proc.poll() is not None:
                return False        # master finished on its own
            time.sleep(0.05)
        return False

    procs = []
    try:
        master = subprocess.Popen(master_cmd, cwd=REPO, env=env,
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.DEVNULL)
        procs.append(master)
        slaves = [subprocess.Popen(
            base + ["--master-address", "127.0.0.1:%d" % port,
                    "--slave-retries", "0"],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL) for _ in range(2)]
        procs += slaves

        for round_ in range(2):
            before = master_files()
            if not wait_new_master_file(before, master):
                # the run may legitimately complete before a second
                # kill window opens; the restart already proved itself
                assert round_ > 0 and master.poll() is not None, \
                    "no master persist before kill %d" % round_
                break
            time.sleep(0.5)       # accumulate some post-persist work
            master.kill()         # SIGKILL: no handler, no goodbye
            master.wait(timeout=30)
            master = subprocess.Popen(
                master_cmd + ["--snapshot", "auto"], cwd=REPO,
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            procs.append(master)

        assert master.wait(timeout=600) == 0
        for slave in slaves:
            assert slave.wait(timeout=120) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


# -- snapshot store degradation ----------------------------------------


def _dead_port():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    return port


def test_http_store_retries_then_breaker_opens():
    from veles.snapshotter import CircuitOpenError, HTTPSnapshotStore
    store = HTTPSnapshotStore(
        "http://127.0.0.1:%d/snaps" % _dead_port(), timeout=0.5,
        retries=1, retry_backoff=0.01, breaker_threshold=2,
        breaker_reset=60.0)
    for _ in range(2):
        with pytest.raises(OSError):
            store.get("x.ckpt.npz.gz")
    m = store.metrics()
    assert m["breaker_open"] and m["breaker_trips"] == 1
    assert m["retries"] >= 2          # each attempt retried once
    # breaker open -> instant fail, no socket work
    t0 = time.monotonic()
    with pytest.raises(CircuitOpenError):
        store.get("x.ckpt.npz.gz")
    assert time.monotonic() - t0 < 0.1
    assert store.metrics()["breaker_fast_fails"] == 1


def test_http_store_breaker_half_open_recovers():
    """After breaker_reset one probe goes through; success closes the
    breaker (and a 5xx-flapping server is retried to success)."""
    import http.server
    import json as _json
    fails = {"n": 2}
    blobs = {"snaps/ok.ckpt.npz": b"payload"}

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if fails["n"] > 0:
                fails["n"] -= 1
                self.send_response(503)
                self.end_headers()
                return
            name = self.path.lstrip("/")
            if name.endswith("/") or not name:
                body = _json.dumps(sorted(blobs)).encode()
            elif name in blobs:
                body = blobs[name]
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        from veles.snapshotter import (CircuitOpenError,
                                       HTTPSnapshotStore)
        url = "http://127.0.0.1:%d/snaps" % httpd.server_address[1]
        # 5xx then success within one call's retry budget
        store = HTTPSnapshotStore(url, timeout=5, retries=3,
                                  retry_backoff=0.01)
        assert store.get("ok.ckpt.npz") == b"payload"
        assert store.metrics()["retries"] == 2
        assert not store.metrics()["breaker_open"]

        # force the breaker open, then let the reset window pass: the
        # half-open probe succeeds and closes it
        store2 = HTTPSnapshotStore(url, timeout=5, retries=0,
                                   breaker_threshold=1,
                                   breaker_reset=0.2)
        fails["n"] = 1
        with pytest.raises(OSError):
            store2.get("ok.ckpt.npz")
        assert store2.breaker_open()
        with pytest.raises(CircuitOpenError):
            store2.get("ok.ckpt.npz")
        time.sleep(0.25)
        # half-open admits exactly one probe: a second caller racing
        # the probe window fast-fails instead of stacking timeouts
        with store2._lock:
            store2._probe_in_flight = True
        with pytest.raises(CircuitOpenError):
            store2.get("ok.ckpt.npz")
        with store2._lock:
            store2._probe_in_flight = False
        assert store2.get("ok.ckpt.npz") == b"payload"
        assert not store2.breaker_open()
        # a 404 is an ANSWER, not a health event: no breaker action
        with pytest.raises(KeyError):
            store2.get("missing.ckpt.npz")
        assert not store2.breaker_open()
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_store_for_shares_breaker_state():
    """store_for caches one HTTPSnapshotStore per base URL so repeated
    checkpoint refreshes share a circuit breaker."""
    from veles.snapshotter import store_for
    url = "http://127.0.0.1:%d/bucket" % _dead_port()
    s1, name1 = store_for(url + "/a.ckpt.npz.gz")
    s2, name2 = store_for(url + "/b.ckpt.npz.gz")
    assert s1 is s2
    assert (name1, name2) == ("a.ckpt.npz.gz", "b.ckpt.npz.gz")


def test_registry_reload_degrades_not_dies():
    """A failed hot reload (source gone / checkpoint store down) keeps
    serving the loaded version and counts the failure."""
    from veles.serving.registry import ModelRegistry

    class FakeEntry:
        name = "m"
        source = "/nonexistent/archive-dir"
        checkpoint = None
        version = 3

    reg = ModelRegistry(backend="numpy")
    entry = FakeEntry()
    reg._models["m"] = entry
    assert reg.reload("m") is entry           # degraded, not raised
    assert reg._refresh_failures["m"] == 1
    assert reg.reload("m") is entry
    assert reg._refresh_failures["m"] == 2


def test_web_status_renders_cluster_faults():
    from veles.web_status import WebStatus
    status = WebStatus(port=0)
    try:
        status.register("cluster", lambda: {
            "mode": "master", "n_slaves": 2,
            "faults": {"drops": 1, "fenced_updates": 2}})
        page = status.render_page()
        assert "n_slaves" in page and "faults" in page
        assert "fenced_updates" in page
    finally:
        status.close()
