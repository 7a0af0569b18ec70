"""Service layer: snapshot/resume, CLI, launcher, master↔slave wire
protocol (SURVEY.md §2.7, §3.3, §3.4, §4 "Distributed tests")."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.config import root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_wf(name, backend="numpy", max_epochs=2, snapdir=None):
    prng.seed_all(555)
    from veles.znicz_tpu.models import mnist
    root.mnist.loader.minibatch_size = 50
    root.mnist.loader.n_train = 500
    root.mnist.loader.n_valid = 100
    root.mnist.decision.max_epochs = max_epochs
    cfg = dict(snapshotter_config={"directory": snapdir}) \
        if snapdir else {}
    from veles.znicz_tpu.standard_workflow import StandardWorkflow
    wf = StandardWorkflow(
        None, name=name,
        layers=root.mnist.layers,
        loader_factory=lambda w: mnist.MnistLoader(
            w, name="loader", minibatch_size=50),
        decision_config=root.mnist.decision.to_dict(),
        **cfg)
    wf.initialize(device=backend)
    return wf


def test_snapshot_resume(tmp_path):
    snapdir = str(tmp_path)
    wf = make_wf("SnapWf", snapdir=snapdir)
    wf.run()
    assert wf.snapshotter.destination, "no snapshot written"
    assert os.path.exists(wf.snapshotter.destination)

    # resume into a FRESH workflow; training continues from the saved
    # best state rather than from scratch
    from veles.snapshotter import load_snapshot
    state = load_snapshot(wf.snapshotter.destination)
    wf2 = make_wf("SnapWf2", max_epochs=3)
    wf2.restore_state(state)
    # the snapshot is of the BEST point (improved gate), not the end
    assert wf2.decision.epoch_number == wf.decision.best_epoch
    assert numpy.allclose(
        wf2.forwards[0].weights.map_read().mem,
        state["params"][wf.forwards[0].name]["weights"])
    wf2.run()
    assert wf2.decision.epoch_number == 3


def test_snapshot_resume_xla(tmp_path):
    wf = make_wf("SnapX", backend="cpu", snapdir=str(tmp_path))
    wf.run()
    from veles.snapshotter import load_snapshot
    state = load_snapshot(wf.snapshotter.destination)
    wf2 = make_wf("SnapX2", backend="cpu", max_epochs=3)
    wf2.restore_state(state)
    wf2.run()
    assert wf2.decision.epoch_number == 3
    err = wf2.decision.history[-1]["validation"]["metric"]
    assert err <= wf.decision.history[-1]["validation"]["metric"] + 0.05


class _BlobHandler:
    """Minimal in-process object server (PUT/GET/DELETE /name,
    GET / -> JSON list) for the HTTPSnapshotStore round-trip."""

    @staticmethod
    def serve():
        import http.server
        import json as _json
        import threading
        blobs = {}

        class H(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _name(self):
                return self.path.lstrip("/")

            def do_PUT(self):
                n = int(self.headers.get("Content-Length", 0))
                blobs[self._name()] = self.rfile.read(n)
                self.send_response(201)
                self.end_headers()

            def do_GET(self):
                name = self._name()
                if not name or name.endswith("/"):
                    # prefix list (S3-style): GET <prefix>/ returns
                    # the full object paths under it
                    body = _json.dumps(
                        sorted(n for n in blobs
                               if n.startswith(name))).encode()
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

            def do_DELETE(self):
                existed = blobs.pop(self._name(), None) is not None
                self.send_response(204 if existed else 404)
                self.end_headers()

        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        return server, blobs


def test_snapshot_http_store_roundtrip():
    """Snapshot + resume through the REMOTE store (SURVEY §2.7
    alternate-backend row): snapshots land on an HTTP object server,
    retention DELETEs stale names there, and --snapshot-style resume
    loads straight from the http:// URI."""
    from veles.snapshotter import load_snapshot
    server, blobs = _BlobHandler.serve()
    try:
        url = "http://127.0.0.1:%d/ckpts" % server.server_address[1]
        prng.seed_all(555)
        from veles.znicz_tpu.models import mnist
        root.mnist.loader.minibatch_size = 50
        root.mnist.loader.n_train = 500
        root.mnist.loader.n_valid = 100
        root.mnist.decision.max_epochs = 2
        from veles.znicz_tpu.standard_workflow import StandardWorkflow
        wf = StandardWorkflow(
            None, name="SnapHTTP", layers=root.mnist.layers,
            loader_factory=lambda w: mnist.MnistLoader(
                w, name="loader", minibatch_size=50),
            decision_config=root.mnist.decision.to_dict(),
            snapshotter_config={"store": url})
        wf.initialize(device="numpy")
        wf.run()
        dest = wf.snapshotter.destination
        assert dest.startswith(url), dest
        # blobs really live on the server, within retention
        assert blobs and len(
            [n for n in blobs if n.startswith("ckpts/")]) \
            <= wf.snapshotter.keep
        # list() works against this very endpoint shape and filters/
        # normalizes like the file store (ADVICE r4): base-relative
        # .ckpt. names only
        listed = wf.snapshotter.store.list()
        assert listed == sorted(
            n[len("ckpts/"):] for n in blobs
            if n.startswith("ckpts/") and ".ckpt." in n)
        assert all("/" not in n and ".ckpt." in n for n in listed)
        state = load_snapshot(dest)
        wf2 = make_wf("SnapHTTP2", max_epochs=3)
        wf2.restore_state(state)
        assert wf2.decision.epoch_number == wf.decision.best_epoch
        wf2.run()
        assert wf2.decision.epoch_number == 3
    finally:
        server.shutdown()
        server.server_close()


def test_snapshot_store_failure_escalates(tmp_path):
    """Transient store failures warn and continue; a store that fails
    ``max_store_failures`` times IN A ROW raises — a permanently dead
    backend must not silently disable checkpointing for a whole run
    (ADVICE r4). A success in between resets the counter."""
    from veles.snapshotter import FileSnapshotStore

    wf = make_wf("SnapFail", max_epochs=1,
                 snapdir=str(tmp_path / "snaps"))
    wf.run()
    snap = wf.snapshotter

    class FlakyStore(FileSnapshotStore):
        broken = True

        def stream(self, name):
            if self.broken:
                raise OSError("store down")
            return super().stream(name)

    snap._store = FlakyStore(str(tmp_path / "flaky"))
    assert snap.max_store_failures == 3
    assert snap.export_snapshot() is None
    assert snap.export_snapshot() is None
    snap._store.broken = False           # success resets the counter
    assert snap.export_snapshot() is not None
    assert snap._store_failures == 0
    snap._store.broken = True
    assert snap.export_snapshot() is None
    assert snap.export_snapshot() is None
    with pytest.raises(OSError):
        snap.export_snapshot()


def test_cli_end_to_end(tmp_path):
    """Drive the real CLI: sample module + overrides + result file."""
    result = tmp_path / "result.json"
    graph = tmp_path / "graph.dot"
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    base = [sys.executable, "-m", "veles",
            os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
            "--seed", "99", "-d", "cpu", "--no-stats",
            "root.mnist.decision.max_epochs=2",
            "root.mnist.loader.n_train=300",
            "root.mnist.loader.n_valid=100",
            "root.mnist.loader.minibatch_size=50"]
    out = subprocess.run(
        base + ["--result-file", str(result)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(result.read_text())
    assert len(data["history"]) == 2
    assert data["best_metric"] < 0.9

    out = subprocess.run(
        base + ["--workflow-graph", str(graph)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "digraph" in graph.read_text()


def test_request_kind_counter_is_bounded():
    """Admission hardening (zlint unbounded-cardinality): the frame
    chooses the request kind string, but the per-kind counter cache
    and its Prometheus label universe must not be the wire's to grow
    — unknown kinds fold into one ``other`` bucket."""
    from veles.server import _REQUEST_KINDS, _resolve_request_kind
    for kind in _REQUEST_KINDS:
        assert _resolve_request_kind(kind) == kind
    assert _resolve_request_kind("jailbreak") == "other"
    assert _resolve_request_kind("job2") == "other"
    assert _resolve_request_kind(b"\xff" * 64) == "other"
    assert _resolve_request_kind(None) == "other"
    # the dispatched universe is exactly the bounded label set
    assert _REQUEST_KINDS == {"hello", "ping", "job", "update"}


def test_master_slave_protocol():
    """In-process master + 2 slaves over localhost TCP: job/update
    round-trips, weight averaging, slave-drop requeue (§3.3, §4)."""
    from veles.server import MasterServer
    from veles.client import SlaveClient

    master_wf = make_wf("MasterWf", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    addr = "127.0.0.1:%d" % server.bound_address[1]

    w0 = numpy.array(master_wf.forwards[0].weights.map_read().mem)

    slaves = [make_wf("SlaveWf%d" % i) for i in range(2)]
    for s in slaves:
        s.is_slave = True
    counts = []

    def run_slave(wf):
        client = SlaveClient(wf, addr, name=wf.name)
        counts.append(client.run_forever())

    threads = [threading.Thread(target=run_slave, args=(s,))
               for s in slaves]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert server.done.is_set()
    assert sum(counts) >= 2 * (500 // 50 + 100 // 50)  # 2 epochs of jobs
    # master weights moved (averaged in from slave updates)
    w1 = master_wf.forwards[0].weights.map_read().mem
    assert not numpy.allclose(w0, w1)


def test_single_slave_matches_standalone():
    """Delta-shipping makes one-slave distributed training EXACTLY
    sequential SGD: master hands weights + a minibatch job, slave
    trains it, ships the delta, master applies it verbatim — the final
    weights equal a standalone run over the same minibatch order
    (shuffling disabled: the master deliberately shuffles with a
    separate PRNG stream, so bitwise parity needs a fixed order)."""
    from veles.server import MasterServer
    from veles.client import SlaveClient

    from veles.loader.base import CLASS_TRAIN

    def unshuffled(name, **kw):
        wf = make_wf(name, **kw)
        wf.loader.shuffle_enabled = False
        wf.loader._start_epoch(first=True)   # regenerate the order
        return wf

    # reference: plain sequential SGD over exactly 2 epochs of serves.
    # (wf.run() is NOT the reference here: its decision gates off the
    # final minibatch's GD update once `complete` fires — a stop-logic
    # artifact the master/slave protocol doesn't replicate.)
    ref = unshuffled("StandaloneRef", max_epochs=2)
    loader = ref.loader
    for _ in range(2 * ref.loader.effective_batches_per_epoch):
        loader.run()
        for u in ref.forwards:
            u.run()
        ref.evaluator.run()
        if loader.minibatch_class == CLASS_TRAIN:
            for gd in reversed(ref.gds):
                gd.run()
    w_ref = numpy.array(ref.forwards[0].weights.map_read().mem)

    master_wf = unshuffled("Master1", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    addr = "127.0.0.1:%d" % server.bound_address[1]
    slave = unshuffled("Slave1")
    slave.is_slave = True
    SlaveClient(slave, addr, name="s1").run_forever()
    assert server.done.is_set()
    w_master = master_wf.forwards[0].weights.map_read().mem
    numpy.testing.assert_allclose(w_master, w_ref, atol=1e-6)


def test_xla_slave_trains():
    """A slave on the FUSED XLA backend: weights pushed by the master
    re-upload per job (refresh_device), train, sync back, ship deltas."""
    from veles.server import MasterServer

    from veles.launcher import Launcher
    from veles.znicz_tpu.standard_workflow import StandardWorkflow
    from veles.znicz_tpu.models import mnist

    master_wf = make_wf("MasterXla", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    addr = "127.0.0.1:%d" % server.bound_address[1]
    w0 = numpy.array(master_wf.forwards[0].weights.map_read().mem)

    # the REAL slave surface: the Launcher flags is_slave before
    # initialize, which pins the per-step (non-scan) execution mode
    prng.seed_all(555)
    slave = StandardWorkflow(
        None, name="SlaveXla", layers=root.mnist.layers,
        loader_factory=lambda w: mnist.MnistLoader(
            w, name="loader", minibatch_size=50),
        decision_config={"max_epochs": 2})
    launcher = Launcher(device="cpu", master_address=addr, stats=False)
    launcher.initialize(slave)
    assert slave.xla_step is not None \
        and not slave.xla_step.scan_mode   # slaves stay per-step
    launcher.run()
    assert server.done.is_set()
    w1 = master_wf.forwards[0].weights.map_read().mem
    assert not numpy.allclose(w0, w1)
    assert numpy.isfinite(w1).all()


def test_wire_protocol_carries_all_params():
    """The master↔slave link must ship EVERY forward parameter —
    attention's weights_out / FFN's weights2 included, not just
    weights/bias."""
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    from tests.test_conv_stack import build

    prng.seed_all(77)
    wf, feed, fwd, gd, x, err, comp = build(
        MultiHeadAttention, input_shape=(2, 8, 8), gd_kwargs={},
        heads=2)
    payload = gd.generate_data_for_slave()
    assert set(payload) >= {"weights", "weights_out"}

    # slave side: apply master weights, "train" (mutate), ship deltas
    gd.apply_data_from_master(payload)
    fwd.weights_out.map_write()
    fwd.weights_out.mem[...] += 0.25
    update = gd.generate_data_for_master()
    assert "dweights_out" in update
    numpy.testing.assert_allclose(update["dweights_out"], 0.25,
                                  atol=1e-6)
    numpy.testing.assert_allclose(update["dweights"], 0.0, atol=1e-6)

    # master side: deltas apply verbatim
    before = numpy.array(fwd.weights_out.mem)
    gd.apply_data_from_slave(update)
    numpy.testing.assert_allclose(
        fwd.weights_out.mem, before + 0.25, atol=1e-6)


def test_wire_bytes_counted_per_frame():
    """veles_wire_bytes_total accounts every frame both ways."""
    import socket
    import threading
    from veles.server import recv_frame, send_frame
    a, b = socket.socketpair()
    try:
        reg = telemetry.get_registry()
        payload = ("job", {"x": numpy.zeros(64).tolist()}, 1, 0)
        got = []
        t = threading.Thread(target=lambda: got.append(recv_frame(b)))
        t.start()
        send_frame(a, payload)
        t.join(timeout=10)
        assert got and got[0] == payload
        tx = reg.counter_total("veles_wire_bytes_total",
                               direction="tx")
        rx = reg.counter_total("veles_wire_bytes_total",
                               direction="rx")
        assert tx == rx and tx > 36     # header+tag+payload
    finally:
        a.close()
        b.close()


def test_drop_slave_requeues():
    from veles.loader.base import CLASS_TRAIN
    wf = make_wf("DropWf")
    loader = wf.loader
    loader.master_start_epoch()
    total = len(loader._pending_jobs)
    job = loader.generate_data_for_slave(slave=7)
    assert job is not None and len(loader._pending_jobs) == total - 1
    loader.drop_slave(7)
    assert len(loader._pending_jobs) == total
    assert loader._pending_jobs[0] == job


def test_cli_background_daemon(tmp_path):
    """--background detaches: the foreground process returns
    immediately with the daemon pid; the daemon finishes the run and
    writes the result file + log (SURVEY.md §2.7 CLI row)."""
    result = tmp_path / "result.json"
    log = tmp_path / "daemon.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "veles",
         os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
         "--seed", "99", "-d", "numpy", "--no-stats",
         "root.mnist.decision.max_epochs=1",
         "root.mnist.loader.n_train=120",
         "root.mnist.loader.n_valid=40",
         "root.mnist.loader.minibatch_size=40",
         "--result-file", str(result),
         "--background", "--log-file", str(log)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    pid = doc["daemon_pid"]
    assert pid > 0
    # the daemon runs on after the foreground returned: poll for its
    # result file
    deadline = time.time() + 180
    while time.time() < deadline and not result.exists():
        time.sleep(0.5)
    assert result.exists(), "daemon never wrote the result file"
    data = json.loads(result.read_text())
    assert len(data["history"]) == 1


def test_master_dashboard_shows_slaves():
    """The master's web dashboard reports cluster topology from the
    live server registry: joined slaves with job counts (§5.5)."""
    import urllib.request
    from veles.server import MasterServer
    from veles.client import SlaveClient
    from veles.web_status import WebStatus

    master_wf = make_wf("DashMasterWf", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    status = WebStatus(port=0)
    try:
        # what Launcher._run_master registers
        status.register("cluster", server.status)
        server.start_background()
        addr = "127.0.0.1:%d" % server.bound_address[1]
        slave_wf = make_wf("DashSlaveWf")
        slave_wf.is_slave = True
        seen = {}

        def run_slave():
            client = SlaveClient(slave_wf, addr, name="dash-slave")
            client.run_forever()

        t = threading.Thread(target=run_slave)
        t.start()
        # poll the dashboard WHILE the run is live
        deadline = time.time() + 60
        while time.time() < deadline:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/status.json" % status.port,
                    timeout=5) as resp:
                seen = json.loads(resp.read().decode())["cluster"]
            if seen.get("n_slaves", 0) >= 1 and any(
                    s.get("jobs", 0) > 0
                    for s in seen.get("slaves", {}).values()):
                break
            time.sleep(0.05)
        t.join(timeout=120)
        assert seen.get("n_slaves", 0) >= 1, seen
        assert any(s.get("name") == "dash-slave"
                   for s in seen["slaves"].values()), seen
        assert any(s.get("jobs", 0) > 0
                   for s in seen["slaves"].values()), seen
        # page renders too (no provider crash)
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/" % status.port, timeout=5) as r:
            assert b"cluster" in r.read()
    finally:
        status.close()
        server.done.set()
