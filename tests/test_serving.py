"""veles.serving: registry / engine / batcher / HTTP frontend, plus
the round-5 satellite regressions (GA slave error ack, WebDAV
absolute-URL snapshot listing, footprint-derived pallas VMEM grant).

The acceptance path (ISSUE 1): ``velescli.py serve`` answering a
concurrent-client predict load against an exported MNIST model with
dynamic batching — batch-fill ratio > 1 observed via ``/metrics.json``,
deadlines enforced, shedding instead of unbounded queueing — on the
numpy/CPU backend.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

import veles.prng as prng
from veles.config import root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- shared trained artifact -------------------------------------------


@pytest.fixture(scope="module")
def mnist_artifact(tmp_path_factory):
    """Train a tiny MNIST MLP on numpy, snapshot + export it once."""
    prng.seed_all(4242)
    from veles.znicz_tpu.models import mnist
    saved_loader = {k: root.mnist.loader.get(k)
                    for k in ("minibatch_size", "n_train", "n_valid")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    root.mnist.loader.update({"minibatch_size": 50, "n_train": 300,
                              "n_valid": 100})
    root.mnist.decision.max_epochs = 2
    base = tmp_path_factory.mktemp("serving")
    try:
        wf = mnist.StandardWorkflow(
            None, name="ServeTrain", layers=root.mnist.layers,
            loader_factory=lambda w: mnist.MnistLoader(
                w, name="loader", minibatch_size=50),
            decision_config=root.mnist.decision.to_dict(),
            snapshotter_config={"directory": str(base / "snapshots")})
        wf.initialize(device="numpy")
        wf.run()
        archive = str(base / "archive")
        wf.export_inference(archive)
        x = wf.loader.original_data.mem[:9].astype(numpy.float32)
        params = {
            "w1": wf.forwards[0].weights.map_read().mem.copy(),
            "b1": wf.forwards[0].bias.map_read().mem.copy(),
            "w2": wf.forwards[1].weights.map_read().mem.copy(),
            "b2": wf.forwards[1].bias.map_read().mem.copy(),
        }
        yield {"archive": archive, "x": x, "params": params,
               "unit_names": [u.name for u in wf.forwards],
               "snapshot": wf.snapshotter.destination}
    finally:
        root.mnist.loader.update(saved_loader)
        root.mnist.decision.max_epochs = saved_epochs


def mlp_oracle(p, x):
    h = 1.7159 * numpy.tanh((2.0 / 3.0) * (x @ p["w1"] + p["b1"]))
    v = h @ p["w2"] + p["b2"]
    e = numpy.exp(v - v.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


# -- registry + engine -------------------------------------------------


def test_registry_numpy_matches_training_forward(mnist_artifact):
    from veles.serving import ModelRegistry
    reg = ModelRegistry(backend="numpy")
    try:
        entry = reg.load("mnist", mnist_artifact["archive"])
        out = entry.predict(mnist_artifact["x"])
        expected = mlp_oracle(mnist_artifact["params"],
                              mnist_artifact["x"])
        numpy.testing.assert_allclose(out, expected, atol=1e-6)
        desc = entry.describe()
        assert desc["units"] == ["all2all_tanh", "softmax"]
        assert desc["input_sample_shape"] == (784,)
    finally:
        reg.close()


def test_jit_engine_bucket_cache(mnist_artifact):
    """The per-(model, bucket) compiled cache: warmup precompiles the
    power-of-two ladder, every batch size rides an existing bucket."""
    from veles.serving import ModelRegistry
    from veles.serving.engine import bucket_sizes
    reg = ModelRegistry(backend="jit", max_batch=16)
    try:
        entry = reg.load("mnist", mnist_artifact["archive"],
                         warmup=True)
        assert entry.engine.compiled_buckets == \
            bucket_sizes(16) == [1, 2, 4, 8, 16]
        expected = mlp_oracle(mnist_artifact["params"],
                              mnist_artifact["x"])
        for n in (1, 3, 9):
            out, bucket = entry.engine.predict(
                mnist_artifact["x"][:n])
            assert bucket == entry.engine.bucket_for(n)
            numpy.testing.assert_allclose(out, expected[:n],
                                          atol=1e-5)
        # no new compiles happened: every size mapped onto the ladder
        assert entry.engine.compiled_buckets == [1, 2, 4, 8, 16]
        with pytest.raises(ValueError, match="max_batch"):
            entry.engine.bucket_for(17)
    finally:
        reg.close()


def test_jit_engine_without_recorded_sample_shape(mnist_artifact):
    """Archives exported from loader-less workflows record
    input_sample_shape: null — the jit engine must still compile from
    the real request shape (review finding: it used to lower a rank-1
    spec and 500 every request)."""
    from veles.serving import ArchiveModel
    from veles.serving.engine import InferenceEngine
    model = ArchiveModel.from_dir(mnist_artifact["archive"])
    model.input_sample_shape = None
    engine = InferenceEngine(model, backend="jit", max_batch=8)
    assert engine.warmup() == {}      # nothing to precompile from
    out, bucket = engine.predict(mnist_artifact["x"][:3])
    assert bucket == 4
    numpy.testing.assert_allclose(
        out, mlp_oracle(mnist_artifact["params"],
                        mnist_artifact["x"][:3]), atol=1e-5)
    assert engine.compiled_buckets == [4]


def test_registry_checkpoint_refresh(mnist_artifact):
    """Params refresh from a snapshotter checkpoint (the best-epoch
    view), by unit name."""
    from veles.serving import ArchiveModel
    from veles.snapshotter import load_snapshot
    model = ArchiveModel.from_dir(mnist_artifact["archive"])
    loaded = model.load_checkpoint(mnist_artifact["snapshot"])
    assert loaded >= 4            # 2 x (weights, bias)
    state = load_snapshot(mnist_artifact["snapshot"])
    name0 = mnist_artifact["unit_names"][0]
    numpy.testing.assert_allclose(
        model.params[name0]["weights"],
        state["params"][name0]["weights"], atol=1e-6)


def test_hot_reload_bumps_version_and_keeps_cache(mnist_artifact,
                                                  tmp_path):
    """Same-architecture reload swaps params in place: version bumps,
    compiled programs survive, outputs track the new weights."""
    import shutil
    from veles.serving import ModelRegistry
    src = str(tmp_path / "archive")
    shutil.copytree(mnist_artifact["archive"], src)
    reg = ModelRegistry(backend="jit", max_batch=8)
    try:
        entry = reg.load("m", src, warmup=True)
        buckets = list(entry.engine.compiled_buckets)
        before = entry.predict(mnist_artifact["x"][:2])
        # retrain stand-in: zero the head weights on disk -> uniform
        with open(os.path.join(src, "contents.json")) as f:
            head = [u for u in json.load(f)["units"]
                    if u["type"] == "softmax"][0]
        for key in ("weights", "bias"):
            path = os.path.join(src, head[key])
            numpy.save(path, numpy.zeros_like(numpy.load(path)))
        entry2 = reg.reload("m")
        assert entry2 is entry and entry.version == 2
        assert entry.engine.compiled_buckets == buckets
        after = entry.predict(mnist_artifact["x"][:2])
        assert numpy.abs(after - before).max() > 1e-4
        numpy.testing.assert_allclose(after, 0.1, atol=1e-6)
    finally:
        reg.close()


def test_conv_model_serving_matches_numpy_units():
    """Coverage past the MLP: the conv/pooling interpreter ops equal
    the training units' numpy oracle on the CIFAR stack."""
    prng.seed_all(77)
    from veles.serving import ArchiveModel
    from veles.znicz_tpu.models import cifar10
    saved = {k: root.cifar.loader.get(k)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.cifar.loader.update({"minibatch_size": 10, "n_train": 40,
                              "n_valid": 20})
    try:
        wf = cifar10.create_workflow(name="ServeConv")
        wf.initialize(device="numpy")
        import tempfile
        with tempfile.TemporaryDirectory() as tmp:
            wf.export_inference(tmp)
            model = ArchiveModel.from_dir(tmp)
        wf.loader.run()
        x = wf.loader.minibatch_data.mem.astype(numpy.float32).copy()
        for u in wf.forwards:
            u.run()
        expected = wf.forwards[-1].output.mem
        numpy.testing.assert_allclose(model(x), expected, atol=1e-5)
    finally:
        root.cifar.loader.update(saved)


def test_moe_serving_is_per_request_deterministic(rng):
    """MoE routing/capacity must be a function of each sample alone:
    co-batched traffic (or bucket pad rows) must not change which
    tokens an expert drops (review finding)."""
    from veles.serving import ArchiveModel
    d, e, h, seq = 8, 4, 16, 6
    params = {"moe": {
        "router": rng.normal(0, 1, (d, e)).astype(numpy.float32),
        "weights": rng.normal(0, 0.3, (e, d, h)).astype(numpy.float32),
        "bias": numpy.zeros((e, h), numpy.float32),
        "weights2": rng.normal(0, 0.3, (e, h, d)).astype(numpy.float32),
        "bias2": numpy.zeros((e, d), numpy.float32),
    }}
    spec = {"type": "moe_ffn", "name": "moe",
            "config": {"experts": e, "hidden": h, "residual": True,
                       "capacity_factor": 1.0}}
    model = ArchiveModel("moe_wf", (seq, d), [spec], params)
    x = rng.normal(0, 1, (5, seq, d)).astype(numpy.float32)
    batched = model(x)
    for i in range(len(x)):
        numpy.testing.assert_allclose(
            model(x[i:i + 1])[0], batched[i], atol=1e-6,
            err_msg="row %d depends on co-batched rows" % i)


def test_batcher_groups_mixed_sample_shapes():
    """Differently-shaped requests (no-sample-shape archives) must not
    poison each other's batch (review finding)."""
    from veles.serving import MicroBatcher

    def echo(rows):
        time.sleep(0.005)
        return rows + 1.0, rows.shape[0]

    b = MicroBatcher(echo, max_batch=16, max_wait_ms=20.0)
    try:
        results = {}

        def client(i):
            shape = (1, 4) if i % 2 else (1, 6)
            results[i] = (shape,
                          b.predict(numpy.zeros(shape, numpy.float32)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 10
        for shape, out in results.values():
            assert out.shape == shape
            numpy.testing.assert_array_equal(out, numpy.ones(shape))
    finally:
        b.close()


# -- batcher -----------------------------------------------------------


def test_batcher_coalesces_concurrent_requests():
    from veles.serving import MicroBatcher
    calls = []

    def run_batch(rows):
        calls.append(rows.shape[0])
        time.sleep(0.005)            # give the queue time to fill
        return rows * 2.0, rows.shape[0]

    b = MicroBatcher(run_batch, max_batch=16, max_wait_ms=20.0)
    try:
        results = {}

        def client(i):
            results[i] = b.predict(
                numpy.full((1, 4), float(i), numpy.float32))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 24
        for i, out in results.items():
            numpy.testing.assert_array_equal(out, numpy.full(
                (1, 4), 2.0 * i, numpy.float32))
        m = b.metrics()
        assert m["requests_total"] == 24
        assert m["batches_total"] == len(calls) < 24
        assert m["batch_fill_ratio"] > 1.0
        assert max(calls) <= 16
        assert m["latency_ms_p99"] >= m["latency_ms_p50"] > 0
    finally:
        b.close()


def test_batcher_enforces_deadlines():
    from veles.serving import DeadlineExceeded, MicroBatcher
    release = threading.Event()

    def slow_batch(rows):
        release.wait(timeout=5)
        return rows, rows.shape[0]

    b = MicroBatcher(slow_batch, max_batch=4, max_wait_ms=1.0)
    try:
        first = b.submit(numpy.zeros((1, 2), numpy.float32),
                         timeout_ms=5000)
        time.sleep(0.05)             # worker is now stuck in batch 1
        doomed = b.submit(numpy.zeros((1, 2), numpy.float32),
                          timeout_ms=10)
        time.sleep(0.05)
        release.set()
        first.event.wait(5)
        doomed.event.wait(5)
        assert first.error is None
        assert isinstance(doomed.error, DeadlineExceeded)
        assert b.metrics()["expired_total"] == 1
    finally:
        release.set()
        b.close()


def test_batcher_sheds_instead_of_queueing_unboundedly():
    from veles.serving import MicroBatcher, QueueFull
    release = threading.Event()

    def slow_batch(rows):
        release.wait(timeout=5)
        return rows, rows.shape[0]

    b = MicroBatcher(slow_batch, max_batch=2, max_queue=3,
                     max_wait_ms=1.0)
    try:
        held = [b.submit(numpy.zeros((1, 2), numpy.float32))
                for _ in range(3)]
        time.sleep(0.05)
        # worker holds <=2 rows; <=1 slot left of the 3-row queue
        with pytest.raises(QueueFull):
            for _ in range(4):
                held.append(b.submit(
                    numpy.zeros((1, 2), numpy.float32)))
        assert b.metrics()["shed_total"] >= 1
    finally:
        release.set()
        b.close()


# -- HTTP frontend -----------------------------------------------------


def _post(url, doc, timeout=15):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_http_predict_round_trip(mnist_artifact):
    """End-to-end on the numpy backend: concurrent clients coalesce
    (fill ratio > 1 in /metrics.json), predictions match the oracle."""
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    reg = ModelRegistry(backend="numpy", max_wait_ms=15.0)
    front = None
    try:
        reg.load("mnist", mnist_artifact["archive"])
        front = ServingFrontend(reg, port=0)
        base = "http://127.0.0.1:%d" % front.port
        assert _get(base + "/healthz")["status"] == "ok"
        # a loaded warm model on a closed-breaker registry is READY
        assert _get(base + "/readyz")["ready"] is True
        models = _get(base + "/v1/models")["models"]
        assert [m["name"] for m in models] == ["mnist"]

        x = mnist_artifact["x"]
        expected = mlp_oracle(mnist_artifact["params"], x)
        results = {}

        def client(i):
            results[i] = _post(base + "/v1/predict", {
                "model": "mnist",
                "inputs": [x[i % len(x)].tolist()]})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 24
        for i, doc in results.items():
            numpy.testing.assert_allclose(
                numpy.asarray(doc["outputs"][0]),
                expected[i % len(x)], atol=1e-5)
        m = _get(base + "/metrics.json")["models"]["mnist"]
        assert m["requests_total"] >= 24
        assert m["batch_fill_ratio"] > 1.0
        assert m["shed_total"] == 0
        assert m["latency_ms_p99"] > 0
        assert m["requests_per_sec"] > 0
    finally:
        if front is not None:
            front.close()
        reg.close()


def test_http_error_paths(mnist_artifact):
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    reg = ModelRegistry(backend="numpy")
    front = None
    try:
        reg.load("mnist", mnist_artifact["archive"])
        front = ServingFrontend(reg, port=0)
        # exercised through the shared request handler (no sockets)
        code, _ = front.predict_request({"model": "nope",
                                         "inputs": [[0.0]]})
        assert code == 404
        code, _ = front.predict_request({"inputs": [[0.0]]})
        assert code == 400
        code, reply = front.predict_request(
            {"model": "mnist", "inputs": [[1.0, 2.0]]})
        assert code == 400 and "shape" in reply["error"]
        # single un-batched sample is promoted
        code, reply = front.predict_request(
            {"model": "mnist",
             "inputs": mnist_artifact["x"][0].tolist()})
        assert code == 200 and len(reply["outputs"]) == 1
        # oversized request is the CLIENT's fault -> 400, not 500
        big = numpy.zeros((reg.max_batch + 1, 784), numpy.float32)
        code, reply = front.predict_request(
            {"model": "mnist", "inputs": big.tolist()})
        assert code == 400 and "outside" in reply["error"]
        base = "http://127.0.0.1:%d" % front.port
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(base + "/nope")
        assert err.value.code == 404
    finally:
        if front is not None:
            front.close()
        reg.close()


def test_traceparent_propagation_and_debug_endpoints(mnist_artifact):
    """ISSUE 6 serving leg: a predict carrying a W3C traceparent gets
    the SAME trace echoed on the response, its queue wait and the
    batched execution appear as spans of that trace in the flight
    recorder, and the frontend serves /debug/trace + /debug/events."""
    from veles import telemetry
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    reg = ModelRegistry(backend="numpy", max_wait_ms=1.0)
    front = None
    try:
        reg.load("mnist", mnist_artifact["archive"])
        front = ServingFrontend(reg, port=0)
        base = "http://127.0.0.1:%d" % front.port
        ctx = telemetry.TraceContext.new()
        req = urllib.request.Request(
            base + "/v1/predict",
            json.dumps({"model": "mnist",
                        "inputs": [mnist_artifact["x"][0].tolist()]}
                       ).encode(),
            method="POST",
            headers={"Content-Type": "application/json",
                     "traceparent": ctx.to_traceparent()})
        with urllib.request.urlopen(req, timeout=15) as resp:
            assert resp.status == 200
            echoed = resp.headers.get("traceparent")
        assert echoed == ctx.to_traceparent()

        # a request WITHOUT the header mints a fresh context
        req2 = urllib.request.Request(
            base + "/v1/predict",
            json.dumps({"model": "mnist",
                        "inputs": [mnist_artifact["x"][1].tolist()]}
                       ).encode(),
            method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req2, timeout=15) as resp:
            minted = resp.headers.get("traceparent")
        assert minted and minted != echoed
        from veles.telemetry import TraceContext
        assert TraceContext.from_traceparent(minted) is not None

        # flight recorder (never telemetry.tracer.start()ed) holds
        # the request's spans under ITS trace_id
        doc = _get(base + "/debug/trace")
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        mine = [e for e in spans
                if e.get("args", {}).get("trace_id") == ctx.trace_id]
        names = {e["name"] for e in mine}
        assert "http.predict" in names, sorted(names)
        assert "serving.queue" in names, sorted(names)
        assert any(e["name"] == "serving.execute" for e in spans)
        events_doc = _get(base + "/debug/events")
        assert "events" in events_doc
    finally:
        if front is not None:
            front.close()
        reg.close()


def test_web_status_surfaces_serving_metrics(mnist_artifact):
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    from veles.web_status import WebStatus
    reg = ModelRegistry(backend="numpy")
    front = status = None
    try:
        reg.load("mnist", mnist_artifact["archive"])
        front = ServingFrontend(reg, port=0)
        status = WebStatus(port=0)
        front.register_status(status)
        reg.get("mnist").predict(mnist_artifact["x"][:1])
        snap = status.snapshot()
        entry = snap["serving:%d" % front.port]
        assert entry["mode"] == "serving"
        assert entry["workflow"] == "mnist"
        assert entry["last_metrics"]["mnist"]["rps"] >= 0
        assert "serving" in status.render_page()
    finally:
        if status is not None:
            status.close()
        if front is not None:
            front.close()
        reg.close()


def test_velescli_serve_subcommand(mnist_artifact):
    """The acceptance path: ``velescli.py serve`` under concurrent
    HTTP load — dynamic batching visible in /metrics.json."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "velescli.py"), "serve",
         "--model", "mnist=%s" % mnist_artifact["archive"],
         "--port", "0", "--backend", "numpy",
         "--max-wait-ms", "15", "--timeout-ms", "5000"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True)
    try:
        line = proc.stdout.readline()
        base = json.loads(line)["serving"]
        x = mnist_artifact["x"]
        expected = mlp_oracle(mnist_artifact["params"], x)
        results = {}

        def client(i):
            results[i] = _post(base + "/v1/predict", {
                "model": "mnist",
                "inputs": [x[i % len(x)].tolist()],
                "timeout_ms": 5000})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, doc in results.items():
            numpy.testing.assert_allclose(
                numpy.asarray(doc["outputs"][0]),
                expected[i % len(x)], atol=1e-5)
        m = _get(base + "/metrics.json")["models"]["mnist"]
        assert m["requests_total"] >= 16
        assert m["batch_fill_ratio"] > 1.0
        assert m["expired_total"] == 0
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# -- satellite regressions (ADVICE round 5) ----------------------------


def _ga_eval(values):          # module-level: ships through pickle
    return 0.25


def test_ga_slave_stops_on_result_error_reply():
    """A master ('error', ...) reply to a result frame must NOT count
    as served (the slave used to treat any reply as an ack)."""
    import socket
    from veles.genetics import ga_slave_loop
    from veles.server import recv_frame, send_frame
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    seen = []

    def master():
        conn, _ = srv.accept()
        try:
            while True:
                frame = recv_frame(conn)
                if frame is None:
                    return
                seen.append(frame[0])
                if frame[0] == "hello":
                    send_frame(conn, ("welcome", 7))
                elif frame[0] == "task":
                    send_frame(conn, ("task", 0, _ga_eval,
                                      {"lr": 0.1}, 0))
                elif frame[0] == "result":
                    send_frame(conn, ("error", "mixed-build master "
                                      "refused the frame"))
        finally:
            conn.close()

    t = threading.Thread(target=master, daemon=True)
    t.start()
    try:
        served = ga_slave_loop("127.0.0.1:%d" % port, name="t-slave",
                               max_tasks=5, reconnect_attempts=1,
                               reconnect_delay=0.01)
    finally:
        srv.close()
        t.join(timeout=5)
    assert served == 0
    assert "result" in seen        # the evaluation WAS reported


def test_http_snapshot_store_lists_absolute_url_hrefs(caplog):
    """WebDAV-style listers returning FULL URLs must still resolve to
    base-relative names; an all-filtered listing must be logged."""
    import logging
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from veles.snapshotter import HTTPSnapshotStore
    payload = {"doc": None}

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def do_GET(self):
            body = json.dumps(payload["doc"]).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        base = "http://127.0.0.1:%d/bucket" % httpd.server_address[1]
        store = HTTPSnapshotStore(base)
        payload["doc"] = [
            base + "/wf_=0.01.ckpt.npz.gz",        # absolute URL
            "/bucket/wf_=0.02.ckpt.npz.gz",        # absolute path
            "wf_=0.03.ckpt.npz.gz",                # relative
            base + "/other/foreign_=9.ckpt.npz.gz",  # foreign prefix
            "readme.txt",                          # not a checkpoint
        ]
        assert store.list() == ["wf_=0.01.ckpt.npz.gz",
                                "wf_=0.02.ckpt.npz.gz",
                                "wf_=0.03.ckpt.npz.gz"]
        payload["doc"] = ["http://elsewhere/x/a.ckpt.npz.gz",
                          "junk.bin"]
        with caplog.at_level(logging.WARNING):
            assert store.list() == []
        assert any("filtered out" in r.getMessage()
                   for r in caplog.records)
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_bench_serving_row_runs():
    """bench.py's serving_throughput_rps: in-process, no sockets, no
    device required."""
    import bench
    rps, fill, cache = bench.serving_throughput_rps(duration=0.3,
                                                    clients=4)
    assert cache > 0
    assert rps > 0
    assert fill >= 1.0
