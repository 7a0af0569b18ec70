"""Continuous profiling plane (ISSUE 10, veles/profiling.py):
sampling profiler + speedscope rendering, memory accounting in the
health ring, critical-path analysis over the flight recorder, the
HTTP/CLI surfaces, and the master+2-slave acceptance run."""

import json
import threading
import time
import urllib.request

import pytest

from veles import health, profiling, telemetry
from veles.health import HealthMonitor


@pytest.fixture
def mnist_config_guard():
    """Workflow builders mutate root.mnist without restoring; tests
    here that build workflows must not leak that config into later
    files (same guard as tests/test_health.py)."""
    from veles.config import root
    from veles.znicz_tpu.models import mnist  # noqa: F401
    saved_loader = {k: root.mnist.loader.get(k)
                    for k in ("minibatch_size", "n_train", "n_valid")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    yield
    root.mnist.loader.update(saved_loader)
    root.mnist.decision.max_epochs = saved_epochs


def _busy_thread(stop, name="busy-worker"):
    def spin():
        x = 0
        while not stop.is_set():
            x += 1
    t = threading.Thread(target=spin, daemon=True, name=name)
    t.start()
    return t


def _assert_speedscope_shape(doc):
    """The schema-shape contract a speedscope import needs."""
    assert doc["$schema"] == \
        "https://www.speedscope.app/file-format-schema.json"
    frames = doc["shared"]["frames"]
    assert isinstance(frames, list) and frames
    for f in frames:
        assert isinstance(f["name"], str)
        assert isinstance(f["file"], str)
        assert isinstance(f["line"], int)
    assert isinstance(doc["profiles"], list) and doc["profiles"]
    assert doc["activeProfileIndex"] == 0
    for prof in doc["profiles"]:
        assert prof["type"] == "sampled"
        assert prof["unit"] == "seconds"
        assert isinstance(prof["name"], str)
        assert len(prof["samples"]) == len(prof["weights"])
        total = 0.0
        for sample, weight in zip(prof["samples"], prof["weights"]):
            assert sample, "empty stack sample"
            for idx in sample:
                assert 0 <= idx < len(frames)
            assert weight > 0
            total += weight
        assert prof["endValue"] == pytest.approx(total, abs=1e-3)


# -- the sampler --------------------------------------------------------


def test_speedscope_document_names_threads_and_validates():
    stop = threading.Event()
    _busy_thread(stop, "busy-worker")
    try:
        prof = profiling.capture_profile(0.4, hz=200)
    finally:
        stop.set()
    assert prof.ticks > 10
    doc = prof.to_speedscope()
    _assert_speedscope_shape(doc)
    names = [p["name"] for p in doc["profiles"]]
    assert "busy-worker" in names       # per named thread, folded
    assert "MainThread" in names
    # the sampler never profiles itself
    assert "profiler-sampler" not in names
    # capture honesty metadata
    assert doc["veles"]["ticks"] == prof.ticks
    assert 0.0 <= doc["veles"]["overhead_fraction"] < 1.0


def test_collapsed_stack_render_parses():
    stop = threading.Event()
    _busy_thread(stop, "busy-worker")
    try:
        prof = profiling.capture_profile(0.3, hz=200)
    finally:
        stop.set()
    lines = prof.to_collapsed().splitlines()
    assert lines
    total = 0
    for line in lines:
        stack, count = line.rsplit(" ", 1)
        assert ";" in stack             # thread;frame...;leaf
        total += int(count)
    assert total == prof.ticks
    assert any(line.startswith("busy-worker;") for line in lines)


def test_bounded_aggregate_folds_overflow_into_truncated():
    stop = threading.Event()
    _busy_thread(stop, "busy-a")
    _busy_thread(stop, "busy-b")
    profiler = profiling.SamplingProfiler(hz=300, max_stacks=1)
    profiler.start()
    time.sleep(0.3)
    profiler.stop()
    stop.set()
    prof = profiler.profile()
    assert len(prof.stacks) <= 1 + len(prof.thread_names())
    assert prof.truncated > 0
    assert any(stack == (profiling._TRUNCATED_FRAME,)
               for _, stack in prof.stacks)
    # the truncation is visible in the rendered document too
    assert prof.to_speedscope()["veles"]["truncated_samples"] > 0


def test_profiler_overhead_bound():
    """The default-rate sampler must stay cheap — measured by its own
    accounting: seconds spent walking stacks over the capture wall
    time. Run in isolation this is ~0.5-1%; under the FULL suite the
    process drags dozens of leaked daemon threads (reactors, batcher
    workers, heartbeats from earlier tests), every sample walks all
    of them and GIL waits inflate the self-time, so the unit bound is
    load-tolerant. The < 3% ACCEPTANCE bound is the bench row
    (`profiler_overhead_pct`): the measured throughput delta of the
    MNIST train loop, off vs on — the number that prices what a
    profiled process actually loses."""
    stop = threading.Event()
    _busy_thread(stop)
    try:
        prof = profiling.capture_profile(1.0, hz=profiling.DEFAULT_HZ)
    finally:
        stop.set()
    assert prof.ticks > 40              # it really sampled
    assert prof.overhead_fraction < 0.10, prof.overhead_fraction
    # absolute per-tick cost stays sub-millisecond-scale: a sampler
    # gone O(n^2) (or holding its lock across the frame walk) blows
    # this long before it blows the fraction
    assert prof.self_seconds / prof.ticks < 0.002, \
        prof.self_seconds / prof.ticks


def test_profile_endpoint_params_and_formats():
    code, body, ctype = profiling.profile_endpoint(
        "/debug/profile?seconds=0.05&hz=200")
    assert code == 200 and ctype.startswith("application/json")
    _assert_speedscope_shape(json.loads(body))
    code, body, ctype = profiling.profile_endpoint(
        "/debug/profile?seconds=0.05&format=collapsed")
    assert code == 200 and ctype.startswith("text/plain")
    # garbage params answer 400, never a traceback — including
    # non-finite floats: hz=nan would slip through a min/max clamp
    # (NaN compares False) and busy-spin the sampler at zero delay
    for q in ("seconds=banana", "hz=x", "format=zorp", "hz=nan",
              "hz=inf", "seconds=nan"):
        code, body, _ = profiling.profile_endpoint(
            "/debug/profile?" + q)
        assert code == 400, q
        assert "error" in json.loads(body)
    # constructor defense in depth: a direct NaN hz falls back to the
    # default instead of a zero-period loop
    assert profiling.SamplingProfiler(hz=float("nan")).hz \
        == profiling.DEFAULT_HZ


# -- memory accounting --------------------------------------------------


def test_host_memory_and_gauges_reach_metrics_history():
    mem = profiling.host_memory()
    assert mem["rss_bytes"] > 1 << 20   # a python process holds MBs
    assert mem["open_fds"] > 0
    with health.scoped(HealthMonitor(interval=60.0)) as monitor:
        monitor.tick()
        doc = monitor.history_doc()
        series = doc["series"]
        assert series["veles_host_rss_bytes"][-1][1] > 1 << 20
        assert series["veles_host_open_fds"][-1][1] > 0
    # the gauges landed in the registry too (a /metrics scrape
    # carries them, not only the ring)
    text = telemetry.get_registry().render_prometheus()
    assert "veles_host_rss_bytes" in text


def test_forward_cache_estimate_tracks_params_and_buckets(
        tmp_path, mnist_config_guard):
    # a minimal hand-built archive: no training, no serving fixture
    import numpy
    from veles.serving import ModelRegistry
    w = numpy.zeros((4, 3), numpy.float32)
    numpy.save(tmp_path / "w.npy", w)
    (tmp_path / "contents.json").write_text(json.dumps({
        "format": 1, "workflow": "tiny",
        "input_sample_shape": [4],
        "units": [{"type": "all2all", "name": "fc",
                   "config": {"neurons": 3}, "weights": "w.npy"}],
    }))
    reg = ModelRegistry(backend="numpy")
    try:
        entry = reg.load("tiny", str(tmp_path))
        assert entry.cache_bytes() == w.nbytes   # numpy: one copy
        fam = telemetry.get_registry().gauge(
            "veles_serving_forward_cache_bytes", labels=("model",))
        assert fam.labels("tiny").value == w.nbytes
        reg.unload("tiny")
        assert fam.labels("tiny").value == 0     # gone, reads zero
    finally:
        reg.close()


# -- critical-path analysis ---------------------------------------------


def _span(name, wall, dur, ctx, **args):
    """Inject one wall-anchored span into the flight ring (the
    absorb_remote path — deterministic timestamps)."""
    telemetry.tracer.absorb_remote([{
        "name": name, "wall": wall, "dur": dur, "pid": 1, "tid": 1,
        "args": dict(ctx.span_args(), **args)}])


def test_critical_path_sums_match_hand_computed_fixture():
    tr = telemetry.tracer
    tr.clear()
    now = time.time()
    # job A on slave 1: dispatch 10ms, wire 20ms, compute 60ms,
    # merge 10ms over a 100ms extent (fully attributed)
    a = telemetry.TraceContext.new()
    _span("job.dispatch", now - 10.0, 0.010, a, slave=1, job_id=1)
    _span("job.wire", now - 9.99, 0.020, a, slave=1, job_id=1)
    _span("slave.apply", now - 9.99, 0.010, a, slave=1, job_id=1)
    _span("slave.compute", now - 9.98, 0.040, a, slave=1, job_id=1)
    _span("slave.update_build", now - 9.94, 0.010, a, slave=1,
          job_id=1)
    _span("job.merge", now - 9.91, 0.010, a, slave=1, job_id=1)
    # job B on slave 2: same shape but 3x the compute -> straggler
    b = telemetry.TraceContext.new()
    _span("job.dispatch", now - 5.0, 0.010, b, slave=2, job_id=2)
    _span("job.wire", now - 4.99, 0.020, b, slave=2, job_id=2)
    _span("slave.compute", now - 4.97, 0.180, b, slave=2, job_id=2)
    _span("job.merge", now - 4.79, 0.010, b, slave=2, job_id=2)
    doc = profiling.critical_path_doc(60.0)
    train = doc["train"]
    assert doc["serving"] is None
    assert train["jobs"] == 2
    legs = train["legs"]
    assert legs["dispatch"]["total_s"] == pytest.approx(0.020)
    assert legs["wire"]["total_s"] == pytest.approx(0.040)
    assert legs["compute"]["total_s"] == pytest.approx(0.240)
    assert legs["merge"]["total_s"] == pytest.approx(0.020)
    # extents: A = 100ms, B = 220ms -> everything attributed
    assert train["wall_s"] == pytest.approx(0.320, abs=1e-3)
    assert train["attributed_fraction"] >= 0.99
    assert train["legs"]["compute"]["fraction"] == pytest.approx(
        0.240 / 0.320, abs=0.01)
    # straggler: slave 2, compute-dominated
    assert train["straggler"]["slave"] == "2"
    assert train["straggler"]["leg"] == "compute"
    assert set(train["slaves"]) == {"1", "2"}


def test_critical_path_serving_legs_and_window():
    tr = telemetry.tracer
    tr.clear()
    now = time.time()
    ctx = telemetry.TraceContext.new()
    _span("serving.queue", now - 2.0, 0.004, ctx, model="m")
    _span("serving.execute", now - 1.996, 0.016, ctx, model="m")
    _span("http.predict", now - 2.0, 0.020, ctx, model="m")
    old = telemetry.TraceContext.new()
    _span("serving.execute", now - 500.0, 0.5, old, model="m")
    doc = profiling.critical_path_doc(60.0)
    serve = doc["serving"]
    assert doc["train"] is None
    assert serve["jobs"] == 1           # the old trace fell outside
    assert serve["legs"]["queue"]["total_s"] == pytest.approx(0.004)
    assert serve["legs"]["execute"]["total_s"] == pytest.approx(0.016)
    assert serve["attributed_fraction"] >= 0.99
    # routed through the shared debug endpoint
    routed = telemetry.debug_endpoint(
        "/debug/critical_path?window=60")
    assert routed["serving"]["jobs"] == 1
    assert routed["train"] is None


# -- HTTP + CLI surfaces ------------------------------------------------


def _get_json(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.load(resp)


def test_profile_and_critical_path_over_web_status_http():
    from veles.web_status import WebStatus
    ws = WebStatus(port=0)
    try:
        base = "http://127.0.0.1:%d" % ws.port
        code, doc = _get_json(
            base + "/debug/profile?seconds=0.3&hz=200")
        assert code == 200
        _assert_speedscope_shape(doc)
        names = [p["name"] for p in doc["profiles"]]
        # the capture names the reactor loop and the worker thread
        # the deferred handler itself runs on
        assert "reactor" in names, names
        assert "http-worker" in names, names
        code, doc = _get_json(base + "/debug/critical_path?window=60")
        assert code == 200
        assert set(doc) >= {"window_s", "train", "serving", "traces"}
        # probes keep answering while a capture is in flight (the
        # whole point of the defer)
        t = threading.Thread(
            target=lambda: urllib.request.urlopen(
                base + "/debug/profile?seconds=1.2", timeout=30).read(),
            daemon=True)
        t.start()
        time.sleep(0.2)
        t0 = time.perf_counter()
        code, _ = _get_json(base + "/healthz")
        assert code == 200
        assert time.perf_counter() - t0 < 0.5
        t.join(timeout=30)
    finally:
        ws.close()


def test_profile_served_on_serving_frontend_too():
    """The tentpole wires BOTH HTTP planes: the serving frontend
    serves /debug/profile (deferred) and /debug/critical_path like
    web-status does — even with an empty registry."""
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    reg = ModelRegistry(backend="numpy")
    front = ServingFrontend(reg, port=0)
    try:
        base = "http://127.0.0.1:%d" % front.port
        code, doc = _get_json(
            base + "/debug/profile?seconds=0.2&hz=200")
        assert code == 200
        _assert_speedscope_shape(doc)
        code, doc = _get_json(base + "/debug/critical_path")
        assert code == 200 and "train" in doc
    finally:
        front.close()
        reg.close()


def test_rss_slo_fires_on_memory_threshold():
    """Memory trajectories are SLO-able: a threshold objective over
    the ring's veles_host_rss_bytes series fires when RSS exceeds the
    bound (the leak-alert path the ISSUE asks for)."""
    with health.scoped(HealthMonitor(interval=60.0)) as monitor:
        now = time.time()
        monitor.tick(now=now)
        slo = monitor.add_slo({
            "name": "rss_leak", "series": "veles_host_rss_bytes",
            "op": "<=", "threshold": 1.0,        # 1 byte: must trip
            "target": 0.99, "fast_window": 30, "slow_window": 60})
        monitor.tick(now=now + 1)
        assert slo.firing
        ready, reasons = monitor.ready_state()
        assert not ready
        assert any("rss_leak" in r for r in reasons)


def test_velescli_profile_cli_roundtrip(tmp_path, capsys):
    from veles.__main__ import profile_main
    from veles.web_status import WebStatus
    ws = WebStatus(port=0)
    try:
        out = tmp_path / "prof.json"
        rc = profile_main(["http://127.0.0.1:%d" % ws.port,
                           "--seconds", "0.3", "--hz", "200",
                           "--out", str(out)])
        assert rc == 0
        captured = capsys.readouterr().out
        assert "thread(s)" in captured
        assert "reactor" in captured
        doc = json.loads(out.read_text())
        _assert_speedscope_shape(doc)
        # a 200 that is NOT a speedscope document exits 2 (here:
        # /status.json answers JSON of the wrong shape)
        rc = profile_main(["http://127.0.0.1:%d/status.json"
                           % ws.port])
        assert rc == 2
    finally:
        ws.close()
    # unreachable endpoint exits 2, never a traceback
    assert profile_main(["http://127.0.0.1:1", "--seconds",
                         "0.1"]) == 2


def test_velescli_profile_rejects_malformed_indices(capsys):
    """A 200 whose document passes the outer shape check but carries
    out-of-range frame indices (version skew, buggy server) must exit
    2, not traceback in the summary loop."""
    import http.server
    import socketserver
    from veles.__main__ import profile_main

    evil = json.dumps({
        "shared": {"frames": [{"name": "f", "file": "", "line": 1}]},
        "profiles": [{"type": "sampled", "name": "t",
                      "unit": "seconds", "startValue": 0,
                      "endValue": 1.0, "samples": [[0, 99]],
                      "weights": [1.0]}]}).encode()

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(evil)))
            self.end_headers()
            self.wfile.write(evil)

        def log_message(self, *a):
            pass

    httpd = socketserver.TCPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        rc = profile_main(["http://127.0.0.1:%d"
                           % httpd.server_address[1]])
        assert rc == 2
        assert "error:" in capsys.readouterr().err
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- velescli top rendering ---------------------------------------------


def test_top_renders_rss_lag_and_breakdown_side_by_side():
    from veles.fleet import render_snapshot
    snap = {
        "ts": 0.0,
        "fleet": {"targets": 2, "reachable": 2, "ready": 1,
                  "slaves": 2, "firing_slos": [], "degraded": []},
        "targets": [
            {"url": "http://a:1", "reachable": True, "ready": True,
             "role": "master",
             "metrics": {"reactor_lag_s": 0.0004,
                         "host_rss_bytes": 191889408},
             "critical_path": {
                 "train": {
                     "jobs": 12,
                     "legs": {
                         "dispatch": {"fraction": 0.02},
                         "wire": {"fraction": 0.31},
                         "compute": {"fraction": 0.62},
                         "merge": {"fraction": 0.05}},
                     "straggler": {"slave": "3", "leg": "compute"}},
                 "serving": None}},
            # pre-PR-10 target: no RSS, no critical path — the row
            # renders without error
            {"url": "http://b:2", "reachable": True, "ready": None,
             "role": "process", "metrics": {}},
        ],
    }
    out = render_snapshot(snap)
    assert "rss 183.0MB, reactor lag 0.4ms" in out
    assert "step: dispatch 2% | wire 31% | compute 62% | merge 5%" \
        in out
    assert "straggler slave 3: compute" in out
    assert "b:2" in out                 # degraded row still present


def test_top_degrades_against_pre_pr10_target(capsys):
    """A live process WITHOUT the new surfaces (no /debug/critical_
    path, no veles_host_* gauges) scrapes into a normal row — no
    error key, no crash (the graceful-degradation satellite)."""
    import http.server
    import socketserver

    class OldHandler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/healthz"):
                body = b'{"status": "ok"}'
                self.send_response(200)
            else:
                body = b"not found"
                self.send_response(404)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = socketserver.TCPServer(("127.0.0.1", 0), OldHandler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        from veles.fleet import render_snapshot, scrape_target
        row = scrape_target(
            "http://127.0.0.1:%d" % httpd.server_address[1],
            timeout=5.0)
        assert row["reachable"] and row["live"]
        assert "error" not in row
        assert "critical_path" not in row
        assert "host_rss_bytes" not in row.get("metrics", {})
        # and it renders
        snap = {"ts": 0.0, "targets": [row],
                "fleet": {"targets": 1, "reachable": 1, "ready": 0,
                          "slaves": 0, "firing_slos": [],
                          "degraded": []}}
        assert row["url"].replace("http://", "") in \
            render_snapshot(snap)
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- acceptance: real master + 2 slaves ---------------------------------


def test_profiling_acceptance_master_two_slaves(mnist_config_guard):
    """ISSUE 10 acceptance: on a real master + 2-slave run,
    /debug/profile returns valid speedscope JSON naming the reactor
    and worker threads, and /debug/critical_path attributes the bulk
    of each job's wall time to the dispatch/wire/compute/merge legs
    consistently with the flight-recorder spans."""
    from tests.test_service import make_wf
    from veles.client import SlaveClient
    from veles.server import MasterServer
    from veles.web_status import WebStatus

    telemetry.tracer.clear()
    master_wf = make_wf("ProfMaster")
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    ws = WebStatus(port=0)
    try:
        address = "127.0.0.1:%d" % server.bound_address[1]
        base = "http://127.0.0.1:%d" % ws.port
        threads, ok = [], [0, 0]

        def pump(i):
            wf = make_wf("ProfSlave%d" % i)
            wf.is_slave = True
            ok[i] = SlaveClient(wf, address,
                                name="prof-%d" % i).run_forever()

        for i in range(2):
            t = threading.Thread(target=pump, args=(i,))
            t.start()
            threads.append(t)
        # capture WHILE the cluster trains: the profile must name the
        # live threads doing the work
        code, prof = _get_json(
            base + "/debug/profile?seconds=0.5&hz=200")
        assert code == 200
        _assert_speedscope_shape(prof)
        names = [p["name"] for p in prof["profiles"]]
        assert "reactor" in names, names
        assert "http-worker" in names, names
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert sum(ok) >= 4             # the cluster really trained
        code, doc = _get_json(base + "/debug/critical_path?window=300")
        assert code == 200
        train = doc["train"]
        assert train is not None and train["jobs"] >= 4
        # >= 90% of per-job wall time lands in the four legs, and the
        # leg sums agree with the raw flight-recorder spans
        assert train["attributed_fraction"] >= 0.9, train
        spans = telemetry.tracer.flight_spans(300.0)
        raw = {}
        for _, ev in spans:
            leg = profiling._TRAIN_LEGS.get(ev["name"])
            if leg and (ev.get("args") or {}).get("trace_id"):
                raw[leg] = raw.get(leg, 0.0) + ev["dur"] / 1e6
        for leg in ("dispatch", "wire", "compute", "merge"):
            assert train["legs"][leg]["total_s"] == pytest.approx(
                raw.get(leg, 0.0), rel=0.05, abs=1e-4), leg
        # every slave that served jobs is attributed; the straggler
        # names one of them
        assert len(train["slaves"]) == sum(1 for n in ok if n)
        assert train["straggler"]["slave"] in train["slaves"]
    finally:
        ws.close()
        server.request_stop()
