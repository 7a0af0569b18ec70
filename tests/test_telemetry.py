"""Unified telemetry core (ISSUE 3): registry instruments, label
handling, Prometheus exposition, span tracing, the serving JSON view
over the registry, and cluster counter aggregation.

Every test runs under a fresh scoped registry (autouse fixture in
conftest.py) — the isolation itself is regression-tested here too.
"""

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import numpy
import pytest

from veles import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- instruments -------------------------------------------------------


def test_counter_labels_and_totals():
    fam = telemetry.counter("t_requests_total", "test", ("model",))
    fam.labels("a").inc()
    fam.labels("a").inc(2)
    fam.labels("b").inc()
    assert fam.labels("a").value == 3
    assert fam.labels(model="b").value == 1
    reg = telemetry.get_registry()
    assert reg.counter_total("t_requests_total") == 4
    assert reg.counter_total("t_requests_total", model="a") == 3
    assert reg.counter_total("no_such_total") == 0.0
    # label arity/name validation
    with pytest.raises(ValueError):
        fam.labels("a", "b")
    with pytest.raises(ValueError):
        fam.labels(nope="a")
    # label-less family acts as its own child
    plain = telemetry.counter("t_plain_total")
    plain.inc(5)
    assert plain.value == 5
    with pytest.raises(ValueError):
        plain.inc(-1)              # counters only go up
    # a labelled family refuses direct use
    with pytest.raises(ValueError):
        fam.inc()
    # same name, different kind -> loud failure
    with pytest.raises(ValueError):
        telemetry.gauge("t_requests_total")


def test_absorb_before_declare_adopts_label_schema():
    """Regression: a master may absorb a slave's counters BEFORE the
    local instrumented path declares the family with labels — the
    later declared schema must be adopted, not rejected."""
    reg = telemetry.get_registry()
    reg.absorb_counters(
        {("t_adopt_total", (("cls", "train"),)): 5.0},
        extra_labels=(("slave", "1"),))
    fam = telemetry.counter("t_adopt_total", "declared later",
                            ("loader", "cls"))
    fam.labels("ld", "train").inc(2)      # must not raise
    assert reg.counter_total("t_adopt_total") == 7
    assert reg.counter_total("t_adopt_total", slave="1") == 5


def test_gauge_set_inc_dec():
    g = telemetry.gauge("t_depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8


def test_histogram_percentiles_vs_numpy(rng):
    h = telemetry.histogram("t_lat_seconds", "test")
    vals = rng.random(1500)
    for v in vals:
        h.observe(float(v))
    assert h.count == 1500
    assert abs(h.sum - vals.sum()) < 1e-6
    lat = numpy.sort(vals)
    # the exact index convention the serving metrics always used
    assert h.percentile(0.5) == lat[min(len(lat) - 1,
                                        int(len(lat) * 0.5))]
    assert h.percentile(0.99) == lat[min(len(lat) - 1,
                                         int(len(lat) * 0.99))]
    # and numerically equivalent to numpy's percentiles on this size
    assert abs(h.percentile(0.5)
               - numpy.percentile(vals, 50)) < 0.01
    assert abs(h.percentile(0.99)
               - numpy.percentile(vals, 99)) < 0.01
    assert telemetry.histogram("t_empty_seconds").percentile(0.5) \
        is None


# -- test isolation (the autouse scoped-registry fixture) --------------
# Both directions: whichever runs first increments, the other must
# still see a virgin registry.


def test_registry_isolation_leg_a():
    assert telemetry.get_registry().counter_total(
        "t_isolation_total") == 0
    telemetry.counter("t_isolation_total").inc(41)


def test_registry_isolation_leg_b():
    assert telemetry.get_registry().counter_total(
        "t_isolation_total") == 0
    telemetry.counter("t_isolation_total").inc(17)


# -- Prometheus exposition ---------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (\+Inf|-?[0-9.eE+-]+)$')


def test_prometheus_exposition_parses():
    telemetry.counter("t_c_total", "counter help",
                      ("model",)).labels('we"ird\\na<me').inc(2)
    telemetry.gauge("t_g", "gauge help").set(1.5)
    h = telemetry.histogram("t_h_seconds", "hist help")
    for v in (0.0001, 0.003, 0.04, 2.0):
        h.observe(v)
    text = telemetry.get_registry().render_prometheus()
    lines = text.strip().split("\n")
    # TYPE lines present and correct
    assert "# TYPE t_c_total counter" in lines
    assert "# TYPE t_g gauge" in lines
    assert "# TYPE t_h_seconds histogram" in lines
    # every sample line parses
    samples = [l for l in lines if not l.startswith("#")]
    for line in samples:
        assert _SAMPLE_RE.match(line), "unparseable: %r" % line
    # histogram contract: cumulative buckets, +Inf == count
    buckets = [l for l in samples
               if l.startswith("t_h_seconds_bucket")]
    counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert buckets[-1].startswith('t_h_seconds_bucket{le="+Inf"}')
    assert counts[-1] == 4
    count_line = [l for l in samples
                  if l.startswith("t_h_seconds_count")][0]
    assert count_line.endswith(" 4")
    assert any(l.startswith("t_h_seconds_sum") for l in samples)
    # label escaping survived
    assert 't_c_total{model="we\\"ird\\\\na<me"} 2' in samples


def test_prometheus_escapes_label_values_and_help():
    """Satellite regression (ISSUE 6): label values carrying every
    escapable character (backslash, double quote, newline) and HELP
    text carrying backslash/newline must render per the exposition
    format — one raw ``"`` in a model name used to be the difference
    between a scrape and a parser error."""
    fam = telemetry.counter(
        "t_esc_total", 'help with \\ backslash\nand newline',
        ("model",))
    fam.labels('say "hi"\\now\n!').inc(3)
    text = telemetry.get_registry().render_prometheus()
    lines = text.strip().split("\n")
    help_line = [l for l in lines
                 if l.startswith("# HELP t_esc_total")][0]
    assert help_line == ("# HELP t_esc_total help with \\\\ "
                         "backslash\\nand newline")
    sample = [l for l in lines if l.startswith("t_esc_total{")][0]
    assert sample == \
        't_esc_total{model="say \\"hi\\"\\\\now\\n!"} 3'
    assert _SAMPLE_RE.match(sample), sample
    # no raw newline leaked into any line
    assert all("\n" not in l for l in lines)


# -- span tracer -------------------------------------------------------


def test_traceparent_round_trip_and_rejects_garbage():
    ctx = telemetry.TraceContext.new()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = telemetry.TraceContext.from_traceparent(
        ctx.to_traceparent())
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    assert child.span_id != ctx.span_id
    for bad in (None, "", "xx", "00-short-ff-01",
                "00-%s-%s-01" % ("g" * 32, "f" * 16)):
        assert telemetry.TraceContext.from_traceparent(bad) is None
    assert telemetry.TraceContext.from_wire("not-a-dict") is None
    wire = telemetry.TraceContext.from_wire(ctx.to_wire())
    assert wire.trace_id == ctx.trace_id


def test_tracer_drop_counter_exported():
    """Satellite: full-buffer drops are a scraped counter, not just a
    note buried in the dump's otherData — a scrape can now SEE that a
    trace window is incomplete."""
    tr = telemetry.Tracer()
    tr.max_events = 3
    tr.start()
    for _ in range(5):
        tr.add_complete("e", 0.0, 0.0)
    reg = telemetry.get_registry()
    assert reg.counter_total(
        "veles_trace_dropped_events_total") == 2
    assert len(tr.events()) == 3


def test_flight_recorder_records_while_disabled(tmp_path):
    """The tentpole's postmortem contract: with the tracer NEVER
    enabled, spans still land in the bounded ring and flight_doc()
    serves a parseable Perfetto window of them."""
    assert not telemetry.tracer.enabled
    assert telemetry.tracer.active          # flight is on by default
    with telemetry.span("bg.work", step=1):
        pass
    assert telemetry.tracer.events() == []  # full buffer untouched
    doc = telemetry.tracer.flight_doc()
    names = [e["name"] for e in doc["traceEvents"]
             if e["ph"] == "X"]
    assert "bg.work" in names
    # the document round-trips as JSON (what /debug/trace serves)
    doc2 = json.loads(json.dumps(doc))
    assert doc2["otherData"]["spans"] == str(len(names))
    # a zero-width window serves nothing
    assert [e for e in telemetry.tracer.flight_doc(0)["traceEvents"]
            if e["ph"] == "X"] in ([], )


def test_record_event_log_and_absorb_remote():
    telemetry.record_event("reconnect", name="slave-1", attempt=2)
    telemetry.record_event("checkpoint_written", name="x", slot="best")
    events = telemetry.tracer.recent_events()
    assert [e["event"] for e in events[-2:]] == \
        ["reconnect", "checkpoint_written"]
    assert telemetry.tracer.recent_events(limit=1)[0]["event"] == \
        "checkpoint_written"
    # remote spans merge wall-anchored, with a named track; malformed
    # entries are skipped, not fatal
    import time as _time
    n = telemetry.tracer.absorb_remote([
        {"name": "slave.compute", "wall": _time.time(), "dur": 0.01,
         "pid": 4242, "tid": 7, "args": {"trace_id": "t" * 32}},
        {"garbage": True},
    ], process_name="slave:far")
    assert n == 1
    doc = telemetry.tracer.flight_doc()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert any(e["name"] == "slave.compute" and e["pid"] == 4242
               for e in spans)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"]["name"] == "slave:far" for e in meta)


def test_debug_endpoints_on_web_status_and_cli(tmp_path):
    """GET /debug/trace and /debug/events on a live dashboard return
    parseable payloads, and ``velescli debug`` drives them end to
    end (table + saved Perfetto file; exit 2 on a dead endpoint)."""
    from veles.web_status import WebStatus
    from veles.__main__ import debug_main
    with telemetry.span("live.span", job=1):
        pass
    telemetry.record_event("fault", kind="drops", n=1)
    ws = WebStatus(port=0)
    try:
        base = "http://127.0.0.1:%d" % ws.port
        with urllib.request.urlopen(base + "/debug/trace?window=60",
                                    timeout=10) as resp:
            doc = json.load(resp)
        assert doc["otherData"]["window_s"] == "60"
        assert any(e["name"] == "live.span"
                   for e in doc["traceEvents"] if e["ph"] == "X")
        with urllib.request.urlopen(base + "/debug/events",
                                    timeout=10) as resp:
            events = json.load(resp)["events"]
        assert any(e["event"] == "fault" for e in events)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + "/debug/nope", timeout=10)
        assert err.value.code == 404
        out = str(tmp_path / "window.json")
        assert debug_main([base, "--trace-out", out]) == 0
        with open(out) as f:
            saved = json.load(f)
        assert any(e["name"] == "live.span"
                   for e in saved["traceEvents"] if e["ph"] == "X")
    finally:
        ws.close()
    assert debug_main(["http://127.0.0.1:1"]) == 2


def test_debug_cli_exits_2_on_misshaped_200():
    """A 200 answer that is not the /debug payload shape (array
    instead of object, wrong value types) exits 2 — never a
    traceback (same contract as the checkpoints CLI)."""
    import http.server
    import threading
    from veles.__main__ import debug_main

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b'["not", "the", "shape"]'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        rc = debug_main(["http://127.0.0.1:%d"
                         % srv.server_address[1]])
        assert rc == 2
    finally:
        srv.shutdown()
        srv.server_close()


def test_flight_doc_reports_ring_coverage():
    """Under span pressure the bounded ring holds less than the
    requested window; flight_doc must say so (covered_s +
    ring_evicted) instead of silently truncating."""
    import time as _time
    tr = telemetry.Tracer()
    tr.flight_max_events = 8
    tr._ring = __import__("collections").deque(maxlen=8)
    now = _time.perf_counter()
    for i in range(20):
        tr.add_complete("e%d" % (i % 2), now + i * 1e-6, 0.0)
    doc = tr.flight_doc(window=600)
    other = doc["otherData"]
    assert other["ring_evicted"] == "12"
    assert int(other["spans"]) == 8
    assert float(other["covered_s"]) <= 600.0


def test_trace_file_is_valid_chrome_trace(tmp_path):
    telemetry.tracer.start()
    with telemetry.span("outer", unit="conv1"):
        with telemetry.span("inner"):
            pass
    path = str(tmp_path / "t.json")
    telemetry.tracer.dump(path)
    telemetry.tracer.stop()
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == 2
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner"}
    for e in events:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert isinstance(e["dur"], float) and e["dur"] >= 0
        assert "pid" in e and "tid" in e
    assert by_name["outer"]["args"] == {"unit": "conv1"}
    # inner nests inside outer on the timeline
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3


def test_disabled_tracer_records_nothing():
    assert not telemetry.tracer.enabled
    with telemetry.span("ghost"):
        pass
    telemetry.tracer.add_complete("ghost2", 0.0, 1.0)
    assert telemetry.tracer.events() == []


# -- unit runtime instrumentation --------------------------------------


def test_unit_run_histogram_and_spans():
    from veles.units import Unit
    from veles.workflow import Workflow

    class Work(Unit):
        def run(self):
            pass

    wf = Workflow(None, name="TeleWF")
    u = Work(wf, name="worker")
    u.link_from(wf.start_point)
    wf.end_point.link_from(u)
    telemetry.tracer.start()
    wf.run()
    telemetry.tracer.stop()
    reg = telemetry.get_registry()
    text = reg.render_prometheus()
    assert 'veles_unit_run_seconds_count{unit="worker"} 1' in text
    assert u.run_calls == 1 and u.run_time >= 0  # old view survives
    names = {e["name"] for e in telemetry.tracer.events()}
    assert "worker.run" in names
    assert "workflow.run" in names


def test_loader_counters_on_a_real_run():
    import veles.prng as prng
    from veles.config import root
    from veles.znicz_tpu.models import mnist
    prng.seed_all(404)
    saved = {k: root.mnist.loader.get(k)
             for k in ("n_train", "n_valid", "minibatch_size")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    root.mnist.loader.update(
        {"n_train": 200, "n_valid": 80, "minibatch_size": 40})
    root.mnist.decision.max_epochs = 2
    try:
        wf = mnist.create_workflow(name="TeleMnist")
        wf.initialize(device="numpy")
        wf.run()
    finally:
        root.mnist.loader.update(saved)
        root.mnist.decision.max_epochs = saved_epochs
    reg = telemetry.get_registry()
    loader = wf.loader.name
    # 2 epochs × 200 train samples
    assert reg.counter_total("veles_loader_samples_total",
                             loader=loader, cls="train") == 400
    assert reg.counter_total("veles_loader_samples_total",
                             loader=loader, cls="validation") == 160
    assert reg.counter_total("veles_loader_minibatches_total",
                             loader=loader, cls="train") == 10
    assert reg.counter_total("veles_loader_epochs_total",
                             loader=loader) >= 1
    # per-unit histograms cover the hot units
    text = reg.render_prometheus()
    assert 'veles_unit_run_seconds_count{unit="%s"}' % loader in text


def test_xla_compile_and_dispatch_metrics():
    import veles.prng as prng
    from veles.config import root
    from veles.znicz_tpu.models import mnist
    prng.seed_all(405)
    saved = {k: root.mnist.loader.get(k)
             for k in ("n_train", "n_valid", "minibatch_size")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    root.mnist.loader.update(
        {"n_train": 64, "n_valid": 32, "minibatch_size": 16})
    root.mnist.decision.max_epochs = 2
    try:
        wf = mnist.create_workflow(name="TeleXla")
        wf.initialize(device="cpu")
        wf.run()
    finally:
        root.mnist.loader.update(saved)
        root.mnist.decision.max_epochs = saved_epochs
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_xla_cache_misses_total") >= 1
    text = reg.render_prometheus()
    assert "# TYPE veles_xla_build_seconds histogram" in text
    assert "veles_xla_dispatch_seconds_count" in text


# -- serving: JSON view + endpoints ------------------------------------

#: the exact pre-registry (PR 1/2 era) /metrics JSON key shape — the
#: satellite regression contract for /metrics.json consumers
GOLDEN_BATCHER_KEYS = {
    "queue_depth", "requests_total", "shed_total", "expired_total",
    "error_total", "batches_total", "batch_fill_ratio",
    "bucket_pad_ratio", "requests_per_sec",
    "latency_ms_p50", "latency_ms_p99",
}


def test_metrics_json_keeps_pre_registry_shape():
    from veles.serving.batcher import MicroBatcher
    b = MicroBatcher(lambda rows: (rows, len(rows)),
                     max_wait_ms=0.5, name="batcher-m", model="m")
    try:
        m0 = b.metrics()
        # before any completion the latency keys are absent — exactly
        # the pre-registry behaviour
        assert set(m0) == GOLDEN_BATCHER_KEYS - {
            "latency_ms_p50", "latency_ms_p99"}
        b.predict(numpy.zeros((2, 3), numpy.float32))
        # the worker wakes the caller BEFORE it counts the batch and
        # observes its latency: give it a moment
        deadline = time.monotonic() + 5.0
        m = b.metrics()
        while "latency_ms_p50" not in m and time.monotonic() < deadline:
            time.sleep(0.01)
            m = b.metrics()
        assert set(m) == GOLDEN_BATCHER_KEYS
        assert m["requests_total"] == 1
        assert isinstance(m["requests_total"], int)
        assert m["batches_total"] == 1
        assert m["latency_ms_p50"] > 0
        json.dumps(m)               # JSON-serializable end to end
    finally:
        b.close()


class _StubRegistry:
    """Just enough ModelRegistry surface for the frontend."""

    def __init__(self, batcher):
        self._batcher = batcher

    def describe(self):
        return []

    def metrics(self):
        return {"m": self._batcher.metrics()}


def _get_raw(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode(), r.headers.get("Content-Type")


def test_frontend_metrics_endpoints():
    """/metrics is Prometheus text, /metrics.json the original JSON."""
    from veles.serving.batcher import MicroBatcher
    from veles.serving.frontend import ServingFrontend
    b = MicroBatcher(lambda rows: (rows, len(rows)),
                     max_wait_ms=0.5, name="batcher-m", model="m")
    front = None
    try:
        b.predict(numpy.zeros((1, 3), numpy.float32))
        front = ServingFrontend(_StubRegistry(b), port=0)
        base = "http://127.0.0.1:%d" % front.port
        doc = json.loads(_get_raw(base + "/metrics.json")[0])
        assert set(doc["models"]["m"]) == GOLDEN_BATCHER_KEYS
        text, ctype = _get_raw(base + "/metrics")
        assert ctype.startswith("text/plain")
        assert "# TYPE veles_serving_requests_total counter" in text
        assert 'veles_serving_requests_total{model="m"} 1' in text
        assert 'veles_serving_latency_seconds_count{model="m"} 1' \
            in text
    finally:
        if front is not None:
            front.close()
        b.close()


# -- web status: /metrics + escaping -----------------------------------


def test_web_status_metrics_and_html_escaping():
    from veles.web_status import WebStatus
    telemetry.counter("t_scrape_total").inc(3)
    ws = WebStatus(port=0)
    try:
        ws.register("evil", lambda: {
            "workflow": "<script>alert(1)</script>",
            "epoch": 1})
        base = "http://127.0.0.1:%d" % ws.port
        text, ctype = _get_raw(base + "/metrics")
        assert ctype.startswith("text/plain")
        assert "t_scrape_total 3" in text
        page = _get_raw(base + "/")[0]
        # provider values are untrusted page content: every cell is
        # escaped, a hostile workflow name cannot break the dashboard
        assert "<script>alert(1)</script>" not in page
        assert "&lt;script&gt;" in page
    finally:
        ws.close()


# -- cluster aggregation: one scrape sees the whole cluster ------------


def test_master_scrape_aggregates_slave_counters():
    from veles.client import SlaveClient
    from veles.server import MasterServer
    from tests.test_service import make_wf
    master_wf = make_wf("TeleMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server.start_background()
    slave_wf = make_wf("TeleSlave")
    slave_wf.is_slave = True
    client = SlaveClient(slave_wf,
                         "127.0.0.1:%d" % server.bound_address[1],
                         name="tele-slave", io_timeout=10.0)
    jobs = client.run_forever()
    assert jobs > 0 and server.done.is_set()
    reg = telemetry.get_registry()
    # slave-pushed counters landed under slave="1" series
    assert reg.counter_total("veles_slave_jobs_done_total",
                             slave="1") >= 1
    assert reg.counter_total("veles_loader_samples_total",
                             slave="1", cls="train") > 0
    # master-side counters are in the same registry
    assert reg.counter_total("veles_cluster_faults_total",
                             kind="joins") >= 1
    assert reg.counter_total("veles_master_requests_total",
                             kind="update") >= jobs
    # the faults dict view matches the registry counters
    assert server.faults["joins"] == reg.counter_total(
        "veles_cluster_faults_total", kind="joins")
    text = reg.render_prometheus()
    assert 'slave="1"' in text
    assert "# TYPE veles_cluster_faults_total counter" in text


# -- logger satellite: JSONL postmortems -------------------------------


def test_jsonl_handler_serializes_exc_info(tmp_path):
    import logging
    from veles.logger import _JsonlHandler
    path = str(tmp_path / "log.jsonl")
    handler = _JsonlHandler(path)
    logger = logging.getLogger("tele-jsonl-test")
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        try:
            raise ValueError("boom for the postmortem")
        except ValueError:
            logger.exception("it failed")
        logger.info("plain line")
    finally:
        logger.removeHandler(handler)
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) == 2
    exc_row, plain_row = rows
    assert exc_row["msg"] == "it failed"
    assert "Traceback (most recent call last)" in exc_row["exc"]
    assert "boom for the postmortem" in exc_row["exc"]
    assert "ValueError" in exc_row["exc"]
    assert "exc" not in plain_row
    # timestamps are the records' own creation times, in order
    assert 0 < exc_row["t"] <= plain_row["t"]


def test_distributed_trace_merges_three_processes(tmp_path):
    """ISSUE 6 acceptance: a 2-slave training run with ``--trace-out``
    on the master produces ONE Perfetto file in which at least one
    job's dispatch, wire, slave-compute and merge spans share one
    trace_id across three real processes (master + 2 slaves), with
    per-process track names."""
    import socket
    import subprocess
    import threading
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    trace = str(tmp_path / "cluster.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    # enough jobs (10/epoch x 3) that BOTH slaves serve some even if
    # one's interpreter start lags the other by a few seconds — with
    # a handful of jobs the early slave drains the whole run alone
    # and the merged trace shows only two pids
    overrides = [
        os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
        "root.mnist.decision.max_epochs=3",
        "root.mnist.loader.n_train=400",
        "root.mnist.loader.n_valid=100",
        "root.mnist.loader.minibatch_size=50",
        "-d", "numpy", "--no-stats", "--seed", "11",
    ]
    cli = [sys.executable, "-m", "veles"]
    master = subprocess.Popen(
        cli + overrides + ["--listen-address", "127.0.0.1:%d" % port,
                           "--trace-out", trace],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    slaves = [subprocess.Popen(
        cli + overrides + ["--master-address",
                           "127.0.0.1:%d" % port,
                           "--slave-retries", "60"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for _ in range(2)]

    def _drain(proc, sink):
        sink.append(proc.communicate()[0])

    outs = {p: [] for p in [master] + slaves}
    threads = [threading.Thread(target=_drain, args=(p, outs[p]))
               for p in [master] + slaves]
    for t in threads:
        t.start()
    try:
        master.wait(timeout=420)
        for p in slaves:
            p.wait(timeout=120)
    finally:
        for p in [master] + slaves:
            if p.poll() is None:
                p.kill()
    for t in threads:
        t.join(timeout=30)
    assert master.returncode == 0, outs[master]
    with open(trace) as f:
        doc = json.load(f)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    pids = {e["pid"] for e in spans}
    assert len(pids) >= 3, "expected master+2 slave pids, got %s" % pids
    track_names = {e["args"]["name"] for e in meta
                   if e["name"] == "process_name"}
    assert "master" in track_names, track_names
    assert any(n.startswith("slave") for n in track_names), track_names
    by_trace = {}
    for e in spans:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    want = {"job.dispatch", "job.wire", "slave.compute", "job.merge"}
    full = [evs for evs in by_trace.values()
            if want <= {e["name"] for e in evs}]
    assert full, "no job with the full causal chain: %s" % sorted(
        {e["name"] for evs in by_trace.values() for e in evs})
    # the chain genuinely crosses the process boundary
    chain = full[0]
    master_pid = next(e["pid"] for e in chain
                      if e["name"] == "job.dispatch")
    slave_pid = next(e["pid"] for e in chain
                     if e["name"] == "slave.compute")
    assert master_pid != slave_pid


# -- CLI acceptance: --trace-out on a sample run -----------------------


def test_velescli_trace_out(tmp_path):
    trace = str(tmp_path / "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "velescli.py"),
         os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
         "root.mnist.loader.n_train=120",
         "root.mnist.loader.n_valid=40",
         "root.mnist.loader.minibatch_size=40",
         "root.mnist.decision.max_epochs=1",
         "-d", "numpy", "--seed", "7", "--no-stats",
         "--trace-out", trace],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "trace -> %s" % trace in r.stdout
    with open(trace) as f:
        doc = json.load(f)
    # span events plus ph="M" process_name metadata (the launcher
    # names this pid's track — ISSUE 6 per-process track names)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert events, "empty trace"
    assert any(e["name"] == "process_name" for e in meta)
    for e in events:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
    names = {e["name"] for e in events}
    assert "workflow.run" in names
    assert any(n.endswith(".run") for n in names - {"workflow.run"})
