"""Cluster health plane (ISSUE 8): the time-series ring, readiness
probes, SLO burn-rate alerts, the fleet scraper (`velescli top`), the
503+Retry-After rejection path, trace-correlated JSONL logs and the
bench self-check — unit level first, then the end-to-end chaos
acceptance run (master + 2 slaves under ChaosProxy).

Determinism: unit-level SLO/ring tests drive ``HealthMonitor.tick``
with injected timestamps (no sampler thread, no wall-clock luck); the
chaos acceptance asserts on convergence of states behind generous
deadlines, never on exact timing.
"""

import json
import logging
import statistics
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

from veles import health, telemetry
from veles.health import HealthMonitor


def _get(url, timeout=10):
    """(code, json_doc) — non-200 probe answers carry JSON too."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as exc:
        return exc.code, json.load(exc)


@pytest.fixture
def mnist_config_guard():
    """make_wf (tests/test_service.py) mutates root.mnist without
    restoring; tests here that build workflows must not leak that
    config into later files (test_mnist_functional reads it)."""
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


# -- the time-series ring ----------------------------------------------


def test_history_ring_samples_and_windows():
    mon = HealthMonitor(interval=0.5, max_samples=4)
    c = telemetry.counter("veles_serving_shed_total", "x", ("model",))
    g = telemetry.gauge("veles_cluster_slaves", "x")
    h = telemetry.histogram("veles_serving_latency_seconds", "x",
                            ("model",))
    t0 = time.time() - 6
    for i in range(6):
        c.labels("m").inc(2)
        g.set(i)
        h.labels("m").observe(0.01 * (i + 1))
        mon.tick(now=t0 + i)
    doc = mon.history_doc(window=3600)
    # bounded: maxlen=4 kept only the newest 4 ticks (the
    # constructor's own tick was evicted by the ring)
    assert doc["samples"] == 4
    series = doc["series"]
    assert series['veles_serving_shed_total{model="m"}'][-1][1] == 12.0
    assert series["veles_cluster_slaves"][-1][1] == 5.0
    key = 'veles_serving_latency_seconds{model="m"}'
    assert key + ":p50" in series and key + ":p99" in series
    assert series[key + ":count"][-1][1] == 6.0
    # the window filter works off the recorded walls
    mon.close()


def test_history_window_query_filters_by_wall():
    mon = HealthMonitor(interval=0.1, max_samples=100)
    g = telemetry.gauge("veles_cluster_slaves", "x")
    g.set(1)
    mon._samples.clear()        # drop the constructor's own sample
    now = time.time()
    mon.tick(now=now - 30)
    mon.tick(now=now - 1)
    doc = mon.history_doc(window=5)
    assert doc["samples"] == 1          # only the fresh sample
    assert mon.history_doc(window=3600)["samples"] == 2
    mon.close()


def test_series_value_sums_family_children():
    from veles.health import _series_value
    flat = {'veles_serving_shed_total{model="a"}': 3.0,
            'veles_serving_shed_total{model="b"}': 4.0,
            'veles_serving_latency_seconds{model="a"}:p99': 0.5}
    assert _series_value(flat, "veles_serving_shed_total") == 7.0
    assert _series_value(
        flat, 'veles_serving_shed_total{model="b"}') == 4.0
    # percentile keys resolve exactly, and never sum into the family
    assert _series_value(
        flat,
        'veles_serving_latency_seconds{model="a"}:p99') == 0.5
    assert _series_value(flat, "veles_serving_latency_seconds") \
        is None
    assert _series_value(flat, "veles_absent_total") is None
    # label VALUES containing a colon still sum into the family
    # (only the }:pNN suffix keys are excluded)
    colon = {'veles_req_total{endpoint="host:8080"}': 2.0,
             'veles_req_total{endpoint="host:8081"}': 3.0}
    assert _series_value(colon, "veles_req_total") == 5.0


# -- readiness checks --------------------------------------------------


def test_readiness_checks_and_probe_cache():
    mon = HealthMonitor(interval=5.0)
    ok, reasons = mon.ready_state()
    assert ok and reasons == []         # no checks -> ready
    state = {"ok": True}
    mon.add_check("thing", lambda: (state["ok"], None)
                  if state["ok"] else (False, "thing broke"))
    assert mon.ready_state()[0] is True
    state["ok"] = False
    mon.tick()
    ok, reasons = mon.ready_state()
    assert ok is False
    assert any("thing broke" in r for r in reasons)
    code, doc = mon.probe("/readyz")
    assert code == 503 and doc["checks"]["thing"]["ok"] is False
    # a RAISING check degrades to not-ready with the exception named,
    # never kills the tick
    mon.add_check("bad", lambda: 1 / 0)
    ok, reasons = mon.ready_state()
    assert ok is False
    assert any("ZeroDivisionError" in r for r in reasons)
    mon.remove_check("bad")
    state["ok"] = True
    mon.tick()
    assert mon.ready_state()[0] is True
    # liveness flips on shutdown
    assert mon.probe("/healthz")[0] == 200
    mon.mark_shutdown()
    assert mon.probe("/healthz")[0] == 503
    assert mon.ready_state()[0] is False
    mon.close()


# -- SLO engine --------------------------------------------------------


def _slaves_slo(**over):
    spec = {"name": "slaves_floor", "series": "veles_cluster_slaves",
            "op": ">=", "threshold": 2, "target": 0.9,
            "fast_window": 4.0, "slow_window": 12.0,
            "burn_threshold": 1.0}
    spec.update(over)
    return spec


def test_slo_threshold_fires_and_resolves_multi_window():
    mon = HealthMonitor(interval=1.0)
    g = telemetry.gauge("veles_cluster_slaves", "x")
    g.set(2)
    mon.add_slo(_slaves_slo())
    t0 = 5000.0
    for i in range(12):                 # healthy history
        mon.tick(now=t0 + i)
    assert mon.ready_state()[0] is True
    slo = mon.slos()[0]
    assert not slo.firing and slo.burn_fast == 0.0
    # sustained violation: both windows cross the burn threshold
    g.set(1)
    fired_at = None
    for i in range(12, 24):
        mon.tick(now=t0 + i)
        if mon.slos()[0].firing and fired_at is None:
            fired_at = i
    assert fired_at is not None, "alert never fired"
    ok, reasons = mon.ready_state()
    assert ok is False
    assert any("slo:slaves_floor" in r for r in reasons)
    # exported gauges carry the state
    firing = telemetry.gauge(
        "veles_slo_alert_firing",
        labels=("objective",)).labels("slaves_floor")
    assert firing.value == 1.0
    # the transition landed in the flight-recorder event log
    events = [e for e in telemetry.tracer.recent_events()
              if e["event"] == "slo_alert"]
    assert events and events[-1]["state"] == "firing"
    assert events[-1]["objective"] == "slaves_floor"
    # recovery: good samples age the violation out of both windows;
    # the FAST window clears first, which is what ends the alert
    g.set(2)
    resolved_at = None
    for i in range(24, 48):
        mon.tick(now=t0 + i)
        if not mon.slos()[0].firing and resolved_at is None:
            resolved_at = i
    assert resolved_at is not None, "alert never resolved"
    assert firing.value == 0.0
    assert mon.ready_state()[0] is True
    events = [e for e in telemetry.tracer.recent_events()
              if e["event"] == "slo_alert"]
    assert events[-1]["state"] == "resolved"
    mon.close()


def test_slo_ratio_kind_counter_deltas():
    mon = HealthMonitor(interval=1.0)
    bad = telemetry.counter("veles_serving_error_total", "x")
    total = telemetry.counter("veles_serving_requests_total", "x")
    mon.add_slo({"name": "error_ratio", "kind": "ratio",
                 "bad": "veles_serving_error_total",
                 "total": "veles_serving_requests_total",
                 "target": 0.9, "fast_window": 4.0,
                 "slow_window": 8.0, "burn_threshold": 1.0})
    t0 = 9000.0
    for i in range(10):                 # healthy traffic
        total.inc(10)
        mon.tick(now=t0 + i)
    assert not mon.slos()[0].firing
    for i in range(10, 20):             # 50% errors: burn 5x budget
        total.inc(10)
        bad.inc(5)
        mon.tick(now=t0 + i)
    slo = mon.slos()[0]
    assert slo.firing, (slo.burn_fast, slo.burn_slow)
    assert slo.burn_fast == pytest.approx(5.0, rel=0.25)
    for i in range(20, 40):             # clean traffic again
        total.inc(10)
        mon.tick(now=t0 + i)
    assert not mon.slos()[0].firing
    mon.close()


def test_slo_spec_validation_and_file_loading(tmp_path):
    mon = HealthMonitor(interval=5.0)
    with pytest.raises(ValueError):
        mon.add_slo({"series": "x", "threshold": 1})   # no name
    with pytest.raises(ValueError, match="missing required key"):
        mon.add_slo({"name": "p99"})                   # no series
    with pytest.raises(ValueError, match="missing required key"):
        mon.add_slo({"name": "r", "kind": "ratio",
                     "bad": "veles_x_total"})          # no total
    with pytest.raises(ValueError):
        mon.add_slo(_slaves_slo(target=1.5))           # bad target
    with pytest.raises(ValueError):
        mon.add_slo(_slaves_slo(op="~="))              # bad op
    with pytest.raises(ValueError):
        mon.add_slo(_slaves_slo(bogus=1))              # unknown key
    mon.add_slo(_slaves_slo())
    with pytest.raises(ValueError):
        mon.add_slo(_slaves_slo())                     # duplicate
    path = tmp_path / "slos.json"
    path.write_text(json.dumps([
        _slaves_slo(name="from_file"),
        {"name": "ratio_from_file", "kind": "ratio",
         "bad": "veles_serving_error_total",
         "total": "veles_serving_requests_total"},
    ]))
    assert mon.load_slo_file(str(path)) == 2
    assert {s.name for s in mon.slos()} \
        == {"slaves_floor", "from_file", "ratio_from_file"}
    # the readiness doc describes every objective
    doc = mon.probe("/readyz")[1]
    assert set(doc["slos"]) == {s.name for s in mon.slos()}
    mon.close()


# -- serving frontend: rejection + probes ------------------------------


class _ShedModel:
    input_sample_shape = (4,)


class _ShedEntry:
    """Registry entry whose batcher queue is always full."""
    name = "m"
    model = _ShedModel()
    version = 1
    warm = True
    checkpoint = None

    def predict(self, rows, timeout_ms=None, trace=None, tenant=None):
        from veles.serving.batcher import QueueFull
        raise QueueFull("queue full (256 rows pending, max 256)")


def _post_predict(base, doc):
    req = urllib.request.Request(
        base + "/v1/predict", data=json.dumps(doc).encode(),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), json.load(resp)
    except urllib.error.HTTPError as exc:
        return exc.code, dict(exc.headers), json.load(exc)


def test_frontend_not_ready_503_retry_after_and_counter():
    """Satellite: an empty (cold) registry means /readyz false, and
    POST /v1/predict answers 503 + Retry-After with the reason —
    counted under veles_serving_rejected_total{reason="not_ready"}."""
    from veles.serving.frontend import ServingFrontend
    from veles.serving.registry import ModelRegistry
    with health.scoped(HealthMonitor(interval=30.0)):
        registry = ModelRegistry(backend="numpy")
        front = ServingFrontend(registry, port=0)
        try:
            base = "http://127.0.0.1:%d" % front.port
            code, doc = _get(base + "/readyz")
            assert code == 503
            assert any("no models loaded" in r
                       for r in doc["reasons"])
            code, headers, reply = _post_predict(
                base, {"model": "m", "inputs": [[1, 2, 3, 4]]})
            assert code == 503
            assert headers.get("Retry-After") == "5"
            assert any("no models loaded" in r
                       for r in reply["reasons"])
            reg = telemetry.get_registry()
            assert reg.counter_total("veles_serving_rejected_total",
                                     reason="not_ready") == 1.0
        finally:
            front.close()


def test_frontend_shed_503_retry_after_and_counter():
    """Satellite: a full batcher queue answers 503 + Retry-After and
    counts reason="shed" (previously a generic 503 body only)."""
    from veles.serving.frontend import ServingFrontend
    from veles.serving.registry import ModelRegistry
    with health.scoped(HealthMonitor(interval=30.0)):
        registry = ModelRegistry(backend="numpy")
        registry._models["m"] = _ShedEntry()
        front = ServingFrontend(registry, port=0)
        try:
            base = "http://127.0.0.1:%d" % front.port
            assert _get(base + "/readyz")[0] == 200
            code, headers, reply = _post_predict(
                base, {"model": "m", "inputs": [[1, 2, 3, 4]]})
            assert code == 503
            assert headers.get("Retry-After") == "1"
            assert "queue full" in reply["error"]
            reg = telemetry.get_registry()
            assert reg.counter_total("veles_serving_rejected_total",
                                     reason="shed") == 1.0
        finally:
            front.close()


def test_frontend_history_endpoint_serves_ring():
    from veles.serving.frontend import ServingFrontend
    from veles.serving.registry import ModelRegistry
    with health.scoped(HealthMonitor(interval=30.0)) as mon:
        telemetry.gauge("veles_cluster_slaves", "x").set(3)
        registry = ModelRegistry(backend="numpy")
        front = ServingFrontend(registry, port=0)
        try:
            mon.tick()
            code, doc = _get("http://127.0.0.1:%d"
                             "/metrics/history?window=60" % front.port)
            assert code == 200
            assert doc["series"]["veles_cluster_slaves"][-1][1] == 3.0
        finally:
            front.close()


# -- JSONL log / trace correlation -------------------------------------


def test_jsonl_logs_carry_trace_ids(tmp_path):
    """Satellite: log lines emitted on behalf of a traced request
    carry its trace_id/span_id; unrelated lines don't."""
    from veles.logger import _JsonlHandler
    path = str(tmp_path / "log.jsonl")
    handler = _JsonlHandler(path)
    logger = logging.getLogger("trace-corr-test")
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        ctx = telemetry.TraceContext.new()
        with telemetry.context(ctx):
            logger.info("inside the trace")
        logger.info("outside the trace")
    finally:
        logger.removeHandler(handler)
    rows = [json.loads(line) for line in open(path)]
    assert rows[0]["msg"] == "inside the trace"
    assert rows[0]["trace_id"] == ctx.trace_id
    assert rows[0]["span_id"] == ctx.span_id
    assert "trace_id" not in rows[1]


def test_context_nesting_restores_previous():
    a, b = telemetry.TraceContext.new(), telemetry.TraceContext.new()
    assert telemetry.current_context() is None
    with telemetry.context(a):
        assert telemetry.current_context() is a
        with telemetry.context(b):
            assert telemetry.current_context() is b
        assert telemetry.current_context() is a
    assert telemetry.current_context() is None


# -- fleet scraper / velescli top --------------------------------------


def test_parse_prometheus_exposition():
    from veles.fleet import metric_total, parse_prometheus
    text = "\n".join((
        "# HELP veles_x_total help text",
        "# TYPE veles_x_total counter",
        'veles_x_total{kind="a"} 3',
        'veles_x_total{kind="b",other="q\\"uote"} 4.5',
        "veles_up 1",
        "garbage line without value",
        'veles_lat_bucket{le="+Inf"} 7',
    ))
    m = parse_prometheus(text)
    assert m[("veles_up", ())] == 1.0
    assert m[("veles_x_total", (("kind", "a"),))] == 3.0
    assert metric_total(m, "veles_x_total") == 7.5
    assert metric_total(m, "veles_x_total", kind="b") == 4.5
    assert metric_total(m, "veles_absent") is None
    # escape decoding is one left-to-right pass: an escaped
    # backslash followed by a literal n must NOT become a newline
    esc = parse_prometheus('veles_p{path="C:\\\\new\\nline"} 1')
    assert esc[("veles_p", (("path", "C:\\new\nline"),))] == 1.0


def test_top_json_snapshot_over_live_endpoints(capsys):
    """`velescli top --json` against a live web-status: the snapshot
    names the target, its readiness and the fleet summary."""
    from veles.fleet import top_main
    from veles.web_status import WebStatus
    with health.scoped(HealthMonitor(interval=0.1)) as mon:
        telemetry.gauge("veles_cluster_slaves", "x").set(2)
        mon.tick()
        ws = WebStatus(port=0)
        try:
            base = "http://127.0.0.1:%d" % ws.port
            rc = top_main(["--json", base])
            assert rc == 0
            snap = json.loads(capsys.readouterr().out)
            assert snap["fleet"]["targets"] == 1
            assert snap["fleet"]["reachable"] == 1
            assert snap["fleet"]["ready"] == 1
            assert snap["fleet"]["slaves"] == 2
            row = snap["targets"][0]
            assert row["url"] == base and row["ready"] is True
        finally:
            ws.close()
    # an unreachable fleet exits 2 (scriptable)
    rc = top_main(["--json", "http://127.0.0.1:9/"])
    out = capsys.readouterr().out
    assert rc == 2
    assert json.loads(out)["fleet"]["reachable"] == 0


def test_scrape_degrades_pre_health_plane_target():
    """A live process whose /healthz 404s with a TEXT body (pre-PR-8
    dashboard) must scrape as reachable-but-not-live, never DOWN."""
    import http.server
    from veles.fleet import scrape_target

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b"not found"
            self.send_response(404)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        row = scrape_target(
            "http://127.0.0.1:%d" % httpd.server_address[1])
        assert row["reachable"] is True
        assert row["live"] is False
        assert row["healthz"] is None
        assert row["ready"] is None     # no /readyz either
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_top_once_renders_dashboard(capsys):
    from veles.fleet import top_main
    from veles.web_status import WebStatus
    with health.scoped(HealthMonitor(interval=0.1)):
        ws = WebStatus(port=0)
        try:
            rc = top_main(["--once",
                           "http://127.0.0.1:%d" % ws.port])
        finally:
            ws.close()
    assert rc == 0
    out = capsys.readouterr().out
    assert "veles fleet" in out and "TARGET" in out


# -- bench self-check --------------------------------------------------


def test_bench_self_check_flags_directional_regressions(tmp_path,
                                                        capsys):
    import bench
    baseline = {
        "n": 1, "rc": 0,
        "parsed": {
            "metric": "mnist_train_steps_per_sec", "value": 1000.0,
            "extra": {
                "cifar_conv_images_per_sec": 200.0,
                "grad_sync_wire_bytes_per_step_int8": 100000,
                "lm_57M_tokens_per_sec": 50000.0,
                "lm_57M_tokens_per_sec_best": 60000.0,
                "calibration_matmul8k_bf16_tflops": 150.0,
                "dist_scaling_steps_per_sec_n2": 100.0,
                "dist_scaling_efficiency_n2": 0.8,
                "profiler_overhead_pct": 1.0,
                "generate_tokens_per_sec_continuous": 4000.0,
                "generate_first_token_latency_s": 0.01,
                "lm_mfu_s8192": 0.35,
                "bias_grad_step_seconds": 0.002,
                "serving_cache_bytes_int8": 200000,
                "serving_throughput_rps_int8": 3000.0,
                "model_stats_overhead_pct": 0.5,
                "loadgen_shed_rate_pct": 1.0,
                "serving_rejected_per_sec": 10.0,
                "routed_capacity_rps_at_p99_slo": 100.0,
                "lint_full_tree_seconds": 10.0,
                "lint_full_tree_warm_seconds": 2.0,
                "some_row_error": "boom",
            }}}
    path = tmp_path / "BENCH_r07.json"
    path.write_text(json.dumps(baseline))
    report = {
        "metric": "mnist_train_steps_per_sec", "value": 800.0,
        "extra": {
            "cifar_conv_images_per_sec": 195.0,       # -2.5%: fine
            "grad_sync_wire_bytes_per_step_int8": 150000,  # +50%: bad
            "lm_57M_tokens_per_sec": 55000.0,         # +10%: fine
            # ISSUE 9: scaling rows are throughput/efficiency figures
            # — DOWN is the bad direction for both families
            "dist_scaling_steps_per_sec_n2": 50.0,    # -50%: bad
            "dist_scaling_efficiency_n2": 0.4,        # -50%: bad
            # ISSUE 10: profiler overhead is a COST — UP is bad
            "profiler_overhead_pct": 2.5,             # +150%: bad
            # ISSUE 11: decode throughput DOWN and first-token
            # latency UP are the bad directions
            "generate_tokens_per_sec_continuous": 2000.0,  # -50%: bad
            "generate_first_token_latency_s": 0.05,        # +400%: bad
            # ISSUE 14: an MFU ratio is a utilization figure — DOWN
            # is bad (explicitly in bench._HIGHER_BETTER, immune to
            # any lower-better substring); kernel step seconds and
            # the quantized cache footprint are costs — UP is bad;
            # quantized serving rps is throughput — DOWN is bad
            "lm_mfu_s8192": 0.20,                          # -43%: bad
            "bias_grad_step_seconds": 0.004,               # +100%: bad
            "serving_cache_bytes_int8": 400000,            # +100%: bad
            "serving_throughput_rps_int8": 3300.0,         # +10%: fine
            # ISSUE 15: in-graph model-stat cost is an overhead — UP
            # is the bad direction ("overhead" is in _LOWER_BETTER)
            "model_stats_overhead_pct": 1.8,               # +260%: bad
            # ISSUE 18: shed/rejected rates are costs — UP is bad;
            # routed capacity carries a "p99" substring but is a
            # capacity figure (bench._HIGHER_BETTER) — DOWN is bad
            "loadgen_shed_rate_pct": 5.0,                  # +400%: bad
            "serving_rejected_per_sec": 20.0,              # +100%: bad
            "routed_capacity_rps_at_p99_slo": 50.0,        # -50%: bad
            # ISSUE 20: lint wall times are costs ("seconds" is in
            # _LOWER_BETTER) — a warm-cache regression means the
            # incremental cache stopped earning its keep
            "lint_full_tree_seconds": 9.0,                 # -10%: fine
            "lint_full_tree_warm_seconds": 6.0,            # +200%: bad
        }}
    regressed = bench.self_check(report, threshold_pct=10.0,
                                 baseline_path=str(path))
    err = capsys.readouterr().err
    # throughput DOWN 20% and byte-count UP 50% regress; the small
    # dip, the improvement, _best and calibration keys don't
    assert set(regressed) == {"mnist_train_steps_per_sec",
                              "grad_sync_wire_bytes_per_step_int8",
                              "dist_scaling_steps_per_sec_n2",
                              "dist_scaling_efficiency_n2",
                              "profiler_overhead_pct",
                              "generate_tokens_per_sec_continuous",
                              "generate_first_token_latency_s",
                              "lm_mfu_s8192",
                              "bias_grad_step_seconds",
                              "serving_cache_bytes_int8",
                              "model_stats_overhead_pct",
                              "loadgen_shed_rate_pct",
                              "serving_rejected_per_sec",
                              "routed_capacity_rps_at_p99_slo",
                              "lint_full_tree_warm_seconds"}
    assert "REGRESSION" in err and "warn-only" in err
    assert "_best" not in err.split("rows in baseline")[0]
    # no baseline -> a note, no crash, nothing regressed
    assert bench.self_check(report, baseline_path=str(
        tmp_path / "missing.json")) == []


def test_bench_latest_artifact_natural_order(tmp_path):
    import bench
    for n in (2, 10, 9):
        (tmp_path / ("BENCH_r%02d.json" % n)).write_text("{}")
    assert bench._latest_bench_artifact(str(tmp_path)).endswith(
        "BENCH_r10.json")
    assert bench._latest_bench_artifact(
        str(tmp_path / "empty")) is None


# -- snapshot-store breaker flips /readyz ------------------------------


def test_readyz_snapshot_breaker_trips_and_recovers(
        mnist_config_guard):
    """Satellite chaos: tripping the master's snapshot-store circuit
    breaker flips /readyz to 503 naming the store; the half-open
    probe closing the breaker flips it back."""
    import http.server
    from veles.snapshotter import HTTPSnapshotStore
    from tests.test_service import make_wf
    from veles.server import MasterServer

    fails = {"n": 0}
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
            body = blobs.get(name)
            if body is None:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    server = None
    try:
        url = "http://127.0.0.1:%d/snaps" % httpd.server_address[1]
        store = HTTPSnapshotStore(url, timeout=5, retries=0,
                                  breaker_threshold=1,
                                  breaker_reset=0.2)
        wf = make_wf("BreakerHealthWF", max_epochs=None)
        wf.decision.max_epochs = 50
        server = MasterServer(wf, "127.0.0.1:0", max_epochs=50)
        server.start_background()
        server.checkpoint_store = store
        with health.scoped(HealthMonitor(interval=30.0)) as mon:
            server.register_health(mon)
            assert mon.ready_state()[0] is True
            # trip: one failing GET opens the breaker
            fails["n"] = 1
            with pytest.raises(OSError):
                store.get("ok.ckpt.npz")
            assert store.breaker_open()
            mon.tick()
            ok, reasons = mon.ready_state()
            assert ok is False
            assert any("snapshot-store circuit breaker" in r
                       for r in reasons)
            # recovery: reset window passes, the half-open probe
            # succeeds, the breaker closes
            time.sleep(0.25)
            assert store.get("ok.ckpt.npz") == b"payload"
            assert not store.breaker_open()
            mon.tick()
            assert mon.ready_state()[0] is True
    finally:
        if server is not None:
            server.kill()
        httpd.shutdown()
        httpd.server_close()


# -- end-to-end chaos acceptance ---------------------------------------


def test_cluster_health_chaos_acceptance(capsys,
                                         mnist_config_guard):
    """Acceptance (ISSUE 8): a real master + 2 slaves run under
    ChaosProxy. A mid-job slave kill degrades the slave-floor SLO,
    which fires a burn-rate alert visible in /debug/events and as a
    veles_slo_* gauge, flips /readyz with a reason naming the
    objective, and `velescli top --json` over the live processes
    reports the degraded target; probe endpoints answer fast while
    training is in flight; a replacement slave resolves the alert and
    flips /readyz back."""
    from tests.test_service import make_wf
    from veles.chaos import ChaosProxy
    from veles.client import SlaveClient
    from veles.fleet import parse_prometheus, top_main
    from veles.server import MasterServer
    from veles.web_status import WebStatus

    master_wf = make_wf("HealthChaosMaster", max_epochs=None)
    master_wf.decision.max_epochs = 10000   # outlives the scenario
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=10000,
                          slave_timeout=5.0)
    server.start_background()

    def wait_until(fn, timeout=60, what=""):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            v = fn()
            if v:
                return v
            time.sleep(0.05)
        pytest.fail("timed out waiting for %s" % (what or fn))

    clients, threads = [], []
    ws = proxy = None
    try:
        with health.scoped(HealthMonitor(interval=0.05)) as mon:
            server.register_health(mon)
            ws = WebStatus(port=0)
            ws.register("cluster", server.status)
            base = "http://127.0.0.1:%d" % ws.port
            proxy = ChaosProxy(
                ("127.0.0.1", server.bound_address[1]), seed=7,
                delay_rate=0.05, delay_s=0.01)

            def run_slave(idx, max_retries):
                wf = make_wf("HealthChaosSlave%d" % idx)
                wf.is_slave = True
                client = SlaveClient(
                    wf, proxy.address, name="hc-%d" % idx,
                    io_timeout=2.0, retry_base=0.02, retry_max=0.25,
                    max_retries=max_retries)
                clients.append(client)
                try:
                    client.run_forever()
                except ConnectionError:
                    pass            # the killed slave gives up — the
                                    # scenario under test

            for idx, retries in ((0, None), (1, 0)):
                t = threading.Thread(target=run_slave,
                                     args=(idx, retries))
                t.start()
                threads.append(t)
            wait_until(lambda: server.status()["n_slaves"] == 2,
                       what="both slaves joining")
            # the floor objective goes in once the fleet is at
            # strength; the ring may still hold pre-join samples
            # inside the slow window, so readiness SETTLES to 200 as
            # they age out rather than holding it instantly
            mon.add_slo({"name": "cluster_slaves_floor",
                         "series": "veles_cluster_slaves",
                         "op": ">=", "threshold": 2, "target": 0.9,
                         "fast_window": 0.5, "slow_window": 1.5,
                         "burn_threshold": 1.0})
            wait_until(lambda: _get(base + "/readyz")[0] == 200,
                       timeout=30,
                       what="/readyz settling after both joins")

            # probes answer fast WHILE training is in flight: the
            # handler reads one cached attribute, so even a loaded
            # CI box keeps the median far under the 50ms budget
            for path in ("/healthz", "/readyz"):
                times = []
                for _ in range(20):
                    t0 = time.perf_counter()
                    code, _doc = _get(base + path)
                    times.append(time.perf_counter() - t0)
                    assert code in (200, 503)
                assert statistics.median(times) < 0.05, (path, times)

            # mid-job kill: sever every proxied connection. Slave 1
            # (max_retries=0) dies for good; slave 0 reconnects and
            # keeps training — the cluster runs degraded at 1 < 2
            assert proxy.kill_all() >= 2
            wait_until(
                lambda: not threads[1].is_alive(),
                what="killed slave giving up")
            wait_until(lambda: server.status()["n_slaves"] == 1,
                       what="master dropping the dead slave")

            # the burn-rate alert fires and flips /readyz with a
            # reason naming the objective
            def degraded():
                code, doc = _get(base + "/readyz")
                return (code, doc) if code == 503 else None
            code, doc = wait_until(degraded, timeout=30,
                                   what="/readyz flipping to 503")
            assert any("cluster_slaves_floor" in r
                       for r in doc["reasons"]), doc
            assert doc["slos"]["cluster_slaves_floor"]["firing"]

            # ... visible as a veles_slo_* gauge on /metrics
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as resp:
                metrics = parse_prometheus(
                    resp.read().decode("utf-8", "replace"))
            assert metrics[(
                "veles_slo_alert_firing",
                (("objective", "cluster_slaves_floor"),))] == 1.0

            # ... and in the flight recorder's event log
            events = json.loads(urllib.request.urlopen(
                base + "/debug/events", timeout=10).read())["events"]
            fired = [e for e in events if e["event"] == "slo_alert"
                     and e.get("state") == "firing"]
            assert fired
            assert fired[-1]["objective"] == "cluster_slaves_floor"

            # velescli top --json over the live process reports the
            # degraded target (what an autoscaler would consume)
            rc = top_main(["--json", base])
            assert rc == 0
            snap = json.loads(capsys.readouterr().out)
            assert snap["fleet"]["firing_slos"] \
                == ["cluster_slaves_floor"]
            assert snap["fleet"]["degraded"] == [base]
            row = snap["targets"][0]
            assert row["ready"] is False and row["role"] == "master"
            assert row["master"]["n_slaves"] == 1
            assert any("cluster_slaves_floor" in r
                       for r in row["reasons"])
            # the per-slave timing the master already tracks is
            # merged into the snapshot (the surviving slave's row)
            assert len(row["master"]["slaves"]) == 1

            # the history ring recorded the degradation trajectory
            # (the ring samples at its own cadence: the surviving
            # slave's 1.0 may be one tick away when the status already
            # says 1)
            def degraded_trajectory():
                series = _get(base + "/metrics/history?window=120")[1][
                    "series"]["veles_cluster_slaves"]
                return any(v == 2.0 for _, v in series) \
                    and any(v == 1.0 for _, v in series)

            wait_until(degraded_trajectory, timeout=10,
                       what="the ring recording 2 then 1 slaves")

            # recovery: a replacement slave joins through the proxy;
            # the alert resolves and /readyz flips back to 200
            t = threading.Thread(target=run_slave, args=(2, None))
            t.start()
            threads.append(t)
            wait_until(lambda: server.status()["n_slaves"] == 2,
                       what="replacement slave joining")
            wait_until(lambda: _get(base + "/readyz")[0] == 200,
                       timeout=30, what="/readyz recovering")
            assert not mon.slos()[0].firing
            events = json.loads(urllib.request.urlopen(
                base + "/debug/events", timeout=10).read())["events"]
            assert any(e["event"] == "slo_alert"
                       and e.get("state") == "resolved"
                       for e in events)
    finally:
        server.kill()
        for client in clients:
            client.request_stop()
        if proxy is not None:
            proxy.close()
        for t in threads:
            t.join(timeout=60)
        if ws is not None:
            ws.close()
    assert not any(t.is_alive() for t in threads), \
        "slave thread leaked past the scenario"
