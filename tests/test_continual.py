"""Closed-loop continual training (ISSUE 16): streaming ingest ->
durable trainer -> verified-checkpoint rolling fleet refresh, with
staleness as the SLO.

Unit level first (round cursor, resume parity, shard leases, the
refresh poll's diverged gate, rolling-refresh policy, top rendering —
no sockets where possible), then the chaos run (BrownoutProxy
black-holes the HTTP ingest source -> the staleness burn-rate alert
fires and /readyz names the objective -> restore resolves), then the
slow-marked multi-process acceptance loop (continual trainer + two
``velescli serve`` replicas rolled one at a time with zero failed
requests and a staleness drop, diverged checkpoint never rolled out).
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
from veles import continual, fleet, telemetry
from veles.config import root
from veles.loader.stream import ArraySource, ContinualStreamLoader
from veles.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_until(fn, timeout=30.0, interval=0.02, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        value = fn()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError("timed out waiting for %s" % what)


def _source(n=256, dim=16, seed=5):
    rng = numpy.random.RandomState(seed)
    return ArraySource(
        rng.uniform(-1, 1, (n, dim)).astype(numpy.float32),
        rng.randint(0, 4, n).astype(numpy.int32))


def _loader(name="loader", source=None, **kwargs):
    kwargs.setdefault("minibatch_size", 32)
    kwargs.setdefault("round_samples", 128)
    kwargs.setdefault("valid_samples", 32)
    wf = Workflow(None, name="CW_" + name)
    ld = ContinualStreamLoader(
        wf, name=name, source=source or _source(), **kwargs)
    ld.initialize()
    return ld


def _serve_round(ld, collect_train=False):
    """Drive ld.run() through one full round; -> train indices (or
    [])."""
    out = []
    while True:
        ld.run()
        if collect_train and int(ld.minibatch_class) == 2:
            out.extend(
                ld.minibatch_indices.mem[:int(ld.minibatch_size)]
                .tolist())
        if bool(ld.epoch_ended):
            return out


# -- the streaming loader ----------------------------------------------


def test_rounds_advance_cursor_and_serve_stream_order():
    src = _source()
    ld = _loader(source=src)
    try:
        assert ld.cursor_base == 32        # head fed the pinned valid
        r1, first_batch = [], None
        while True:
            ld.run()
            if int(ld.minibatch_class) == 2:
                size = int(ld.minibatch_size)
                r1.extend(ld.minibatch_indices.mem[:size].tolist())
                if first_batch is None:
                    first_batch = numpy.array(
                        ld.minibatch_data.mem[:size])
            if bool(ld.epoch_ended):
                break
        assert ld.cursor_base == 160
        r2 = _serve_round(ld, collect_train=True)
        assert ld.cursor_base == 288
        off = ld.class_offset(2)
        assert r1 == list(range(off + 32, off + 160))
        assert r2 == list(range(off + 160, off + 288))
        # the round's data really is the stream window (position p
        # serves source row p, through the prefetch plane): the first
        # train minibatch of round 1 covers stream positions 32..63
        numpy.testing.assert_array_equal(
            first_batch, src.fetch(32, 32)["data"])
        # the bounded buffer never grows past its cap
        assert len(ld._blocks) <= ld.prefetch_blocks
        assert ld.last_ingest_wall > 0
    finally:
        ld.stop()


def test_checkpoint_cursor_resume_no_replay_no_skip():
    """The satellite contract: a resumed run continues at the next
    round's first position — the restored loader serves EXACTLY the
    round the original would have served next."""
    a = _loader(name="a")
    try:
        _serve_round(a)
        state = a.get_state()
        assert state["stream_cursor"]["cursor_base"] == 160
        next_round = _serve_round(a, collect_train=True)
    finally:
        a.stop()
    b = _loader(name="b")
    try:
        b.set_state(state)
        resumed = _serve_round(b, collect_train=True)
    finally:
        b.stop()
    assert resumed == next_round


def test_zlint_checkpoint_state_rule_passes_without_pragma():
    from veles.analysis import analyze_paths
    findings = analyze_paths(
        [os.path.join(REPO, "veles", "loader", "stream.py")],
        select=["checkpoint-state"])
    assert findings == []


def test_shard_assignment_is_sticky_and_steals_orphans():
    ld = _loader(shards=2, valid_samples=0, round_samples=128)
    try:
        ld.master_start_epoch()
        assert ld.cursor_base == 128       # queue filled == claimed
        mb = ld.max_minibatch_size

        def shard_of(job):
            return (int(job[1][0]) // mb) % 2

        j1 = ld.generate_data_for_slave("s1")
        j2 = ld.generate_data_for_slave("s2")
        assert shard_of(j1) == ld._slave_shards["s1"]
        assert shard_of(j2) == ld._slave_shards["s2"]
        assert shard_of(j1) != shard_of(j2)
        # each slave keeps pulling only its own shard while both live
        j1b = ld.generate_data_for_slave("s1")
        assert shard_of(j1b) == shard_of(j1)
        # s2 dies: its lease is released and s1 STEALS the orphaned
        # shard instead of wedging the round
        ld.drop_slave("s2")
        served = {tuple(j[1]) for j in (j1, j1b)}
        while True:
            job = ld.generate_data_for_slave("s1")
            if job is None:
                break
            assert tuple(job[1]) not in served
            served.add(tuple(job[1]))
        assert not ld._pending_jobs
        assert len(served) == 128 // mb
    finally:
        ld.stop()


def test_fetch_failures_counted_and_retried():
    class Flaky(ArraySource):
        def __init__(self, *args):
            super().__init__(*args)
            self.failures = 2

        def fetch(self, start, count):
            if start >= 32 and self.failures:
                self.failures -= 1
                raise OSError("synthetic ingest outage")
            return super().fetch(start, count)

    rng = numpy.random.RandomState(3)
    src = Flaky(rng.uniform(-1, 1, (64, 8)).astype(numpy.float32),
                rng.randint(0, 4, 64).astype(numpy.int32))
    ld = _loader(source=src, fetch_retry_s=0.01)
    try:
        _serve_round(ld)
        assert src.failures == 0
        assert telemetry.get_registry().counter_total(
            "veles_stream_fetch_failures_total") >= 2.0
    finally:
        ld.stop()


# -- the trainer loop --------------------------------------------------


def _continual_workflow(name, rounds_data=1024, snapdir=None):
    import veles.znicz_tpu.models.mnist  # noqa: populates root.mnist
    from veles.znicz_tpu.standard_workflow import StandardWorkflow
    prng.seed_all(1313)
    rng = numpy.random.RandomState(7)
    data = rng.uniform(-1, 1, (rounds_data, 784)).astype(numpy.float32)
    labels = rng.randint(0, 10, rounds_data).astype(numpy.int32)
    extra = {}
    if snapdir:
        extra["snapshotter_config"] = {"directory": snapdir}
    wf = StandardWorkflow(
        None, name=name, layers=root.mnist.layers,
        loader_factory=lambda w: ContinualStreamLoader(
            w, name="loader", minibatch_size=32,
            source=ArraySource(data, labels),
            round_samples=128, valid_samples=64),
        decision_config={"max_epochs": 1, "fail_iterations": 50},
        **extra)
    wf.initialize(device="cpu")
    return wf


def test_continual_loop_runs_rounds_and_publishes_staleness():
    wf = _continual_workflow("ContinualRounds")
    done = continual.continual_loop(wf, rounds=2)
    assert done == 2
    assert int(wf.decision.epoch_number) == 2
    # successive rounds consumed successive stream windows
    assert wf.loader.cursor_base == 64 + 2 * 128
    # the ingest clock is registered process-wide and the trainer
    # staleness point reads near-zero right after a round
    wall = continual.ingest_wall()
    assert wall and time.time() - wall < 60.0
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_continual_rounds_total") == 2.0
    stale = reg.gauge(continual.STALENESS_FAMILY,
                      labels=("point",)).labels("trainer").value
    assert 0.0 <= stale < 60.0
    # patience is disarmed: a shifting stream must not trip the
    # no-improvement stop between rounds
    assert wf.decision.fail_iterations == float("inf")


def test_checkpoints_carry_ingest_wall(tmp_path):
    from veles import snapshotter as S
    wf = _continual_workflow("ContinualSnap", snapdir=str(tmp_path))
    continual.continual_loop(wf, rounds=1)
    wf.snapshotter.export_snapshot(slot="current")
    infos = [i for i in S.scan_checkpoints(str(tmp_path))
             if i.status == "valid"]
    assert infos
    newest = infos[0]
    assert newest.ingest_wall is not None
    assert abs(newest.ingest_wall
               - wf.loader.last_ingest_wall) < 1e-6
    assert newest.health_verdict == "healthy"


# -- serving refresh + rolling fleet refresh ---------------------------


@pytest.fixture(scope="module")
def mnist_archive(tmp_path_factory):
    """An (untrained) exported MNIST MLP archive — the serving side's
    model; params are what checkpoints must shape-match."""
    prng.seed_all(77)
    from veles.znicz_tpu.models import mnist
    saved = {k: root.mnist.loader.get(k)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.mnist.loader.update({"minibatch_size": 50, "n_train": 200,
                              "n_valid": 50})
    base = tmp_path_factory.mktemp("continual_serving")
    try:
        wf = mnist.create_workflow(name="ContinualServe")
        wf.initialize(device="numpy")
        archive = str(base / "archive")
        wf.export_inference(archive)
        x = wf.loader.original_data.mem[:4].astype(numpy.float32)
        yield {"archive": archive, "x": x}
    finally:
        root.mnist.loader.update(saved)


def _write_ckpt(store_dir, name, params, scale, wall,
                verdict="healthy", ingest_wall=None):
    from veles import snapshotter as S
    store = S.store_for_base(str(store_dir), create=True)
    tree = {"params": {
        uname: {k: numpy.asarray(v, numpy.float32) * scale
                for k, v in attrs.items()}
        for uname, attrs in params.items()}}
    extra = {"wall_time": float(wall),
             "model_health": {"verdict": verdict,
                              "reasons": [] if verdict == "healthy"
                              else ["nonfinite_wire:fc"]}}
    if ingest_wall is not None:
        extra["ingest_wall"] = float(ingest_wall)
    S.write_checkpoint(store, name, tree, slot="current",
                       extra_meta=extra)


def test_refresh_newest_loads_healthy_and_skips_diverged(
        tmp_path, mnist_archive):
    from veles.serving import ModelRegistry
    reg = ModelRegistry(backend="numpy")
    try:
        entry = reg.load("mnist", mnist_archive["archive"],
                         refresh_store=str(tmp_path))
        params = entry.model.params
        t0 = time.time()
        _write_ckpt(tmp_path, "m_current-00000001.ckpt.npz.gz",
                    params, 0.5, t0 - 10, ingest_wall=t0 - 12)
        # the poisoned update: NEWEST blob, diverged verdict
        _write_ckpt(tmp_path, "m_current-00000002.ckpt.npz.gz",
                    params, 99.0, t0, verdict="diverged")
        before = telemetry.get_registry().counter_total(
            "veles_checkpoint_diverged_skips_total") or 0.0
        loaded = reg.refresh_newest("mnist")
        assert loaded and loaded.endswith("00000001.ckpt.npz.gz")
        entry = reg.get("mnist")
        assert entry.model.checkpoint_meta["wall_time"] == t0 - 10
        assert entry.model.checkpoint_meta["ingest_wall"] == t0 - 12
        assert telemetry.get_registry().counter_total(
            "veles_checkpoint_diverged_skips_total") == before + 1.0
        skips = [e for e in telemetry.tracer.recent_events()
                 if e["event"] == "refresh_skipped_diverged"]
        assert skips and skips[-1]["checkpoint"] == \
            "m_current-00000002.ckpt.npz.gz"
        # nothing newer (and the diverged blob stays refused): no-op
        assert reg.refresh_newest("mnist") is None
        # the scrape-side gauges carry the served wall + staleness
        g = telemetry.get_registry().gauge(
            "veles_serving_checkpoint_wall_seconds",
            labels=("model",)).labels("mnist")
        assert g.value == t0 - 10
        stale = telemetry.get_registry().gauge(
            continual.STALENESS_FAMILY,
            labels=("point",)).labels("serving:mnist").value
        assert 10.0 <= stale < 60.0
    finally:
        reg.close()


def test_refresh_http_endpoint(tmp_path, mnist_archive):
    from veles.serving import ModelRegistry
    from veles.serving.frontend import ServingFrontend
    reg = ModelRegistry(backend="numpy")
    front = None
    try:
        # the refresh plane only admits targets inside stores the
        # OPERATOR configured at load time (zlint untrusted-path):
        # attach the store here, not via the HTTP body
        entry = reg.load("mnist", mnist_archive["archive"],
                         refresh_store=str(tmp_path))
        t0 = time.time()
        _write_ckpt(tmp_path, "m_current-00000001.ckpt.npz.gz",
                    entry.model.params, 0.5, t0 - 5,
                    ingest_wall=t0 - 6)
        front = ServingFrontend(reg, port=0)
        base = "http://127.0.0.1:%d" % front.port
        req = urllib.request.Request(
            base + "/v1/models/mnist/refresh",
            data=json.dumps({"store": str(tmp_path)}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            doc = json.load(resp)
        assert doc["loaded"].endswith("00000001.ckpt.npz.gz")
        assert doc["checkpoint_meta"]["ingest_wall"] == t0 - 6
        # an explicitly-named diverged checkpoint is refused with 409
        _write_ckpt(tmp_path, "m_current-00000002.ckpt.npz.gz",
                    entry.model.params, 9.0, t0, verdict="diverged")
        req = urllib.request.Request(
            base + "/v1/models/mnist/refresh",
            data=json.dumps({"checkpoint": str(
                tmp_path / "m_current-00000002.ckpt.npz.gz")}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 409
        # a refresh target OUTSIDE every configured store is refused
        # with 400 before any filesystem access
        req = urllib.request.Request(
            base + "/v1/models/mnist/refresh",
            data=json.dumps(
                {"store": "/etc"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 400
        assert "outside" in json.load(err.value)["error"]
    finally:
        if front is not None:
            front.close()
        reg.close()


def test_controller_readmit_and_ckpt_wall_from_rows():
    from veles.router import ADMITTED, DRAINING, FleetController
    urls = ["http://a:1", "http://b:1"]
    ctl = FleetController(urls, interval=3600)

    def row(url, **metrics):
        return {"url": url, "reachable": True, "ready": True,
                "firing": [], "reasons": [], "metrics": metrics}

    ctl.tick(rows=[row("http://a:1", serving_ckpt_wall=123.0,
                       staleness_seconds=42.0),
                   row("http://b:1")])          # pre-PR-16 replica
    doc = {b["url"]: b for b in ctl.status_doc["backends"]}
    assert doc["http://a:1"]["ckpt_wall"] == 123.0
    assert doc["http://a:1"]["staleness"] == 42.0
    assert doc["http://b:1"]["ckpt_wall"] is None
    # drain -> readmit is a clean round trip; readmit refuses other
    # states (it must not shortcut the half-open probe)
    assert ctl.drain("http://a:1") == 0
    with ctl._lock:
        assert ctl._replicas["http://a:1"].state == DRAINING
    assert ctl.readmit("http://a:1") is True
    with ctl._lock:
        assert ctl._replicas["http://a:1"].state == ADMITTED
    assert ctl.readmit("http://a:1") is False
    assert ctl.readmit("http://nope:1") is False
    ctl.close()


def test_rolling_refresh_never_rolls_diverged(tmp_path, mnist_archive):
    """The orchestrator's poisoned-update gate at unit level: with the
    newest blob diverged, the newest HEALTHY wall is what replicas are
    compared against — replicas already there are left alone."""
    from veles.router import FleetController, RollingRefresh
    from veles.serving import ModelRegistry
    reg = ModelRegistry(backend="numpy")
    try:
        params = reg.load(
            "mnist", mnist_archive["archive"]).model.params
    finally:
        reg.close()
    t0 = time.time()
    _write_ckpt(tmp_path, "m_current-00000001.ckpt.npz.gz",
                params, 0.5, t0 - 10)
    _write_ckpt(tmp_path, "m_current-00000002.ckpt.npz.gz",
                params, 99.0, t0, verdict="diverged")
    rr = RollingRefresh(str(tmp_path), "mnist", period_s=0.0)
    info = rr._newest_healthy()
    assert info.name == "m_current-00000001.ckpt.npz.gz"
    skips = [e for e in telemetry.tracer.recent_events()
             if e["event"] == "refresh_skipped_diverged"]
    assert skips and skips[-1]["checkpoint"] == \
        "m_current-00000002.ckpt.npz.gz"
    ctl = FleetController(["http://a:1"], interval=3600)
    ctl.tick(rows=[{"url": "http://a:1", "reachable": True,
                    "ready": True, "firing": [], "reasons": [],
                    "metrics": {"serving_ckpt_wall": t0 - 10}}])
    # evaluate spawns the scan thread; it must decide "nothing to
    # roll" (replica already serves the newest HEALTHY wall)
    rr.evaluate(ctl)
    wait_until(lambda: not (rr._thread and rr._thread.is_alive()),
               what="refresh scan to finish")
    assert rr.describe()["rolls"] == 0
    with ctl._lock:
        assert ctl._replicas["http://a:1"].state == "admitted"
    ctl.close()


def test_top_renders_staleness_and_last_refresh_and_degrades():
    snap = {"fleet": {"targets": 2, "reachable": 2, "ready": 2,
                      "slaves": 0, "firing_slos": []},
            "targets": [
                {"url": "http://t:1", "reachable": True, "ready": True,
                 "role": "process",
                 "metrics": {"staleness_seconds": 42.0}},
                {"url": "http://r:1", "reachable": True, "ready": True,
                 "role": "router", "metrics": {},
                 "router": {"backends": [
                     {"url": "http://t:1", "state": "admitted"},
                     {"url": "http://u:1", "state": "admitted"}],
                     "rolling_refresh": {
                         "last": {"replica": "http://u:1",
                                  "outcome": "ok"}}}},
            ]}
    out = fleet.render_snapshot(snap)
    assert "staleness 42s" in out
    assert "last refresh: replica 1 (ok)" in out
    # pre-PR-16 rows (no staleness key, no rolling_refresh doc) must
    # only degrade
    for row in snap["targets"]:
        row["metrics"] = {}
        if "router" in row:
            row["router"].pop("rolling_refresh")
    out = fleet.render_snapshot(snap)
    assert "staleness" not in out and "last refresh" not in out


def test_fleet_metric_max_vs_total():
    metrics = {("veles_staleness_seconds", (("point", "trainer"),)): 7.0,
               ("veles_staleness_seconds",
                (("point", "serving:m"),)): 41.0}
    assert fleet.metric_max(metrics, "veles_staleness_seconds") == 41.0
    assert fleet.metric_max(metrics, "veles_nope") is None


# -- chaos: ingest black-hole -> staleness alert -----------------------


def test_blackhole_ingest_fires_staleness_slo_and_resolves():
    """The loop-stall drill: BrownoutProxy black-holes the HTTP ingest
    wire; staleness climbs past the objective, the burn-rate alert
    fires and /readyz names it; restoring the wire lets the round
    finish and the alert resolve."""
    from veles.chaos import BrownoutProxy
    from veles.health import HealthMonitor
    from veles.reactor import HttpServer
    src = _source(n=64, dim=8)
    server = HttpServer("127.0.0.1", 0,
                        continual.stream_handler(src),
                        name="ingest")
    proxy = BrownoutProxy("127.0.0.1:%d" % server.port)
    mon = HealthMonitor(interval=3600)   # ticked manually
    ld = None
    try:
        http_src = continual.HttpStreamSource(proxy.url, timeout=0.3)
        ld = _loader(source=http_src, minibatch_size=16,
                     round_samples=64, valid_samples=16,
                     fetch_retry_s=0.05, prefetch_blocks=2)
        continual.register_ingest_clock(
            lambda: ld.last_ingest_wall)
        continual.install_point_gauge("trainer",
                                      continual.ingest_wall)
        assert continual.install_staleness_slo(
            threshold=0.3, monitor=mon, fast_window=0.5,
            slow_window=1.0) == 1
        assert continual.install_staleness_slo(
            threshold=0.3, monitor=mon) == 0    # idempotent
        _serve_round(ld)
        mon.tick()
        assert not mon.slos()[0].firing
        def tick_firing():
            mon.tick()
            return mon.slos()[0].firing

        # black hole: connections wedge, bytes vanish — the producer
        # retries forever while the round stalls mid-flight
        proxy.set_black_hole(True)
        rounds, stop_evt = [0], threading.Event()

        def round_pump():
            try:
                while not stop_evt.is_set():
                    _serve_round(ld)
                    rounds[0] += 1
            except RuntimeError:
                pass    # loader stopped by the finally block

        runner = threading.Thread(target=round_pump, daemon=True)
        runner.start()
        wait_until(tick_firing, timeout=30.0, interval=0.1,
                   what="staleness alert to fire")
        assert rounds[0] == 0, "round finished through a black hole"
        ok, reasons = mon.ready_state()
        assert ok is False
        assert any("staleness" in r for r in reasons)
        # the alert can fire (0.3 s of staleness) before the first
        # wedged fetch has timed out (0.3 s from ITS start) and counted
        wait_until(lambda: telemetry.get_registry().counter_total(
            "veles_stream_fetch_failures_total") >= 1.0, timeout=10.0,
            interval=0.05, what="a wedged fetch to time out")
        # restore: the wedged round completes, ingest flows again and
        # good samples age the violation out of both windows
        proxy.restore()
        wait_until(lambda: rounds[0] > 0, timeout=30.0,
                   what="wedged round to complete")
        wait_until(lambda: not tick_firing(),
                   timeout=30.0, interval=0.1,
                   what="staleness alert to resolve")
        assert mon.ready_state()[0] is True
        stop_evt.set()
    finally:
        if ld is not None:
            ld.stop()
        proxy.kill_all()
        mon.close()
        server.close()


# -- the acceptance loop (multi-process, slow) -------------------------


@pytest.mark.slow
def test_continual_loop_end_to_end(tmp_path, mnist_archive):
    """ISSUE 16 acceptance: a 2-replica routed fleet serving an old
    checkpoint; a newer HEALTHY checkpoint lands in the store (plus a
    poisoned newest one) -> the rolling refresh rolls both replicas
    one at a time with ZERO failed requests, serving staleness drops,
    and the diverged blob is never rolled out."""
    from veles.router import (FleetController, RollingRefresh,
                              RouterFrontend)
    from veles.serving import ModelRegistry
    store = tmp_path / "store"
    store.mkdir()
    reg = ModelRegistry(backend="numpy")
    try:
        params = reg.load(
            "mnist", mnist_archive["archive"]).model.params
    finally:
        reg.close()
    t0 = time.time()
    _write_ckpt(store, "m_current-00000001.ckpt.npz.gz", params,
                1.0, t0 - 600, ingest_wall=t0 - 600)
    v1 = str(store / "m_current-00000001.ckpt.npz.gz")
    procs, fronts = [], []
    controller = front = refresher = None
    try:
        for i in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO, "velescli.py"),
                 "serve", "--model",
                 "mnist=%s" % mnist_archive["archive"],
                 "--checkpoint", "mnist=%s" % v1,
                 "--port", "0", "--backend", "numpy", "--no-warmup",
                 "--timeout-ms", "10000"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True))
        replicas = [json.loads(p.stdout.readline())["serving"]
                    for p in procs]
        refresher = RollingRefresh(str(store), "mnist", period_s=0.2,
                                   ready_timeout_s=30.0)
        controller = FleetController(replicas, interval=0.2,
                                     refresher=refresher)
        front = RouterFrontend(controller, port=0)
        x = mnist_archive["x"]
        payload = json.dumps({"model": "mnist",
                              "inputs": [x[0].tolist()],
                              "timeout_ms": 10000}).encode()

        def scraped_walls():
            rows = fleet.scrape_targets(replicas, timeout=5.0)
            return [r.get("metrics", {}).get("serving_ckpt_wall")
                    for r in rows]

        controller.ensure_started()
        wait_until(lambda: all(w == t0 - 600
                               for w in scraped_walls()),
                   what="both replicas serving v1")
        stale_before = max(
            r.get("metrics", {}).get("staleness_seconds") or 0.0
            for r in fleet.scrape_targets(replicas, timeout=5.0))
        assert stale_before >= 500.0
        # continuous client load through the router for the whole roll
        failures, counts, stop = [], [0], threading.Event()

        def hammer():
            while not stop.is_set():
                req = urllib.request.Request(
                    front.url + "/v1/predict", data=payload,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req,
                                                timeout=15) as resp:
                        json.load(resp)
                    counts[0] += 1
                except Exception as exc:
                    failures.append(repr(exc))

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        # fresh training output lands: a newer HEALTHY checkpoint and
        # an even newer POISONED one
        _write_ckpt(store, "m_current-00000002.ckpt.npz.gz", params,
                    0.5, t0 - 1, ingest_wall=t0 - 2)
        _write_ckpt(store, "m_current-00000003.ckpt.npz.gz", params,
                    99.0, t0, verdict="diverged")
        wait_until(lambda: all(w == t0 - 1 for w in scraped_walls()),
                   timeout=60.0,
                   what="both replicas rolled to v2")
        stop.set()
        for t in threads:
            t.join(timeout=20)
        assert not failures, failures[:3]
        assert counts[0] > 0
        # rolled one at a time, every roll ok, diverged never out
        rolls = refresher.rolls
        assert len(rolls) == 2
        assert all(r["outcome"] == "ok" for r in rolls)
        assert {r["checkpoint"] for r in rolls} == \
            {"m_current-00000002.ckpt.npz.gz"}
        assert {r["replica"] for r in rolls} == set(replicas)
        # staleness dropped end to end
        stale_after = max(
            r.get("metrics", {}).get("staleness_seconds") or 0.0
            for r in fleet.scrape_targets(replicas, timeout=5.0))
        assert stale_after < stale_before - 400.0
        # and the fleet stayed whole
        admitted, total = controller.counts()
        assert (admitted, total) == (2, 2)
    finally:
        if front is not None:
            front.close()
        if controller is not None:
            controller.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
