"""Model-health plane (ISSUE 15, veles/model_health.py): in-graph
training-dynamics telemetry, the divergence detector + SLOs, verified
checkpoints, the rollback actuators, and the fleet surfaces.

Everything deterministic: detector tests feed observations directly,
the master-side tests drive server.handle() synchronously (no socket
luck), and the E2E runs real sockets with ONE planned poisoned update.
"""

import json
import threading
import time
import urllib.request

import numpy
import pytest

import veles.prng as prng
from veles import health, model_health, telemetry
from veles.chaos import poison_update
from veles.client import SlaveClient
from veles.config import root
from veles.distributable import DistributionRegistry
from veles.loader.base import CLASS_TRAIN
from veles.model_health import (ModelHealthMonitor, WeightGuard,
                                install_model_slos, take_stats)
from veles.server import MasterServer
from veles.snapshotter import (FileSnapshotStore, resolve_auto,
                               scan_checkpoints, write_checkpoint)
from tests.test_chaos import run_iteration, sequential_reference
from tests.test_service import make_wf


# -- the detector (pure observations) ----------------------------------


def test_take_stats_routes_stat_keys():
    outs = {"loss": 1.0, "stat/gd1": [1, 2, 3, 0], "n_err": 2}
    stats, rest = take_stats(outs)
    assert stats == {"gd1": [1, 2, 3, 0]}
    assert rest == {"loss": 1.0, "n_err": 2}


def test_nonfinite_stats_diverge_then_recover():
    mon = ModelHealthMonitor(recover_after=2)
    mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 0.0])})
    assert mon.verdict_state()[0] == "healthy"
    mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 3.0])})
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged"
    assert any("nonfinite:fc" in r for r in reasons)
    doc = mon.snapshot()
    assert doc["nonfinite_total"] == 3
    assert doc["layers"]["fc"]["nonfinite"] == 3.0
    # recovery: recover_after clean observations flip it back
    mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 0.0])})
    assert mon.verdict_state()[0] == "diverged"
    mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 0.0])})
    assert mon.verdict_state()[0] == "healthy"


def test_nonfinite_norm_counts_even_when_count_missed():
    """inf^2 overflow can turn the in-trace count into NaN/inf norms
    with count 0 — a non-finite norm still reads as >= 1 bad value."""
    mon = ModelHealthMonitor()
    mon.observe_stats(
        {"fc": numpy.array([numpy.nan, 5.0, 0.01, 0.0])})
    assert mon.verdict_state()[0] == "diverged"
    assert mon.snapshot()["nonfinite_total"] >= 1


def test_loss_spike_zscore_suspect_and_diverged():
    mon = ModelHealthMonitor(suspect_z=4.0, diverged_z=8.0,
                             ewma_alpha=0.2, recover_after=3)
    rng = numpy.random.Generator(numpy.random.PCG64(7))
    for i in range(20):
        mon.observe_loss(1.0 + 0.01 * rng.standard_normal(), epoch=i)
    assert mon.verdict_state()[0] == "healthy"
    mon.observe_loss(1.3, epoch=20)        # far above EWMA noise
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged"
    assert any("loss_spike" in r for r in reasons)
    assert mon.snapshot()["loss_zscore"] > 8.0


def test_loss_blowup_on_second_observation_is_caught():
    """Review fix: with one prior loss the variance is still 0 — the
    relative-jump fallback (loss > 4x baseline) must catch a finite
    blow-up instead of forcing z=0, and the spike must NOT fold into
    the EWMA baseline (later z-scores stay sensitive)."""
    mon = ModelHealthMonitor()
    mon.observe_loss(0.5, epoch=0)
    mon.observe_loss(1.0e6, epoch=1)
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged"
    assert any("loss_spike" in r for r in reasons)
    assert mon.snapshot()["loss_ewma"] == pytest.approx(0.5)


def test_nonfinite_loss_diverges_immediately():
    mon = ModelHealthMonitor()
    mon.observe_loss(float("nan"), epoch=0)
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged" and "loss_nonfinite" in reasons


def test_grad_explosion_flags_suspect():
    mon = ModelHealthMonitor(explosion_factor=10.0)
    for _ in range(5):
        mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 0.0])})
    mon.observe_stats({"fc": numpy.array([50.0, 5.0, 0.01, 0.0])})
    verdict, reasons = mon.verdict_state()
    assert verdict == "suspect"
    assert any("grad_explosion:fc" in r for r in reasons)


def test_clean_wire_notes_do_not_clear_a_diverged_latch():
    """A poisoned merge is followed by the SAME update frame's other
    units reporting 0 — clean notes are TIME-paced (at most one
    healthy observation per wire_recovery_interval), so a burst of
    per-unit notes — however many units the model has — can never
    re-earn healthy before the ring samples the spike or the guard
    ticks."""
    mon = ModelHealthMonitor(recover_after=2)
    mon.note_wire_nonfinite("gd2", 4, slave=7)
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged"
    assert any("slave 7" in r for r in reasons)
    for _ in range(100):                    # a wide model's frame
        mon.note_wire_nonfinite("gd1", 0)
    assert mon.verdict_state()[0] == "diverged"
    # once the pacing interval elapses, clean notes recover
    mon.wire_recovery_interval = 0.0
    for _ in range(3):
        mon.note_wire_nonfinite("gd1", 0)
    assert mon.verdict_state()[0] == "healthy"


def test_absorb_slave_republishes_and_folds_verdict():
    mon = ModelHealthMonitor()
    mon.absorb_slave({"loss": 0.5, "verdict": "healthy",
                      "layers": {"fc": {"grad_norm": 1.5,
                                        "weight_norm": 4.0,
                                        "update_ratio": 0.01,
                                        "nonfinite": 0}}}, 3)
    assert mon.verdict_state()[0] == "healthy"
    assert "3" in mon.snapshot()["slaves"]
    reg = telemetry.get_registry()
    fam = reg.gauge("veles_model_grad_norm")
    values = {items: child.value for items, child in fam.children()}
    assert values[(("layer", "fc"), ("slave", "3"))] == 1.5
    # a slave that judged ITSELF diverged flips the master's verdict
    mon.absorb_slave({"loss": 9.9, "verdict": "diverged",
                      "layers": {}}, 4)
    verdict, reasons = mon.verdict_state()
    assert verdict == "diverged"
    assert any("slave_diverged:4" in r for r in reasons)


def test_healthy_slave_summaries_do_not_clear_diverged_latch():
    """Review fix: with NaN merged into the canonical weights, the
    OTHER slaves' routine healthy summaries keep arriving — they must
    not advance the healthy streak and re-stamp checkpoints healthy
    within seconds."""
    mon = ModelHealthMonitor(recover_after=2)
    mon.note_wire_nonfinite("gd", 3, slave=1)
    assert mon.verdict_state()[0] == "diverged"
    for _ in range(10):
        mon.absorb_slave({"loss": 0.4, "verdict": "healthy",
                          "layers": {}}, 2)
    assert mon.verdict_state()[0] == "diverged"


def test_weight_guard_does_not_stash_while_suspect():
    """Review fix: a finite blow-up flags suspect before the loss
    z-score confirms diverged — the guard must keep the PRE-spike
    stash through that window, not refresh onto spiked weights."""
    master_wf = make_wf("MHGuardSus", max_epochs=None)
    master_wf.decision.max_epochs = 2
    guard = WeightGuard(master_wf, stash_interval=1)
    guard.tick()                            # healthy -> stash armed
    w_good = numpy.array(
        master_wf.forwards[0].weights.map_read().mem)
    mon = model_health.get_model_monitor()
    # grad explosion: suspect
    for _ in range(4):
        mon.observe_stats({"fc": numpy.array([1.0, 5.0, 0.01, 0.0])})
    mon.observe_stats({"fc": numpy.array([99.0, 5.0, 0.01, 0.0])})
    assert mon.verdict_state()[0] == "suspect"
    # weights drift while suspect; guard ticks must NOT re-stash
    master_wf.forwards[0].weights.map_write().mem += 100.0
    guard.tick()
    mon.note_wire_nonfinite("fc", 1)        # now confirmed diverged
    assert guard.tick()                     # -> restore
    numpy.testing.assert_array_equal(
        master_wf.forwards[0].weights.map_read().mem, w_good)


def test_disabled_plane_never_judges():
    """Review fix: --model-stats off stands the WHOLE plane down — a
    loss spike or wire NaN must not flip the verdict (and thereby
    stamp checkpoints diverged / skip them on resume) while the
    operator turned the observability off."""
    mon = ModelHealthMonitor()
    mon.enabled = False
    mon.observe_loss(float("nan"), epoch=0)
    mon.note_wire_nonfinite("fc", 9)
    assert mon.verdict_state() == ("healthy", [])
    assert mon.snapshot()["loss"] is not None   # gauges still record
    # the MANIFEST stamp must not claim positive health a blind run
    # never established ("unknown" blobs still resume/serve — only
    # "diverged" is skipped)
    assert mon.manifest_stamp()["verdict"] == "unknown"


def test_render_survives_garbled_model_doc():
    """Review fix: a version-skewed /debug/model doc (non-numeric
    loss/rollbacks) degrades the row, never crashes the render."""
    from veles.fleet import render_snapshot
    row = {"url": "http://x:1", "reachable": True, "ready": True,
           "model": {"verdict": "diverged", "loss": "oops",
                     "rollbacks": "many", "layers": {"fc": "bad"}}}
    snap = {"ts": 0.0, "targets": [row],
            "fleet": {"targets": 1, "reachable": 1, "ready": 1,
                      "slaves": 0, "firing_slos": [],
                      "degraded": []}}
    out = render_snapshot(snap)
    assert "verdict diverged" in out


def test_serving_drift_gauges():
    mon = ModelHealthMonitor()
    # already a distribution: rows sum to 1
    probs = numpy.array([[0.8, 0.1, 0.1], [0.6, 0.3, 0.1]])
    mon.observe_serving("mnist", probs)
    drift = mon.snapshot()["serving"]["mnist"]
    assert 0.0 < drift["entropy"] < numpy.log(3.0) + 1e-9
    assert drift["top1_margin"] == pytest.approx(
        numpy.mean([0.7, 0.3]), abs=1e-6)
    # logits get softmaxed first; 1-D / scalar outputs are ignored
    mon.observe_serving("lm", numpy.array([[5.0, 1.0, 0.0]]))
    assert mon.snapshot()["serving"]["lm"]["top1_margin"] > 0.9
    mon.observe_serving("reg", numpy.array([1.0, 2.0]))
    assert "reg" not in mon.snapshot()["serving"]
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_serving_logit_entropy") > 0


# -- SLO wiring --------------------------------------------------------


def test_model_slos_fire_on_nonfinite_and_flip_readyz():
    """Acceptance piece: one bad ring sample fires model_nonfinite
    within a tick (= an evaluation tick in a live run), and /readyz's
    cached verdict names the objective."""
    hm = health.get_monitor()
    added = install_model_slos(hm)
    assert added == 3
    assert install_model_slos(hm) == 0      # idempotent
    hm.tick()
    assert hm.probe("/readyz")[0] == 200
    model_health.get_model_monitor().note_wire_nonfinite("fc", 2)
    hm.tick()
    code, doc = hm.probe("/readyz")
    assert code == 503
    assert any("model_nonfinite" in r for r in doc["reasons"])
    assert doc["slos"]["model_nonfinite"]["firing"]
    # the verdict objective fires too (gauge 2 == diverged)
    assert doc["slos"]["model_divergence"]["firing"]
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_slo_alert_firing",
                             objective="model_nonfinite") == 1.0


def test_register_health_check_names_divergence():
    hm = health.get_monitor()
    mon = model_health.get_model_monitor()
    mon.register_health(hm)
    hm.tick()
    assert hm.probe("/readyz")[0] == 200
    mon.note_wire_nonfinite("fc", 1)
    hm.tick()
    code, doc = hm.probe("/readyz")
    assert code == 503
    assert any("model diverged" in r for r in doc["reasons"])


# -- in-graph stats on a real compiled run -----------------------------


def test_xla_run_publishes_layer_stats_and_off_switch():
    """The compiled MNIST run exports per-GD-unit stat vectors as one
    fused extra output; the monitor sees finite norms for every layer
    and the judged loss. Flipping collection off removes them."""
    wf = make_wf("MHStatsOn", backend="xla", max_epochs=2)
    wf.run()
    doc = model_health.get_model_monitor().snapshot()
    assert doc["loss"] is not None and doc["verdict"] == "healthy"
    layer_names = set(doc["layers"])
    assert len(layer_names) == 2            # one per GD unit
    for stats in doc["layers"].values():
        assert stats["grad_norm"] > 0.0
        assert stats["weight_norm"] > 0.0
        assert 0.0 <= stats["update_ratio"] < 1.0
        assert stats["nonfinite"] == 0.0
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_model_nonfinite_total") == 0.0

    with model_health.scoped() as fresh:
        wf2 = make_wf("MHStatsOff", backend="xla", max_epochs=2)
        wf2.xla_step.set_stats_enabled(False)
        wf2.run()
        assert fresh.snapshot()["layers"] == {}
        # the loss feed rides the decision, not the stat outputs
        assert fresh.snapshot()["loss"] is not None


def test_stats_stride_sentinels_are_skipped():
    """A stride longer than the run still publishes the t=0 sample
    and NEVER a sentinel row (negative norms must not reach the
    monitor)."""
    with model_health.scoped() as fresh:
        wf = make_wf("MHStride", backend="xla", max_epochs=1)
        wf.xla_step.stats_interval = 10 ** 6
        wf.xla_step.compiler.stats_stride = 10 ** 6
        wf.run()
        layers = fresh.snapshot()["layers"]
        assert layers, "the t=0 sample must publish"
        for stats in layers.values():
            assert stats["weight_norm"] >= 0.0


# -- verified checkpoints ----------------------------------------------


def test_manifest_verdict_stamped_and_auto_resume_skips(tmp_path):
    """Every checkpoint MANIFEST carries the verdict; resolve_auto
    skips 'diverged' blobs (counted), scan_checkpoints/`velescli
    checkpoints` surface the verdict column."""
    wf = make_wf("MHSnap", snapdir=str(tmp_path))
    wf.run()
    infos = scan_checkpoints(str(tmp_path))
    assert infos and all(i.health_verdict == "healthy"
                         for i in infos if i.status == "valid")
    healthy_names = {i.name for i in infos}
    # now the run diverges and a rolling checkpoint gets written
    model_health.get_model_monitor().note_wire_nonfinite("gd", 5)
    wf.snapshotter.export_snapshot(slot="current")
    bad = [i for i in scan_checkpoints(str(tmp_path))
           if i.name not in healthy_names]
    assert len(bad) == 1 and bad[0].health_verdict == "diverged"
    resolved = resolve_auto(str(tmp_path), prefixes={wf.snapshotter.prefix})
    assert resolved is not None
    _, name, _ = resolved
    assert name in healthy_names, \
        "auto-resume must fall back past the diverged blob"
    reg = telemetry.get_registry()
    assert reg.counter_total(
        "veles_checkpoint_diverged_skips_total") >= 1.0
    # the audit CLI shows the verdict column
    from veles.__main__ import checkpoints_main
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = checkpoints_main([str(tmp_path), "--json"])
    assert rc == 0
    rows = json.loads(buf.getvalue())
    assert {r["verdict"] for r in rows} == {"healthy", "diverged"}


def test_serving_refresh_refuses_diverged_checkpoint(tmp_path):
    """The registry's checkpoint refresh path: a blob whose MANIFEST
    says diverged raises (reload() then degrades to the loaded
    version) instead of grafting blown-up weights onto a server."""
    from veles.serving.model import ArchiveModel
    store = FileSnapshotStore(str(tmp_path))
    tree = {"params": {"fc": {
        "weights": numpy.ones((2, 2), numpy.float32)}}}
    write_checkpoint(store, "bad_=1.ckpt.npz.gz", tree,
                     extra_meta={"model_health":
                                 {"verdict": "diverged",
                                  "reasons": ["nonfinite_wire:fc"]}})
    write_checkpoint(store, "good_=1.ckpt.npz.gz", tree)
    model = ArchiveModel.__new__(ArchiveModel)
    model.params = {"fc": {
        "weights": numpy.zeros((2, 2), numpy.float32)}}
    with pytest.raises(ValueError, match="diverged"):
        model.load_checkpoint(str(tmp_path / "bad_=1.ckpt.npz.gz"))
    assert model.load_checkpoint(
        str(tmp_path / "good_=1.ckpt.npz.gz")) == 1
    assert model.params["fc"]["weights"][0, 0] == 1.0


# -- rollback actuators ------------------------------------------------


def _pump_one_update(server, sreg, slave_wf, sid, lease,
                     poison=False):
    """Pull jobs until a TRAIN one, run it on the slave workflow, and
    push the (optionally poisoned) update; -> the handle reply."""
    loader_name = server.workflow.loader.name
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
    if poison:
        uname, entry = poison_update(update)
        assert entry.startswith("d")
    return server.handle(
        ("update", sid, lease, job_id, epoch, update))


def test_weight_guard_restores_pre_spike_weights():
    """Chaos satellite: a NaN-poisoned delta merges, the master-side
    counter increments, and the guard's same-handle tick restores the
    stash — canonical weights return to the pre-spike values
    exactly."""
    master_wf = make_wf("MHGuardMaster", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          rollback_on_divergence=True)
    _, sid, lease = server.handle(("hello", "guard-slave"))
    slave_wf = make_wf("MHGuardSlave")
    slave_wf.is_slave = True
    sreg = DistributionRegistry(slave_wf)

    assert _pump_one_update(server, sreg, slave_wf, sid,
                            lease) == ("ok",)
    w_stash = numpy.array(
        master_wf.forwards[0].weights.map_read().mem)
    assert _pump_one_update(server, sreg, slave_wf, sid, lease,
                            poison=True) == ("ok",)
    # the guard ticked inside handle(): weights are the stash again
    w_after = master_wf.forwards[0].weights.map_read().mem
    assert numpy.isfinite(w_after).all()
    numpy.testing.assert_array_equal(w_after, w_stash)
    assert server._weight_guard.rollback_count == 1
    reg = telemetry.get_registry()
    assert reg.counter_total("veles_model_nonfinite_total") >= 1.0
    verdict, _ = model_health.get_model_monitor().verdict_state()
    assert verdict == "suspect"             # latched until clean obs
    events = [e for e in telemetry.tracer.recent_events(50)
              if e.get("event") == "model_rollback"]
    assert events and events[-1]["source"] == "weight_guard"


def test_restore_stash_copies_instead_of_aliasing():
    """Review fix: Array.mem assignment aliases same-dtype arrays, so
    a restore must COPY — otherwise post-restore in-place merges
    corrupt the stash and a SECOND divergence restores post-spike
    values."""
    wf = make_wf("MHAlias", max_epochs=None)
    wf.decision.max_epochs = 2
    stash = wf.stash_state()
    fwd = wf.forwards[0]
    w0 = numpy.array(stash[fwd.name][0]["weights"])
    wf.restore_stash(stash)
    fwd.weights.map_write().mem[...] += 5.0     # the next merges
    numpy.testing.assert_array_equal(
        stash[fwd.name][0]["weights"], w0)      # stash untouched
    wf.restore_stash(stash)                     # second divergence
    numpy.testing.assert_array_equal(
        fwd.weights.map_read().mem, w0)


def test_nn_rollback_divergence_tick_restores():
    """Standalone actuator: NNRollback watches the verdict every
    cycle when rollback_on_divergence is set and restores its stash
    (cutting lr) without waiting for an epoch-loss blow-up."""
    prng.seed_all(31337)
    from veles.znicz_tpu.models import mnist
    saved = {k: getattr(root.mnist.loader, k, None)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.mnist.loader.update({"minibatch_size": 20,
                              "n_train": 100, "n_valid": 40})
    root.mnist.decision.max_epochs = 2
    try:
        wf = mnist.create_workflow(name="MHRollback")
        rb = wf.link_rollback(lr_cut=0.5)
        rb.rollback_on_divergence = True
        wf.initialize(device="numpy")
        wf.run()                            # 2 sane epochs -> stash
    finally:
        root.mnist.loader.update(
            {k: v for k, v in saved.items() if v is not None})
    assert rb._stash is not None and rb.rollback_count == 0
    stash_w = rb._stash[wf.forwards[0].name][0]["weights"]
    # poison the live weights + flip the verdict, then tick
    wf.forwards[0].weights.map_write().mem[0, 0] = numpy.nan
    model_health.get_model_monitor().note_wire_nonfinite("gd", 1)
    rb.run()
    assert rb.rollback_count == 1
    w = wf.forwards[0].weights.map_read().mem
    assert numpy.isfinite(w).all()
    numpy.testing.assert_array_equal(w, stash_w)
    assert wf.gds[0].lr_scale == pytest.approx(0.5)
    verdict, _ = model_health.get_model_monitor().verdict_state()
    assert verdict == "suspect"


# -- master-side surfaces (deterministic, handle-level) ----------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


def test_master_divergence_surfaces(tmp_path):
    """The diverged state, frozen (no rollback guard): SLO fires
    within a tick, /readyz names it, /debug/model + the velescli top
    row report the diverged verdict, and the master's next persisted
    checkpoint is stamped diverged and skipped by resolve_auto."""
    from veles.web_status import WebStatus
    store = FileSnapshotStore(str(tmp_path))
    master_wf = make_wf("MHSurf", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          checkpoint_store=store)
    hm = health.get_monitor()
    install_model_slos(hm)
    web = WebStatus(port=0)
    try:
        _, sid, lease = server.handle(("hello", "surf-slave"))
        slave_wf = make_wf("MHSurfSlave")
        slave_wf.is_slave = True
        sreg = DistributionRegistry(slave_wf)
        assert _pump_one_update(server, sreg, slave_wf, sid,
                                lease) == ("ok",)
        healthy_uri = server.persist_state("pre-spike")
        assert healthy_uri
        assert _pump_one_update(server, sreg, slave_wf, sid, lease,
                                poison=True) == ("ok",)
        verdict, _ = model_health.get_model_monitor().verdict_state()
        assert verdict == "diverged"
        # the SLO fires within ONE evaluation tick of the engine
        hm.tick()
        code, doc = hm.probe("/readyz")
        assert code == 503
        assert any("model_nonfinite" in r for r in doc["reasons"])
        # /debug/model over real HTTP
        base = "http://127.0.0.1:%d" % web.port
        mdoc = _get_json(base + "/debug/model")
        assert mdoc["verdict"] == "diverged"
        assert mdoc["nonfinite_total"] >= 1
        # the velescli top row (fleet scraper + renderer)
        from veles.fleet import fleet_snapshot, render_snapshot
        snap = fleet_snapshot([base], timeout=10.0)
        row = snap["targets"][0]
        assert row["model"]["verdict"] == "diverged"
        rendered = render_snapshot(snap)
        assert "verdict diverged" in rendered
        # the next master checkpoint carries the diverged stamp and
        # auto-resume falls back to the pre-spike one
        diverged_uri = server.persist_state("post-spike")
        assert diverged_uri
        infos = {i.name: i for i in scan_checkpoints(str(tmp_path))}
        assert len(infos) == 2
        verdicts = sorted(i.health_verdict for i in infos.values())
        assert verdicts == ["diverged", "healthy"]
        resolved = resolve_auto(str(tmp_path),
                                prefixes={master_wf.name})
        assert resolved is not None
        assert healthy_uri.endswith(resolved[1])
    finally:
        web.close()


def test_top_degrades_against_pre_issue15_target():
    """`velescli top` satellite: a live target that predates
    /debug/model scrapes into a normal row — no model key, no error,
    and the renderer stays silent about it."""
    import http.server
    import socketserver

    class OldHandler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/healthz"):
                body, code = b'{"status": "ok"}', 200
            else:
                body, code = b"not found", 404
            self.send_response(code)
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
        assert row["reachable"] and "error" not in row
        assert "model" not in row
        snap = {"ts": 0.0, "targets": [row],
                "fleet": {"targets": 1, "reachable": 1, "ready": 0,
                          "slaves": 0, "firing_slos": [],
                          "degraded": []}}
        assert "model:" not in render_snapshot(snap)
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_absorbed_slave_summary_rides_telemetry_path():
    """The __telemetry__ side channel: a pushed model summary lands
    slave-labelled on the master and folds into its detector."""
    master_wf = make_wf("MHAbsorb", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    server._absorb_telemetry(
        {"model": {"loss": 0.7, "verdict": "healthy",
                   "layers": {"fc": {"grad_norm": 2.0,
                                     "weight_norm": 3.0,
                                     "update_ratio": 0.02,
                                     "nonfinite": 0}}}}, 11)
    doc = model_health.get_model_monitor().snapshot()
    assert "11" in doc["slaves"]
    reg = telemetry.get_registry()
    fam = reg.gauge("veles_model_loss")
    values = {items: c.value for items, c in fam.children()}
    assert values[(("slave", "11"),)] == 0.7


def test_serving_frontend_serves_debug_model():
    """The serving frontend answers /debug/model inline (same doc as
    web-status): drift gauges recorded by the batcher show up under
    'serving'."""
    from veles.serving.frontend import ServingFrontend
    from veles.serving.registry import ModelRegistry
    registry = ModelRegistry()
    front = ServingFrontend(registry, port=0)
    try:
        model_health.get_model_monitor().observe_serving(
            "toy", numpy.array([[0.9, 0.05, 0.05]]))
        doc = _get_json(
            "http://127.0.0.1:%d/debug/model" % front.port)
        assert doc["verdict"] == "healthy"
        assert "toy" in doc["serving"]
    finally:
        front.close()
        registry.close()


# -- chaos helper ------------------------------------------------------


def test_drop_slave_evicts_absorbed_model_summary():
    """Review fix: a departed slave's absorbed summary and its
    slave-labelled gauge children must not read as current forever."""
    master_wf = make_wf("MHEvict", max_epochs=None)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2)
    _, sid, _lease = server.handle(("hello", "evict-slave"))
    server._absorb_telemetry(
        {"model": {"loss": 0.9, "verdict": "healthy",
                   "layers": {"fc": {"grad_norm": 1.0,
                                     "weight_norm": 2.0,
                                     "update_ratio": 0.1,
                                     "nonfinite": 0}}}}, sid)
    mon = model_health.get_model_monitor()
    assert str(sid) in mon.snapshot()["slaves"]
    server.drop_slave(sid)
    assert str(sid) not in mon.snapshot()["slaves"]
    reg = telemetry.get_registry()
    for fam_name in ("veles_model_loss", "veles_model_grad_norm"):
        fam = reg.gauge(fam_name)
        assert not any(("slave", str(sid)) in items
                       for items, _ in fam.children()), fam_name


def test_poison_update_writes_through_noncontiguous():
    """Review fix: a strided/transposed delta view must be poisoned
    IN PLACE, not in a reshape copy that reads as success."""
    base = numpy.ones((4, 4), numpy.float32)
    view = base.T[::2]                      # non-contiguous
    assert not view.flags["C_CONTIGUOUS"]
    update = {"gd": {"dweights": view}}
    poison_update(update)
    assert not numpy.isfinite(view).all()


def test_poison_update_helper_contract():
    wf = make_wf("MHPoison", max_epochs=None)
    wf.decision.max_epochs = 2
    wf.is_slave = True
    sreg = DistributionRegistry(wf)
    wf.loader.run()
    run_iteration(wf)
    update = sreg.generate_update()
    uname, entry = poison_update(update)
    arr = update[uname][entry]
    assert not numpy.isfinite(arr.reshape(-1)[0])
    with pytest.raises(ValueError):
        poison_update({"unit": {"note": "no arrays here"}})


# -- the E2E acceptance ------------------------------------------------


def test_e2e_divergence_rollback_two_slaves():
    """ISSUE 15 acceptance: real master + 2 slaves over sockets,
    --rollback-on-divergence armed. One planned NaN-poisoned update:
    the divergence SLO fires within 2 evaluation ticks, /readyz flips
    naming the objective, exactly one rollback restores the pre-spike
    weights, and training runs on to match the unpoisoned sequential
    reference within the existing chaos tolerance."""
    w_ref = sequential_reference(max_epochs=2)

    master_wf = make_wf("MHE2EMaster", max_epochs=None)
    master_wf.loader.shuffle_enabled = False
    master_wf.loader._start_epoch(first=True)
    master_wf.decision.max_epochs = 2
    server = MasterServer(master_wf, "127.0.0.1:0", max_epochs=2,
                          slave_timeout=5.0,
                          rollback_on_divergence=True)
    hm = health.get_monitor()
    install_model_slos(hm)
    server.start_background()

    slaves = [make_wf("MHE2ESlave%d" % i) for i in range(2)]
    for wf in slaves:
        wf.is_slave = True
    clients, errors = [], []
    poisoned = threading.Event()

    def run_slave(wf, idx):
        client = SlaveClient(
            wf, "127.0.0.1:%d" % server.bound_address[1],
            name="mh-%d" % idx, io_timeout=2.0, retry_base=0.02,
            retry_max=0.25, max_retries=25)
        clients.append(client)
        if idx == 1:
            orig = client.registry.generate_update
            state = {"n": 0}

            def poisoned_update():
                update = orig()
                state["n"] += 1
                # poison exactly ONE update, once a clean merge has
                # armed the guard's stash
                if state["n"] == 3 and not poisoned.is_set():
                    try:
                        poison_update(update)
                        poisoned.set()
                    except ValueError:
                        pass            # eval-only payload: next one
                return update

            client.registry.generate_update = poisoned_update
        try:
            client.run_forever()
        except ConnectionError:
            if not server.done.is_set():
                errors.append("gave up before done")

    threads = [threading.Thread(target=run_slave, args=(wf, i))
               for i, wf in enumerate(slaves)]
    for t in threads:
        t.start()

    # the moment the poisoned update merges, the verdict flips; two
    # engine ticks bound the alert latency
    deadline = time.monotonic() + 120
    fired = False
    while time.monotonic() < deadline:
        if poisoned.is_set() and \
                server._weight_guard.rollback_count >= 1:
            hm.tick()
            code, doc = hm.probe("/readyz")
            if any("model_nonfinite" in r
                   for r in doc.get("reasons", ())):
                assert code == 503
                fired = True
                break
            hm.tick()                   # tick #2 of the bound
            code, doc = hm.probe("/readyz")
            assert code == 503, doc
            assert any("model_nonfinite" in r
                       for r in doc["reasons"])
            fired = True
            break
        time.sleep(0.01)
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert server.done.is_set(), server.status()
    assert poisoned.is_set()
    assert fired, "divergence SLO never fired"
    assert server._weight_guard.rollback_count == 1

    # the restored run converged onto the unpoisoned reference: the
    # only deviation is the single discarded minibatch delta
    w_master = numpy.asarray(
        master_wf.forwards[0].weights.map_read().mem)
    assert numpy.isfinite(w_master).all()
    numpy.testing.assert_allclose(w_master, w_ref, atol=0.02)

    doc = model_health.get_model_monitor().snapshot()
    assert doc["rollbacks"] == 1
    assert doc["nonfinite_total"] >= 1
    events = [e for e in telemetry.tracer.recent_events(100)
              if e.get("event") == "model_divergence"]
    assert any(e.get("verdict") == "diverged" for e in events)
