"""Genetics GA + ensemble (SURVEY.md §2.7 rows 8-9, L9)."""

import json
import os
import subprocess
import sys

import numpy
import pytest

import veles.prng as prng
from veles.config import Config, Tune, root
from veles.genetics import GeneticOptimizer, apply_values

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ga_minimizes_quadratic():
    """Pure-function sanity: the GA finds the box minimum."""
    tunables = {"a": Tune(5.0, -10.0, 10.0),
                "b": Tune(-3.0, -10.0, 10.0)}

    def evaluate(v):
        return (v["a"] - 2.0) ** 2 + (v["b"] - 7.0) ** 2

    opt = GeneticOptimizer(evaluate, tunables, population_size=16,
                           generations=12, seed=3)
    best, fitness = opt.run()
    assert fitness < 0.5, (best, fitness)
    assert abs(best["a"] - 2.0) < 0.6
    assert abs(best["b"] - 7.0) < 0.6


def test_ga_respects_discrete_and_bounds():
    tunables = {"n": Tune(4, 2, 16)}
    seen = []

    def evaluate(v):
        seen.append(v["n"])
        return abs(v["n"] - 9)

    opt = GeneticOptimizer(evaluate, tunables, population_size=12,
                           generations=8, seed=1)
    best, fitness = opt.run()
    assert all(isinstance(n, int) and 2 <= n <= 16 for n in seen)
    assert best["n"] == 9 and fitness == 0


def test_ga_failed_individuals_are_skipped():
    tunables = {"x": Tune(0.0, -1.0, 1.0)}

    def evaluate(v):
        if v["x"] < 0:
            raise RuntimeError("diverged")
        return v["x"]

    opt = GeneticOptimizer(evaluate, tunables, population_size=8,
                           generations=3, seed=2)
    best, fitness = opt.run()
    assert numpy.isfinite(fitness) and best["x"] >= 0


def test_find_and_apply_values():
    from veles.genetics import find_tunables
    cfg = Config("test_ga")
    cfg.update({"layer": {"lr": Tune(0.1, 0.001, 1.0)}})
    cfg.layers = [{"<-": {"lr": Tune(0.2, 0.01, 0.5)}}]
    found = find_tunables(cfg)
    assert set(found) == {"layer/lr", "layers/0/<-/lr"}
    apply_values(cfg, {"layer/lr": 0.25, "layers/0/<-/lr": 0.3})
    assert cfg.layer.lr == 0.25
    assert cfg.layers[0]["<-"]["lr"] == 0.3


def test_ga_improves_mnist_config():
    """The acceptance criterion from VERDICT: GA demonstrably improves
    a (deliberately mistuned) MNIST config."""
    import copy

    from veles.genetics import optimize_config
    from veles.znicz_tpu.models import mnist
    saved_layers = copy.deepcopy(root.mnist.layers)
    saved = {k: root.mnist.loader.get(k)
             for k in ("n_train", "n_valid", "minibatch_size")}
    root.mnist.loader.update(
        {"n_train": 200, "n_valid": 80, "minibatch_size": 40})
    root.mnist.decision.max_epochs = 2
    # mistuned lr, marked searchable
    for layer in root.mnist.layers:
        if "<-" in layer:
            layer["<-"]["learning_rate"] = Tune(1e-4, 1e-4, 0.1)

    def run_one():
        prng.seed_all(1234)
        wf = mnist.create_workflow(name="GAMnist")
        wf.initialize(device="numpy")
        wf.run()
        return float(wf.decision.best_metric)

    try:
        baseline = run_one()   # defaults = the mistuned lr
        opt = optimize_config(root.mnist, run_one,
                              population_size=5, generations=2, seed=9)
    finally:
        root.mnist.layers = saved_layers
        root.mnist.loader.update(saved)
        root.mnist.decision.max_epochs = 5
    assert opt.best_fitness <= baseline, \
        (opt.best_fitness, baseline)
    assert opt.best_fitness < baseline - 0.05, \
        "GA failed to improve the mistuned lr"


def test_ensemble_beats_or_matches_members():
    from veles.ensemble import Ensemble
    from veles.znicz_tpu.models import mnist
    saved = {k: root.mnist.loader.get(k)
             for k in ("n_train", "n_valid", "minibatch_size")}
    root.mnist.loader.update(
        {"n_train": 300, "n_valid": 100, "minibatch_size": 50})
    root.mnist.decision.max_epochs = 2

    def factory(name):
        return mnist.create_workflow(name=name)

    try:
        ens = Ensemble(factory, n_models=3, base_seed=42,
                       device="numpy")
        ens.train()
        report = ens.evaluate_classification()
    finally:
        root.mnist.loader.update(saved)
        root.mnist.decision.max_epochs = 5
    assert report["n_valid"] == 100
    assert len(report["member_errors"]) == 3
    # mean-of-softmax must not be worse than the weakest member
    assert report["ensemble_error"] <= max(report["member_errors"]), \
        report


def test_cli_optimize_smoke(tmp_path):
    """--optimize end-to-end through velescli (config file marks the
    lr searchable with Tune, reference-style)."""
    cfg = tmp_path / "ga_config.py"
    cfg.write_text(
        "from veles.config import root, Tune\n"
        "for layer in root.mnist.layers:\n"
        "    if '<-' in layer:\n"
        "        layer['<-']['learning_rate'] = "
        "Tune(0.02, 0.005, 0.1)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "veles",
         os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
         str(cfg),
         "root.mnist.loader.n_train=120",
         "root.mnist.loader.n_valid=40",
         "root.mnist.loader.minibatch_size=40",
         "root.mnist.decision.max_epochs=1",
         "-d", "numpy", "--seed", "5", "--no-stats",
         "--optimize", "1x3"],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert numpy.isfinite(doc["best_fitness"])
    assert doc["evaluations"] >= 3


def test_ga_parallel_matches_sequential(tmp_path):
    """A ProcessPoolMap generation scores EXACTLY like a sequential
    one (results in population order, per-individual seeding), and the
    search is deterministic given the seed — the rebuild's answer to
    the reference farming GA individuals to slaves."""
    from veles.genetics import (
        GeneticOptimizer, ProcessPoolMap, SubprocessTrainer,
        find_tunables)
    from veles.config import Tune, root

    cfg = tmp_path / "ga_config.py"
    cfg.write_text(
        "from veles.config import root, Tune\n"
        "for layer in root.mnist.layers:\n"
        "    if '<-' in layer:\n"
        "        layer['<-']['learning_rate'] = "
        "Tune(0.02, 0.005, 0.1)\n"
        "root.mnist.loader.n_train = 120\n"
        "root.mnist.loader.n_valid = 40\n"
        "root.mnist.loader.minibatch_size = 40\n"
        "root.mnist.decision.max_epochs = 1\n")
    wf_path = os.path.join(REPO, "veles/znicz_tpu/models/mnist.py")
    # tunables must match what the workers will see: workflow module
    # first (its defaults create root.mnist.layers), config on top —
    # Main.run ordering
    import veles.__main__ as vmain
    vmain.import_file(wf_path, "ga_wf_probe")
    vmain.import_file(str(cfg), "ga_cfg_probe")
    tunables = find_tunables(root)
    assert tunables, "config file produced no Tune leaves"

    def search(map_fn):
        evaluate = SubprocessTrainer(
            wf_path, str(cfg), seed=5, device="numpy")
        opt = GeneticOptimizer(
            evaluate, dict(tunables), generations=1,
            population_size=3, elite=1, seed=5, map_fn=map_fn)
        opt.run()
        return opt

    try:
        seq = search(None)
        with ProcessPoolMap(2) as pmap:
            par = search(pmap)
    finally:
        # the sequential path evaluates IN-PROCESS (config file + Tune
        # application mutate root.mnist, including the layer dicts in
        # place): re-executing the sample module restores its defaults
        # wholesale so later test modules see a clean tree
        vmain.import_file(wf_path, "ga_wf_probe")
    assert seq.evaluations == par.evaluations >= 4
    assert numpy.isfinite(par.best_fitness)
    # parallel == sequential: same champions, same fitness history
    assert [f for f, _ in seq.history] == [f for f, _ in par.history]
    assert seq.best_fitness == par.best_fitness
    assert seq.best_values == par.best_values


def test_cli_optimize_parallel_smoke(tmp_path):
    """--optimize GENSxPOPxWORKERS end-to-end through velescli."""
    cfg = tmp_path / "ga_config.py"
    cfg.write_text(
        "from veles.config import root, Tune\n"
        "for layer in root.mnist.layers:\n"
        "    if '<-' in layer:\n"
        "        layer['<-']['learning_rate'] = "
        "Tune(0.02, 0.005, 0.1)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "veles",
         os.path.join(REPO, "veles/znicz_tpu/models/mnist.py"),
         str(cfg),
         "root.mnist.loader.n_train=120",
         "root.mnist.loader.n_valid=40",
         "root.mnist.loader.minibatch_size=40",
         "root.mnist.decision.max_epochs=1",
         "-d", "numpy", "--seed", "5", "--no-stats",
         "--optimize", "1x3x2"],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert numpy.isfinite(doc["best_fitness"])
    assert doc["evaluations"] >= 4
    assert doc["workers"] == 2


@pytest.mark.parametrize("device, jax_platforms", [
    ("xla", None),      # a TPU host: the variable unset, xla = the chip
    ("xla", "tpu"),
    ("tpu", "cpu"),     # an explicit tpu spec is never "on the cpu"
])
def test_cli_optimize_workers_refuse_a_possible_tpu(
        monkeypatch, device, jax_platforms):
    """GENSxPOPxWORKERS spawns WORKERS trainers at once; a TPU chip
    belongs to one process, so anything not pinned to the CPU is
    refused before a single child starts."""
    import veles.__main__ as vmain
    if jax_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", jax_platforms)
    wf_path = os.path.join(REPO, "veles/znicz_tpu/models/mnist.py")
    main = vmain.Main([wf_path, "-d", device, "--seed", "5",
                       "--no-stats", "--optimize", "1x3x2"])
    with pytest.raises(SystemExit, match="one process at a time"):
        main.run()


# -- GA over slaves (SURVEY §2.7 "runs distributed over slaves") ------

def _quad_fitness(values):
    """Picklable deterministic fitness for the slave-dispatch tests."""
    return (values["a/lr"] - 0.37) ** 2


def _slow_quad_fitness(values):
    """Same, but slower than the timeout-drop test's slave_timeout."""
    import time
    time.sleep(0.6)
    return _quad_fitness(values)


def test_ga_slave_survives_timeout_drop():
    """A healthy slave whose evaluation outlives the master's
    slave_timeout gets dropped (its task requeues) — it must
    RECONNECT, re-register under a fresh id, re-report the finished
    result, and keep serving, instead of mistaking the closed socket
    for a finished search and exiting (ADVICE r4 medium: with every
    evaluation longer than the timeout, a non-reconnecting pool
    drains one task per slave into a silent livelock). One slave,
    slave_timeout far below the evaluation time: the search can only
    complete through the reconnect path."""
    import threading
    import time
    from veles.genetics import GATaskServer, _SafeEval, ga_slave_loop

    with GATaskServer("127.0.0.1:0", slave_timeout=0.25) as server:
        addr = "127.0.0.1:%d" % server.bound_address[1]
        t_slave = threading.Thread(
            target=ga_slave_loop, args=(addr,),
            kwargs={"name": "slow", "reconnect_delay": 0.05},
            daemon=True)
        t_slave.start()
        done = {}
        t_map = threading.Thread(
            target=lambda: done.update(out=server.map(
                _SafeEval(_slow_quad_fitness),
                [{"a/lr": v} for v in (0.1, 0.3)])),
            daemon=True)
        t_map.start()
        t_map.join(timeout=30)
        assert not t_map.is_alive(), \
            "map() livelocked: dropped slave never came back"
        assert [r[0] for r in done["out"]] == [
            pytest.approx((v - 0.37) ** 2) for v in (0.1, 0.3)]
        # the slave really was dropped and re-registered at least once
        assert server._next_slave > 2
    t_slave.join(timeout=10)
    assert not t_slave.is_alive()


def test_ga_over_slaves_matches_sequential():
    """One GA search dispatched over TWO in-process slaves through the
    HMAC-framed task server equals the sequential run bit-for-bit
    (every individual carries its own deterministic evaluation), and
    the topology records both slaves serving."""
    import threading
    from veles.config import Tune
    from veles.genetics import (
        GATaskServer, GeneticOptimizer, ga_slave_loop)

    tun = {"a/lr": Tune(0.1, 0.01, 1.0)}
    seq = GeneticOptimizer(_quad_fitness, dict(tun), generations=3,
                           population_size=6, seed=11)
    seq.run()

    with GATaskServer("127.0.0.1:0") as server:
        addr = "127.0.0.1:%d" % server.bound_address[1]
        threads = [threading.Thread(
            target=ga_slave_loop, args=(addr,),
            kwargs={"name": "slave%d" % i}, daemon=True)
            for i in range(2)]
        for t in threads:
            t.start()
        par = GeneticOptimizer(_quad_fitness, dict(tun), generations=3,
                               population_size=6, seed=11,
                               map_fn=server)
        par.run()
        status = server.status()
    for t in threads:
        t.join(timeout=5)
    assert par.best_fitness == seq.best_fitness
    assert par.best_values == seq.best_values
    assert [f for f, _ in par.history] == [f for f, _ in seq.history]
    assert status["n_slaves"] >= 1


def test_ga_requeue_protocol_level():
    """The drop->requeue contract, exercised DIRECTLY: a slave takes a
    task and dies before reporting — drop_slave must put exactly that
    task back at the head of the pending pool, and a completed task
    must NOT requeue on a later drop of the same slave."""
    import threading
    from veles.genetics import GATaskServer, _SafeEval

    with GATaskServer("127.0.0.1:0") as server:
        # two registered slaves, three tasks
        sid_a = server._handle(("hello", "a"))[1]
        sid_b = server._handle(("hello", "b"))[1]
        fn = _SafeEval(_quad_fitness)
        done = {}
        t = threading.Thread(
            target=lambda: done.update(
                out=server.map(fn, [{"a/lr": v}
                                    for v in (0.1, 0.2, 0.3)])),
            daemon=True)
        t.start()
        import time
        for _ in range(100):
            if server.queue or server.tasks:
                break
            time.sleep(0.01)
        kind, idx_a, fn_a, vals_a, epoch = server._handle(
            ("task", sid_a))
        assert kind == "task"
        # slave A dies holding idx_a: it must return to the pool head
        server.drop_slave(sid_a)
        assert server.queue[0] == idx_a
        assert sid_a not in server.inflight
        # slave B drains everything (including the requeued task)
        while len(server.results) < 3:
            resp = server._handle(("task", sid_b))
            if resp[0] != "task":
                time.sleep(0.01)
                continue
            _, idx, fn_b, vals, ep = resp
            server._handle(("result", sid_b, idx, fn_b(vals), ep))
        # completed tasks must not resurrect when B later drops
        server.drop_slave(sid_b)
        assert not server.queue or all(
            i not in server.results for i in server.queue)
        t.join(timeout=10)
        assert not t.is_alive()
        assert [r[0] for r in done["out"]] == [
            pytest.approx((v - 0.37) ** 2) for v in (0.1, 0.2, 0.3)]
        # a STALE-generation re-report (a dropped slave finishing
        # after its generation completed) is acknowledged but
        # discarded — it must not poison a later map()'s results
        before = dict(server.results)
        assert server._handle(
            ("result", sid_b, 0, -1.0, epoch - 1)) == ("ok",)
        assert server.results == before


def test_ga_slave_churn_late_join_elasticity():
    """Slave churn over the real sockets: a short-lived slave serves
    one task and leaves cleanly; a slave joining MID-GENERATION picks
    up the rest and the search completes. (The die-while-HOLDING-a-
    task requeue path is covered at protocol level by
    test_ga_requeue_protocol_level — a clean exit after the result
    ack leaves nothing in flight to requeue.)"""
    import threading
    import time
    from veles.config import Tune
    from veles.genetics import (
        GATaskServer, GeneticOptimizer, ga_slave_loop)

    tun = {"a/lr": Tune(0.1, 0.01, 1.0)}
    with GATaskServer("127.0.0.1:0") as server:
        addr = "127.0.0.1:%d" % server.bound_address[1]
        # slave A serves exactly one task, then disconnects
        a = threading.Thread(target=ga_slave_loop, args=(addr,),
                             kwargs={"name": "mortal", "max_tasks": 1},
                             daemon=True)
        a.start()
        opt = GeneticOptimizer(_quad_fitness, dict(tun), generations=1,
                               population_size=5, seed=7,
                               map_fn=server)
        done = {}

        def search():
            done["opt"] = opt.run()

        t = threading.Thread(target=search, daemon=True)
        t.start()
        time.sleep(0.3)   # let the mortal slave take+finish one task
        b = threading.Thread(target=ga_slave_loop, args=(addr,),
                             kwargs={"name": "survivor"}, daemon=True)
        b.start()
        t.join(timeout=30)
        assert not t.is_alive(), "generation never completed"
    assert numpy.isfinite(opt.best_fitness)
    # initial pop (5) + one child generation minus the 2 elites (3)
    assert opt.evaluations == 8
