"""velescli — the command-line entry point.

Re-design of ``velescli.py`` = ``veles/__main__.py`` [U] (SURVEY.md
§2.7 "CLI", §3.1 call stack). Usage keeps the reference shape:

    python -m veles [options] <workflow.py> [<config.py>] [root.x.y=v ...]

* the workflow module must expose ``run(load, main)``; ``load`` builds
  the workflow class with kwargs, ``main`` launches it;
* the config module is plain python mutating the global ``root``;
* trailing ``a.b=value`` args are dot-path overrides (python literals);
* ``-d/--device`` picks the backend (xla/tpu/cpu/numpy),
  ``--seed`` seeds every PRNG, ``--snapshot`` resumes,
  ``--listen-address``/``--master-address`` select master/slave modes,
  ``--workflow-graph`` dumps graphviz, ``--result-file`` writes the
  run's metric history as JSON.

Four subcommands live OUTSIDE the workflow shape:

    python -m veles serve --model NAME=ARCHIVE_DIR [...]

starts the batched online-inference frontend (``veles/serving/``) over
``export_inference`` artifacts — see ``velescli.py serve --help``;

    python -m veles checkpoints <dir-or-url>

audits a snapshot store (manifest verification: valid / corrupt /
legacy per blob) before an operator trusts it with ``--snapshot auto``;

    python -m veles lint [--json] [paths...]

runs the zlint static-analysis gate (``veles/analysis/``: tracer
purity, lock order, checkpoint completeness, telemetry hygiene,
thread lifecycle) — exit 0 clean / 1 findings / 2 usage;

    python -m veles debug http://host:port [--trace-out t.json]

pulls the flight-recorder postmortem surfaces (``/debug/events``,
``/debug/trace``) off a LIVE web-status dashboard or serving
frontend — recent structured events printed as a table, the retained
span window written as Perfetto JSON. Works on a degraded cluster
that was never started with ``--trace-out``;

    python -m veles top http://host:port [...] [--json]

the live fleet dashboard (``veles/fleet.py``): polls every target's
``/healthz`` + ``/readyz`` + ``/metrics`` + status surfaces, merges
the master's per-slave timing, and renders a refreshing terminal
view — ``--json`` emits one machine-readable snapshot (the artifact
a router/autoscaler consumes);

    python -m veles route http://replica1:8080 http://replica2:8080

fronts N serving replicas behind ONE address (``veles/router.py``):
a reactor-hosted proxy whose least-queue/consistent-hash routing,
eager failover (readiness flips, SLO burn-rate alerts, scrape
timeouts) and optional autoscaling (``--autoscale MIN:MAX
--scale-cmd ...``) are driven by the same health-plane scrapes
``velescli top`` renders — see ``velescli route --help``;

    python -m veles profile http://host:port [--seconds N] [--out p.json]

captures a live sampling-profiler window off a running master or
serving process (``GET /debug/profile`` — ``veles/profiling.py``):
speedscope JSON written to ``--out`` (load at speedscope.app), or a
per-thread hot-function summary printed to the terminal. Like
``velescli debug``, it works on a process that was never started
with any profiling flag.
"""

import argparse
import importlib.util
import json
import os
import sys

from veles import prng
from veles.config import root
from veles.launcher import Launcher


def build_argparser():
    p = argparse.ArgumentParser(
        prog="velescli",
        description="Run a znicz-tpu workflow (TPU-native VELES)")
    p.add_argument("workflow", help="path to the workflow python module")
    p.add_argument("config", nargs="?", default=None,
                   help="python config file mutating root.*")
    p.add_argument("overrides", nargs="*", default=[],
                   help="root.x.y=value dot-path overrides")
    p.add_argument("-d", "--device", default=None,
                   help="backend: xla | tpu | cpu | numpy")
    p.add_argument("--seed", type=int, default=None,
                   help="master seed for every PRNG")
    p.add_argument("--snapshot", default=None,
                   help="checkpoint to resume from: a file/URI, "
                        "'auto' (newest manifest-verified checkpoint "
                        "in the --snapshots store, falling back past "
                        "corrupt ones), or 'auto:TARGET' to scan an "
                        "explicit directory/URL")
    p.add_argument("--snapshots", default=None, metavar="DIR",
                   help="write improved-gated checkpoints to DIR "
                        "(links a Snapshotter when the workflow has "
                        "none)")
    p.add_argument("--checkpoint-every", type=float, default=None,
                   metavar="SECS",
                   help="also write rolling 'current' checkpoints at "
                        "the first unit boundary after every SECS "
                        "seconds (preemption bound); in master mode, "
                        "persist the master's aggregated state + job "
                        "journal at this cadence")
    p.add_argument("--slave-retries", type=int, default=None,
                   metavar="N",
                   help="slave mode: give up after N consecutive "
                        "failed reconnect attempts (0 = retry "
                        "forever; default 8). Use 0 when the master "
                        "is preemptible — its restart takes longer "
                        "than the default budget")
    p.add_argument("--listen-address", default=None,
                   help="host:port -> run as distribution master")
    p.add_argument("--master-address", default=None,
                   help="host:port -> run as slave of that master")
    p.add_argument("--grad-codec", default=None,
                   choices=["none", "bf16", "int8", "topk"],
                   help="gradient wire codec for master/slave sync "
                        "(veles/compression.py): bf16 = 2x shrink, "
                        "int8 = 4x with error-feedback residuals, "
                        "topk = ship only the largest K%% of delta "
                        "entries. Negotiated at hello; the master's "
                        "setting wins and mismatched slaves fall "
                        "back to 'none' with a counted warning")
    p.add_argument("--grad-topk-percent", type=float, default=1.0,
                   metavar="K",
                   help="topk codec: percentage of delta entries "
                        "shipped per sync (default 1.0; the rest "
                        "accumulates in the error-feedback residual)")
    p.add_argument("--workflow-graph", default=None,
                   help="write the unit DAG as graphviz dot and exit")
    p.add_argument("--dump-config", action="store_true",
                   help="print the effective config before running")
    p.add_argument("--result-file", default=None,
                   help="write decision history JSON here")
    p.add_argument("--no-stats", action="store_true",
                   help="skip the per-unit timing report")
    p.add_argument("--dump-unit-sizes", action="store_true",
                   help="print per-unit buffer footprints after "
                        "initialize")
    p.add_argument("--graphics-dir", default=None,
                   help="stream plots to a renderer process writing "
                        "PNGs here (also auto-links the standard "
                        "plotters when the workflow has none)")
    p.add_argument("--generate", default=None, metavar="IDS",
                   help="after the run, decode from the trained LM: "
                        "comma-separated prompt token ids (e.g. "
                        "'1,2,3'); prints the continuation")
    p.add_argument("--generate-text", default=None, metavar="PROMPT",
                   help="like --generate but with TEXT through the "
                        "loader's character vocabulary (text-corpus "
                        "LMs: root.lm.loader.text_file)")
    p.add_argument("--gen-tokens", type=int, default=32,
                   help="tokens to generate with --generate")
    p.add_argument("--gen-temperature", type=float, default=0.0,
                   help="sampling temperature for --generate "
                        "(0 = greedy)")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the run here "
                        "(kernel-level timeline; view in TensorBoard "
                        "or Perfetto)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome-trace/Perfetto JSON of the "
                        "run's HOST-side spans here (unit runs, step "
                        "builds, fused dispatches — the veles span "
                        "tracer; load in chrome://tracing or "
                        "ui.perfetto.dev)")
    p.add_argument("--background", action="store_true",
                   help="daemonize before running: fork, detach from "
                        "the terminal (setsid), redirect stdio to "
                        "--log-file (default /dev/null), print the "
                        "daemon pid and return immediately")
    p.add_argument("--log-file", default=None, metavar="PATH",
                   help="with --background: append stdout/stderr here")
    p.add_argument("--web-status", type=int, default=None,
                   metavar="PORT",
                   help="serve the status dashboard on this port "
                        "(0 = pick a free one)")
    p.add_argument("--slo-config", default=None, metavar="PATH",
                   help="JSON list of SLO objectives for the health "
                        "monitor (veles/health.py): burn-rate alerts "
                        "land in /readyz, /debug/events and the "
                        "veles_slo_* gauges on --web-status")
    p.add_argument("--export-inference", default=None, metavar="DIR",
                   help="after the run, export the C++-engine archive "
                        "(contents.json + .npy) to DIR")
    p.add_argument("--optimize", default=None,
                   metavar="GENSxPOP[xWORKERS]",
                   help="genetic search over the config's Tune leaves "
                        "(e.g. 6x12: 6 generations, population 12; "
                        "6x12x4 evaluates 4 individuals concurrently "
                        "in spawned worker processes); fitness = best "
                        "validation metric")
    p.add_argument("--slave-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="master modes: drop a silent slave and "
                        "requeue its work after this long. Default "
                        "60s for the training master (jobs are one "
                        "minibatch) and 3600s for the GA master "
                        "(--optimize: jobs are whole training runs, "
                        "so this must exceed the longest single "
                        "evaluation)")
    p.add_argument("--ensemble", type=int, default=None, metavar="N",
                   help="train N differently-seeded instances and "
                        "report ensemble vs member validation error")
    p.add_argument("--model-stats", choices=("on", "off"),
                   default="on",
                   help="in-graph model-health stats on the compiled "
                        "step (per-layer grad/weight/update norms, "
                        "non-finite counts -> veles_model_* "
                        "instruments, /debug/model, divergence SLOs; "
                        "veles/model_health.py). Default on; 'off' "
                        "removes the fused stat outputs entirely")
    p.add_argument("--stats-interval", type=int, default=None,
                   metavar="N",
                   help="host-sync cadence of the in-graph stats: "
                        "publish every Nth train step's vectors "
                        "(default 8; materializing more often costs "
                        "a device sync per step in per-step mode)")
    p.add_argument("--rollback-on-divergence", action="store_true",
                   help="when the model-health verdict flips to "
                        "diverged (non-finite grads/deltas, loss "
                        "z-score spike), restore the last healthy "
                        "weights: NNRollback's stash in standalone "
                        "mode, the master's finiteness-checked RAM "
                        "stash in master mode")
    p.add_argument("--stash-interval", type=int, default=None,
                   metavar="N",
                   help="master mode, with --rollback-on-divergence: "
                        "refresh the rollback stash every Nth merge "
                        "(default 1 = every merge; each refresh is a "
                        "full-model RAM copy + finiteness scan under "
                        "the request lock, so large models amortize "
                        "it — a restore discards at most N merges)")
    p.add_argument("--continual", type=int, nargs="?", const=0,
                   default=None, metavar="ROUNDS",
                   help="continual training (ISSUE 16): keep running "
                        "the workflow over its (streaming) loader in "
                        "rounds of max_epochs, re-opening the stop "
                        "gate between rounds, until interrupted/"
                        "preempted — or for ROUNDS rounds when given. "
                        "The snapshotter's --checkpoint-every gate "
                        "keeps emitting verified 'current'-slot "
                        "checkpoints throughout; MANIFESTs carry the "
                        "ingest wall so serving staleness is "
                        "measurable end to end")
    return p


def import_file(path, name=None):
    name = name or os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError("cannot import %s" % path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


class Main:
    """The reference's Main object: owns launcher + workflow."""

    def __init__(self, argv=None):
        # INTERMIXED parsing: the reference CLI shape puts dot-path
        # overrides at the tail, but callers legitimately interleave
        # (``--seed 99 root.a=1 --result-file r.json``); plain
        # parse_args refuses trailing positionals after optionals
        self.args = build_argparser().parse_intermixed_args(argv)
        self.workflow = None
        self.launcher = None

    def setup_config(self):
        # a lone "a.b=c" positional is an override, not a config file
        if self.args.config and "=" in self.args.config \
                and not os.path.exists(self.args.config):
            self.args.overrides.insert(0, self.args.config)
            self.args.config = None
        if self.args.config:
            import_file(self.args.config, "veles_config_module")
        for override in self.args.overrides:
            root.apply_override(override)
        if self.args.seed is not None:
            prng.seed_all(self.args.seed)
        if self.args.dump_config:
            root.print_config(stream=sys.stderr)

    # -- the load/main pair handed to the sample's run() ---------------

    def load(self, WorkflowClass, **kwargs):
        self.workflow = WorkflowClass(None, **kwargs)
        return self.workflow

    def main(self, **kwargs):
        args = self.args
        if self.workflow is None:
            raise RuntimeError("workflow.run() never called load()")
        if args.workflow_graph:
            with open(args.workflow_graph, "w") as f:
                f.write(self.workflow.generate_graph())
            print("workflow graph -> %s" % args.workflow_graph)
            return self.workflow
        if not args.trace_out:
            return self._launch(**kwargs)
        # start BEFORE initialize so step-build spans are captured;
        # dump in a finally — a crashed run's spans are exactly the
        # postmortem the trace is for
        from veles import telemetry
        telemetry.tracer.start()
        try:
            return self._launch(**kwargs)
        finally:
            telemetry.tracer.stop()
            try:
                telemetry.tracer.dump(args.trace_out)
                print("trace -> %s" % args.trace_out)
            except OSError as exc:
                # never let a failed dump mask the run's own outcome
                print("trace dump failed: %s" % exc, file=sys.stderr)

    def _launch(self, **kwargs):
        args = self.args
        slave_options = {}
        if args.slave_retries is not None:
            slave_options["max_retries"] = \
                None if args.slave_retries == 0 else args.slave_retries
        self.launcher = Launcher(
            device=args.device, snapshot=args.snapshot,
            stats=not args.no_stats,
            listen_address=args.listen_address,
            master_address=args.master_address,
            graphics_dir=args.graphics_dir,
            web_status_port=args.web_status,
            profile_dir=args.profile_dir,
            slave_timeout=args.slave_timeout,
            slave_options=slave_options,
            checkpoint_every=args.checkpoint_every,
            grad_codec=args.grad_codec,
            grad_topk_percent=args.grad_topk_percent,
            slo_config=args.slo_config,
            model_stats=args.model_stats != "off",
            stats_interval=args.stats_interval,
            rollback_on_divergence=args.rollback_on_divergence,
            stash_interval=args.stash_interval,
            continual=args.continual)
        if args.graphics_dir and not getattr(
                self.workflow, "plotters", None) \
                and hasattr(self.workflow, "link_plotters"):
            self.workflow.link_plotters(out_dir=args.graphics_dir)
        if args.snapshots and getattr(
                self.workflow, "snapshotter", None) is None \
                and hasattr(self.workflow, "link_snapshotter"):
            self.workflow.link_snapshotter(
                directory=args.snapshots,
                interval=args.checkpoint_every)
        self.launcher.initialize(self.workflow, **kwargs)
        if args.dump_unit_sizes:
            self.workflow.print_unit_sizes(sys.stderr)
        self.launcher.run()
        if args.export_inference:
            self.workflow.export_inference(args.export_inference)
            print("inference archive -> %s" % args.export_inference)
        if args.generate or args.generate_text:
            import numpy
            from veles.znicz_tpu.generate import generate
            loader = getattr(self.workflow, "loader", None)
            if args.generate_text:
                if not hasattr(loader, "encode"):
                    raise SystemExit(
                        "--generate-text needs a text-corpus loader "
                        "(root.lm.loader.text_file)")
                try:
                    prompt = loader.encode(args.generate_text)
                except ValueError as exc:
                    raise SystemExit("--generate-text: %s" % exc)
            else:
                try:
                    prompt = numpy.array(
                        [[int(t) for t in args.generate.split(",")]],
                        numpy.int32)
                except ValueError:
                    raise SystemExit(
                        "--generate: expected comma-separated integer "
                        "token ids, got %r" % args.generate)
            step = getattr(self.workflow, "xla_step", None)
            if step is not None:
                step.sync_host()
            out = generate(self.workflow, prompt, args.gen_tokens,
                           temperature=args.gen_temperature)
            if args.generate_text:
                print("generated: %s"
                      % (args.generate_text + loader.decode(out[0])))
            else:
                print("generated: %s"
                      % ",".join(str(t) for t in out[0].tolist()))
        if args.result_file and self.workflow.decision is not None:
            from veles.backends import device_report
            device = self.workflow.device
            with open(args.result_file, "w") as f:
                json.dump({
                    "workflow": self.workflow.name,
                    # which device produced these numbers
                    "device": device_report(device.jax_devices)
                    if device.is_xla else
                    {"platform": device.backend_name},
                    "history": self.workflow.decision.history,
                    "best_metric": float(
                        self.workflow.decision.best_metric),
                }, f, indent=2)
        return self.workflow

    # -- meta-optimization modes (SURVEY.md §2.7 rows 8-9, L9) ---------

    def _train_once(self, module):
        """One full training run of the module with the CURRENT config;
        -> best validation metric."""
        self.workflow = None
        module.run(self.load, self.main)
        return float(self.workflow.decision.best_metric)

    def optimize(self, module):
        """``--optimize``: GA over every Tune leaf in root;
        GENSxPOPxWORKERS distributes each generation's individuals
        over spawned worker processes, and --listen-address /
        --master-address farm them over REGISTERED SLAVES instead —
        the reference's distributed genetics (SURVEY.md §2.7):

            master:  velescli wf.py cfg.py --optimize 6x12 \\
                         --listen-address 0.0.0.0:8888
            slaves:  velescli wf.py cfg.py --optimize slave \\
                         --master-address master:8888
        """
        from veles.genetics import optimize_config
        seed = self.args.seed if self.args.seed is not None else 1
        if self.args.optimize == "slave":
            if not self.args.master_address:
                raise SystemExit(
                    "--optimize slave requires --master-address "
                    "HOST:PORT (the GA master to join)")
            # GA slave: evaluate callables ship inside the task frames,
            # so the loop needs no local trainer construction
            from veles.genetics import ga_slave_loop
            served = ga_slave_loop(self.args.master_address,
                                   name="ga-%s" % os.getpid())
            print(json.dumps({"ga_slave_tasks": served}))
            return None
        if self.args.master_address:
            # refuse rather than silently discard the GENSxPOP search
            raise SystemExit(
                "--optimize %r conflicts with --master-address: a GA "
                "master uses --listen-address; to JOIN a master, use "
                "--optimize slave" % self.args.optimize)
        parts = self.args.optimize.split("x")
        gens = parts[0]
        pop = parts[1] if len(parts) > 1 and parts[1] else 12
        workers = int(parts[2]) if len(parts) > 2 else 1
        if self.args.listen_address:
            if workers > 1:
                # refuse rather than silently discard the WORKERS
                # component (mirrors the --master-address conflict)
                raise SystemExit(
                    "--optimize %r combines a workers count with "
                    "--listen-address: registered slaves evaluate "
                    "the individuals, so local workers would be "
                    "ignored — drop the x%d or the --listen-address"
                    % (self.args.optimize, workers))
            return self._optimize_distributed(
                int(gens), int(pop), seed, slaves=True)
        if workers > 1:
            return self._optimize_distributed(
                int(gens), int(pop), seed, workers=workers)

        def run_one():
            prng.seed_all(seed)   # identical universe per individual
            return self._train_once(module)

        opt = optimize_config(
            root, run_one, generations=int(gens),
            population_size=int(pop or 12), seed=seed)
        print(json.dumps({
            "best_fitness": opt.best_fitness,
            "best_values": opt.best_values,
            "evaluations": opt.evaluations,
        }))
        return opt

    def _optimize_distributed(self, gens, pop, seed, workers=None,
                              slaves=False):
        """Shared GA driver for both distributed maps: registered
        SLAVES over the HMAC-framed task protocol (--listen-address;
        drop/requeue keeps a generation alive through slave churn) or
        local spawned WORKER processes (GENSxPOPxWORKERS)."""
        from veles.genetics import (
            GATaskServer, GeneticOptimizer, ProcessPoolMap,
            SubprocessTrainer, apply_values, find_tunables)
        device = self.args.device or "numpy"
        # decided from the spec and the environment alone: asking jax
        # would make THIS process take the chip. On a TPU host
        # JAX_PLATFORMS is normally unset and "xla" resolves to the
        # chip, so anything that is not pinned to the CPU counts.
        on_cpu = device in ("numpy", "cpu") or (
            device != "tpu" and os.environ.get(
                "JAX_PLATFORMS", "").split(",")[0] == "cpu")
        if workers and not on_cpu:
            raise SystemExit(
                "--optimize %s with -d %s: unless JAX_PLATFORMS pins "
                "jax to the cpu this may be a TPU, and a TPU chip "
                "belongs to one process at a time, so %d spawned "
                "trainers would race for it and all but one would "
                "fail or hang. Drop the x%d (individuals then train "
                "one after another on the chip), farm them over "
                "slaves on other hosts with --listen-address, or set "
                "JAX_PLATFORMS=cpu" % (self.args.optimize, device,
                                       workers, workers))
        evaluate = SubprocessTrainer(
            self.args.workflow, self.args.config,
            overrides=self.args.overrides, seed=seed, device=device)
        if slaves:
            map_cm = GATaskServer(
                self.args.listen_address,
                slave_timeout=3600.0
                if self.args.slave_timeout is None
                else self.args.slave_timeout)
            print(json.dumps({"ga_master_listen":
                              "%s:%d" % map_cm.bound_address}),
                  flush=True)
        else:
            map_cm = ProcessPoolMap(workers)
        with map_cm:
            opt = GeneticOptimizer(
                evaluate, find_tunables(root), generations=gens,
                population_size=pop, seed=seed, map_fn=map_cm)
            best_values, _ = opt.run()
        if best_values is not None:
            apply_values(root, best_values)
        report = {
            "best_fitness": opt.best_fitness,
            "best_values": opt.best_values,
            "evaluations": opt.evaluations,
        }
        if workers:
            report["workers"] = workers
        print(json.dumps(report))
        return opt

    def ensemble(self, module):
        """``--ensemble N``: bag of differently-seeded runs."""
        from veles.ensemble import Ensemble

        def factory(name):
            self.workflow = None
            module.run(self.load, lambda **kw: None)  # build only
            return self.workflow

        ens = Ensemble(factory, n_models=self.args.ensemble,
                       base_seed=self.args.seed or 1000,
                       device=self.args.device or "numpy")
        ens.train()
        report = ens.evaluate_classification()
        print(json.dumps(report))
        if self.args.result_file:
            with open(self.args.result_file, "w") as f:
                json.dump(report, f, indent=2)
        return ens

    def run(self):
        # Import the workflow module FIRST: its module-level defaults
        # land in root before the config file and the CLI dot-path
        # overrides are applied on top (reference ordering [U]).
        module = import_file(self.args.workflow, "veles_workflow_module")
        self.setup_config()
        if not hasattr(module, "run"):
            raise AttributeError(
                "%s has no run(load, main)" % self.args.workflow)
        if self.args.optimize:
            # inner runs must not spam side effects: no result/export
            # files, and no per-individual renderer subprocesses or
            # dashboard port binds
            self.args.result_file = None
            self.args.export_inference = None
            self.args.graphics_dir = None
            self.args.web_status = None
            self.optimize(module)
        elif self.args.ensemble:
            self.ensemble(module)
        else:
            module.run(self.load, self.main)
        return 0


def daemonize(log_file=None):
    """Classic double-fork detach (reference ``--background`` [U],
    SURVEY.md §2.7 CLI row): the caller's process prints the daemon
    pid and exits; the grandchild runs the workflow with stdio
    redirected. Called BEFORE any backend/threads initialize."""
    pid = os.fork()
    if pid > 0:
        # wait for the intermediate child so it never zombifies, then
        # report the daemon from the original foreground process
        os.waitpid(pid, 0)
        return False
    os.setsid()
    pid2 = os.fork()
    if pid2 > 0:
        print(json.dumps({"daemon_pid": pid2}), flush=True)
        os._exit(0)
    sys.stdout.flush()
    sys.stderr.flush()
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    os.close(devnull)
    out = os.open(log_file, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                  0o644) if log_file else os.open(os.devnull,
                                                  os.O_WRONLY)
    os.dup2(out, 1)
    os.dup2(out, 2)
    os.close(out)
    return True


def checkpoints_main(argv):
    """``velescli checkpoints <store>``: audit a snapshot store before
    resuming — every blob with its manifest verdict (valid / corrupt /
    legacy), age, slot and schema. Exit code 1 when any checkpoint is
    corrupt (scriptable pre-resume gate), 0 otherwise."""
    import time as _time
    from veles.snapshotter import scan_checkpoints
    p = argparse.ArgumentParser(
        prog="velescli checkpoints",
        description="List checkpoints in a store with their manifest "
                    "verification status")
    p.add_argument("store",
                   help="snapshot directory or http(s) base URL")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    args = p.parse_args(argv)
    from http.client import HTTPException
    try:
        infos = scan_checkpoints(args.store)
    except (OSError, HTTPException, ValueError) as exc:
        # missing directory, unreachable/garbled HTTP endpoint
        # (ValueError covers json/unicode decode errors from a
        # non-store answering the listing): a DOWN store must exit
        # distinctly (2) — never 1, which the gate contract reserves
        # for "store holds a corrupt checkpoint", and never a
        # traceback
        print("error: %s: %s" % (type(exc).__name__, exc),
              file=sys.stderr)
        return 2
    rows = []
    for info in infos:
        m = info.manifest or {}
        age = None
        if info.wall_time:
            age = round(_time.time() - info.wall_time, 1)
        rows.append({"name": info.name, "status": info.status,
                     "slot": m.get("slot"), "schema": m.get("schema"),
                     "age_s": age, "error": info.error,
                     "verdict": info.health_verdict})
    if args.json:
        print(json.dumps(rows, indent=2))
    else:
        print("%-8s %-9s %-7s %-9s %12s  %s"
              % ("STATUS", "SLOT", "SCHEMA", "VERDICT", "AGE(s)",
                 "NAME"))
        for r in rows:
            print("%-8s %-9s %-7s %-9s %12s  %s"
                  % (r["status"], r["slot"] or "-",
                     r["schema"] if r["schema"] is not None else "-",
                     r["verdict"] or "-",
                     r["age_s"] if r["age_s"] is not None else "-",
                     r["name"]))
            if r["error"]:
                print("         !! %s" % r["error"])
        print("%d checkpoint(s): %d valid, %d legacy, %d corrupt"
              % (len(rows),
                 sum(r["status"] == "valid" for r in rows),
                 sum(r["status"] == "legacy" for r in rows),
                 sum(r["status"] == "corrupt" for r in rows)))
    return 1 if any(r["status"] == "corrupt" for r in rows) else 0


def debug_main(argv):
    """``velescli debug <url>``: fetch the flight-recorder surfaces
    of a live process — ``/debug/events`` printed as a table (or
    ``--json``), ``/debug/trace`` optionally saved as Perfetto JSON
    (``--trace-out``). Exit 0 on success, 2 when the endpoint is
    unreachable or answers garbage."""
    import time as _time
    import urllib.request
    p = argparse.ArgumentParser(
        prog="velescli debug",
        description="Postmortem view of a live master/serving "
                    "process via its /debug endpoints")
    p.add_argument("url",
                   help="base URL of a --web-status dashboard or "
                        "serving frontend (http://host:port)")
    p.add_argument("--window", type=float, default=None,
                   metavar="SECS",
                   help="trace window to fetch (default: the "
                        "recorder's full retained window)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write the Perfetto JSON trace window here "
                        "(load in ui.perfetto.dev)")
    p.add_argument("--json", action="store_true",
                   help="print raw events JSON instead of the table")
    args = p.parse_args(argv)
    base = args.url.rstrip("/")
    if "://" not in base:
        base = "http://" + base
    trace_url = base + "/debug/trace"
    if args.window is not None:
        trace_url += "?window=%g" % args.window
    try:
        with urllib.request.urlopen(base + "/debug/events",
                                    timeout=10) as resp:
            events = json.load(resp)["events"]
        with urllib.request.urlopen(trace_url, timeout=10) as resp:
            trace = json.load(resp)
        # shape validation INSIDE the guard: a 200 from something
        # that is not a veles debug surface (JSON array, wrong value
        # types) must exit 2 like any other non-store answer — the
        # same contract the checkpoints CLI hardened in PR 4
        if not isinstance(events, list) \
                or not all(isinstance(e, dict)
                           and isinstance(e.get("wall", 0.0),
                                          (int, float))
                           for e in events) \
                or not isinstance(trace, dict) \
                or not isinstance(trace.get("traceEvents", []), list) \
                or not all(isinstance(e, dict)
                           for e in trace.get("traceEvents", [])):
            raise ValueError("endpoint answered 200 but not the "
                             "/debug payload shape")
    except (OSError, ValueError, KeyError, TypeError) as exc:
        # unreachable endpoint / non-debug server answering HTML or
        # mis-shaped JSON: distinct exit, never a traceback
        print("error: %s: %s" % (type(exc).__name__, exc),
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(events, indent=2))
    else:
        print("%-12s %-20s %s" % ("AGE(s)", "EVENT", "FIELDS"))
        now = _time.time()
        for ev in events:
            fields = " ".join(
                "%s=%s" % (k, v) for k, v in sorted(ev.items())
                if k not in ("wall", "event"))
            print("%-12s %-20s %s"
                  % (round(now - ev.get("wall", now), 1),
                     ev.get("event", "?"), fields))
        print("%d event(s)" % len(events))
    spans = sum(1 for e in trace.get("traceEvents", ())
                if e.get("ph") == "X")
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            json.dump(trace, f)
        print("trace window (%d span(s)) -> %s"
              % (spans, args.trace_out))
    else:
        print("trace window holds %d span(s); re-run with "
              "--trace-out PATH to save the Perfetto JSON" % spans)
    return 0


def profile_main(argv):
    """``velescli profile <url>``: capture a sampling-profiler window
    off a LIVE process via ``GET /debug/profile`` and either save the
    speedscope JSON (``--out``) or print a per-thread summary of the
    hottest functions. Exit 0 on success, 2 when the endpoint is
    unreachable or answers something that is not a speedscope
    document (mirrors ``velescli debug``)."""
    import urllib.request
    p = argparse.ArgumentParser(
        prog="velescli profile",
        description="Sampling CPU profile of a live master/serving "
                    "process via its /debug/profile endpoint "
                    "(veles/profiling.py)")
    p.add_argument("url",
                   help="base URL of a --web-status dashboard or "
                        "serving frontend (http://host:port)")
    p.add_argument("--seconds", type=float, default=2.0,
                   help="capture window (server clamps to its own "
                        "bounds; default 2)")
    p.add_argument("--hz", type=float, default=None,
                   help="sampling rate (default: the server's 97)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="write the speedscope JSON here (load at "
                        "https://www.speedscope.app)")
    p.add_argument("--top", type=int, default=5,
                   help="hot functions listed per thread in the "
                        "summary (default 5)")
    args = p.parse_args(argv)
    base = args.url.rstrip("/")
    if "://" not in base:
        base = "http://" + base
    url = base + "/debug/profile?seconds=%g" % args.seconds
    if args.hz is not None:
        url += "&hz=%g" % args.hz
    try:
        with urllib.request.urlopen(
                url, timeout=args.seconds + 30) as resp:
            doc = json.load(resp)
        # shape validation INSIDE the guard (the checkpoints/debug CLI
        # contract): a 200 from a non-profiling server must exit 2,
        # never a traceback or a garbage artifact written to --out
        frames = doc["shared"]["frames"]
        profiles = doc["profiles"]
        if not isinstance(frames, list) \
                or not all(isinstance(f, dict) for f in frames) \
                or not isinstance(profiles, list) \
                or not all(isinstance(pr, dict)
                           and isinstance(pr.get("samples"), list)
                           and isinstance(pr.get("weights"), list)
                           and len(pr["samples"]) == len(pr["weights"])
                           and all(isinstance(w, (int, float))
                                   for w in pr["weights"])
                           and isinstance(pr.get("endValue", 0.0),
                                          (int, float))
                           for pr in profiles) \
                or not all(isinstance(i, int) and 0 <= i < len(frames)
                           for pr in profiles
                           for sample in pr["samples"]
                           for i in (sample if isinstance(sample, list)
                                     else [None])):
            # frame-index bounds checked HERE too: the summary loop
            # below indexes frames[sample[-1]], and a shape-valid doc
            # with garbage indices must exit 2, not traceback
            raise ValueError("endpoint answered 200 but not a "
                             "speedscope profile document")
    except (OSError, ValueError, KeyError, TypeError) as exc:
        print("error: %s: %s" % (type(exc).__name__, exc),
              file=sys.stderr)
        return 2
    meta = doc.get("veles") or {}
    print("profile: %d thread(s), %s tick(s) @ %sHz over %ss "
          "(sampler overhead %.2f%%)"
          % (len(profiles), meta.get("ticks", "?"),
             meta.get("hz", "?"), meta.get("seconds", "?"),
             float(meta.get("overhead_fraction", 0.0)) * 100.0))
    for pr in profiles:
        # leaf-frame self time: the "where is this thread" view
        leaf = {}
        for sample, weight in zip(pr["samples"], pr["weights"]):
            if not sample:
                continue
            frame = frames[sample[-1]]
            leaf[frame.get("name", "?")] = \
                leaf.get(frame.get("name", "?"), 0.0) + float(weight)
        hot = sorted(leaf.items(), key=lambda kv: -kv[1])[:args.top]
        total = max(float(pr.get("endValue", 0.0)), 1e-9)
        print("  %-24s %8.3fs  %s"
              % (pr.get("name", "?"), float(pr.get("endValue", 0.0)),
                 ", ".join("%s %.0f%%" % (name, 100.0 * w / total)
                           for name, w in hot) or "-"))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f)
        print("speedscope profile -> %s" % args.out)
    return 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # the serving subcommand (veles/serving/): no workflow module,
        # no launcher — a registry of exported models behind the
        # batched HTTP frontend
        from veles.serving.frontend import serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "checkpoints":
        # store audit: list checkpoints + manifest status so an
        # operator can vet a store before --snapshot auto trusts it
        return checkpoints_main(argv[1:])
    if argv and argv[0] == "lint":
        # zlint static analysis (veles/analysis/): the tier-1 gate
        # runs the same engine over the whole package
        from veles.analysis.cli import lint_main
        return lint_main(argv[1:])
    if argv and argv[0] == "debug":
        # flight-recorder postmortem: /debug/events + /debug/trace
        # off a live web-status or serving endpoint
        return debug_main(argv[1:])
    if argv and argv[0] == "top":
        # live fleet dashboard / --json snapshot over N processes'
        # health + metrics surfaces (veles/fleet.py)
        from veles.fleet import top_main
        return top_main(argv[1:])
    if argv and argv[0] == "route":
        # the fleet router/autoscaler tier (veles/router.py): one
        # address in front of N replicas, steered by the health plane
        from veles.router import route_main
        return route_main(argv[1:])
    if argv and argv[0] == "profile":
        # sampling-profiler capture off a live process's
        # /debug/profile surface (veles/profiling.py)
        return profile_main(argv[1:])
    if argv and argv[0] == "loadgen":
        # open-loop tenant-mix load generator (veles/loadgen.py):
        # per-tenant goodput/p99/shed curves + the
        # routed_capacity_rps_at_p99_slo bench row
        from veles.loadgen import loadgen_main
        return loadgen_main(argv[1:])
    m = Main(argv)
    if getattr(m.args, "background", False):
        if not daemonize(m.args.log_file):
            return 0        # foreground parent: daemon pid printed
    return m.run()


if __name__ == "__main__":
    sys.exit(main())
