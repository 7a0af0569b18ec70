"""Run orchestration.

Re-design of ``veles/launcher.py`` [U] (SURVEY.md §2.7 "Launcher",
§3.1): builds the Device, initializes the workflow (shape resolution +
step compilation), optionally restores a snapshot, drives the run,
reports per-unit timing, and owns the distributed role:

* **standalone** — everything in-process (the default);
* **master**     — owns the canonical weights + job queue, serves
  slaves over the wire transport (``veles/server.py``), computes
  nothing (reference semantics, SURVEY.md §3.3);
* **slave**      — pulls jobs, runs iterations, pushes updates.

The reference needed a Twisted reactor here; the TPU rebuild's hot path
is compiled collectives, so the launcher stays synchronous and the wire
layer (used for the elastic-DP compat path and observability only) is
plain sockets in ``veles/server.py`` / ``veles/client.py``.
"""

import signal
import sys

from veles.logger import Logger

#: process exit code after a SIGTERM-driven preemption shutdown (the
#: k8s/TPU-maintenance kill path): distinct from both success and
#: crash so a supervisor can tell "reschedule me, I checkpointed" from
#: "I failed". 75 = BSD EX_TEMPFAIL ("temporary failure, retry").
EXIT_PREEMPTED = 75


class Launcher(Logger):
    """Drives one workflow run."""

    def __init__(self, device=None, snapshot=None, stats=True,
                 listen_address=None, master_address=None,
                 graphics_dir=None, web_status_port=None,
                 profile_dir=None, slave_timeout=None,
                 slave_options=None, checkpoint_every=None,
                 grad_codec=None, grad_topk_percent=None,
                 slo_config=None, model_stats=True,
                 stats_interval=None, rollback_on_divergence=False,
                 stash_interval=None, continual=None):
        self.name = "Launcher"
        self.device_spec = device
        self.snapshot = snapshot
        self.stats = stats
        self.listen_address = listen_address
        self.master_address = master_address
        #: master mode: drop a silent slave (and requeue its work)
        #: after this many seconds; None -> MasterServer's finite
        #: default
        self.slave_timeout = slave_timeout
        #: slave mode: SlaveClient fault-tolerance kwargs
        #: (io_timeout, retry_base, retry_max, max_retries, ...)
        self.slave_options = dict(slave_options or {})
        #: wall-clock checkpoint cadence (seconds): wires the
        #: snapshotter's rolling ``current`` slot in standalone mode
        #: and the master's state-persist loop in master mode
        self.checkpoint_every = checkpoint_every
        #: gradient wire codec for the distributed modes
        #: (veles/compression.py): the master's configured codec wins
        #: the per-slave hello negotiation; the slave offers its own
        self.grad_codec = grad_codec or "none"
        self.grad_topk_percent = 1.0 if grad_topk_percent is None \
            else float(grad_topk_percent)
        #: path to a JSON list of SLO objectives for the in-process
        #: health monitor (veles/health.py): burn-rate alerts land in
        #: /readyz, /debug/events and the veles_slo_* gauges
        self.slo_config = slo_config
        #: model-health plane (veles/model_health.py): in-graph layer
        #: stats on the compiled step (--model-stats off disables),
        #: the host-sync cadence, and the divergence actuator —
        #: NNRollback in standalone mode, the master's WeightGuard in
        #: master mode
        self.model_stats = bool(model_stats)
        self.stats_interval = stats_interval
        self.rollback_on_divergence = bool(rollback_on_divergence)
        #: master mode: merges between WeightGuard stash refreshes —
        #: each stash is a full-model RAM copy + finiteness scan under
        #: the request lock, so large models amortize it (a restore
        #: then discards at most this many merges)
        self.stash_interval = stash_interval
        #: continual mode (ISSUE 16, veles/continual.py): None = one
        #: ordinary run; 0 = endless rounds; N>0 = that many rounds.
        #: Standalone only — the distributed modes own their loops
        self.continual = continual
        self.workflow = None
        self.interrupted = False
        #: True once SIGTERM asked for a preemption shutdown: the run
        #: stops at the next unit boundary, a final checkpoint is
        #: written, and run() exits the process with EXIT_PREEMPTED
        self.preempted = False
        self.master_server = None
        self.slave_client = None
        self._master_resume = None
        #: directory for a jax.profiler trace of the run (XLA op/HLO
        #: timeline, viewable in TensorBoard/Perfetto) — the kernel-
        #: level complement to the per-unit wall times (SURVEY.md §5.1
        #: "TPU equivalent: jax.profiler traces + per-step timing")
        self.profile_dir = profile_dir
        #: directory for streamed plot PNGs (spawns the renderer
        #: process); None disables graphics (SURVEY.md §2.7)
        self.graphics_dir = graphics_dir
        #: port for the status dashboard; None disables it
        self.web_status_port = web_status_port
        self.graphics = None
        self.web_status = None

    @property
    def mode(self):
        if self.listen_address:
            return "master"
        if self.master_address:
            return "slave"
        return "standalone"

    def initialize(self, workflow, **kwargs):
        self.workflow = workflow
        # name this pid's track in span dumps: a merged cluster trace
        # (master absorbing slave spans) reads as roles, not pids
        from veles import telemetry
        telemetry.tracer.set_process_name(
            self.mode if self.mode != "standalone" else workflow.name)
        if self.mode == "slave":
            workflow.is_slave = True
        # master holds weights but never computes: numpy device is
        # enough and avoids grabbing a TPU (reference: no Device on
        # master [U])
        device = "numpy" if self.mode == "master" else self.device_spec
        workflow.initialize(device=device, **kwargs)
        snap = getattr(workflow, "snapshotter", None)
        if snap is not None and self.checkpoint_every \
                and not snap.interval:
            snap.interval = float(self.checkpoint_every)
            # the improvement-only graph gate would keep run() from
            # ever seeing the wall clock: open it, the unit gates
            # internally (see SnapshotterBase.run)
            from veles.mutable import Bool
            snap.gate_skip = Bool(False)
        elif snap is None and self.checkpoint_every \
                and self.mode == "standalone":
            # a silently-unwired cadence is the worst failure mode: the
            # operator believes the job is preemption-safe until the
            # SIGKILL hours later proves otherwise
            self.warning(
                "--checkpoint-every %.6g has no snapshotter to drive "
                "(pass --snapshots DIR or link one) — NO interval "
                "checkpoints will be written", self.checkpoint_every)
        if self.snapshot:
            self._restore_snapshot(workflow)
        if self.graphics_dir and self.mode != "slave":
            # master/standalone only, like the reference (plots render
            # in a separate process so they never block the run)
            from veles.graphics import GraphicsServer
            self.graphics = GraphicsServer(self.graphics_dir)
            workflow.graphics = self.graphics
        if self.web_status_port is not None:
            from veles.web_status import WebStatus, workflow_status
            self.web_status = WebStatus(port=self.web_status_port)
            self.web_status.register(
                workflow.name, workflow_status(workflow, self.mode))
        if self.slo_config:
            from veles import health
            n = health.get_monitor().load_slo_file(self.slo_config)
            self.info("%d SLO objective(s) loaded from %s", n,
                      self.slo_config)
        self._wire_model_health(workflow)
        return workflow

    def _wire_model_health(self, workflow):
        """Model-health plane wiring (ISSUE 15): stat collection knobs
        on the compiled step, the divergence SLOs + readiness check,
        and the --rollback-on-divergence actuator."""
        from veles import model_health
        step = getattr(workflow, "xla_step", None)
        if not self.model_stats:
            # the WHOLE plane stands down, not just the in-graph
            # stats: with the detector's other inputs (loss z-score,
            # wire scans) left armed, a verdict could still stamp
            # checkpoints diverged — actuation the operator turned
            # the observability off for
            model_health.get_model_monitor().enabled = False
        if step is not None:
            if not self.model_stats:
                step.set_stats_enabled(False)
            if self.stats_interval:
                # the stride is a compile-time knob: sync the compiler
                # and drop the cached per-step programs (none compiled
                # yet on this path — initialize just ran)
                step.stats_interval = max(1, int(self.stats_interval))
                if step.compiler is not None:
                    step.compiler.stats_stride = step.stats_interval
                    step._train_fn = step._eval_fn = None
        if not self.model_stats:
            return
        monitor = model_health.get_model_monitor()
        monitor.register_health()
        n = model_health.install_model_slos()
        if n:
            self.info("model-health plane armed: %d divergence SLO "
                      "objective(s), verdict check on /readyz", n)
        if self.rollback_on_divergence:
            rollback = getattr(workflow, "rollback", None)
            if rollback is not None:
                rollback.rollback_on_divergence = True
            elif self.mode == "standalone":
                self.warning(
                    "--rollback-on-divergence: workflow has no "
                    "rollback unit (link_rollback) — divergence will "
                    "flip /readyz but nothing restores weights")

    # -- resume --------------------------------------------------------

    def _checkpoint_base(self):
        """Where this run's checkpoints live: an explicit
        ``auto:<target>`` wins, else the workflow snapshotter's store."""
        if self.snapshot and self.snapshot.startswith("auto:"):
            from veles.snapshotter import store_for_base
            # read-side semantics: auto:TARGET means "resume from
            # here", so a mistyped path must raise, not be created
            # empty and read as a fresh start
            return store_for_base(self.snapshot[len("auto:"):],
                                  create=False)
        snap = getattr(self.workflow, "snapshotter", None)
        return snap.store if snap is not None else None

    def _restore_snapshot(self, workflow):
        from veles.snapshotter import load_snapshot, resolve_auto
        target = self.snapshot
        if target == "auto" or target.startswith("auto:"):
            base = self._checkpoint_base()
            if base is None:
                raise ValueError(
                    "--snapshot auto needs a checkpoint location: "
                    "pass --snapshots DIR (or --snapshot auto:TARGET) "
                    "or configure a snapshotter")
            # identity filter: a shared --snapshots directory can
            # hold several workflows' checkpoints — only THIS run's
            # prefixes (snapshotter prefix + workflow name, which is
            # also the master persist slot's prefix) are candidates
            snap = getattr(workflow, "snapshotter", None)
            prefixes = {workflow.name}
            if snap is not None:
                prefixes.add(snap.prefix)
            resolved = resolve_auto(base, logger=self,
                                    prefixes=prefixes)
            if resolved is None:
                self.info("--snapshot auto: no verifiable checkpoint "
                          "in the store — starting fresh")
                return
            state, name, corrupt = resolved
            if corrupt:
                # a corrupt blob's age is unreadable, so whether it
                # OUTRANKED the chosen one is unknowable — report
                # presence, don't claim a fallback happened
                self.warning("--snapshot auto: store holds %d corrupt "
                             "checkpoint(s); resuming %s", corrupt,
                             name)
            self._apply_state(workflow, state, name)
        else:
            self._apply_state(workflow, load_snapshot(target), target)

    def _apply_state(self, workflow, state, origin):
        if "master" in state and "workflow" in state:
            # a master-persisted tree: the workflow part restores here,
            # the job-queue/journal part waits for the MasterServer
            self._master_resume = state["master"]
            workflow.restore_state(state["workflow"])
        else:
            workflow.restore_state(state)
        self.info("resumed from %s", origin)

    def run(self):
        wf = self.workflow
        previous = signal.getsignal(signal.SIGINT)
        previous_term = signal.getsignal(signal.SIGTERM)

        def on_sigint(sig, frame):
            self.interrupted = True
            self.warning("interrupt: stopping workflow")
            wf.stop()
            signal.signal(signal.SIGINT, previous)

        def on_sigterm(sig, frame):
            # TPU/k8s preemption: stop at the next unit boundary,
            # checkpoint, exit EXIT_PREEMPTED (handled after the run
            # loop unwinds — never checkpoint from signal context)
            self.preempted = True
            self.warning("SIGTERM: preemption shutdown — stopping at "
                         "the next unit boundary")
            wf.stop()
            if self.master_server is not None:
                # signal-safe: the serving thread persists the final
                # journal on its way out
                self.master_server.request_stop()
            if self.slave_client is not None:
                # wf.stop() means nothing to a slave (the client
                # drives units directly): stop the job pump itself
                self.slave_client.request_stop()

        try:
            signal.signal(signal.SIGINT, on_sigint)
            signal.signal(signal.SIGTERM, on_sigterm)
        except ValueError:          # not on the main thread
            previous = previous_term = None
        import contextlib
        prof = contextlib.nullcontext()
        if self.profile_dir:
            if self.mode == "master":
                # master never computes — nothing worth tracing
                self.warning("--profile-dir ignored in master mode")
            else:
                import jax
                prof = jax.profiler.trace(self.profile_dir)
        try:
            with prof:
                if self.mode == "master":
                    if self.continual is not None:
                        self.warning("--continual is standalone-only "
                                     "for now; running one ordinary "
                                     "master session")
                    self._run_master()
                elif self.mode == "slave":
                    self._run_slave()
                elif self.continual is not None:
                    from veles import continual as continual_mod
                    continual_mod.continual_loop(
                        wf, rounds=self.continual or None,
                        launcher=self)
                else:
                    wf.run()
            if not isinstance(prof, contextlib.nullcontext):
                self.info("profiler trace in %s", self.profile_dir)
        finally:
            if previous is not None:
                signal.signal(signal.SIGINT, previous)
            if previous_term is not None:
                signal.signal(signal.SIGTERM, previous_term)
            if self.graphics is not None:
                self.graphics.close()
            if self.web_status is not None:
                # per-run dashboard dies with the run (a persistent
                # fleet dashboard is a standalone WebStatus that
                # launchers POST to via /update)
                self.web_status.close()
        if self.preempted:
            self._preemption_exit()
        if self.stats:
            wf.print_stats(sys.stderr)
            step = getattr(wf, "xla_step", None)
            if step is not None:
                step.print_dispatch_phases(sys.stderr)
        return wf

    def _preemption_exit(self):
        """Final checkpoint + distinct exit code after a SIGTERM stop.
        The master persists on its serving thread's way out; a SLAVE
        must never snapshot — its mid-sync replica state written into
        a shared store would outrank the master's own checkpoints on
        the next --snapshot auto. Only standalone runs write here."""
        snap = getattr(self.workflow, "snapshotter", None)
        if self.mode == "standalone" and snap is not None:
            path = snap.preempt_snapshot()
            if path:
                self.info("preemption checkpoint -> %s", path)
        self.warning("preempted: exiting with code %d", EXIT_PREEMPTED)
        raise SystemExit(EXIT_PREEMPTED)

    # -- distributed modes --------------------------------------------

    def _run_master(self):
        from veles.server import MasterServer
        kwargs = {} if self.slave_timeout is None \
            else {"slave_timeout": self.slave_timeout}
        store = self._checkpoint_base()
        if store is None and self.checkpoint_every:
            self.warning(
                "--checkpoint-every %.6g: no checkpoint store "
                "resolves (pass --snapshots DIR) — master state will "
                "NOT be persisted and a restart cannot recover",
                self.checkpoint_every)
        server = MasterServer(self.workflow, self.listen_address,
                              checkpoint_store=store,
                              checkpoint_every=self.checkpoint_every,
                              resume_state=self._master_resume,
                              grad_codec=self.grad_codec,
                              grad_topk_percent=self.grad_topk_percent,
                              rollback_on_divergence=(
                                  self.rollback_on_divergence
                                  and self.model_stats),
                              stash_interval=self.stash_interval or 1,
                              **kwargs)
        self.master_server = server
        if self.preempted:
            # SIGTERM landed while MasterServer.__init__ was still
            # rebuilding its persist slot (a slow store makes that
            # window real): the handler saw master_server=None, so
            # relay the stop here or serve_forever runs to max_epochs
            server.request_stop()
        if self.web_status is not None:
            # cluster topology on the dashboard: connected slaves and
            # their job counts straight from the server registry
            self.web_status.register("cluster", server.status)
        # /healthz + /readyz on the dashboard reflect THIS master:
        # lease table serving, snapshot-store breaker closed
        server.register_health()
        server.serve_forever()

    def _run_slave(self):
        from veles.client import SlaveClient
        client = SlaveClient(self.workflow, self.master_address,
                             grad_codec=self.grad_codec,
                             grad_topk_percent=self.grad_topk_percent,
                             **self.slave_options)
        self.slave_client = client
        if self.preempted:
            # SIGTERM landed before the client existed: same relay
            # race as the master branch above
            client.request_stop()
        client.run_forever()


def run_workflow(workflow, device=None, snapshot=None, stats=False,
                 **kwargs):
    """One-call convenience used by tests and samples."""
    launcher = Launcher(device=device, snapshot=snapshot, stats=stats)
    launcher.initialize(workflow, **kwargs)
    return launcher.run()
