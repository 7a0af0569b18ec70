"""Driver of a training cell (traffic ``"kind": "train"``).

The cell trains through the entry point a user calls —
``veles.__main__.Main(argv).run()`` in this process, with the argv
``python -m veles <workflow.py> -d tpu --seed <n> root.<...>=<...>``
would get — and a thread of the harness watches it from outside:

* the program's public counters (``veles_xla_dispatch_seconds`` counts
  dispatches) tell WHEN a dispatch ended; the time is taken on the
  harness's own clock;
* the program's ``xla.dispatch.epoch`` spans (the flight recorder,
  which is on for every user) tell what each dispatch held: how many
  epochs, and whether it was the first run of its program;
* warm-up lasts until two dispatches in a row were warm and of one
  chunk length (the program's own chunk policy is left alone), the
  window is the next ``--seconds`` seconds, then the run is stopped
  through ``workflow.stop()`` — once the decision has been told every
  epoch of the window's dispatches (``wait_replayed``);
* the reference check runs AFTER the window, on the weights the
  program started from (its units still hold them on the host) and the
  program's first validation loss, so the check neither competes with
  the warm-up for the chip nor shows in the peak memory.

Nothing private is read: ``workflow.loader / forwards / decision /
device / run_number / stop()``, ``unit.export_params()``,
``loader.peek_epoch_orders / class_schedule / class_lengths /
original_data``, ``decision.history``, ``telemetry.get_registry()`` and
``telemetry.tracer.flight_spans()``.
"""

import json
import math
import shutil
import statistics
import threading
import time

from benchmark import harness

DISPATCH_FAMILY = "veles_xla_dispatch_seconds"
DISPATCH_SPAN = "xla.dispatch.epoch"
#: complete dispatches a traced run profiles
TRACE_DISPATCHES = 2
#: a cold first run compiles; the driver allows it 1200 s in all
DEADLINE_S = 1100.0
#: between two looks at the dispatch counter: 0.025% of a 20 s window,
#: and little enough work to leave the program's host thread the GIL
POLL_S = 0.005
CLASS_VALID, CLASS_TRAIN = 1, 2     # the loader's class indices


class RunFailure(Exception):
    """The run cannot give a measurement (not: gave a wrong answer)."""


def resolve_value(value, cell):
    """``"$model.dim"`` / ``"$traffic.seq_len"`` -> the number in the
    configuration's ``model`` or the traffic file; anything else is
    itself. A key the file does not hold gives None (the override is
    left to the program's default)."""
    if not (isinstance(value, str) and value.startswith("$")):
        return value
    group, key = value[1:].split(".", 1)
    source = cell["config"]["model"] if group == "model" \
        else cell["traffic"]
    return source.get(key)


def build_argv(cell, seed, platform):
    """The command line after ``python -m veles``."""
    program = cell["config"]["program"]
    argv = [program["workflow"], "-d", platform, "--seed", str(seed)]
    for flag in cell["traffic"].get("flags", ()):
        argv += flag["argv"]
    for path, value in program["overrides"].items():
        value = resolve_value(value, cell)
        if value is not None:
            argv.append("%s=%r" % (path, value))
    return argv


class Watcher(threading.Thread):
    """Watches the training run from outside and ends it."""

    def __init__(self, main, cell, seconds, trace, t_process_start,
                 trace_dir):
        super().__init__(name="benchmark-watcher", daemon=True)
        self.main = main
        self.cell = cell
        self.seconds = seconds
        self.trace = trace
        self.t0 = t_process_start
        self.trace_dir = trace_dir
        self.run_over = threading.Event()   # set by the main thread
        self.error = None
        self.marks = {}             # name -> seconds since process start
        self.boundaries = []        # (harness time, dispatches so far)
        self.dispatch_family = None
        self.spans = []             # every dispatch span, oldest first
        self.window = None          # (index of boundary a, of b)
        self.initial = None
        self.peak_bytes = None
        self.memory_stats = None
        self.traced = False

    # -- small readers of the program's public surfaces ----------------

    def workflow(self):
        return self.main.workflow

    def mark(self, name):
        self.marks[name] = time.perf_counter() - self.t0

    def dispatch_count(self):
        """Epoch dispatches the program has counted so far. Polled
        every ``POLL_S``, so the family is looked up once."""
        if self.dispatch_family is None:
            from veles import telemetry
            for family in telemetry.get_registry().families():
                if family.name == DISPATCH_FAMILY:
                    self.dispatch_family = family
                    break
            else:
                return 0
        return sum(child.count
                   for items, child in self.dispatch_family.children()
                   if ("kind", "epoch") in items)

    def drain_spans(self, count):
        """Append the dispatch spans recorded since the last call, up
        to the ``count`` dispatches the counter has seen. The flight
        recorder is a ring shared with every unit's per-step span, so
        it is read at every dispatch, long before it wraps, and only as
        far back as the last dispatch seen: the program's host thread
        is replaying the chunk just now, and copying the whole ring
        would take the GIL from it. The program counts a dispatch a
        moment before it records the span, hence the short wait. A
        span that never comes was lost to the ring, which fails the
        run: it never shortens the window in silence.
        """
        from veles import telemetry
        for _ in range(200):
            if self.spans:
                last = self.spans[-1]["start"]
                back = time.time() - last + 1.0
            else:               # the first one may have compiled
                last, back = -math.inf, DEADLINE_S
            for wall, ev in telemetry.tracer.flight_spans(window=back):
                if ev["name"] == DISPATCH_SPAN and wall > last:
                    args = ev.get("args", {})
                    self.spans.append({
                        "start": wall, "dur": ev["dur"] / 1e6,
                        "epochs": int(args["epochs"]),
                        "warm": bool(args["warm"])})
            if len(self.spans) >= count:
                break
            time.sleep(0.001)
        if len(self.spans) != count:
            raise RunFailure(
                "%d dispatches counted, %d spans in the flight "
                "recorder" % (count, len(self.spans)))

    def check_alive(self):
        if self.run_over.is_set():
            raise RunFailure("the program's run ended before the "
                             "window did")
        if time.perf_counter() - self.t0 > DEADLINE_S:
            raise RunFailure("no window within %d s" % DEADLINE_S)

    # -- phases ---------------------------------------------------------

    def run(self):
        try:
            self.wait_started()
            self.capture_initial()
            self.measure()
        except BaseException as exc:    # reported by the main thread
            self.error = exc
        finally:
            self.stop_program()

    def wait_started(self):
        while True:
            wf = self.workflow()
            if wf is not None and getattr(wf, "run_number", 0) >= 1:
                break
            self.check_alive()
            time.sleep(0.005)
        self.mark("program_initialized")

    def capture_initial(self):
        """The weights the program starts from, its first validation
        minibatch and, for the training check, the first epoch's
        minibatches in the order the program will serve them. The first
        dispatch (which loads or compiles its program) has just begun,
        so epoch 0 is still the current epoch."""
        wf = self.workflow()
        loader = wf.loader
        check = self.cell["traffic"].get("check", {})
        n_valid = int(loader.class_lengths[CLASS_VALID])
        data = loader.original_data.mem
        labels = loader.original_labels.mem
        initial = {
            "units": [(type(u).MAPPING, u.export_params())
                      for u in wf.forwards],
            "valid": (data[:n_valid].copy(), labels[:n_valid].copy()),
            "n_train": int(loader.class_lengths[CLASS_TRAIN]),
            "minibatch": int(loader.max_minibatch_size),
        }
        if check.get("train_epochs"):
            order = loader.peek_epoch_orders(1)[0]
            idx, valids = loader.class_schedule(CLASS_TRAIN, order)
            if int(valids.min()) != initial["minibatch"]:
                raise RunFailure("the training check needs whole "
                                 "minibatches")
            initial["train"] = [(data[i].copy(), labels[i].copy())
                                for i in idx]
        if loader.epoch_number != 0 or self.dispatch_count() > 0:
            raise RunFailure("epoch 0 was over before its minibatches "
                             "were read")
        self.initial = initial

    def measure(self):
        """Warm-up, window, the traced dispatches, the stop."""
        count = 0
        warm_run = 0                # warm dispatches in a row, one length
        t_window = t_end = None
        trace_from = trace_until = None
        while True:
            self.check_alive()
            n = self.dispatch_count()
            now = time.perf_counter()
            if n != count:
                count = n
                self.boundaries.append((now, n))
                self.drain_spans(n)
                if n == 1:
                    self.marks["first_dispatch_done"] = now - self.t0
                if t_window is None:
                    last = self.spans[-1]
                    same = len(self.spans) > 1 and \
                        self.spans[-2]["epochs"] == last["epochs"]
                    warm_run = (warm_run + 1 if same else 1) \
                        if last["warm"] else 0
                    if warm_run >= 2:
                        t_window, t_end = now, now + self.seconds
                        self.window = [len(self.boundaries) - 1, None]
                        self.marks["window_start"] = now - self.t0
                        if self.trace:
                            trace_from = self.start_trace()
                elif now <= t_end:
                    self.window[1] = len(self.boundaries) - 1
                if trace_from is not None and trace_until is None \
                        and n > trace_from + TRACE_DISPATCHES:
                    trace_until = self.stop_trace()
            if t_end is not None and now > t_end:
                break
            time.sleep(POLL_S)
        if trace_from is not None and trace_until is None:
            self.stop_trace()
        self.wait_replayed()
        devices = self.workflow().device.jax_devices[:self.cell["chips"]]
        self.peak_bytes = harness.memory_peak_bytes(devices)
        self.memory_stats = devices[0].memory_stats()
        self.mark("window_end")

    def wait_replayed(self):
        """Hold the stop until ``decision.history`` has every epoch of
        the window's dispatches. The program counts a dispatch when the
        whole chunk has run on the device, and only then replays the
        chunk's epochs through the decision on the host; a stop given
        inside that replay would cut the history short of the window.
        """
        if self.window[1] is None:      # no complete dispatch: run() says so
            return
        n_b = self.boundaries[self.window[1]][1]
        epochs = sum(s["epochs"] for s in self.spans[:n_b])
        while len(self.workflow().decision.history) < epochs:
            self.check_alive()
            time.sleep(POLL_S)

    def start_trace(self):
        """Device and runtime events only: the Python tracer would put
        a hook on every call of the program's host loop."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=options)
        self.traced = True
        return self.dispatch_count()

    def stop_trace(self):
        import jax
        jax.profiler.stop_trace()
        return self.dispatch_count()

    def stop_program(self):
        """``workflow.run()`` clears the stop flag as it starts, so the
        stop is given only once the run is under way."""
        while not self.run_over.is_set():
            wf = self.workflow()
            if wf is not None and getattr(wf, "run_number", 0) >= 1:
                wf.stop()
                return
            time.sleep(0.01)


def reference_check(cell, initial, history, log):
    """The program's losses against the plain reference's on the same
    weights and data; -> True when every comparison holds. Tolerances
    are the traffic file's ``check`` block, which gives their reason.
    """
    config, traffic = cell["config"], cell["traffic"]
    check = traffic.get("check", {})
    model = config["model"]
    ref = harness.load_module(cell["bench_dir"], "reference",
                              config["reference"])
    tree = ref.from_program(initial["units"], model)
    ok = True

    def compare(what, got, want, tol):
        nonlocal ok
        good = math.isfinite(got) and abs(got - want) <= tol
        ok = ok and good
        log("check %s: program %.6f, reference %.6f, |diff| %.2e "
            "(tolerance %.1e) %s" % (what, got, want, abs(got - want),
                                     tol, "ok" if good else "FAILED"))

    compare("first validation loss", history[0]["validation"]["loss"],
            ref.loss(tree, initial["valid"], model),
            check["forward_tolerance"])
    for _ in range(int(check.get("train_epochs", 0))):
        lr = traffic.get("learning_rate", model.get("learning_rate"))
        tree, losses = ref.train(tree, initial["train"], model, lr,
                                 model["gradient_moment"])
        compare("mean train loss of epoch 0",
                history[0]["train"]["loss"], statistics.fmean(losses),
                check["train_tolerance"])
        compare("validation loss after epoch 0",
                history[1]["validation"]["loss"],
                ref.loss(tree, initial["valid"], model),
                check["train_tolerance"])
    return ok


def run(cell, seed, seconds, trace, platform, t_process_start):
    """Run the cell once; -> what ``run.py`` needs for its last line
    (``correct``, ``attempted``, ``failed``, ``end_to_end``, ``device``,
    ``breakdown``, ``ctx``)."""
    def log(text):
        print(text, flush=True)

    traffic, config = cell["traffic"], cell["config"]
    devices = harness.require_devices(platform, cell["chips"])
    to_chip = time.perf_counter() - t_process_start
    from veles.__main__ import Main
    argv = build_argv(cell, seed, platform)
    log("+ python -m veles %s" % " ".join(argv))
    main = Main(argv)
    trace_dir = harness.trace_dir(cell["bench_dir"], cell["name"])
    watcher = Watcher(main, cell, seconds, trace, t_process_start,
                      trace_dir)
    watcher.start()
    try:
        main.run()
    finally:
        watcher.run_over.set()
        watcher.join()
    if watcher.error is not None:
        raise watcher.error
    t_run_over = time.perf_counter() - t_process_start

    # -- the window ----------------------------------------------------
    a, b = watcher.window
    if b is None or b == a:
        raise RunFailure("no complete dispatch inside the %g s window"
                         % seconds)
    (t_a, n_a), (t_b, n_b) = watcher.boundaries[a], watcher.boundaries[b]
    dispatches = watcher.spans[n_a:n_b]
    initial = watcher.initial
    steps_per_epoch = -(-initial["n_train"] // initial["minibatch"])
    work_per_sample = resolve_value(config["work"]["per_sample"], cell)
    if not isinstance(work_per_sample, (int, float)):
        raise RunFailure("work per sample %r is not a number"
                         % (work_per_sample,))
    epochs = sum(d["epochs"] for d in dispatches)
    samples = epochs * initial["n_train"]
    rate = samples * work_per_sample / (t_b - t_a)
    cold = [d for d in dispatches if not d["warm"]]
    lengths = sorted({d["epochs"] for d in dispatches})

    # -- what the program reported ---------------------------------------
    wf = main.workflow
    history = wf.decision.history
    first_epoch = sum(d["epochs"] for d in watcher.spans[:n_a])
    in_window = history[first_epoch:first_epoch + epochs]
    train_losses = [h["train"]["loss"] for h in in_window]
    bad_epochs = sum(1 for v in train_losses if not math.isfinite(v))
    served = sum(h["train"]["samples"] for h in in_window)

    log("window: %.3f s, %d dispatches of %s epoch(s), %d epochs, "
        "%d steps, %s per dispatch: %s"
        % (t_b - t_a, len(dispatches), lengths, epochs,
           epochs * steps_per_epoch, config["work"]["metric"],
           " ".join("%.0f" % (d["epochs"] * initial["n_train"]
                              * work_per_sample / d["dur"])
                    for d in dispatches)))
    log("train loss by epoch in the window: %s"
        % " ".join("%.4f" % v for v in train_losses))
    log("loss trajectory from epoch 0 (validation/train): %s"
        % " ".join("%.4f/%.4f" % (h["validation"]["loss"],
                                  h["train"]["loss"])
                   for h in history[:8]))

    correct = True

    def hold(ok, what):
        nonlocal correct
        if not ok:
            correct = False
            log("check FAILED: %s" % what)

    hold(not cold, "%d cold dispatch(es) inside the window" % len(cold))
    hold(len(in_window) == epochs and served == samples,
         "the decision recorded %d epochs and %d train samples for the "
         "window's %d epochs and %d samples"
         % (len(in_window), served, epochs, samples))
    hold(bad_epochs == 0, "%d epoch(s) with a non-finite train loss"
         % bad_epochs)
    if traffic.get("check", {}).get("loss_falls", True):
        hold(len(train_losses) > 1 and train_losses[-1] < train_losses[0],
             "the train loss did not fall over the window (%r -> %r)"
             % (train_losses[:1], train_losses[-1:]))
    t_check = time.perf_counter()
    hold(reference_check(cell, initial, history, log),
         "the program disagrees with the plain reference")
    t_check = time.perf_counter() - t_check

    # -- set-up, told apart ----------------------------------------------
    marks = watcher.marks
    setup_s = marks["window_start"]
    split = {
        "process_to_chip_s": to_chip,
        "program_init_s": marks["program_initialized"] - to_chip,
        "first_dispatch_s": marks["first_dispatch_done"]
        - marks["program_initialized"],
        "first_dispatch_was_warm": watcher.spans[0]["warm"],
        "warm_up_s": setup_s - marks["first_dispatch_done"],
        "setup_s": setup_s,
        "stop_s": t_run_over - marks["window_end"],
        "reference_check_s": t_check,
    }
    log("memory_stats of device 0 at the end of the window: %s"
        % json.dumps(watcher.memory_stats))
    log("setup split: %s" % json.dumps(
        {k: round(v, 3) if isinstance(v, float) else v
         for k, v in split.items()}))

    ctx = harness.Context(
        cell=cell, chips=cell["chips"],
        device_kind=devices[0].device_kind,
        peaks=(harness.peaks.peaks_of(devices[0].device_kind)
               if platform == "tpu" else None),
        dispatches=dispatches, samples_per_epoch=initial["n_train"],
        steps_per_epoch=steps_per_epoch,
        work_per_sample=work_per_sample,
        memory_peak_bytes=watcher.peak_bytes, trace=None,
        costs=harness.load_module(cell["bench_dir"], "costs",
                                  config["costs"]))
    device = harness.device_facts(devices, watcher.peak_bytes)
    breakdown = None
    if watcher.traced:
        from benchmark.reduce import trace as trace_reduce
        try:
            ctx.trace = trace_reduce.reduce_dir(
                trace_dir, chips=cell["chips"])
        except trace_reduce.NoDeviceTrace:
            if platform == "tpu":   # a traced run must show the device
                raise
        else:
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            breakdown = ctx.trace.breakdown()
            log("device trace: %s" % json.dumps(ctx.trace.summary()))
    return {"correct": correct,
            "attempted": epochs * steps_per_epoch,
            "failed": bad_epochs * steps_per_epoch,
            "end_to_end": {config["work"]["metric"]: rate,
                           "setup_s": setup_s},
            "device": device, "breakdown": breakdown, "ctx": ctx}
