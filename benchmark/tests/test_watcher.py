"""The watcher's stop against a program that replays slowly.

``XLAStep`` counts a dispatch when the whole chunk has run on the
device, and only then replays the chunk's epochs through the decision on
the host. The stand-in below does the same with sleeps, through the
program's own ``_record_dispatch``, and the window is made to end just
after a dispatch was counted, in the middle of its replay: the stop must
wait until ``decision.history`` holds every epoch of the window.
"""

import os
import sys
import threading
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

from benchmark.drivers import train     # noqa: E402

EPOCHS = 4              # of one dispatch
DEVICE_S = 0.05         # the chunk on the device
REPLAY_S = 0.05         # one epoch through the decision, on the host
PERIOD_S = DEVICE_S + EPOCHS * REPLAY_S


class SlowReplayWorkflow:
    """Dispatch, count it, replay its epochs; stop between two units,
    as ``Workflow.run`` does."""

    def __init__(self):
        import jax
        self.run_number = 0
        self.stopped = False
        self.decision = types.SimpleNamespace(history=[])
        self.device = types.SimpleNamespace(jax_devices=jax.devices())

    def stop(self):
        self.stopped = True

    def run(self):
        from veles.znicz_tpu.xla_step import _record_dispatch
        self.run_number += 1
        dispatched = 0
        while not self.stopped:
            t0 = time.perf_counter()
            time.sleep(DEVICE_S)
            _record_dispatch("epoch", dispatched > 0, t0,
                             time.perf_counter() - t0, epochs=EPOCHS)
            dispatched += 1
            for _ in range(EPOCHS):
                if self.stopped:
                    return
                time.sleep(REPLAY_S)
                self.decision.history.append({})


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """The program's counters and flight recorder belong to the process:
    each case starts them from nothing, as a run of a cell does."""
    from veles import telemetry
    with telemetry.scoped():
        yield
    telemetry.tracer.clear()


@pytest.mark.parametrize("into_replay", [0.2, 0.5, 0.8])
def test_stop_waits_for_the_replay_of_the_last_dispatch(into_replay):
    """The window ends ``into_replay`` of the way through the replay of
    its last dispatch (two periods after the boundary that opened it)."""
    workflow = SlowReplayWorkflow()
    main = types.SimpleNamespace(workflow=workflow)
    seconds = 2 * PERIOD_S + into_replay * EPOCHS * REPLAY_S
    watcher = train.Watcher(main, {"chips": 1}, seconds, trace=False,
                            t_process_start=time.perf_counter(),
                            trace_dir=None)

    def watch():
        try:
            watcher.measure()
        finally:
            watcher.stop_program()

    thread = threading.Thread(target=watch)
    thread.start()
    workflow.run()
    watcher.run_over.set()
    thread.join()

    # the arithmetic of ``train.run``
    a, b = watcher.window
    n_a, n_b = watcher.boundaries[a][1], watcher.boundaries[b][1]
    assert n_b - n_a == 2           # the window held its two dispatches
    first_epoch = sum(d["epochs"] for d in watcher.spans[:n_a])
    epochs = sum(d["epochs"] for d in watcher.spans[n_a:n_b])
    in_window = workflow.decision.history[first_epoch:
                                          first_epoch + epochs]
    assert epochs == 2 * EPOCHS and len(in_window) == epochs
