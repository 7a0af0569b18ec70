"""``test_cells_cpu.py`` runs every cell of the manifest through
``cpu_cell.py``, whose table of tiny presets is keyed by traffic name
and may not be edited by the PR that adds a cell: a cell whose traffic
has no preset there would train at its real size on the CPU. Such a
case is skipped here — by that rule, not by name — and the PR that
brought the cell brings a runner with its preset and the same test
(``cpu_cell_lfm2.py`` and ``test_lfm2_moe.py`` for PR 28's cell)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def pytest_collection_modifyitems(items):
    import cpu_cell
    from benchmark import run
    for item in items:
        if getattr(item, "originalname", "") != "test_cell_runs_end_to_end":
            continue
        cell = run.resolve(cpu_cell.BENCH_DIR,
                           item.callspec.params["workload"])
        if cell["traffic_name"] not in cpu_cell.PRESETS:
            item.add_marker(pytest.mark.skip(
                reason="cpu_cell.py has no tiny preset for the traffic "
                "%r; the cell's own runner rehearses it"
                % cell["traffic_name"]))
