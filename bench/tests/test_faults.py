"""A whole run with the timed path broken underneath: the harness's look
for a chip is skipped, the cell runs at smoke size on the CPU, and each
fault a serving cell can have makes ``correct`` come out false, while
the sound path at the same size reads true.  The limit is the cell's
own.  (A cell on one chip has no exchange between chips to leave out.)"""
import time

import pytest

from bench import run as R
from bench.tests import faults, tiny

CELLS = [("internlm2-chat", "internlm2-1.8b")]


def _traffic():
    """Bursts dense enough to fill every slot of the smoke server."""
    t = tiny.traffic()
    t.update(rate_rps=40.0, interarrival_cv=3.0,
             output={"median": 14, "sigma": 0.3, "min": 8, "max": 16})
    return t


def _run(cell_name, config_name, fault, monkeypatch):
    bench, cell = tiny.bench_and_cell(cell_name)
    config = tiny.config(config_name)
    if fault:
        faults.plant(monkeypatch, fault)
    return R.run(bench, cell, config, _traffic(), 5, 2.0, False,
                 require_tpu=False, t_start=time.perf_counter())


@pytest.mark.parametrize("cell,config", CELLS)
@pytest.mark.parametrize("fault", [None, *faults.FAULTS])
def test_fault_fails_the_check(cell, config, fault, monkeypatch):
    res = _run(cell, config, fault, monkeypatch)
    assert list(res)[-1] == "compared"
    assert res["correct"] is (fault is None), res["compared"]
