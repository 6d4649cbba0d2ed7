"""The control of the check, at a size a test run holds on the CPU.

On the chip (bench/control.py) the control is read at the cell's own
size; ``PERF.md`` gives its readings and the limit set from them.  Here
the same code runs at smoke size: the float cell's control serves the
schedule again through the program's int8 path, from the same weights,
and goes through the same run and comparison as the program."""
import numpy as np

from bench import control
from bench.tests import tiny


def test_control_runs_through_the_benchmark_run():
    bench, cell = tiny.bench_and_cell("internlm2-chat")
    row = control.one_seed(bench, cell, tiny.config("internlm2-1.8b"),
                           tiny.traffic(), 4, 2.0, True, require_tpu=False)
    prog, ctrl = row["program"], row["control"]
    assert prog["sample_tokens"] > 0
    assert ctrl["sample_tokens"] == prog["sample_tokens"]
    assert ctrl["limit"] == prog["limit"]
    for r in (prog, ctrl):
        assert 0.0 <= r["max_logit_gap"] < np.inf
        assert 0.0 <= r["miss_share"] <= 1.0
    assert prog["correct"] is True
