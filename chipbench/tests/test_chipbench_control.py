"""``correct`` comes out false with the control in the program's place:
the reference one precision below the configuration's (three-pass bf16
products), judged by the cell's limits as the program is. At the
cell's widths and batch on the CPU, with fewer users: the precision the
control lacks shows at the widths it has."""
import time

import pytest

from chipbench import harness
from chipbench.tests.tiny import tiny_cell
from chipbench.trace import Tracer


@pytest.mark.parametrize("workload", ["recsys-table1-mlp.train",
                                      "recsys-table1-mlp.serve-zipf",
                                      "recsys-table1-mlp.serve-uniform"])
def test_control_is_not_correct(workload):
    cell = tiny_cell(workload, seconds=0.3, control=True, widths=True)
    if cell.traffic["kind"] == "serve":
        cell.traffic["max_batch"] = 8
    out = cell.kind.run(cell, Tracer(False), time.perf_counter)
    assert harness.is_correct(harness.judge(out["readings"]["program"],
                                            cell.limits))
    checks = harness.judge({**out["readings"]["program"],
                            **out["readings"]["control"]}, cell.limits)
    assert not harness.is_correct(checks), checks
