"""Every training cell's functions end to end on the CPU at a test size."""
import time

import pytest

from chipbench import harness
from chipbench.kinds import train
from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell
from chipbench.trace import Tracer


def test_train_cell_runs_and_is_correct():
    cell = tiny_cell("recsys-table1-mlp.train", seconds=0.5)
    r = harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(cell.limits["checks"])


def test_traced_train_cell_reports_its_per_layer_metrics():
    cell = tiny_cell("recsys-table1-mlp.train", seconds=0.5, trace=True)
    r = harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())
    assert r["correct"], r["checks"]
    # the CPU has no device plane: the device metrics read as an idle
    # device here and are never reported from a CPU run by the entry
    assert {"train_mfu", "exchange_wait_ms.train",
            "device_idle_share.train"} <= set(r["metrics"])
    assert r["device"]["window_s"] > 0
    assert "device_ops" in r["breakdown"]


@pytest.mark.parametrize("n, batch, warm, steps", [
    (114263, 512, "epoch", [224, 447]),   # Table 1: 223 full, 87-row tail
    (390, 64, "none", [3, 6]),
    (384, 64, "epoch", [6])])             # no short last batch
def test_window_rounds_compared(n, batch, warm, steps):
    assert train.later_steps(n, batch, 3, warm) == steps
