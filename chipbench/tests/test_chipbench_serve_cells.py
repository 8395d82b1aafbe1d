"""Every serving cell's functions end to end on the CPU at a test size."""
import time

import pytest

from chipbench import harness
from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell


@pytest.mark.parametrize("workload", ["recsys-table1-mlp.serve-zipf",
                                      "recsys-table1-mlp.serve-uniform"])
def test_serve_cell_runs_and_is_correct(workload):
    cell = tiny_cell(workload, seconds=1.0)
    r = harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["attempted"] == 150 and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_p95_ms", "serve_p50_ms", "setup_s"}
    assert r["metrics"]["serve_p95_ms"]["value"] >= \
        r["metrics"]["serve_p50_ms"]["value"] > 0
    assert any("lateness" in n for n in r["notes"])


def test_traced_serve_cell_reads_the_counters():
    cell = tiny_cell("recsys-table1-mlp.serve-zipf", seconds=1.0,
                     trace=True)
    r = harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert {"serve_queue_ms", "embed_cache_hit_share",
            "serve_compiles_in_window", "serve_p99_ms"} <= set(m)
    assert m["serve_compiles_in_window"]["value"] == 0
    assert 0 < m["embed_cache_hit_share"]["value"] < 100
