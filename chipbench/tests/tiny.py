"""A cell of the benchmark at a size a CPU test run holds: the same
code, the same towers, a few hundred users and a narrow silo."""
from __future__ import annotations

import copy

from chipbench import harness

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_cell(workload: str, seed: int = 3, seconds: float = 1.0,
              trace: bool = False, control: bool = False,
              widths: bool = False) -> harness.Cell:
    """``workload`` is ``<config>.<traffic>``, a cell of the benchmark or
    not. ``widths=True`` keeps the configuration's widths and batch and
    cuts only the users."""
    config, traffic = workload.rsplit(".", 1)
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    limits = harness.BENCH_DIR / "limits" / f"{workload}.json"
    cell = harness.Cell(
        name=workload, chips=1,
        config=harness.load_json(harness.BENCH_DIR / "configs"
                                 / f"{config}.json"),
        traffic=harness.load_json(harness.BENCH_DIR / "traffic"
                                  / f"{traffic}.json"),
        limits=harness.load_json(limits) if limits.exists()
        else {"checks": {}},
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])
    cfg = copy.deepcopy(cell.config)
    if widths:
        cfg["data"].update(n_users=3000, n_interactions=16650)
    else:
        cfg["data"].update(n_users=650, n_interactions=3600,
                           master_features=40, member_features=[12])
        cfg["batch_size"] = 64
    traffic = dict(cell.traffic)
    if traffic["kind"] == "serve":
        traffic.update(rate_per_s=150, max_batch=6, cache_rows=48,
                       warm_requests=64)
    cell.config, cell.traffic = cfg, traffic
    cell.seed, cell.seconds, cell.trace, cell.control = \
        seed, seconds, trace, control
    return cell

