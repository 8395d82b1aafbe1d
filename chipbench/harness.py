"""What every cell shares: the spec, the device, the compile cache, the
metric readers and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration file (``configs/<config>.json``) names the plain
reference it is compared with (``references/<module>.py``); its traffic
file (``traffic/<traffic>.json``) names the kind of traffic
(``kinds/<kind>.py``, the generator that reads it); its limits
(``limits/<cell>.json``) bound each number the comparison reads; and
each metric is read by ``metrics/<metric>.py``. All are found by name.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks."""


def load_json(path) -> Any:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import a file by path (names may hold ``-`` and ``.``)."""
    name = name or "chipbench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(ROOT)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    control: bool = False          # also read the lower-precision control

    @property
    def reference(self):
        return load_module(BENCH_DIR / "references" / self.config["reference"])

    @property
    def kind(self):
        return importlib.import_module(
            f"chipbench.kinds.{self.traffic['kind']}")

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in a run with or without the
        trace, in the spec's order."""
        if not trace:
            return [m for m in self.end_to_end
                    if self.name in m.get("workloads", [self.name])]
        e2e = {m["name"] for m in self.metrics(False)}
        return [m for m in self.per_layer
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_cell(workload: str, spec: Optional[Dict[str, Any]] = None
              ) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(ROOT / configs[w["config"]]["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])


def use_chip(chips: int) -> Dict[str, Any]:
    """The device this run measures, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r}); the "
                     f"benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def settled():
    """Around the window: what set-up made is collected once and kept
    out of the collector's reach, so that a collection in the window
    scans only what the window makes. Released after the window."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip of the run."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def read_metrics(metrics: List[Dict[str, Any]], record: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """Each metric from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
        value = reader.read(record)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(readings: Dict[str, float], limits: Dict[str, Any]
          ) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit (a missing reading or a
    limit with no reading fails)."""
    checks = {}
    for name, lim in limits["checks"].items():
        value = readings.get(name, math.inf)
        checks[name] = {"value": float(value), "limit": float(lim["limit"])}
    return checks


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cell: Cell, device: Dict[str, Any], t_start: float,
             clock=None) -> Dict[str, Any]:
    """Set up, measure and check one cell once; the result line."""
    import time

    import jax
    import numpy as np

    from chipbench.trace import Tracer
    clock = clock or time.perf_counter
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    tracer = Tracer(cell.trace, cell.chips)
    out = cell.kind.run(cell, tracer, clock)
    record = dict(out, setup_s=out["window_start"] - t_start,
                  device=device, peaks=peaks(device["kind"]),
                  trace=tracer.reduced)
    checks = judge(out["readings"]["program"], cell.limits)
    dev = dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))
    if tracer.reduced is not None:
        dev.update(busy_s=tracer.reduced["busy_s"],
                   window_s=tracer.reduced["window_s"])
    result: Dict[str, Any] = {
        "correct": is_correct(checks), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": read_metrics(cell.metrics(cell.trace), record),
        "device": dev}
    if tracer.reduced is not None:
        result["breakdown"] = {k: tracer.reduced[k]
                               for k in ("device_ops", "idle_gaps")}
    notes = [f"setup phases s: {out['setup_phases']!r}"]
    if "lateness_ms" in out:
        from chipbench.stats import percentile
        late, lat = out["lateness_ms"], out["latency_ms"]
        worst = int(np.argmax(late))
        notes.append(
            f"generator lateness ms: p50 {percentile(late, 50)!r} "
            f"p99 {percentile(late, 99)!r} max {float(late[worst])!r} "
            f"(request {worst} of {len(late)}); late over 20 ms: "
            f"{int(np.sum(late > 20))}; backlog by window half "
            f"{out['backlog_halves']!r}; latency ms p90 "
            f"{percentile(lat, 90)!r} p95 {percentile(lat, 95)!r} "
            f"p99 {percentile(lat, 99)!r} p99.9 {percentile(lat, 99.9)!r}")
    result["notes"] = notes
    result["checks"] = checks
    return result
