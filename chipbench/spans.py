"""The program's own spans in a traced window, and the per-layer numbers
that read them.

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--obs 1|0] [--tiny]

Runs one cell once as ``run.py --trace 1`` does, with the program's
spans (``repro.obs``) switched on (``--obs 1``, the default) or left
off (``--obs 0``: the spans the benchmark puts round the protocol hooks
only), and prints one JSON line: the result's end-to-end and per-layer
metrics, every idle gap by host span (not only the top ten), each
program span's total, count and longest inside the window, the master
round's self time, and the six numbers of ``METRICS`` that read them.
Not part of a benchmark run. ``--tiny`` rehearses on the CPU at a test
size.

A program span is named ``<party>.<site>`` (``master.d2h``,
``member0.recv_wait``) or ``serve.batcher.<site>``; docs/serving.md
("Tracing") says what each covers.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace  # noqa: E402

ROUND = "master.round"
# the master-thread spans with no program span inside them
LEAVES = tuple(f"master.{site}" for site in (
    "recv_wait", "decode", "encode", "h2d", "gather", "step", "d2h"))
SITES = ("recv_wait", "decode", "encode", "h2d", "gather", "step", "d2h",
         "round")


@dataclasses.dataclass
class LineSpan:
    """A host span and the thread (``(plane, line index)``) it ran on."""
    name: str
    line: Tuple[str, int]
    start_ns: float
    end_ns: float


def is_program_span(name: str) -> bool:
    party, _, site = name.partition(".")
    return (party == "serve" and site.startswith("batcher.")) or (
        (party == "master" or party.startswith("member")) and site in SITES)


def load_lines(path: str) -> List[LineSpan]:
    """Every host event of an ``.xplane.pb``, with its thread."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [LineSpan(e.name, (plane.name, i), e.start_ns,
                                 e.end_ns) for e in line.events]
    return out


def _clipped(s: LineSpan, lo: float, hi: float
             ) -> Optional[Tuple[float, float]]:
    a, b = max(s.start_ns, lo), min(s.end_ns, hi)
    return (a, b) if b > a else None


def reduce_spans(spans: Sequence[LineSpan], lo: float, hi: float
                 ) -> Dict[str, Dict[str, float]]:
    """For every span the benchmark counts as its own or the program's
    (``trace.OURS``), clipped to ``[lo, hi)``: ``total_s``, ``count``
    and ``max_s``. ``master.round`` adds ``self_s``: each round less
    the union of the master-thread leaf spans inside it."""
    out: Dict[str, Dict[str, float]] = {}
    for s in spans:
        ab = _clipped(s, lo, hi) if s.name.startswith(trace.OURS) else None
        if ab is None:
            continue
        d = (ab[1] - ab[0]) * 1e-9
        e = out.setdefault(s.name, {"total_s": 0.0, "count": 0,
                                    "max_s": 0.0})
        e["total_s"] += d
        e["count"] += 1
        e["max_s"] = max(e["max_s"], d)
    if ROUND in out:
        leaves: Dict[Tuple[str, int], List[Tuple[float, float]]] = {}
        for s in spans:
            if s.name in LEAVES:
                leaves.setdefault(s.line, []).append((s.start_ns, s.end_ns))
        self_ns = 0.0
        for s in spans:
            ab = _clipped(s, lo, hi) if s.name == ROUND else None
            if ab is None:
                continue
            inner = trace.union_ns([(max(a, ab[0]), min(b, ab[1]))
                                    for a, b in leaves.get(s.line, [])
                                    if b > ab[0] and a < ab[1]])
            self_ns += (ab[1] - ab[0]) - sum(b - a for a, b in inner)
        out[ROUND]["self_s"] = self_ns * 1e-9
    return out


def _total(r: Dict[str, Any], *sites: str) -> Optional[float]:
    """Seconds in the program's spans of ``sites``, every party; None
    where the run has none (spans off, or a program without them)."""
    found = [v["total_s"] for k, v in (r.get("spans") or {}).items()
             if k.rsplit(".", 1)[-1] in sites and is_program_span(k)]
    return sum(found) if found else None


def _per_round(seconds: Optional[float], rounds) -> Optional[float]:
    return 1e3 * seconds / rounds if seconds is not None and rounds \
        else None


def _serve_rounds(r: Dict[str, Any]) -> int:
    return r["spans"].get("serve.round", {}).get("count", 0)


# -- the per-layer numbers: each reads a record with ``spans`` (and the
#    window's counters) and gives None where there is nothing to read --
def transfer_ms_train(r):
    """ms per round in host<->device copies (every party's d2h + h2d)."""
    return _per_round(_total(r, "d2h", "h2d"), r.get("steps"))


def codec_ms_train(r):
    """ms per round encoding and decoding frames (every party)."""
    return _per_round(_total(r, "encode", "decode"), r.get("steps"))


def host_glue_ms_train(r):
    """Mean ms of a master round outside its leaf spans."""
    rnd = (r.get("spans") or {}).get(ROUND)
    if not rnd or not rnd["count"]:
        return None
    return 1e3 * rnd["self_s"] / rnd["count"]


def transfer_ms_serve(r):
    """ms per serve round in host<->device copies."""
    t = _total(r, "d2h", "h2d")
    return None if t is None else _per_round(t, _serve_rounds(r))


def host_read_max_ms_serve(r):
    """The longest single device-to-host read in the window, ms."""
    reads = [v["max_s"] for k, v in (r.get("spans") or {}).items()
             if k.endswith(".d2h") and is_program_span(k)]
    return 1e3 * max(reads) if reads else None


def batcher_hold_ms_serve(r):
    """ms per serve round the batcher held a partial round open (the
    batcher's ``take`` spans show that its spans were on)."""
    if _total(r, "take") is None:
        return None
    hold = r["spans"].get("serve.batcher.hold", {}).get("total_s", 0.0)
    return _per_round(hold, _serve_rounds(r))


METRICS: Dict[str, Callable[[Dict[str, Any]], Optional[float]]] = {
    "transfer_ms.train": transfer_ms_train,
    "codec_ms.train": codec_ms_train,
    "host_glue_ms.train": host_glue_ms_train,
    "transfer_ms.serve": transfer_ms_serve,
    "host_read_max_ms.serve": host_read_max_ms_serve,
    "batcher_hold_ms.serve": batcher_hold_ms_serve,
}


class SpanTracer(trace.Tracer):
    """The benchmark's tracer, whose reduction keeps every device op and
    idle gap (not the top ten), and the window's spans by thread."""

    def __init__(self, chips: int = 1):
        super().__init__(True, chips)
        self.spans: Dict[str, Dict[str, float]] = {}

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                path = glob.glob(os.path.join(
                    self._dir, "**", "*.xplane.pb"), recursive=True)[0]
                ops, spans = trace.load(path)
                top, trace.TOP = trace.TOP, 1 << 30
                try:
                    self.reduced = trace.reduce(ops, spans,
                                                chips=self.chips)
                finally:
                    trace.TOP = top
                w = next(s for s in spans if s.name == trace.WINDOW)
                self.spans = reduce_spans(load_lines(path), w.start_ns,
                                          w.end_ns)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


def run(cell, device: Dict[str, Any], t_start: float, clock=None
        ) -> Dict[str, Any]:
    """One traced run of ``cell``; the result with the spans."""
    import jax
    clock = clock or time.perf_counter
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    tracer = SpanTracer(cell.chips)
    out = cell.kind.run(cell, tracer, clock)
    record = dict(out, setup_s=out["window_start"] - t_start,
                  device=device, peaks=harness.peaks(device["kind"]),
                  trace=tracer.reduced, spans=tracer.spans)
    checks = harness.judge(out["readings"]["program"], cell.limits)
    kind = "train" if cell.traffic["kind"] == "train" else "serve"
    program = {k: v for k, v in tracer.spans.items() if is_program_span(k)}
    rounds = record.get("steps") if kind == "train" else \
        tracer.spans.get("serve.round", {}).get("count")
    values = {name: fn(record) for name, fn in METRICS.items()
              if name.endswith("." + kind)}
    return {
        "workload": cell.name, "seed": cell.seed,
        "correct": harness.is_correct(checks),
        "attempted": int(out["attempted"]), "failed": int(out["failed"]),
        "end_to_end": harness.read_metrics(cell.metrics(False), record),
        "metrics": harness.read_metrics(cell.metrics(True), record),
        "span_metrics": values,
        "rounds": rounds,
        "spans_per_round": (sum(v["count"] for v in program.values())
                            / rounds) if rounds else None,
        "device": dict(device, busy_s=tracer.reduced["busy_s"],
                       window_s=tracer.reduced["window_s"],
                       memory_peak_bytes=int(out["memory_peak_bytes"])),
        "device_ops": tracer.reduced["device_ops"][:trace.TOP],
        "idle_gaps": tracer.reduced["idle_gaps"],
        "spans": tracer.spans,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    if args.tiny:
        from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell
        cell, device = tiny_cell(args.workload), dict(FAKE_DEVICE)
    else:
        cell = harness.load_cell(args.workload)
        device = harness.use_chip(cell.chips)
        harness.enable_compile_cache()
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, True
    from repro import obs
    obs.enable(bool(args.obs))
    r = run(cell, device, t_start)
    r["obs"] = args.obs
    print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
