"""The profiler's trace of the window, and its reduction to device busy
time, device time by op and the host's share in the device's idle gaps.

The window is the host span ``chipbench.window``. A device op is an
event on a TPU plane's ``XLA Ops`` line; its name is the op's HLO text
(``%name = shape opcode(operands), ...``), shortened to its name, opcode
and shape. Device busy time is the union of the
ops' intervals inside the window, averaged over the chips used. An idle
gap is named by the innermost of the benchmark's own host spans that
covers its middle (``host`` where none does), and after ``>`` by the
innermost other host span there, where one does.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


OURS = ("master.", "member", "serve.", "client.")
_OPCODE = re.compile(r"^(%\S+) = (\(.*?\)|\S+) ([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Op:
    name: str              # the op's HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """``%name opcode shape``, without layouts and operands."""
        m = _OPCODE.match(self.name)
        if m is None:
            return self.name[:120]
        shape = re.sub(r"\{[^}]*\}", "", m.group(2))
        return f"{m.group(1)} {m.group(3)} {shape}"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


class Tracer:
    """Context manager around the measured window: off, it does
    nothing; on, it traces the window into a directory of its own and
    reduces the trace when the window closes."""

    def __init__(self, enabled: bool, chips: int = 1):
        self.enabled, self.chips = enabled, chips
        self.reduced: Optional[Dict[str, Any]] = None
        self._dir = self._span = None

    def __enter__(self):
        if self.enabled:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                path = glob.glob(os.path.join(
                    self._dir, "**", "*.xplane.pb"), recursive=True)
                self.reduced = reduce(*load(path[0]), chips=self.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


def load(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device ops by plane and host spans, from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Op(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.append(Span(e.name, e.start_ns, e.end_ns))
    return ops, spans


def union_ns(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops
            if o.end_ns > lo and o.start_ns < hi]


def reduce(ops: Dict[str, List[Op]], spans: Sequence[Span],
           chips: int = 1) -> Dict[str, Any]:
    """Busy and traced seconds, the top device ops and the idle gaps by
    host activity, for the window."""
    windows = [s for s in spans if s.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    planes = sorted(ops)[:chips]
    busy, by_name, gaps = [], defaultdict(float), defaultdict(float)
    near = [s for s in spans if s.name != WINDOW and s.end_ns > lo
            and s.start_ns < hi]
    ours = _Innermost([s for s in near if s.name.startswith(OURS)])
    others = _Innermost([s for s in near if not s.name.startswith(OURS)])
    idle: List[Tuple[float, float]] = []
    for plane in planes:
        merged = union_ns(_clip(ops[plane], lo, hi))
        busy.append(sum(b - a for a, b in merged))
        for o in ops[plane]:
            if o.end_ns > lo and o.start_ns < hi:
                by_name[o.short] += (min(o.end_ns, hi)
                                     - max(o.start_ns, lo)) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    for a, b in sorted(idle, key=lambda ab: ab[0] + ab[1]):
        mid = (a + b) / 2
        name = ours.at(mid) or "host"
        inner = others.at(mid)
        if inner:
            name = f"{name} > {inner}"
        gaps[name] += (b - a) * 1e-9 / len(planes)
    n = max(1, len(planes))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n,
        "device_ops": sorted(([k, v / n] for k, v in by_name.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:TOP],
    }


class _Innermost:
    """The innermost (latest started) span covering a time, for times
    asked in increasing order: a sweep over the spans by start, keeping
    the started ones in a heap by start and dropping ended ones from
    its top."""

    def __init__(self, spans: Sequence[Span]):
        self._spans = sorted(spans, key=lambda s: s.start_ns)
        self._next = 0
        self._heap: List[Tuple[float, int, Span]] = []

    def at(self, t: float) -> Optional[str]:
        while (self._next < len(self._spans)
               and self._spans[self._next].start_ns <= t):
            s = self._spans[self._next]
            heapq.heappush(self._heap, (-s.start_ns, self._next, s))
            self._next += 1
        while self._heap and self._heap[0][2].end_ns <= t:
            heapq.heappop(self._heap)
        return self._heap[0][2].name if self._heap else None


def idle_share(r: Dict[str, Any]) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    t = r.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
