"""Sweep the offered rate of a serving cell, to find its knee.

    python3 chipbench/knee.py --workload <cell> --seed <n> \\
        --rates 1000,2000,4000 --seconds <s> [--out chiprun_out/knee.jsonl]

One federation is set up once and offered each rate in turn for
``--seconds``, each with a schedule of its own drawn from the seed. For
each rate it prints the share of due requests completed, the shed and
unanswered counts, p50 and p99 from the due times, the generator's
lateness and the backlog (submitted, not answered; sampled every 64
requests) in each half of the window, by its mean and its median. The
knee is the highest rate at which, in each of two sweeps and at every
lower rate swept, at least 99% of the requests due are completed, none
is shed, and the backlog does not grow between the halves: its median
over the second half is not more than 20% above the first half's (the
median, because a stall of the whole process lifts a half's mean, not
its median); a serving cell offers four fifths of it. At the end every answer of
every window is checked against the reference. Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.kinds import serve  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.tiny:
        from chipbench.tests.tiny import tiny_cell
        cell = tiny_cell(args.workload, seed=args.seed)
    else:
        cell = harness.load_cell(args.workload)
        cell.seed = args.seed
        harness.use_chip(cell.chips)
        harness.enable_compile_cache()
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    out = open(args.out, "a") if args.out else None

    def emit(d):
        line = json.dumps(d)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    s = serve.Session(cell, time.perf_counter)
    try:
        t0 = time.perf_counter()
        s.open()
        emit({"workload": cell.name, "setup_s": time.perf_counter() - t0})
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = s.window(rate, args.seconds, 10 + i)
            hits = w["cache_hits"] / max(1, w["cache_hits"]
                                         + w["cache_misses"])
            emit({"rate": rate, "attempted": w["attempted"],
                  "completed_share": 1 - w["failed"] / w["attempted"],
                  "shed": w["shed"], "unanswered": w["unanswered"],
                  "p50_ms": percentile(w["latency_ms"], 50),
                  "p99_ms": percentile(w["latency_ms"], 99),
                  "late_p99_ms": percentile(w["lateness_ms"], 99),
                  "backlog_halves": w["backlog_halves"],
                  "backlog_half_medians": w["backlog_half_medians"],
                  "rounds": w["rounds"],
                  "rows_per_round": w["rows_wire"] / max(1, w["rounds"]),
                  "hit_share": hits,
                  "compiles_in_window": w["compiles_in_window"]})
    finally:
        s.close()
    emit({"readings": s.readings(),
          "answers": int(sum(len(r) for r, _ in s.answers))})
    return 0


if __name__ == "__main__":
    sys.exit(main())
