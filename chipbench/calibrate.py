"""Readings that the correctness limits are set from, for one cell.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds <s> [--out chiprun_out/calib.jsonl]

For each seed, in one process: the program's run of the cell (a
training cell's window runs from the end of its warm epoch to its
first tail round; a serving cell serves a window of ``--seconds`` at
the cell's rate), its gaps to
the plain reference, and the same gaps of the control (the reference
one precision below the configuration's) and of each fault a run of the
cell can have, planted in the reference put in the program's place.
Not part of a benchmark run. ``--tiny`` rehearses on the CPU at a test
size.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control-only", action="store_true",
                    help="serving: the control's and the faults' readings "
                         "over the rows of a window's schedule, without "
                         "the program")
    args = ap.parse_args(argv)
    if args.tiny:
        from chipbench.tests.tiny import tiny_cell
        cell = tiny_cell(args.workload)
    else:
        cell = harness.load_cell(args.workload)
        harness.use_chip(cell.chips)
        harness.enable_compile_cache()
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    if cell.traffic["kind"] == "train":
        args.seconds = 0.0
    cell.seconds, cell.control = args.seconds, True
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.seed = seed
        t0 = time.perf_counter()
        if args.control_only:
            r = _control_only(cell)
        else:
            r = cell.kind.run(cell, Tracer(False), time.perf_counter)
        line = json.dumps({"workload": cell.name, "seed": seed,
                           "seconds": time.perf_counter() - t0,
                           "attempted": r["attempted"],
                           "failed": r["failed"], **r["readings"]})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


def _control_only(cell):
    import numpy as np

    from chipbench.kinds import serve
    s = serve.Session(cell, time.perf_counter)
    _, users = serve.schedule(cell.traffic, s.n_users,
                              float(cell.traffic["rate_per_s"]),
                              cell.seconds,
                              np.random.default_rng([cell.seed, 2]))
    return {"attempted": len(users), "failed": 0,
            "readings": s.control_readings(users)}


if __name__ == "__main__":
    sys.exit(main())
