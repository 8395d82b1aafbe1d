"""Roofline table of the LLM dry runs: dryrun JSONs -> the
EXPERIMENTS.md §Roofline markdown table
(``python -m repro.launch.roofline [--mesh single]``).
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

NOTES = {
    "compute_s": "compute-bound: more chips or lower precision",
    "memory_s": "HBM-bound: fuse reads / shrink cache or state traffic",
    "collective_s": "collective-bound: resharding or dispatch schedule "
                    "(see §Perf)",
}


def rows_for(mesh: str) -> List[Dict]:
    from repro.launch.dryrun import RESULTS_DIR
    rows = []
    for f in sorted(RESULTS_DIR.glob(f"*__{mesh}.json")):
        rows.append(json.loads(f.read_text()))
    return rows


def table(mesh: str) -> str:
    out = ["| arch | shape | compute s | memory s | collective s | "
           "dominant | MODEL/HLO | fits/chip | note |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows_for(mesh):
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                       f"skipped | — | — | {r['reason'][:40]} |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | ERROR "
                       f"| — | — | |")
            continue
        rf = r["roofline"]
        # MODEL_FLOPS / analytic HLO-equivalent flops (useful-compute frac)
        ratio = rf["model_flops"] / max(rf["analytic_flops"], 1)
        mem = r["memory"].get("per_device_gib_estimate", 0)
        dom = rf["dominant"]
        out.append(
            f"| {r['arch']} | {r['shape']} | {rf['compute_s']:.3f} | "
            f"{rf['memory_s']:.3f} | {rf['collective_s']:.3f} | "
            f"{dom.replace('_s', '')} | {ratio:.2f} | "
            f"{mem:.2f} GiB | {NOTES[dom][:46]} |")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    print(table(args.mesh))


if __name__ == "__main__":
    main()
