"""Transformer-tower split-NN under pipelining (DESIGN.md §12): the
workload the tower factory exists for — member compute AND exchange
both non-trivial, measured with the driver's per-step exchange
account.

Workload: one member with an embed + attn_block + mlp tower
(`TowerSpec`, ~0.4 GFLOP forward per 512-row step) and a 128 KiB
float32 activation exchange per step, over real TCP sockets with one
OS process per agent (``socket_proc``), the link shaped to a
10 Mbit/s, 10 ms WAN profile — sized on the 2-core CI host so
per-step compute and wire time are the same order (each ≥ 25% of the
step in the committed baseline). Depth 1 is lock-step; depth 2
overlaps the member's forward with the in-flight exchange — the
pipeline win the exchange account explains.

Methodology (the bench-discipline note in ROADMAP.md):

* each agent process capped at 1 compute thread (per-silo hardware
  emulation; uncapped XLA pools thrash the 2-core host),
* depths interleaved, per-depth MIN over reps (host throughput
  drifts minute-to-minute; interleaving samples both arms under the
  same conditions),
* steady-state per-step time from the master's wall stamps, first
  steps skipped (per-process jit compile + pipeline fill).

Gated rows (benchmarks/check_regression.py, ``vfl_tower_`` prefix):
``vfl_tower_splitnn_d1`` and ``vfl_tower_splitnn_d2``; the d2 row's
``derived`` carries the member's exchange split (unblocked_frac /
wire_frac) and the d2-vs-d1 speedup. The ``vfl_tower_exchange_*``
rows are informational (per-step unblocked seconds per role).

Standalone: PYTHONPATH=src python -m benchmarks.bench_tower [--quick]

A host-CPU bench: it pins JAX to the CPU (``JAX_PLATFORMS=cpu``, set
before JAX is imported) so its ``socket_proc`` agents, which inherit the
setting, never contend for a chip.
"""
from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

N_ROWS = 4096
BATCH = 512
WIDTHS = [48]
EMBED_DIM = 64
TOWER = ("embed:tokens=8,dim=64", "attn_block:heads=4",
         "mlp:hidden=64")
TOP_TOWER = ("mlp:hidden=64,final_act=0",)
# WAN shape: 131 KiB activations take ~105 ms at 10 Mbit/s — the same
# order as the ~145 ms member forward+backward on the CI host
LATENCY_MS = 10.0
BANDWIDTH_MBPS = 10.0


def bench_tower(emit, quick: bool = False) -> None:
    caps = {"XLA_FLAGS": "--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1",
            "OPENBLAS_NUM_THREADS": "1", "OMP_NUM_THREADS": "1"}
    saved = {k: os.environ.get(k) for k in caps}
    os.environ.update(caps)        # spawned agents inherit
    try:
        _bench_tower(emit, quick)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _steady_us(history, skip: int) -> float:
    h = history
    skip = min(skip, len(h) - 2)
    return (h[-1]["wall_s"] - h[skip]["wall_s"]) / \
        (len(h) - 1 - skip) * 1e6


def _bench_tower(emit, quick: bool) -> None:
    from repro.comm.base import CommCfg, LinkSpec
    from repro.core.party import run_vfl
    from repro.core.protocols.base import VFLConfig
    from repro.data.vertical import vertical_partition

    rng = np.random.default_rng(0)
    items = 8
    d = sum(WIDTHS) + 16
    x = rng.normal(size=(N_ROWS, d))
    y = (x @ rng.normal(size=(d, items)) > 0).astype(np.float64)
    ids = [f"u{i:06d}" for i in range(N_ROWS)]
    master, members = vertical_partition(ids, x, y, widths=WIDTHS,
                                         overlap=1.0, seed=1)
    cfg = VFLConfig(protocol="split_nn", epochs=1 if quick else 2,
                    batch_size=BATCH, lr=0.05, use_psi=False,
                    embedding_dim=EMBED_DIM, tower=TOWER,
                    top_tower=TOP_TOWER)
    link = CommCfg(link=LinkSpec(latency_ms=LATENCY_MS,
                                 bandwidth_mbps=BANDWIDTH_MBPS))

    per_step = {1: float("inf"), 2: float("inf")}
    info: dict = {}
    exch: dict = {}
    for _ in range(2 if quick else 3):
        for depth in per_step:
            res = run_vfl(cfg, master, members, mode="socket_proc",
                          pipeline_depth=depth, comm_cfg=link)
            h = res["master"]["history"]
            us = _steady_us(h, skip=4)
            if us < per_step[depth]:
                per_step[depth] = us
                info[depth] = f"steps={len(h)} loss={h[-1]['loss']:.4f}"
                exch[depth] = {r: res[r]["exchange"]
                               for r in ("master", "member0")}
    for depth, us in per_step.items():
        m0 = exch[depth]["member0"]
        extra = "" if depth == 1 else \
            f" speedup_x{per_step[1] / max(us, 1e-9):.2f}"
        emit(f"vfl_tower_splitnn_d{depth}", us,
             f"{info[depth]} mode=socket_proc "
             f"wan={LATENCY_MS:.0f}ms/{BANDWIDTH_MBPS:.0f}Mbps "
             f"member_unblocked_frac={m0['unblocked_frac']:.2f} "
             f"member_wire_frac={m0['wire_frac']:.2f}{extra}")
    # informational: the per-role exchange split behind the d2 win
    for role in ("master", "member0"):
        r = exch[2][role]
        emit(f"vfl_tower_exchange_{role}",
             r["unblocked_s_per_step"] * 1e6,
             f"d2 wall_us={r['wall_s_per_step'] * 1e6:.0f} "
             f"unblocked_frac={r['unblocked_frac']:.2f} "
             f"wire_frac={r['wire_frac']:.2f} "
             f"stall_frac={r['stall_frac']:.2f} "
             f"flops_per_step={r['model_flops_per_step']:.3g} "
             f"exch_intensity={r.get('exchange_intensity', 0):.0f}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    def emit(name, us, derived):
        print(f"{name},{us:.2f},{derived}")

    print("# host-CPU bench: JAX_PLATFORMS=cpu; no row is a device number")
    print("name,us_per_call,derived")
    bench_tower(emit, args.quick)
