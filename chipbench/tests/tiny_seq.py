"""The token-sequence training cell at a size a CPU test run holds: the
same code and block kinds, with narrow widths, few users and short
sequences."""
from __future__ import annotations

import copy

from chipbench import harness
from chipbench.tests.tiny import tiny_cell

WORKLOAD = "recsys-table1-dsv2lite.train"
_YARN = ("theta=10000,yarn_factor=40,yarn_original=4096,beta_fast=32,"
         "beta_slow=1,mscale=0.707,mscale_all_dim=0.707,eps=1e-6")
MLA = f"mla:heads=2,kv_lora=16,nope=8,rope=4,v=8,{_YARN}"
MOE = ("moe:experts=16,held=4,shard=0,top_k=3,hidden=12,shared=2,"
       "norm_topk=0,scaling=1,eps=1e-6")
TOWER = ["tokens:vocab=64,dim=32", MLA, "ffn:hidden=48,eps=1e-6", MLA, MOE,
         "rmsnorm:eps=1e-6", "mlp:hidden=16"]


def tiny_seq_cell(seed: int = 3, seconds: float = 0.3, trace: bool = False,
                  control: bool = False):
    cell = tiny_cell(WORKLOAD, seed=seed, seconds=seconds, trace=trace,
                     control=control)
    cfg = copy.deepcopy(cell.config)
    cfg["data"].update(member_tokens={"seq_len": 16, "vocab": 64,
                                      "zipf_alpha": 1.0})
    cfg["towers"]["bottom"] = list(TOWER)
    cfg["batch_size"] = 8
    cell.config = cfg
    cell.traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                     / "train-seq.json")
    return cell
