"""Run the split-NN main path once on a TPU, at the paper's Table 1 widths.

One process holds the chip and runs every party (``VFLJob`` in
``thread`` mode) on the synthetic SBOL-like silos of
``configs/vfl_recsys.py``: 190,439 users, a 1,345-feature master silo
with 19 items, one 381-feature member silo, 60% id overlap.

  python chip_smoke.py             # one chip: phases A and B
  python chip_smoke.py --chips 4   # four chips: the sharded member tower only

Phase A trains the config's MLP split-NN for one epoch at batch 512
(tail batch included), evaluates it, and serves a few queries through a
``FederatedServer``, each of which must equal offline ``predict`` bit
for bit. Phase B trains the kernel tower (embed, attention, int8
quantize, MLP) for one epoch with the Pallas kernels compiled for the
chip, and checks them against the reference math. ``--chips 4`` trains
that tower with ``tower_shard=4`` next to ``tower_shard=1``.

The script finds no TPU and exits non-zero on any other platform: it
has no CPU fallback. The phase functions take their silos as arguments,
so a CPU test runs them at a reduced size (with the towers' ``auto``
kernels steered to Pallas, which runs in interpret mode there).
The last line of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.vfl_recsys import VFLRecsysConfig  # noqa: E402
from repro.core.party import VFLJob  # noqa: E402
from repro.core.protocols.base import (MasterData, MemberData,  # noqa: E402
                                       VFLConfig)
from repro.core.protocols.driver import Callback  # noqa: E402
from repro.data.synthetic import make_recsys_silos  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import tower as twr  # noqa: E402
from repro.serve.federated import FederatedServer, ServeCfg  # noqa: E402

# configs/vfl_recsys.py widths as TowerSpecs: bottom (in, 256, 128),
# embedding 128, top (128, 128, 64, 19 items)
EMBEDDING_DIM = 128
MLP_TOWER = ("mlp:hidden=256",)
TOP_TOWER = ("mlp:hidden=128|64,final_act=0",)
KERNEL_TOWER = ("embed:tokens=8,dim=64", "attn_block:heads=4", "quantize",
                "mlp:hidden=64")
BATCH = 512
LR = 0.1
SERVE_ROWS = (1, 16, 64, 512)

# Phase B pallas-vs-reference tolerances. The member forward is compared
# under float32 matmuls ("highest"), so what differs is the kernels'
# own arithmetic plus the int8 rounding flips it can cause (each moves
# one value by one quantization step, absmax/127 of its row).
FWD_RTOL = 1e-2          # max |pallas - ref| over max |ref|
LOSS_RTOL = 1e-3         # first-step training loss, pallas vs ref
SHARD_RTOL = 1e-3        # per-step loss, tower_shard=4 vs 1


# ---------------------------------------------------------------------------
# compile accounting (JAX's own monitoring events)
# ---------------------------------------------------------------------------

_EVENTS: collections.Counter = collections.Counter()
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"


def _listen() -> None:
    jax.monitoring.register_event_listener(
        lambda name, **kw: _EVENTS.update({name: 1}))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: _EVENTS.update({name: secs}))


def _compile_snapshot() -> dict:
    return {"compile_s": _EVENTS[_COMPILE], "cache_hits": _EVENTS[_HITS],
            "cache_misses": _EVENTS[_MISSES]}


def _since(snap: dict) -> dict:
    now = _compile_snapshot()
    return {k: round(now[k] - snap[k], 3) if k == "compile_s"
            else now[k] - snap[k] for k in now}


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def load_silos(rcfg: VFLRecsysConfig, seed: int = 0):
    """Master (features + 19-item labels) and member silos, from a seed."""
    data = make_recsys_silos(rcfg, seed=seed)
    master = MasterData(data.ids, data.labels, data.features)
    members = [MemberData(ids, x) for ids, x in
               zip(data.member_ids, data.member_features)]
    return master, members


def split_cfg(tower, batch: int, **kw) -> VFLConfig:
    return VFLConfig(protocol="split_nn", epochs=1, batch_size=batch, lr=LR,
                     seed=0, use_psi=False, embedding_dim=EMBEDDING_DIM,
                     tower=tuple(tower), top_tower=TOP_TOWER, **kw)


def kernel_tower(kernel: str):
    """KERNEL_TOWER with its kernel blocks pinned to ``kernel``."""
    return tuple(f"{b},kernel={kernel}" if b.startswith("attn_block")
                 else f"{b}:kernel={kernel}" if b == "quantize" else b
                 for b in KERNEL_TOWER)


class StopAfter(Callback):
    """Master-side: end the fit phase after ``steps`` rounds."""

    def __init__(self, steps: int):
        self.steps = steps

    def on_batch_end(self, driver, step, epoch, loss):
        if driver.role == "master" and step + 1 >= self.steps:
            driver.request_stop(f"smoke: {self.steps} steps")


class ParamDevices(Callback):
    """Member-side: record where each param leaf's shards live."""

    def __init__(self):
        self.leaves = {}

    def on_fit_end(self, driver):
        if driver.role != "member0":
            return
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                driver.proto.params):
            self.leaves[jax.tree_util.keystr(path)] = {
                "spec": str(leaf.sharding.spec)
                if hasattr(leaf.sharding, "spec") else "single",
                "devices": sorted(s.device.id
                                  for s in leaf.addressable_shards)}


def _check(ok: bool, what) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def _losses(fit) -> list:
    return [h["loss"] for h in fit["history"]]


def _check_epoch(fit, batch: int) -> dict:
    losses = _losses(fit)
    n = fit["n_common"]
    _check(len(losses) == math.ceil(n / batch), (len(losses), n, batch))
    _check(all(np.isfinite(losses)), losses)
    k = max(1, len(losses) // 10)
    first, last = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    _check(last < first, f"loss did not fall: {first} -> {last}")
    return {"n_common": n, "steps": len(losses), "tail_rows": n % batch,
            "loss_first": losses[0], f"loss_first{k}_mean": first,
            f"loss_last{k}_mean": last}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_a(master, members, batch: int = BATCH) -> dict:
    """MLP split-NN: one epoch, evaluate, then serve == offline predict."""
    cfg = split_cfg(MLP_TOWER, batch)
    out: dict = {}
    t0 = time.perf_counter()
    with VFLJob(cfg, master, members, mode="thread") as job:
        fit = job.fit()
        out["fit_s"] = round(time.perf_counter() - t0, 3)
        out.update(_check_epoch(fit, batch))
        t1 = time.perf_counter()
        out["eval"] = job.evaluate()
        out["eval_s"] = round(time.perf_counter() - t1, 3)
        rng = np.random.default_rng(1)
        queries = [rng.choice(fit["n_common"], size=min(r, fit["n_common"]),
                              replace=False) for r in SERVE_ROWS]
        offline = [job.predict(rows=q, batch_size=len(q)) for q in queries]
        t1 = time.perf_counter()
        with FederatedServer(job, ServeCfg(max_batch=max(SERVE_ROWS),
                                           max_wait_ms=0.0)) as srv:
            served = [srv.query(q) for q in queries]
        out["serve_s"] = round(time.perf_counter() - t1, 3)
    for q, s, o in zip(queries, served, offline):
        _check(s.shape == (len(q), master.y.shape[1]), s.shape)
        np.testing.assert_array_equal(s, o)
    out["serve_queries"] = [len(q) for q in queries]
    out["serve_equals_predict"] = True
    return out


def phase_b(master, members, batch: int = BATCH) -> dict:
    """Kernel tower: Pallas vs reference on one batch, one epoch of
    training, and the first-step loss against a reference-kernel job."""
    interpret = ops.default_interpret()
    out: dict = {"interpret": interpret}
    # -- member forward, pallas vs ref, on the first batch ------------------
    xb = jnp.asarray(members[0].x[:batch], jnp.float32)
    spec_p = twr.resolve(kernel_tower("pallas"), xb.shape[1], EMBEDDING_DIM)
    spec_r = twr.resolve(kernel_tower("ref"), xb.shape[1], EMBEDDING_DIM)
    params = twr.init(spec_p, jax.random.key(0))
    fwd_p = jax.jit(functools.partial(twr.apply, spec_p))
    fwd_r = jax.jit(functools.partial(twr.apply, spec_r))
    hlo = fwd_p.lower(params, xb).as_text()
    mosaic = "tpu_custom_call" in hlo
    _check(mosaic == (not interpret),
           f"tpu_custom_call in member forward: {mosaic}, "
           f"interpret={interpret}")
    out["tpu_custom_call"] = mosaic
    with jax.default_matmul_precision("highest"):
        up, ur = np.asarray(fwd_p(params, xb)), np.asarray(fwd_r(params, xb))
    err = float(np.abs(up - ur).max() / np.abs(ur).max())
    out["fwd_rel_err"] = err
    _check(err <= FWD_RTOL, f"pallas vs ref member forward: {err}")
    # -- one epoch through the protocol --------------------------------------
    t0 = time.perf_counter()
    with VFLJob(split_cfg(KERNEL_TOWER, batch), master, members,
                mode="thread") as job:
        fit = job.fit()
    out["fit_s"] = round(time.perf_counter() - t0, 3)
    out.update(_check_epoch(fit, batch))
    # -- first-step loss against the reference kernels ----------------------
    with VFLJob(split_cfg(kernel_tower("ref"), batch), master, members,
                mode="thread", callbacks=[StopAfter(1)]) as job:
        ref_loss = _losses(job.fit())[0]
    rel = abs(out["loss_first"] - ref_loss) / abs(ref_loss)
    out["ref_loss_first"] = ref_loss
    out["loss_first_rel_err"] = rel
    _check(rel <= LOSS_RTOL, f"first-step loss pallas vs ref: {rel}")
    return out


def phase_shard(master, members, shard: int, batch: int = BATCH,
                steps: int = 32) -> dict:
    """The kernel tower with ``tower_shard=shard`` next to unsharded:
    per-step losses agree, and the member's params span ``shard``
    distinct devices."""
    losses, placement = {}, {}
    for s in (1, shard):
        where = ParamDevices()
        t0 = time.perf_counter()
        with VFLJob(split_cfg(KERNEL_TOWER, batch, tower_shard=s),
                    master, members, mode="thread",
                    callbacks=[StopAfter(steps), where]) as job:
            losses[s] = _losses(job.fit())
        placement[s] = where.leaves
        print(f"tower_shard={s}: {len(losses[s])} steps in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    for name, leaf in placement[shard].items():
        print(f"  member0 param {name}: spec={leaf['spec']} "
              f"devices={leaf['devices']}", flush=True)
    devices = sorted({d for leaf in placement[shard].values()
                      for d in leaf["devices"]})
    split = [n for n, leaf in placement[shard].items()
             if len(leaf["devices"]) == shard and "model" in leaf["spec"]]
    _check(len(devices) == shard and split, (devices, split))
    a, b = np.asarray(losses[1]), np.asarray(losses[shard])
    _check(len(a) == len(b) == steps and np.isfinite(b).all(), (a, b))
    rel = float(np.max(np.abs(a - b) / np.abs(a)))
    _check(rel <= SHARD_RTOL, f"tower_shard={shard} vs 1 losses: {rel}")
    return {"steps": steps, "param_devices": devices,
            "sharded_leaves": len(split), "loss_max_rel_diff": rel,
            "loss_first": [a[0], b[0]], "loss_last": [a[-1], b[-1]]}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _run(name: str, fn, *args, **kw) -> dict:
    snap, t0 = _compile_snapshot(), time.perf_counter()
    out = fn(*args, **kw)
    out["wall_s"] = round(time.perf_counter() - t0, 3)
    out.update(_since(snap))
    print(f"{name}: {json.dumps(out, default=float)}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded member tower")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) found", file=sys.stderr)
        return 2
    _listen()
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    rcfg = VFLRecsysConfig()
    t0 = time.perf_counter()
    master, members = load_silos(rcfg)
    print(f"silos: users={rcfg.n_users} master_features="
          f"{master.x.shape[1]} items={master.y.shape[1]} member_features="
          f"{members[0].x.shape[1]} member_users={len(members[0].ids)} "
          f"overlap={rcfg.id_overlap} (no rows cut) built in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    if args.chips == 4:
        _run("phase_shard", phase_shard, master, members, shard=args.chips)
    else:
        _run("phase_a", phase_a, master, members)
        _run("phase_b", phase_b, master, members)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
