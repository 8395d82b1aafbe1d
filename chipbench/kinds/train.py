"""Training traffic: ``VFLJob.fit`` over continuous shuffled epochs.

Set-up builds one job and drives it from the seed through its first
steps (kept for the comparison with the reference), then through the
rest of the first epoch, so that every batch shape (the full batch and
the epoch's tail) is compiled. The measured window continues the same
job: it starts before the fit call and ends at the end of the first
round past ``--seconds``, and not before the window's first tail round;
the rate is every sample of those rounds over the whole window.

The reference replays plain SGD from the benchmark's weights over the
rows of every round up to that tail round. Compared with it: the loss
of every step; the first steps' gradient and the change of the weights
over them; and the gradient of the window's first round and of its
first tail round (the epoch's short last batch, a program of its own),
each the program's as (weights before - weights after) / lr.

Traffic file keys: ``kind`` ("train"), ``first_steps`` (steps compared
with the reference), ``warm`` ("epoch": finish the first epoch in
set-up).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import compare, harness, program
from chipbench.silos import key_from_seed, make_silos


def later_steps(n: int, batch: int, first: int, warm: str) -> List[int]:
    """The steps compared in the window: its first round, and its first
    tail round where the epoch has a short last batch."""
    per_epoch = -(-n // batch)
    start = per_epoch if warm == "epoch" else first
    if n % batch == 0:
        return [start]
    return [start, start + (per_epoch - 1 - start % per_epoch) % per_epoch]


def run(cell, tracer, clock) -> Dict[str, Any]:
    import jax
    traffic, cfg = cell.traffic, cell.config
    first = int(traffic["first_steps"])
    model = cell.reference.Model(cfg)
    key = key_from_seed(cell.seed)
    phases = program.Phases(clock)
    silos = make_silos(cfg["data"], jax.random.fold_in(key, 0))
    weights = model.init(jax.random.fold_in(key, 1))
    phases.mark("silos_and_weights")
    later = later_steps(len(silos.common), int(cfg["batch_size"]), first,
                        traffic["warm"])
    last = later[-1]
    hooks = program.make_hooks()(weights, spans=cell.trace)
    hooks.keep_rows = last + 1
    compiles = program.CompileCounter()
    job = program.make_job(program.vfl_config(cfg, cell.seed,
                                              epochs=1 << 30),
                           silos, hooks)
    # each party's weights after the steps the comparison needs
    # (references to the arrays the party holds, no copy)
    need = {first - 1} | {s for k in [0] + later for s in (k - 1, k)}
    snaps: Dict[str, Dict[int, Any]] = {}

    def keep(d, step):
        if step in need:
            p = d.proto
            snaps.setdefault(d.role, {})[step] = (
                (p.top, p.bottom) if d.role == "master" else p.params)
    out: Dict[str, Any] = {"setup_phases": phases.seconds}
    try:
        # -- the first steps, through the window's own call ----------------
        def first_round(d, step):
            keep(d, step)
            if step + 1 >= first:
                d.request_stop("first steps")
        hooks.on_round, hooks.on_member_round = first_round, keep
        job.fit()
        phases.mark("job_and_first_steps")
        # -- the rest of the first epoch: every batch shape compiles -------
        if traffic["warm"] == "epoch":
            hooks.on_round = keep
            hooks.on_epoch = lambda d, epoch: d.request_stop("warm epoch")
            job.fit()
            hooks.on_epoch = None
            phases.mark("warm_epoch")
        # -- the window ----------------------------------------------------
        stats = hooks.master_stats()
        mark: Dict[str, Any] = {}

        def window_round(d, step):
            keep(d, step)
            if clock() >= mark["t0"] + cell.seconds and step >= last \
                    and "t1" not in mark:
                mark.update(t1=clock(), rounds=len(hooks.rows),
                            wait=stats.recv_wait_s,
                            compiles=compiles.count)
                d.request_stop("window closed")
        hooks.on_round = window_round
        with harness.settled(), tracer:
            r0, wait0, c0 = len(hooks.rows), stats.recv_wait_s, \
                compiles.count
            mark["t0"] = t0 = clock()
            history = job.fit()["history"]
        hooks.on_round = None
        window_rows = hooks.rows[r0:mark["rounds"]]
        window_losses = [h["loss"] for h in history[r0:mark["rounds"]]]
        out.update(
            window_start=t0, window_s=mark["t1"] - t0, attempted=len(window_rows),
            failed=int(np.sum(~np.isfinite(window_losses))),
            samples=int(np.sum(window_rows)), steps=len(window_rows),
            exchange_wait_s=mark["wait"] - wait0,
            compiles_in_window=mark["compiles"] - c0,
            flops_per_sample=model.train_flops_per_sample())
        out["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    finally:
        job.shutdown()
    rows = hooks.kept[:last + 1]
    prog = _program_run(snaps, weights, model.lr, first, later,
                        [h["loss"] for h in history[:last + 1]])
    del job, hooks, snaps
    # -- the reference, once the program's state is freed ------------------
    readings = _readings(model, weights, silos, rows, prog, first, later,
                         cell.control)
    readings["program"]["window_nonfinite_losses"] = out["failed"]
    out["readings"] = readings
    return out


def _tree(snaps, step) -> Dict[str, Any]:
    top, bottom = snaps["master"][step]
    members = sorted((r for r in snaps if r != "master"),
                     key=lambda r: int(r[len("member"):]))
    return {"bottom": bottom, "top": top,
            "members": [snaps[r][step] for r in members]}


def _program_run(snaps, w0, lr, first, later, losses) -> Dict[str, Any]:
    """The program's losses, its gradient at step 0 and at each later
    step compared, and its change of the weights over the first steps;
    a step whose weights a party did not keep is left out."""
    def weights(step):
        return w0 if step < 0 else _tree(snaps, step)
    grads = {}
    for k in [0] + later:
        try:
            grads[k] = compare.tree_scale(compare.tree_sub(
                weights(k - 1), weights(k)), 1.0 / lr)
        except KeyError:
            pass
    return {"losses": losses, "grads": grads,
            "delta": compare.tree_sub(weights(first - 1), w0)}


def _batch(silos, rows):
    """One batch of the matched order, as every party held it."""
    mrows = silos.common[np.asarray(rows)]
    return (silos.master_x[mrows],
            [x[silos.member_index(j, mrows)]
             for j, x in enumerate(silos.member_x)],
            silos.labels[mrows])


def _readings(model, w0, silos, rows, prog, first, later, control):
    """The program's readings against the reference, which replays plain
    SGD from ``w0`` over ``rows``, and, where asked, the control's (the
    reference one precision below) and each fault's, planted in the
    reference put in the program's place."""
    def replay(prec="highest", change=lambda b: b):
        losses, grads, w_first = [], {}, None
        steps = model.sgd_steps(w0, (change(_batch(silos, r)) for r in rows),
                                prec)
        for k, (loss, g, w) in enumerate(steps):
            losses.append(loss)
            if k == 0 or k in later:
                grads[k] = g
            if k == first - 1:
                w_first = w
        return {"losses": [float(x) for x in losses], "grads": grads,
                "delta": compare.tree_sub(w_first, w0)}

    def against(run):
        return {
            **compare.train_readings(
                run["losses"][:first], run["grads"][0], run["delta"],
                ref["losses"][:first], ref["grads"][0], ref["delta"]),
            **compare.window_readings(
                run["losses"][first:],
                {k: g for k, g in run["grads"].items() if k in later},
                ref["losses"][first:], {k: ref["grads"][k] for k in later})}
    ref = replay()
    out = {"program": against(prog)}
    if not control:
        return out

    def half(b):
        xm, xbs, y = b
        h = len(y) // 2
        return xm[:h], [xb[:h] for xb in xbs], y[:h]
    out["control"] = against(replay("high"))
    # the faults of a training step, planted in the reference put in the
    # program's place (a step that leaves the state unchanged reads 1 on
    # the gradient and change norms by their definition)
    out["fault_half_batch"] = against(replay(change=half))
    out["fault_no_exchange"] = against(replay(
        change=lambda b: (b[0], [], b[2])))
    out["fault_altered_row"] = against(replay(
        change=lambda b: (b[0], [xb.at[0].add(8.0) for xb in b[1]], b[2])))
    return out
