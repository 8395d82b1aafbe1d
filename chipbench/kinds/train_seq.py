"""Training traffic over token sequences: ``VFLJob.fit`` over shuffled
users, where the member's silo holds each user's recent text as token
ids and its tower is a decoder stack with routed experts.

Set-up draws the Table 1 silos (``silos.make_silos``) and the member's
token silo on the device from the seed, builds one job and drives it
through its first steps from the benchmark's weights. No epoch is
warmed: every round of the window has the full batch's shapes, which
the first steps compiled. The measured window continues the same job
and ends at the end of the first round past ``--seconds``; the rate is
every sample of those rounds over the whole window.

The reference replays plain SGD from the benchmark's weights over the
rows of the first steps and of the window's first round, routed by the
program's own expert choices at each of them (kept on the device for
those steps only). Compared with it: the loss of each replayed step;
the gradient of the first step and of the window's first round and the
change of the weights over the first steps, by the norm of every leaf
(taken on the device as the steps pass, so that no whole copy of the
weights is kept beyond the benchmark's own). Both sides' gradients are
read alike, as (weights before - weights after) / lr from their float32
weights: a norm scale of 1 moved by a small update keeps few digits of
it, and read against the exact gradient that rounding, not the
program, would set the gap. How far each expert choice
the program made is from the reference's own
(``route_flip_margin``); and the routed rows the program left
uncomputed (``dropped_rows``).

The member's counts of rows routed to each held expert are read once,
after the window. With ``--trace 1`` the grouped expert products'
device time in the window is read from the trace too
(``expert_gmm_s``).

Traffic file keys: ``kind`` ("train_seq"), ``first_steps`` (steps
compared before the window).
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, harness, program
from chipbench import trace as tr
from chipbench.kinds.train import _batch
from chipbench.silos import Silos, key_from_seed, make_silos

# grouped products a routed row goes through in one round: the
# forward's three (gate, up, down), and in the backward the forward
# again, its recomputation (each decoder block is rematerialised) and
# the two products of each of the three (the rows' and the weights'
# gradients)
GMM_PER_ROW = 3 + 3 + 3 + 6
GMM_OP = "%ragged-dot"


# ---------------------------------------------------------------------------
# the member's token silo
# ---------------------------------------------------------------------------


def make_token_silos(data: Dict[str, Any], key) -> Silos:
    """The Table 1 silos with the member's silo replaced by token ids.

    The master's silo and labels, and which users the member holds, are
    ``make_silos``'s (with a one-column stand-in member silo, dropped).
    Each held user gets ``seq_len`` ids over the ``vocab`` slice, drawn
    Zipf(``zipf_alpha``) by rank; the user's ranks are rotated by an
    offset set by their latent factor (the first of ``make_silos``'s
    draws, drawn again here), so that the labels depend on the text."""
    tok = data["member_tokens"]
    seq, vocab = int(tok["seq_len"]), int(tok["vocab"])
    alpha = float(tok["zipf_alpha"])
    stand_in = dict(data, member_features=[1])
    silos = make_silos(stand_in, key)
    n, latent = int(data["n_users"]), int(data["latent"])
    rows = jnp.asarray(silos.member_rows[0])

    @jax.jit
    def draw(key, rows):
        ks = jax.random.split(key, 6 + 3)
        zu = jax.random.normal(ks[0], (n, latent), jnp.float32)
        k1, k2 = jax.random.split(jax.random.fold_in(key, 1 << 20))
        a = jax.random.normal(k1, (latent,), jnp.float32)
        a = a / jnp.linalg.norm(a)
        shift = jnp.floor(vocab * jax.scipy.stats.norm.cdf(zu[rows] @ a))
        p = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -alpha
        cdf = jnp.cumsum(p) / jnp.sum(p)
        u = jax.random.uniform(k2, (rows.shape[0], seq), jnp.float32)
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        return ((rank + shift[:, None].astype(jnp.int32)) % vocab
                ).astype(jnp.int32)
    tokens = draw(key, rows)
    return Silos(silos.master_x, silos.labels, [tokens],
                 silos.member_rows, silos.ids)


# ---------------------------------------------------------------------------
# what the grouped expert product should take
# ---------------------------------------------------------------------------


def gmm_ideal_s(rows_by_layer, rounds: int, d: int, f: int, held: int,
                peaks: Dict[str, float]) -> float:
    """Least device time of the window's grouped expert products: per
    ``moe`` block and round, ``GMM_PER_ROW`` products over the block's
    mean routed rows a round, each the larger of its FLOPs
    (``2 * rows * d * f``) over the bf16 peak and its bytes (float32:
    the rows in and out, ``rows * (d + f)``, and the held experts'
    weights, ``held * d * f``) over the HBM bandwidth."""
    total = 0.0
    for rows in rows_by_layer:
        r = float(np.sum(rows)) / rounds
        flops = 2.0 * r * d * f
        nbytes = 4.0 * (r * (d + f) + held * d * f)
        total += GMM_PER_ROW * rounds * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return total


def gmm_device_s(ops, spans, chips: int = 1) -> float:
    """Device seconds of the grouped products (``ragged-dot`` kernels
    and their metadata) inside the window, averaged over the chips."""
    win = [s for s in spans if s.name == tr.WINDOW][0]
    planes = sorted(ops)[:chips]
    total = 0.0
    for plane in planes:
        for o in ops[plane]:
            if o.name.startswith(GMM_OP) and o.end_ns > win.start_ns \
                    and o.start_ns < win.end_ns:
                total += (min(o.end_ns, win.end_ns)
                          - max(o.start_ns, win.start_ns)) * 1e-9
    return total / max(1, len(planes))


class _Tracer(tr.Tracer):
    """The harness's tracer, which also sums the grouped products' device
    time before the trace is thrown away; the harness's own reduction is
    handed back to it unchanged."""

    def __init__(self, outer: tr.Tracer):
        super().__init__(outer.enabled, outer.chips)
        self.outer = outer
        self.gmm_s = None

    def __exit__(self, *exc):
        if not self.enabled:
            return False
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                path = glob.glob(os.path.join(
                    self._dir, "**", "*.xplane.pb"), recursive=True)
                ops, spans = tr.load(path[0])
                self.outer.reduced = tr.reduce(ops, spans, chips=self.chips)
                self.gmm_s = gmm_device_s(ops, spans, self.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _state(d):
    p = d.proto
    return {"top": p.top, "bottom": p.bottom} if d.role == "master" \
        else {"params": p.params}


class _Norms:
    """Per-leaf norms of the program's gradients and change of weights,
    taken on the device as the steps pass: the first step's gradient,
    the change over the first ``first`` steps, and the gradient of step
    ``later``."""

    def __init__(self, w0, lr: float, first: int, later: int):
        self.w0 = {"master": {"top": w0["top"], "bottom": w0["bottom"]},
                   "member0": {"params": w0["members"][0]}}
        self.lr, self.first, self.later = lr, first, later
        self.grads: Dict[int, Dict[str, Any]] = {0: {}, later: {}}
        self.delta: Dict[str, Any] = {}
        self._prev: Dict[str, Any] = {}

    def see(self, role: str, state, step: int) -> None:
        if step == 0:
            self.grads[0][role] = _diff_norms(self.w0[role], state,
                                              1.0 / self.lr)
        if step == self.first - 1:
            self.delta[role] = _diff_norms(state, self.w0[role], 1.0)
        if step == self.later - 1:
            self._prev[role] = state
        if step == self.later and role in self._prev:
            self.grads[step][role] = _diff_norms(self._prev.pop(role),
                                                 state, 1.0 / self.lr)

    @staticmethod
    def tree(by_role) -> Dict[str, Any]:
        """The benchmark's weight layout, each leaf its norm."""
        m = by_role["master"]
        return {"bottom": m["bottom"], "top": m["top"],
                "members": [by_role["member0"]["params"]]}


def run(cell, tracer, clock) -> Dict[str, Any]:
    traffic, cfg = cell.traffic, cell.config
    first = int(traffic["first_steps"])
    later = first                        # the window's first round
    model = cell.reference.Model(cfg)
    # the program's configuration first: a program without a member tower
    # of its own fails here, before anything is drawn
    vfl = program.vfl_config(
        cfg, cell.seed, epochs=1 << 30,
        master_tower=tuple(cfg["towers"]["master_bottom"]))
    key = key_from_seed(cell.seed)
    phases = program.Phases(clock)
    silos = make_token_silos(cfg["data"], jax.random.fold_in(key, 0))
    phases.mark("silos")
    weights = model.init(jax.random.fold_in(key, 1))
    phases.mark("weights")
    hooks = program.make_hooks()(weights, spans=cell.trace)
    hooks.keep_rows = later + 1
    compiles = program.CompileCounter()
    job = program.make_job(vfl, silos, hooks)
    norms = _Norms(weights, model.lr, first, later)
    routes: Dict[int, Any] = {}          # step -> the member's choices
    counts: Dict[str, Any] = {}          # the member's routed-row counts

    def member():
        return hooks.drivers["member0"].proto

    def keep(d, step):
        norms.see(d.role, _state(d), step)

    def master_round(d, step):
        """After the master's step: the member has made this step's
        forward and cannot have begun the next one (it is announced
        after this hook), so its choices and counts are this step's."""
        keep(d, step)
        if step <= later:
            routes[step] = member().routes
        if step == first - 1:
            counts["start"] = member().route_stats
    out: Dict[str, Any] = {"setup_phases": phases.seconds}
    traced = _Tracer(tracer)
    try:
        # -- the first steps, through the window's own call ----------------
        def first_round(d, step):
            master_round(d, step)
            if step + 1 >= first:
                d.request_stop("first steps")
        hooks.on_round, hooks.on_member_round = first_round, keep
        job.fit()
        phases.mark("job_and_first_steps")
        # -- the window ----------------------------------------------------
        master = hooks.master_stats()
        mark: Dict[str, Any] = {}

        def window_round(d, step):
            master_round(d, step)
            if clock() >= mark["t0"] + cell.seconds and "t1" not in mark:
                mark.update(t1=clock(), rounds=len(hooks.rows),
                            wait=master.recv_wait_s,
                            compiles=compiles.count)
                counts["end"] = member().route_stats
                d.request_stop("window closed")
        hooks.on_round = window_round
        with harness.settled(), traced:
            r0, wait0, c0 = len(hooks.rows), master.recv_wait_s, \
                compiles.count
            mark["t0"] = t0 = clock()
            history = job.fit()["history"]
        hooks.on_round = None
        window_rows = hooks.rows[r0:mark["rounds"]]
        window_losses = [h["loss"] for h in history[r0:mark["rounds"]]]
        rows_by_layer = [np.asarray(b) - np.asarray(a) for a, b in zip(
            counts["start"]["rows"], counts["end"]["rows"])]
        dropped = int(counts["end"]["dropped"])
        tokens = int(np.sum(window_rows)) * model.seq
        routed = float(sum(np.sum(r) for r in rows_by_layer)) / tokens
        moe = model.blocks[model.moe[0]]
        out.update(
            window_start=t0, window_s=mark["t1"] - t0,
            attempted=len(window_rows),
            failed=int(np.sum(~np.isfinite(window_losses))),
            samples=int(np.sum(window_rows)), steps=len(window_rows),
            exchange_wait_s=mark["wait"] - wait0,
            compiles_in_window=mark["compiles"] - c0,
            flops_per_sample=model.train_flops_per_sample(routed),
            expert_rows_window=[r.tolist() for r in rows_by_layer],
            expert_gmm={"d": model.dim, "f": int(moe["hidden"]),
                        "held": int(moe.get("held", moe["experts"]))})
        if traced.gmm_s is not None:
            out["expert_gmm"]["device_s"] = traced.gmm_s
        out["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    finally:
        job.shutdown()
    rows = hooks.kept[:later + 1]
    prog = {"losses": [h["loss"] for h in history[:later + 1]],
            "grads": {k: _host(_Norms.tree(v))
                      for k, v in norms.grads.items()},
            "delta": _host(_Norms.tree(norms.delta))}
    prog_routes = [routes[k] for k in range(later + 1)]
    del job, hooks, norms, routes, counts
    gc.collect()     # the parties' state is held in reference cycles
    # -- the reference, once the program's state is freed ------------------
    readings = _readings(model, weights, silos, rows, prog, prog_routes,
                         first, later, cell.control)
    readings["program"]["window_nonfinite_losses"] = out["failed"]
    readings["program"]["dropped_rows"] = dropped
    out["readings"] = readings
    return out


def _host(tree):
    return compare.tree_scale(tree, 1.0)


def _readings(model, w0, silos, rows, prog, prog_routes, first, later,
              control):
    """The program's readings against the reference, routed by the
    program's choices, and, where asked, the control's (the reference
    one precision below, routed by its own choices) and each fault's,
    planted in the reference put in the program's place."""
    def replay(routes=None, prec="highest", change=lambda b: b, **fault):
        """SGD over the compared rows: losses, gradient and change
        norms, the routes taken and the largest flip margin."""
        w, out = w0, {"losses": [], "grads": {}, "routes": [],
                      "margin": 0.0}
        for k, r in enumerate(rows):
            xm, (tokens,), y = change(_batch(silos, r))
            loss, g, w_new, used, margin = model.sgd_step(
                w, xm, tokens, y, None if routes is None else routes[k],
                prec, **fault)
            out["losses"].append(float(loss))
            out["routes"].append(used)
            out["margin"] = max(out["margin"], margin)
            if k in (0, later):
                # read as the program's is: from the float32 weights
                out["grads"][k] = _host(_diff_norms(w, w_new,
                                                    1.0 / model.lr))
            if k == first - 1:
                out["delta"] = _host(_diff_norms(w_new, w0, 1.0))
            del g
            w = w_new
        return out

    def against(run, ref):
        return {
            **compare.train_readings(
                run["losses"][:first], run["grads"][0], run["delta"],
                ref["losses"][:first], ref["grads"][0], ref["delta"]),
            **compare.window_readings(
                run["losses"][first:], {later: run["grads"][later]},
                ref["losses"][first:], {later: ref["grads"][later]}),
            "route_flip_margin": ref["margin"]}
    ref = replay(prog_routes)
    out = {"program": against(prog, ref)}
    if not control:
        return out

    def judged(run):
        """A run in the program's place, against the reference routed by
        the run's own choices."""
        return against(run, replay(run["routes"]))
    out["control"] = judged(replay(prec="high"))
    out["fault_route_swap"] = judged(replay(swap=True))
    out["fault_expert_zeroed"] = against(
        replay(prog_routes, zero_expert=0), ref)

    def altered(b):
        xm, (tokens,), y = b
        return xm, [tokens.at[0, 0].set((tokens[0, 0] + 1) % model.vocab)], y
    out["fault_altered_token"] = against(
        replay(prog_routes, change=altered), ref)
    return out


@jax.jit
def _diff_norms(a, b, scale: float):
    """Each leaf's norm of ``a - b`` (of ``a`` where ``b`` is None),
    times ``scale``, taken on the device in one program."""
    if b is None:
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))) * scale, a)
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))) * scale, a, b)
