"""Serving traffic: an open loop of single-user requests into the
federated server.

The schedule is fixed in advance from the seed: ``round(rate x
seconds)`` arrival times spread as a Poisson process over the window,
and a user for each, drawn Zipf(``zipf_alpha``) over the users the
parties share (popularity ranks shuffled by the seed) or uniformly.
Each request is timed from the moment it was due; one shed by
admission control counts as failed, as does one never answered. The
tails cover every request due in the window.

Set-up loads the weights, compiles every round size the batcher can
form (1 to ``max_batch`` distinct rows), and fills the members'
embedding cache with ``warm_requests`` requests drawn as the traffic
draws them (from another stream of the seed).

Traffic file keys: ``kind`` ("serve"), ``users`` ("zipf" or
"uniform"), ``zipf_alpha``, ``rate_per_s``, ``max_batch``,
``max_wait_ms``, ``admission_limit``, ``cache_rows``,
``warm_requests``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import compare, harness, program
from chipbench.silos import key_from_seed, make_silos

ANSWER_WAIT_S = 60.0       # how long past the close an answer may come


def schedule(traffic: Dict[str, Any], n_users: int, rate: float,
             seconds: float, rng: np.random.Generator):
    """(due offsets in seconds, sorted; user row of each request)."""
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return due, draw_users(traffic, n_users, n, rng)


def draw_users(traffic: Dict[str, Any], n_users: int, n: int,
               rng: np.random.Generator) -> np.ndarray:
    if traffic["users"] == "uniform":
        return rng.integers(0, n_users, n)
    if traffic["users"] != "zipf":
        raise ValueError(f"unknown user mix {traffic['users']!r}")
    weights = 1.0 / np.arange(1, n_users + 1) ** float(traffic["zipf_alpha"])
    cdf = np.cumsum(weights / weights.sum())
    order = rng.permutation(n_users)          # popularity rank -> user
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n)), n_users - 1)
    return order[ranks]


class Outcomes:
    """What became of each request, kept as numbers: a handle is
    released as soon as its answer is read, so that the run does not
    hold every request's objects."""

    OPEN, ANSWERED, SHED, FAILED = 0, 1, 2, 3

    def __init__(self, n: int):
        self.state = np.zeros(n, np.int8)
        self.t_done = np.zeros(n)
        self.scores: Dict[int, np.ndarray] = {}
        self.open: Dict[int, Any] = {}

    def add(self, i: int, handle) -> None:
        if handle is None:
            self.state[i] = self.SHED
        else:
            self.open[i] = handle

    def harvest(self) -> int:
        """Read every answered handle; the backlog left open."""
        for i in [i for i, h in self.open.items() if h.done.is_set()]:
            h = self.open.pop(i)
            if h.err is None:
                self.state[i], self.t_done[i] = self.ANSWERED, h.t_done
                self.scores[i] = h.scores
            else:
                self.state[i] = self.FAILED
        return len(self.open)

    def wait(self, deadline: float, clock) -> None:
        for h in list(self.open.values()):
            h.done.wait(max(0.0, deadline - clock()))
        self.harvest()


def offer(submit, due, users, t0: float, clock, shed_error):
    """Submit request ``i`` (user ``users[i]``) when it is due, at
    ``t0 + due[i]``, whatever became of the earlier ones. Returns the
    outcomes, how late each request was submitted (seconds), and the
    backlog (submitted, not yet answered) every 64 requests."""
    out = Outcomes(len(due))
    late = np.zeros(len(due))
    backlog = []
    for i in range(len(due)):
        at = t0 + due[i]
        delay = at - clock()
        if delay > 0:
            time.sleep(delay)
        try:
            with program.span("client.submit"):
                out.add(i, submit([int(users[i])]))
        except shed_error:
            out.add(i, None)
        now = clock()
        late[i] = now - at
        if i % 64 == 0:
            backlog.append((now - t0, out.harvest()))
    return out, late, backlog


def tally(out: Outcomes, due, users, t0: float, t_wait: float
          ) -> Dict[str, Any]:
    """Latency of every request from when it was due (ms). A request
    shed, failed or never answered by ``t_wait`` counts as failed, with
    the time it waited until then; the answered ones' users and scores
    are kept for the comparison."""
    out.harvest()
    answered = out.state == out.ANSWERED
    lat = np.where(answered, out.t_done, t_wait) - (t0 + np.asarray(due))
    shed = int(np.sum(out.state == out.SHED))
    idx = np.flatnonzero(answered)
    return {"attempted": len(due), "failed": len(due) - len(idx),
            "shed": shed, "unanswered": len(due) - len(idx) - shed,
            "latency_ms": lat * 1e3,
            "rows": np.asarray(users, np.int64)[idx],
            "scores": [out.scores[i] for i in idx]}


class Session:
    """One federation serving one configuration: set up once, then
    measured in one or more windows."""

    def __init__(self, cell, clock):
        import jax
        self.cell, self.clock = cell, clock
        cfg, self.traffic = cell.config, cell.traffic
        self.model = cell.reference.Model(cfg)
        key = key_from_seed(cell.seed)
        self.phases = program.Phases(clock)
        self.silos = make_silos(cfg["data"], jax.random.fold_in(key, 0))
        self.weights = self.model.init(jax.random.fold_in(key, 1))
        self.phases.mark("silos_and_weights")
        self.n_users = len(self.silos.common)
        self.job = self.server = None
        self.answers: List[Any] = []

    def open(self):
        """The federation up with the weights in, every round size
        compiled, the cache filled."""
        cell, traffic = self.cell, self.traffic
        self.hooks = program.make_hooks()(self.weights, spans=cell.trace)
        self.compiles = program.CompileCounter()
        self.job = job = program.make_job(program.vfl_config(
            cell.config, cell.seed, epochs=0,
            serve_cache_rows=int(traffic["cache_rows"])),
            self.silos, self.hooks)
        job.fit()                                  # loads the weights
        engine = program.SpannedEngine(job) if self.cell.trace else job
        self.server = program.make_server(engine, traffic).start()
        self.phases.mark("job")
        rng = np.random.default_rng([self.cell.seed, 1])
        distinct = rng.permutation(self.n_users)
        lo, top = 0, int(traffic["max_batch"])
        for k in range(1, top + 1):
            job.serve_query(rows=distinct[lo:lo + k])
            lo += k
        self.phases.mark("round_sizes")
        warm = draw_users(traffic, self.n_users,
                          int(traffic["warm_requests"]), rng)
        for i in range(0, len(warm), top):
            job.serve_query(rows=warm[i:i + top])
        self.phases.mark("cache_fill")

    def window(self, rate: float, seconds: float, seed_stream: int,
               tracer=None) -> Dict[str, Any]:
        """Offer the schedule for ``seconds`` at ``rate``; wait for the
        answers; the window's numbers."""
        server, clock = self.server, self.clock
        due, users = schedule(self.traffic, self.n_users, rate, seconds,
                              np.random.default_rng([self.cell.seed,
                                                     seed_stream]))
        with harness.settled(), tracer or program.nothing():
            snap0 = self._counters()
            t0 = clock() + 0.01
            outcomes, late, backlog = offer(
                server.submit, due, users, t0, clock,
                program.admission_error())
            rest = t0 + seconds - clock()
            if rest > 0:
                time.sleep(rest)
            t1 = clock()
            snap1 = self._counters()
        outcomes.wait(t1 + ANSWER_WAIT_S, clock)
        out = tally(outcomes, due, users, t0, clock())
        self.answers.append((out.pop("rows"), out.pop("scores")))
        half = [b for t, b in backlog if t < seconds / 2], \
            [b for t, b in backlog if t >= seconds / 2]
        return {
            **out, "window_start": t0, "window_s": t1 - t0, "rate": rate,
            "lateness_ms": late * 1e3,
            "backlog_halves": [float(np.mean(h)) if h else 0.0
                               for h in half],
            "backlog_half_medians": [float(np.median(h)) if h else 0.0
                                     for h in half],
            **{k: snap1[k] - snap0[k] for k in snap0}}

    def _counters(self) -> Dict[str, float]:
        s = self.server.stats
        hits, misses = self.hooks.embed_cache()
        return {"completed": s.requests, "queue_s": s.queue_s,
                "rounds": s.batches, "rows_wire": s.rows_wire,
                "cache_hits": hits, "cache_misses": misses,
                "compiles_in_window": self.compiles.count}

    def close(self) -> int:
        """Close the session and free the federation; the peak memory
        it used."""
        try:
            if self.server is not None:
                self.server.stop()
            peak = harness.memory_peak_bytes(self.cell.chips)
        finally:
            if self.job is not None:
                self.job.shutdown()
        self.job = self.server = self.hooks = None
        return peak

    def readings(self) -> Dict[str, Dict[str, float]]:
        """Every answer of every window against the reference (and the
        control, where asked), in blocks of rows."""
        rows = np.concatenate([r for r, _ in self.answers])
        served = np.concatenate([np.concatenate(s, 0) if s else
                                 np.zeros((0, 1)) for _, s in self.answers])
        ref = self._reference(rows, "highest")
        out = {"program": {"score_gap": compare.score_gap(served, ref)}}
        if self.cell.control:
            out.update(self.control_readings(rows, ref))
        return out

    def control_readings(self, rows: np.ndarray, ref=None
                         ) -> Dict[str, Dict[str, float]]:
        """The control (the reference one precision below) and each
        fault of an answer, planted in the reference put in the
        program's place, over ``rows``."""
        ref = self._reference(rows, "highest") if ref is None else ref
        altered = ref.copy()
        altered[0, 0] += 1.0
        return {
            "control": {"score_gap": compare.score_gap(
                self._reference(rows, "high"), ref)},
            "fault_altered_answer": {
                "score_gap": compare.score_gap(altered, ref)},
            "fault_no_exchange": {"score_gap": compare.score_gap(
                self._reference(rows, "highest", members=False), ref)}}

    def _reference(self, rows: np.ndarray, prec: str,
                   block: int = 8192, members: bool = True) -> np.ndarray:
        import jax
        silos, model = self.silos, self.model
        fwd = jax.jit(model.logits, static_argnums=(3,))
        parts = []
        for i in range(0, len(rows), block):
            mrows = silos.common[rows[i:i + block]]
            xbs = [x[silos.member_index(j, mrows)]
                   for j, x in enumerate(silos.member_x)] if members else []
            parts.append(np.asarray(fwd(self.weights, silos.master_x[mrows],
                                        xbs, prec)))
        return np.concatenate(parts, 0) if parts else np.zeros((0, 1))


def run(cell, tracer, clock) -> Dict[str, Any]:
    s = Session(cell, clock)
    try:
        s.open()
        out = s.window(float(cell.traffic["rate_per_s"]), cell.seconds, 2,
                       tracer)
    finally:
        peak = s.close()
    out["memory_peak_bytes"] = peak
    out["setup_phases"] = s.phases.seconds
    out["readings"] = s.readings()
    out["readings"]["program"]["unanswered"] = out["unanswered"]
    return out
