"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here:
``VFLJob`` in ``thread`` mode (every party in this one process, which
holds the chip), the split-NN protocol configured from the
configuration file, the serving engine, and the driver hooks through
which the benchmark loads its own weights, reads the parties' counters
and records host spans. The program is imported only once the chip has
been found (its package lives in ``src/`` of the checkout).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from chipbench.silos import Silos


def _repro():
    from repro.core import party
    from repro.core.protocols import base, driver
    from repro.serve import federated
    return party, base, driver, federated


def vfl_config(cfg: Dict[str, Any], seed: int, epochs: int, **kw):
    """The program's configuration of the split-NN job the config file
    describes (``kw``: further ``VFLConfig`` fields)."""
    _, base, _, _ = _repro()
    return base.VFLConfig(
        protocol="split_nn", epochs=epochs,
        batch_size=int(cfg["batch_size"]), lr=float(cfg["lr"]),
        seed=int(seed) & 0xFFFFFFFF, use_psi=False,
        embedding_dim=int(cfg["embedding_dim"]),
        tower=tuple(cfg["towers"]["bottom"]),
        top_tower=tuple(cfg["towers"]["top"]), **kw)


def party_data(silos: Silos):
    """Master and member data as the program takes them: string ids and
    host arrays (each party moves its matched rows to the device)."""
    _, base, _, _ = _repro()
    master = base.MasterData(silos.ids, np.asarray(silos.labels),
                             np.asarray(silos.master_x))
    members = [base.MemberData([silos.ids[i] for i in rows], np.asarray(x))
               for rows, x in zip(silos.member_rows, silos.member_x)]
    return master, members


def span(name: str):
    """A host span in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name)


def _spanned(name: str, fn: Callable) -> Callable:
    def call(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return call


def make_hooks():
    _, _, driver, _ = _repro()

    class Hooks(driver.Callback):
        """Driver callbacks on every party.

        At the first fit phase each party loads the benchmark's
        weights (its checkpoint API) and its driver is kept, for the
        counters. ``on_round(driver, step)`` is called after each
        master round; ``on_member_round`` after each member round.
        With ``spans`` on, the protocol hooks run inside host spans.
        """

        def __init__(self, weights, spans: bool = False):
            self.weights = weights
            self.spans = spans
            self.drivers: Dict[str, Any] = {}
            self.on_round: Optional[Callable] = None
            self.on_member_round: Optional[Callable] = None
            self.on_epoch: Optional[Callable] = None
            self.rows: List[int] = []          # rows of each master round
            self.keep_rows = 0                 # keep the first N rows arrays
            self.kept: List[np.ndarray] = []

        def on_fit_start(self, d):
            if d.role in self.drivers:
                return
            self.drivers[d.role] = d
            proto, w = d.proto, self.weights
            if d.role == "master":
                proto.load_state_dict({"top": w["top"],
                                       "bottom": w["bottom"]})
                inner = proto.on_batch_master

                def on_batch_master(rows, step):
                    self.rows.append(len(rows))
                    if len(self.kept) < self.keep_rows:
                        self.kept.append(np.array(rows))
                    return inner(rows, step)
                proto.on_batch_master = on_batch_master
                if self.spans:
                    proto.on_batch_master = _spanned(
                        "master.on_batch_master", on_batch_master)
                    proto.predict_master = _spanned(
                        "master.predict_master", proto.predict_master)
            else:
                j = int(d.role[len("member"):])
                proto.load_state_dict({"params": w["members"][j]})
                if self.spans:
                    for hook in ("member_stage_send", "member_stage_recv",
                                 "predict_embed", "send_embed"):
                        setattr(proto, hook, _spanned(
                            f"{d.role}.{hook}", getattr(proto, hook)))

        def on_batch_end(self, d, step, epoch, loss):
            if d.role == "master":
                if self.on_round is not None:
                    self.on_round(d, step)
            elif self.on_member_round is not None:
                self.on_member_round(d, step)

        def on_epoch_end(self, d, epoch):
            if d.role == "master" and self.on_epoch is not None:
                self.on_epoch(d, epoch)

        # -- what the parties hold ------------------------------------------
        def params(self) -> Dict[str, Any]:
            """Every party's current weights, in the benchmark's layout
            (references to the arrays, no copy)."""
            m = self.drivers["master"].proto
            members = sorted((r for r in self.drivers if r != "master"),
                             key=lambda r: int(r[len("member"):]))
            return {"bottom": m.bottom, "top": m.top,
                    "members": [self.drivers[r].proto.params
                                for r in members]}

        def master_stats(self):
            return self.drivers["master"].ch.stats

        def embed_cache(self):
            """Hit and miss counts of the members' serving caches."""
            hits = misses = 0
            for role, d in self.drivers.items():
                cache = getattr(d, "_embed_cache", None)
                if role != "master" and cache is not None:
                    hits, misses = hits + cache.hits, misses + cache.misses
            return hits, misses

    return Hooks


def make_job(cfg, silos: Silos, hooks):
    party, _, _, _ = _repro()
    master, members = party_data(silos)
    return party.VFLJob(cfg, master, members, mode="thread",
                        callbacks=[hooks])


class SpannedEngine:
    """The serving engine with each federated round in a host span."""

    def __init__(self, job):
        self.job = job

    def serve_open(self):
        return self.job.serve_open()

    def serve_query(self, rows):
        with span("serve.round"):
            return self.job.serve_query(rows=rows)

    def serve_close(self):
        return self.job.serve_close()


def make_server(engine, traffic: Dict[str, Any]):
    _, _, _, federated = _repro()
    return federated.FederatedServer(engine, federated.ServeCfg(
        max_batch=int(traffic["max_batch"]),
        max_wait_ms=float(traffic["max_wait_ms"]),
        admission_limit=int(traffic["admission_limit"])))


def admission_error():
    return _repro()[3].AdmissionError


class CompileCounter:
    """Counts XLA compilations (JAX's own monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += secs


class Phases:
    """Seconds of each named step of set-up, for the notes."""

    def __init__(self, clock):
        self.clock, self.seconds = clock, {}
        self._t = clock()

    def mark(self, name: str) -> None:
        t = self.clock()
        self.seconds[name] = t - self._t
        self._t = t


@contextlib.contextmanager
def nothing():
    yield

