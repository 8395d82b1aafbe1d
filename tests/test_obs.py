"""Program spans (``repro.obs``): switched off, a split-NN fit and a
serve round make no profiler annotation at all; switched on, under a
CPU profiler trace of the same job, every span site appears with its
party's prefix, the host<->device and dispatch spans fall inside the
protocol hook that calls them, and the channel's wait spans add up to
what ``CommStats`` counted."""
import glob
import os
import time
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.party import VFLJob
from repro.core.protocols.base import VFLConfig
from repro.core.protocols.driver import Callback
from repro.data.vertical import vertical_partition
from repro.serve.federated import FederatedServer, ServeCfg

SITES = ("recv_wait", "decode", "encode", "h2d", "gather", "step", "d2h")
HOOKS = ("on_batch_master", "predict_master", "member_stage_send",
         "member_stage_recv", "predict_embed")


def _case():
    rng = np.random.default_rng(0)
    n, d = 96, 12
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=(d, 2)) > 0).astype(np.float64)
    ids = [f"u{i:05d}" for i in range(n)]
    master, members = vertical_partition(ids, x, y, widths=[5], seed=3)
    cfg = VFLConfig(protocol="split_nn", epochs=2, batch_size=32, lr=0.1,
                    seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    return cfg, master, members


class _Drivers(Callback):
    """Keeps each party's driver; with ``wrap``, runs the protocol
    hooks inside profiler spans named ``hook.<role>.<hook>``."""

    def __init__(self, wrap: bool):
        self.wrap = wrap
        self.drivers = {}

    def on_fit_start(self, d):
        if d.role in self.drivers:
            return
        self.drivers[d.role] = d
        for name in HOOKS if self.wrap else ():
            if hasattr(d.proto, name):
                setattr(d.proto, name,
                        _spanned(f"hook.{d.role}.{name}",
                                 getattr(d.proto, name)))


def _spanned(name, fn):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


def _fit_and_serve(wrap: bool) -> _Drivers:
    cfg, master, members = _case()
    drivers = _Drivers(wrap)
    with VFLJob(cfg, master, members, mode="thread",
                callbacks=[drivers]) as job:
        job.fit()
        # a partial round held open up to max_wait_ms, then the
        # batcher idles on the empty queue until stop
        with FederatedServer(job, ServeCfg(max_batch=64,
                                           max_wait_ms=5.0)) as server:
            scores = server.query([1, 2, 3])
            time.sleep(0.06)
        assert scores.shape == (3, 2)
        job.shutdown()
    return drivers


def test_spans_off_make_no_annotation(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a span was made with spans off")
    obs.enable(True)          # the profiler module is bound from here on
    obs.enable(False)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    drivers = _fit_and_serve(wrap=False)
    assert drivers.drivers["master"].global_step > 0
    assert obs.span("master.d2h", step=3) is obs.span("serve.batcher.take")


def _host_spans(path):
    """(name, thread, start_ns, end_ns, stats) of every host event."""
    from jax.profiler import ProfileData
    out = []
    with warnings.catch_warnings():
        # reading an event's stats warns of the binding's type name
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    out += [(e.name, (plane.name, i), e.start_ns,
                             e.end_ns, dict(e.stats)) for e in line.events]
    return out


@pytest.fixture
def traced(tmp_path):
    """The job's host spans under a profiler trace, spans switched on."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.enable(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        drivers = _fit_and_serve(wrap=True)
    finally:
        jax.profiler.stop_trace()
        obs.enable(False)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    return drivers, _host_spans(path)


def test_spans_on_name_every_site_by_party(traced):
    _, spans = traced
    names = {s[0] for s in spans}
    want = {f"{p}.{site}" for p in ("master", "member0") for site in SITES}
    want |= {"master.round"} | {f"serve.batcher.{s}" for s in
                                ("take", "idle", "hold", "finish")}
    assert want <= names, sorted(want - names)


def test_device_spans_fall_inside_their_hook(traced):
    _, spans = traced
    hooks = {}
    for name, line, a, b, _ in spans:
        if name.startswith("hook."):
            hooks.setdefault(line, []).append((name, a, b))
    inner = [s for s in spans if s[0].split(".")[-1] in ("d2h", "h2d",
                                                          "step")]
    assert len(inner) > 20
    for name, line, a, b, _ in inner:
        party = name.split(".")[0]
        assert any(h.startswith(f"hook.{party}.") and ha <= a and b <= hb
                   for h, ha, hb in hooks.get(line, [])), name


def test_recv_wait_spans_agree_with_comm_stats(traced):
    drivers, spans = traced
    for role, d in drivers.drivers.items():
        total = sum(s[3] - s[2] for s in spans
                    if s[0] == f"{role}.recv_wait") * 1e-9
        counted = d.ch.stats.recv_wait_s
        assert counted > 0
        assert total == pytest.approx(counted, rel=0.05), role


def test_round_spans_carry_the_step(traced):
    """One ``master.round`` per training step, and the member's spans
    of a round carry the same step, which links the two threads."""
    drivers, spans = traced
    steps = list(range(drivers.drivers["master"].global_step))
    assert len(steps) > 1
    assert sorted(s[4]["step"] for s in spans
                  if s[0] == "master.round") == steps
    assert sorted(s[4]["step"] for s in spans
                  if s[0] == "member0.h2d") == steps
