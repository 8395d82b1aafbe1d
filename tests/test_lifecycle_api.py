"""Lifecycle API (driver + typed schema + VFLJob): ported protocols must
reproduce the recorded seed traces bit-for-bit across execution modes,
callbacks fire in order, checkpoint/resume is deterministic mid-epoch,
predict round-trips without retraining, agent failures propagate with
real tracebacks, and tail batches are no longer silently dropped."""
import dataclasses
import json
import os
import pathlib
import re
import threading
import time

import numpy as np
import pytest

from repro.comm import schema
from repro.comm.base import CommCfg
from repro.comm.local import ThreadBus
from repro.comm.schema import Field, SchemaError, TypedChannel
from repro.core.party import VFLJob, run_vfl
from repro.core.protocols.base import (VFLConfig, batch_bounds, batches)
from repro.core.protocols.driver import (Callback, Checkpointer,
                                         EarlyStopping, EvalEveryEpoch,
                                         MetricsStream, StopAtStep)
from repro.core.protocols.linreg import LinRegProtocol
from repro.data.vertical import vertical_partition

TRACES = json.loads(
    (pathlib.Path(__file__).parent / "fixtures" / "seed_traces.json")
    .read_text())


def _dataset(n=192, d=12, items=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=(d, items))
    y = x @ w * 0.4 + rng.normal(scale=0.05, size=(n, items))
    ids = [f"u{i:05d}" for i in range(n)]
    return ids, x, y


def _linreg_case():
    ids, x, y = _dataset()
    master, members = vertical_partition(ids, x, y, widths=[4, 3],
                                         overlap=1.0, seed=1)
    cfg = VFLConfig(protocol="linreg", epochs=3, batch_size=48, lr=0.1,
                    seed=0, use_psi=False)
    return cfg, master, members


def _logreg_case():
    ids, x, y = _dataset(n=64, d=8, items=1)
    yb = (y > 0).astype(np.float64)
    master, members = vertical_partition(ids, x, yb, widths=[3], seed=4)
    cfg = VFLConfig(protocol="logreg_he", epochs=1, batch_size=32, lr=0.5,
                    seed=0, use_psi=False, he_bits=256)
    return cfg, master, members


def _splitnn_case():
    ids, x, y = _dataset(n=128, d=12, items=3)
    yb = (y > 0).astype(np.float64)
    master, members = vertical_partition(ids, x, yb, widths=[5], seed=3)
    cfg = VFLConfig(protocol="split_nn", epochs=3, batch_size=32, lr=0.1,
                    seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    return cfg, master, members


# ---------------------------------------------------------------------------
# ported protocols == recorded seed traces, across modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["thread", "socket", "process"])
def test_linreg_matches_seed_trace(mode):
    """The lifecycle port must change ZERO arithmetic: losses and every
    weight slice equal the monolithic role functions' recorded trace."""
    cfg, master, members = _linreg_case()
    res = run_vfl(cfg, master, members, mode=mode)
    got = [h["loss"] for h in res["master"]["history"]]
    np.testing.assert_allclose(got, TRACES["linreg"]["losses"],
                               rtol=0, atol=0)
    np.testing.assert_allclose(res["master"]["w_master"],
                               TRACES["linreg"]["w_master"], rtol=0, atol=0)
    for j in range(2):
        np.testing.assert_allclose(res[f"member{j}"]["w"],
                                   TRACES["linreg"]["w_members"][j],
                                   rtol=0, atol=0)


@pytest.mark.parametrize("mode", ["thread", "socket"])
def test_logreg_he_matches_seed_trace(mode):
    cfg, master, members = _logreg_case()
    res = run_vfl(cfg, master, members, mode=mode)
    got = [h["loss"] for h in res["master"]["history"]]
    np.testing.assert_allclose(got, TRACES["logreg_he"]["losses"],
                               rtol=0, atol=0)
    np.testing.assert_allclose(res["member0"]["w"],
                               TRACES["logreg_he"]["w_members"][0],
                               rtol=0, atol=0)


@pytest.mark.parametrize("mode", ["thread", "socket"])
def test_split_nn_matches_seed_trace(mode):
    cfg, master, members = _splitnn_case()
    res = run_vfl(cfg, master, members, mode=mode)
    got = [h["loss"] for h in res["master"]["history"]]
    np.testing.assert_allclose(got, TRACES["split_nn"]["losses"],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# callbacks
# ---------------------------------------------------------------------------


class _Recorder(Callback):
    def __init__(self):
        self.events = []

    def _rec(self, driver, kind, *detail):
        if driver.role == "master":
            self.events.append((kind,) + detail)

    def on_fit_start(self, driver):
        self._rec(driver, "fit_start")

    def on_epoch_start(self, driver, epoch):
        self._rec(driver, "epoch_start", epoch)

    def on_batch_end(self, driver, step, epoch, loss):
        self._rec(driver, "batch_end", step)

    def on_epoch_end(self, driver, epoch):
        self._rec(driver, "epoch_end", epoch)

    def on_fit_end(self, driver):
        self._rec(driver, "fit_end")


def test_callback_invocation_order():
    cfg, master, members = _linreg_case()
    cfg = dataclasses.replace(cfg, epochs=2)
    rec = _Recorder()
    run_vfl(cfg, master, members, callbacks=[rec])
    want = [("fit_start",)]
    step = 0
    for epoch in range(2):
        want.append(("epoch_start", epoch))
        for _ in range(4):          # 192 / 48
            want.append(("batch_end", step))
            step += 1
        want.append(("epoch_end", epoch))
    want.append(("fit_end",))
    assert rec.events == want


def test_metrics_stream_and_early_stop():
    cfg, master, members = _linreg_case()
    ms = MetricsStream()
    res = run_vfl(cfg, master, members,
                  callbacks=[ms, EarlyStopping(patience=2,
                                               min_delta=10.0)])
    # min_delta=10 means nothing beats the first round's loss: stop
    # after `patience` further rounds
    assert len(res["master"]["history"]) == 3
    assert "early-stop" in res["master"]["stopped"]
    assert [r["step"] for r in ms.rows] == [0, 1, 2]
    assert all(r["sent_bytes"] > 0 for r in ms.rows)


def test_eval_every_epoch_streams_metrics():
    cfg, master, members = _logreg_case()
    res = run_vfl(cfg, master, members, callbacks=[EvalEveryEpoch()])
    ev = res["master"]["eval_history"]
    assert len(ev) == 1 and ev[0]["epoch"] == 0
    assert 0.0 <= ev[0]["auc"] <= 1.0 and ev[0]["logloss"] > 0
    # the mid-fit eval must not perturb training
    np.testing.assert_allclose(
        [h["loss"] for h in res["master"]["history"]],
        TRACES["logreg_he"]["losses"], rtol=0, atol=0)


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case,stop_at,atol", [
    (_linreg_case, 5, 0.0),        # mid-epoch-2 (4 steps/epoch)
    (_splitnn_case, 6, 1e-5),      # f32 state round-trips through numpy
])
def test_checkpoint_resume_mid_epoch(case, stop_at, atol, tmp_path):
    cfg, master, members = case()
    ref = run_vfl(cfg, master, members)
    job = VFLJob(cfg, master, members,
                 callbacks=[Checkpointer(tmp_path, every_steps=1),
                            StopAtStep(stop_at)])
    r1 = job.fit()
    job.shutdown()
    assert len(r1["history"]) == stop_at and r1["stopped"]

    job2 = VFLJob(cfg, master, members, resume_dir=tmp_path)
    r2 = job2.fit()
    res2 = job2.shutdown()
    ref_losses = [h["loss"] for h in ref["master"]["history"]]
    np.testing.assert_allclose([h["loss"] for h in r2["history"]],
                               ref_losses, rtol=0, atol=atol)
    if cfg.protocol == "linreg":
        np.testing.assert_allclose(res2["master"]["w_master"],
                                   ref["master"]["w_master"],
                                   rtol=0, atol=0)
        np.testing.assert_allclose(res2["member0"]["w"],
                                   ref["member0"]["w"], rtol=0, atol=0)


def test_checkpoint_resume_pipelined_with_compression(tmp_path):
    """Resume under ``pipeline_depth=2`` with compression on: the
    checkpoint must carry the error-feedback residuals and the typed
    channel's sequence numbers, so the resumed federation rejoins the
    stream without desync and reproduces the uninterrupted depth-2
    trace."""
    import dataclasses
    cfg, master, members = _splitnn_case()
    cfg = dataclasses.replace(cfg, compress=True)
    ref = run_vfl(cfg, master, members, pipeline_depth=2)
    job = VFLJob(cfg, master, members, pipeline_depth=2,
                 callbacks=[Checkpointer(tmp_path, every_steps=1),
                            StopAtStep(6)])
    r1 = job.fit()
    job.shutdown()
    # the stop request lands with up to depth-1 extra rounds already
    # announced; the master completes every announced round
    assert 6 <= len(r1["history"]) <= 7 and r1["stopped"]

    job2 = VFLJob(cfg, master, members, pipeline_depth=2,
                  resume_dir=tmp_path)
    r2 = job2.fit()
    job2.shutdown()
    got = [h["loss"] for h in r2["history"]]
    want = [h["loss"] for h in ref["master"]["history"]]
    assert len(got) == len(want)
    # the checkpointed prefix is exact; past the cut the member's EF
    # residual legitimately includes the quantization of the round that
    # was in flight at save time, so the continuation tracks the
    # uninterrupted trace tightly but not bit-for-bit
    np.testing.assert_allclose(got[:7], want[:7], rtol=0, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)
    assert got[-1] < got[0]


# ---------------------------------------------------------------------------
# predict / evaluate phase
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case,metric", [
    (_linreg_case, "mse"),
    (_logreg_case, "auc"),
    (_splitnn_case, "auc"),
])
def test_predict_roundtrip(case, metric):
    """train -> predict -> metrics on live agents, no retraining."""
    cfg, master, members = case()
    job = VFLJob(cfg, master, members)
    fit = job.fit()
    steps = len(fit["history"])
    s1 = job.predict()
    s2 = job.predict()
    ev = job.evaluate()
    res = job.shutdown()
    n = res["master"]["n_common"]
    assert s1.shape[0] == n
    np.testing.assert_allclose(s1, s2, rtol=0, atol=0)   # serving is pure
    assert len(res["master"]["history"]) == steps        # no extra steps
    assert metric in ev
    if metric == "auc":
        assert ev["auc"] > 0.55                          # actually learned
    assert res["master"]["phase_s"].get("predict", 0) > 0
    ppb = res["master"]["comm"]["per_phase_bytes"]
    assert ppb["match"] > 0 and ppb["fit"] > 0 and ppb["predict"] > 0


def test_predict_row_subset():
    cfg, master, members = _linreg_case()
    with VFLJob(cfg, master, members) as job:
        job.fit()
        full = job.predict()
        sub = job.predict(rows=np.arange(10, 30))
        np.testing.assert_allclose(sub, full[10:30], rtol=0, atol=0)


def test_secure_agg_predict_masks_cancel():
    """Members mask predict-query activations too (the master only ever
    sees the aggregate); pairwise masks cancel in the sum, so scores
    match the unmasked run."""
    ids, x, y = _dataset(n=96, items=2)
    yb = (y > 0).astype(np.float64)
    master, members = vertical_partition(ids, x, yb, widths=[4, 4],
                                         seed=7)
    cfg = VFLConfig(protocol="split_nn", epochs=2, batch_size=32, lr=0.1,
                    seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    with VFLJob(cfg, master, members) as plain_job:
        plain_job.fit()
        plain = plain_job.predict()
    sec_cfg = dataclasses.replace(cfg, secure_agg=True)
    with VFLJob(sec_cfg, master, members) as sec_job:
        sec_job.fit()
        sec1 = sec_job.predict()
        sec2 = sec_job.predict()
    np.testing.assert_allclose(sec1, plain, rtol=1e-3, atol=1e-3)
    # distinct mask streams per query, still canceling
    np.testing.assert_allclose(sec2, sec1, rtol=1e-3, atol=1e-3)


def test_followers_survive_idle_between_phases():
    """A live job can sit idle between fit and predict far longer than
    the transports' per-message timeout; followers must keep waiting for
    the next phase announcement instead of dying."""
    import threading

    from repro.core.party import PartyMaster, PartyMember

    cfg, master_data, member_datas = _linreg_case()
    bus = ThreadBus(["master", "member0", "member1"])
    comms = {w: bus.communicator(w) for w in bus.world}
    for c in comms.values():
        c._timeout = 0.3                   # transport times out fast
    out = {}

    def run_member(name):
        out[name] = PartyMember(comms[name], cfg).serve(member_datas[
            int(name.replace("member", ""))])

    threads = [threading.Thread(target=run_member, args=(m,), daemon=True)
               for m in ("member0", "member1")]
    for t in threads:
        t.start()
    pm = PartyMaster(comms["master"], cfg)
    pm.fit(master_data)
    time.sleep(1.0)                        # idle >> transport timeout
    scores = pm.predict()
    pm.shutdown()
    for t in threads:
        t.join(timeout=60)
    assert scores.shape[0] == pm.driver.n
    assert "w" in out["member0"] and "w" in out["member1"]


def test_call_after_shutdown_fails_fast():
    cfg, master, members = _linreg_case()
    job = VFLJob(cfg, master, members)
    job.fit()
    job.shutdown()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="already shut down"):
        job.predict()
    assert time.monotonic() - t0 < 5


def test_explicit_role_objects():
    """The deployment-style API: you own the transports, one agent per
    host — PartyMaster drives phases directly, members/arbiter serve."""
    import threading

    from repro.core.party import Arbiter, PartyMaster, PartyMember

    cfg, master_data, member_datas = _logreg_case()
    bus = ThreadBus(["master", "member0", "arbiter"])
    out = {}

    def run_member():
        out["member0"] = PartyMember(bus.communicator("member0"),
                                     cfg).serve(member_datas[0])

    def run_arbiter():
        out["arbiter"] = Arbiter(bus.communicator("arbiter"), cfg).serve()

    threads = [threading.Thread(target=run_member, daemon=True),
               threading.Thread(target=run_arbiter, daemon=True)]
    for t in threads:
        t.start()
    pm = PartyMaster(bus.communicator("master"), cfg)
    fit = pm.fit(master_data)
    scores = pm.predict()
    res = pm.shutdown()
    for t in threads:
        t.join(timeout=60)
    np.testing.assert_allclose([h["loss"] for h in fit["history"]],
                               TRACES["logreg_he"]["losses"],
                               rtol=0, atol=0)
    assert scores.shape == (res["n_common"], 1)
    assert "w" in out["member0"] and "decrypted_values" in out["arbiter"]


# ---------------------------------------------------------------------------
# failure propagation (regression: process mode used to block 600s and
# die with queue.Empty when an agent crashed)
# ---------------------------------------------------------------------------


class FailingMemberProtocol(LinRegProtocol):
    name = "failing_member"

    def setup(self):
        if self.is_member:
            raise RuntimeError("deliberate member failure")
        super().setup()


@pytest.mark.parametrize("mode", ["thread", "process"])
def test_agent_failure_propagates_fast(mode):
    cfg, master, members = _linreg_case()
    cfg = dataclasses.replace(
        cfg, protocol="test_lifecycle_api:FailingMemberProtocol")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as ei:
        run_vfl(cfg, master, members, mode=mode)
    assert time.monotonic() - t0 < 120        # far below the 600s hang
    assert "deliberate member failure" in str(ei.value.__cause__)


class ExitingMemberProtocol(LinRegProtocol):
    """A member whose process ends in setup with nothing posted."""
    name = "exiting_member"

    def setup(self):
        if self.is_member:
            os._exit(7)
        super().setup()


class _DiesWhilePosted(RuntimeError):
    """An error of 1 MiB of text, more than a pipe holds, whose process
    ends 50 ms after the text is first asked for: that is, part way
    through posting it to the job."""

    def __str__(self):
        threading.Timer(0.05, os._exit, (7,)).start()
        return "x" * (1 << 20)


class DyingWhilePostingMemberProtocol(LinRegProtocol):
    name = "dying_while_posting_member"

    def setup(self):
        if self.is_member:
            raise _DiesWhilePosted()
        super().setup()


@pytest.mark.parametrize("proto", ["ExitingMemberProtocol",
                                   "DyingWhilePostingMemberProtocol"])
def test_dead_agent_ends_the_wait_attributed(proto):
    """An agent process that ends without posting, or part way through
    posting, leaves the job's pipe at EOF: ``fit`` raises an attributed
    error within seconds, where a channel whose write end the job also
    held left its read blocked on the missing rest for good."""
    cfg, master, members = _linreg_case()
    cfg = dataclasses.replace(cfg, protocol=f"test_lifecycle_api:{proto}")
    out = {}

    def run():
        try:
            run_vfl(cfg, master, members, mode="process",
                    comm_cfg=CommCfg(timeout=10.0))
        except BaseException as e:
            out["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=60)       # the comm timeout and the agents' spawn
    assert not t.is_alive(), "the job still waits on its dead member"
    err = out["err"]
    assert re.fullmatch(r"agent member[01] failed", str(err)), err
    why = str(err.__cause__)[:2000]
    assert "exit code 7" in why or "_DiesWhilePosted" in why, why


# ---------------------------------------------------------------------------
# tail batches (regression: batches() silently dropped up to
# batch_size-1 matched samples per epoch)
# ---------------------------------------------------------------------------


def test_batch_bounds_cover_all_samples():
    cfg = VFLConfig(batch_size=16)
    b = batch_bounds(70, cfg)
    assert b[-1] == (64, 70)                       # tail kept
    assert sum(hi - lo for lo, hi in b) == 70
    rows = np.concatenate(list(batches(70, cfg, epoch=0)))
    assert sorted(rows.tolist()) == list(range(70))
    b2 = batch_bounds(70, dataclasses.replace(cfg, drop_last=True))
    assert b2[-1] == (48, 64)                      # old behaviour, opt-in
    assert batch_bounds(64, cfg) == batch_bounds(
        64, dataclasses.replace(cfg, drop_last=True))


def test_tail_batch_modes_agree():
    """Every party and every mode derives the identical tail batch."""
    ids, x, y = _dataset(n=100)
    master, members = vertical_partition(ids, x, y, widths=[4],
                                         overlap=1.0, seed=2)
    cfg = VFLConfig(protocol="linreg", epochs=2, batch_size=48, lr=0.1,
                    seed=0, use_psi=False)
    ref = run_vfl(cfg, master, members, mode="thread")
    assert len(ref["master"]["history"]) == 2 * 3  # 48+48+4 per epoch
    got = run_vfl(cfg, master, members, mode="socket")
    np.testing.assert_allclose(
        [h["loss"] for h in got["master"]["history"]],
        [h["loss"] for h in ref["master"]["history"]], rtol=0, atol=0)
    np.testing.assert_allclose(got["member0"]["w"], ref["member0"]["w"],
                               rtol=0, atol=0)
    # centralized reference with the same batching matches exactly
    w = np.zeros((x.shape[1], y.shape[1]))
    losses = []
    for epoch in range(cfg.epochs):
        for rows in batches(100, cfg, epoch):
            z = x[rows] @ w
            r = (z - y[rows]) / len(rows)
            losses.append(float(0.5 * np.mean((z - y[rows]) ** 2)))
            w -= cfg.lr * (x[rows].T @ r)
    np.testing.assert_allclose(
        [h["loss"] for h in ref["master"]["history"]], losses, rtol=1e-10)


# ---------------------------------------------------------------------------
# typed message schema
# ---------------------------------------------------------------------------

schema.message("t/plain", {"x": Field("float64", 2)})
schema.message("t/stepped", {"x": Field("float64", 1)}, stepped=True)
schema.message("t/wide", {"c": Field("uint8", 2, width_meta="width")})


def _pair():
    bus = ThreadBus(["master", "member0"])
    return (TypedChannel(bus.communicator("master")),
            TypedChannel(bus.communicator("member0")))


def test_schema_rejects_bad_payloads():
    a, _ = _pair()
    with pytest.raises(SchemaError, match="unregistered"):
        a.send("member0", "t/unknown", {"x": np.zeros((2, 2))})
    with pytest.raises(SchemaError, match="fields"):
        a.send("member0", "t/plain", {"y": np.zeros((2, 2))})
    with pytest.raises(SchemaError, match="dtype"):
        a.send("member0", "t/plain", {"x": np.zeros((2, 2), np.float32)})
    with pytest.raises(SchemaError, match="rank"):
        a.send("member0", "t/plain", {"x": np.zeros(3)})


def test_schema_width_validated_at_decode():
    a, b = _pair()
    a.send("member0", "t/wide", {"c": np.zeros((4, 64), np.uint8)},
           meta={"width": "64"})
    assert b.recv("master", "t/wide").tensor("c").shape == (4, 64)
    # sender-side check trips on a mismatched declaration
    with pytest.raises(SchemaError, match="width"):
        a.send("member0", "t/wide", {"c": np.zeros((4, 64), np.uint8)},
               meta={"width": "128"})


def test_schema_auto_steps_sequence_numbers():
    a, b = _pair()
    for i in range(3):
        a.send("member0", "t/stepped", {"x": np.full(2, float(i))})
    for i in range(3):
        msg = b.recv("master", "t/stepped")
        assert msg.tag == f"t/stepped/{i}"
        assert msg.tensor("x")[0] == i
    # non-stepped tags don't accumulate a counter
    a.send("member0", "t/plain", {"x": np.zeros((1, 1))})
    assert b.recv("master", "t/plain").tag == "t/plain"


def test_schema_conflicting_redeclaration_rejected():
    schema.message("t/redecl", {"x": Field("float64")})
    schema.message("t/redecl", {"x": Field("float64")})   # idempotent ok
    with pytest.raises(SchemaError, match="redeclaration"):
        schema.message("t/redecl", {"x": Field("float32")})
