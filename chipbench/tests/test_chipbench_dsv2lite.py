"""The DeepSeek-V2-Lite training cell's functions on the CPU at a test
size: the run is correct and reports its metrics, the planted faults in
the program and the control come out not correct, the token silo, and
the grouped product's roofline counting."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import train_seq
from chipbench.tests.tiny import FAKE_DEVICE
from chipbench.tests.tiny_seq import tiny_seq_cell
from chipbench.trace import Op, Span, WINDOW, Tracer
from repro.core.protocols import split_nn
from repro.models import moe


def _run(cell):
    return harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())


def test_seq_cell_runs_and_is_correct():
    cell = tiny_seq_cell()
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(r["checks"]) == set(cell.limits["checks"])
    # the program routed as the reference would: no flip, nothing dropped
    assert r["checks"]["route_flip_margin"]["value"] == 0.0
    assert r["checks"]["dropped_rows"]["value"] == 0.0


def test_traced_seq_cell_reports_its_per_layer_metrics():
    cell = tiny_seq_cell(trace=True)
    r = _run(cell)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: no grouped product is found, and the
    # roofline share is left out rather than read as zero
    assert {"train_mfu", "exchange_wait_ms.train", "device_idle_share.train",
            "expert_load_max_over_mean"} <= set(r["metrics"])
    assert "expert_gmm_roofline_share" not in r["metrics"]
    assert r["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


# -- faults planted in the program -------------------------------------------


def _route_swap(monkeypatch):
    """Token 0's k-th expert swapped for its (k+1)-th, in every batch."""
    def swapped(scores, k):
        top = jax.lax.top_k(scores, k + 1)[1]
        return top[:, :k].at[0, k - 1].set(top[0, k])
    monkeypatch.setattr(moe, "top_experts", swapped)


def _expert_zeroed(monkeypatch):
    """The first held expert's output left out."""
    ffn = moe.expert_ffn

    def zeroed(xs, params, sizes, act="silu"):
        o = ffn(xs, params, sizes, act)
        return jnp.where((jnp.arange(xs.shape[0]) < sizes[0])[:, None],
                         0.0, o)
    monkeypatch.setattr(moe, "expert_ffn", zeroed)


def _altered_token(monkeypatch):
    """One token of one member row altered, in every batch."""
    make = split_nn._make_routed_member_fns

    def member(spec, rules):
        fwd, bwd = make(spec, rules)

        def altered(params, x, stats):
            return fwd(params, x.at[0, 0].set((x[0, 0] + 1) % 64), stats)
        return altered, bwd
    monkeypatch.setattr(split_nn, "_make_routed_member_fns", member)


FAULTS = {"route_swap": _route_swap, "expert_zeroed": _expert_zeroed,
          "altered_token": _altered_token}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_seq_fault_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    r = _run(tiny_seq_cell())
    assert not r["correct"], r["checks"]


def test_seq_control_and_faults_in_the_programs_place_are_not_correct():
    cell = tiny_seq_cell(control=True)
    out = cell.kind.run(cell, Tracer(False), time.perf_counter)
    readings = out["readings"]
    assert harness.is_correct(harness.judge(readings["program"],
                                            cell.limits))
    assert set(readings) == {"program", "control", "fault_route_swap",
                             "fault_expert_zeroed", "fault_altered_token"}
    for name, run in readings.items():
        if name == "program":
            continue
        checks = harness.judge({**readings["program"], **run}, cell.limits)
        assert not harness.is_correct(checks), (name, checks)
    assert readings["fault_route_swap"]["route_flip_margin"] > 1e-3


# -- the token silo ----------------------------------------------------------


def test_token_silo_is_int32_zipf_and_follows_the_seed():
    data = tiny_seq_cell().config["data"]
    key = jax.random.key(11)
    a = train_seq.make_token_silos(data, key)
    b = train_seq.make_token_silos(data, key)
    (tok,) = a.member_x
    assert tok.dtype == jnp.int32
    assert tok.shape == (len(a.member_rows[0]),
                         data["member_tokens"]["seq_len"])
    assert int(tok.min()) >= 0 and int(tok.max()) < 64
    np.testing.assert_array_equal(tok, b.member_x[0])
    # each user's most frequent id holds about 1/H(64) of their tokens
    top = [np.bincount(np.asarray(row), minlength=64).max()
           for row in np.asarray(tok)]
    assert np.mean(top) > 2 * tok.shape[1] / 64
    # users' favourite ids differ (their ranks are rotated by the latent)
    fav = [np.argmax(np.bincount(np.asarray(row), minlength=64))
           for row in np.asarray(tok)]
    assert len(set(fav)) > 8


# -- the grouped product's roofline ------------------------------------------


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_gmm_ideal_time_by_hand():
    # one layer, two rounds, 6,144 rows in all: 3,072 a round
    d, f, held = 2048, 1408, 8
    got = train_seq.gmm_ideal_s([[768] * 8], 2, d, f, held, PEAKS)
    flops = 2.0 * 3072 * d * f                       # 17.7 GFLOP
    nbytes = 4.0 * (3072 * (d + f) + held * d * f)   # 134.7 MB
    per = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12           # bandwidth-bound here
    assert got == pytest.approx(15 * 2 * per)
    assert train_seq.GMM_PER_ROW == 15


def test_gmm_device_time_from_a_trace():
    ops = {"/device:TPU:0": [
        Op("%ragged-dot-none.3 = f32[49152,1408]{1,0} custom-call(%a)",
           100, 50),
        Op("%ragged-dot-metadata.1 = (s32[9]) custom-call(%g)", 200, 10),
        Op("%fusion.2 = f32[8,8]{1,0} fusion(%p)", 300, 40),
        Op("%ragged-dot-none.4 = f32[49152,2048]{1,0} custom-call(%b)",
           950, 100)]}
    spans = [Span(WINDOW, 0, 1000)]
    assert train_seq.gmm_device_s(ops, spans) == pytest.approx(110e-9)


def test_expert_metric_readers():
    share = harness.load_module(harness.BENCH_DIR / "metrics"
                                / "expert_gmm_roofline_share.py")
    load = harness.load_module(harness.BENCH_DIR / "metrics"
                               / "expert_load_max_over_mean.py")
    g = {"d": 2048, "f": 1408, "held": 8}
    rec = {"expert_rows_window": [[768] * 8, [600, 900] * 4], "steps": 2,
           "peaks": PEAKS, "expert_gmm": dict(g, device_s=0.01)}
    ideal = train_seq.gmm_ideal_s(rec["expert_rows_window"], 2, 2048, 1408,
                                  8, PEAKS)
    assert share.read(rec) == pytest.approx(100 * ideal / 0.01)
    assert load.read(rec) == pytest.approx(900 / 750)
    # a run that traced nothing, or a cell without experts, reads nothing
    assert share.read(dict(rec, expert_gmm=g)) is None
    assert share.read({"steps": 3}) is None
    assert load.read({"steps": 3}) is None
