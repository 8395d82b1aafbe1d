"""The program's spans in a traced window: totals, counts, longest and
the master round's self time on a hand-made two-thread trace counted by
hand, the six numbers that read them, and the tool end to end on the
CPU at a test size."""
import time

import pytest

from chipbench import spans
from chipbench.spans import LineSpan
from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell

MASTER, MEMBER = ("/host:CPU", 0), ("/host:CPU", 1)


def _hand_trace():
    # window [1000, 3000) ns; the master thread runs two rounds, the
    # member thread its own spans; a span spills over the window's end
    return [
        LineSpan("master.round", MASTER, 1000, 1800),
        LineSpan("master.on_batch_master", MASTER, 1050, 1750),
        LineSpan("master.recv_wait", MASTER, 1100, 1400),
        LineSpan("master.decode", MASTER, 1300, 1400),   # inside the wait
        LineSpan("master.step", MASTER, 1500, 1600),
        LineSpan("master.d2h", MASTER, 1600, 1700),
        LineSpan("master.round", MASTER, 2000, 2500),
        LineSpan("master.h2d", MASTER, 2100, 2200),
        LineSpan("master.d2h", MASTER, 2900, 3100),     # 100 ns inside
        # a member leaf on another thread is not the master's
        LineSpan("member0.d2h", MEMBER, 2200, 2500),
        LineSpan("member0.encode", MEMBER, 2500, 2550),
        LineSpan("serve.round", MASTER, 3200, 3300),    # outside
        LineSpan("PjitFunction(add)", MASTER, 1500, 1550),
    ]


def test_totals_counts_and_longest_inside_the_window():
    r = spans.reduce_spans(_hand_trace(), 1000, 3000)
    assert r["master.d2h"]["count"] == 2
    assert r["master.d2h"]["total_s"] == pytest.approx(200e-9)
    assert r["master.d2h"]["max_s"] == pytest.approx(100e-9)
    assert r["member0.d2h"]["total_s"] == pytest.approx(300e-9)
    assert r["master.round"]["count"] == 2
    assert r["master.round"]["total_s"] == pytest.approx(1300e-9)
    assert "serve.round" not in r and "PjitFunction(add)" not in r


def test_master_round_self_time():
    r = spans.reduce_spans(_hand_trace(), 1000, 3000)
    # round 1: 800 - (300 wait with its decode + 100 step + 100 d2h);
    # round 2: 500 - 100 h2d (the member's d2h is on its own thread)
    assert r["master.round"]["self_s"] == pytest.approx(700e-9)


def test_numbers_that_read_the_spans():
    s = spans.reduce_spans(_hand_trace(), 1000, 3000)
    train = {"spans": s, "steps": 2}
    assert spans.transfer_ms_train(train) == pytest.approx(
        1e3 * (200 + 100 + 300) * 1e-9 / 2)
    assert spans.codec_ms_train(train) == pytest.approx(
        1e3 * (100 + 50) * 1e-9 / 2)
    assert spans.host_glue_ms_train(train) == pytest.approx(
        1e3 * 700e-9 / 2)
    serve = {"spans": dict(s, **{
        "serve.round": {"total_s": 1e-3, "count": 4, "max_s": 1e-3},
        "serve.batcher.take": {"total_s": 3e-3, "count": 4, "max_s": 1e-3},
        "serve.batcher.hold": {"total_s": 2e-3, "count": 3,
                               "max_s": 1e-3}})}
    assert spans.transfer_ms_serve(serve) == pytest.approx(
        1e3 * 600e-9 / 4)
    assert spans.batcher_hold_ms_serve(serve) == pytest.approx(0.5)
    assert spans.host_read_max_ms_serve(serve) == pytest.approx(
        1e3 * 300e-9)


@pytest.mark.parametrize("name", sorted(spans.METRICS))
def test_nothing_to_read_without_spans(name):
    # no trace; a trace without spans; the benchmark's spans alone (the
    # program's switched off)
    theirs = {k: {"total_s": 1e-3, "count": 2, "max_s": 1e-3}
              for k in ("serve.round", "master.on_batch_master",
                        "member0.predict_embed", "client.submit")}
    for r in ({}, {"spans": {}}, {"spans": theirs}):
        assert spans.METRICS[name](dict(r, steps=3)) is None


def test_program_and_benchmark_spans_told_apart():
    assert spans.is_program_span("member3.recv_wait")
    assert spans.is_program_span("serve.batcher.hold")
    assert spans.is_program_span("master.round")
    assert not spans.is_program_span("master.on_batch_master")
    assert not spans.is_program_span("serve.round")
    assert not spans.is_program_span("client.submit")


def test_traced_train_cell_with_program_spans():
    from repro import obs
    cell = tiny_cell("recsys-table1-mlp.train", seconds=0.5, trace=True)
    obs.enable(True)
    try:
        r = spans.run(cell, FAKE_DEVICE, time.perf_counter())
    finally:
        obs.enable(False)
    assert r["correct"]
    assert set(r["span_metrics"]) == {"transfer_ms.train",
                                      "codec_ms.train",
                                      "host_glue_ms.train"}
    assert all(v > 0 for v in r["span_metrics"].values())
    wait = r["spans"]["master.recv_wait"]["total_s"] / r["rounds"]
    assert 1e3 * wait == pytest.approx(
        r["metrics"]["exchange_wait_ms.train"]["value"], rel=0.1)
    assert r["spans_per_round"] > 10
