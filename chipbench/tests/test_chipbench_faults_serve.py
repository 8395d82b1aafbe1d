"""``correct`` comes out false when a serving cell's timed path is
broken underneath: each fault a served answer can have, planted in the
program; the run is the harness's own past its look for a chip, at a
test size on the CPU."""
import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell
from repro.core.protocols import split_nn


def _run(cell):
    return harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())


def _altered_answer(monkeypatch):
    predict = split_nn.SplitNNProtocol.predict_master

    def altered(self, rows):
        out = np.array(predict(self, rows))
        out[0, 0] += 1.0
        return out
    monkeypatch.setattr(split_nn.SplitNNProtocol, "predict_master", altered)


def _no_member_answer(monkeypatch):
    embed = split_nn.SplitNNProtocol.predict_embed
    monkeypatch.setattr(split_nn.SplitNNProtocol, "predict_embed",
                        lambda self, rows: 0.0 * embed(self, rows))


SERVE_FAULTS = {"altered_answer": _altered_answer,
                "no_exchange": _no_member_answer}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serve_fault_is_not_correct(monkeypatch, fault):
    SERVE_FAULTS[fault](monkeypatch)
    r = _run(tiny_cell("recsys-table1-mlp.serve-zipf", seconds=0.5))
    assert not r["correct"], r["checks"]


