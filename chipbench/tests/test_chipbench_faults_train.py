"""``correct`` comes out false when a training cell's timed path is
broken underneath: each fault a training step can have, planted in the
program; the run is the harness's own past its look for a chip, at a
test size on the CPU."""
import time

import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests.tiny import FAKE_DEVICE, tiny_cell
from repro.core.protocols import split_nn


def _run(cell):
    return harness.run_cell(cell, FAKE_DEVICE, time.perf_counter())


def _wrap_master_step(monkeypatch, change):
    make = split_nn._make_master_step

    def faulty(bspec, tspec):
        step = make(bspec, tspec)
        return lambda *a: change(step, *a)
    monkeypatch.setattr(split_nn, "_make_master_step", faulty)


def _state_unchanged(monkeypatch):
    def same(step, top, bottom, u, x, y, lr):
        loss, _, _, g = step(top, bottom, u, x, y, lr)
        return loss, top, bottom, g
    _wrap_master_step(monkeypatch, same)
    make = split_nn._make_member_fns

    def member(spec, rules):
        fwd, _ = make(spec, rules)
        return fwd, lambda params, x, du, lr: params
    monkeypatch.setattr(split_nn, "_make_member_fns", member)


def _half_batch(monkeypatch):
    def half(step, top, bottom, u, x, y, lr):
        h = y.shape[0] // 2
        loss, t, b, g = step(top, bottom, tuple(m[:h] for m in u), x[:h],
                             y[:h], lr)
        return loss, t, b, tuple(jnp.concatenate(
            [gg, jnp.zeros((y.shape[0] - h,) + gg.shape[1:], gg.dtype)])
            for gg in g)
    _wrap_master_step(monkeypatch, half)


def _no_exchange(monkeypatch):
    def alone(step, top, bottom, u, x, y, lr):
        return step(top, bottom, tuple(jnp.zeros_like(m) for m in u), x, y,
                    lr)
    _wrap_master_step(monkeypatch, alone)


def _altered_row(monkeypatch):
    make = split_nn._make_member_fns

    def member(spec, rules):
        fwd, bwd = make(spec, rules)
        return (lambda params, x: fwd(params, x).at[0].add(8.0)), bwd
    monkeypatch.setattr(split_nn, "_make_member_fns", member)


def _altered_row_in_tail_batch(monkeypatch):
    """A fault of the epoch's short last batch alone (its own program)."""
    make = split_nn._make_member_fns

    def member(spec, rules):
        fwd, bwd = make(spec, rules)

        def tail_fwd(params, x):
            u = fwd(params, x)
            return u.at[0].add(8.0) if x.shape[0] < TINY_BATCH else u
        return tail_fwd, bwd
    monkeypatch.setattr(split_nn, "_make_member_fns", member)


def _state_unchanged_after_warm_epoch(monkeypatch):
    """The master's state left unchanged from the window on, after the
    first epoch's rounds in set-up went right."""
    calls = [0]

    def late(step, top, bottom, u, x, y, lr):
        calls[0] += 1
        loss, t, b, g = step(top, bottom, u, x, y, lr)
        return (loss, top, bottom, g) if calls[0] > TINY_EPOCH \
            else (loss, t, b, g)
    _wrap_master_step(monkeypatch, late)


TINY_BATCH = 64
TINY_EPOCH = 7                 # 390 matched users in batches of 64

TRAIN_FAULTS = {"state_unchanged": _state_unchanged,
                "half_batch": _half_batch, "no_exchange": _no_exchange,
                "altered_row": _altered_row,
                "altered_row_in_tail_batch": _altered_row_in_tail_batch,
                "state_unchanged_after_warm_epoch":
                    _state_unchanged_after_warm_epoch}


@pytest.mark.parametrize("fault", sorted(TRAIN_FAULTS))
def test_train_fault_is_not_correct(monkeypatch, fault):
    TRAIN_FAULTS[fault](monkeypatch)
    cell = tiny_cell("recsys-table1-mlp.train", seconds=0.2)
    assert (cell.config["batch_size"], TINY_EPOCH) == (
        TINY_BATCH, -(-int(0.6 * cell.config["data"]["n_users"])
                      // TINY_BATCH))
    r = _run(cell)
    assert not r["correct"], r["checks"]


