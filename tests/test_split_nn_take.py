"""Split-NN's per-round row take (``split_nn._take``): one jitted gather
per party per round over silos stored lane-padded (``split_nn._Silo``).
It must give bit for bit what eager ``silo[rows]`` gives, keep the
recorded seed trace, and leave the step factories called once per round
with batch-shaped arrays."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.party import VFLJob
from repro.core.protocols import split_nn
from repro.core.protocols.base import VFLConfig
from repro.data.vertical import vertical_partition

TRACES = json.loads(
    (pathlib.Path(__file__).parent / "fixtures" / "seed_traces.json")
    .read_text())

N_SILO = 2000
BATCH = 512
# a party's silo widths at the benchmark's Table 1 scale: the master
# holds features and labels, a member features alone
WIDTHS = {"master": (1345, 19), "member": (381,)}


def _rows(kind):
    rng = np.random.default_rng(7)
    if kind == "batch":
        return rng.permutation(N_SILO)[:BATCH]
    if kind == "tail":
        return rng.permutation(N_SILO)[:87]
    # serving: a round's deduplicated, sorted user rows
    n = int(kind.split("-")[1])
    return np.unique(rng.choice(N_SILO, size=n, replace=False))


@pytest.mark.parametrize("rows", ["batch", "tail", "serve-1", "serve-64"])
@pytest.mark.parametrize("party", sorted(WIDTHS))
def test_take_equals_eager_indexing_bit_for_bit(party, rows):
    rng = np.random.default_rng(0)
    silos = tuple(jnp.asarray(rng.normal(size=(N_SILO, w)), jnp.float32)
                  for w in WIDTHS[party])
    idx = _rows(rows)
    stored = tuple(split_nn._Silo.put(s) for s in silos)
    assert all(t.data.shape[1] % split_nn.LANES == 0 for t in stored)
    got = split_nn._take(stored, idx)
    assert len(got) == len(silos)
    for g, s in zip(got, silos):
        want = np.asarray(s[idx])
        assert g.shape == (len(idx), s.shape[1]) and g.dtype == s.dtype
        assert np.array_equal(np.asarray(g).view(np.uint32),
                              want.view(np.uint32))


@pytest.mark.parametrize("rows", ["batch", "tail", "serve-1", "serve-64"])
@pytest.mark.parametrize("width", [512, 100])
def test_int32_token_silo_take_equals_eager_indexing(width, rows):
    """A member's token-id silo is stored and taken as int32, never
    through a float cast: the take gives the ids eager indexing gives."""
    rng = np.random.default_rng(1)
    host = rng.integers(0, 102400, size=(N_SILO, width)).astype(np.int32)
    stored = split_nn._Silo.put(host)
    assert stored.data.dtype == jnp.int32 and stored.width == width
    assert stored.data.shape[1] % split_nn.LANES == 0
    idx = _rows(rows)
    (got,) = split_nn._take((stored,), idx)
    assert got.dtype == jnp.int32 and got.shape == (len(idx), width)
    assert np.array_equal(np.asarray(got), host[idx])
    # ids above 2**24 would not survive a float32 round trip
    big = np.full((4, 3), 2**24 + 1, np.int32)
    (back,) = split_nn._take((split_nn._Silo.put(big),), [0, 3])
    assert np.array_equal(np.asarray(back), big[[0, 3]])


def _dataset(n, d=12, items=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=(d, items))
    y = x @ w * 0.4 + rng.normal(scale=0.05, size=(n, items))
    ids = [f"u{i:05d}" for i in range(n)]
    return ids, x, (y > 0).astype(np.float64)


def _splitnn_case(n=128):
    ids, x, y = _dataset(n)
    master, members = vertical_partition(ids, x, y, widths=[5], seed=3)
    cfg = VFLConfig(protocol="split_nn", epochs=3, batch_size=32, lr=0.1,
                    seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    return cfg, master, members


def _fit_predict(mode):
    cfg, master, members = _splitnn_case()
    with VFLJob(cfg, master, members, mode=mode) as job:
        losses = [h["loss"] for h in job.fit()["history"]]
        return losses, np.asarray(job.predict())


@pytest.mark.parametrize("mode", ["thread", "socket"])
def test_fit_and_predict_keep_the_seed_trace(monkeypatch, mode):
    losses, scores = _fit_predict(mode)
    np.testing.assert_allclose(losses, TRACES["split_nn"]["losses"],
                               rtol=0, atol=0)
    # the same job gathering eagerly, as before the take
    monkeypatch.setattr(
        split_nn, "_take",
        lambda silos, rows: tuple(s.data[:, :s.width][np.asarray(rows)]
                                  for s in silos))
    eager_losses, eager_scores = _fit_predict(mode)
    assert losses == eager_losses
    assert np.array_equal(scores.view(np.uint32),
                          eager_scores.view(np.uint32))


def test_step_factories_called_once_per_round_with_batches(monkeypatch):
    """The seam the benchmark's fault tests wrap: the master step and
    the member's fwd/bwd stay per-round Python calls on batch arrays."""
    calls = {"step": [], "fwd": [], "bwd": []}
    make_step = split_nn._make_master_step
    make_member = split_nn._make_member_fns

    def step_factory(bspec, tspec):
        step = make_step(bspec, tspec)

        def counted(top, bottom, u, x, y, lr):
            calls["step"].append((x.shape, y.shape,
                                  tuple(m.shape for m in u)))
            assert isinstance(x, jax.Array) and isinstance(y, jax.Array)
            return step(top, bottom, u, x, y, lr)
        return counted

    def member_factory(spec, rules):
        fwd, bwd = make_member(spec, rules)

        def cfwd(params, x):
            calls["fwd"].append(x.shape)
            return fwd(params, x)

        def cbwd(params, x, du, lr):
            calls["bwd"].append((x.shape, du.shape))
            return bwd(params, x, du, lr)
        return cfwd, cbwd

    monkeypatch.setattr(split_nn, "_make_master_step", step_factory)
    monkeypatch.setattr(split_nn, "_make_member_fns", member_factory)
    cfg, master, members = _splitnn_case(n=150)     # a 22-row tail batch
    with VFLJob(cfg, master, members, mode="thread") as job:
        res = job.fit()
    n, bs = res["n_common"], cfg.batch_size
    sizes = [min(bs, n - lo) for lo in range(0, n, bs)] * cfg.epochs
    assert sizes[-1] == n % bs == 22
    d_master = master.x.shape[1]
    assert calls["step"] == [((b, d_master), (b, 3), ((b, 8),))
                             for b in sizes]
    assert calls["fwd"] == [(b, 5) for b in sizes]
    assert calls["bwd"] == [((b, 5), (b, 8)) for b in sizes]
