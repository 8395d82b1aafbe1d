"""Property tests for pairwise-masked secure aggregation.

The defense claim in docs/privacy.md rests on two exactness
properties of :class:`~repro.core.secure_agg_protocol.PairwiseMasker`:

1. **Telescoping** — summed over the member set, masks cancel *bit
   for bit* (not approximately): the PRG emits values on a fixed
   dyadic grid (multiples of 2^-10, |z| <= 8 clipped), so every mask
   entry and every bounded partial sum is exactly representable in
   float32 and the +/- streams of each pair annihilate in ANY
   summation order.
2. **Transparency** — when the member data itself sums exactly (also
   grid-valued), the masked sum equals the plain sum bit-for-bit, so
   secure aggregation costs exactly zero utility (the privacy.json
   ``secure_agg`` rows report utility_delta 0.0 by construction).

Runs under real hypothesis when installed, else the deterministic
shim (tests/_hypothesis_compat.py).
"""
import threading

import numpy as np

from _hypothesis_compat import given, settings, strategies as st
from repro.comm.local import ThreadBus
from repro.core.secure_agg_protocol import PairwiseMasker


def _mesh(n_members):
    """Full pairwise key agreement between n members over a ThreadBus
    (each masker's DH exchange blocks on its peers, hence threads)."""
    names = [f"member{i}" for i in range(n_members)]
    bus = ThreadBus(names)
    out = {}

    def mk(me):
        out[me] = PairwiseMasker(bus.communicator(me), me, names)

    ts = [threading.Thread(target=mk, args=(m,)) for m in names]
    [t.start() for t in ts]
    [t.join() for t in ts]
    return [out[m] for m in names]


@given(st.integers(2, 5), st.integers(0, 10_000),
       st.integers(1, 7), st.integers(1, 9))
@settings(max_examples=8, deadline=None)
def test_masks_cancel_bit_exact(n_members, rnd, rows, cols):
    """Sum of all members' round-``rnd`` masks is exactly 0.0 — and in
    reversed order too, because grid values make fp32 addition exact."""
    maskers = _mesh(n_members)
    masks = [m.mask(rnd, (rows, cols)) for m in maskers]
    fwd = np.zeros((rows, cols), np.float32)
    for m in masks:
        fwd = fwd + m
    rev = np.zeros((rows, cols), np.float32)
    for m in reversed(masks):
        rev = rev + m
    assert fwd.dtype == np.float32 and rev.dtype == np.float32
    assert np.all(fwd == 0.0)
    assert np.all(rev == 0.0)
    # ... and the masks are not trivially zero (masking actually hides).
    # Judged on 256 entries of the same round: a mask of one entry is
    # below 0.1 for 8% of fresh keys, 256 of them for none in practice
    probe = [m.mask(rnd, (256,)) for m in maskers]
    assert max(float(np.abs(m).max()) for m in probe) > 0.1


@given(st.integers(2, 4), st.integers(0, 500), st.integers(0, 2**31))
@settings(max_examples=8, deadline=None)
def test_masked_sum_equals_plain_sum(n_members, rnd, data_seed):
    """Grid-valued member tensors: sum(u_i + mask_i) == sum(u_i)
    bit-for-bit — the aggregate the master computes under secure_agg
    is *identical* to the unmasked aggregate."""
    shape = (6, 8)
    rng = np.random.default_rng(data_seed)
    us = [(rng.integers(-8192, 8192, shape) / 1024.0).astype(np.float32)
          for _ in range(n_members)]
    maskers = _mesh(n_members)
    masked = [u + m.mask(rnd, shape) for u, m in zip(us, maskers)]
    plain_sum = np.zeros(shape, np.float32)
    masked_sum = np.zeros(shape, np.float32)
    for u, mu in zip(us, masked):
        plain_sum = plain_sum + u
        masked_sum = masked_sum + mu
    assert masked_sum.dtype == plain_sum.dtype == np.float32
    assert np.array_equal(masked_sum, plain_sum)
    # each individual wire tensor differs from the raw one
    for u, mu in zip(us, masked):
        assert not np.array_equal(mu, u)


@given(st.integers(2, 5), st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_pair_streams_equal_and_opposite(n_members, rnd):
    """Each pair (i, j) derives the same DH seed, and i's signed PRG
    contribution is the exact negation of j's — the telescoping is
    per-pair, so ANY subset of complete pairs cancels."""
    maskers = _mesh(n_members)
    by_name = {m.me: m for m in maskers}
    shape = (3, 5)
    for a in maskers:
        for other, seed in a.seeds.items():
            b = by_name[other]
            assert b.seeds[a.me] == seed
            sa = 1.0 if a.me < other else -1.0
            sb = 1.0 if b.me < a.me else -1.0
            pa = sa * a._prg(seed, rnd, shape)
            pb = sb * b._prg(b.seeds[a.me], rnd, shape)
            assert np.array_equal(pa, -pb)


@given(st.integers(0, 10_000), st.integers(1, 8))
@settings(max_examples=8, deadline=None)
def test_prg_grid_quantized(rnd, cols):
    """Every PRG value sits on the 2^-10 dyadic grid within [-8, 8] —
    the invariant the exact-cancellation argument rests on."""
    m0, _ = _mesh(2)
    seed = next(iter(m0.seeds.values()))
    z = m0._prg(seed, rnd, (16, cols))
    assert z.dtype == np.float32
    assert float(np.abs(z).max()) <= 8.0
    scaled = z.astype(np.float64) * 1024.0
    assert np.array_equal(scaled, np.round(scaled))
