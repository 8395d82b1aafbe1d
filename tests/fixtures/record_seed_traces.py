"""Record reference training traces (loss history + final weights) for
the three built-in protocols.

The fixture pins the numerical behaviour of the protocol layer: the
lifecycle API (core/protocols/driver.py) must reproduce these traces
bit-for-bit (f64 paths) / to float32 tolerance (split-NN), which is how
we know the refactor away from monolithic role functions changed zero
arithmetic. The linreg and logreg_he traces in
tests/fixtures/seed_traces.json were generated against the
pre-lifecycle seed code (commit ae0d7bc); they use numpy float64 and
re-record identically. The split_nn trace draws its init from
jax.random, so it depends on JAX's PRNG implementation: it was
re-recorded under JAX 0.9.0, where ``jax_threefry_partitionable``
defaults to True (the ae0d7bc trace predates that default). The
fixture's ``_provenance`` entry says the same.

Configs use n divisible by batch_size so the traces are invariant to the
drop_last default.

  PYTHONPATH=src python tests/fixtures/record_seed_traces.py
"""
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

from repro.core.party import run_vfl                      # noqa: E402
from repro.core.protocols.base import VFLConfig           # noqa: E402
from repro.data.vertical import vertical_partition        # noqa: E402

OUT = pathlib.Path(__file__).resolve().parent / "seed_traces.json"


def dataset(n=192, d=12, items=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=(d, items))
    y = x @ w * 0.4 + rng.normal(scale=0.05, size=(n, items))
    ids = [f"u{i:05d}" for i in range(n)]
    return ids, x, y


def main():
    traces = {}

    ids, x, y = dataset()
    master, members = vertical_partition(ids, x, y, widths=[4, 3],
                                         overlap=1.0, seed=1)
    cfg = VFLConfig(protocol="linreg", epochs=3, batch_size=48, lr=0.1,
                    seed=0, use_psi=False)
    res = run_vfl(cfg, master, members, mode="thread")
    traces["linreg"] = {
        "losses": [h["loss"] for h in res["master"]["history"]],
        "w_master": res["master"]["w_master"].tolist(),
        "w_members": [res[f"member{j}"]["w"].tolist() for j in range(2)],
    }

    ids, x, y = dataset(n=64, d=8, items=1)
    yb = (y > 0).astype(np.float64)
    master, members = vertical_partition(ids, x, yb, widths=[3], seed=4)
    cfg = VFLConfig(protocol="logreg_he", epochs=1, batch_size=32, lr=0.5,
                    seed=0, use_psi=False, he_bits=256)
    res = run_vfl(cfg, master, members, mode="thread")
    traces["logreg_he"] = {
        "losses": [h["loss"] for h in res["master"]["history"]],
        "w_master": res["master"]["w_master"].tolist(),
        "w_members": [res["member0"]["w"].tolist()],
    }

    ids, x, y = dataset(n=128, d=12, items=3)
    yb = (y > 0).astype(np.float64)
    master, members = vertical_partition(ids, x, yb, widths=[5], seed=3)
    cfg = VFLConfig(protocol="split_nn", epochs=3, batch_size=32, lr=0.1,
                    seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    res = run_vfl(cfg, master, members, mode="thread")
    traces["split_nn"] = {
        "losses": [h["loss"] for h in res["master"]["history"]],
    }

    import jax
    traces["_provenance"] = (
        "linreg/logreg_he: pre-lifecycle seed code (commit ae0d7bc), "
        "numpy float64. split_nn: re-recorded with this script under "
        f"jax {jax.__version__}, jax_threefry_partitionable="
        f"{jax.config.jax_threefry_partitionable}, JAX_PLATFORMS=cpu.")
    OUT.write_text(json.dumps(traces, indent=1))
    print(f"wrote {OUT}")
    for k, v in traces.items():
        if k.startswith("_"):
            continue
        print(f"  {k}: {len(v['losses'])} steps, "
              f"loss {v['losses'][0]:.6f} -> {v['losses'][-1]:.6f}")


if __name__ == "__main__":
    main()
