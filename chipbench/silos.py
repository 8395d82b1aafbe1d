"""The configuration's silos, made on the device from the seed.

A latent-factor copy of the Table 1 data (the real data is not
public): user and item latents, implicit-feedback labels thresholded
at the published interaction density, and standardized features that
carry the user latents. The master silo holds every user, its features
and the labels; each member silo holds an ``id_overlap`` share of the
users, drawn from the seed, and its own features. Everything is drawn
by one jitted call on the device; the parties take host copies, as the
program's data API expects, and the reference reads the device arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Silos:
    master_x: jax.Array            # (n_users, master_features)
    labels: jax.Array              # (n_users, n_items), 0/1 float32
    member_x: List[jax.Array]      # (rows_j, member_features[j])
    member_rows: List[np.ndarray]  # master row of each member row, sorted
    ids: List[str]                 # user ids, sorted order = row order

    @property
    def common(self) -> np.ndarray:
        """Master rows every member holds, in the order the parties
        agree on (sorted ids)."""
        rows = self.member_rows[0]
        for r in self.member_rows[1:]:
            rows = np.intersect1d(rows, r)
        return rows

    def member_index(self, j: int, master_rows: np.ndarray) -> np.ndarray:
        """Row of member ``j``'s silo for each of ``master_rows``."""
        return np.searchsorted(self.member_rows[j], master_rows)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (all its bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _standardize(raw):
    mean = raw.mean(0)
    std = jnp.sqrt(jnp.mean(jnp.square(raw - mean), 0))
    return (raw - mean) / (std + 1e-6)


def make_silos(data: Dict[str, Any], key) -> Silos:
    n, items = int(data["n_users"]), int(data["n_items"])
    latent = int(data["latent"])
    widths = [int(w) for w in data["member_features"]]
    m = int(float(data["id_overlap"]) * n)
    density = float(data["n_interactions"]) / (n * items)

    def draw(key):
        ks = iter(jax.random.split(key, 6 + 3 * len(widths)))
        zu = jax.random.normal(next(ks), (n, latent), jnp.float32)
        zi = jax.random.normal(next(ks), (items, latent), jnp.float32)
        logits = zu @ zi.T + float(data["label_noise"]) * \
            jax.random.normal(next(ks), (n, items), jnp.float32)
        thresh = jnp.quantile(logits, 1.0 - density)
        labels = (logits > thresh).astype(jnp.float32)

        def silo(k1, k2, z, width):
            w = jax.random.normal(k1, (latent, width), jnp.float32)
            return _standardize(z @ w + jax.random.normal(
                k2, (z.shape[0], width), jnp.float32))

        master_x = silo(next(ks), next(ks), zu, int(data["master_features"]))
        rows, xs = [], []
        for width in widths:
            keep = jnp.sort(jax.random.permutation(next(ks), n)[:m])
            rows.append(keep)
            xs.append(silo(next(ks), next(ks), zu[keep], width))
        return master_x, labels, xs, rows

    with jax.default_matmul_precision("highest"):
        master_x, labels, xs, rows = jax.jit(draw)(key)
    ids = [f"u{i:08d}" for i in range(n)]
    return Silos(master_x, labels, list(xs),
                 [np.asarray(r).astype(np.int64) for r in rows], ids)

