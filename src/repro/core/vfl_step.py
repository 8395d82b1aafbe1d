"""Mesh-mode VFL: the paper's exchange schedule lowered onto a TPU mesh.

Beyond-paper execution mode (DESIGN.md §2): parties map to the ``pod``
mesh axis. A member's bottom-forward runs pod-locally on its own feature
shard; the embedding exchange ("send u_p to master") becomes a ``psum``
over the pod axis; pairwise secure-aggregation masks (core/secure_agg)
are added before the psum so no pod ever observes another pod's raw
embedding — the same privacy property the thread/socket modes get from
message isolation, now at ICI/DCN speed.

The top model + loss is computed replicated on every pod (it only sees
the aggregate), and the gradient exchange is the transposed collective,
generated automatically by jax.grad through the psum.

The same function also drives the VFL-LLM integration: members hold the
embedding/feature frontends of the assigned architectures and the master
holds the transformer backbone (examples/vfl_llm.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import secure_agg
from repro.core.protocols.split_nn import _bce, mlp_apply, mlp_init


def init_party_params(key, n_parties: int, d_in: int, hidden, e: int):
    """Stacked bottom params, one slice per party (pod)."""
    def one(i):
        return mlp_init(jax.random.fold_in(key, i + 2),
                        (d_in,) + tuple(hidden) + (e,))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        one(i) for i in range(n_parties)])
    return stacked


def make_mesh_vfl_step(mesh: Mesh, n_parties: int, lr: float = 0.05,
                       use_masks: bool = True):
    """Returns a jit'd step: (bottoms, top, x, y, key) -> (..., loss).

    bottoms: party-stacked pytree with leading dim n_parties, sharded
    over 'pod'; x: (n_parties, batch, d_in) — party feature slices
    (padded to a common width); y: (batch, items) labels (replicated —
    only the aggregate loss needs them).
    """
    def step(bottoms, top, x, y, key):
        def loss_fn(bottoms, top):
            def party_fwd(bottom_p, x_p):
                # runs per pod: bottom_p has a leading party dim of 1
                b = jax.tree.map(lambda a: a[0], bottom_p)
                u = mlp_apply(b, x_p[0], final_act=True)
                if use_masks:
                    idx = jax.lax.axis_index("pod")
                    mask = _mask_for(key, idx, n_parties, u.shape)
                    u = u + mask
                return jax.lax.psum(u, "pod")

            # masks are psum-cancelled, which replication checking
            # cannot follow
            agg = jax.shard_map(
                party_fwd, mesh=mesh,
                in_specs=(P("pod"), P("pod")),
                out_specs=P(), check_vma=False)(bottoms, x)
            logits = mlp_apply(top, agg)
            return _bce(logits, y)

        loss, (g_b, g_t) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            bottoms, top)
        new_b = jax.tree.map(lambda p, g: p - lr * g, bottoms, g_b)
        new_t = jax.tree.map(lambda p, g: p - lr * g, top, g_t)
        return new_b, new_t, loss

    return jax.jit(step)


def _mask_for(key, party_idx, n_parties: int, shape):
    """Pairwise-canceling mask, branch-free over the traced party index."""
    masks = jnp.stack([
        secure_agg.pairwise_mask(key, i, n_parties, shape)
        for i in range(n_parties)])
    return masks[party_idx]
