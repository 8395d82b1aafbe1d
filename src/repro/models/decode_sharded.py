"""Sequence-sharded decode attention with partial-softmax combine.

§Perf lever (target 4, decode shapes): the baseline einsum decode path
leaves XLA to all-gather the model-axis-sharded KV cache every step
(~1 GiB/step on glm4 decode_32k). Here each model-axis shard computes
flash-style partials (m, l, o) over its local slice of the cache and the
exact softmax is reconstructed with one tiny ``pmax``/``psum`` pair —
the collective moves O(b*h*dh) instead of O(b*S*kv*dh).

Implemented with ``jax.shard_map`` over the full mesh; only the cache
sequence dim is mapped to ``model``. Enabled via
``ModelConfig.decode_partial_softmax`` (``--opt decodeps``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.sharding.rules import MeshRules

NEG_INF = -1e30


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def sharded_decode_attention(cfg: ModelConfig, params, x, cache, index,
                             rules: MeshRules
                             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """GQA decode with a ('model'-sharded on seq) KV cache.

    x: (b, 1, d); cache k/v: (b, S, kv, hd) with S sharded over 'model'.
    """
    mesh = rules.mesh
    b = x.shape[0]
    hd = cfg.head_dim

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dnk->bsnk", x, params["wk"])
    v_new = jnp.einsum("bsd,dnk->bsnk", x, params["wv"])
    if cfg.qk_norm:
        from repro.models.attention import _qk_norm
        q = _qk_norm(params["q_norm"], q, cfg.norm_eps)
        k_new = _qk_norm(params["k_norm"], k_new, cfg.norm_eps)
    if cfg.rope:
        pos = jnp.full((1, 1), index, jnp.int32)
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k_new = layers.apply_rope(k_new, pos, cfg.rope_theta)

    n_model = mesh.shape["model"]
    s_total = cache["k"].shape[1]
    s_local = s_total // n_model
    batch_ax = _batch_axes(mesh)
    # batch maps to (pod, data) only when divisible (long_500k: batch 1)
    bspec: Optional[Tuple[str, ...]] = None
    if batch_ax:
        size = 1
        for a in batch_ax:
            size *= mesh.shape[a]
        if b % size == 0:
            bspec = batch_ax

    def local(q, k_new, v_new, k_shard, v_shard, index):
        # runs per (data x model) shard; seq dim is the model shard
        shard = jax.lax.axis_index("model")
        offset = shard * s_local
        local_idx = jnp.clip(index - offset, 0, s_local - 1)
        in_range = (index >= offset) & (index < offset + s_local)
        k_upd = jax.lax.dynamic_update_slice_in_dim(
            k_shard, k_new.astype(k_shard.dtype), local_idx, axis=1)
        v_upd = jax.lax.dynamic_update_slice_in_dim(
            v_shard, v_new.astype(v_shard.dtype), local_idx, axis=1)
        k_shard = jnp.where(in_range, k_upd, k_shard)
        v_shard = jnp.where(in_range, v_upd, v_shard)

        kvh = k_shard.shape[2]
        h_eff = q.shape[2]
        g = h_eff // kvh
        qg = q.reshape(q.shape[0], 1, kvh, g, hd)
        scale = hd ** -0.5
        s = jnp.einsum("bqngd,bknd->bnqgk",
                       qg.astype(jnp.float32) * scale,
                       k_shard.astype(jnp.float32))      # (b,kv,1,g,S_l)
        slots = offset + jnp.arange(s_local)
        valid = slots <= index
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)

        m_loc = s.max(axis=-1)                           # (b,kv,1,g)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob[..., None])
        l_loc = p.sum(axis=-1)
        o_loc = jnp.einsum("bnqgk,bknd->bqngd",
                           p.astype(v_shard.dtype), v_shard)
        l_glob = jax.lax.psum(l_loc, "model")
        o = jax.lax.psum(o_loc.astype(jnp.float32), "model")
        o = o / jnp.maximum(
            l_glob.transpose(0, 2, 1, 3), 1e-30)[..., None]
        o = o.reshape(o.shape[0], 1, h_eff, hd).astype(q.dtype)
        return o, k_shard, v_shard

    out, k, v = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, "model"), P(bspec, "model"), P()),
        out_specs=(P(bspec), P(bspec, "model"), P(bspec, "model")),
        check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"],
      jnp.asarray(index, jnp.int32))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k, "v": v}
