"""Multi-head latent attention (DeepSeek-V2 / MiniCPM3).

Faithful points: low-rank compressed KV latent (kv_lora_rank) with RMSNorm,
decoupled RoPE key shared across heads, optional low-rank Q, and YaRN
rope scaling (``cfg.rope_scaling``) with its mscale on the softmax scale.
The decode path stores ONLY the compressed latent + rope key (the MLA
memory win) and uses the absorbed-weight formulation so per-step compute
is O(S * kv_lora_rank) per head, never materializing full K/V.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.attention import NEG_INF, attend
from repro.models.params import Spec
from repro.sharding.rules import reduce_dtype


def mla_spec(cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.eff_heads
    spec = {
        "w_dkv": Spec((d, m.kv_lora_rank), ("embed", "kv_lora")),
        "kv_norm": {"scale": Spec((m.kv_lora_rank,), ("kv_lora",),
                                  init="ones", dtype=jnp.float32)},
        "w_kr": Spec((d, m.rope_head_dim), ("embed", "head_dim")),
        "w_uk": Spec((m.kv_lora_rank, h, m.nope_head_dim),
                     ("kv_lora", "heads", "head_dim")),
        "w_uv": Spec((m.kv_lora_rank, h, m.v_head_dim),
                     ("kv_lora", "heads", "head_dim")),
        "wo": Spec((h, m.v_head_dim, d), ("heads", "head_dim", "embed"),
                   init="zeros" if cfg.pad_heads_to else "normal"),
    }
    if m.q_lora_rank:
        spec["w_dq"] = Spec((d, m.q_lora_rank), ("embed", "q_lora"))
        spec["q_norm"] = {"scale": Spec((m.q_lora_rank,), ("q_lora",),
                                        init="ones", dtype=jnp.float32)}
        spec["w_uq_nope"] = Spec((m.q_lora_rank, h, m.nope_head_dim),
                                 ("q_lora", "heads", "head_dim"))
        spec["w_uq_rope"] = Spec((m.q_lora_rank, h, m.rope_head_dim),
                                 ("q_lora", "heads", "head_dim"))
    else:
        spec["wq_nope"] = Spec((d, h, m.nope_head_dim),
                               ("embed", "heads", "head_dim"))
        spec["wq_rope"] = Spec((d, h, m.rope_head_dim),
                               ("embed", "heads", "head_dim"))
    return spec


def _queries(cfg, params, x, positions):
    m = cfg.mla
    if m.q_lora_rank:
        cq = layers.rmsnorm(params["q_norm"],
                            jnp.einsum("bsd,dr->bsr", x, params["w_dq"]),
                            cfg.norm_eps)
        q_nope = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq_nope"])
        q_rope = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq_rope"])
    else:
        q_nope = jnp.einsum("bsd,dhk->bshk", x, params["wq_nope"])
        q_rope = jnp.einsum("bsd,dhk->bshk", x, params["wq_rope"])
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def softmax_scale(cfg: ModelConfig) -> float:
    """``1/sqrt(qk head dim)``, times YaRN's mscale squared."""
    m = cfg.mla
    return ((m.nope_head_dim + m.rope_head_dim) ** -0.5
            * layers.attn_scale(cfg.rope_scaling))


def mla_self_attention(cfg: ModelConfig, params, x, *, positions=None
                       ) -> jax.Array:
    """Training / prefill. x: (b, s, d)."""
    m = cfg.mla
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    ckv = layers.rmsnorm(params["kv_norm"],
                         jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]),
                         cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dk->bsk", x, params["w_kr"])[:, :, None, :]
    k_rope = layers.apply_rope(k_rope, positions[None], cfg.rope_theta,
                               cfg.rope_scaling)
    q_nope, q_rope = _queries(cfg, params, x, positions[None])

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope,
                                  (b, s, cfg.eff_heads, m.rope_head_dim))],
        axis=-1)
    out = attend(q, k, v, positions, positions, window=0, causal=True,
                 scale=softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=reduce_dtype(out.dtype))


# ---------------------------------------------------------------------------
# decode with compressed latent cache (absorbed formulation)
# ---------------------------------------------------------------------------


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, m.rope_head_dim), dtype),
    }


def mla_decode_attention(cfg: ModelConfig, params, x, cache, index
                         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (b, 1, d). Cache holds latents only: (b, S, kv_lora)+(b, S, rope)."""
    m = cfg.mla
    pos = jnp.full((1, 1), index, jnp.int32)
    ckv_t = layers.rmsnorm(params["kv_norm"],
                           jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]),
                           cfg.norm_eps)
    kr_t = jnp.einsum("bsd,dk->bsk", x, params["w_kr"])[:, :, None, :]
    kr_t = layers.apply_rope(kr_t, pos, cfg.rope_theta,
                             cfg.rope_scaling)[:, :, 0, :]
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_t.astype(cache["ckv"].dtype), index, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_t.astype(cache["k_rope"].dtype), index, axis=1)

    q_nope, q_rope = _queries(cfg, params, x, pos)
    # absorb W_uk into the query: score contraction happens in latent space
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])
    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshr,bSr->bhsS", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bshk,bSk->bhsS", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    valid = jnp.arange(ckv.shape[1]) <= index
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhsS,bSr->bshr", probs.astype(ckv.dtype), ckv)
    out = jnp.einsum("bshr,rhk->bshk", out_lat, params["w_uv"])
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=reduce_dtype(out.dtype))
    return y, {"ckv": ckv, "k_rope": k_rope}
