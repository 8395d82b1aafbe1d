"""GQA attention: full-causal, sliding-window, qk-norm, RoPE; training,
prefill and single-token decode paths.

The jnp implementation here is both the CPU oracle and the dry-run
lowering path (Pallas kernels are validated separately in interpret mode;
see ``repro/kernels``). For long sequences the query dimension is chunked
with ``lax.map`` so prefill_32k never materializes a full S x S score
matrix per head.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.params import Spec
from repro.sharding.rules import reduce_dtype

NEG_INF = -1e30


def attention_spec(cfg: ModelConfig, cross: bool = False):
    # eff_heads >= n_heads when TP head padding is on (§Perf); the extra
    # heads are zero-output-initialized so the function at init matches
    # the unpadded architecture exactly.
    d, h, kv, hd = cfg.d_model, cfg.eff_heads, cfg.n_kv_heads, cfg.head_dim
    spec = {
        "wq": Spec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((h, hd, d), ("heads", "head_dim", "embed"),
                   init="zeros" if cfg.pad_heads_to else "normal"),
    }
    if cfg.qk_norm and not cross:
        spec["q_norm"] = {"scale": Spec((hd,), ("head_dim",), init="ones",
                                        dtype=jnp.float32)}
        spec["k_norm"] = {"scale": Spec((hd,), ("head_dim",), init="ones",
                                        dtype=jnp.float32)}
    return spec


def _qk_norm(scale_params, x, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale_params["scale"]).astype(x.dtype)


def _mask(q_pos, k_pos, window: int, causal: bool):
    """(q, k) boolean mask. q_pos/k_pos: int32 position vectors."""
    d = q_pos[:, None] - k_pos[None, :]
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= d >= 0
    if window:
        m &= d < window
    return m


def _sdpa(q, k, v, mask, scale=None) -> jax.Array:
    """q: (b,sq,kv,g,hd) k/v: (b,sk,kv,hd); grouped-query attention core.
    ``scale`` multiplies the logits (default ``1/sqrt(hd)``)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqngd,bkn d->bnqgk".replace(" ", ""),
                        q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    scores = jnp.where(mask[:, None, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqgk,bknd->bqngd", probs.astype(v.dtype), v)
    return out


def attend(q, k, v, q_pos, k_pos, *, window=0, causal=True,
           q_chunk: int = 2048, scale=None) -> jax.Array:
    """Chunked-over-queries masked attention.

    q: (b, sq, h, hd); k/v: (b, sk, kv, hd). Returns (b, sq, h, hd).
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    vd = v.shape[-1]            # may differ from hd (MLA)
    qg = q.reshape(b, sq, kvh, g, hd)

    if sq <= q_chunk:
        mask = _mask(q_pos, k_pos, window, causal)[None]
        return _sdpa(qg, k, v, mask, scale).reshape(b, sq, h, vd)

    n_chunks = sq // q_chunk
    assert sq % q_chunk == 0, (sq, q_chunk)
    qc = qg.reshape(b, n_chunks, q_chunk, kvh, g, hd)
    pc = q_pos.reshape(n_chunks, q_chunk)

    def one(args):
        qi, pi = args
        mask = _mask(pi, k_pos, window, causal)[None]
        return _sdpa(qi, k, v, mask, scale)

    out = jax.lax.map(one, (qc.swapaxes(0, 1), pc))      # (n, b, qc, kv, g, vd)
    return out.swapaxes(0, 1).reshape(b, sq, h, vd)


def self_attention(cfg: ModelConfig, params, x, *, positions=None,
                   causal=True) -> jax.Array:
    """Training / prefill self-attention. x: (b, s, d)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", x, params["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", x, params["wv"])
    if cfg.qk_norm:
        q = _qk_norm(params["q_norm"], q, cfg.norm_eps)
        k = _qk_norm(params["k_norm"], k, cfg.norm_eps)
    if cfg.rope:
        q = layers.apply_rope(q, positions[None], cfg.rope_theta)
        k = layers.apply_rope(k, positions[None], cfg.rope_theta)
    window = cfg.window if cfg.attention == "swa" else 0
    out = attend(q, k, v, positions, positions, window=window, causal=causal)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=reduce_dtype(out.dtype))


def cross_attention(cfg: ModelConfig, params, x, memory) -> jax.Array:
    """Decoder->encoder attention (whisper). memory: (b, frames, d)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bfd,dnk->bfnk", memory, params["wk"])
    v = jnp.einsum("bfd,dnk->bfnk", memory, params["wv"])
    sq, sk = x.shape[1], memory.shape[1]
    out = attend(q, k, v, jnp.arange(sq), jnp.arange(sk),
                 window=0, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=reduce_dtype(out.dtype))


# ---------------------------------------------------------------------------
# decode (single new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """KV cache for one attention layer.

    SWA archs use a ring buffer of ``window`` slots — the whole point of
    the sub-quadratic carve-out: long_500k keeps a 4096-slot cache.
    """
    slots = min(max_seq, cfg.window) if cfg.attention == "swa" else max_seq
    shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec_axes():
    return ("batch", "cache_seq", "kv_heads", "head_dim")


def decode_attention(cfg: ModelConfig, params, x, cache, index
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (b, 1, d); cache k/v: (b, S, kv, hd); index: scalar int32 count of
    tokens already in cache. Returns (out (b,1,d), new_cache)."""
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dnk->bsnk", x, params["wk"])
    v_new = jnp.einsum("bsd,dnk->bsnk", x, params["wv"])
    if cfg.qk_norm:
        q = _qk_norm(params["q_norm"], q, cfg.norm_eps)
        k_new = _qk_norm(params["k_norm"], k_new, cfg.norm_eps)
    if cfg.rope:
        pos = jnp.full((1, 1), index, jnp.int32)
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k_new = layers.apply_rope(k_new, pos, cfg.rope_theta)

    slots = cache["k"].shape[1]
    slot = index % slots if cfg.attention == "swa" else index
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)

    h_eff = q.shape[2]
    kvh = k.shape[2]
    g = h_eff // kvh
    qg = q.reshape(b, 1, kvh, g, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqngd,bknd->bnqgk", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    slot_ids = jnp.arange(slots)
    if cfg.attention == "swa":
        valid = (slot_ids <= index) | (index >= slots)   # ring: all valid once full
    else:
        valid = slot_ids <= index
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnqgk,bknd->bqngd", probs.astype(v.dtype), v)
    out = out.reshape(b, 1, h_eff, cfg.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=reduce_dtype(out.dtype))
    return y, {"k": k, "v": v}
