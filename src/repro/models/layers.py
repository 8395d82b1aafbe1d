"""Shared primitive layers: norms, RoPE, MLPs, embeddings, losses.

All apply-functions are pure: ``apply(params, x, cfg-ish args) -> y``.
Norm params are kept in fp32 (Spec dtype override); matmuls run in the
activation dtype with fp32 accumulation where it matters.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import Spec
from repro.sharding.rules import reduce_dtype

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int):
    return {"scale": Spec((dim,), ("embed",), init="ones", dtype=jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(x.dtype)


def layernorm_spec(dim: int):
    return {
        "scale": Spec((dim,), ("embed",), init="ones", dtype=jnp.float32),
        "bias": Spec((dim,), ("embed",), init="zeros", dtype=jnp.float32),
    }


def layernorm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times (1 where nothing is stretched)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_ramp(scaling, head_dim: int, theta: float) -> np.ndarray:
    """Per frequency, 0 where YaRN interpolates (stretches) it and 1 where
    it keeps it: a linear ramp between the dims that turn ``beta_fast``
    and ``beta_slow`` times over the original context."""
    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(head_dim // 2, dtype=np.float64)
    return 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)


def rope_frequencies(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Rotary frequencies, computed in float64 on the host and rounded
    once to float32 (at position p the angle is ``p * freq``, so an ulp
    lost here grows with the position); ``scaling`` (a ``YarnConfig`` or
    None) blends each with its ``factor``-stretched copy by YaRN's
    ramp."""
    half = head_dim // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    if scaling is not None:
        keep = _yarn_ramp(scaling, head_dim, theta)
        freqs = freqs / scaling.factor * (1.0 - keep) + freqs * keep
    return jnp.asarray(freqs, jnp.float32)


def attn_scale(scaling) -> float:
    """Factor on the attention logits' ``1/sqrt(d)``: YaRN's
    ``mscale(factor, mscale_all_dim)**2`` (1 without scaling)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    ``scaling``: a ``YarnConfig`` or None (plain rope)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta, scaling)       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def gated_mlp_spec(d_model: int, d_ff: int):
    return {
        "w_gate": Spec((d_model, d_ff), ("embed", "mlp")),
        "w_up": Spec((d_model, d_ff), ("embed", "mlp")),
        "w_down": Spec((d_ff, d_model), ("mlp", "embed")),
    }


def gated_mlp(params, x, act: str = "silu"):
    a = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = _act(act)(a) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"],
                      preferred_element_type=reduce_dtype(h.dtype))


def mlp_spec(d_model: int, d_ff: int):
    """Non-gated MLP (whisper-style)."""
    return {
        "w_up": Spec((d_model, d_ff), ("embed", "mlp")),
        "b_up": Spec((d_ff,), ("mlp",), init="zeros"),
        "w_down": Spec((d_ff, d_model), ("mlp", "embed")),
        "b_down": Spec((d_model,), ("embed",), init="zeros"),
    }


def mlp(params, x, act: str = "gelu"):
    h = _act(act)(jnp.einsum("...d,df->...f", x, params["w_up"])
                  + params["b_up"].astype(x.dtype))
    return (jnp.einsum("...f,fd->...d", h, params["w_down"],
                       preferred_element_type=reduce_dtype(h.dtype))
            + params["b_down"].astype(x.dtype))


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def embedding_spec(vocab: int, d_model: int):
    return {"table": Spec((vocab, d_model), ("vocab", "embed"), scale=1.0)}


def embed(params, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return params["table"].astype(dtype)[tokens]


def unembed_spec(vocab: int, d_model: int):
    return {"w": Spec((d_model, vocab), ("embed", "vocab"))}


def unembed(params, x) -> jax.Array:
    return jnp.einsum("...d,dv->...v", x, params["w"])


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None,
                 z_weight: float = 0.0):
    """Token-level cross-entropy in fp32; returns (mean_loss, aux)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - target
    if z_weight:
        nll = nll + z_weight * jnp.square(lse)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
