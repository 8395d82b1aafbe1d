"""Mixture-of-experts FFN with top-k routing: capacity-based dispatch for
the scanned LLM stacks, and a dropless expert share for a tower that
holds some of the experts (:func:`moe_share`).

Dispatch is scatter-based (memory-safe): tokens are placed into a
per-expert capacity buffer with ``.at[].add`` using positions from a
token-priority cumsum — no (tokens, experts, capacity) one-hot tensor is
ever materialized. Experts are sharded over the ``model`` axis (expert
parallelism); XLA lowers the buffer exchange to an all-to-all-like
collective. Shared experts (DeepSeek style) run densely on every token.

Aux losses: GShard load-balance loss and router z-loss, returned per call
and averaged over layers by the caller.

Gating follows the HF config's keys on ``MoEConfig``: softmax scores,
top-k, renormalized only where ``norm_topk_prob``, times
``routed_scaling_factor``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.params import Spec
from repro.sharding import constrain
from repro.sharding.rules import reduce_dtype


def moe_spec(cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    # experts_dp resolves to replication: data-parallel expert compute
    # with FSDP-sharded weights (§Perf lever for small-expert MoEs)
    e_ax = "experts" if cfg.moe_expert_parallel else "experts_dp"
    spec = {
        "router": Spec((d, m.num_experts), ("embed", "experts_dp"),
                       dtype=jnp.float32),
        "w_gate": Spec((m.num_experts, d, m.d_expert),
                       (e_ax, "embed", "expert_mlp")),
        "w_up": Spec((m.num_experts, d, m.d_expert),
                     (e_ax, "embed", "expert_mlp")),
        "w_down": Spec((m.num_experts, m.d_expert, d),
                       (e_ax, "expert_mlp", "embed")),
    }
    if m.num_shared:
        spec["shared"] = layers.gated_mlp_spec(d, m.num_shared * m.d_expert)
    return spec


def gate_scores(x, router) -> jax.Array:
    """The router's softmax scores over every expert, in float32."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router)
    return jax.nn.softmax(logits, axis=-1)


def top_experts(scores, k: int) -> jax.Array:
    """Greedy top-k: each token's ``k`` highest-scoring experts."""
    return jax.lax.top_k(scores, k)[1]


def gates(m, top_scores) -> jax.Array:
    """Weights of the chosen experts from their scores (``m``: a
    ``MoEConfig``)."""
    if m.scoring_func != "softmax":
        raise ValueError(f"scoring_func {m.scoring_func!r}: only softmax")
    if m.norm_topk_prob:
        top_scores = top_scores / jnp.maximum(
            top_scores.sum(-1, keepdims=True), 1e-9)
    if m.routed_scaling_factor != 1.0:
        top_scores = top_scores * m.routed_scaling_factor
    return top_scores


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(4, (c + 3) // 4 * 4)


def moe_ffn(cfg: ModelConfig, params, x, act: str = "silu"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if cfg.moe_group_dispatch:
        return moe_ffn_grouped(cfg, params, x, act)
    return moe_ffn_global(cfg, params, x, act)


def moe_ffn_global(cfg: ModelConfig, params, x, act: str = "silu"
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Baseline: GLOBAL token-priority dispatch — the capacity cumsum runs
    over the full (sharded) token dim, so SPMD lowers it to cross-device
    prefix collectives. Kept as the §Perf baseline."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"])                       # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, sel = jax.lax.top_k(probs, m.top_k)              # (t, k)
    gate_vals = gates(m, gate_vals)

    # --- aux losses (GShard) ---------------------------------------------
    me = probs.mean(axis=0)                                     # (E,)
    onehot_top1 = jax.nn.one_hot(sel[:, 0], m.num_experts)
    ce = onehot_top1.mean(axis=0)
    aux = {
        "load_balance": m.num_experts * jnp.sum(me * ce),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))),
    }

    # --- capacity dispatch -------------------------------------------------
    cap = _capacity(t, cfg)
    sel_flat = sel.reshape(-1)                                  # (t*k,) slot-major rows
    # priority: token order within each expert, over all (t*k) assignments
    onehot = jax.nn.one_hot(sel_flat, m.num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # (t*k, E)
    pos = jnp.take_along_axis(pos, sel_flat[:, None], axis=1)[:, 0]
    keep = pos < cap
    idx_e = jnp.where(keep, sel_flat, m.num_experts)            # overflow row
    idx_c = jnp.where(keep, pos, 0)

    x_rep = jnp.repeat(xf, m.top_k, axis=0)                     # (t*k, d)
    buf = jnp.zeros((m.num_experts + 1, cap, d), x.dtype)
    buf = buf.at[idx_e, idx_c].add(x_rep)
    buf = constrain(buf[:m.num_experts], ("experts", None, "embed"))

    # --- expert computation (grouped gated MLP) ---------------------------
    a = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(a) if act == "silu" else jax.nn.gelu(a)
    out = jnp.einsum("ecf,efd->ecd", h * u, params["w_down"],
                      preferred_element_type=reduce_dtype(h.dtype))
    out = jnp.concatenate(
        [out, jnp.zeros((1, cap, d), out.dtype)], axis=0)       # overflow row

    # --- combine ------------------------------------------------------------
    gathered = out[idx_e, idx_c]                                # (t*k, d)
    w = (gate_vals.reshape(-1) * keep).astype(x.dtype)
    y = (gathered * w[:, None]).reshape(t, m.top_k, d).sum(axis=1)

    if m.num_shared:
        y = y + layers.gated_mlp(params["shared"], xf, act)
    return y.reshape(b, s, d), aux


MOE_DISPATCH_CHUNK = 128


def moe_ffn_grouped(cfg: ModelConfig, params, x, act: str = "silu"
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Beyond-paper §Perf lever: GROUP-LOCAL one-hot EINSUM dispatch
    (GShard grouping, chunked).

    Two fixes vs the baseline (validated in EXPERIMENTS.md §Perf):
    1. routing positions come from a cumsum *within* each 256-token
       chunk of a sequence row, so no cross-device prefix collectives;
    2. dispatch/combine are dense one-hot einsums instead of
       scatter/gather — XLA's scatter partitioner replicates the f32
       capacity buffer across the model axis and all-reduces it (7.9 GiB
       per MoE layer on granite); einsums partition cleanly.

    Capacity is enforced per chunk (out-of-capacity one_hot rows are all
    zero, which drops the token exactly like the baseline's keep-mask).
    """
    m = cfg.moe
    b, s, d = x.shape
    e_ax = "experts" if cfg.moe_expert_parallel else "experts_dp"

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, sel = jax.lax.top_k(probs, m.top_k)          # (b, s, k)
    gate_vals = gates(m, gate_vals)

    me = probs.mean(axis=(0, 1))
    ce = jax.nn.one_hot(sel[..., 0], m.num_experts).mean(axis=(0, 1))
    aux = {
        "load_balance": m.num_experts * jnp.sum(me * ce),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))),
    }

    chunk = min(MOE_DISPATCH_CHUNK, s)
    if s % chunk:
        chunk = s
    g = s // chunk                                          # chunks per row
    cap = _capacity(chunk, cfg)
    tk = chunk * m.top_k

    sel_c = sel.reshape(b, g, tk)
    gate_c = gate_vals.reshape(b, g, tk)
    oh_e = jax.nn.one_hot(sel_c, m.num_experts, dtype=x.dtype)
    pos = jnp.cumsum(oh_e, axis=2) - oh_e                   # chunk-local
    pos = jnp.take_along_axis(pos, sel_c[..., None], axis=3)[..., 0]
    oh_c = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype)
    # D[b,g,t,e,c]: dispatch one-hot; combine weights fold in the gate
    disp = jnp.einsum("bgte,bgtc->bgtec", oh_e, oh_c)
    comb = disp * gate_c[..., None, None].astype(x.dtype)

    x_rep = jnp.repeat(x.reshape(b, g, chunk, d), m.top_k, axis=2)
    buf = jnp.einsum("bgtec,bgtd->begcd", disp, x_rep)
    buf = buf.reshape(b, m.num_experts, g * cap, d)
    buf = constrain(buf, ("batch", e_ax, None, "embed"))

    # ZeRO-3 semantics: expert weights are STORED d-sharded (FSDP) but
    # COMPUTED gathered — without this constraint XLA contracts over the
    # sharded d and all-reduces the (b,e,cap,d_expert) activation
    # (16 GiB/layer on jamba) instead of gathering 0.4 GiB of weights.
    w_gate = constrain(params["w_gate"], (e_ax, None, None))
    w_up = constrain(params["w_up"], (e_ax, None, None))
    w_down = constrain(params["w_down"], (e_ax, None, None))

    a = jnp.einsum("becd,edf->becf", buf, w_gate)
    u = jnp.einsum("becd,edf->becf", buf, w_up)
    h = jax.nn.silu(a) if act == "silu" else jax.nn.gelu(a)
    out = jnp.einsum("becf,efd->becd", h * u, w_down,
                      preferred_element_type=reduce_dtype(h.dtype))
    out = out.reshape(b, m.num_experts, g, cap, d)

    y = jnp.einsum("bgtec,begcd->bgtd", comb, out)
    y = y.reshape(b, g, chunk, m.top_k, d).sum(axis=3).reshape(b, s, d)

    if m.num_shared:
        y = y + layers.gated_mlp(params["shared"], x, act)
    return y, aux


# ---------------------------------------------------------------------------
# expert share: the experts one chip holds, routed over all, dropless
# ---------------------------------------------------------------------------


def share_rows(tokens: int, top_k: int, held: int) -> int:
    """Rows of the sorted buffer: every assignment a token can make to a
    held expert, so that no routing drops one."""
    return tokens * min(top_k, held)


def expert_ffn(xs, params, sizes, act: str = "silu"):
    """Each held expert's gated MLP over its rows: ``xs`` sorted by
    expert, ``sizes[j]`` rows for the ``j``-th held expert, then rows of
    no expert (their output is left undefined). Grouped products over
    ragged groups (``jax.lax.ragged_dot``), no capacity buffer."""
    a = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    h = layers._act(act)(a) * u
    return jax.lax.ragged_dot(h, params["w_down"], sizes)


def moe_share(m, params, x, *, first: int, routes=None,
              act: str = "silu") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """A dropless share of an expert-parallel MoE FFN (DeepSeek-V2).

    ``x``: (t, d). ``params``: ``router`` (d, E) over all ``E`` experts,
    ``w_gate``/``w_up`` (held, d, f) and ``w_down`` (held, f, d) of the
    experts ``first .. first + held - 1`` this share holds, and
    ``shared`` (the always-on experts' gated MLP) where ``m.num_shared``.
    Every token routes over all ``E`` experts with ``m``'s gating (or by
    ``routes``, (t, k) expert ids, where given); the share computes its
    held experts' part for the rows routed to them, sorted by expert into
    ragged groups, plus the shared experts. Absent experts add nothing
    here: summed over all shares, with the shared experts once, the
    result is the whole layer's.

    Returns ``(y, aux)``: ``aux["routes"]`` (t, k) int32 expert ids,
    ``aux["rows"]`` (held,) int32 rows routed to each held expert,
    ``aux["dropped"]`` () int32 routed rows left uncomputed (0).
    """
    held = params["w_gate"].shape[0]
    t = x.shape[0]
    k = m.top_k
    with jax.named_scope("moe.route"):
        scores = gate_scores(x, params["router"])
        sel = top_experts(scores, k) if routes is None else routes
        g = gates(m, jnp.take_along_axis(scores, sel, axis=-1))
        local = sel.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)                  # held = none
        order = jnp.argsort(key, stable=True)[:share_rows(t, k, held)]
        sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        valid = jnp.arange(order.shape[0]) < sizes.sum()
        tok = order // k
    with jax.named_scope("moe.experts"):
        # rows past the groups are zeroed both ways, so that what a
        # grouped product leaves there never reaches x or its gradient
        xs = jnp.where(valid[:, None], x[tok], 0.0)
        o = expert_ffn(xs, params, sizes, act)
        w = jnp.where(valid, g.reshape(-1)[order], 0.0).astype(o.dtype)
        o = jnp.where(valid[:, None], o, 0.0) * w[:, None]
        y = jnp.zeros_like(x).at[tok].add(o)
    if m.num_shared:
        with jax.named_scope("moe.shared"):
            y = y + layers.gated_mlp(params["shared"], x, act)
    aux = {"routes": sel.astype(jnp.int32), "rows": sizes,
           "dropped": (mine.sum() - valid.sum()).astype(jnp.int32)}
    return y, aux


def init_share(key, m, d: int, first: int, held: int) -> Dict[str, jax.Array]:
    """Weights of a share: the router over all experts, the held experts
    ``first .. first + held - 1`` (each drawn from its own global index,
    so every share holds the same experts the whole layer would) and the
    shared experts; normal over the square root of the fan-in."""
    f = m.d_expert
    n = jax.random.normal
    ks = [jax.random.fold_in(key, i) for i in range(5)]

    def experts(k, shape, fan_in):
        return jax.vmap(lambda e: n(jax.random.fold_in(k, e), shape,
                                    jnp.float32))(
            first + jnp.arange(held)) / jnp.sqrt(float(fan_in))
    out = {"router": n(ks[0], (d, m.num_experts), jnp.float32)
           / jnp.sqrt(float(d)),
           "w_gate": experts(ks[1], (d, f), d),
           "w_up": experts(ks[2], (d, f), d),
           "w_down": experts(ks[3], (f, d), f)}
    if m.num_shared:
        fs = m.num_shared * f
        k = [jax.random.fold_in(ks[4], i) for i in range(3)]
        out["shared"] = {
            "w_gate": n(k[0], (d, fs), jnp.float32) / jnp.sqrt(float(d)),
            "w_up": n(k[1], (d, fs), jnp.float32) / jnp.sqrt(float(d)),
            "w_down": n(k[2], (fs, d), jnp.float32) / jnp.sqrt(float(fs))}
    return out


def share_axes(m) -> Dict[str, object]:
    """Logical axes of :func:`init_share`'s tree."""
    out = {"router": ("embed", None),
           "w_gate": ("experts", "embed", "expert_mlp"),
           "w_up": ("experts", "embed", "expert_mlp"),
           "w_down": ("experts", "expert_mlp", "embed")}
    if m.num_shared:
        out["shared"] = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                         "w_down": ("mlp", "embed")}
    return out
