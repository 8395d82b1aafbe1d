"""Plain float32 reference of DeepSeek-V2-Lite as a split-NN member's
bottom tower over token ids, with the Table 1 master.

Written from the published description (DeepSeek-V2, arXiv:2405.04434,
and the model's ``config.json`` and modelling code on the Hugging Face
hub), not from the program. The member's tower is a list of blocks in
the configuration's block language (``kind:key=val,...``):

- ``tokens``: the token-embedding table, looked up by id;
- ``mla``: ``h + MLA(RMSNorm(h))``. Multi-head latent attention with
  no query LoRA: per head a 128-wide query without position and a
  64-wide rotary one; keys and values decompressed from a 512-wide
  latent (RMSNorm'd) of the input, with a 64-wide rotary key shared by
  the heads; causal softmax over ``1/sqrt(192)`` times YaRN's
  ``mscale(40, 0.707)**2``; the rotary frequencies stretched by YaRN
  (factor 40 over an original 4,096 positions, ramp between 32 and 1
  rotations);
- ``ffn``: ``h + W_down(SiLU(W_gate x) * W_up x)``, ``x = RMSNorm(h)``;
- ``moe``: ``h + sum_e g_e(x) E_e(x) + S(x)``: softmax scores over all
  64 experts, the top 6 taken greedily with their scores as weights
  (``norm_topk_prob`` false, ``routed_scaling_factor`` 1); of the
  routed experts only the ``held`` ones from ``shard * held`` are
  present (the chip's share), each a SiLU-gated MLP; ``S`` the two
  shared experts as one SiLU-gated MLP of twice the expert width;
- ``rmsnorm``: the final norm; then an ``mlp`` head after mean pooling
  over the positions, as in ``references/split_nn.py``.

The master's bottom (``towers.master_bottom``) and the top are the
``mlp`` towers of ``references/split_nn.py``; the loss is mean binary
cross-entropy, the update plain SGD.

Departures, each noted where it is made:
- The MoE is dense-masked: each held expert is applied to every token
  and weighted by its gate where the token chose it (0 elsewhere).
- Routing is handed in: at each compared step the reference routes by
  the program's own top-6 choices (``routes``), since a choice flips
  on a rounding when two scores nearly tie. The reference still takes
  its own top 6, and ``route_flip_margin`` reads how far the handed-in
  choice is from it (below).
- The rotary dims are rotated in halves (``rotate_half``) without the
  checkpoint's interleaved order: with weights drawn from a seed that
  is a fixed permutation of the rotary projections' columns.
- The auxiliary balance loss is left out (the catalog gives no
  coefficient).
- Weights are drawn from a seed; the checkpoint is bf16, this runs
  float32.

Every matrix product goes through ``split_nn.mm`` at the stated
precision (``highest``; ``high`` for the control). The tower runs in
blocks: each block's forward and its backward are jitted on their own,
keeping only the blocks' inputs, so that a step fits on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.split_nn import (init_tower, mm, parse_block,
                                           resolve, tower_flops)
from chipbench.references.split_nn import apply_tower as mlp_apply

NEG = -1e30


# ---------------------------------------------------------------------------
# the block language
# ---------------------------------------------------------------------------


def parse(text: str) -> Dict[str, Any]:
    """A block with every number as a number (floats too)."""
    b = parse_block(text)
    for k, v in list(b.items()):
        if isinstance(v, str) and k != "kind":
            b[k] = float(v)
    return b


def _key(b: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in b.items()))


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies, in float64 on the host."""
    def corr(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * (1.0 - keep) + extra * keep


def rope(x, positions, b):
    """Rotate the last dim of ``x`` (..., s, [heads,] dim) by position."""
    dim = x.shape[-1]
    if b.get("yarn_factor"):
        inv = yarn_inv_freq(dim, b.get("theta", 10000.0), b["yarn_factor"],
                            int(b["yarn_original"]),
                            b.get("beta_fast", 32.0), b.get("beta_slow", 1.0))
        m = (yarn_get_mscale(b["yarn_factor"], b.get("mscale", 1.0))
             / yarn_get_mscale(b["yarn_factor"], b.get("mscale_all_dim", 0)))
    else:
        base = b.get("theta", 10000.0)
        inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        m = 1.0
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)                                    # (s, dim/2)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 4:                                          # (n, s, h, d)
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(b) -> float:
    scale = (int(b["nope"]) + int(b["rope"])) ** -0.5
    if b.get("yarn_factor") and b.get("mscale_all_dim"):
        scale *= yarn_get_mscale(b["yarn_factor"], b["mscale_all_dim"]) ** 2
    return scale


def silu_mlp(p, x, prec):
    return mm(jax.nn.silu(mm(x, p["w_gate"], prec)) * mm(x, p["w_up"], prec),
              p["w_down"], prec)


def flip_margin(scores, own, used):
    """Over tokens whose used top-k differs from the scores' own: the
    score the best expert left out had, less the score of the worst one
    taken in its place, over the former (0 where the sets agree)."""
    in_own = jnp.any(used[:, :, None] == own[:, None, :], -1)
    in_used = jnp.any(own[:, :, None] == used[:, None, :], -1)
    s_used = jnp.take_along_axis(scores, used, -1)
    s_own = jnp.take_along_axis(scores, own, -1)
    worst_taken = jnp.min(jnp.where(in_own, jnp.inf, s_used), -1)
    best_left = jnp.max(jnp.where(in_used, -jnp.inf, s_own), -1)
    differ = jnp.any(~in_own, -1)
    gap = jnp.where(differ, (best_left - worst_taken)
                    / jnp.where(differ, best_left, 1.0), 0.0)
    return jnp.max(gap)


# ---------------------------------------------------------------------------
# blocks: forward of (params, h) at a precision
# ---------------------------------------------------------------------------


def mla_block(b, p, h, prec):
    n, s, d = h.shape
    H, nope, rp, vd = (int(b["heads"]), int(b["nope"]), int(b["rope"]),
                       int(b["v"]))
    eps = b.get("eps", 1e-6)
    a = p["attn"]
    x = rmsnorm(h, p["norm"]["scale"], eps).reshape(n * s, d)
    pos = jnp.arange(s)
    q_nope = mm(x, a["wq_nope"].reshape(d, H * nope), prec)
    q_pe = mm(x, a["wq_rope"].reshape(d, H * rp), prec)
    ckv = rmsnorm(mm(x, a["w_dkv"], prec), a["kv_norm"]["scale"], eps)
    k_pe = rope(mm(x, a["w_kr"], prec).reshape(n, s, rp), pos, b)
    r = ckv.shape[-1]
    k_nope = mm(ckv, a["w_uk"].reshape(r, H * nope), prec)
    v = mm(ckv, a["w_uv"].reshape(r, H * vd), prec)
    q = jnp.concatenate(
        [q_nope.reshape(n, s, H, nope),
         rope(q_pe.reshape(n, s, H, rp), pos, b)], -1)
    k = jnp.concatenate(
        [k_nope.reshape(n, s, H, nope),
         jnp.broadcast_to(k_pe[:, :, None, :], (n, s, H, rp))], -1)
    qh = q.transpose(0, 2, 1, 3)                             # (n, H, s, qk)
    kh = k.transpose(0, 2, 3, 1)                             # (n, H, qk, s)
    scores = mm(qh, kh, prec) * softmax_scale(b)
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, NEG), -1)
    o = mm(probs, v.reshape(n, s, H, vd).transpose(0, 2, 1, 3), prec)
    o = o.transpose(0, 2, 1, 3).reshape(n * s, H * vd)
    out = mm(o, a["wo"].reshape(H * vd, d), prec)
    return h + out.reshape(n, s, d)


def ffn_block(b, p, h, prec):
    n, s, d = h.shape
    x = rmsnorm(h, p["norm"]["scale"], b.get("eps", 1e-6)).reshape(n * s, d)
    return h + silu_mlp(p["mlp"], x, prec).reshape(n, s, d)


def moe_route(b, p, h, prec, swap: bool = False):
    """The gate's own top-k at each token (``swap``: the k-th choice of
    token 0 swapped for the (k+1)-th, a planted fault)."""
    n, s, d = h.shape
    k = int(b["top_k"])
    x = rmsnorm(h, p["norm"]["scale"], b.get("eps", 1e-6)).reshape(n * s, d)
    scores = jax.nn.softmax(mm(x, p["router"], prec), -1)
    top = jax.lax.top_k(scores, k + 1)[1]
    own = top[:, :k]
    if swap:
        own = own.at[0, k - 1].set(top[0, k])
    return own


def moe_block(b, p, h, routes, prec, zero_expert: int = -1):
    """Dense-masked share of the MoE: every held expert on every token,
    weighted by its gate where the token's ``routes`` name it. Returns
    the block's output and the route flip margin."""
    n, s, d = h.shape
    k = int(b["top_k"])
    held = int(b.get("held", b["experts"]))
    first = int(b.get("shard", 0)) * held
    x = rmsnorm(h, p["norm"]["scale"], b.get("eps", 1e-6)).reshape(n * s, d)
    scores = jax.nn.softmax(mm(x, p["router"], prec), -1)   # (t, E)
    own = jax.lax.top_k(scores, k)[1]
    gates = jnp.take_along_axis(scores, routes, -1)
    if int(b.get("norm_topk", 1)):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * b.get("scaling", 1.0)
    y = silu_mlp(p["shared"], x, prec) if "shared" in p else 0.0
    for j in range(held):
        w_j = jnp.sum(jnp.where(routes == first + j, gates, 0.0), -1)
        if j == zero_expert:
            continue
        e = {name: p[name][j] for name in ("w_gate", "w_up", "w_down")}
        y = y + w_j[:, None] * silu_mlp(e, x, prec)
    return h + y.reshape(n, s, d), flip_margin(scores, own, routes)


# ---------------------------------------------------------------------------
# jitted block programs (one per distinct block and precision)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fwd(bkey, prec: str, zero_expert: int = -1):
    b = dict(bkey)
    if b["kind"] == "moe":
        return jax.jit(lambda p, h, r: moe_block(b, p, h, r, prec,
                                                 zero_expert))
    fn = {"mla": mla_block, "ffn": ffn_block}.get(b["kind"])
    if fn is None:                                           # rmsnorm
        return jax.jit(lambda p, h: rmsnorm(h, p["scale"],
                                            b.get("eps", 1e-6)))
    return jax.jit(lambda p, h: fn(b, p, h, prec))


@functools.lru_cache(maxsize=None)
def _bwd(bkey, prec: str, zero_expert: int = -1):
    """(params, input, [routes,] cotangent) -> (params' and input's
    cotangents), the forward recomputed."""
    b = dict(bkey)
    if b["kind"] == "moe":
        def f(p, h, r, g):
            _, vjp = jax.vjp(lambda p_, h_: moe_block(
                b, p_, h_, r, prec, zero_expert)[0], p, h)
            return vjp(g)
        return jax.jit(f)
    fwd = _fwd(bkey, prec)

    def f(p, h, g):
        _, vjp = jax.vjp(fwd, p, h)
        return vjp(g)
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _route(bkey, prec: str, swap: bool):
    b = dict(bkey)
    return jax.jit(lambda p, h: moe_route(b, p, h, prec, swap))


def bce(lg, y):
    return jnp.mean(jnp.clip(lg, 0) - lg * y
                    + jnp.log1p(jnp.exp(-jnp.abs(lg))))


# ---------------------------------------------------------------------------
# the split-NN model
# ---------------------------------------------------------------------------


class Model:
    """The configuration's split-NN with the DeepSeek-V2-Lite member."""

    def __init__(self, cfg: Dict[str, Any]):
        data, emb = cfg["data"], int(cfg["embedding_dim"])
        towers = cfg["towers"]
        self.cfg = cfg
        self.seq = int(data["member_tokens"]["seq_len"])
        self.blocks = [parse(t) for t in towers["bottom"][:-1]]
        tok = self.blocks[0]
        if tok["kind"] != "tokens" or any(
                b["kind"] not in ("mla", "ffn", "moe", "rmsnorm")
                for b in self.blocks[1:]):
            raise ValueError("the member tower must be tokens, then mla, "
                             "ffn, moe and rmsnorm blocks, then one mlp")
        self.vocab, self.dim = int(tok["vocab"]), int(tok["dim"])
        self.head = resolve(towers["bottom"][-1:], self.dim, emb)
        self.bottom = resolve(towers["master_bottom"],
                              data["master_features"], emb)
        self.top = resolve(towers["top"], emb, data["n_items"])
        self.lr = float(cfg["lr"])
        self.moe = [i for i, b in enumerate(self.blocks) if b["kind"] == "moe"]

    # -- weights ----------------------------------------------------------
    def _init_block(self, b, key):
        d = self.dim
        n = jax.random.normal

        def w(k, shape, fan_in):
            return n(k, shape, jnp.float32) / np.sqrt(fan_in)
        ks = [jax.random.fold_in(key, i) for i in range(12)]
        ones = {"scale": jnp.ones((d,), jnp.float32)}
        kind = b["kind"]
        if kind == "tokens":
            return {"table": 0.02 * n(key, (self.vocab, d), jnp.float32)}
        if kind == "rmsnorm":
            return ones
        if kind == "mla":
            H, nope, rp, vd, r = (int(b[x]) for x in
                                  ("heads", "nope", "rope", "v", "kv_lora"))
            return {"norm": ones, "attn": {
                "wq_nope": w(ks[0], (d, H, nope), d),
                "wq_rope": w(ks[1], (d, H, rp), d),
                "w_dkv": w(ks[2], (d, r), d),
                "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
                "w_kr": w(ks[3], (d, rp), d),
                "w_uk": w(ks[4], (r, H, nope), r),
                "w_uv": w(ks[5], (r, H, vd), r),
                "wo": w(ks[6], (H, vd, d), H * vd)}}

        def gated(k, f, lead=()):
            k1, k2, k3 = (jax.random.fold_in(k, i) for i in range(3))
            return {"w_gate": w(k1, lead + (d, f), d),
                    "w_up": w(k2, lead + (d, f), d),
                    "w_down": w(k3, lead + (f, d), f)}
        if kind == "ffn":
            return {"norm": ones, "mlp": gated(ks[0], int(b["hidden"]))}
        f, held = int(b["hidden"]), int(b.get("held", b["experts"]))
        out = {"norm": ones,
               "router": w(ks[0], (d, int(b["experts"])), d),
               **gated(ks[1], f, (held,))}
        if int(b.get("shared", 0)):
            out["shared"] = gated(ks[2], int(b["shared"]) * f)
        return out

    def init(self, key) -> Dict[str, Any]:
        """Every party's weights from one key, in one jitted call, in the
        layout the program's checkpoints take."""
        def make(k):
            km = jax.random.fold_in(k, 2)
            member = [self._init_block(b, jax.random.fold_in(km, i))
                      for i, b in enumerate(self.blocks)]
            member.append(init_tower(self.head, jax.random.fold_in(
                km, len(self.blocks)))[0])
            return {"bottom": init_tower(self.bottom,
                                         jax.random.fold_in(k, 0)),
                    "top": init_tower(self.top, jax.random.fold_in(k, 1)),
                    "members": [member]}
        return jax.jit(make)(key)

    # -- one SGD step, in blocks -----------------------------------------
    @functools.cached_property
    def _head(self):
        def loss(bottom, top, head, h, xm, y, prec):
            u = mlp_apply(self.bottom, bottom, xm, prec) \
                + mlp_apply(self.head, [head], jnp.mean(h, 1), prec)
            return bce(mlp_apply(self.top, top, u, prec), y)

        def f(bottom, top, head, h, xm, y, prec):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
                bottom, top, head, h, xm, y, prec)
        return jax.jit(f, static_argnums=(6,))

    @functools.cached_property
    def _embed(self):
        def embed_bwd(table, ids, g):
            return {"table": jnp.zeros_like(table).at[ids].add(g)}
        return (jax.jit(lambda p, ids: p["table"][ids]), jax.jit(embed_bwd))

    @functools.cached_property
    def _update(self):
        return jax.jit(lambda w, g: jax.tree.map(
            lambda p, gg: p - self.lr * gg, w, g))

    def sgd_step(self, w, xm, tokens, y, routes: Optional[Sequence] = None,
                 prec: str = "highest", zero_expert: int = -1,
                 swap: bool = False):
        """One plain SGD step on one batch: ``(loss, grads, new weights,
        routes, margin)``. ``routes`` (one (tokens, top_k) array per
        ``moe`` block) routes the MoE blocks; without it the gate's own
        top-k does (``swap``: token 0's k-th choice swapped for its
        (k+1)-th in every ``moe`` block, a planted fault).
        ``zero_expert``: that held expert's output left out, a planted
        fault. ``margin``: the largest route flip margin over the
        blocks."""
        mem = w["members"][0]
        embed, embed_bwd = self._embed
        h = embed(mem[0], tokens)
        ins, used, margins = [], [], []
        for i, b in enumerate(self.blocks[1:], 1):
            ins.append(h)
            if b["kind"] == "moe":
                r = (routes[len(used)] if routes is not None else
                     _route(_key(b), prec, swap)(mem[i], h))
                h, m = _fwd(_key(b), prec, zero_expert)(mem[i], h, r)
                used.append(r)
                margins.append(m)
            else:
                h = _fwd(_key(b), prec)(mem[i], h)
        loss, (g_bottom, g_top, g_head, g) = self._head(
            w["bottom"], w["top"], mem[-1], h, xm, y, prec)
        g_mem = [None] * len(mem)
        g_mem[-1] = g_head
        for i in range(len(self.blocks) - 1, 0, -1):
            b = self.blocks[i]
            if b["kind"] == "moe":
                g_mem[i], g = _bwd(_key(b), prec, zero_expert)(
                    mem[i], ins[i - 1], used[self.moe.index(i)], g)
            else:
                g_mem[i], g = _bwd(_key(b), prec)(mem[i], ins[i - 1], g)
        g_mem[0] = embed_bwd(mem[0]["table"], tokens, g)
        del ins, g
        grads = {"bottom": g_bottom, "top": g_top, "members": [g_mem]}
        margin = max((float(m) for m in margins), default=0.0)
        return loss, grads, self._update(w, grads), used, margin

    # -- cost ---------------------------------------------------------------
    def member_flops_per_token(self, routed_rows_per_token: float) -> float:
        """Forward FLOPs of the member's tower per token, with
        ``routed_rows_per_token`` rows through held experts (summed over
        the ``moe`` blocks); attention over every key of the sequence."""
        d, s, fl = self.dim, self.seq, 0.0
        for b in self.blocks[1:]:
            if b["kind"] == "mla":
                H, nope, rp, vd, r = (int(b[x]) for x in
                                      ("heads", "nope", "rope", "v",
                                       "kv_lora"))
                fl += 2.0 * (d * H * (nope + rp) + d * (r + rp)
                             + r * H * (nope + vd) + H * vd * d
                             + s * H * (nope + rp + vd))
            elif b["kind"] == "ffn":
                fl += 6.0 * d * int(b["hidden"])
            elif b["kind"] == "moe":
                fl += 2.0 * d * int(b["experts"]) \
                    + 6.0 * d * int(b["hidden"]) * int(b.get("shared", 0))
        if self.moe:
            f = int(self.blocks[self.moe[0]]["hidden"])
            fl += 6.0 * d * f * routed_rows_per_token
        return fl

    def train_flops_per_sample(self, routed_rows_per_token: float) -> float:
        """Model FLOPs of one training sample (a user's sequence): three
        times the forward of the member's tower over its tokens and its
        head, the master's bottom and the top (recomputed forwards not
        counted)."""
        fwd = self.seq * self.member_flops_per_token(routed_rows_per_token)
        fwd += tower_flops(self.head) + tower_flops(self.bottom) \
            + tower_flops(self.top)
        return 3.0 * fwd
