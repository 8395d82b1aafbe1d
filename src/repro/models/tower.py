"""Composable member-tower factory for the VFL protocols (DESIGN.md §12).

A tower is a sequence of *block configs* (xformers-style: each block is
a small dict of ``kind`` + hyperparameters) resolved against concrete
input/output widths into a :class:`TowerSpec`. The split-NN protocol
builds both its bottom (member) and top (master) models through this
factory; the legacy ``hidden``/``embedding_dim`` MLP path is just the
one-block tower ``mlp_tower(...)`` and stays bit-identical to the
historical ``mlp_init``/``mlp_apply`` pair (seed traces enforce it).

Block kinds
-----------

``embed``      feature chunking + bucketized embedding lookup: the flat
               feature vector is split into ``tokens`` chunks, each
               chunk gets a dense value projection plus a learned
               per-(token, bucket) embedding keyed on the chunk mean,
               plus a positional embedding.  Output is a
               ``(batch, tokens, dim)`` sequence.  Must be first.
``attn_block`` pre-norm transformer block (self-attention + relu MLP,
               both residual) on a 3-D sequence. ``kernel=auto`` runs
               the pallas flash-attention forward on TPU and the
               reference jnp math elsewhere; the backward pass is
               always the reference VJP (pallas_call has no autodiff).
``quantize``   straight-through int8 fake-quantization of activations
               (per-row symmetric, same grid as the wire codec) — lets
               a tower train against the precision it will be served
               and exchanged at.
``mlp``        the legacy relu MLP.  Mean-pools a 3-D sequence first.
               The final block of every tower must be an ``mlp`` (it
               owns the output width).

Decoder blocks over token ids (DeepSeek-V2 style, ``docs/models.md``):

``tokens``     token-embedding lookup: the silo holds int32 token ids
               (its width is the sequence length). Must be first.
``mla``        pre-norm residual multi-head latent attention
               (``models/mla.py``), causal, with YaRN rope scaling.
``ffn``        pre-norm residual dense SiLU-gated FFN.
``moe``        pre-norm residual MoE FFN that holds ``held`` of the
               ``experts`` (those from ``shard * held``), routes over all
               of them and computes its own experts' part, dropless
               (``models/moe.py`` ``moe_share``), plus the shared experts.
``rmsnorm``    a bare RMSNorm (the stack's final norm).

``mla``/``ffn``/``moe`` recompute their forward in the backward pass,
keeping only each block's input (at 16 users x 512 tokens six
DeepSeek-V2-Lite layers' activations would otherwise take ~10 GB of a
16 GB chip). ``apply_kept`` returns those inputs beside the output, and
``vjp_kept`` takes the backward from them block by block, so that a
training step need not run the tower's forward a second time to find
them.

Blocks are written either as dicts or as compact strings
``"kind:key=val,key=val"`` with ``|``-separated integer tuples::

    ("embed:tokens=8,dim=32", "attn_block:heads=4", "mlp:hidden=64|32")

``resolve(blocks, in_dim, out_dim)`` normalizes both forms and
validates the chain; ``init``/``apply`` are the pure param functions;
``logical_axes``/``shard_tower``/``make_tower_rules`` place a large
tower on the local mesh (``launch/mesh.py`` + ``sharding/rules.py``);
``tower_flops`` is the analytic forward cost used by the exchange
account (``launch/exchange.py``) and the benchmark's references.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig
from repro.models import layers, mla, moe
from repro.models import params as prm

BLOCK_KINDS = ("embed", "attn_block", "quantize", "mlp", "tokens", "mla",
               "ffn", "moe", "rmsnorm")
# blocks that run on a (batch, seq, dim) sequence
_SEQ_KINDS = ("attn_block", "mla", "ffn", "moe", "rmsnorm")
_FIRST_KINDS = ("embed", "tokens")

# embed-block bucketization: chunk means of standardized features live
# almost entirely in [-2.5, 2.5]; that range maps linearly onto the
# bucket grid and the ends clip.
_BUCKET_SPAN = 5.0

BlockLike = Union[str, Dict[str, Any]]


# ---------------------------------------------------------------------------
# spec parsing / resolution
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TowerSpec:
    """A resolved tower: normalized block dicts + concrete widths.

    Produced by :func:`resolve` (or the :func:`mlp_tower` /
    :func:`legacy_dims_tower` helpers) — block dicts here always carry
    every hyperparameter explicitly, so ``init``/``apply`` never apply
    defaults.
    """

    blocks: Tuple[Dict[str, Any], ...]
    in_dim: int
    out_dim: int

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(b["kind"] for b in self.blocks)


def parse_block(block: BlockLike) -> Dict[str, Any]:
    """Normalize one block config (string DSL or dict) to a plain dict.

    Strings look like ``"mlp:hidden=64|32"`` or ``"attn_block:heads=4"``;
    ``|`` separates tuple elements, values parse as int when possible.
    """
    if isinstance(block, dict):
        out = dict(block)
        if "kind" not in out:
            raise ValueError(f"tower block {block!r} has no 'kind'")
    elif isinstance(block, str):
        head, _, rest = block.partition(":")
        out = {"kind": head.strip()}
        if rest.strip():
            for item in rest.split(","):
                if "=" not in item:
                    raise ValueError(
                        f"tower block {block!r}: expected key=val, got "
                        f"{item!r}")
                k, _, v = item.partition("=")
                out[k.strip()] = _parse_val(v.strip())
    else:
        raise ValueError(f"tower block must be str or dict, got "
                         f"{type(block).__name__}")
    kind = out["kind"]
    if kind == "attn":               # common shorthand
        kind = out["kind"] = "attn_block"
    if kind not in BLOCK_KINDS:
        raise ValueError(f"unknown tower block kind {kind!r} "
                         f"(expected one of {BLOCK_KINDS})")
    return out


def _parse_val(v: str) -> Any:
    if "|" in v:
        return tuple(_parse_val(e) for e in v.split("|"))
    try:
        return int(v)
    except ValueError:
        return v


_BLOCK_KEYS = {
    "embed": {"tokens", "dim", "buckets"},
    "attn_block": {"heads", "mlp", "kernel"},
    "quantize": {"kernel"},
    "mlp": {"hidden", "final_act"},
    "tokens": {"vocab", "dim"},
    "mla": {"heads", "kv_lora", "nope", "rope", "v", "theta",
            "yarn_factor", "yarn_original", "beta_fast", "beta_slow",
            "mscale", "mscale_all_dim", "eps"},
    "ffn": {"hidden", "eps"},
    "moe": {"experts", "held", "shard", "top_k", "hidden", "shared",
            "norm_topk", "scaling", "eps"},
    "rmsnorm": {"eps"},
}


def check_blocks(blocks: Sequence[BlockLike]) -> List[Dict[str, Any]]:
    """Validate block structure without knowing concrete widths.

    Used by the cluster-spec validator, where ``in_dim`` depends on the
    data provider and is not yet known. Returns the parsed dicts.
    Raises ``ValueError`` on malformed chains.
    """
    if not blocks:
        raise ValueError("tower must have at least one block")
    parsed = [parse_block(b) for b in blocks]
    for i, b in enumerate(parsed):
        kind = b["kind"]
        extra = set(b) - {"kind"} - _BLOCK_KEYS[kind]
        if extra:
            raise ValueError(
                f"tower block {i} ({kind}): unknown keys {sorted(extra)}")
        if kind in _FIRST_KINDS and i != 0:
            raise ValueError(f"{kind!r} must be the first tower block")
        if kind in _SEQ_KINDS:
            if not parsed[:i] or parsed[0]["kind"] not in _FIRST_KINDS:
                raise ValueError(
                    f"{kind!r} needs an 'embed' block first (or "
                    f"'tokens': it runs on the token sequence it "
                    f"produces)")
            if any(p["kind"] == "mlp" for p in parsed[:i]):
                raise ValueError(
                    f"{kind!r} must come before any 'mlp' block — "
                    "'mlp' mean-pools the token sequence to flat "
                    "features, leaving no sequence to attend over")
        if b.get("kernel", "auto") not in ("auto", "pallas", "ref"):
            raise ValueError(
                f"tower block {i} ({kind}): kernel must be "
                f"auto|pallas|ref, got {b.get('kernel')!r}")
    last_real = [b for b in parsed if b["kind"] != "quantize"]
    if not last_real or last_real[-1]["kind"] != "mlp":
        raise ValueError(
            "the last (non-quantize) tower block must be 'mlp' — it "
            "owns the output width")
    return parsed


def resolve(blocks: Sequence[BlockLike], in_dim: int,
            out_dim: int) -> TowerSpec:
    """Resolve block configs + concrete widths into a :class:`TowerSpec`.

    Fills every default, threads widths through the chain, and
    validates shape compatibility (e.g. ``dim % heads == 0``).
    """
    parsed = check_blocks(blocks)
    resolved: List[Dict[str, Any]] = []
    width = int(in_dim)               # current feature width (last axis)
    seq = 0                           # current token count (0 = flat 2-D)
    for i, b in enumerate(parsed):
        kind = b["kind"]
        if kind == "embed":
            tokens = int(b.get("tokens", 8))
            dim = int(b.get("dim", 32))
            buckets = int(b.get("buckets", 32))
            if tokens < 1 or dim < 1 or buckets < 2:
                raise ValueError(
                    f"embed block: tokens/dim >= 1 and buckets >= 2 "
                    f"required, got {tokens}/{dim}/{buckets}")
            chunk = max(1, math.ceil(width / tokens))
            resolved.append({"kind": "embed", "tokens": tokens,
                             "dim": dim, "buckets": buckets,
                             "chunk": chunk, "in_dim": width})
            width, seq = dim, tokens
        elif kind == "attn_block":
            heads = int(b.get("heads", 4))
            ff = int(b.get("mlp", 4 * width))
            if width % heads != 0:
                raise ValueError(
                    f"attn_block: dim {width} not divisible by "
                    f"heads {heads}")
            resolved.append({"kind": "attn_block", "heads": heads,
                             "mlp": ff, "dim": width, "seq": seq,
                             "kernel": b.get("kernel", "auto")})
        elif kind == "quantize":
            resolved.append({"kind": "quantize",
                             "kernel": b.get("kernel", "auto")})
        elif kind == "tokens":
            vocab, dim = int(b.get("vocab", 0)), int(b.get("dim", 0))
            if vocab < 1 or dim < 1:
                raise ValueError(f"tokens block: vocab and dim >= 1 "
                                 f"required, got {vocab}/{dim}")
            resolved.append({"kind": "tokens", "vocab": vocab, "dim": dim,
                             "seq": width})
            width, seq = dim, width
        elif kind in ("mla", "ffn", "moe", "rmsnorm"):
            resolved.append(_RESOLVE[kind](b, width, seq))
        else:  # mlp
            hidden = b.get("hidden", ())
            if isinstance(hidden, int):
                hidden = (hidden,)
            hidden = tuple(int(h) for h in hidden)
            dims = (width,) + hidden + (int(out_dim),)
            resolved.append({"kind": "mlp", "dims": dims,
                             "final_act": bool(b.get("final_act",
                                                     True))})
            width, seq = int(out_dim), 0
    return TowerSpec(blocks=tuple(resolved), in_dim=int(in_dim),
                     out_dim=int(out_dim))


def mlp_tower(in_dim: int, hidden: Sequence[int], out_dim: int,
              final_act: bool = True) -> TowerSpec:
    """The legacy MLP as a one-block tower (bit-identical params/math)."""
    return resolve(({"kind": "mlp", "hidden": tuple(hidden),
                     "final_act": final_act},), in_dim, out_dim)


_warned_dims = False


def legacy_dims_tower(dims: Sequence[int],
                      final_act: bool = True) -> TowerSpec:
    """Deprecated-compat shim: a ``bottom_dims``/``top_dims`` tuple as
    an equivalent one-block MLP tower. Warns once per process."""
    global _warned_dims
    if not _warned_dims:
        _warned_dims = True
        warnings.warn(
            "bottom_dims/top_dims tuples are deprecated; express the "
            "model as a TowerSpec (repro.models.tower) instead",
            DeprecationWarning, stacklevel=2)
    dims = tuple(int(d) for d in dims)
    if len(dims) < 2:
        raise ValueError(f"need >= 2 dims, got {dims}")
    return mlp_tower(dims[0], dims[1:-1], dims[-1], final_act=final_act)


# ---------------------------------------------------------------------------
# kernels: reference/pallas forward, reference backward
# ---------------------------------------------------------------------------


def _use_pallas(kernel: str) -> bool:
    if kernel == "pallas":
        return True
    if kernel == "ref":
        return False
    # auto: the pallas kernels run everywhere via interpret mode, but
    # interpret unrolls the grid Python-side — only worth it on TPU.
    return jax.devices()[0].platform == "tpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, kernel: str = "ref", shard=None):
    """Bidirectional multi-head attention, (b, h, s, dh) layout.

    Forward through ``kernels.ops.flash_attention`` (pallas) or the
    reference math; backward is always the reference VJP because
    ``pallas_call`` is not reverse-differentiable. ``shard`` is
    ``(mesh, PartitionSpec)`` of q/k/v under a sharded tower, else None:
    Mosaic kernels cannot be partitioned automatically, so a sharded
    tower runs the kernel per shard under ``shard_map``.
    """
    return _attention_fwd(q, k, v, kernel, shard)[0]


def _attention_fwd(q, k, v, kernel, shard=None):
    if _use_pallas(kernel):
        from repro.kernels.ops import flash_attention
        fa = partial(flash_attention, causal=False)
        if shard is not None:
            mesh, spec = shard
            fa = jax.shard_map(fa, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, check_vma=False)
        out = fa(q, k, v)
    else:
        from repro.kernels.ref import attention_ref
        out = attention_ref(q, k, v, causal=False)
    return out, (q, k, v)


def _attention_bwd(kernel, shard, res, g):
    from repro.kernels.ref import attention_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=False),
        q, k, v)
    return vjp(g)


_attention.defvjp(_attention_fwd, _attention_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant(x, kernel: str = "ref", shard=None):
    """Straight-through int8 fake-quantization (per-row symmetric).

    Forward quantizes+dequantizes on the wire codec's grid (pallas
    ``quantize_int8`` or the reference); backward is identity (STE).
    ``shard`` is ``(mesh, PartitionSpec)`` of the flattened (rows, d)
    activation under a sharded tower, else None (see :func:`_attention`).
    """
    return _fq_fwd(x, kernel, shard)[0]


def _fq_fwd(x, kernel, shard=None):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if _use_pallas(kernel):
        from repro.kernels.ops import quantize_int8 as quant
        if shard is not None:
            mesh, spec = shard
            quant = jax.shard_map(
                quant, mesh=mesh, in_specs=(spec,),
                out_specs=(spec, jax.sharding.PartitionSpec(spec[0])),
                check_vma=False)
        q, scale = quant(x2)
    else:
        from repro.kernels.ref import quantize_int8_ref
        q, scale = quantize_int8_ref(x2)
    y = (q.astype(jnp.float32) * scale[:, None]).astype(x.dtype)
    return y.reshape(shape), None


def _fq_bwd(kernel, shard, _, g):
    return (g,)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# decoder blocks over token ids: resolution
# ---------------------------------------------------------------------------


def _eps(b) -> float:
    return float(b.get("eps", 1e-6))


def _resolve_mla(b, width, seq):
    heads = int(b.get("heads", 0))
    if heads < 1:
        raise ValueError("mla block: heads >= 1 required")
    yarn = None
    if b.get("yarn_factor"):
        yarn = YarnConfig(
            factor=float(b["yarn_factor"]),
            original_max_position=int(b["yarn_original"]),
            beta_fast=float(b.get("beta_fast", 32)),
            beta_slow=float(b.get("beta_slow", 1)),
            mscale=float(b.get("mscale", 1)),
            mscale_all_dim=float(b.get("mscale_all_dim", 0)))
    m = MLAConfig(kv_lora_rank=int(b["kv_lora"]), q_lora_rank=None,
                  rope_head_dim=int(b["rope"]),
                  nope_head_dim=int(b["nope"]), v_head_dim=int(b["v"]))
    cfg = ModelConfig(
        arch_id="tower-mla", family="moe", source="tower block",
        n_layers=1, d_model=width, d_ff=width, vocab=1, n_heads=heads,
        n_kv_heads=heads, head_dim=m.nope_head_dim, attention="mla",
        mla=m, rope_theta=float(b.get("theta", 10_000)),
        rope_scaling=yarn, norm_eps=_eps(b))
    return {"kind": "mla", "dim": width, "seq": seq, "heads": heads,
            "eps": _eps(b), "cfg": cfg}


def _resolve_ffn(b, width, seq):
    return {"kind": "ffn", "dim": width, "seq": seq,
            "hidden": int(b["hidden"]), "eps": _eps(b)}


def _resolve_moe(b, width, seq):
    experts, top_k = int(b["experts"]), int(b["top_k"])
    held = int(b.get("held", experts))
    shard = int(b.get("shard", 0))
    if not 0 < top_k <= experts or not 0 < held <= experts \
            or not 0 <= shard * held <= experts - held:
        raise ValueError(
            f"moe block: need 0 < top_k, held <= experts and the shard's "
            f"experts inside them, got experts={experts} held={held} "
            f"shard={shard} top_k={top_k}")
    m = MoEConfig(num_experts=experts, top_k=top_k,
                  d_expert=int(b["hidden"]),
                  num_shared=int(b.get("shared", 0)),
                  norm_topk_prob=bool(b.get("norm_topk", 1)),
                  routed_scaling_factor=float(b.get("scaling", 1)))
    return {"kind": "moe", "dim": width, "seq": seq, "held": held,
            "first": shard * held, "eps": _eps(b), "moe": m}


def _resolve_rmsnorm(b, width, seq):
    return {"kind": "rmsnorm", "dim": width, "eps": _eps(b)}


_RESOLVE = {"mla": _resolve_mla, "ffn": _resolve_ffn, "moe": _resolve_moe,
            "rmsnorm": _resolve_rmsnorm}


# ---------------------------------------------------------------------------
# init / apply
# ---------------------------------------------------------------------------


def init(spec: TowerSpec, key) -> List[Any]:
    """Initialize tower params: one pytree entry per block.

    Key discipline: a single-block tower consumes ``key`` directly so
    the one-mlp tower reproduces the historical ``mlp_init(key, dims)``
    stream bit-for-bit; multi-block towers fold in the block index.
    """
    params: List[Any] = []
    for bi, b in enumerate(spec.blocks):
        bkey = key if len(spec.blocks) == 1 else jax.random.fold_in(
            key, bi)
        params.append(_BLOCK_INIT[b["kind"]](b, bkey))
    return params


def _init_mlp(b, key):
    # exact legacy mlp_init: fold_in per layer, normal/sqrt(fan_in)
    layers = []
    dims = b["dims"]
    for i, (a, o) in enumerate(zip(dims[:-1], dims[1:])):
        k = jax.random.fold_in(key, i)
        layers.append({
            "w": jax.random.normal(k, (a, o), jnp.float32) / np.sqrt(a),
            "b": jnp.zeros((o,), jnp.float32),
        })
    return layers


def _init_embed(b, key):
    t, c, d, nb = b["tokens"], b["chunk"], b["dim"], b["buckets"]
    k1, k2, k3 = (jax.random.fold_in(key, i) for i in range(3))
    return {
        "w": jax.random.normal(k1, (t, c, d), jnp.float32) / np.sqrt(c),
        "table": 0.02 * jax.random.normal(k2, (t * nb, d), jnp.float32),
        "pos": 0.02 * jax.random.normal(k3, (t, d), jnp.float32),
    }


def _init_attn(b, key):
    d, f = b["dim"], b["mlp"]
    ks = [jax.random.fold_in(key, i) for i in range(6)]
    n = jax.random.normal
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "wq": n(ks[0], (d, d), jnp.float32) / np.sqrt(d),
        "wk": n(ks[1], (d, d), jnp.float32) / np.sqrt(d),
        "wv": n(ks[2], (d, d), jnp.float32) / np.sqrt(d),
        "wo": n(ks[3], (d, d), jnp.float32) / np.sqrt(d),
        "ln2": jnp.ones((d,), jnp.float32),
        "w1": n(ks[4], (d, f), jnp.float32) / np.sqrt(d),
        "b1": jnp.zeros((f,), jnp.float32),
        "w2": n(ks[5], (f, d), jnp.float32) / np.sqrt(f),
        "b2": jnp.zeros((d,), jnp.float32),
    }


def _norm_scale(dim):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def _gated(key, d, f):
    ks = [jax.random.fold_in(key, i) for i in range(3)]
    n = jax.random.normal
    return {"w_gate": n(ks[0], (d, f), jnp.float32) / np.sqrt(d),
            "w_up": n(ks[1], (d, f), jnp.float32) / np.sqrt(d),
            "w_down": n(ks[2], (f, d), jnp.float32) / np.sqrt(f)}


def _init_tokens(b, key):
    return {"table": 0.02 * jax.random.normal(
        key, (b["vocab"], b["dim"]), jnp.float32)}


def _init_mla(b, key):
    return {"norm": _norm_scale(b["dim"]),
            "attn": prm.init_tree(mla.mla_spec(b["cfg"]), key)}


def _init_ffn(b, key):
    return {"norm": _norm_scale(b["dim"]),
            "mlp": _gated(key, b["dim"], b["hidden"])}


def _init_moe(b, key):
    return {"norm": _norm_scale(b["dim"]),
            **moe.init_share(key, b["moe"], b["dim"], b["first"],
                             b["held"])}


_BLOCK_INIT = {"mlp": _init_mlp, "embed": _init_embed,
               "attn_block": _init_attn,
               "quantize": lambda b, key: {},
               "tokens": _init_tokens, "mla": _init_mla, "ffn": _init_ffn,
               "moe": _init_moe,
               "rmsnorm": lambda b, key: _norm_scale(b["dim"])}


def apply(spec: TowerSpec, params: Sequence[Any], x,
          rules=None):
    """Pure forward pass. ``rules`` (a ``MeshRules`` or None) is threaded
    explicitly — contextvars don't survive jit tracing boundaries."""
    return _run(spec, params, x, rules)[0]


def apply_kept(spec: TowerSpec, params: Sequence[Any], x, rules=None):
    """Forward pass that keeps what :func:`vjp_kept` starts from:
    ``(y, aux)``. ``aux["inputs"]``: the input of every block after the
    first, as this forward computed it; ``aux["routes"]``: each ``moe``
    block's (tokens, top_k) int32 expert choices; ``aux["rows"]``: each
    one's (held,) rows routed to its held experts; ``aux["dropped"]``:
    routed rows left uncomputed (0)."""
    inputs: List[jax.Array] = []
    y, sels, rows, dropped = _run(spec, params, x, rules, kept=inputs)
    return y, {"routes": tuple(sels), "rows": tuple(rows),
               "dropped": sum(dropped, jnp.zeros((), jnp.int32)),
               "inputs": tuple(inputs)}


def vjp_kept(spec: TowerSpec, params: Sequence[Any], x, inputs, routes,
             g, rules=None) -> List[Any]:
    """The gradient of ``<apply(spec, params, x), g>`` in ``params``,
    from a forward's kept block ``inputs`` and ``routes``
    (:func:`apply_kept`'s): block by block from the head down, each
    block's VJP taken at its kept input, its input's cotangent handed to
    the block below. A ``moe`` block routes by its kept choices, so that
    the gradient is exactly the sent forward's. No forward of the whole
    tower runs here; a rematerialised block recomputes its own forward
    once, inside its VJP."""
    grads: List[Any] = [None] * len(spec.blocks)
    routes = list(routes)
    for i in reversed(range(len(spec.blocks))):
        b = spec.blocks[i]
        r = routes.pop() if b["kind"] == "moe" else None
        block = partial(_apply_block, b, rules=rules, route=r)
        if i:
            _, vjp, _ = jax.vjp(block, params[i], inputs[i - 1],
                                has_aux=True)
            grads[i], g = vjp(g)
        else:
            _, vjp, _ = jax.vjp(partial(block, x=x), params[0],
                                has_aux=True)
            (grads[0],) = vjp(g)
    return grads


def routed_blocks(spec: TowerSpec) -> List[Dict[str, Any]]:
    """The tower's ``moe`` blocks, in order."""
    return [b for b in spec.blocks if b["kind"] == "moe"]


# blocks whose forward the backward pass recomputes (``jax.checkpoint``)
REMAT_KINDS = ("mla", "ffn", "moe")


def remat_blocks(spec: TowerSpec) -> List[Dict[str, Any]]:
    """The tower's rematerialised blocks, in order."""
    return [b for b in spec.blocks if b["kind"] in REMAT_KINDS]


def _run(spec, params, x, rules=None, kept=None):
    """The blocks in order: ``(y, routes, rows, dropped)``, the last three
    per ``moe`` block. ``kept``, a list, receives the input of every block
    after the first."""
    sels, rows, dropped = [], [], []
    for i, (b, p) in enumerate(zip(spec.blocks, params)):
        if kept is not None and i:
            kept.append(x)
        x, aux = _apply_block(b, p, x, rules)
        if aux is not None:
            sels.append(aux["routes"])
            rows.append(aux["rows"])
            dropped.append(aux["dropped"])
    return x, sels, rows, dropped


def _apply_block(b, p, x, rules=None, route=None):
    """One block, then its output's sharding: ``(y, aux)``, ``aux`` a
    ``moe`` block's routing (None for any other kind)."""
    aux = None
    if b["kind"] == "moe":
        x, aux = _apply_moe(b, p, x, route)
    else:
        x = _BLOCK_APPLY[b["kind"]](b, p, x, rules)
    if x.ndim == 3:
        x = _constrain(x, ("batch", None, None), rules)
    else:
        x = _constrain(x, ("batch", "mlp"), rules)
    return x, aux


def _shard(rules, logical, shape):
    """(mesh, PartitionSpec) a kernel's shard_map runs over, or None."""
    if rules is None:
        return None
    return rules.mesh, rules.act_spec(logical, shape)


def _constrain(x, logical, rules):
    if rules is None:
        return x
    spec = rules.act_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(rules.mesh, spec))


def _apply_mlp(b, p, x, rules=None):
    if x.ndim == 3:                   # sequence -> pooled features
        x = jnp.mean(x, axis=1)
    # exact legacy mlp_apply loop
    n = len(p)
    for i, layer in enumerate(p):
        x = x @ layer["w"] + layer["b"]
        if i < n - 1 or b["final_act"]:
            x = jax.nn.relu(x)
    return x


def _apply_embed(b, p, x, rules=None):
    t, c, nb = b["tokens"], b["chunk"], b["buckets"]
    pad = t * c - x.shape[-1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    xr = x.reshape(x.shape[0], t, c)
    val = jnp.einsum("ntc,tcd->ntd", xr, p["w"])
    mean = jnp.mean(xr, axis=-1)
    ids = jnp.clip(((mean + _BUCKET_SPAN / 2) * (nb / _BUCKET_SPAN))
                   .astype(jnp.int32), 0, nb - 1)
    look = p["table"][jnp.arange(t)[None, :] * nb + ids]
    return val + look + p["pos"][None, :, :]


def _rmsnorm(scale, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _apply_attn(b, p, x, rules=None):
    n, t, d = x.shape
    h = b["heads"]
    dh = d // h
    y = _rmsnorm(p["ln1"], x)
    # (n, t, d) -> (n, h, t, dh) for the flash-attention layout
    q = (y @ p["wq"]).reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    k = (y @ p["wk"]).reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    v = (y @ p["wv"]).reshape(n, t, h, dh).transpose(0, 2, 1, 3)
    o = _attention(q, k, v, b["kernel"],
                   _shard(rules, ("batch", "heads", None, None), q.shape))
    o = o.transpose(0, 2, 1, 3).reshape(n, t, d) @ p["wo"]
    x = x + o
    y = _rmsnorm(p["ln2"], x)
    y = jax.nn.relu(y @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
    return x + y


def _apply_quant(b, p, x, rules=None):
    flat = (math.prod(x.shape[:-1]), x.shape[-1])
    return fake_quant(x, b["kernel"], _shard(rules, ("batch", None), flat))


def _apply_tokens(b, p, x, rules=None):
    return p["table"][x]


def _apply_mla(b, p, x, rules=None):
    def block(p, x):
        with jax.named_scope("mla"):
            h = layers.rmsnorm(p["norm"], x, b["eps"])
            return x + mla.mla_self_attention(b["cfg"], p["attn"], h)
    return jax.checkpoint(block)(p, x)


def _apply_ffn(b, p, x, rules=None):
    def block(p, x):
        with jax.named_scope("ffn.dense"):
            h = layers.rmsnorm(p["norm"], x, b["eps"])
            return x + layers.gated_mlp(p["mlp"], h, "silu")
    return jax.checkpoint(block)(p, x)


def _apply_moe(b, p, x, routes=None):
    n, s, d = x.shape

    def block(p, x, routes):
        h = layers.rmsnorm(p["norm"], x, b["eps"]).reshape(n * s, d)
        y, aux = moe.moe_share(b["moe"], p, h, first=b["first"],
                               routes=routes)
        return x + y.reshape(n, s, d), aux
    return jax.checkpoint(block)(p, x, routes)


def _apply_rmsnorm(b, p, x, rules=None):
    return layers.rmsnorm(p, x, b["eps"])


_BLOCK_APPLY = {"mlp": _apply_mlp, "embed": _apply_embed,
                "attn_block": _apply_attn, "quantize": _apply_quant,
                "tokens": _apply_tokens, "mla": _apply_mla,
                "ffn": _apply_ffn, "rmsnorm": _apply_rmsnorm}


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------


def logical_axes(spec: TowerSpec) -> List[Any]:
    """Per-param logical axis names, matching the ``init`` tree."""
    axes: List[Any] = []
    for b in spec.blocks:
        kind = b["kind"]
        if kind == "mlp":
            axes.append([{"w": ("embed", "mlp"), "b": ("mlp",)}
                         for _ in range(len(b["dims"]) - 1)])
        elif kind == "embed":
            axes.append({"w": (None, None, "mlp"),
                         "table": ("vocab", None),
                         "pos": (None, None)})
        elif kind == "tokens":
            axes.append({"table": ("vocab", None)})
        elif kind == "mla":
            axes.append({"norm": {"scale": (None,)},
                         "attn": prm.axes_tree(mla.mla_spec(b["cfg"]))})
        elif kind == "ffn":
            axes.append({"norm": {"scale": (None,)},
                         "mlp": {"w_gate": ("embed", "mlp"),
                                 "w_up": ("embed", "mlp"),
                                 "w_down": ("mlp", "embed")}})
        elif kind == "moe":
            axes.append({"norm": {"scale": (None,)},
                         **moe.share_axes(b["moe"])})
        elif kind == "rmsnorm":
            axes.append({"scale": (None,)})
        elif kind == "attn_block":
            axes.append({"ln1": (None,),
                         "wq": ("embed", "heads"),
                         "wk": ("embed", "heads"),
                         "wv": ("embed", "heads"),
                         "wo": ("heads", "embed"),
                         "ln2": (None,),
                         "w1": ("embed", "mlp"), "b1": ("mlp",),
                         "w2": ("mlp", "embed"), "b2": (None,)})
        else:
            axes.append({})
    return axes


def make_tower_rules(shard: int):
    """MeshRules for an N-way model-parallel tower over local devices,
    or None when ``shard <= 1`` (the common unsharded path)."""
    if shard <= 1:
        return None
    ndev = len(jax.devices())
    if ndev < shard:
        raise ValueError(
            f"tower_shard={shard} but only {ndev} local device(s); "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"for CPU testing")
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.rules import MeshRules
    return MeshRules(mesh=make_local_mesh(1, shard))


def shard_tower(params: Sequence[Any], spec: TowerSpec, rules):
    """Place tower params per their logical axes (no-op without rules)."""
    if rules is None:
        return list(params)
    axes = logical_axes(spec)
    return jax.tree.map(
        lambda ax, p: jax.device_put(p, rules.param_sharding(ax, p.shape)),
        axes, list(params),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# analytic cost
# ---------------------------------------------------------------------------


def tower_flops(spec: TowerSpec, batch: int) -> float:
    """Analytic forward FLOPs (matmuls only; 2*M*N*K per GEMM)."""
    fl = 0.0
    n = float(batch)
    for b in spec.blocks:
        if b["kind"] == "mlp":
            dims = b["dims"]
            fl += sum(2.0 * n * a * o
                      for a, o in zip(dims[:-1], dims[1:]))
        elif b["kind"] == "embed":
            fl += 2.0 * n * b["tokens"] * b["chunk"] * b["dim"]
        elif b["kind"] == "attn_block":
            t, d, f = b["seq"], b["dim"], b["mlp"]
            fl += 8.0 * n * t * d * d          # qkv + out projections
            fl += 4.0 * n * t * t * d          # scores + weighted sum
            fl += 4.0 * n * t * d * f          # relu MLP
        elif b["kind"] == "mla":
            fl += n * b["seq"] * _mla_token_flops(b["cfg"], b["seq"])
        elif b["kind"] == "ffn":
            fl += 6.0 * n * b["seq"] * b["dim"] * b["hidden"]
        elif b["kind"] == "moe":
            m, d = b["moe"], b["dim"]
            # the held experts' expected share of each token's top_k
            routed = m.top_k * b["held"] / m.num_experts
            fl += n * b["seq"] * (2.0 * d * m.num_experts
                                  + 6.0 * d * m.d_expert
                                  * (m.num_shared + routed))
    return fl


def _mla_token_flops(cfg, seq: int) -> float:
    """Forward FLOPs of one token of an MLA block over ``seq`` keys:
    its projections, and scores and weighted sum over every key (the
    causal mask not subtracted, as for ``attn_block``)."""
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.nope_head_dim + m.rope_head_dim
    q = d * h * qk
    kv = d * (m.kv_lora_rank + m.rope_head_dim) \
        + m.kv_lora_rank * h * (m.nope_head_dim + m.v_head_dim)
    out = h * m.v_head_dim * d
    attn = seq * h * (qk + m.v_head_dim)
    return 2.0 * (q + kv + out + attn)


def params_bytes(params) -> int:
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(params)))
