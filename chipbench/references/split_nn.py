"""Plain float32 reference of the split-NN recommender.

Written from the model's definition, not from the program: a master
bottom tower over the master's features, one bottom tower per member
silo, the embeddings summed, a top tower to one logit per item, mean
binary cross-entropy, plain SGD. A tower is a list of blocks in the
configuration's own block language (``kind:key=val,key=val``, ``|`` for
tuples); the one kind is ``mlp``: dense layers with ReLU between them
(and after the last unless ``final_act=0``).

Weights are made here from a key, in the layout a split-NN party keeps
in its checkpoint: one entry per block, an ``mlp`` a list of
``{"w", "b"}`` layers.

Every matrix product goes through :func:`mm`. ``prec="highest"`` is
float32 (six bf16 passes on a TPU); ``prec="high"`` is the three-pass
bf16 product, forward and backward: XLA's own on a TPU, and written out
(``hi*hi + hi*lo + lo*hi``) elsewhere, where XLA computes every float32
product in full. The three-pass form is the benchmark's control: the
nearest precision below what the configuration states.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# matrix product at a stated precision
# ---------------------------------------------------------------------------


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm(a, b, prec: str):
    if prec == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if prec != "high":
        raise ValueError(f"unknown precision {prec!r}")
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)

    def f(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(a, b, prec: str = "highest"):
    """``a @ b`` for 2-D operands or operands with equal batch dims."""
    return _mm(a, b, prec)


def _mm_fwd(a, b, prec):
    return _mm(a, b, prec), (a, b)


def _mm_bwd(prec, res, g):
    a, b = res
    return (_mm(g, jnp.swapaxes(b, -1, -2), prec),
            _mm(jnp.swapaxes(a, -1, -2), g, prec))


mm.defvjp(_mm_fwd, _mm_bwd)


# ---------------------------------------------------------------------------
# towers
# ---------------------------------------------------------------------------


def parse_block(text: str) -> Dict[str, Any]:
    head, _, rest = text.partition(":")
    out: Dict[str, Any] = {"kind": head.strip()}
    for item in filter(None, (s.strip() for s in rest.split(","))):
        k, _, v = item.partition("=")
        vals = tuple(int(e) for e in v.split("|")) if "|" in v else (
            int(v) if v.strip().lstrip("-").isdigit() else v.strip())
        out[k.strip()] = vals
    return out


def resolve(blocks: Sequence[str], in_dim: int, out_dim: int
            ) -> List[Dict[str, Any]]:
    """Blocks with every width filled in, from input to output."""
    out, width = [], int(in_dim)
    for text in blocks:
        b = parse_block(text)
        if b["kind"] != "mlp":
            raise ValueError(f"unknown block kind {b['kind']!r}")
        hidden = b.get("hidden", ())
        hidden = (hidden,) if isinstance(hidden, int) else hidden
        out.append({"kind": "mlp",
                    "dims": (width,) + tuple(hidden) + (out_dim,),
                    "final_act": bool(b.get("final_act", 1))})
        width = out_dim
    return out


def _init_block(b, key):
    ks = [jax.random.fold_in(key, i) for i in range(len(b["dims"]) - 1)]
    return [{"w": jax.random.normal(k, (a, o), jnp.float32) / np.sqrt(a),
             "b": jnp.zeros((o,), jnp.float32)}
            for k, (a, o) in zip(ks, zip(b["dims"][:-1], b["dims"][1:]))]


def init_tower(blocks, key) -> List[Any]:
    return [_init_block(b, jax.random.fold_in(key, i))
            for i, b in enumerate(blocks)]


def apply_tower(blocks, params, x, prec: str = "highest"):
    for b, p in zip(blocks, params):
        last = len(p) - 1
        for i, layer in enumerate(p):
            x = mm(x, layer["w"], prec) + layer["b"]
            if i < last or b["final_act"]:
                x = jax.nn.relu(x)
    return x


def tower_flops(blocks) -> float:
    """Forward FLOPs of one sample (2 per multiply-add of a product)."""
    return sum(2.0 * a * o for b in blocks
               for a, o in zip(b["dims"][:-1], b["dims"][1:]))


# ---------------------------------------------------------------------------
# the split-NN model
# ---------------------------------------------------------------------------


class Model:
    """The configuration's split-NN: widths from the data and towers."""

    def __init__(self, cfg: Dict[str, Any]):
        data, emb = cfg["data"], int(cfg["embedding_dim"])
        self.cfg = cfg
        self.bottom = resolve(cfg["towers"]["bottom"],
                              data["master_features"], emb)
        self.members = [resolve(cfg["towers"]["bottom"], w, emb)
                        for w in data["member_features"]]
        self.top = resolve(cfg["towers"]["top"], emb, data["n_items"])
        self.lr = float(cfg["lr"])

    def init(self, key) -> Dict[str, Any]:
        """Every party's weights from one key, in one jitted call."""
        def make(k):
            return {"bottom": init_tower(self.bottom,
                                         jax.random.fold_in(k, 0)),
                    "top": init_tower(self.top, jax.random.fold_in(k, 1)),
                    "members": [init_tower(b, jax.random.fold_in(k, 2 + j))
                                for j, b in enumerate(self.members)]}
        return jax.jit(make)(key)

    def logits(self, w, xm, xbs, prec: str = "highest"):
        u = apply_tower(self.bottom, w["bottom"], xm, prec)
        for blocks, p, xb in zip(self.members, w["members"], xbs):
            u = u + apply_tower(blocks, p, xb, prec)
        return apply_tower(self.top, w["top"], u, prec)

    def loss(self, w, xm, xbs, y, prec: str = "highest"):
        lg = self.logits(w, xm, xbs, prec)
        return jnp.mean(jnp.clip(lg, 0) - lg * y
                        + jnp.log1p(jnp.exp(-jnp.abs(lg))))

    @functools.cached_property
    def _sgd_step(self):
        def step(w, xm, xbs, y, prec):
            loss, g = jax.value_and_grad(self.loss)(w, xm, xbs, y, prec)
            return loss, g, jax.tree.map(lambda p, gg: p - self.lr * gg,
                                         w, g)
        return jax.jit(step, static_argnums=(4,))

    def sgd_steps(self, w, batches, prec: str = "highest"):
        """Plain SGD over ``batches`` of (xm, [xb...], y), one step at a
        time: the loss of each step (a device scalar), its gradient and
        the weights after it."""
        for xm, xbs, y in batches:
            loss, g, w = self._sgd_step(w, xm, tuple(xbs), y, prec)
            yield loss, g, w

    def train_flops_per_sample(self) -> float:
        """Model FLOPs of one training sample: three times the forward
        of both bottoms and the top (a recomputed forward not
        counted)."""
        fwd = tower_flops(self.bottom) + tower_flops(self.top) + sum(
            tower_flops(b) for b in self.members)
        return 3.0 * fwd
