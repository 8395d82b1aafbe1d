"""Logical-axis sharding: rules resolve logical names -> mesh axes with
divisibility fallback.

Params and activations carry *logical* axis names ("embed", "heads",
"mlp", ...). A :class:`MeshRules` binds them to mesh axes ("pod", "data",
"model"). Resolution drops a mesh axis when the dimension size is not
divisible by it (e.g. glm4's 2 KV heads on a 16-way model axis fall back
to replication) — every fallback is recorded so the dry-run can report it.

FSDP-style: the "embed" dim of weights shards over the data axis (ZeRO-3
analogue), tensor-parallel dims ("heads", "mlp", "experts", "vocab") over
the model axis, batch over (pod, data).
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical axis -> preferred mesh axes (tried in order, tuple = joint)
PARAM_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "embed": ("data",),          # FSDP shard of weight matrices
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "expert_mlp": None,          # experts already shard over model
    "experts": ("model",),
    "experts_dp": None,          # data-parallel experts (§Perf lever)
    "vocab": ("model",),
    "kv_lora": None,
    "q_lora": None,
    "head_dim": None,
    "layers": None,
    "state": None,
    "conv": None,
    # dt_rank must stay replicated: sharding it makes the dt_proj
    # contraction emit a 4 GB fp32 all-reduce of the full d_inner
    # activation per mamba layer (EXPERIMENTS.md §Perf, jamba iter 3)
    "dt_rank": None,
    "d_inner": ("model",),
    "frames": None,
}

TRAIN_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "experts_dp": None,
    "expert_mlp": None,
    "vocab": ("model",),
    "head_dim": None,
    "kv_lora": None,
    "q_lora": None,
    "state": None,
    "d_inner": ("model",),
    "cache_seq": ("model",),
    "frames": None,
}

# decode: batch over data only (pod reserved for parties / spare DP),
# KV-cache sequence over model (partial-softmax combine by SPMD).
DECODE_RULES = dict(TRAIN_RULES)
DECODE_RULES["batch"] = ("data",)


@dataclass
class MeshRules:
    mesh: Mesh
    param_rules: Dict[str, Optional[Tuple[str, ...]]] = field(
        default_factory=lambda: dict(PARAM_RULES))
    act_rules: Dict[str, Optional[Tuple[str, ...]]] = field(
        default_factory=lambda: dict(TRAIN_RULES))
    fallbacks: List[str] = field(default_factory=list)
    # §Perf lever: accumulate TP out-projections in bf16 so the SPMD
    # partial-sum all-reduces move bf16 instead of the f32 accumulator
    # (halves TP collective bytes; documented numerics trade-off)
    bf16_collectives: bool = False

    def _axis_size(self, names: Sequence[str]) -> int:
        size = 1
        for n in names:
            size *= self.mesh.shape[n]
        return size

    def spec(self, logical: Sequence[Optional[str]], shape: Sequence[int],
             rules: Dict[str, Optional[Tuple[str, ...]]],
             what: str = "") -> P:
        used: set = set()
        parts = []
        for name, dim in zip(logical, shape):
            target = rules.get(name) if name else None
            if target is None:
                parts.append(None)
                continue
            target = tuple(a for a in target
                           if a in self.mesh.shape and a not in used)
            if not target or dim % self._axis_size(target) != 0:
                if target:
                    self.fallbacks.append(
                        f"{what}: dim {name}={dim} not divisible by "
                        f"{target} (size {self._axis_size(target)}) -> replicated")
                parts.append(None)
                continue
            used.update(target)
            parts.append(target if len(target) > 1 else target[0])
        return P(*parts)

    def param_sharding(self, logical, shape) -> NamedSharding:
        return NamedSharding(
            self.mesh, self.spec(logical, shape, self.param_rules, "param"))

    def act_spec(self, logical, shape) -> P:
        return self.spec(logical, shape, self.act_rules, "act")


_current: contextvars.ContextVar[Optional[MeshRules]] = \
    contextvars.ContextVar("mesh_rules", default=None)


def current_rules() -> Optional[MeshRules]:
    return _current.get()


@contextlib.contextmanager
def use_rules(rules: Optional[MeshRules]):
    tok = _current.set(rules)
    try:
        yield rules
    finally:
        _current.reset(tok)


def constrain(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """Apply with_sharding_constraint if mesh rules are active, else no-op.

    Model code calls this at block boundaries; smoke tests (no mesh) are
    unaffected.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.act_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def reduce_dtype(x_dtype):
    """preferred_element_type for TP out-projections (None = default)."""
    import jax.numpy as jnp
    r = current_rules()
    if r is not None and r.bf16_collectives and x_dtype == jnp.bfloat16:
        return jnp.bfloat16
    return None


def logical_to_spec(rules: Optional[MeshRules], logical, shape,
                    for_params: bool = True) -> P:
    if rules is None:
        return P()
    table = rules.param_rules if for_params else rules.act_rules
    return rules.spec(logical, shape, table,
                      "param" if for_params else "act")


def param_shardings(rules: MeshRules, axes_tree, abstract_params):
    """Resolve a whole axes tree to NamedShardings (matching SDS tree)."""
    return jax.tree.map(
        lambda ax, sds: rules.param_sharding(ax, sds.shape),
        axes_tree, abstract_params,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
