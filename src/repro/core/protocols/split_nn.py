"""Split-learning VFL protocol (paper §2: "neural networks-based
algorithms enabled with a split-learning approach"), on the lifecycle
API.

Members own bottom towers over their feature slices; the master owns
the top model and labels. Per batch:

1. members send bottom activations u_p = f_p(X_p),
2. master sums aggregated embedding u = u_master + sum_p u_p, runs the
   top model, computes the multi-label BCE loss,
3. master backprops and returns du_p to each member (the only gradient
   signal that crosses the boundary),
4. members apply their bottom VJP locally.

Models are built by the composable tower factory
(``repro.models.tower``, DESIGN.md §12): ``cfg.tower`` names the
member/bottom block chain (embedding table + transformer blocks on the
pallas kernels, quantize taps, MLP head) and ``cfg.top_tower`` the
master top model; both default to the legacy one-block MLP derived from
``cfg.hidden``/``cfg.embedding_dim``, which is bit-identical to the
recorded seed traces (same param init stream, same math). Large member
towers shard over local devices via ``cfg.tower_shard``.

Predict is the forward half federated end-to-end: members answer
feature-slice queries with bottom activations, the master composes the
top model — nobody ever holds another silo's features or parameters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.comm import schema
from repro.comm.schema import Field
from repro.core.protocols import base
from repro.core.protocols.driver import VFLProtocol
from repro.models import tower as twr

# activation/gradient exchanges declare compress=True: when the channel
# is built with compression on (cfg.compress), payloads ride as int8 +
# per-column scale with error feedback — entirely below the protocol,
# which always sees float32 tensors (DESIGN.md §7). Predict queries stay
# exempt so serving fidelity never depends on the training-path knob.
schema.message("splitnn/u", {"u": Field("float32", 2)}, stepped=True,
               compress=True,
               doc="member bottom activations for one training round")
schema.message("splitnn/du", {"du": Field("float32", 2)}, stepped=True,
               compress=True,
               doc="embedding gradient returned to one member")
schema.message("splitnn/pred_u", {"u": Field("float32", 2)}, stepped=True,
               doc="bottom activations for a predict query")


def mlp_init(key, dims: Tuple[int, ...]) -> List[Dict[str, jax.Array]]:
    """Legacy MLP primitive — the tower factory's ``mlp`` block
    reproduces this init stream exactly (kept public: mesh-mode
    ``core/vfl_step.py`` and tests build raw MLPs with it)."""
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        k = jax.random.fold_in(key, i)
        layers.append({
            "w": jax.random.normal(k, (a, b), jnp.float32) / np.sqrt(a),
            "b": jnp.zeros((b,), jnp.float32),
        })
    return layers


def mlp_apply(params, x, final_act: bool = False):
    for i, lyr in enumerate(params):
        x = x @ lyr["w"] + lyr["b"]
        if i < len(params) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def _bce(logits, y):
    return jnp.mean(jnp.clip(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def bottom_spec(cfg, in_dim: int, master: bool = False) -> twr.TowerSpec:
    """Resolve the bottom-model tower for one party's feature width (the
    master's from ``cfg.master_tower`` where that is set)."""
    blocks = (cfg.master_tower or cfg.tower) if master else cfg.tower
    if blocks:
        return twr.resolve(tuple(blocks), in_dim, cfg.embedding_dim)
    return twr.mlp_tower(in_dim, cfg.hidden, cfg.embedding_dim,
                         final_act=True)


def top_spec(cfg, items: int) -> twr.TowerSpec:
    """Resolve the master's top-model tower (embeddings -> logits)."""
    if cfg.top_tower:
        return twr.resolve(tuple(cfg.top_tower), cfg.embedding_dim,
                           items)
    return twr.mlp_tower(cfg.embedding_dim, cfg.hidden, items,
                         final_act=False)


LANES = 128


@functools.partial(jax.tree_util.register_dataclass, data_fields=["data"],
                   meta_fields=["width"])
@dataclasses.dataclass(frozen=True)
class _Silo:
    """A party's silo on the device, its feature width zero-padded to
    whole 128-lane tiles; ``width`` is the silo's own. Integer silos
    (token ids) are kept as int32, everything else as float32.

    A TPU keeps an array in whichever tiled layout pads it least. For a
    float32 silo whose width is not a multiple of 128 that can be the
    column-major one, and then every row gather first relays out the
    whole silo (Table 1's 1,345-wide master silo: 615 MB, ~1.9 ms a round
    on a v5e). Padded, the layout the chip picks is the row-major one a
    row gather reads. The layout is chosen by the shape, not committed
    to the array, since a jitted program loaded from JAX's persistent
    compilation cache does not keep a committed input layout."""
    data: jax.Array
    width: int

    @classmethod
    def put(cls, arr) -> "_Silo":
        dtype = arr.dtype if hasattr(arr, "dtype") else np.asarray(arr).dtype
        ints = np.issubdtype(dtype, np.integer)
        x = jnp.asarray(arr, jnp.int32 if ints else jnp.float32)
        width = x.shape[1]
        return cls(jnp.pad(x, ((0, 0), (0, -width % LANES))), width)


@jax.jit
def _take_rows(silos, rows):
    return tuple(s.data[rows, :s.width] for s in silos)


def _take(silos, rows) -> Tuple[jax.Array, ...]:
    """One party's row take for a round: the rows, uploaded once as
    int32, of every silo in one dispatch. A gather is exact, so each
    value is the one ``silo[rows]`` gives."""
    return _take_rows(silos, np.asarray(rows, np.int32))


def _make_master_step(bspec: twr.TowerSpec, tspec: twr.TowerSpec):
    @jax.jit
    def step(top_params, bottom_params, u_members, x_m, y, lr):
        """Returns (loss, new_top, new_bottom, du_members)."""
        def fwd(top, bottom, u_ms):
            u = twr.apply(bspec, bottom, x_m)
            for um in u_ms:
                u = u + um
            logits = twr.apply(tspec, top, u)
            return _bce(logits, y)

        loss, grads = jax.value_and_grad(fwd, argnums=(0, 1, 2))(
            top_params, bottom_params, u_members)
        g_top, g_bottom, g_u = grads
        new_top = jax.tree.map(lambda p, g: p - lr * g, top_params,
                               g_top)
        new_bottom = jax.tree.map(lambda p, g: p - lr * g,
                                  bottom_params, g_bottom)
        return loss, new_top, new_bottom, g_u
    return step


def _make_member_fns(spec: twr.TowerSpec, rules):
    @jax.jit
    def fwd(params, x):
        return twr.apply(spec, params, x, rules=rules)

    @jax.jit
    def bwd(params, x, du, lr):
        _, vjp = jax.vjp(
            lambda p: twr.apply(spec, p, x, rules=rules), params)
        (g,) = vjp(du)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)

    return fwd, bwd


def _make_routed_member_fns(spec: twr.TowerSpec, rules):
    """A member tower with ``moe`` blocks: the forward also gives each
    block's expert choices and adds its rows per held expert to the
    running counts ``stats`` on the device; the backward routes by the
    choices its forward made."""
    @jax.jit
    def fwd(params, x, stats):
        u, aux = twr.apply_routed(spec, params, x, rules=rules)
        return u, aux["routes"], {
            "rows": tuple(a + b for a, b in zip(stats["rows"],
                                                aux["rows"])),
            "dropped": stats["dropped"] + aux["dropped"]}

    @jax.jit
    def bwd(params, x, du, lr, routes):
        _, vjp = jax.vjp(lambda p: twr.apply_routed(
            spec, p, x, routes=routes, rules=rules)[0], params)
        (g,) = vjp(du)
        return jax.tree.map(lambda p, gg: p - lr * gg, params, g)

    return fwd, bwd


def route_stats(spec: twr.TowerSpec) -> Dict[str, object]:
    """Zero counts for :func:`_make_routed_member_fns`: rows routed to
    each held expert of each ``moe`` block, and rows dropped."""
    return {"rows": tuple(jnp.zeros((b["held"],), jnp.int32)
                          for b in twr.routed_blocks(spec)),
            "dropped": jnp.zeros((), jnp.int32)}


@base.register
class SplitNNProtocol(VFLProtocol):
    name = "split_nn"
    supports_pipeline = True

    def setup(self) -> None:
        cfg, d = self.cfg, self.data
        self.lr = jnp.float32(cfg.lr)
        key = jax.random.key(cfg.seed)
        # span names (repro.obs), built once per party
        r = self.role
        self._sp_h2d, self._sp_gather = f"{r}.h2d", f"{r}.gather"
        self._sp_step, self._sp_d2h = f"{r}.step", f"{r}.d2h"
        if self.is_master:
            self.y = _Silo.put(base._select(d.ids, self.order, d.y))
            self.x = _Silo.put(base._select(d.ids, self.order, d.x))
            items = self.y.width
            self._bspec = bottom_spec(cfg, self.x.width, master=True)
            self._tspec = top_spec(cfg, items)
            self.bottom = twr.init(self._bspec,
                                   jax.random.fold_in(key, 0))
            self.top = twr.init(self._tspec, jax.random.fold_in(key, 1))
            self._step = _make_master_step(self._bspec, self._tspec)
            # the master's own bottom forward for predict (unsharded:
            # the master bottom is the small party-side slice)
            self._fwd, _ = _make_member_fns(self._bspec, None)
            self._top_fwd = jax.jit(functools.partial(twr.apply,
                                                      self._tspec))
        else:
            self.x = _Silo.put(base._select(d.ids, self.order, d.x))
            # member index determines its init stream (from its id)
            midx = int(self.role.replace("member", "")) + 2
            self._spec = bottom_spec(cfg, self.x.width)
            self.params = twr.init(self._spec,
                                   jax.random.fold_in(key, midx))
            # model-parallel placement of a large member tower over the
            # local mesh; rules=None (the default) never builds a mesh
            self._rules = twr.make_tower_rules(cfg.tower_shard)
            self.params = twr.shard_tower(self.params, self._spec,
                                          self._rules)
            # a tower with moe blocks keeps its expert choices of the
            # last forward (``routes``, the backward's) and its routed
            # rows per held expert (``route_stats``), on the device
            self._routed = bool(twr.routed_blocks(self._spec))
            if self._routed:
                self._fwd, self._bwd = _make_routed_member_fns(
                    self._spec, self._rules)
                self._embed = jax.jit(functools.partial(
                    twr.apply, self._spec, rules=self._rules))
                self.route_stats = route_stats(self._spec)
                self.routes = None
            else:
                self._fwd, self._bwd = _make_member_fns(self._spec,
                                                        self._rules)
                self._embed = self._fwd
            self.masker = None
            # mask-stream namespace for predict queries: every member
            # sees the same EVAL round sequence, so a shared counter
            # keeps pairwise masks aligned without colliding with
            # training-step masks
            self._pred_step = 1 << 20
            if cfg.secure_agg:
                if cfg.compress:
                    raise ValueError("secure_agg masks do not survive "
                                     "independent quantization; choose one")
                from repro.core.secure_agg_protocol import PairwiseMasker
                self.masker = PairwiseMasker(self.ch.comm, self.role,
                                             self.ch.members)

    def cost_profile(self) -> Dict[str, float]:
        """Analytic per-step cost for the exchange account
        (launch/exchange.py): training FLOPs ~= 3x the forward pass
        (fwd + input/weight VJPs), wire bytes = the float32 u/du
        exchange this role sees each round."""
        cfg = self.cfg
        nb = cfg.batch_size
        ubytes = nb * cfg.embedding_dim * 4
        if self.is_master:
            flops = 3.0 * (twr.tower_flops(self._bspec, nb)
                           + twr.tower_flops(self._tspec, nb))
            wire = 2 * ubytes * max(1, len(self.ch.members))
            pbytes = twr.params_bytes(self.bottom) \
                + twr.params_bytes(self.top)
        else:
            flops = 3.0 * twr.tower_flops(self._spec, nb)
            wire = 2 * ubytes
            pbytes = twr.params_bytes(self.params)
        return {"flops_per_step": flops, "bytes_per_step": float(wire),
                "params_bytes": float(pbytes)}

    def on_batch_master(self, rows, step) -> float:
        ch = self.ch
        msgs = ch.gather(ch.members, "splitnn/u")
        with obs.span(self._sp_h2d, step=step):
            # fit_rows: a stale substitution (down/straggling peer) may
            # carry a different tail-batch row count than this round
            u_members = tuple(
                jnp.asarray(base.fit_rows(m.tensor("u"), len(rows)),
                            jnp.float32) for m in msgs)
        with obs.span(self._sp_gather, step=step):
            xb, yb = _take((self.x, self.y), rows)
        with obs.span(self._sp_step, step=step):
            loss, self.top, self.bottom, g_u = self._step(
                self.top, self.bottom, u_members, xb, yb, self.lr)
        for mname, du in zip(ch.members, g_u):
            with obs.span(self._sp_d2h, step=step):
                du = np.asarray(du)
            # isend: the per-member gradient writes overlap each other
            # and the next round's activation gather
            ch.isend(mname, "splitnn/du", {"du": du})
        with obs.span(self._sp_d2h, step=step):
            return float(loss)

    def member_stage_send(self, rows, step):
        """Bottom forward + activation isend; the batch slice (with the
        expert choices, for a routed tower) is the ctx the deferred
        backward stage reuses (its VJP must see the inputs and routing
        this forward actually saw)."""
        with obs.span(self._sp_gather, step=step):
            (xb,) = _take((self.x,), rows)
        with obs.span(self._sp_step, step=step):
            if self._routed:
                u, self.routes, self.route_stats = self._fwd(
                    self.params, xb, self.route_stats)
                xb = (xb, self.routes)
            else:
                u = self._fwd(self.params, xb)
        if self.cfg.noise_sigma > 0:
            # noising defense (docs/privacy.md): the member perturbs
            # its outgoing embedding before any masking, so neither the
            # master nor a wire adversary ever sees the clean
            # activations an embedding-clustering attack feeds on
            u = jnp.asarray(np.asarray(u)
                            + base.defense_noise(self.cfg,
                                                 np.asarray(u), step,
                                                 self.role))
        if self.masker is not None:
            u = jnp.asarray(np.asarray(u)
                            + self.masker.mask(step, np.asarray(u).shape))
        with obs.span(self._sp_d2h, step=step):
            u = np.asarray(u)
        self.ch.isend("master", "splitnn/u", {"u": u})
        return xb

    def member_stage_recv(self, rows, step, xb) -> None:
        du = self.ch.recv("master", "splitnn/du").tensor("du")
        with obs.span(self._sp_h2d, step=step):
            du = jnp.asarray(du, jnp.float32)
        with obs.span(self._sp_step, step=step):
            if self._routed:
                xb, routes = xb
                self.params = self._bwd(self.params, xb, du, self.lr,
                                        routes)
            else:
                self.params = self._bwd(self.params, xb, du, self.lr)

    # -- predict/serve -------------------------------------------------------
    def predict_master(self, rows) -> np.ndarray:
        with obs.span(self._sp_gather):
            (xb,) = _take((self.x,), rows)
        with obs.span(self._sp_step):
            u = self._fwd(self.bottom, xb)
        for msg in self.ch.gather(self.ch.members, "splitnn/pred_u"):
            with obs.span(self._sp_h2d):
                um = jnp.asarray(msg.tensor("u"), jnp.float32)
            u = u + um
        with obs.span(self._sp_step):
            scores = self._top_fwd(self.top, u)
        with obs.span(self._sp_d2h):
            return np.asarray(scores)

    def predict_member(self, rows) -> None:
        self.send_embed(self.predict_embed(rows), rows)

    def predict_embed(self, rows) -> np.ndarray:
        # pure bottom-model forward: cacheable per row (no masking —
        # masks are per-query and applied in send_embed)
        with obs.span(self._sp_gather):
            (xb,) = _take((self.x,), rows)
        with obs.span(self._sp_step):
            u = self._embed(self.params, xb)
        with obs.span(self._sp_d2h):
            return np.asarray(u)

    def send_embed(self, u, rows) -> None:
        if self.masker is not None:
            # predict queries get the same pairwise masking as training
            # rounds — the master only ever sees the aggregate
            u = np.asarray(u + self.masker.mask(self._pred_step, u.shape),
                           np.float32)
            self._pred_step += 1
        self.ch.send("master", "splitnn/pred_u", {"u": np.asarray(u)})

    def evaluate_master(self, scores, rows) -> Dict[str, float]:
        from repro.train.evals import recsys_report
        (yb,) = _take((self.y,), rows)
        return recsys_report(np.asarray(scores), np.asarray(yb), k=5)

    def finalize(self) -> Dict:
        if self.is_master:
            return {"top": jax.tree.map(np.asarray, self.top),
                    "bottom": jax.tree.map(np.asarray, self.bottom),
                    "order": self.order}
        return {"params": jax.tree.map(np.asarray, self.params)}

    def _ef_residuals(self) -> Dict:
        # error feedback now lives on the typed channel (schema-level
        # compression); its residuals are part of this role's state
        ef = self.ch.error_feedback
        return dict(ef.residuals) if ef is not None else {}

    def state_dict(self) -> Dict:
        if self.is_master:
            return {"top": jax.tree.map(np.asarray, self.top),
                    "bottom": jax.tree.map(np.asarray, self.bottom),
                    "ef": self._ef_residuals()}
        return {"params": jax.tree.map(np.asarray, self.params),
                "ef": self._ef_residuals()}

    @staticmethod
    def _as_tower(state):
        """Migrate pre-§12 checkpoints: a flat legacy MLP layer list
        becomes the one-block tower param tree. A legacy layer is a
        dict of exactly ``{'w', 'b'}`` — new-format block entries
        never look like that (an mlp block is a *list* of layers;
        embed/attn dicts carry extra keys), so checking the full key
        set keeps embed-first towers out of the legacy path."""
        if (state and isinstance(state[0], dict)
                and set(state[0]) == {"w", "b"}):
            state = [state]
        return jax.tree.map(jnp.asarray, list(state))

    def load_state_dict(self, state) -> None:
        if self.is_master:
            self.top = self._as_tower(state["top"])
            self.bottom = self._as_tower(state["bottom"])
        else:
            self.params = twr.shard_tower(
                self._as_tower(state["params"]), self._spec,
                self._rules)
        if state.get("ef"):
            from repro.core import compression
            # migrate pre-§7 checkpoints: the protocol-owned EF keyed
            # streams as "u" (member) / member name (master); channel
            # EF keys are "{to}/{msg-type}/{field}"
            residuals = {}
            for k, v in state["ef"].items():
                if "/" in k:
                    residuals[k] = v
                elif k == "u":
                    residuals["master/splitnn/u/u"] = v
                else:
                    residuals[f"{k}/splitnn/du/du"] = v
            if self.ch.error_feedback is None:
                self.ch.error_feedback = compression.ErrorFeedback()
            self.ch.error_feedback.residuals = residuals
