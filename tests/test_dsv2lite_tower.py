"""DeepSeek-V2-Lite's blocks in the split-NN member tower (``tokens``,
``mla``, ``ffn``, ``moe``, ``rmsnorm``): each block and the whole tower
against the benchmark's plain reference (``chipbench/references/
deepseek_v2_lite.py``), forward and gradient, at small widths on seeded
random weights; YaRN against its formula; the expert share against the
whole layer; dropless routing; the configuration's widths against the
model's; and a tiny tower trained through ``VFLJob``."""
import dataclasses
import functools
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.references import deepseek_v2_lite as R  # noqa: E402
from repro.configs.base import MoEConfig, YarnConfig  # noqa: E402
from repro.models import layers, moe  # noqa: E402
from repro.models import tower as twr  # noqa: E402

YARN = ("theta=10000,yarn_factor=40,yarn_original=4096,beta_fast=32,"
        "beta_slow=1,mscale=0.707,mscale_all_dim=0.707,eps=1e-6")
MLA = f"mla:heads=2,kv_lora=16,nope=8,rope=4,v=8,{YARN}"
MOE = ("moe:experts=16,held=4,shard=1,top_k=3,hidden=12,shared=2,"
       "norm_topk=0,scaling=1,eps=1e-6")
TOWER = ("tokens:vocab=64,dim=32", MLA, "ffn:hidden=48,eps=1e-6",
         MLA, MOE, "rmsnorm:eps=1e-6", "mlp:hidden=16")
SEQ, BATCH, VOCAB, DIM = 16, 4, 64, 32


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _model():
    cfg = {"data": {"member_tokens": {"seq_len": SEQ}, "master_features": 6,
                    "n_items": 3},
           "towers": {"bottom": list(TOWER), "master_bottom": ["mlp"],
                      "top": ["mlp:final_act=0"]},
           "embedding_dim": 8, "lr": 0.1}
    return R.Model(cfg)


def _tokens(seed=2):
    return jax.random.randint(jax.random.key(seed), (BATCH, SEQ), 0, VOCAB)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _assert_trees_close(got, want, tol):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert _rel(a, b) < tol, (jax.tree_util.keystr(path), _rel(a, b))


# ---------------------------------------------------------------------------
# each block and the whole tower against the reference
# ---------------------------------------------------------------------------


def _reference_block(model, i, p, h, routes):
    b = model.blocks[i]
    if b["kind"] == "moe":
        return R.moe_block(b, p, h, routes, "highest")[0]
    if b["kind"] == "mla":
        return R.mla_block(b, p, h, "highest")
    if b["kind"] == "ffn":
        return R.ffn_block(b, p, h, "highest")
    return R.rmsnorm(h, p["scale"], b["eps"])


@pytest.mark.parametrize("index", [1, 2, 4, 5], ids=["mla", "ffn", "moe",
                                                     "rmsnorm"])
def test_block_matches_reference(index):
    model = _model()
    w = model.init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    b, p = spec.blocks[index], w[index]
    h = jax.random.normal(jax.random.key(1), (BATCH, SEQ, DIM))
    routes = None
    if b["kind"] == "moe":
        x = layers.rmsnorm(p["norm"], h, b["eps"]).reshape(-1, DIM)
        routes = moe.top_experts(moe.gate_scores(x, p["router"]), 3)

    def prog(p, h):
        if b["kind"] == "moe":
            return twr._apply_moe(b, p, h, routes)[0]
        return twr._BLOCK_APPLY[b["kind"]](b, p, h)

    def ref(p, h):
        return _reference_block(model, index, p, h, routes)
    np.testing.assert_allclose(jax.jit(prog)(p, h), jax.jit(ref)(p, h),
                               rtol=2e-5, atol=2e-6)
    c = jax.random.normal(jax.random.key(3), (BATCH, SEQ, DIM))
    def grad(f):
        return jax.jit(jax.grad(lambda p, h: jnp.sum(f(p, h) * c), (0, 1)))
    gp, gr = grad(prog)(p, h), grad(ref)(p, h)
    _assert_trees_close(gp, gr, 2e-5)


def test_member_tower_matches_reference_forward_and_gradient():
    model = _model()
    w = model.init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    tokens = _tokens()
    kept = jax.jit(functools.partial(twr.apply_kept, spec))
    u, aux = kept(w, tokens)
    routes = aux["routes"]
    assert [r.shape for r in routes] == [(BATCH * SEQ, 3)]
    np.testing.assert_array_equal(
        u, jax.jit(functools.partial(twr.apply, spec))(w, tokens))

    def ref(p):
        h = p[0]["table"][tokens]
        j = 0
        for i in range(1, len(model.blocks)):
            h = _reference_block(model, i, p[i], h,
                                 routes[j] if model.blocks[i]["kind"] == "moe"
                                 else None)
            j += model.blocks[i]["kind"] == "moe"
        return R.mlp_apply(model.head, [p[-1]], jnp.mean(h, 1), "highest")
    np.testing.assert_allclose(u, jax.jit(ref)(w), rtol=2e-5, atol=2e-6)
    c = jax.random.normal(jax.random.key(3), u.shape)
    gp = jax.jit(functools.partial(twr.vjp_kept, spec))(
        w, tokens, aux["inputs"], routes, c)
    gr = jax.jit(jax.grad(lambda p: jnp.sum(ref(p) * c)))(w)
    _assert_trees_close(gp, gr, 2e-5)


def test_routed_backward_follows_the_forward_routes():
    """Handed the forward's choices, the backward gives the gradient of
    the tower that chooses again; handed other choices, another one."""
    w = _model().init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    tokens = _tokens()
    u, aux = jax.jit(functools.partial(twr.apply_kept, spec))(w, tokens)
    c = jax.random.normal(jax.random.key(3), u.shape)
    grad = jax.jit(functools.partial(twr.vjp_kept, spec))
    again = jax.jit(jax.grad(lambda p: jnp.sum(twr.apply(spec, p, tokens)
                                               * c)))(w)
    _assert_trees_close(grad(w, tokens, aux["inputs"], aux["routes"], c),
                        again, 1e-6)
    moved = grad(w, tokens, aux["inputs"],
                 tuple((r + 1) % 16 for r in aux["routes"]), c)
    assert not np.allclose(moved[4]["w_gate"], again[4]["w_gate"])


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def _yarn_by_hand(dim, base, factor, orig, fast, slow):
    """DeepSeek-V2's yarn_find_correction_range / linear ramp, written
    out term by term."""
    low = math.floor(dim * math.log(orig / (fast * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(dim * math.log(orig / (slow * 2 * math.pi))
                     / (2 * math.log(base)))
    low, high = max(low, 0), min(high, dim - 1)
    out = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / factor * ramp + extra * (1 - ramp))
    return np.array(out)


def test_yarn_rope_matches_its_formula():
    y = YarnConfig(factor=40, original_max_position=4096, beta_fast=32,
                   beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
    want = _yarn_by_hand(64, 10000.0, 40, 4096, 32, 1)
    got = np.asarray(layers.rope_frequencies(64, 10000.0, y))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the high frequencies are kept, the low ones stretched 40 times
    plain = np.asarray(layers.rope_frequencies(64, 10000.0))
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    # the softmax scale: mscale(40, 0.707) ** 2 ~ 1.59
    m = 0.1 * 0.707 * math.log(40) + 1
    assert layers.attn_scale(y) == pytest.approx(m * m)
    assert 1.58 < layers.attn_scale(y) < 1.60
    assert layers.attn_scale(None) == 1.0
    # equal mscales: the rotation itself is not rescaled
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    r = layers.apply_rope(x, pos, 10000.0, y)
    np.testing.assert_allclose(jnp.linalg.norm(r, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    ang = 4 * got
    x1, x2 = np.asarray(x[0, 4, 0, :32]), np.asarray(x[0, 4, 0, 32:])
    np.testing.assert_allclose(
        r[0, 4, 0], np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                    x1 * np.sin(ang) + x2 * np.cos(ang)]),
        rtol=1e-5, atol=1e-6)
    # the reference's own YaRN gives the same frequencies
    np.testing.assert_allclose(
        R.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1), want, rtol=1e-12)


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------

_M = MoEConfig(num_experts=16, top_k=3, d_expert=12, num_shared=2,
               norm_topk_prob=False)


def _whole_layer(key):
    return moe.init_share(key, _M, DIM, 0, 16)


def _share(whole, s, held):
    sl = slice(s * held, (s + 1) * held)
    return {"router": whole["router"], "shared": whole["shared"],
            **{k: whole[k][sl] for k in ("w_gate", "w_up", "w_down")}}


def test_expert_shares_add_up_to_the_whole_layer():
    whole = _whole_layer(jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (40, DIM))
    share = jax.jit(lambda p, x, first: moe.moe_share(_M, p, x, first=first))
    full, aux_full = share(whole, x, 0)
    shared = layers.gated_mlp(whole["shared"], x)
    held, parts, rows = 2, [], []
    for s in range(8):
        y, aux = share(_share(whole, s, held), x, s * held)
        np.testing.assert_array_equal(aux["routes"], aux_full["routes"])
        parts.append(y - shared)
        rows.append(aux["rows"])
    np.testing.assert_allclose(sum(parts) + shared, full, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(rows), aux_full["rows"])
    assert int(sum(np.sum(r) for r in rows)) == 40 * 3
    # a share's init holds the same experts the whole layer does
    one = moe.init_share(jax.random.key(7), _M, DIM, 4, 2)
    np.testing.assert_array_equal(one["w_up"], whole["w_up"][4:6])
    # and the whole layer is the reference's dense sum over every expert
    b = R.parse(MOE.replace("held=4,shard=1", "held=16,shard=0"))
    h = x.reshape(4, 10, DIM)
    p = {"norm": {"scale": jnp.ones((DIM,))}, **whole}
    ref, margin = R.moe_block(b, p, h, moe.top_experts(moe.gate_scores(
        R.rmsnorm(h, 1.0, 1e-6).reshape(40, DIM), whole["router"]), 3),
        "highest")
    got = twr._apply_moe(twr.resolve(("tokens:vocab=4,dim=32",
                                      MOE.replace("held=4,shard=1",
                                                  "held=16,shard=0"),
                                      "mlp"), 10, 8).blocks[1], p, h)[0]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    assert float(margin) == 0.0


def test_no_row_is_dropped_when_every_token_routes_to_the_same_experts():
    whole = _whole_layer(jax.random.key(9))
    # a router that sends every token to experts 0, 1 and 2
    bias = np.zeros((DIM, 16), np.float32)
    bias[:, :3] = 50.0
    x = jnp.abs(jax.random.normal(jax.random.key(1), (64, DIM))) / DIM
    p = dict(_share(whole, 0, 4), router=jnp.asarray(bias))
    y, aux = moe.moe_share(_M, p, x, first=0)
    np.testing.assert_array_equal(aux["rows"], [64, 64, 64, 0])
    assert int(aux["dropped"]) == 0
    assert moe.share_rows(64, 3, 4) == 64 * 3
    scores = moe.gate_scores(x, p["router"])
    g = jnp.take_along_axis(scores, aux["routes"], -1)
    want = layers.gated_mlp(p["shared"], x)
    for k in range(3):
        e = {n: p[n][k] for n in ("w_gate", "w_up", "w_down")}
        want = want + g[:, [k]] * layers.gated_mlp(e, x)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_routing_counts_and_choices_reach_the_member():
    model = _model()
    w = model.init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    from repro.core.protocols import split_nn
    fwd, bwd = split_nn._make_routed_member_fns(spec, None)
    stats = split_nn.route_stats(spec)
    tokens = _tokens()
    u, kept, stats = fwd(w, tokens, stats)
    u, kept, stats = fwd(w, tokens, stats)
    rows = [np.asarray(r) for r in stats["rows"]]
    assert [r.shape for r in rows] == [(4,)]
    for r, sel in zip(rows, kept["routes"]):
        held = (np.asarray(sel) >= 4) & (np.asarray(sel) < 8)
        assert int(r.sum()) == 2 * int(held.sum())
    assert int(stats["dropped"]) == 0
    new = bwd(w, tokens, jnp.ones_like(u), jnp.float32(0.1), kept)
    assert jax.tree.structure(new) == jax.tree.structure(w)


# ---------------------------------------------------------------------------
# the member's backward from the block inputs its forward kept
# ---------------------------------------------------------------------------


def _primitives(jaxpr, counts):
    """Each primitive's count in ``jaxpr`` and its sub-jaxprs."""
    from jax.extend import core as jcore
    for e in jaxpr.eqns:
        counts[e.primitive.name] = counts.get(e.primitive.name, 0) + 1
        for v in e.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(j, jcore.ClosedJaxpr):
                    _primitives(j.jaxpr, counts)
                elif isinstance(j, jcore.Jaxpr):
                    _primitives(j, counts)
    return counts


def _live_primitives(fn, *args):
    """Primitive counts of what ``jax.jit`` lowers for ``fn(*args)``: the
    traced jaxpr with its dead equations removed, as jit removes them
    (each block's VJP leaves the block's own output unused)."""
    from jax._src.interpreters import partial_eval as pe
    jaxpr = fn.trace(*args).jaxpr.jaxpr
    live, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return _primitives(live, {})


def test_member_backward_runs_no_whole_tower_forward():
    """The backward is each block's VJP at the input its forward kept: a
    routed row goes through 3 grouped products in the forward and 9 in
    the backward (the block's recomputation and the two gradients of
    each), and every product of the forward at most three times there.
    A forward of the whole tower inside the backward would make both 12
    and about four."""
    from repro.core.protocols import split_nn
    w = _model().init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    fwd, bwd = split_nn._make_routed_member_fns(spec, None)
    stats = split_nn.route_stats(spec)
    tokens = _tokens()
    u, kept, _ = fwd(w, tokens, stats)
    f = _live_primitives(fwd, w, tokens, stats)
    b = _live_primitives(bwd, w, tokens, jnp.ones_like(u), jnp.float32(0.1),
                         kept)
    moe_blocks = len(twr.routed_blocks(spec))
    assert f["ragged_dot_general"] == 3 * moe_blocks
    assert b["ragged_dot_general"] == 9 * moe_blocks
    assert f["dot_general"] < b["dot_general"] <= 3 * f["dot_general"]
    assert "top_k" not in b                    # routed by the kept choices


def test_member_forward_keeps_block_inputs_and_routes_only():
    """What the forward hands its backward is the input of every block
    after the first and each moe block's choices: no more bytes than
    those, and no leaf shaped like a weight."""
    from repro.core.protocols import split_nn
    w = _model().init(jax.random.key(5))["members"][0]
    spec = twr.resolve(TOWER, SEQ, 8)
    fwd, _ = split_nn._make_routed_member_fns(spec, None)
    _, kept, _ = fwd(w, _tokens(), split_nn.route_stats(spec))
    leaves = jax.tree.leaves(kept)
    boundaries = (len(spec.blocks) - 1) * BATCH * SEQ * DIM * 4
    routes = len(twr.routed_blocks(spec)) * BATCH * SEQ * 3 * 4
    assert sum(x.nbytes for x in leaves) == boundaries + routes
    assert [x.shape for x in kept["inputs"]] \
        == [(BATCH, SEQ, DIM)] * (len(spec.blocks) - 1)
    weights = {x.shape for x in jax.tree.leaves(w)}
    assert not weights & {x.shape for x in leaves}


def _whole_tower_member(make):
    """The member with a backward through the whole tower from the token
    ids, routed by its forward's choices, through the activations its
    forward computed. At ``pipeline_depth`` 2 a round's backward comes
    after a later round has moved the weights: each block is then
    differentiated at the weights of now, at the input its forward saw
    (from the forward's weights, which this member keeps and the
    program does not)."""
    def member(spec, rules):
        fwd, _ = make(spec, rules)

        def keep_weights(params, x, stats):
            u, kept, stats = fwd(params, x, stats)
            return u, {"routes": kept["routes"], "weights": params}, stats

        @jax.jit
        def bwd(params, x, du, lr, kept):
            def tower(p):
                h, routes = x, list(kept["routes"])
                for b, now, then in zip(spec.blocks, p, kept["weights"]):
                    r = routes.pop(0) if b["kind"] == "moe" else None
                    # the value of the forward's, the derivative of now's
                    y = twr._apply_block(b, now, h, rules, r)[0]
                    h = jax.lax.stop_gradient(
                        twr._apply_block(b, then, h, rules, r)[0]) \
                        + (y - jax.lax.stop_gradient(y))
                return h
            _, vjp = jax.vjp(tower, params)
            (g,) = vjp(du)
            return jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return keep_weights, bwd
    return member


def _three_rounds(depth):
    """Three rounds of the tiny token tower through ``VFLJob``: the
    member's result and the master's losses."""
    job = _token_job(48, epochs=1, depth=depth)
    try:
        losses = [h["loss"] for h in job.fit()["history"]]
    finally:
        res = job.shutdown()
    return res["member0"], losses


@pytest.mark.parametrize("depth", [1, 2])
def test_member_step_from_kept_inputs_matches_the_whole_tower_backward(
        monkeypatch, depth):
    """Over three rounds through ``VFLJob``, the member's step from its
    kept block inputs updates the weights as a backward through the
    whole tower from the token ids does, within a few float32 roundings
    of each leaf, and counts every round it ran."""
    from repro.core.protocols import split_nn
    got, losses = _three_rounds(depth)
    assert got["kept_rounds"] == len(losses) == 3
    monkeypatch.setattr(split_nn, "_make_routed_member_fns",
                        _whole_tower_member(split_nn._make_routed_member_fns))
    want, want_losses = _three_rounds(depth)
    np.testing.assert_allclose(losses, want_losses, rtol=4e-7)
    eps = float(np.finfo(np.float32).eps)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got["params"]),
            jax.tree.leaves(want["params"])):
        gap = float(np.max(np.abs(a - b)))
        assert gap <= 4 * eps * float(np.max(np.abs(b))), (
            jax.tree_util.keystr(path), gap)


# ---------------------------------------------------------------------------
# block validation and the configuration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blocks,msg", [
    (("mlp", "tokens:vocab=4,dim=8"), "'tokens' must be the first"),
    (("mla:heads=2,kv_lora=4,nope=2,rope=2,v=2", "mlp"),
     "needs an 'embed' block first"),
    (("tokens:vocab=4,dim=8", "mlp", "moe:experts=4,top_k=2,hidden=4",
      "mlp"), "must come before any 'mlp'"),
    (("tokens:vocab=4,dim=8", "moe:experts=4,top_k=2,hidden=4,held=3", "mlp"),
     None),
    (("tokens:vocab=4,dim=8", "ffn:hidden=4,widht=3", "mlp"),
     "unknown keys"),
])
def test_check_blocks_new_kinds(blocks, msg):
    if msg is None:
        twr.check_blocks(blocks)
        return
    with pytest.raises(ValueError, match=msg):
        twr.check_blocks(blocks)


def test_moe_share_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="shard's"):
        twr.resolve(("tokens:vocab=4,dim=8",
                     "moe:experts=4,top_k=2,hidden=4,held=2,shard=2", "mlp"),
                    4, 2)


def test_benchmark_configuration_has_the_models_widths():
    from repro.configs import get_config
    m = get_config("deepseek-v2-lite-16b")
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "recsys-table1-dsv2lite.json").read_text())
    spec = twr.resolve(cfg["towers"]["bottom"],
                       cfg["data"]["member_tokens"]["seq_len"],
                       cfg["embedding_dim"])
    kinds = spec.kinds
    assert kinds == (("tokens", "mla", "ffn") + ("mla", "moe") * 5
                     + ("rmsnorm", "mlp"))
    assert sum(k == "mla" for k in kinds) == cfg["num_hidden_layers"] == 6
    tok = spec.blocks[0]
    assert tok["dim"] == m.d_model == cfg["hidden_size"]
    assert tok["vocab"] * 8 == m.vocab == cfg["published"]["vocab_size"]
    for b in spec.blocks:
        if b["kind"] == "mla":
            c = b["cfg"]
            assert (c.n_heads, c.mla, c.rope_theta, c.rope_scaling,
                    c.norm_eps) == (m.n_heads, m.mla, m.rope_theta,
                                    m.rope_scaling, m.norm_eps)
        elif b["kind"] == "ffn":
            assert b["hidden"] == m.d_ff == cfg["intermediate_size"]
        elif b["kind"] == "moe":
            bm = b["moe"]
            assert dataclasses.replace(
                bm, capacity_factor=m.moe.capacity_factor) == m.moe
            assert b["held"] * 8 == bm.num_experts \
                == cfg["published"]["n_routed_experts"]
            assert b["held"] == cfg["n_routed_experts"]
    r = cfg["rope_scaling"]
    y = m.rope_scaling
    assert (r["factor"], r["original_max_position_embeddings"],
            r["beta_fast"], r["beta_slow"], r["mscale"],
            r["mscale_all_dim"]) == (y.factor, y.original_max_position,
                                     y.beta_fast, y.beta_slow, y.mscale,
                                     y.mscale_all_dim)
    assert (m.moe.norm_topk_prob, m.moe.scoring_func,
            m.moe.routed_scaling_factor) == (
        cfg["norm_topk_prob"], cfg["scoring_func"],
        cfg["routed_scaling_factor"])


# ---------------------------------------------------------------------------
# through the normal path
# ---------------------------------------------------------------------------


def _token_job(rows, epochs, depth=1):
    """A tiny token tower's job in thread mode: ``rows`` member rows
    among ``rows + 32`` users, 16 a round."""
    from repro.core.party import VFLJob
    from repro.core.protocols.base import MasterData, MemberData, VFLConfig
    rng = np.random.default_rng(0)
    n = rows + 32
    ids = [f"u{i:04d}" for i in range(n)]
    y = (rng.random((n, 3)) < 0.3).astype(np.float32)
    xm = rng.normal(size=(n, 6)).astype(np.float32)
    tokens = rng.integers(0, VOCAB, (rows, SEQ)).astype(np.int32)
    cfg = VFLConfig(protocol="split_nn", epochs=epochs, batch_size=16,
                    lr=0.1, seed=0, use_psi=False, embedding_dim=8,
                    tower=TOWER, master_tower=("mlp:hidden=16",),
                    top_tower=("mlp:hidden=8,final_act=0",),
                    pipeline_depth=depth)
    return VFLJob(cfg, MasterData(ids, y, xm),
                  [MemberData(ids[16:16 + rows], tokens)], mode="thread")


def test_token_tower_trains_through_vfljob():
    with _token_job(64, epochs=2) as job:
        res = job.fit()
        scores = np.asarray(job.predict())
    losses = [h["loss"] for h in res["history"]]
    assert len(losses) == 8 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert scores.shape == (64, 3)
