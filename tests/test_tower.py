"""Composable tower factory (DESIGN.md §12): spec parsing/validation,
bit-identity of the default MLP path with the recorded seed traces,
transformer-tower convergence under pipelining, pallas-vs-reference
kernel parity, mesh sharding, the exchange account, and the per-link
``[comm.a.b]`` CommCfg overrides that ride the same PR."""
import dataclasses
import json
import pathlib
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro.core.party import run_vfl
from repro.core.protocols.base import VFLConfig
from repro.core.protocols.split_nn import (SplitNNProtocol, bottom_spec,
                                           mlp_init, top_spec)
from repro.data.vertical import vertical_partition
from repro.launch.exchange import exchange_account
from repro.models import tower as twr

TRACES = json.loads(
    (pathlib.Path(__file__).parent / "fixtures" / "seed_traces.json")
    .read_text())


def _dataset(n=128, d=12, items=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w = rng.normal(size=(d, items))
    y = x @ w * 0.4 + rng.normal(scale=0.05, size=(n, items))
    ids = [f"u{i:05d}" for i in range(n)]
    return ids, x, (y > 0).astype(np.float64)


def _splitnn_case(**over):
    ids, x, yb = _dataset()
    master, members = vertical_partition(ids, x, yb, widths=[5], seed=3)
    kw = dict(protocol="split_nn", epochs=3, batch_size=32, lr=0.1,
              seed=0, use_psi=False, embedding_dim=8, hidden=(16,))
    kw.update(over)
    return VFLConfig(**kw), master, members


TINY_TOWER = ("embed:tokens=4,dim=16", "attn_block:heads=2", "quantize",
              "mlp:hidden=16")


# ---------------------------------------------------------------------------
# spec parsing / validation
# ---------------------------------------------------------------------------


def test_parse_block_dsl():
    b = twr.parse_block("mlp:hidden=64|32,final_act=0")
    assert b == {"kind": "mlp", "hidden": (64, 32), "final_act": 0}
    assert twr.parse_block("attn:heads=2")["kind"] == "attn_block"
    assert twr.parse_block({"kind": "quantize"}) == {"kind": "quantize"}


@pytest.mark.parametrize("blocks,msg", [
    ((), "at least one block"),
    (("mlp", "embed"), "'embed' must be the first"),
    (("attn_block:heads=2", "mlp"), "needs an 'embed' block first"),
    (("embed", "mlp", "attn_block:heads=2", "mlp"),
     "must come before any 'mlp'"),
    (("embed", "mlp", "embed:tokens=2"), "'embed' must be the first"),
    (("embed",), "must be 'mlp'"),
    (("embed", "mlp", "quantize"), None),        # trailing quantize OK
    (("mlp:widht=3",), "unknown keys"),
    (("wat",), "unknown tower block kind"),
    (("mlp:hidden",), "expected key=val"),
    (("embed", "attn_block:heads=2,kernel=cuda", "mlp"),
     "kernel must be"),
    ((3,), "must be str or dict"),
    (({"hidden": (4,)},), "no 'kind'"),
])
def test_check_blocks_rejects(blocks, msg):
    if msg is None:
        twr.check_blocks(blocks)
        return
    with pytest.raises(ValueError, match=msg):
        twr.check_blocks(blocks)


def test_resolve_threads_widths():
    spec = twr.resolve(TINY_TOWER, in_dim=5, out_dim=8)
    assert spec.kinds == ("embed", "attn_block", "quantize", "mlp")
    e, a, _, m = spec.blocks
    assert e["tokens"] == 4 and e["chunk"] == 2      # ceil(5/4)
    assert a["dim"] == 16 and a["seq"] == 4 and a["mlp"] == 64
    assert m["dims"] == (16, 16, 8)
    assert (spec.in_dim, spec.out_dim) == (5, 8)


def test_resolve_rejects_indivisible_heads():
    with pytest.raises(ValueError, match="not divisible"):
        twr.resolve(("embed:dim=10", "attn_block:heads=4", "mlp"), 5, 8)


def test_legacy_dims_tower_warns_once():
    twr._warned_dims = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        s1 = twr.legacy_dims_tower((5, 16, 8))
        twr.legacy_dims_tower((8, 4, 3))
    assert len(w) == 1 and issubclass(w[0].category, DeprecationWarning)
    assert s1.blocks[0]["dims"] == (5, 16, 8)
    # equivalent to the explicit mlp tower
    assert s1 == twr.mlp_tower(5, (16,), 8)


def test_recsys_config_dims_shims():
    from repro.configs.vfl_recsys import VFLRecsysConfig
    cfg = VFLRecsysConfig().reduced()
    bt = cfg.bottom_tower(64)
    assert bt.blocks[0]["dims"] == (64, 32, cfg.embedding_dim)
    tt = cfg.top_tower()
    assert tt.blocks[0]["dims"] == (16, 16, 8, cfg.n_items)
    assert tt.blocks[0]["final_act"] is False


# ---------------------------------------------------------------------------
# bit-identity: the default path IS the legacy MLP
# ---------------------------------------------------------------------------


def test_mlp_tower_params_match_legacy_mlp_init():
    key = jax.random.PRNGKey(7)
    legacy = mlp_init(key, (5, 16, 8))
    spec = twr.mlp_tower(5, (16,), 8)
    params = twr.init(spec, key)
    assert len(params) == 1
    for lp, tp in zip(legacy, params[0]):
        np.testing.assert_array_equal(np.asarray(lp["w"]),
                                      np.asarray(tp["w"]))
        np.testing.assert_array_equal(np.asarray(lp["b"]),
                                      np.asarray(tp["b"]))


def test_default_cfg_resolves_to_mlp_tower():
    cfg, master, members = _splitnn_case()
    bs = bottom_spec(cfg, 5)
    assert bs == twr.mlp_tower(5, cfg.hidden, cfg.embedding_dim)
    ts = top_spec(cfg, 3)
    assert ts.blocks[0]["final_act"] is False


def test_depth1_tower_path_matches_seed_trace():
    """The TowerSpec-backed split-NN at depth 1 reproduces the recorded
    seed losses bit-for-bit (same assertion as the legacy engine test,
    now exercising the factory path end to end)."""
    cfg, master, members = _splitnn_case()
    res = run_vfl(cfg, master, members, mode="thread")
    np.testing.assert_allclose(
        [h["loss"] for h in res["master"]["history"]],
        TRACES["split_nn"]["losses"], rtol=1e-6)


def test_checkpoint_migrates_legacy_flat_layers():
    """Pre-tower checkpoints stored the bottom/top as a flat layer list;
    load_state_dict must lift them into the one-block tower shape."""
    key = jax.random.PRNGKey(3)
    flat = mlp_init(key, (5, 16, 8))
    tower = SplitNNProtocol._as_tower(
        [{"w": np.asarray(l["w"]), "b": np.asarray(l["b"])}
         for l in flat])
    assert len(tower) == 1 and len(tower[0]) == 2
    np.testing.assert_array_equal(np.asarray(tower[0][0]["w"]),
                                  np.asarray(flat[0]["w"]))
    # already-nested state passes through unchanged
    again = SplitNNProtocol._as_tower(tower)
    assert again is tower or again == tower


def test_checkpoint_roundtrip_preserves_tower_blocks():
    """New-format checkpoints of embed-first towers must NOT trip the
    legacy flat-MLP migration: the embed block's param dict contains
    'w' too, and wrapping the whole tree as [state] collapses a 4-block
    tower to 1 entry whose apply() silently pairs wrong params."""
    spec = twr.resolve(TINY_TOWER, in_dim=5, out_dim=8)
    params = twr.init(spec, jax.random.PRNGKey(2))
    state = jax.tree.map(np.asarray, params)      # what state_dict saves
    back = SplitNNProtocol._as_tower(state)
    assert len(back) == len(spec.blocks) == 4
    assert set(back[0]) == {"w", "table", "pos"}  # embed stayed block 0
    x = np.random.default_rng(0).normal(size=(4, 5)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(twr.apply(spec, back, x)),
        np.asarray(twr.apply(spec, params, x)), rtol=1e-6)


# ---------------------------------------------------------------------------
# transformer tower: convergence + pipelining
# ---------------------------------------------------------------------------


def test_transformer_tower_converges_at_depth2():
    cfg, master, members = _splitnn_case(tower=TINY_TOWER,
                                         pipeline_depth=2)
    res = run_vfl(cfg, master, members, mode="thread")
    losses = [h["loss"] for h in res["master"]["history"]]
    assert losses[-1] < losses[0]
    acc = res["master"]["exchange"]
    assert acc["steps"] == len(losses)
    assert acc["model_flops_per_step"] > 0
    assert res["member0"]["exchange"]["model_bytes_per_step"] > 0


def test_tower_depths_agree_on_final_loss():
    """Bounded staleness: depth 2 converges to the neighborhood of the
    lock-step run (not bit-identical — gradients are stale)."""
    cfg, master, members = _splitnn_case(tower=TINY_TOWER, epochs=4)
    r1 = run_vfl(cfg, master, members, mode="thread")
    cfg2 = dataclasses.replace(cfg, pipeline_depth=2)
    r2 = run_vfl(cfg2, master, members, mode="thread")
    l1 = r1["master"]["history"][-1]["loss"]
    l2 = r2["master"]["history"][-1]["loss"]
    assert abs(l1 - l2) < 0.1


def test_top_tower_cfg_is_honored():
    cfg, master, members = _splitnn_case(
        top_tower=("mlp:hidden=8|4,final_act=0",), epochs=1)
    res = run_vfl(cfg, master, members, mode="thread")
    assert np.isfinite(res["master"]["history"][-1]["loss"])


# ---------------------------------------------------------------------------
# kernels: pallas (interpret) forward == reference forward
# ---------------------------------------------------------------------------


def test_attention_pallas_matches_ref():
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (2, 2, 4, 8), jnp_dtype())
               for i in range(3))
    ref = twr._attention(q, k, v, "ref")
    pal = twr._attention(q, k, v, "pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fake_quant_pallas_matches_ref_and_is_ste():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (8, 16), jnp_dtype())
    ref = twr.fake_quant(x, "ref")
    pal = twr.fake_quant(x, "pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # straight-through gradient: d(sum(fq(x)))/dx == 1
    g = jax.grad(lambda t: twr.fake_quant(t, "ref").sum())(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(g))


def jnp_dtype():
    import jax.numpy as jnp
    return jnp.float32


# ---------------------------------------------------------------------------
# sharding: sharded == unsharded (subprocess: needs >1 host device)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.models import tower as twr

spec = twr.resolve(("embed:tokens=4,dim=16", "attn_block:heads=2",
                    "mlp:hidden=16"), in_dim=5, out_dim=8)
key = jax.random.PRNGKey(0)
params = twr.init(spec, key)
x = jax.random.normal(jax.random.fold_in(key, 99), (32, 5))
plain = twr.apply(spec, params, x)

rules = twr.make_tower_rules(4)
sh = twr.shard_tower(params, spec, rules)
out = twr.apply(spec, sh, x, rules=rules)
np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                           rtol=1e-5, atol=1e-6)
print("SHARD_OK", float(np.abs(np.asarray(out) - np.asarray(plain)).max()))
"""


def test_sharded_tower_matches_unsharded():
    env = dict(__import__("os").environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARD_OK" in r.stdout


def test_make_tower_rules_guards_device_count():
    assert twr.make_tower_rules(1) is None
    if len(jax.devices()) < 64:
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            twr.make_tower_rules(64)


# ---------------------------------------------------------------------------
# exchange account
# ---------------------------------------------------------------------------


def test_step_account_splits_wall():
    acc = exchange_account(
        10.0, 100,
        {"recv_wait_s": 2.0, "send_s": 1.0, "queued_s": 0.5,
         "wire_s": 1.5, "sent_bytes": 1000.0},
        profile={"flops_per_step": 2e6, "bytes_per_step": 1e3,
                 "params_bytes": 4096})
    assert acc["steps"] == 100
    assert acc["unblocked_s_per_step"] == pytest.approx(0.07)
    assert acc["wire_s_per_step"] == pytest.approx(0.03)
    assert acc["stall_s_per_step"] == pytest.approx(0.02)
    assert acc["dominant"] == "unblocked"
    assert acc["exchange_intensity"] == pytest.approx(2000.0)
    assert acc["params_bytes"] == 4096


def test_driver_result_carries_roofline():
    cfg, master, members = _splitnn_case(epochs=1)
    res = run_vfl(cfg, master, members, mode="thread")
    for role in ("master", "member0"):
        acc = res[role]["exchange"]
        assert acc["steps"] > 0
        assert acc["wall_s_per_step"] > 0
        assert 0.0 <= acc["stall_frac"]
        assert acc["model_flops_per_step"] > 0


def test_roofline_profile_counts_tower_flops():
    cfg, master, members = _splitnn_case(tower=TINY_TOWER)
    spec = bottom_spec(cfg, 5)
    per_fwd = twr.tower_flops(spec, cfg.batch_size)
    proto = SplitNNProtocol.__new__(SplitNNProtocol)
    proto.cfg, proto.role = cfg, "member0"
    proto._spec = spec
    proto.params = twr.init(spec, jax.random.PRNGKey(0))
    prof = proto.cost_profile()
    assert prof["flops_per_step"] == pytest.approx(3.0 * per_fwd)
    assert prof["bytes_per_step"] == pytest.approx(
        2.0 * cfg.batch_size * cfg.embedding_dim * 4)


# ---------------------------------------------------------------------------
# per-link CommCfg ([comm.a.b] edge overrides)
# ---------------------------------------------------------------------------


def _edge_spec_dict(comm):
    return {
        "protocol": {"name": "split_nn", "epochs": 1},
        "agents": {"master": "127.0.0.1:7001",
                   "member0": "127.0.0.1:7002",
                   "member1": "127.0.0.1:7003"},
        "hosts": {"h0": {"control": "127.0.0.1:7100",
                         "agents": ["master", "member0", "member1"]}},
        "comm": comm,
    }


def test_spec_edge_overrides_resolve_per_role():
    from repro.launch.cluster import _spec_from_dict
    spec = _spec_from_dict(_edge_spec_dict({
        "framing": "sock", "timeout": 30.0,
        "link": {"latency_ms": 1.0},
        "master": {"member0": {"latency_ms": 50.0,
                               "bandwidth_mbps": 10.0},
                   "member1": {"timeout": 5.0}},
    }), pathlib.Path("."))
    spec.validate()
    cm = spec.comm_for("master")
    assert cm.peer_overrides["member0"].link.latency_ms == 50.0
    # link-only edge: timeout unset, the transport falls back to the
    # world-level 30.0 (so a job-level comm_timeout still reaches it)
    assert cm.peer_overrides["member0"].timeout is None
    # timeout-only edge: link unset — it rides the shared world link
    # (and its "*" clock / runtime set_link swaps), not a pinned copy
    assert cm.peer_overrides["member1"].link is None
    assert cm.peer_overrides["member1"].timeout == 5.0
    # symmetric: the member sees the same edge toward the master
    c0 = spec.comm_for("member0")
    assert set(c0.peer_overrides) == {"master"}
    assert c0.peer_overrides["master"].link.bandwidth_mbps == 10.0
    # roles with no edges resolve to the plain cfg
    spec2 = _spec_from_dict(_edge_spec_dict({"framing": "sock"}),
                            pathlib.Path("."))
    assert spec2.comm_for("master") is spec2.comm


@pytest.mark.parametrize("comm,msg", [
    ({"master": {"member0": {"tls": {}}}}, "unknown keys"),
    ({"master": {"member0": 5}}, "per-peer tables"),
    ({"master": {"nobody": {"loss": 0.1}}}, "not an agent"),
    ({"master": {"master": {"loss": 0.1}}}, "self"),
    ({"master": {"member0": {"latency_ms": 1.0}},
      "member0": {"master": {"latency_ms": 2.0}}}, "symmetric"),
])
def test_spec_edge_overrides_reject(comm, msg):
    from repro.launch.cluster import _spec_from_dict
    with pytest.raises(ValueError, match=msg):
        spec = _spec_from_dict(_edge_spec_dict(comm), pathlib.Path("."))
        spec.validate()


def test_spec_validates_tower_blocks():
    from repro.launch.cluster import _spec_from_dict
    raw = _edge_spec_dict({"framing": "sock"})
    raw["protocol"]["tower"] = ["embed", "attn_block:heads=0,heads=2"]
    with pytest.raises(ValueError, match=r"\[protocol\] tower"):
        _spec_from_dict(raw, pathlib.Path(".")).validate()
    raw["protocol"]["tower"] = ["embed", "mlp"]
    raw["protocol"]["tower_shard"] = 0
    with pytest.raises(ValueError, match="tower_shard"):
        _spec_from_dict(raw, pathlib.Path(".")).validate()


def test_engine_honors_peer_link_overrides():
    """Only the overridden edge is shaped; the default edge stays
    fast. (ThreadBus + CommCfg.peer_overrides, no cluster involved.)"""
    import time

    from repro.comm.base import CommCfg, LinkSpec
    from repro.comm.local import ThreadBus, ThreadCommunicator

    bus = ThreadBus(["a", "b", "c"])
    cfg = CommCfg(peer_overrides={
        "b": CommCfg(link=LinkSpec(latency_ms=80.0))})
    ca = ThreadCommunicator("a", bus, comm_cfg=cfg)
    cb = ThreadCommunicator("b", bus)
    cc = ThreadCommunicator("c", bus)
    x = {"x": np.zeros(4)}
    t0 = time.monotonic()
    ca.send("c", "t", x)
    cc.recv("a", "t")
    fast = time.monotonic() - t0
    t0 = time.monotonic()
    ca.send("b", "t", x)
    cb.recv("a", "t")
    slow = time.monotonic() - t0
    assert slow >= 0.07
    assert fast < slow
    for c in (ca, cb, cc):
        c.close()


def test_engine_peer_timeout_override():
    from repro.comm.base import CommCfg
    from repro.comm.local import ThreadBus, ThreadCommunicator

    bus = ThreadBus(["a", "b"])
    cfg = CommCfg(timeout=60.0,
                  peer_overrides={"b": CommCfg(timeout=0.2)})
    ca = ThreadCommunicator("a", bus, comm_cfg=cfg)
    with pytest.raises(TimeoutError):
        ca.recv("b", "never")
    ca.close()


def test_timeout_only_override_follows_set_link():
    """A [comm.a.b] edge that only customizes its timeout must not be
    pinned: scripted chaos (set_link partition/slow) still shapes it."""
    import time

    from repro.comm.base import CommCfg, LinkSpec
    from repro.comm.local import ThreadBus, ThreadCommunicator

    bus = ThreadBus(["a", "b"])
    cfg = CommCfg(timeout=60.0,
                  peer_overrides={"b": CommCfg(timeout=30.0)})
    ca = ThreadCommunicator("a", bus, comm_cfg=cfg)
    cb = ThreadCommunicator("b", bus)
    assert "b" not in ca._peer_links          # not pinned
    ca.set_link(LinkSpec(latency_ms=80.0))
    x = {"x": np.zeros(4)}
    t0 = time.monotonic()
    ca.send("b", "t", x)
    cb.recv("a", "t")
    assert time.monotonic() - t0 >= 0.07      # chaos swap reached it
    for c in (ca, cb):
        c.close()


def test_comm_timeout_overrides_edge_pinned_timeouts():
    """VFLJob's comm_timeout rewrites the per-message wait everywhere,
    including timeouts pinned by [comm.a.b] peer_overrides."""
    from repro.comm.base import CommCfg, LinkSpec
    from repro.core.party import _force_comm_timeout

    cfg = CommCfg(timeout=60.0, peer_overrides={
        "member0": CommCfg(timeout=5.0,
                           link=LinkSpec(latency_ms=3.0)),
        "member1": CommCfg(timeout=5.0)})
    out = _force_comm_timeout(cfg, 0.5)
    assert out.timeout == 0.5
    assert all(o.timeout == 0.5 for o in out.peer_overrides.values())
    # link pins survive — only the waits are rewritten
    assert out.peer_overrides["member0"].link.latency_ms == 3.0


def test_vfljob_honors_comm_cfgs():
    """VFLJob plumbs per-role resolved CommCfgs (what from_spec builds
    from [comm.a.b] edges) down to each agent's communicator; the run
    still trains and carries the exchange account."""
    from repro.comm.base import CommCfg, LinkSpec
    from repro.core.party import VFLJob
    cfg, master, members = _splitnn_case(epochs=1)
    edge = CommCfg(peer_overrides={
        "member0": CommCfg(link=LinkSpec(latency_ms=2.0))})
    cfgs = {"master": edge,
            "member0": CommCfg(peer_overrides={
                "master": CommCfg(link=LinkSpec(latency_ms=2.0))})}
    job = VFLJob(cfg, master, members, mode="thread", comm_cfgs=cfgs)
    try:
        fit = job.fit()
        assert np.isfinite(fit["history"][-1]["loss"])
    finally:
        res = job.shutdown()
    assert res["master"]["exchange"]["steps"] > 0
    # the shaped link actually metered wire time
    assert res["master"]["comm"]["wire_s"] > 0
