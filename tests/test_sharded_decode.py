"""Multi-device correctness of the §Perf decode levers (subprocess keeps
this test process single-device) + process-mode VFL equivalence."""
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


@pytest.mark.slow
def test_partial_softmax_decode_matches_baseline():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import params as PRM, transformer as T
        from repro.launch import specs as S
        from repro.sharding.rules import MeshRules
        from repro.configs.base import InputShape

        cfg = get_config("glm4-9b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=2, n_heads=4, head_dim=32)
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(2, 4)
        rules = MeshRules(mesh)
        spec = T.model_spec(cfg)
        params = PRM.init_tree(spec, jax.random.key(0), jnp.float32)

        b, s = 4, 16
        toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)

        def run(use_ps):
            c = dataclasses.replace(cfg, decode_partial_softmax=use_ps)
            from repro.sharding.rules import use_rules
            cache = T.init_cache(c, b, s, jnp.float32)
            if use_ps:
                # shard cache seq over model like the dry-run does
                ax = S.cache_axes(c)
                cache = jax.tree.map(
                    lambda x, a: jax.device_put(
                        x, NamedSharding(mesh, rules.act_spec(a, x.shape))),
                    cache, ax,
                    is_leaf=lambda x: hasattr(x, "shape"))

            def step_fn(p, t, ch, i):
                with use_rules(rules if use_ps else None):
                    return T.decode_step(c, p, t, ch, i, None, jnp.float32)

            step = jax.jit(step_fn)
            outs = []
            with mesh:
                for i in range(s):
                    logits, cache = step(params, toks[:, i:i+1], cache, i)
                    outs.append(np.asarray(logits[:, 0]))
            return np.stack(outs, 1)

        base = run(False)
        shard = run(True)
        err = np.abs(base - shard).max()
        assert err < 2e-3, err
        print("SHARDED_DECODE_OK", err)
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=560)
    assert "SHARDED_DECODE_OK" in out.stdout, out.stderr[-3000:]


def test_process_mode_equivalence():
    """The paper's third execution mode (multiprocessing) produces the
    same training trace as thread mode."""
    from repro.core.party import run_vfl
    from repro.core.protocols.base import VFLConfig
    from repro.data.vertical import vertical_partition
    rng = np.random.default_rng(0)
    n, d = 96, 10
    x = rng.normal(size=(n, d))
    y = x @ rng.normal(size=(d, 2)) * 0.3
    ids = [f"u{i:05d}" for i in range(n)]
    master, members = vertical_partition(ids, x, y, widths=[4], seed=1)
    cfg = VFLConfig(protocol="linreg", epochs=1, batch_size=32, lr=0.1,
                    use_psi=False)
    ref = run_vfl(cfg, master, members, mode="thread")
    got = run_vfl(cfg, master, members, mode="process")
    np.testing.assert_allclose(
        [h["loss"] for h in got["master"]["history"]],
        [h["loss"] for h in ref["master"]["history"]], rtol=0, atol=0)
