"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at the
reduced recsys size with the Pallas kernels in interpret mode (tail
batches included; the towers' ``auto`` kernels, which pick Pallas only
on a TPU, are steered to it here), the sharded phase on four virtual
CPU devices, the no-TPU exit, the one-process-per-chip guard on the
``*_proc`` modes, and the compile cache's directory choice."""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.vfl_recsys import VFLRecsysConfig  # noqa: E402
from repro.core.party import VFLJob  # noqa: E402
from repro.core.protocols.base import VFLConfig  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import tower as twr  # noqa: E402

# the reduced silos hold 307 common users: batch 32 gives 9 full
# batches and a 19-row tail (19 x 8 = 152 rows into quantize)
BATCH = 32


@pytest.fixture(scope="module")
def silos():
    return chip_smoke.load_silos(VFLRecsysConfig().reduced())


def test_phase_a_trains_tail_batch_and_serves_predict(silos):
    out = chip_smoke.phase_a(*silos, batch=BATCH)
    assert out["n_common"] == 307 and out["tail_rows"] == 19
    assert out["steps"] == 10
    assert out["serve_equals_predict"]
    assert out["serve_queries"] == [1, 16, 64, 307]
    assert 0.0 <= out["eval"]["auc"] <= 1.0


def test_phase_b_interpret_pallas_matches_ref(silos, monkeypatch):
    monkeypatch.setattr(twr, "_use_pallas", lambda kernel: kernel != "ref")
    out = chip_smoke.phase_b(*silos, batch=BATCH)
    assert out["interpret"] and not out["tpu_custom_call"]
    assert out["steps"] == 10 and out["tail_rows"] == 19
    assert out["fwd_rel_err"] <= chip_smoke.FWD_RTOL
    assert out["loss_first_rel_err"] <= chip_smoke.LOSS_RTOL


def test_kernel_tower_pins_kernel_blocks():
    assert chip_smoke.kernel_tower("ref") == (
        "embed:tokens=8,dim=64", "attn_block:heads=4,kernel=ref",
        "quantize:kernel=ref", "mlp:hidden=64")


_SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import json
import chip_smoke
from repro.configs.vfl_recsys import VFLRecsysConfig
from repro.models import tower as twr
twr._use_pallas = lambda kernel: kernel != "ref"   # interpret-mode pallas
m, ms = chip_smoke.load_silos(VFLRecsysConfig().reduced())
out = chip_smoke.phase_shard(m, ms, shard=4, batch=32, steps=4)
print("SHARD " + json.dumps(out, default=float))
"""


def test_phase_shard_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("SHARD ")]
    out = json.loads(line[-1][len("SHARD "):])
    assert out["param_devices"] == [0, 1, 2, 3]
    assert out["sharded_leaves"] > 0
    assert out["loss_max_rel_diff"] <= chip_smoke.SHARD_RTOL


def test_chip_smoke_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=str(ROOT))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("mode", ["process", "socket_proc", "grpc_proc"])
def test_proc_modes_refuse_an_accelerator_backend(monkeypatch, mode):
    rng = np.random.default_rng(0)
    from repro.data.vertical import vertical_partition
    x = rng.normal(size=(16, 4))
    master, members = vertical_partition(
        [f"u{i}" for i in range(16)], x, (x[:, :1] > 0).astype(float),
        widths=[2], seed=0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        VFLJob(VFLConfig(protocol="linreg", use_psi=False), master,
               members, mode=mode)
    assert time.monotonic() - t0 < 5.0


def test_compile_cache_uses_env_dir_else_fixed_checkout_dir(monkeypatch):
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])


_CACHE_SCRIPT = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if name == "/jax/compilation_cache/cache_hits" else None)
enable_compile_cache()
jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(jnp.ones(8)))
print("HITS", len(hits))
"""


def test_compile_cache_second_process_hits(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    runs = [subprocess.run([sys.executable, "-c", _CACHE_SCRIPT,
                            str(ROOT / "src")], capture_output=True,
                           text=True, timeout=120, env=env)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    assert any((tmp_path / "cc").iterdir())
    assert "HITS 0" in runs[0].stdout
    assert "HITS 0" not in runs[1].stdout
