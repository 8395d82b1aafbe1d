"""The entry refuses a backend that is not a TPU, and prints no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(cwd, workload="recsys-table1-mlp.train"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_entry_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_entry_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_every_cell_is_found_by_name():
    from chipbench import harness
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell.kind.run
        assert cell.reference.Model
        assert cell.metrics(False) and cell.metrics(True)
        names = {m["name"] for m in cell.metrics(False)}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.metrics(False) + cell.metrics(True):
            assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
