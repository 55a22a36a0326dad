"""The chip-facing entry points refuse to run without a TPU, fail when a
phase fails, and share one compile cache.  (That ``chip_smoke.py``
passes is only ever shown on the chip; here the sandbox has none, which
is exactly the case these tests need.)"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

import deepspeed_tpu as ds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _CELLS = [w["name"] for w in json.load(_f)["workloads"]]


# the smoke, and the driver's own command for every cell of the benchmark
@pytest.mark.parametrize("command", [
    pytest.param(["chip_smoke.py"], id="chip_smoke.py"),
    *(pytest.param(["benchmark/run.py", "--workload", cell, "--seed", "1",
                    "--seconds", "1", "--trace", "0"], id=cell)
      for cell in _CELLS)])
def test_script_fails_without_a_tpu(command):
    """Non-zero exit, a message naming the missing TPU, and no metric or
    result line on stdout — never a CPU number under a chip's name."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, *command],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("failing", ["train_phase", "serve_phase"])
def test_a_failing_phase_fails_the_smoke(smoke, monkeypatch, capsys,
                                         failing):
    device = {"platform": "tpu", "kind": "fake", "count": 1}
    monkeypatch.setattr(smoke, "require_tpu", lambda chips: device)
    monkeypatch.setattr(smoke, "train_phase",
                        lambda *a, **k: ({"phase": "train"}, None))

    def boom(*a, **k):
        raise smoke.SmokeFailure("forced")
    monkeypatch.setattr(smoke, failing, boom)
    with pytest.raises(smoke.SmokeFailure, match="forced"):
        smoke.main(["chip_smoke.py"])
    assert '"ok"' not in capsys.readouterr().out


def test_wrong_chip_count_is_refused(smoke, capsys):
    """The CPU mesh has 8 devices, none a TPU: exit 2 whatever is asked."""
    with pytest.raises(SystemExit) as e:
        smoke.require_tpu(8)
    assert e.value.code == 2
    assert "TPU" in capsys.readouterr().err


class TestCompileCache:
    def test_env_variable_wins_and_no_path_is_set(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert ds.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

    def test_cpu_runs_are_left_alone(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert ds.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_accelerator_default_is_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        try:
            assert ds.enable_compile_cache() == os.path.join(
                ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                ROOT, ".jax_cache")
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
