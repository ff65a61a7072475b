"""The serving launcher's entry point: ``main(argv)`` replays a trace on
real (smoke-size) engines and returns the report, and the persistent
compilation cache lands where ``launch.compile_cache`` says."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import serve
from repro.launch.compile_cache import DEFAULT_CACHE_DIR

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
SRC = os.path.join(REPO, "src")

CACHE_SCRIPT = r"""
import sys

import jax

from repro.launch.compile_cache import enable_compile_cache

path = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2.0)(1.0).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_run(env_dir, compile_):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT,
         "compile" if compile_ else "no-compile"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    cache = tmp_path / "jax-cache"
    path, configured = _cache_run(cache, compile_=True)
    assert path == configured == str(cache)
    assert any(cache.iterdir())          # the compiled program is there


def test_compile_cache_defaults_to_ignored_checkout_dir():
    path, configured = _cache_run(None, compile_=False)
    assert path == configured == str(DEFAULT_CACHE_DIR)
    assert os.path.samefile(DEFAULT_CACHE_DIR.parent, REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.fixture
def cache_config(tmp_path, monkeypatch):
    """``serve.main`` turns the persistent cache on for the process:
    point it at a scratch directory and put the setting back after."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel,bank_mode", [("einsum", "padded"),
                                              ("sgmv", "bucketed")])
def test_main_replays_and_returns_the_report(cache_config, capsys,
                                             kernel, bank_mode):
    argv = ["--arch", "internlm2-1.8b", "--servers", "2",
            "--requests", "6", "--prompt-len", "8", "--max-new", "4",
            "--duration", "0.5", "--rebalance-period", "0.2",
            "--lora-kernel", kernel, "--bank-mode", bank_mode]
    model = serve.build_model("internlm2-1.8b", "smoke", 0)
    report = serve.main(argv, model=model)
    out = capsys.readouterr().out
    assert "finished=6/6 timed_out=0" in out
    assert report.completed() == 6 and report.timed_out == 0
    assert report.rebalances >= 1
    assert all(len(r.tokens) == 4 and r.n_output == 4
               for r in report.results)
