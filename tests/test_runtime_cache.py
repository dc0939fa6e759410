"""The persistent compilation cache: where entry points put it.

Each case runs in a fresh interpreter, so the cache setting never reaches
the test process.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENABLE = """
import jax
from repro.runtime.cache import enable_compilation_cache
print("USING", enable_compilation_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def _run(code, **env_changes):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_changes)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_env_dir_is_used_and_no_other(tmp_path):
    code = ENABLE + """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda v: v * 2 + 1)(jax.numpy.arange(4.0)).block_until_ready()
"""
    out = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert f"USING {tmp_path}" in out and f"CONFIG {tmp_path}" in out
    assert os.listdir(tmp_path)  # the compiled program landed there


def test_default_dir_is_the_repos():
    want = os.path.join(ROOT, ".jax_cache")
    out = _run(ENABLE)
    assert f"USING {want}" in out and f"CONFIG {want}" in out


def test_importing_the_library_sets_no_cache():
    out = _run("""
import jax
import repro.api, repro.cluster, repro.engine, repro.serve, repro.tune
print("CONFIG", jax.config.jax_compilation_cache_dir)
""")
    assert "CONFIG None" in out
