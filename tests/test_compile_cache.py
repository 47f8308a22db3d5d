"""The persistent compile cache goes where the environment says, or to the
one fixed directory in the repo — never anywhere else, and never on import."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCRIPT = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    import repro
    before = jax.config.jax_compilation_cache_dir
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    if len(sys.argv) > 1:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda a: jnp.sin(a) * 3.0)(jnp.ones((7, 5))).block_until_ready()
    print(json.dumps({"before": before, "path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
""")


def _run(env_dir, *argv):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    res = subprocess.run([sys.executable, "-c", SCRIPT, *argv], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    import json
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_holds_the_entries(tmp_path):
    out = _run(tmp_path / "cache", "compile")
    assert out["path"] == out["config"] == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())


def test_default_is_the_fixed_repo_dir_and_import_sets_nothing():
    out = _run(None)
    assert out["before"] is None          # importing repro enables nothing
    assert out["path"] == out["config"] == str(REPO / ".jax_cache")
