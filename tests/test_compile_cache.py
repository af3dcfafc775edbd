"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: JAX sets its cache up once per
process, on the first compile.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.REPO_CACHE_DIR = Path(sys.argv[2])
used = compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(8)).block_until_ready()
print(json.dumps({"used": used}))
"""


def _run(tmp_path, env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    repo_dir = tmp_path / "repo_cache"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(ROOT / "src"), str(repo_dir)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])["used"], repo_dir


def _entries(d: Path) -> list:
    return sorted(p.name for p in d.iterdir()) if d.exists() else []


@pytest.mark.parametrize("from_env", [True, False])
def test_compiled_entries_land_in_one_directory(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land there and
    nowhere else; without it, in the repository's fixed directory."""
    env_dir = tmp_path / "env_cache" if from_env else None
    used, repo_dir = _run(tmp_path, env_dir)
    want, other = (env_dir, repo_dir) if from_env else (repo_dir, None)
    assert Path(used) == want
    assert _entries(want)
    if other is not None:
        assert not _entries(other)


def test_repo_cache_dir_is_fixed_and_ignored():
    from repro.launch.compile_cache import REPO_CACHE_DIR

    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
