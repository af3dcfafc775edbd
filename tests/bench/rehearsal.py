"""Benchmark cells run on the CPU at a tiny size, with or without a planted
fault (``bench.faults``): a helper of the tests beside it, which also puts
the checkout's root on the import path for ``bench``, keeps a test run
from editing the saved Hypothesis examples, and gives them the fixture
that keeps a run's settings to its test.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run is the benchmark's own.  A copy of the benchmark's files,
with each traffic mix cut to a few lanes, stands in a temporary root with
the program's ``src`` beside it.

  python tests/bench/rehearsal.py <root> <cell> <plant|none> ...

runs the given (cell, plant) pairs in one process and prints one JSON
result a line: the four-device cell runs so, in a process of its own with
four virtual CPU devices.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# The saved examples under .hypothesis/examples are part of the tree: a
# run replays them but writes and deletes none (Hypothesis otherwise drops
# those that no longer fail, two at random in each run of
# tests/test_reference_replay.py).  tests/bench is collected before the
# suite's other files, so the profile is in force when their @settings run.
try:
    from hypothesis import settings as _hypothesis_settings
    from hypothesis.database import ReadOnlyDatabase
except ImportError:     # the suite's @given tests skip without hypothesis
    _hypothesis_settings = None
if (_hypothesis_settings is not None
        and _hypothesis_settings.default.database is not None):
    _hypothesis_settings.register_profile(
        "read-only-examples", parent=_hypothesis_settings.default,
        database=ReadOnlyDatabase(_hypothesis_settings.default.database))
    _hypothesis_settings.load_profile("read-only-examples")

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")

#: Lanes per chip of a chunk, and lanes the reference replays, per run.
TINY_LANES = 16
TINY_REF_LANES = 4
SEED = 2**31 + 77
#: A four-chip cell that a tiny root carries where BENCHMARK.json has
#: none, so that the meshed sweep is always rehearsed.
MESH_CELL = {"name": "rehearsal.mesh4", "config": "mnist-sonic-1mF",
             "traffic": "stoch", "chips": 4,
             "why": "the meshed sweep over four devices"}


def workloads() -> list[dict]:
    """The cells a tiny root holds."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = spec["workloads"]
    if all(int(w["chips"]) == 1 for w in cells):
        cells = cells + [MESH_CELL]
    return cells


def cells() -> dict:
    """Chips of every cell a tiny root holds."""
    return {w["name"]: int(w["chips"]) for w in workloads()}


@pytest.fixture(autouse=True)
def own_run_settings(tmp_path_factory, monkeypatch):
    """A run turns JAX's persistent cache on for every program, and a
    traced run adds libtpu flags; in a test the cache is a temporary
    directory, and both are restored for the tests that share the
    worker."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    libtpu = os.environ.get("LIBTPU_INIT_ARGS")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    if libtpu is None:
        os.environ.pop("LIBTPU_INIT_ARGS", None)
    else:
        os.environ["LIBTPU_INIT_ARGS"] = libtpu


def tiny_root(tmp: Path) -> Path:
    """A copy of the benchmark, every traffic mix cut to a tiny size."""
    root = Path(tmp) / "checkout"
    root.mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = workloads()
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", root / "src")
    for path in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(lanes_per_chip=TINY_LANES, ref_lanes=TINY_REF_LANES)
        path.write_text(json.dumps(mix))
    return root


def run(root: Path, cell: str, plant: str | None = None,
        trace: bool = False, seed: int = SEED) -> dict:
    from bench import faults, run as bench_run

    with faults.plant(plant):
        return bench_run.run_cell(cell, seed, 0.5, trace, require_tpu=False,
                                  root=Path(root), t0=time.perf_counter())


def run_on_devices(root: Path, pairs, n_devices: int,
                   timeout: float = 600) -> list[dict]:
    """``run`` of each (cell, plant) pair in a fresh process that sees
    ``n_devices`` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          f"host_platform_device_count={n_devices}"))
    args = [a for cell, plant in pairs for a in (cell, plant or "none")]
    proc = subprocess.run([sys.executable, __file__, str(root), *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=ROOT)
    if proc.returncode:
        raise RuntimeError(f"rehearsal exited {proc.returncode}:\n"
                           f"{proc.stderr[-4000:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return [json.loads(ln) for ln in lines]


def main(argv) -> None:
    root, rest = Path(argv[0]), argv[1:]
    for cell, plant in zip(rest[::2], rest[1::2]):
        res = run(root, cell, None if plant == "none" else plant)
        print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
