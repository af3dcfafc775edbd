"""Every cell of BENCHMARK.json, run end to end on the CPU at a tiny size
through the benchmark's own driver and checks (the look for a chip
skipped)."""

import json

import pytest
import rehearsal
from rehearsal import own_run_settings  # noqa: F401 (autouse)

SPEC = json.loads((rehearsal.ROOT / "BENCHMARK.json").read_text())
CHIPS = rehearsal.cells()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, plant=None):
    if CHIPS[cell] == 1:
        return rehearsal.run(root, cell, plant)
    [res] = rehearsal.run_on_devices(root, [(cell, plant)], CHIPS[cell])
    return res


@pytest.mark.parametrize("cell", sorted(CHIPS))
def test_cell_runs_correct_end_to_end(root, cell):
    res = _run(root, cell)
    assert res["correct"] is True, res["checks"]
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name
    assert res["failed"] == 0
    assert res["attempted"] >= 2 * rehearsal.TINY_LANES * CHIPS[cell]
    assert res["attempted"] % (rehearsal.TINY_LANES * CHIPS[cell]) == 0
    e2e = [m["name"] for m in SPEC["end_to_end"]]
    assert sorted(res["metrics"]) == sorted(e2e)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == CHIPS[cell]
    assert list(res)[-1] == "checks"


def test_traced_run_reads_the_host_pipeline(root):
    """A traced run on the CPU: the host metrics come from the Python
    tracer; no device plane, so no device metric is reported."""
    res = rehearsal.run(root, "mnist-sonic.stoch", trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"host.sample_ms_per_chunk",
                                   "host.fold_ms_per_chunk"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["window_s"] > 0
    assert res["device"]["busy_s"] == 0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}
