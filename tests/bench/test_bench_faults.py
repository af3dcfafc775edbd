"""The benchmark's ``correct`` comes out false with a fault planted under
a run (``bench.faults``), in every cell that can have the fault, and with
the replay or the fold computed in float32, in every cell."""

import pytest
import rehearsal
from rehearsal import own_run_settings  # noqa: F401 (autouse)

ONE_CHIP = sorted(c for c, n in rehearsal.cells().items() if n == 1)
MESHED = sorted((c, n) for c, n in rehearsal.cells().items() if n > 1)
FAULTS = ("state_unchanged", "half_batch", "answer_altered", "fold32",
          "plan_altered")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_is_not_correct(root, cell, fault):
    res = rehearsal.run(root, cell, fault)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell,chips", MESHED)
def test_fault_across_chips_is_not_correct(root, cell, chips):
    plants = FAULTS + ("no_exchange",)
    got = rehearsal.run_on_devices(root, [(cell, p) for p in plants], chips)
    assert [r["correct"] for r in got] == [False] * len(plants), \
        [r["checks"] for r in got]


def test_float32_replay_is_not_correct_on_the_closed_form(root):
    """The control: the steady traffic's closed-form replay keeps
    fractional charges, which float32 rounds."""
    res = rehearsal.run(root, "mnist-sonic.steady", "replay32")
    assert res["correct"] is False
    assert res["checks"]["ref_cycle_gap"]["value"] > \
        res["checks"]["ref_cycle_gap"]["limit"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_float32_replay_is_not_correct(root, cell):
    """The control in every cell: where float32 holds every whole cycle
    of the traffic (the stoch cells), the replay's float32 channels still
    fail the configuration's state precision."""
    res = rehearsal.run(root, cell, "replay32")
    assert res["correct"] is False
    narrow = res["checks"]["replay_narrow_channels"]
    assert narrow["value"] > narrow["limit"]


def test_altered_plan_is_caught_by_the_pin_alone(root):
    """The reference replays the plan's own rows, so it agrees with an
    altered plan; the configuration's pinned digest does not."""
    res = rehearsal.run(root, "mnist-sonic.stoch", "plan_altered")
    assert res["correct"] is False
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert checks["plan_fields_off"] > 0
    assert checks["ref_exact_lanes_off"] == 0
