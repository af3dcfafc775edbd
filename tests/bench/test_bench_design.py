"""The design-sweep configuration and its two metrics: every candidate's
pinned plan digest holds at a seed other than the runs', the driver's
lanes are plan-major with every candidate in the reference sample, and
the metrics read nothing where the program counts or traces nothing."""

import json

import numpy as np
import pytest
from rehearsal import own_run_settings  # noqa: F401 (autouse)

from bench import events, run
from bench.drivers import fleet_design

SPEC = run.load_spec()
CELL = "mnist-design.stoch"


def _config() -> dict:
    cell = {w["name"]: w for w in SPEC["workloads"]}[CELL]
    entry = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    return json.loads((run.ROOT / entry["file"]).read_text())


def test_every_candidate_pins_its_plan_at_another_seed():
    """Each candidate's plan digest holds at a seed neither the runs nor
    the base configuration's test use (the base candidate is held at two
    seeds there)."""
    from repro.core import build_plan

    cfg = _config()
    plans = fleet_design.build_plans(cfg, 2**32 + 19, build_plan)
    assert len(plans) == len(cfg["candidates"]) == 12
    for cand, plan in zip(cfg["candidates"], plans):
        digest = events.plan_digest(events.plan_rows(plan))
        assert events.plan_fields_off(digest, cand["plan"]) == 0, \
            (cand["label"], digest)
        assert plan.strategy == cand["strategy"]


def test_candidates_are_config_major_and_the_base_is_the_first():
    cfg = _config()
    labels = [c["label"] for c in cfg["candidates"]]
    assert labels == [f"{k}-{s}" for k in "ABCD"
                      for s in ("sonic", "tails", "tile-8")]
    base = cfg["candidates"][0]
    assert base["compress"] == {} and base["plan"] == cfg["plan"]
    assert (base["strategy"], base["power"]) == (cfg["strategy"],
                                                 cfg["power"])
    assert {c["power"] for c in cfg["candidates"]} == {"1mF"}


def test_lanes_a_candidate_and_the_reference_sample():
    traffic = json.loads(
        (run.ROOT / "bench" / "traffic" / "design-stoch.json").read_text())
    assert fleet_design.lanes_per_candidate(traffic, 1) == 256
    assert fleet_design.lanes_per_candidate(
        dict(traffic, lanes_per_chip=16), 1) == 4
    with pytest.raises(ValueError, match="does not split"):
        fleet_design.lanes_per_candidate(
            dict(traffic, lanes_per_chip=1022), 1)
    outs = {"reboots": np.arange(48.0), "live": -np.arange(48.0)}
    lanes = fleet_design.sample_lanes(7, 12, 4, 4, outs)
    assert {lane // 4 for lane in lanes} == set(range(12))
    assert 47 in lanes and 0 in lanes
    many = fleet_design.sample_lanes(7, 12, 256, 24, {})
    assert all(sum(lane // 256 == p for lane in many) <= 2
               for p in range(12))


def test_candidate_network_overrides_named_layers_only():
    cfg = _config()
    spec = fleet_design.candidate_network(cfg["network"],
                                          {"fc1": ["svd", 25]})
    got = {ly["name"]: ly.get("compress") for ly in spec["layers"]}
    assert got["fc1"] == ["svd", 25]
    assert got["conv2"] == ["prune", 0.9]
    assert cfg["network"]["layers"][4]["compress"] == ["prune", 0.95]
    with pytest.raises(ValueError, match="no layer"):
        fleet_design.candidate_network(cfg["network"], {"fc9": ["keep"]})


class _NoPython:
    """A trace whose host recorded no Python events."""

    def host_seconds(self, functions):
        return None


@pytest.mark.parametrize("counts", [None, {}, {"replay_events": 0,
                                               "replay_event_slots": 0},
                                    {"replay_events": 5}])
def test_useful_event_share_reads_none_without_counts(counts):
    read = run.load_reader("replay.useful_event_share").read
    ctx = {"trace": _NoPython(), "chunks": 3}
    if counts is not None:
        ctx["counts"] = counts
    assert read(ctx) is None


def test_useful_event_share_reads_the_counts():
    read = run.load_reader("replay.useful_event_share").read
    assert read({"counts": {"replay_events": 3,
                            "replay_event_slots": 12}}) == 25.0


def test_table_ms_per_chunk_reads_none_without_python_events():
    read = run.load_reader("host.table_ms_per_chunk").read
    assert read({"trace": _NoPython(), "chunks": 3}) is None


def test_table_ms_per_chunk_reads_the_table_functions():
    from bench import trace_reduce

    class Ev:
        def __init__(self, name, a, b):
            self.name, self.start_ns, self.duration_ns = name, a, b - a

    class Line:
        def __init__(self, events):
            self.name, self.events = "python", events

    class Plane:
        def __init__(self, lines):
            self.name, self.lines = "/host:CPU", lines

    tr = trace_reduce.Trace([Plane([
        Line([Ev("$driver.py:1 bench_window", 0, 10_000_000),
              Ev("$fleetsim.py:10 _rows_as", 1_000_000, 3_000_000),
              Ev("$fleetsim.py:20 _reboot_upper_bound", 4_000_000,
                 5_000_000),
              Ev("$fleetsim.py:30 _bucket_rows", 5_000_000, 9_000_000)]),
        Line([Ev("$fleetsim.py:40 _int_rows_fit", 2_000_000,
                 4_000_000)])])], "bench_window")
    read = run.load_reader("host.table_ms_per_chunk").read
    assert read({"trace": tr, "chunks": 2}) == pytest.approx(2.5)


def test_kept_heap_keeps_freed_blocks_and_restores_the_defaults():
    """Inside ``KeptHeap`` a block far over glibc's mmap threshold comes
    from the heap, so freeing it and asking again faults in no new pages;
    on leaving, the defaults are back and a large block is mapped again."""
    import resource

    def faults_of_two_blocks(n):
        first = np.ones(n, np.uint8)
        del first
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        second = np.ones(n, np.uint8)
        after = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        del second
        return after - before

    n = 256 << 20
    with fleet_design.KeptHeap() as heap:
        if heap.mallopt is None:
            pytest.skip("the C library has no mallopt")
        kept = faults_of_two_blocks(n)
    mapped = faults_of_two_blocks(n)
    assert kept < mapped // 8, (kept, mapped)
