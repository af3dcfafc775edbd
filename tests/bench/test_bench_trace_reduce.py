"""The trace reduction and the metric readers on a trace whose busy
union, gaps and event names are known by hand, and the counts of
simulated work on a tiny plan counted by hand."""

from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np
import pytest
from rehearsal import own_run_settings  # noqa: F401 (autouse)

from bench import events, run, trace_reduce


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


def ev(name, a, b):
    return Ev(name, a, b - a)


def planes():
    main = Line("python", [
        ev("$fleet.py:140 bench_window", 1000, 11000),
        ev("$fleetsim.py:1476 _lane_results", 2000, 9000),
        ev("$numpy asarray", 2500, 8500),
        ev("$fleetsim.py:1456 _dead_time", 8800, 8900),
        ev("$fleetstats.py:291 stats_from_outputs", 9000, 9600),
    ])
    producer = Line("python", [
        ev("$failures.py:339 charge_capacity_jitter_stream", 3000, 4000),
        ev("$failures.py:292 _stream_normals", 3100, 3900),
        ev("$failures.py:309 harvest_jitter_stream", 5000, 5500),
        ev("$failures.py:309 harvest_jitter_stream", 11500, 12500),
    ])
    chip0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_replay", 0, 1500),
                             ev("jit_replay", 2000, 5000),
                             ev("jit_replay", 4500, 7000),
                             ev("jit_replay", 9500, 12000)]),
        Line("XLA Ops", [ev("while", 2000, 6000), ev("fusion", 6000, 7000),
                         ev("copy", 9500, 12000), ev("copy", 0, 1500)]),
    ])
    chip1 = Plane("/device:TPU:1", [
        Line("XLA Modules", [ev("jit_replay", 0, 20000)]),
        Line("XLA Ops", [ev("while", 0, 20000)]),
    ])
    other = Plane("/device:TPU:0 SparseCore 0",
                  [Line("XLA Modules", [ev("x", 0, 20000)])])
    host = Plane("/host:CPU", [main, producer,
                               Line("TraceMe", [ev("not python", 0, 5)])])
    return [host, chip0, chip1, other]


@pytest.fixture
def tr():
    return trace_reduce.Trace(planes(), "bench_window")


def test_window_busy_union_and_gaps(tr):
    assert tr.window_s == pytest.approx(10000e-9)
    assert tr.chips == 2
    # chip 0 inside [1000, 11000]: [1000,1500] + [2000,7000] + [9500,11000]
    assert tr.busy == [[(1000, 1500), (2000, 7000), (9500, 11000)],
                       [(1000, 11000)]]
    assert tr.busy_total_s() == pytest.approx(17000e-9)
    assert tr.busy_s() == pytest.approx(8500e-9)
    assert tr.gaps() == [(1500, 2000), (7000, 9500)]


def test_top_ops_and_labelled_gaps(tr):
    ops = dict(tr.top_device_ops(10))
    assert ops["while"] == pytest.approx((4000 + 10000) / 2 * 1e-9)
    assert ops["fusion"] == pytest.approx(500e-9)
    assert ops["copy"] == pytest.approx((500 + 1500) / 2 * 1e-9)
    assert tr.top_device_ops(1)[0][0] == "while"
    assert tr.idle_gaps(10) == [["numpy asarray", pytest.approx(2500e-9)],
                                ["fleet.py:140 bench_window",
                                 pytest.approx(500e-9)]]


def test_modules_stand_in_for_an_empty_ops_line():
    """A program compiled without op-level trace points: its modules are
    the device's operations."""
    ps = planes()
    ps[1].lines[1] = Line("XLA Ops", [])
    ops = dict(trace_reduce.Trace(ps, "bench_window").top_device_ops(10))
    # chip 0's module events inside the window, each counted whole:
    # 500 + 3,000 + 2,500 + 1,500; and chip 1's "while" op
    assert ops == {"jit_replay": pytest.approx(7500 / 2 * 1e-9),
                   "while": pytest.approx(10000 / 2 * 1e-9)}


def test_host_seconds_union_inside_the_window(tr):
    funcs = [("failures.py", "charge_capacity_jitter_stream"),
             ("failures.py", "harvest_jitter_stream"),
             ("failures.py", "_stream_normals")]
    # the nested _stream_normals counts once; the call after the window
    # does not count
    assert tr.host_seconds(funcs) == pytest.approx(1500e-9)
    assert tr.host_seconds([("fleetsim.py", "nothing")]) is None


def test_metric_readers(tr):
    ctx = {"trace": tr, "chunks": 2, "lane_events": 100.0,
           "bytes_per_event": 40,
           "peaks": lambda: {"hbm_bytes_per_s": 1e9}}
    read = {name: run.load_reader(name).read(ctx) for name in (
        "device.idle_share", "replay.ns_per_event", "replay.roofline_share",
        "host.sample_ms_per_chunk", "host.fold_ms_per_chunk")}
    assert read["device.idle_share"] == pytest.approx(15.0)
    assert read["replay.ns_per_event"] == pytest.approx(170.0)
    # 4,000 bytes at 1 GB/s is 4 us against 17 us busy
    assert read["replay.roofline_share"] == pytest.approx(100 * 4 / 17)
    assert read["host.sample_ms_per_chunk"] == pytest.approx(
        1500e-9 * 1e3 / 2)
    assert read["host.fold_ms_per_chunk"] == pytest.approx(700e-9 * 1e3 / 2)


def test_readers_find_nothing_in_a_trace_without_device_planes():
    tr = trace_reduce.Trace(planes()[:1], "bench_window")
    ctx = {"trace": tr, "chunks": 2, "lane_events": 100.0,
           "bytes_per_event": 40, "peaks": lambda: {}}
    for name in ("device.idle_share", "replay.ns_per_event",
                 "replay.roofline_share"):
        assert run.load_reader(name).read(ctx) is None
    assert tr.idle_gaps(10) == [] and tr.top_device_ops(10) == []


def test_a_trace_without_the_window_is_an_error():
    with pytest.raises(ValueError, match="some_other_window"):
        trace_reduce.Trace(planes(), "some_other_window")


def _tiny_plan(tiles: bool):
    s, c, g, k = 3, 17, 2, 10
    plan = SimpleNamespace(
        kind=np.zeros(s), n=np.zeros(s), iter_cycles=np.zeros(s),
        entry_cycles=np.zeros(s), iter_class=np.zeros((s, c)),
        entry_class=np.zeros((s, c)), commit_cycles=np.zeros(s),
        commit_class=np.zeros((s, c)), entry_seg_class=np.zeros((s, g)),
        entry_seg_cycles=np.zeros((s, g)), tile_flag=np.zeros(s),
        tile_sel_cost=None)
    if tiles:
        plan.tile_n = np.zeros((s, k))
        plan.tile_iter_cycles = np.zeros((s, k))
        plan.tile_iter_class = np.zeros((s, k, c))
        plan.tile_sel_cost = np.zeros((s, k))
    return plan


def test_bytes_per_event_counts_semantic_fields():
    # 6 scalar fields + 3 class vectors of 17 + 2 segment lists of 2
    assert events.bytes_per_event(
        events.plan_rows(_tiny_plan(False))) == 4 * (6 + 51 + 4)
    # TAILS rows add 3 ladders of 10 and a 10 x 17 class table
    assert events.bytes_per_event(
        events.plan_rows(_tiny_plan(True))) == 4 * (61 + 30 + 170)


def test_lane_events_are_rows_plus_charges():
    # three lanes of a 3-row plan, rebooting 0, 2 and 5 times
    assert events.lane_events(3, [0.0, 2.0, 5.0]) == 3 * 3 + 7


def test_plan_digest_counts_by_hand_and_ignores_row_order():
    plan = _tiny_plan(False)
    plan.kind = np.array([0, 0, 2])
    plan.n = np.array([2.0, 0.0, 0.0])
    plan.iter_cycles = np.array([10.0, 0.0, 0.0])
    plan.entry_cycles = np.array([3.0, 5.0, 1.0])
    plan.iter_class[0, 4] = 10.0
    plan.entry_class[:, 2] = [3.0, 5.0, 1.0]
    d = events.plan_digest(events.plan_rows(plan))
    assert d["rows"] == 3
    assert d["rows_by_kind"] == {"0": 2, "2": 1}
    assert d["total_cycles"] == 2 * 10 + 3 + 5 + 1
    assert d["class_cycles"][2] == 9.0 and d["class_cycles"][4] == 20.0
    assert sum(d["class_cycles"]) == d["total_cycles"]
    perm = [2, 0, 1]
    rows = {k: v[perm] for k, v in events.plan_rows(plan).items()}
    assert events.plan_digest(rows) == d
    assert events.plan_fields_off(d, d) == 0
    plan.entry_cycles[1] += 1.0
    moved = events.plan_digest(events.plan_rows(plan))
    # the row hash and the total move; the counts and classes do not
    assert events.plan_fields_off(moved, d) == 2
