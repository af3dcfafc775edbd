"""The streamed fleet sweep's own instrumentation, on the CPU: the
profiler spans of each stage of the chunk pipeline, the replay's count
of its active and executed lane-event slots, and the stable names of the
replay programs.

  python tests/test_fleet_spans.py <prefetch> <mesh shards>

runs one profiled sweep and prints its spans as one JSON line: the test
of the four-device mesh runs so, in a process with four virtual CPU
devices.  The design sweep of a ``PlanSet`` carries its candidates in the
spans' ``plans`` args.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import (Conv2D, DenseFC, MaxPool2D, PlanSet, SimNet,
                        build_plan, fleet_sweep)
from repro.core import fleetsim
from repro.kernels.charge_replay import event_slots

ROOT = Path(__file__).resolve().parents[1]
#: Every stage span of the pipeline, beside the whole sweep's.
STAGES = ("fleet.sample", "fleet.prep", "fleet.dispatch", "fleet.download",
          "fleet.fold")
LANES, CHUNK = 64, 32
SWEEP = dict(n_devices=LANES, lane_chunk=CHUNK, seed=3, trace_reboots=8,
             charge_cv=0.25, charge_reboots=16, reduce="stats")


def _net():
    rng = np.random.default_rng(0)
    net = SimNet([
        Conv2D(rng.normal(size=(3, 1, 3, 3)).astype(np.float32),
               rng.normal(size=3).astype(np.float32)),
        MaxPool2D(2),
        DenseFC((rng.normal(size=(8, 75)) * 0.1).astype(np.float32),
                rng.normal(size=8).astype(np.float32), relu=False),
    ], input_shape=(1, 12, 12), name="spannet")
    x = rng.normal(size=(1, 12, 12)).astype(np.float32)
    return net, x


def profiled_spans(prefetch: int, shards: int = 0) -> list[dict]:
    """The ``fleet.*`` spans of one profiled two-chunk sweep (after an
    unprofiled one that compiles), each with its thread's line and its
    arguments."""
    mesh = None
    if shards:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(shards)
    plan = build_plan(*_net(), "sonic", "1mF")
    return _profile(dict(SWEEP, plan=plan, prefetch=prefetch, mesh=mesh))


def _profile(kw: dict) -> list[dict]:
    """The ``fleet.*`` spans of ``fleet_sweep(**kw)``, profiled after an
    unprofiled call that compiles."""
    import jax

    fleet_sweep(**kw)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            fleet_sweep(**kw)
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        planes = jax.profiler.ProfileData.from_file(path).planes
        return [{"line": i, "name": ev.name, "args": dict(ev.stats),
                 "start": ev.start_ns, "end": ev.start_ns + ev.duration_ns}
                for plane in planes if plane.name.startswith("/host:")
                for i, ln in enumerate(plane.lines) for ev in ln.events
                if ev.name.startswith("fleet.")]


def _chunks(spans, name, line=None) -> set:
    return {s["args"].get("chunk") for s in spans
            if s["name"] == name and line in (None, s["line"])}


def _check_spans(spans, queue: bool) -> int:
    """Every span of the table is there, for chunks 0 and 1; returns the
    line of the thread that ran the sweep."""
    [sweep] = [s for s in spans if s["name"] == "fleet.sweep"]
    assert sweep["args"] == {"lanes": LANES, "chunks": 2, "plans": 1}
    for name in STAGES:
        assert _chunks(spans, name) == {0, 1}, name
    assert _chunks(spans, "fleet.queue_wait") == ({1} if queue else set())
    names = {s["name"] for s in spans}
    assert names == {"fleet.sweep", "fleet.rows", *STAGES} | (
        {"fleet.queue_wait"} if queue else set())
    # the fixed-policy stochastic sweep replays every chunk in int64, one
    # plan a chunk
    assert {(s["args"].get("state"), s["args"].get("plans")) for s in spans
            if s["name"] == "fleet.dispatch"} == {("int64", 1)}
    for s in spans:
        assert sweep["start"] <= s["start"] <= s["end"] <= sweep["end"] \
            or s["line"] != sweep["line"], s
    return sweep["line"]


def test_overlapped_sweep_has_every_span():
    """Prefetch 1: the producer thread samples and prepares chunk 1; the
    window's thread waits for it, dispatches, downloads and folds."""
    spans = profiled_spans(prefetch=1)
    main = _check_spans(spans, queue=True)
    assert _chunks(spans, "fleet.prep", main) == {0}
    assert _chunks(spans, "fleet.sample", main) == {0}
    for name in ("fleet.dispatch", "fleet.download", "fleet.fold",
                 "fleet.queue_wait"):
        assert {s["line"] for s in spans if s["name"] == name} == {main}


def test_synchronous_sweep_has_every_span_but_the_queue():
    spans = profiled_spans(prefetch=0)
    main = _check_spans(spans, queue=False)
    assert {s["line"] for s in spans} == {main}


def test_meshed_sweep_prepares_on_the_calling_thread():
    """Four virtual devices: the mesh path's per-chunk prep runs on the
    thread that called the sweep, not on the producer thread."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          "host_platform_device_count=4"))
    proc = subprocess.run([sys.executable, __file__, "1", "4"],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    spans = json.loads(proc.stdout.splitlines()[-1])
    main = _check_spans(spans, queue=True)
    assert {s["line"] for s in spans if s["name"] == "fleet.prep"} == {main}
    assert _chunks(spans, "fleet.sample", main) == {0}


def _table_bytes(plan) -> int:
    """Bytes of the plan's bucketed row table in the integer state."""
    rows = fleetsim._bucket_rows(fleetsim._plan_rows(plan), lane_axis=False)
    return sum(v.nbytes for v in fleetsim._rows_as(
        rows, np.dtype(np.int64)).values())


def test_row_table_is_uploaded_once_per_state():
    """The overlapped sweep converts and uploads the shared row table once
    for the state its chunks replay in; the synchronous loop, once a
    chunk."""
    plan = build_plan(*_net(), "sonic", "1mF")
    want = {"rows": fleetsim._bucket_target(len(plan)),
            "bytes": _table_bytes(plan)}
    for prefetch, chunks in ((1, 1), (0, 2)):
        spans = profiled_spans(prefetch=prefetch)
        rows = [s for s in spans if s["name"] == "fleet.rows"]
        assert [s["args"] for s in rows] == [want] * chunks, prefetch
        [prep] = [s for s in spans if s["name"] == "fleet.prep"
                  and s["args"]["chunk"] == 0]
        assert prep["start"] <= rows[0]["start"] <= rows[0]["end"] \
            <= prep["end"]


@pytest.mark.parametrize("prefetch", [0, 1])
def test_design_sweep_spans_count_its_candidates(prefetch):
    """A PlanSet of three candidates, plan-major lanes in chunks that
    hold one or two of them: ``fleet.sweep`` carries the set's size,
    each ``fleet.dispatch`` the candidates among its chunk's real lanes,
    and ``fleet.rows`` the whole stacked table, once per state (once a
    chunk on the synchronous loop)."""
    net, x = _net()
    ps = PlanSet.from_plans([build_plan(net, x, s, "1mF")
                             for s in ("sonic", "tails", "tile-8")])
    kw = dict(SWEEP, plan=ps, n_devices=12, lane_chunk=16,
              prefetch=prefetch)
    spans = _profile(kw)
    [sweep] = [s for s in spans if s["name"] == "fleet.sweep"]
    assert sweep["args"] == {"lanes": 36, "chunks": 3, "plans": 3}
    # lanes 0-11, 12-23, 24-35: chunk 0 holds candidates 0 and 1, chunk 1
    # candidates 1 and 2, chunk 2 four lanes of candidate 2 and padding
    assert {s["args"]["chunk"]: s["args"]["plans"] for s in spans
            if s["name"] == "fleet.dispatch"} == {0: 2, 1: 2, 2: 1}
    rows = [s["args"] for s in spans if s["name"] == "fleet.rows"]
    table = ps.rows["kind"].size
    assert [r["rows"] for r in rows] == [table] * (1 if prefetch else 3)
    assert all(r["bytes"] > 0 for r in rows)


def test_event_slots_by_hand():
    # one batch: 4 lanes x 4 events a trip x 3 trips for the 10-event lane
    assert event_slots([3, 10, 0, 5], chunk=4) == 48
    # two shards of two lanes: 2 x 4 x 3 and 2 x 4 x 2
    assert event_slots([3, 10, 0, 5], chunk=4, shards=2) == 40
    assert event_slots([0, 0], chunk=4) == 0


def test_counters_count_a_continuous_power_plan_by_hand():
    """On continuous power every lane walks each row in one event: a
    lane-event a row, and the batched loop runs one trip of the chunk
    for every lane of every chunk, padding lanes included."""
    from repro.core.fleetsim import _bucket_target
    from repro.kernels.charge_replay import default_event_chunk

    plan = build_plan(*_net(), "sonic", "continuous")
    st = fleet_sweep(plan=plan, n_devices=40, lane_chunk=16, seed=3,
                     charge_cv=0.25, charge_reboots=8, reduce="stats")
    chunk = default_event_chunk(_bucket_target(len(plan)))
    trips = -(-len(plan) // chunk)
    assert st.replay_events == 40 * len(plan)
    assert st.replay_event_slots == 3 * 16 * chunk * trips


def test_counters_bound_and_agree_across_prefetch():
    plan = build_plan(*_net(), "sonic", "1mF")
    runs = [fleet_sweep(plan=plan, prefetch=p, **SWEEP) for p in (0, 1)]
    a, b = runs
    assert (a.replay_events, a.replay_event_slots) == \
        (b.replay_events, b.replay_event_slots)
    assert LANES * len(plan) <= a.replay_events <= a.replay_event_slots
    assert a.summary()["replay_events"] == a.replay_events
    assert a.summary()["replay_event_slots"] == a.replay_event_slots
    # each chunk a sweep of its own: the partials add up under merge
    halves = [fleet_sweep(plan=plan, **dict(SWEEP, n_devices=CHUNK,
                                            seed=s)) for s in (3, 4)]
    both = halves[0].merge(halves[1])
    assert both.replay_events == sum(h.replay_events for h in halves)
    assert both.replay_event_slots == sum(h.replay_event_slots
                                          for h in halves)


def test_closed_form_counts_nothing():
    st = fleet_sweep(plan=build_plan(*_net(), "sonic", "1mF"),
                     **dict(SWEEP, trace_reboots=0, charge_cv=0.0,
                            charge_reboots=0))
    assert (st.replay_events, st.replay_event_slots) == (0, 0)


@pytest.mark.parametrize("prefetch", [0, 1])
def test_the_counter_does_not_reach_the_lane_results(monkeypatch,
                                                     prefetch):
    """``_lane_results`` gets the replay's lane channels, in their types,
    and never the event counter: this fixed-policy stochastic sweep
    replays in the integer state, so every cycle channel comes as int64
    and the dead time, which the host recomputes, not at all."""
    real, seen = fleetsim._lane_results, {}

    def spy(res, *a, **kw):
        for k, v in res.items():
            seen.setdefault(k, set()).add(np.dtype(v.dtype))
        return real(res, *a, **kw)

    monkeypatch.setattr(fleetsim, "_lane_results", spy)
    fleet_sweep(plan=build_plan(*_net(), "sonic", "1mF"), prefetch=prefetch,
                **SWEEP)
    i64 = {np.dtype(np.int64)}
    assert seen == {**{k: i64 for k in (
        "live", "reboots", "classes", "wasted", "rem", "belief",
        "tx_bytes", "msgs_sent", "msgs_deferred")},
        "stuck": {np.dtype(bool)}}


def test_replay_programs_have_stable_names(monkeypatch):
    """The replay lowers to ``jit_fleet_replay`` and the sharded replay
    to ``jit_fleet_replay_sharded``, whatever the configuration."""
    import jax

    from repro.launch.mesh import make_fleet_mesh

    seen = {}
    for name in ("_jit_replay", "_jit_sharded_replay"):
        real = getattr(fleetsim, name)

        def capture(*key, _name=name, _real=real):
            fn = _real(*key)

            def call(*args):
                seen[_name] = (fn, args)
                return fn(*args)
            return call
        monkeypatch.setattr(fleetsim, name, capture)
    plan = build_plan(*_net(), "sonic", "1mF")
    fleet_sweep(plan=plan, **SWEEP)
    fleet_sweep(plan=plan, mesh=make_fleet_mesh(1), **SWEEP)
    with jax.enable_x64(True):
        for name, module in (("_jit_replay", "jit_fleet_replay"),
                             ("_jit_sharded_replay",
                              "jit_fleet_replay_sharded")):
            fn, args = seen[name]
            text = fn.lower(*args).as_text()
            assert f"module @{module} " in text, text[:200]


if __name__ == "__main__":
    print(json.dumps(profiled_spans(int(sys.argv[1]), int(sys.argv[2]))))
