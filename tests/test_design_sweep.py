"""GENESIS's design sweep streamed in lane chunks, on the CPU: a
``PlanSet`` of SONIC, TAILS and Tile-8 candidates whose plan-major lanes
share chunks, replayed on the integer state.

* every lane agrees with the reference interpreter on its own
  candidate's rows, bit for bit;
* each ``FleetStats`` group is the fold of its own lanes;
* ``group_events``, the active lane-events of each group, sums to
  ``replay_events``, does not depend on the chunking, and merges.
"""

from __future__ import annotations

import numpy as np
import pytest
from conftest import make_random_net
from reference_replay import reference_replay

from repro.core import PlanSet, build_plan, fleet_sweep
from repro.core import fleetsim
from repro.core.fleetstats import STAT_CHANNELS, stats_from_outputs
from repro.runtime.failures import (charge_capacity_jitter_stream,
                                    charge_trace_cumulative,
                                    harvest_jitter_stream,
                                    initial_charge_fraction_stream,
                                    reboot_recharge_times_stream,
                                    recharge_trace_cumulative)

STRATEGIES = ("sonic", "tails", "tile-8")
#: 6 lanes of each of 6 candidates in chunks of 8: every chunk holds the
#: lanes of two candidates, runtimes mixed, and the last is padded.
D, CHUNK = 6, 8
SWEEP = dict(n_devices=D, seed=2**31 + 11, recharge_cv=0.25,
             trace_reboots=8, charge_cv=0.25, charge_reboots=16,
             reduce="stats")
CHANNELS = ("live", "reboots", "dead", "classes", "wasted", "stuck",
            "belief")


@pytest.fixture(scope="module")
def plans():
    out = []
    for s in (0, 1):
        net, x = make_random_net(s)
        out += [build_plan(net, x, st, "100uF") for st in STRATEGIES]
    return out


def _sweep(ps, **kw):
    """A chunked design sweep's statistics, the per-lane outputs its fold
    took (in lane order), and the types of the replay's channels."""
    outs, types = [], {}
    fold, results = fleetsim.stats_from_outputs, fleetsim._lane_results

    def tap_fold(out, *a, **k):
        outs.append(out)
        return fold(out, *a, **k)

    def tap_results(res, *a, **k):
        for ch, v in res.items():
            types.setdefault(ch, set()).add(np.dtype(v.dtype))
        return results(res, *a, **k)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fleetsim, "stats_from_outputs", tap_fold)
        mp.setattr(fleetsim, "_lane_results", tap_results)
        stats = fleet_sweep(plan=ps, **dict(SWEEP, **kw))
    lanes = {ch: np.concatenate([o[ch] for o in outs]) for ch in outs[0]}
    return stats, lanes, types


@pytest.fixture(scope="module")
def design(plans):
    ps = PlanSet.from_plans(plans)
    return ps, *_sweep(ps, lane_chunk=CHUNK)


def _lane_inputs(ps, n_lanes: int) -> list[dict]:
    """Every lane's reference inputs, drawn whole from the chunk-invariant
    samplers the chunked sweep draws from."""
    seed, p = SWEEP["seed"], np.arange(n_lanes) // D
    caps, rec = ps.capacity[p], ps.recharge_s[p]
    frac = initial_charge_fraction_stream(n_lanes, seed=seed)
    jm = harvest_jitter_stream(n_lanes, seed=seed, cv=SWEEP["recharge_cv"])
    cum = recharge_trace_cumulative(reboot_recharge_times_stream(
        n_lanes, SWEEP["trace_reboots"], rec, seed=seed) * jm[:, None])
    ccum = charge_trace_cumulative(charge_capacity_jitter_stream(
        n_lanes, SWEEP["charge_reboots"], caps, seed=seed,
        cv=SWEEP["charge_cv"]))
    return [dict(cap=caps[i], rem0=caps[i] * frac[i], tail_s=rec[i] * jm[i],
                 recharge_cum=cum[i], charge_cum=ccum[i])
            for i in range(n_lanes)]


def test_mixed_chunks_replay_each_lane_as_the_reference(plans, design):
    ps, stats, lanes, types = design
    n_lanes = len(plans) * D
    assert len(lanes["stuck"]) == n_lanes
    # the fixed-policy stream on the integer state, in every chunk
    assert types["live"] == {np.dtype(np.int64)}
    for i, kw in enumerate(_lane_inputs(ps, n_lanes)):
        plan = plans[i // D]
        ref = reference_replay(fleetsim._plan_rows(plan), kw.pop("cap"),
                               kw.pop("rem0"), **kw)
        for ch in CHANNELS:
            assert np.array_equal(np.asarray(lanes[ch][i], np.float64),
                                  np.asarray(ref[ch], np.float64)), \
                (i, plan.strategy, ch)
    # Tile-8 lanes reboot more often than the SONIC lanes of their net
    reboots = lanes["reboots"].reshape(len(plans), D).mean(axis=1)
    assert reboots[2] > reboots[0] and reboots[5] > reboots[3]


def test_each_group_is_the_fold_of_its_own_lanes(plans, design):
    ps, stats, lanes, _ = design
    assert stats.n_groups == len(plans)
    assert list(stats.group_labels) == list(ps.labels)
    for p in range(len(plans)):
        own = stats_from_outputs(
            {ch: v[p * D:(p + 1) * D] for ch, v in lanes.items()},
            stats.edges)
        assert stats.count[p] == own.count[0] == D
        assert stats.completed[p] == own.completed[0]
        np.testing.assert_allclose(stats.class_sums[p], own.class_sums[0],
                                   rtol=1e-12)
        for ch in STAT_CHANNELS:
            np.testing.assert_array_equal(stats.hists[ch][p],
                                          own.hists[ch][0])
            assert stats.mins[ch][p] == own.mins[ch][0]
            assert stats.maxs[ch][p] == own.maxs[ch][0]
            np.testing.assert_allclose(stats.sums[ch][p], own.sums[ch][0],
                                       rtol=1e-12)
            np.testing.assert_allclose(stats.sumsqs[ch][p],
                                       own.sumsqs[ch][0], rtol=1e-12)


def test_group_events_sum_to_the_replay_events(plans, design):
    ps, stats, lanes, _ = design
    ge = stats.group_events
    assert ge.dtype == np.int64 and ge.shape == (len(plans),)
    assert int(ge.sum()) == stats.replay_events
    assert stats.replay_events <= stats.replay_event_slots
    # every completed lane walks each of its candidate's rows
    done = (~lanes["stuck"]).reshape(len(plans), D).sum(axis=1)
    assert np.all(ge >= done * ps.n_rows)
    for p in range(len(plans)):
        assert stats.summary(p)["group_events"] == ge[p]


@pytest.mark.parametrize("lane_chunk,prefetch", [(36, 1), (16, 0), (8, 0)])
def test_group_events_do_not_depend_on_the_chunking(plans, design,
                                                    lane_chunk, prefetch):
    """A lane's events are its own: one chunk or many, overlapped or not,
    each group counts the same events; the slots the loop ran do
    depend on the batches."""
    ps, stats, _, _ = design
    other, _, _ = _sweep(ps, lane_chunk=lane_chunk, prefetch=prefetch)
    np.testing.assert_array_equal(other.group_events, stats.group_events)
    assert other.replay_events == stats.replay_events


def test_group_events_merge(plans):
    """Two sweeps' statistics merge group by group, the counters too."""
    ps = PlanSet.from_plans(plans[:3])
    a, _, _ = _sweep(ps, lane_chunk=CHUNK)
    b, _, _ = _sweep(ps, lane_chunk=CHUNK, seed=5)
    both = a.merge(b)
    np.testing.assert_array_equal(both.group_events,
                                  a.group_events + b.group_events)
    assert both.replay_events == a.replay_events + b.replay_events
    assert int(both.group_events.sum()) == both.replay_events


def test_stats_without_a_replay_count_no_events():
    """A fold of hand-built outputs (no event loop) has a zero counter for
    each of its groups."""
    out = {"live": np.ones(3), "dead": np.zeros(3), "reboots": np.zeros(3),
           "wasted": np.zeros(3), "belief": np.zeros(3),
           "classes": np.zeros((3, len(fleetsim.OP_CLASSES))),
           "stuck": np.zeros(3, bool)}
    st = stats_from_outputs(out, fleetsim.default_stat_edges(1.0, 1.0, 1.0),
                            np.array([0, 2, 2]), 3)
    np.testing.assert_array_equal(st.group_events, np.zeros(3, np.int64))
    assert st.group_events.dtype == np.int64
    assert st.merge(st).group_events.tolist() == [0, 0, 0]
