"""Differential tests for the overlapped streaming replay pipeline.

The contract under test: ``prefetch >= 1`` (the default double-buffered
producer/consumer pipeline of ``_chunked_replay``, folding each chunk on
the host while the next replays) must be *bit-exact*
against ``prefetch=0`` -- the legacy fully synchronous chunk loop -- on
every output channel, for both ``reduce="stats"`` (same chunk partials,
same left-fold merge order) and ``reduce="none"`` (same concatenated
lanes), across the strategy x policy x charge-jitter grid, non-divisible
final chunks, the PlanSet plan-mode chunk path, `capacitor_sweep`, and
``replay_plans``' explicit per-device trace matrices (which since this
PR stream through ``lane_chunk`` by per-chunk slicing, bit-exact vs the
unchunked call).  The chunk partials' ``FleetStats`` merge is
additionally pinned against one fold over all lanes.
"""

import numpy as np
import pytest

from repro.core import (Conv2D, DenseFC, MaxPool2D, SimNet, SparseFC,
                        FleetStats, STAT_CHANNELS, capacitor_sweep,
                        fleet_sweep, replay_plans)
from repro.core.energy import OP_CLASSES
from repro.core.fleetsim import PlanSet, build_plan


@pytest.fixture(scope="module")
def small_net():
    rng = np.random.default_rng(0)
    w1 = rng.normal(size=(3, 1, 3, 3)).astype(np.float32)
    wfc = (rng.normal(size=(8, 75)) * 0.1).astype(np.float32)
    wsp = (rng.normal(size=(5, 8))
           * (rng.random((5, 8)) < 0.35)).astype(np.float32)
    net = SimNet([
        Conv2D(w1, rng.normal(size=3).astype(np.float32)),
        MaxPool2D(2),
        DenseFC(wfc, rng.normal(size=8).astype(np.float32)),
        SparseFC(wsp, rng.normal(size=5).astype(np.float32), relu=False),
    ], input_shape=(1, 12, 12), name="pipenet")
    x = rng.normal(size=(1, 12, 12)).astype(np.float32)
    return net, x


def _assert_stats_bitexact(a: FleetStats, b: FleetStats):
    """Bit-exact equality on EVERY statistic -- the pipeline runs the
    identical chunk partials through the identical left-fold additions,
    so unlike chunk-size invariance there is no fp-reassociation
    tolerance to grant."""
    assert np.array_equal(a.count, b.count)
    assert np.array_equal(a.completed, b.completed)
    assert np.array_equal(a.class_sums, b.class_sums)
    for ch in STAT_CHANNELS:
        assert np.array_equal(a.sums[ch], b.sums[ch]), ch
        assert np.array_equal(a.sumsqs[ch], b.sumsqs[ch]), ch
        assert np.array_equal(a.mins[ch], b.mins[ch]), ch
        assert np.array_equal(a.maxs[ch], b.maxs[ch]), ch
        assert np.array_equal(a.hists[ch], b.hists[ch]), ch
        assert np.array_equal(a.edges[ch], b.edges[ch]), ch


_SWEEP_CHANNELS = ("completed", "live_s", "dead_s", "reboots",
                   "energy_j", "wasted_cycles", "belief_cycles")


def _assert_sweep_bitexact(a, b):
    for ch in _SWEEP_CHANNELS:
        va, vb = getattr(a, ch), getattr(b, ch)
        if va is None:
            assert vb is None, ch
        else:
            assert np.array_equal(va, vb), ch


#: strategy x policy x charge-jitter differential grid.  cv > 0 rides
#: the stochastic fused event stream (the path with trace
#: post-processing on the producer thread); cv = 0 the deterministic
#: closed form.
GRID = [
    ("sonic", "fixed", 0.0),
    ("sonic", "adaptive", 0.3),
    ("tails", "fixed", 0.3),
    ("tails", "adaptive", 0.0),
    ("tile-8", "adaptive", 0.5),
]


@pytest.mark.parametrize("strategy,policy,cv", GRID)
def test_prefetch_bitexact_grid(small_net, strategy, policy, cv):
    net, x = small_net
    kw = dict(n_devices=96, seed=5, policy=policy, theta=0.5,
              batch_rows=4 if policy == "adaptive" else 1,
              belief_alpha=0.25 if cv > 0 else 0.0,
              charge_cv=cv, charge_reboots=16 if cv > 0 else 0,
              trace_reboots=8, lane_chunk=32)
    s0 = fleet_sweep(net, x, strategy, "1mF", reduce="stats",
                     prefetch=0, **kw)
    s1 = fleet_sweep(net, x, strategy, "1mF", reduce="stats",
                     prefetch=1, **kw)
    _assert_stats_bitexact(s0, s1)
    r0 = fleet_sweep(net, x, strategy, "1mF", prefetch=0, **kw)
    r1 = fleet_sweep(net, x, strategy, "1mF", prefetch=1, **kw)
    _assert_sweep_bitexact(r0, r1)


def test_prefetch_nondivisible_final_chunk(small_net):
    """77 lanes / 32-lane chunks: the padded final chunk must survive the
    pipeline bit-exactly (inert lanes masked, outputs sliced), at
    prefetch depths past double buffering too."""
    net, x = small_net
    kw = dict(n_devices=77, seed=9, charge_cv=0.2, charge_reboots=16,
              lane_chunk=32)
    s0 = fleet_sweep(net, x, "sonic", "1mF", reduce="stats",
                     prefetch=0, **kw)
    for depth in (1, 3):
        sd = fleet_sweep(net, x, "sonic", "1mF", reduce="stats",
                         prefetch=depth, **kw)
        _assert_stats_bitexact(s0, sd)
    r0 = fleet_sweep(net, x, "sonic", "1mF", prefetch=0, **kw)
    r1 = fleet_sweep(net, x, "sonic", "1mF", prefetch=1, **kw)
    _assert_sweep_bitexact(r0, r1)
    assert int(s0.count.sum()) == 77


def test_prefetch_peak_bound(small_net):
    """The pipeline's recorded peak is the documented bound: at most
    ``prefetch + 1`` chunk buffers plus one stats partial -- strictly
    more than the sequential single-chunk peak, under (depth+1)x it
    plus the fixed-size partial."""
    net, x = small_net
    kw = dict(n_devices=96, seed=5, charge_cv=0.2, charge_reboots=16,
              lane_chunk=32, reduce="stats")
    p0 = fleet_sweep(net, x, "sonic", "1mF", prefetch=0, **kw)
    p1 = fleet_sweep(net, x, "sonic", "1mF", prefetch=1, **kw)
    from repro.core.fleetstats import partial_nbytes
    partial = partial_nbytes(p0.edges, 1)
    assert p0.peak_lane_bytes < p1.peak_lane_bytes
    assert p1.peak_lane_bytes == 2 * p0.peak_lane_bytes + partial


def test_planset_plan_mode_prefetch_bitexact(small_net):
    net, x = small_net
    ps = PlanSet.from_plans([build_plan(net, x, s, "1mF")
                             for s in ("sonic", "tails")])
    kw = dict(plan=ps, n_devices=40, seed=4, charge_cv=0.1,
              charge_reboots=8, lane_chunk=32)   # 80 lanes, padded tail
    s0 = fleet_sweep(reduce="stats", prefetch=0, **kw)
    s1 = fleet_sweep(reduce="stats", prefetch=1, **kw)
    _assert_stats_bitexact(s0, s1)
    d0 = fleet_sweep(prefetch=0, **kw)
    d1 = fleet_sweep(prefetch=1, **kw)
    _assert_sweep_bitexact(d0, d1)


def test_capacitor_sweep_prefetch_bitexact(small_net):
    net, x = small_net
    kw = dict(capacities=[2e4, 1e5, 5e6], n_devices=30, seed=2,
              charge_cv=0.15, charge_reboots=8, lane_chunk=32)
    s0 = capacitor_sweep(net, x, reduce="stats", prefetch=0, **kw)
    s1 = capacitor_sweep(net, x, reduce="stats", prefetch=1, **kw)
    _assert_stats_bitexact(s0, s1)
    r0 = capacitor_sweep(net, x, prefetch=0, **kw)
    r1 = capacitor_sweep(net, x, prefetch=1, **kw)
    _assert_sweep_bitexact(r0, r1)


def _plan_batch(net, x):
    return [build_plan(net, x, s, p)
            for s in ("sonic", "tails") for p in ("1mF", "100uF")] * 5


def test_replay_plans_explicit_traces_chunked_bitexact(small_net):
    """The closed streamed-sampler gap: explicit ``recharge_traces`` /
    ``charge_traces`` matrices ride ``lane_chunk`` by per-chunk slicing
    and must reproduce the unchunked call bit for bit (non-divisible
    20-lane batch through 8-lane chunks), prefetch on or off."""
    net, x = small_net
    plans = _plan_batch(net, x)
    n = len(plans)
    rng = np.random.default_rng(7)
    rtr = rng.exponential(0.1, (n, 6))
    caps = np.asarray([p.capacity for p in plans])
    ctr = caps[:, None] * rng.lognormal(0.0, 0.2, (n, 8))
    kw = dict(policy="adaptive", theta=0.4, batch_rows=2,
              belief_alpha=0.1, recharge_traces=rtr, charge_traces=ctr)
    base = replay_plans(plans, **kw)
    for prefetch in (0, 1):
        got = replay_plans(plans, lane_chunk=8, prefetch=prefetch, **kw)
        for a, b in zip(base, got):
            assert a.live_cycles == b.live_cycles
            assert a.reboots == b.reboots
            assert a.completed == b.completed
            assert a.dead_s == b.dead_s
            assert a.wasted_cycles == b.wasted_cycles
            assert a.belief_cycles == b.belief_cycles
            assert a.by_class == b.by_class
    s0 = replay_plans(plans, reduce="stats", lane_chunk=8, prefetch=0,
                      **kw)
    s1 = replay_plans(plans, reduce="stats", lane_chunk=8, prefetch=1,
                      **kw)
    _assert_stats_bitexact(s0, s1)
    # chunked vs unchunked stats: identical draws and identical lanes,
    # only the partial-merge association differs -- and with one group
    # the per-chunk sums add in lane order either way, so the histogram
    # and count channels stay exact while fp moments agree to 1e-12.
    su = replay_plans(plans, reduce="stats", **kw)
    assert np.array_equal(su.count, s1.count)
    assert np.array_equal(su.completed, s1.completed)
    for ch in STAT_CHANNELS:
        np.testing.assert_allclose(su.sums[ch], s1.sums[ch], rtol=1e-12)
        assert np.array_equal(su.hists[ch], s1.hists[ch]), ch


def test_replay_plans_seeded_chunked_bitexact(small_net):
    """Philox ``seed=`` draws are lane-indexed, so the drawn traces
    slice per chunk exactly like explicit ones."""
    net, x = small_net
    plans = _plan_batch(net, x)
    kw = dict(seed=11, trace_reboots=4, charge_cv=0.2, recharge_cv=0.25)
    base = replay_plans(plans, **kw)
    got = replay_plans(plans, lane_chunk=8, **kw)
    for a, b in zip(base, got):
        assert a.live_cycles == b.live_cycles
        assert a.reboots == b.reboots
        assert a.completed == b.completed


def test_chunk_partials_merge_like_one_fold():
    """Folding chunks of lanes separately and merging the partials left to
    right gives the one fold over all lanes: counts, histograms and
    extremes exactly, fp moments to 1e-12 (another summation order), and
    the merge associates."""
    from repro.core.fleetstats import default_stat_edges, stats_from_outputs

    rng = np.random.default_rng(3)
    edges = default_stat_edges(5e5, 1e4, 0.5, 16)
    n_groups, n = 2, 180
    out = {
        "live": rng.integers(1, 10**6, n) * 1.0,
        "dead": rng.random(n) * 50,
        "reboots": rng.integers(0, 99, n) * 1.0,
        "wasted": rng.integers(0, 500, n) * 1.0,
        "belief": rng.random(n) * 1e4,
        "stuck": rng.random(n) < 0.1,
        "classes": rng.random((n, len(OP_CLASSES))) * 100,
    }
    gid = rng.integers(0, n_groups, n)
    a, b, c = (stats_from_outputs({k: v[sl] for k, v in out.items()},
                                  edges, gid[sl], n_groups)
               for sl in (slice(0, 60), slice(60, 120), slice(120, n)))
    whole = stats_from_outputs(out, edges, gid, n_groups)
    left, right = a.merge(b).merge(c), a.merge(b.merge(c))
    for st in (left, right):
        assert np.array_equal(st.count, whole.count)
        assert np.array_equal(st.completed, whole.completed)
        for ch in STAT_CHANNELS:
            np.testing.assert_allclose(st.sums[ch], whole.sums[ch],
                                       rtol=1e-12)
            assert np.array_equal(st.hists[ch], whole.hists[ch]), ch
            assert np.array_equal(st.mins[ch], whole.mins[ch]), ch
            assert np.array_equal(st.maxs[ch], whole.maxs[ch]), ch


def test_event_chunk_auto_matches_default(small_net):
    """``event_chunk="auto"`` must pick a measured winner without
    changing any result (every candidate is bit-identical -- the chunk
    length only re-tiles the fused event scan), and must cache the
    winner per bucket-shape key so later sweeps skip the timing runs."""
    from repro.core.fleetsim import _EVENT_CHUNK_CACHE

    net, x = small_net
    kw = dict(n_devices=64, seed=3, charge_cv=0.2, charge_reboots=8,
              lane_chunk=32, reduce="stats")
    before = len(_EVENT_CHUNK_CACHE)
    auto = fleet_sweep(net, x, "sonic", "1mF", event_chunk="auto", **kw)
    assert len(_EVENT_CHUNK_CACHE) == before + 1
    default = fleet_sweep(net, x, "sonic", "1mF", **kw)
    _assert_stats_bitexact(auto, default)
    again = fleet_sweep(net, x, "sonic", "1mF", event_chunk="auto", **kw)
    assert len(_EVENT_CHUNK_CACHE) == before + 1    # cache hit
    _assert_stats_bitexact(auto, again)


def test_prefetch_validation(small_net):
    net, x = small_net
    with pytest.raises(ValueError, match="prefetch"):
        fleet_sweep(net, x, "sonic", "1mF", n_devices=8, lane_chunk=4,
                    prefetch=-1)


@pytest.mark.parametrize("prefetch", [0, 1])
def test_replay_operands_are_float64(small_net, monkeypatch, prefetch):
    """Every chunk's replay call gets 64-bit operands, including those
    the producer thread uploads (64-bit mode is per thread, so the
    producer enters its own): every float one float64, never a float32,
    and every energy-state one (capacities, initial charges, charge
    trace, all-nominal index) int64 or float64 -- here int64, the
    fixed-policy stochastic replay's integer state."""
    import jax

    import repro.core.fleetsim as fs

    real, dtypes, state = fs._jit_replay, [], []

    def spy(*key):
        fn = real(*key)

        def call(*args):
            dtypes.append({np.dtype(a.dtype)
                           for a in jax.tree_util.tree_leaves(args)
                           if np.issubdtype(a.dtype, np.floating)})
            state.append({np.dtype(args[i].dtype) for i in (1, 2, 5, 6)})
            return fn(*args)
        return call

    monkeypatch.setattr(fs, "_jit_replay", spy)
    net, x = small_net
    fleet_sweep(net, x, "sonic", "1mF", n_devices=96, lane_chunk=32, seed=3,
                charge_cv=0.25, charge_reboots=8, prefetch=prefetch,
                reduce="stats")
    assert len(dtypes) == 3
    assert all(d == {np.dtype(np.float64)} for d in dtypes), dtypes
    assert state == [{np.dtype(np.int64)}] * 3, state
