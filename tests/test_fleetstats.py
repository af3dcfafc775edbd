"""Differential tests for the streamed fleet-statistics reduction.

The contract under test: ``reduce="stats"`` must be *bit-exact* on
counts/sums/histograms against the same statistics computed from the
materialized ``reduce="none"`` outputs (``stats_from_outputs`` is the
numpy oracle), chunked streaming (``lane_chunk=``) must be invariant to
the chunk size (the counter-based samplers give every lane the same draws
no matter which chunk it lands in), and the sharded mesh path must reduce
to the identical fleet summary.
"""

import numpy as np
import pytest

from repro.core import (Conv2D, DenseFC, FleetStats, MaxPool2D, SimNet,
                        SparseFC, STAT_CHANNELS, capacitor_sweep,
                        fleet_sweep, replay_plans, stats_from_outputs)
from repro.core.energy import JOULES_PER_CYCLE, OP_CLASSES


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
    ], input_shape=(1, 12, 12), name="statsnet")
    x = rng.normal(size=(1, 12, 12)).astype(np.float32)
    return net, x


def _assert_stats_equal(a, b, *, approx=()):
    """Bit-exact equality on every statistic; channels in ``approx``
    compare to 1e-12 relative on the fp moments (sums/sumsqs) and exactly
    on everything else.  ``approx`` covers the one legitimate ulp
    source: fp accumulation order differing across chunk partitions
    (min/max, counts and histograms are truly associative and stay
    exact)."""
    assert np.array_equal(a.count, b.count)
    assert np.array_equal(a.completed, b.completed)
    for ch in STAT_CHANNELS:
        if ch in approx:
            assert np.allclose(a.sums[ch], b.sums[ch], rtol=1e-12), ch
            assert np.allclose(a.sumsqs[ch], b.sumsqs[ch], rtol=1e-12), ch
            assert np.allclose(a.mins[ch], b.mins[ch], rtol=1e-12), ch
            assert np.allclose(a.maxs[ch], b.maxs[ch], rtol=1e-12), ch
        else:
            assert np.array_equal(a.sums[ch], b.sums[ch]), ch
            assert np.array_equal(a.sumsqs[ch], b.sumsqs[ch]), ch
            assert np.array_equal(a.mins[ch], b.mins[ch]), ch
            assert np.array_equal(a.maxs[ch], b.maxs[ch]), ch
        assert np.array_equal(a.hists[ch], b.hists[ch]), ch
    if approx:
        assert np.allclose(a.class_sums, b.class_sums, rtol=1e-12)
    else:
        assert np.array_equal(a.class_sums, b.class_sums)


def test_replay_plans_stats_bitexact_raw(small_net):
    """The raw-output oracle: ``replay_plans`` materializes ``ReplayOut``
    lanes with the exact live cycles and per-class breakdown, so every
    streamed statistic -- class_sums included -- must be bit-exact
    against ``stats_from_outputs`` over them."""
    from repro.core import build_plan
    from repro.core.energy import OP_CLASSES
    from repro.runtime.failures import charge_capacity_jitter

    net, x = small_net
    plan = build_plan(net, x, "sonic", "1mF")
    n = 24
    rng = np.random.default_rng(5)
    frac = 0.05 + 0.95 * rng.random(n)
    traces = charge_capacity_jitter(n, 16, plan.capacity, seed=11, cv=0.3)
    kw = dict(init_frac=frac, charge_traces=traces)
    outs = replay_plans([plan] * n, **kw)
    st = replay_plans([plan] * n, reduce="stats", **kw)
    classes = np.zeros((n, len(OP_CLASSES)))
    for i, o in enumerate(outs):
        for j, c in enumerate(OP_CLASSES):
            classes[i, j] = o.by_class.get(c, 0.0)
    out = {
        "live": np.array([o.live_cycles for o in outs]),
        "dead": np.array([o.dead_s for o in outs]),
        "reboots": np.array([o.reboots for o in outs], float),
        "wasted": np.array([o.wasted_cycles for o in outs]),
        "belief": np.array([o.belief_cycles for o in outs]),
        "stuck": np.array([not o.completed for o in outs]),
        "classes": classes,
    }
    ref = stats_from_outputs(out, st.edges)
    _assert_stats_equal(st, ref)
    assert st.count[0] == n


@pytest.mark.parametrize("strategy,policy,cv", [
    ("sonic", "fixed", 0.0),
    ("sonic", "fixed", 0.25),
    ("sonic", "adaptive", 0.3),
    ("tails", "fixed", 0.25),
])
def test_stats_bitexact_vs_materialized(small_net, strategy, policy, cv):
    """Unchunked ``reduce="stats"`` shares the legacy samplers with
    ``reduce="none"``, so the streamed statistics must match the numpy
    oracle over the materialized raw outputs bit for bit, on every
    channel and the per-class sums."""
    net, x = small_net
    kw = dict(n_devices=48, seed=3, policy=policy,
              charge_cv=cv, charge_reboots=16 if cv > 0 else 0)
    if policy == "adaptive":
        kw.update(theta=0.5, batch_rows=4, belief_alpha=0.25)
    r = fleet_sweep(net, x, strategy, "1mF", **kw)
    st = fleet_sweep(net, x, strategy, "1mF", reduce="stats", **kw)
    _assert_stats_equal(st, stats_from_outputs(r.outputs, st.edges))
    # live cycles are the energy channel
    assert np.allclose(st.energy_j_sum,
                       r.energy_j[r.completed].sum(), rtol=1e-12)
    assert st.summary()["devices"] == 48


def test_stats_summary_matches_materialized_summary(small_net):
    net, x = small_net
    kw = dict(n_devices=48, seed=3, charge_cv=0.25, charge_reboots=16)
    r = fleet_sweep(net, x, "sonic", "1mF", **kw)
    st = fleet_sweep(net, x, "sonic", "1mF", reduce="stats", **kw)
    s, ss = r.summary(), st.summary()
    assert ss["completed"] == s["completed"]
    assert ss["mean_reboots"] == pytest.approx(s["mean_reboots"])
    assert ss["mean_total_s"] == pytest.approx(s["mean_total_s"])
    # histogram percentiles are accurate to one bin width
    width = st.edges["total_s"][1] - st.edges["total_s"][0]
    assert abs(ss["p95_total_s"] - s["p95_total_s"]) <= width


def test_chunked_invariant_to_chunk_size(small_net):
    """The counter-based streamed samplers make chunked replay invariant
    to ``lane_chunk`` -- including non-divisible chunks that pad the
    final partial chunk with inert lanes."""
    net, x = small_net
    kw = dict(n_devices=50, seed=3, charge_cv=0.25, charge_reboots=16,
              reduce="stats")
    a = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=50, **kw)
    b = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=17, **kw)
    # every lane's draws and outputs are identical (the reduce="none"
    # test pins that bit-exactly); the fp moments accumulate in a
    # different partition order across chunkings, so they compare to
    # 1e-12 while counts/hists/extremes stay bit-equal
    _assert_stats_equal(a, b, approx=STAT_CHANNELS)
    # peak lane-buffer bytes track the chunk, not the fleet
    assert 0 < b.peak_lane_bytes < a.peak_lane_bytes


def test_chunked_none_reduce_concatenates_bitexact(small_net):
    """``reduce="none"`` with ``lane_chunk`` still returns per-lane rows:
    the chunk concatenation must be invariant to the chunk size too."""
    net, x = small_net
    kw = dict(n_devices=50, seed=3, charge_cv=0.25, charge_reboots=16)
    rn = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=50, **kw)
    rc = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=17, **kw)
    assert np.array_equal(rn.live_s, rc.live_s)
    assert np.array_equal(rn.dead_s, rc.dead_s)
    assert np.array_equal(rn.reboots, rc.reboots)
    assert np.array_equal(rn.completed, rc.completed)


def test_mesh_stats_match_unmeshed(small_net):
    """The shard_map path gives the fleet summary the unmeshed sweep
    gives, bit for bit, unchunked and chunked."""
    from repro.launch.mesh import make_fleet_mesh

    net, x = small_net
    kw = dict(n_devices=48, seed=3, charge_cv=0.25, charge_reboots=16,
              reduce="stats")
    st = fleet_sweep(net, x, "sonic", "1mF", **kw)
    sm = fleet_sweep(net, x, "sonic", "1mF", mesh=make_fleet_mesh(), **kw)
    _assert_stats_equal(st, sm)
    smc = fleet_sweep(net, x, "sonic", "1mF", mesh=make_fleet_mesh(),
                      lane_chunk=17, **kw)
    sc = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=17, **kw)
    _assert_stats_equal(sc, smc)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, sys.argv[1])
import numpy as np
from repro.core import (Conv2D, DenseFC, MaxPool2D, SimNet, STAT_CHANNELS,
                        fleet_sweep)
from repro.launch.mesh import make_fleet_mesh

rng = np.random.default_rng(0)
net = SimNet([
    Conv2D(rng.normal(size=(3, 1, 3, 3)).astype(np.float32),
           rng.normal(size=3).astype(np.float32)),
    MaxPool2D(2),
    DenseFC((rng.normal(size=(8, 75)) * 0.1).astype(np.float32),
            rng.normal(size=8).astype(np.float32), relu=False),
], input_shape=(1, 12, 12), name="meshnet")
x = rng.normal(size=(1, 12, 12)).astype(np.float32)
kw = dict(n_devices=256, seed=3, trace_reboots=8, charge_cv=0.25,
          charge_reboots=16, reduce="stats")
differ = []
for lane_chunk in (None, 96):
    one = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=lane_chunk, **kw)
    four = fleet_sweep(net, x, "sonic", "1mF", lane_chunk=lane_chunk,
                       mesh=make_fleet_mesh(4), **kw)
    for name in ("count", "completed", "class_sums"):
        if not np.array_equal(getattr(one, name), getattr(four, name)):
            differ.append(f"{lane_chunk}/{name}")
    for ch in STAT_CHANNELS:
        for k in ("sums", "sumsqs", "mins", "maxs", "hists"):
            if not np.array_equal(getattr(one, k)[ch], getattr(four, k)[ch]):
                differ.append(f"{lane_chunk}/{ch}:{k}")
print(json.dumps({"differ": differ, "count": float(four.count[0])}))
"""


def test_mesh_stats_match_unmeshed_on_four_devices():
    """Sharded over four devices, the streamed statistics equal the
    unmeshed sweep's bit for bit, floating-point moments included
    (unchunked and chunked)."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT, src],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["count"] == 256
    assert out["differ"] == []


def test_capacitor_sweep_stats_groups(small_net):
    """One stats group per capacitor: group means must match the per-cap
    means of the materialized grid, labels carry the capacitor sizes."""
    net, x = small_net
    caps = [2e4, 1e5, np.inf]
    kw = dict(n_devices=8, seed=1, charge_cv=0.2, charge_reboots=16)
    cs = capacitor_sweep(net, x, caps, reduce="stats", **kw)
    cn = capacitor_sweep(net, x, caps, **kw)
    assert cs.n_groups == 3
    assert np.array_equal(cs.group_labels, np.asarray(caps))
    assert np.array_equal(cs.count, np.full(3, 8.0))
    assert np.array_equal(cs.completed, cn.completed.sum(axis=1))
    done = cn.completed
    for g in range(3):
        assert cs.mean("reboots")[g] == pytest.approx(
            cn.reboots[g][done[g]].mean())
        assert cs.mins["total_s"][g] == pytest.approx(
            cn.total_s[g][done[g]].min(), rel=1e-12)


def test_merge_is_associative_and_checks_edges(small_net):
    net, x = small_net
    kw = dict(seed=3, charge_cv=0.25, charge_reboots=16, reduce="stats")
    parts = [fleet_sweep(net, x, "sonic", "1mF", n_devices=n, **kw)
             for n in (16, 16, 16)]
    ab_c = parts[0].merge(parts[1]).merge(parts[2])
    a_bc = parts[0].merge(parts[1].merge(parts[2]))
    _assert_stats_equal(ab_c, a_bc, approx=STAT_CHANNELS)
    assert ab_c.count.sum() == 48
    bad = parts[1]
    bad.edges = {ch: e * 2.0 for ch, e in bad.edges.items()}
    with pytest.raises(ValueError, match="edges"):
        parts[0].merge(bad)


def test_percentile_and_queries(small_net):
    net, x = small_net
    st = fleet_sweep(net, x, "sonic", "1mF", n_devices=48, seed=3,
                     charge_cv=0.25, charge_reboots=16, reduce="stats")
    r = fleet_sweep(net, x, "sonic", "1mF", n_devices=48, seed=3,
                    charge_cv=0.25, charge_reboots=16)
    ch = "total_s"
    p0, p50, p100 = (st.percentile(ch, q)[0] for q in (0.0, 50.0, 100.0))
    assert p0 <= p50 <= p100
    width = st.edges[ch][1] - st.edges[ch][0]
    assert abs(p50 - np.percentile(r.total_s[r.completed], 50)) <= width
    assert st.completion_rate[0] == pytest.approx(
        r.completed.mean())
    assert st.std(ch)[0] == pytest.approx(
        r.total_s[r.completed].std(), rel=1e-6)
    assert st.energy_percentile(50.0)[0] == pytest.approx(
        st.percentile("live_cycles", 50.0)[0] * JOULES_PER_CYCLE)
    assert st.overhead_cycles.shape == (1,)
    assert (st.overhead_cycles >= 0).all()


def test_reduce_argument_validated(small_net):
    net, x = small_net
    with pytest.raises(ValueError, match="reduce"):
        fleet_sweep(net, x, "sonic", "1mF", n_devices=4, reduce="median")
    with pytest.raises(ValueError, match="reduce"):
        capacitor_sweep(net, x, [1e5], n_devices=4, reduce="median")
