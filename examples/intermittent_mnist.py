"""The paper, end to end: GENESIS-compress an MNIST-shaped network, then run
it on the simulated energy-harvesting device under all six implementations
and four power systems (Fig. 9's experiment) -- and then across a jittered
1000-device fleet.

All experiments run on the vectorized replay engine
(``repro.core.fleetsim``): the 6 x 4 matrix is ONE vmapped call
(``fleet_evaluate``, bit-exact vs the scalar ``evaluate``), the fleet
sweep replays the same plan across 1000 simulated devices with per-device
wake charges and per-reboot recharge traces in another -- seconds of wall
clock, where looping the scalar simulator would take minutes -- a risk
sweep gives every charge a stochastic capacity to show where the
energy-adaptive commit policy's batched cursor writes stop paying, and a
closing fleet-scale query streams ONE MILLION devices through
``reduce="stats"`` + ``lane_chunk=`` to answer completion-rate and
energy-percentile questions without ever materializing the fleet.

  PYTHONPATH=src python examples/intermittent_mnist.py
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.compress import DEVICE_WEIGHT_BYTES  # noqa: E402
from repro.core import (POWER_SYSTEMS, STRATEGIES,  # noqa: E402
                        fleet_evaluate, fleet_sweep)
from repro.core.energy import JOULES_PER_CYCLE  # noqa: E402
from repro.data import make_task  # noqa: E402
from repro.models.dnn import mnist_net  # noqa: E402


def main():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.paper_figs import compressed_net

    orig = mnist_net()
    net = compressed_net("mnist")
    print(f"GENESIS: {orig.total_params()} params "
          f"({orig.params_bytes()//1024} KB, "
          f"fits={orig.params_bytes() <= DEVICE_WEIGHT_BYTES}) -> "
          f"{net.total_params()} params ({net.params_bytes()//1024} KB, "
          f"fits={net.params_bytes() <= DEVICE_WEIGHT_BYTES})")

    # quick accuracy check on the synthetic stand-in task
    from repro.compress.train_small import net_accuracy, train
    task = make_task("mnist", n_train=512, n_test=256, noise=0.85)
    net, acc = train(net, task, epochs=2)
    print(f"retrained compressed net accuracy: {acc:.3f}\n")

    # Fig. 9 matrix: all 24 (strategy, power) cells in one vectorized replay.
    x = task.x_test[0]
    t0 = time.perf_counter()
    matrix = {(r.strategy, r.power): r for r in fleet_evaluate(net, x)}
    matrix_s = time.perf_counter() - t0
    print(f"{'impl':10s}" + "".join(f"{p:>14s}" for p in POWER_SYSTEMS))
    for strat in STRATEGIES:
        cells = [f"{matrix[(strat, p)].total_time_s*1e3:10.1f} ms"
                 if matrix[(strat, p)].completed else f"{'DNF':>13s}"
                 for p in POWER_SYSTEMS]
        print(f"{strat:10s}" + "".join(f"{c:>14s}" for c in cells))
    print(f"\n(naive/large tiles DNF on small capacitors; SONIC & TAILS "
          f"always complete -- the paper's Fig. 9.  Entire matrix replayed "
          f"in {matrix_s:.2f}s.)\n")

    # The same plans across a jittered fleet: 1000 devices, each waking at
    # its own charge level and paying per-reboot recharge times drawn from
    # its own harvest trace.
    n = 1000
    print(f"{n}-device fleet on the 1 mF capacitor "
          f"(per-device wake charge + recharge traces):")
    for strat in ("sonic", "tails"):
        r = fleet_sweep(net, x, strat, "1mF", n_devices=n, seed=42,
                        trace_reboots=64)
        s = r.summary()
        print(f"  {strat:6s} completed={s['completed']}/{n} "
              f"mean={s['mean_total_s']*1e3:8.1f} ms "
              f"p95={s['p95_total_s']*1e3:8.1f} ms "
              f"mean_reboots={s['mean_reboots']:.1f} "
              f"wall={s['wall_s']:.2f}s")
    print("\n(one compiled scan per strategy -- the scalar simulator at "
          f"~tens of ms/device would need minutes for {2 * n} runs.)")

    # Close the loop to the host: give every device a radio and a duty-
    # cycled basestation, and each completed inference takes a traced
    # send/defer/compress decision (decision 5) charged against the same
    # capacitor as compute.  The three send policies trade messages for
    # energy -- the information-per-joule frontier the paper's IMpJ metric
    # becomes once the uplink is simulated rather than assumed free.
    from repro.runtime import RadioModel, SEND_POLICIES, pack_radio
    basestation = RadioModel(window_period_s=0.05, window_duty=0.3)
    print(f"\nuplink co-simulation: {n} sonic devices, basestation "
          f"listening {basestation.window_duty:.0%} of every "
          f"{basestation.window_period_s * 1e3:.0f} ms:")
    print(f"  {'policy':16s} {'sent':>5s} {'defer':>6s} {'bytes':>7s} "
          f"{'radio uJ':>9s} {'bits/J':>10s}")
    for pol in SEND_POLICIES:
        r = fleet_sweep(net, x, "sonic", "1mF", n_devices=n, seed=42,
                        trace_reboots=64,
                        radio=pack_radio(basestation, pol))
        u = r.summary()["uplink"]
        bits = 8.0 * (u["tx_bytes"]
                      - basestation.header_bytes * u["msgs_sent"])
        print(f"  {pol.name:16s} {u['msgs_sent']:5d} "
              f"{u['msgs_deferred']:6d} {u['tx_bytes']:7.0f} "
              f"{u['tx_joules'] * 1e6:9.2f} "
              f"{bits / r.energy_j.sum():10.0f}")
    print("(a send waking into a closed window defers -- dead time, no "
          "energy; a send torn by a power failure re-pays its preamble "
          "after the reboot, like any other atomic row.)")

    # Plan IR v2: the whole (networks x tile-k x capacitors) design space
    # as ONE PlanSet replay.  Every candidate -- original vs GENESIS-
    # compressed network, task tiling vs SONIC vs TAILS, three capacitor
    # sizes -- becomes one lane-major stripe of a single compiled sweep,
    # with per-charge capacity jitter; the Pareto column marks the
    # (completion up, energy down) frontier.  SONIC and Tile-8 rows don't
    # depend on the capacitor, so those plans are built once and restamped
    # per power system; TAILS bakes its tile choice from the capacitor at
    # build time (the "tiles" axis), so it builds per power.  Tile-8 on
    # the 476k-param original would alone be a ~500k-row plan (minutes of
    # build for a config the Fig. 9 matrix already shows DNFs on small
    # caps), so the original network enters via SONIC/TAILS.
    import dataclasses
    from repro.core import PlanSet, build_plan
    from repro.core.energy import make_power_system
    from repro.core.fleetsim import _jit_replay
    powers = ("100uF", "1mF", "50mF")

    def restamped(plan, power):
        p = make_power_system(power)
        return dataclasses.replace(plan, capacity=p.cycles_per_charge,
                                   recharge_s=p.recharge_s, power=p.name)

    plans, labels = [], []
    for nname, cnet in (("orig", orig), ("genesis", net)):
        sonic = build_plan(cnet, x, "sonic", "1mF")
        for p in powers:
            plans.append(restamped(sonic, p))
            labels.append(f"{nname}/sonic/{p}")
            plans.append(build_plan(cnet, x, "tails", p))
            labels.append(f"{nname}/tails/{p}")
    tile8 = build_plan(net, x, "tile-8", "1mF")
    for p in powers:
        plans.append(restamped(tile8, p))
        labels.append(f"genesis/tile-8/{p}")
    design = PlanSet.from_plans(plans, labels=labels)
    res = fleet_sweep(plan=design, n_devices=64, seed=42, charge_cv=0.2,
                      charge_reboots=32)
    rows = res.summary()
    frontier = set()
    best = -1.0
    for i in sorted(range(len(rows)),
                    key=lambda i: rows[i]["mean_energy_j"]):
        if rows[i]["completion"] > best:
            frontier.add(i)
            best = rows[i]["completion"]
    print(f"\ndesign-space sweep: {len(design)} candidates x "
          f"{res.n_devices} devices in ONE compiled replay "
          f"(compiles={_jit_replay(*res.replay_config)._cache_size()}, "
          f"wall={res.wall_s:.2f}s):")
    print(f"  {'candidate':22s} {'done':>5s} {'mean uJ':>9s} "
          f"{'p95 ms':>8s} {'pareto':>6s}")
    for i, row in enumerate(rows):
        uj = (f"{row['mean_energy_j'] * 1e6:9.2f}"
              if np.isfinite(row["mean_energy_j"]) else f"{'DNF':>9s}")
        ms = (f"{row['p95_total_s'] * 1e3:8.1f}"
              if np.isfinite(row["p95_total_s"]) else f"{'-':>8s}")
        print(f"  {row['label']:22s} {row['completion']:5.2f} {uj} {ms} "
              f"{'  *' if i in frontier else '':>6s}")
    print("(every row above replayed under the same jit -- the stacked "
          "candidate axis is how GENESIS prices its whole accuracy-energy "
          "frontier in one fleet_sweep call.)")

    # Risk sweep: the energy-adaptive commit policy (batch the per-
    # iteration cursor write to one commit per charge chunk) is a strict
    # win while every charge delivers exactly its nominal budget.  Give
    # each charge a stochastic capacity instead and every mis-predicted
    # chunk dies before its commit, rolls back to the last cursor, and
    # re-executes -- the wasted_cycles channel.  Where that waste eats the
    # commit savings, adaptive batching stops paying.  Cross-charge
    # batching (one cursor commit per charge spanning many rows) raises
    # both the saving and the stake -- a torn charge now rolls back the
    # whole multi-row window -- and EWMA belief recalibration
    # (belief_alpha) lets a lane with persistently short charges learn its
    # own budget instead of dying at the nominal belief forever.
    from benchmarks.paper_figs import sonic_risk_plan
    plan, ps = sonic_risk_plan(net, x)
    nd = 256
    print(f"\nadaptive-commit risk on a {ps.cycles_per_charge:.0f}-cycle "
          f"capacitor ({plan.total_cycles / ps.cycles_per_charge:.1f} "
          f"charges/inference, {nd} devices, theta=0.5; jitter = "
          f"per-charge cv + equal persistent per-device bias):")
    print(f"  {'charge cv':>9s} {'fixed uJ':>9s} {'adapt uJ':>9s} "
          f"{'xchg uJ':>9s} {'+ewma uJ':>9s} {'xchg waste':>10s} "
          f"{'ewma waste':>10s}")
    variants = (dict(batch_rows=1, belief_alpha=0.0),
                dict(batch_rows=10**6, belief_alpha=0.0),
                dict(batch_rows=10**6, belief_alpha=0.25))
    for cv in (0.0, 0.2, 0.4, 0.8):
        jitter = dict(charge_cv=cv, charge_bias_cv=cv, charge_reboots=160)
        fx = fleet_sweep(net, x, "sonic", ps, n_devices=nd, seed=42,
                         plan=plan, **jitter)
        ads = [fleet_sweep(net, x, "sonic", ps, n_devices=nd, seed=42,
                           plan=plan, policy="adaptive", theta=0.5,
                           **kn, **jitter) for kn in variants]
        uj = [a.energy_j.mean() * 1e6 for a in ads]
        print(f"  {cv:9.1f} {fx.energy_j.mean() * 1e6:9.3f} "
              f"{uj[0]:9.3f} {uj[1]:9.3f} {uj[2]:9.3f} "
              f"{ads[1].wasted_cycles.mean():10.0f} "
              f"{ads[2].wasted_cycles.mean():10.0f}")
    print("(single-row chunks bound each rollback to one row; the "
          "cross-charge window wins big on calm charges and bleeds on "
          "jittery ones; EWMA recalibration claws most of that back -- "
          "1 cycle = {:.1e} J.)".format(JOULES_PER_CYCLE))

    # Fleet-scale queries: past ~1e5 devices the per-lane result arrays
    # (and the per-lane input traces behind them) stop fitting anywhere,
    # so ask the *question* instead of materializing the fleet.
    # reduce="stats" folds every lane into fixed-size running statistics
    # chunk by chunk and lane_chunk= streams the device axis
    # through one constant-size buffer -- peak memory is set by
    # the chunk, not the fleet, so the same call scales to 1e7 lanes
    # (FleetStats.peak_lane_bytes records the bound).
    big = 1_000_000
    st = fleet_sweep(net, x, "sonic", "1mF", n_devices=big, seed=42,
                     reduce="stats", lane_chunk=8192)
    s = st.summary()
    print(f"\n{big}-device fleet-level query (streamed, reduce='stats'):")
    print(f"  completion rate : {st.completion_rate[0]:.4f} "
          f"({s['completed']}/{s['devices']})")
    print(f"  energy/inference: p50={st.energy_percentile(50.0)[0]*1e6:.2f}"
          f" uJ  p95={st.energy_percentile(95.0)[0]*1e6:.2f} uJ "
          f"(exact max {st.maxs['live_cycles'][0] * JOULES_PER_CYCLE*1e6:.2f} uJ)")
    print(f"  p95 wall/device : {s['p95_total_s']*1e3:.1f} ms "
          f"(histogram-resolution percentile)")
    print(f"  peak lane buffer: {st.peak_lane_bytes/1e6:.1f} MB for "
          f"{big} lanes -- identical at 1e4 or 1e7 (wall "
          f"{s['wall_s']:.1f}s)")


if __name__ == "__main__":
    main()
