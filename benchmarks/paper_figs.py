"""Paper-figure reproductions (one function per figure/table).

fig1/fig2  -- IMpJ application model curves (Sec. 3).
table2     -- GENESIS compression of the three networks.
fig4/fig5  -- accuracy/energy Pareto + IMpJ-optimal selection.
fig9       -- inference time: 6 implementations x 4 power systems x 3 nets.
fig10      -- kernel vs control time proportions.
fig11      -- inference energy (1 mF).
fig12      -- SONIC energy profile by op class.
adaptive_risk -- (beyond the paper) energy-adaptive commit batching vs
             stochastic per-charge capacity: rollback waste and the
             adaptive/fixed energy ratio per jitter cv, for the
             single-row window, the cross-charge window, and the
             cross-charge window with EWMA belief recalibration.

The compressed network used by fig9-12 is a fixed, documented configuration
(separate conv1, prune conv2/FCs) matching Table 2's structure; the full
GENESIS sweep (fig4/5) is run at reduced budget and cached under results/.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.compress import LayerChoice, apply_config, pareto_frontier, select, sweep
from repro.core import (POWER_SYSTEMS, STRATEGIES, WILDLIFE, accuracy_sweep,
                        fleet_evaluate)
from repro.core.inference import Conv2D, DenseFC, MaxPool2D, SimNet, SparseFC
from repro.data import make_task
from repro.models.dnn import NETWORKS

RESULTS = Path(__file__).resolve().parent / "results"

PAPER_CLAIMS = {
    "sonic_vs_naive": 1.45,       # SONIC slowdown over naive (continuous)
    "tails_vs_naive": 1.0 / 1.2,  # TAILS is 1.2x FASTER
    "tile8_vs_naive": 13.4,
    "sonic_vs_tile_gain": 6.9,
    "tails_vs_tile_gain": 12.2,
}


# --------------------------------------------------------------------------
# Fig. 1 / Fig. 2
# --------------------------------------------------------------------------

def fig1_2() -> list[tuple]:
    rows = []
    accs = [0.80, 0.90, 0.95, 0.99]
    sw = accuracy_sweep(WILDLIFE, accs)
    for i, a in enumerate(accs):
        rows.append((f"fig1/impj_acc{a:.2f}", round(sw["inference"][i], 4),
                     f"baseline={sw['baseline'][i]:.4f} "
                     f"oracle={sw['oracle'][i]:.4f} "
                     f"ideal={sw['ideal'][i]:.4f}"))
    m2 = WILDLIFE.with_result_only_comm(98.0)
    gain = m2.inference(0.99, 0.99) / WILDLIFE.baseline()
    rows.append(("fig2/results_only_gain_vs_baseline", round(gain, 1),
                 "paper: ~480x"))
    rows.append(("fig2/ideal_over_oracle_gap",
                 round(m2.ideal() / m2.oracle(), 2), "paper: ~2.2x"))
    return rows


# --------------------------------------------------------------------------
# Fixed compressed configurations (Table 2 structure)
# --------------------------------------------------------------------------

def compressed_net(name: str) -> SimNet:
    net = NETWORKS[name]()
    choices = []
    for layer in net.layers:
        if isinstance(layer, Conv2D):
            co, ci, kh, kw = layer.w.shape
            if ci == 1:                       # first conv: separate (HOOI)
                choices.append(LayerChoice("separate",
                                           max(2, min(ci * kh, co * kw) // 6)))
            else:                             # deep conv: prune
                choices.append(LayerChoice("prune", 0.9))
        elif isinstance(layer, DenseFC) and layer.w.size > 20_000:
            choices.append(LayerChoice("prune", 0.95))
        elif isinstance(layer, DenseFC) and layer.w.size > 4_000:
            choices.append(LayerChoice("prune", 0.9))
        else:
            choices.append(LayerChoice("keep"))
    return apply_config(net, tuple(choices))


def table2() -> list[tuple]:
    rows = []
    for name, maker in NETWORKS.items():
        orig = maker()
        comp = compressed_net(name)
        ratio = orig.total_params() / comp.total_params()
        rows.append((f"table2/{name}_params", comp.total_params(),
                     f"orig={orig.total_params()} compression={ratio:.1f}x "
                     f"bytes={comp.params_bytes()} "
                     f"fits={comp.params_bytes() <= 200*1024}"))
    return rows


# --------------------------------------------------------------------------
# Fig. 4 / Fig. 5: GENESIS sweep (cached; reduced budget on CPU)
# --------------------------------------------------------------------------

def fig4_5(budget_configs: int = 10, epochs: int = 2) -> list[tuple]:
    cache = RESULTS / "genesis_sweep.json"
    if cache.exists():
        data = json.loads(cache.read_text())
    else:
        data = {}
        for name in ("mnist", "har"):
            task = make_task(name, n_train=768, n_test=256, noise=0.85)
            res = sweep(NETWORKS[name](), task, WILDLIFE, epochs=epochs,
                        max_configs=budget_configs)
            front = pareto_frontier(res)
            feas = [r for r in res if r.feasible]
            best = select(res) if feas else None
            most_acc = max(feas, key=lambda r: r.accuracy) if feas else None
            data[name] = {
                "n_configs": len(res),
                "n_feasible": len(feas),
                "frontier": [[r.e_infer_j, r.accuracy] for r in front],
                "best_impj": best.impj if best else 0.0,
                "best_acc": best.accuracy if best else 0.0,
                "most_acc_impj": most_acc.impj if most_acc else 0.0,
                "most_acc_acc": most_acc.accuracy if most_acc else 0.0,
                "orig_feasible": res[0].feasible,
            }
        cache.write_text(json.dumps(data, indent=1))
    rows = []
    for name, d in data.items():
        rows.append((f"fig4/{name}_pareto_points", len(d["frontier"]),
                     f"{d['n_feasible']}/{d['n_configs']} feasible; "
                     f"original feasible={d['orig_feasible']} (paper: no)"))
        nontrivial = d["best_impj"] >= d["most_acc_impj"]
        rows.append((f"fig5/{name}_selected_impj", round(d["best_impj"], 4),
                     f"most-accurate-config impj={d['most_acc_impj']:.4f} "
                     f"(selection non-trivial: {nontrivial})"))
    return rows


# --------------------------------------------------------------------------
# Fig. 9-12: intermittent execution matrix
# --------------------------------------------------------------------------

def _matrix(nets=("mnist", "har", "okg")) -> dict:
    """The 6-strategy x 4-power matrix per network, replayed by the
    vectorized fleet simulator (one jitted vmap'd call per network; the
    differential tests pin its equivalence to the scalar ``evaluate``)."""
    cache = RESULTS / "fig9_matrix.json"
    if cache.exists():
        return json.loads(cache.read_text())
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = {}
    for name in nets:
        net = compressed_net(name)
        rng = np.random.default_rng(1)
        x = rng.normal(size=net.input_shape).astype(np.float32)
        for r in fleet_evaluate(net, x, strategies=STRATEGIES,
                                powers=POWER_SYSTEMS):
            out[f"{name}/{r.strategy}/{r.power}"] = {
                "completed": r.completed,
                "live_s": r.live_time_s, "dead_s": r.dead_time_s,
                "total_s": r.total_time_s,
                "energy_j": r.energy_j, "reboots": r.reboots,
                "by_class": r.by_class,
                "dnf": r.dnf_reason,
            }
    cache.write_text(json.dumps(out, indent=1))
    return out


def fig9() -> list[tuple]:
    m = _matrix()
    rows = []
    nets = sorted({k.split("/")[0] for k in m})
    # completion matrix + headline ratios
    ratios = {}
    for name in nets:
        naive = m[f"{name}/naive/continuous"]["live_s"]
        for strat in STRATEGIES:
            cont = m[f"{name}/{strat}/continuous"]
            ratios.setdefault(strat, []).append(cont["live_s"] / naive)
        compl = {p: sum(m[f"{name}/{s}/{p}"]["completed"]
                        for s in STRATEGIES) for p in POWER_SYSTEMS}
        rows.append((f"fig9/{name}_completions_100uF", compl["100uF"],
                     f"of {len(STRATEGIES)} implementations "
                     f"(naive and large tiles may DNF, paper Fig 9b)"))
    gmean = lambda v: float(np.exp(np.mean(np.log(v))))
    for strat in ("tile-8", "tile-128", "sonic", "tails"):
        g = gmean(ratios[strat])
        claim = {"tile-8": "13.4x", "tile-128": "~7.5x", "sonic": "1.45x",
                 "tails": "0.83x (1.2x faster)"}[strat]
        rows.append((f"fig9/{strat}_vs_naive_gmean", round(g, 2),
                     f"paper: {claim}"))
    sonic_gain = gmean([ratios["tile-8"][i] / ratios["sonic"][i]
                        for i in range(len(nets))])
    tails_gain = gmean([ratios["tile-8"][i] / ratios["tails"][i]
                        for i in range(len(nets))])
    rows.append(("fig9/sonic_gain_over_tiled", round(sonic_gain, 1),
                 "paper: 6.9x (vs best reliable tiling)"))
    rows.append(("fig9/tails_gain_over_tiled", round(tails_gain, 1),
                 "paper: 12.2x"))
    return rows


KERNEL_OPS = ("mac", "lea_mac", "alu", "dma_word", "fram_read")
CONTROL_OPS = ("control", "task_transition", "redo_log", "log_lookup",
               "commit_word", "shift_sw", "lea_invoke", "dma_setup",
               "fram_write")


def fig10() -> list[tuple]:
    m = _matrix()
    rows = []
    for strat in ("naive", "tile-32", "sonic", "tails"):
        e = m[f"mnist/{strat}/continuous"]["by_class"]
        kern = sum(e.get(k, 0.0) for k in KERNEL_OPS)
        ctrl = sum(e.get(k, 0.0) for k in CONTROL_OPS)
        frac = kern / (kern + ctrl)
        rows.append((f"fig10/mnist_{strat}_kernel_fraction", round(frac, 3),
                     "paper: SONIC/TAILS mostly kernel; tiled mostly "
                     "control+redo"))
    return rows


def fig11() -> list[tuple]:
    m = _matrix()
    rows = []
    for name in ("mnist", "har", "okg"):
        for strat in ("tile-8", "sonic", "tails"):
            r = m[f"{name}/{strat}/1mF"]
            val = r["energy_j"] * 1e3 if r["completed"] else float("inf")
            rows.append((f"fig11/{name}_{strat}_energy_mJ",
                         round(val, 3) if np.isfinite(val) else -1,
                         "completed" if r["completed"] else "DNF"))
    return rows


def fig12() -> list[tuple]:
    m = _matrix()
    rows = []
    e = m["mnist/sonic/continuous"]["by_class"]
    tot = sum(e.values())
    for cls in ("mac", "fram_read", "fram_write", "control"):
        rows.append((f"fig12/mnist_sonic_{cls}_fraction",
                     round(e.get(cls, 0.0) / tot, 3),
                     "paper: control ~26%, loop-index FRAM writes ~14%"))
    # Under intermittent power the same breakdown includes re-execution and
    # torn partial burns; the replay attributes torn burns by charge order
    # (not lumped into control), so the per-class split stays meaningful.
    ei = m["mnist/sonic/1mF"]["by_class"]
    toti = sum(ei.values())
    for cls in ("mac", "control"):
        rows.append((f"fig12/mnist_sonic_1mF_{cls}_fraction",
                     round(ei.get(cls, 0.0) / toti, 3),
                     f"intermittent profile (continuous: "
                     f"{e.get(cls, 0.0) / tot:.3f}); torn burns attributed "
                     f"by charge order"))
    return rows


def sonic_risk_plan(net, x, span: float = 8.0):
    """One SONIC plan restamped onto a capacitor the inference spans
    ``span`` times -- the risk regime where every run crosses several
    charge boundaries.  SONIC rows are capacity-independent, so the
    restamp avoids a second plan extraction.  Shared by
    :func:`adaptive_risk` and ``examples/intermittent_mnist.py``."""
    import dataclasses

    from repro.core import build_plan, custom_power_system

    plan = build_plan(net, x, "sonic", custom_power_system(1e5))
    ps = custom_power_system(max(1e5, plan.total_cycles / span))
    return dataclasses.replace(plan, power=ps.name,
                               capacity=ps.cycles_per_charge,
                               recharge_s=ps.recharge_s), ps


def adaptive_risk() -> list[tuple]:
    """Beyond the paper: the energy-adaptive commit policy's risk frontier
    on the compressed MNIST net.  Deterministic charges make batched
    commits a strict win (fewer cursor writes, identical reboots); jittered
    per-charge capacities make every mis-predicted chunk roll back to the
    last committed cursor and re-execute -- the ``wasted_cycles`` channel.
    Rows report, per charge-jitter cv, the rollback waste and the
    adaptive/fixed energy ratio (< 1 means batching still pays) -- for the
    single-row window, the cross-charge window (one commit per charge,
    multi-row rollback), and the cross-charge window with EWMA belief
    recalibration (per-lane bias learned instead of believed nominal)."""
    from repro.core import fleet_sweep

    net = compressed_net("mnist")
    rng = np.random.default_rng(1)
    x = rng.normal(size=net.input_shape).astype(np.float32)
    plan, ps = sonic_risk_plan(net, x)
    rows = []
    variants = (("", dict(batch_rows=1, belief_alpha=0.0)),
                ("_xchg", dict(batch_rows=10**6, belief_alpha=0.0)),
                ("_xchg_ewma", dict(batch_rows=10**6, belief_alpha=0.25)))
    for cv in (0.0, 0.3, 0.6):
        jitter = dict(charge_cv=cv, charge_bias_cv=cv, charge_reboots=160)
        fixed = fleet_sweep(net, x, "sonic", ps, n_devices=64, seed=11,
                            plan=plan, **jitter)
        for tag, knobs in variants:
            adap = fleet_sweep(net, x, "sonic", ps, n_devices=64, seed=11,
                               plan=plan, policy="adaptive", theta=0.5,
                               **knobs, **jitter)
            ratio = float(adap.energy_j.mean() / fixed.energy_j.mean())
            rows.append(
                (f"risk/mnist_sonic_wasted_cycles{tag}_cv{cv:g}",
                 round(float(adap.wasted_cycles.mean()), 1),
                 f"fixed-policy waste stays "
                 f"{float(fixed.wasted_cycles.mean()):g}"))
            rows.append(
                (f"risk/mnist_sonic_adaptive_energy_ratio{tag}_cv{cv:g}",
                 round(ratio, 4),
                 "batching pays while < 1 (deterministic: strict win; "
                 "jitter erodes it; EWMA claws it back)"))
    return rows


def svm_vs_dnn() -> list[tuple]:
    """Sec. 5.1: no SVM model is competitive with the DNNs on IMpJ
    (paper: 2x worse on MNIST, 8x on HAR)."""
    from repro.compress.svm_baseline import svm_impj, train_svm
    from repro.compress.train_small import class_rates, train
    from repro.compress.genesis import estimate_energy
    from repro.core.energy import JOULES_PER_CYCLE
    from repro.core.imp import AppModel

    cache = RESULTS / "svm_vs_dnn.json"
    if cache.exists():
        data = json.loads(cache.read_text())
    else:
        data = {}
        for name in ("mnist", "har"):
            # sign-flipped task: zero class means, so the linear SVM is at
            # its structural ceiling while the conv net is not
            task = make_task(name, n_train=768, n_test=256, noise=0.6,
                             sign_flip=True)
            w, b, acc = train_svm(task)
            svm = svm_impj(w, b, task, WILDLIFE)
            dnn, dnn_acc = train(compressed_net(name), task, epochs=3)
            tp, tn = class_rates(dnn, task, 0)
            m = AppModel(WILDLIFE.p, WILDLIFE.e_sense, WILDLIFE.e_comm,
                         estimate_energy(dnn))
            data[name] = {"svm_impj": svm["impj"], "svm_acc": acc,
                          "dnn_impj": m.inference(tp, tn),
                          "dnn_acc": dnn_acc}
        cache.write_text(json.dumps(data, indent=1))
    rows = []
    for name, d in data.items():
        ratio = d["dnn_impj"] / max(d["svm_impj"], 1e-12)
        rows.append((f"sec5.1/{name}_dnn_over_svm_impj", round(ratio, 2),
                     f"svm_acc={d['svm_acc']:.3f} dnn_acc={d['dnn_acc']:.3f}"
                     f" (paper: DNN 2x on MNIST, 8x on HAR)"))
    return rows


def run() -> list[tuple]:
    RESULTS.mkdir(parents=True, exist_ok=True)
    rows = []
    for fn in (fig1_2, table2, fig4_5, fig9, fig10, fig11, fig12,
               adaptive_risk, svm_vs_dnn):
        rows.extend(fn())
    return rows


def main() -> None:
    import argparse

    argparse.ArgumentParser(description=__doc__).parse_args()
    for n, v, d in run():
        print(f'{n},{v},"{d}"')


if __name__ == "__main__":
    main()
