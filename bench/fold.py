"""The reference statistics fold: what a streamed sweep's ``FleetStats``
must hold for the per-lane outputs it folded.

Same semantics as the program's fold (distributions over completed lanes,
completion over all, fixed-bin histograms clipped into the end bins, exact
min and max), computed here without the program: every sum is
``math.fsum``, the correctly rounded sum, so the comparison measures how
far the program's own summation lies from the exact one.
"""

from __future__ import annotations

import math

import numpy as np

CLOCK_HZ = 16e6                 # the simulated MSP430's clock
JOULES_PER_CYCLE = 6.25e-11
_RADIO = 16                     # index of the "radio" operation class

STAT_CHANNELS = ("live_cycles", "dead_s", "total_s", "reboots",
                 "wasted_cycles", "belief_cycles", "tx_bytes",
                 "msgs_sent", "msgs_deferred", "tx_joules")
STAT_FIELDS = ("sums", "sumsqs", "mins", "maxs", "hists")


def stat_edges(total_cycles: float, capacity: float, recharge_s: float,
               bins: int = 64) -> dict:
    """Histogram edges from a plan's nominal bounds, for one finite
    capacitor (the sweep's default edges)."""
    total = max(float(total_cycles), 1.0)
    cap = float(capacity)
    reboots_hi = max(8.0 * total / max(cap, 1.0), 8.0)
    live_hi = 8.0 * total
    dead_hi = max(4.0 * reboots_hi * max(float(recharge_s), 1e-9), 1e-9)
    return {
        "live_cycles": np.linspace(0.0, live_hi, bins + 1),
        "dead_s": np.linspace(0.0, dead_hi, bins + 1),
        "total_s": np.linspace(0.0, live_hi / CLOCK_HZ + dead_hi, bins + 1),
        "reboots": np.linspace(0.0, reboots_hi, bins + 1),
        "wasted_cycles": np.linspace(0.0, 2.0 * total, bins + 1),
        "belief_cycles": np.linspace(0.0, 2.0 * max(cap, 1.0), bins + 1),
        "tx_bytes": np.linspace(0.0, 4096.0, bins + 1),
        "msgs_sent": np.linspace(0.0, 256.0, bins + 1),
        "msgs_deferred": np.linspace(0.0, 256.0, bins + 1),
        "tx_joules": np.linspace(0.0, 2.0 * total * JOULES_PER_CYCLE,
                                 bins + 1),
    }


def lane_values(out: dict) -> dict:
    """Each statistics channel's per-lane value."""
    live = np.asarray(out["live"], np.float64)
    zero = live * 0.0
    return {
        "live_cycles": live,
        "dead_s": np.asarray(out["dead"], np.float64),
        "total_s": live / CLOCK_HZ + np.asarray(out["dead"], np.float64),
        "reboots": np.asarray(out["reboots"], np.float64),
        "wasted_cycles": np.asarray(out["wasted"], np.float64),
        "belief_cycles": np.asarray(out["belief"], np.float64),
        "tx_bytes": np.asarray(out.get("tx_bytes", zero), np.float64),
        "msgs_sent": np.asarray(out.get("msgs_sent", zero), np.float64),
        "msgs_deferred": np.asarray(out.get("msgs_deferred", zero),
                                    np.float64),
        "tx_joules": np.asarray(out["classes"], np.float64)[:, _RADIO]
        * JOULES_PER_CYCLE,
    }


def fold(out: dict, edges: dict) -> dict:
    """The exact statistics of per-lane outputs (one group): a flat dict
    of ``name -> float array``."""
    done = ~np.asarray(out["stuck"], bool)
    vals = lane_values(out)
    classes = np.asarray(out["classes"], np.float64)[done]
    ref = {"count": np.array([float(done.size)]),
           "completed": np.array([float(done.sum())]),
           "class_sums": np.array([math.fsum(classes[:, c])
                                   for c in range(classes.shape[1])])}
    for ch in STAT_CHANNELS:
        v = vals[ch][done]
        e = np.asarray(edges[ch])
        bins = e.shape[0] - 1
        idx = np.clip(np.searchsorted(e, v, side="right") - 1, 0, bins - 1)
        ref[f"{ch}:sums"] = np.array([math.fsum(v)])
        ref[f"{ch}:sumsqs"] = np.array([math.fsum(v * v)])
        ref[f"{ch}:mins"] = np.array([v.min() if v.size else np.inf])
        ref[f"{ch}:maxs"] = np.array([v.max() if v.size else -np.inf])
        ref[f"{ch}:hists"] = np.bincount(idx, minlength=bins).astype(float)
        ref[f"{ch}:edges"] = e
    return ref


def flatten(stats) -> dict:
    """A ``FleetStats`` (group 0) in the layout of :func:`fold`."""
    flat = {"count": np.asarray(stats.count, np.float64)[:1],
            "completed": np.asarray(stats.completed, np.float64)[:1],
            "class_sums": np.asarray(stats.class_sums, np.float64)[0]}
    for ch in STAT_CHANNELS:
        for f in STAT_FIELDS:
            v = np.asarray(getattr(stats, f)[ch], np.float64)
            flat[f"{ch}:{f}"] = v[0] if f == "hists" else v[:1]
        flat[f"{ch}:edges"] = np.asarray(stats.edges[ch], np.float64)
    return flat


def max_rel_gap(got: dict, want: dict) -> float:
    """The widest relative gap between two flat statistics dicts, over
    every entry of ``want``; a missing or misshapen entry reads as 1."""
    worst = 0.0
    for k, w in want.items():
        g = got.get(k)
        if g is None or np.shape(g) != np.shape(w):
            return 1.0
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        same = (g == w) | (np.isnan(g) & np.isnan(w))
        if same.all():
            continue
        d = np.abs(g[~same] - w[~same])
        rel = d / np.maximum(np.abs(w[~same]), 1e-300)
        worst = max(worst, float(np.nan_to_num(rel, nan=1.0,
                                                posinf=1.0).max()))
    return worst
