"""The inputs of chosen lanes of a streamed fleet sweep, rebuilt from the
seed for the reference interpreter.

A copy of the arithmetic of the program's chunk-invariant samplers
(``repro.runtime.failures.*_stream`` as ``fleet_sweep(lane_chunk=...)``
draws them): a counter-based Philox stream keyed on ``(seed, stream)``
and advanced to the lane's slot, so lane ``i`` draws the same numbers in
any chunk of any sweep.  Kept here so that the reference's inputs come
from the seed and the traffic file, not from the program.
"""

from __future__ import annotations

import numpy as np

_FRAC, _HARVEST, _RECHARGE, _CHARGE = 0, 1, 2, 3
#: Truncation of the per-charge capacity multiplier.
_CHARGE_LO, _CHARGE_HI = 0.25, 4.0
#: Charges drawn when the traffic asks for a capacity trace without
#: giving its length.
_DEFAULT_CHARGES = 256


def uniforms(lanes, draws: int, seed: int, stream: int) -> np.ndarray:
    """``(len(lanes), draws)`` doubles in [0, 1) of the given lanes."""
    slot = -(-int(draws) // 4) * 4          # whole 4-draw Philox blocks
    out = np.empty((len(lanes), slot))
    for j, lane in enumerate(lanes):
        bg = np.random.Philox(key=np.array([seed, stream], np.uint64))
        bg.advance(int(lane) * slot // 4)
        out[j] = np.random.Generator(bg).random(slot)
    return out[:, :draws]


def normals(lanes, per_lane: int, seed: int, stream: int) -> np.ndarray:
    """Box-Muller standard normals, two uniforms each."""
    u = uniforms(lanes, 2 * per_lane, seed, stream)
    u1, u2 = u[:, :per_lane], u[:, per_lane:]
    return np.sqrt(-2.0 * np.log1p(-u1)) * np.cos(2.0 * np.pi * u2)


def _cumulative(traces: np.ndarray) -> np.ndarray:
    out = np.zeros((traces.shape[0], traces.shape[1] + 1))
    np.cumsum(traces, axis=1, out=out[:, 1:])
    return out


def lane_inputs(lanes, seed: int, capacity: float, recharge_s: float,
                sweep: dict) -> list[dict]:
    """Per lane, the keyword inputs of ``reference_replay``: ``rem0``,
    ``tail_s``, ``recharge_cum`` and ``charge_cum`` (``None`` where the
    traffic draws no such trace).  ``sweep`` holds the traffic's
    ``recharge_cv``, ``trace_reboots``, ``charge_cv`` and
    ``charge_reboots``."""
    lanes = np.asarray(lanes, np.int64)
    frac = 0.05 + 0.95 * uniforms(lanes, 1, seed, _FRAC)[:, 0]
    z = normals(lanes, 1, seed, _HARVEST)[:, 0]
    sigma = np.sqrt(np.log1p(sweep["recharge_cv"] ** 2))
    jm = np.exp(-sigma * sigma / 2 + sigma * z)
    rem0 = capacity * frac
    tail = recharge_s * jm
    cum = ccum = None
    if sweep["trace_reboots"] > 0:
        u = uniforms(lanes, sweep["trace_reboots"], seed, _RECHARGE)
        cum = _cumulative(-np.float64(recharge_s) * np.log1p(-u)
                          * jm[:, None])
    cv = sweep["charge_cv"]
    if cv > 0 or sweep["charge_reboots"] > 0:
        n = sweep["charge_reboots"] or _DEFAULT_CHARGES
        zc = normals(lanes, n + 1, seed, _CHARGE)
        nominal = np.broadcast_to(np.float64(capacity), (len(lanes), n))
        if cv > 0:
            s = np.sqrt(np.log1p(cv * cv))
            mult = np.clip(np.exp(-s * s / 2 + s * zc[:, :n]),
                           _CHARGE_LO, _CHARGE_HI)
        else:
            mult = np.ones((len(lanes), n))
        ccum = _cumulative(np.maximum(np.rint(nominal * mult), 1.0))
    return [dict(rem0=float(rem0[j]), tail_s=float(tail[j]),
                 recharge_cum=None if cum is None else cum[j],
                 charge_cum=None if ccum is None else ccum[j])
            for j in range(len(lanes))]
