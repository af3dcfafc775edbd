"""The simulated work of a fleet window, counted from the plan's row count
that the configuration pins and from the window's outputs, whatever
implements the replay; and the digest that pins a plan.

A lane-event is one plan row or one charge of one lane: a lane that
reboots ``r`` times walks every row once and sees ``r`` charges die, so
``lane_events = lanes * rows + sum(reboots)``.  Each event reads one row's
semantic fields, counted at 4 bytes each whatever width a program packs
them in.
"""

from __future__ import annotations

import hashlib

import numpy as np

#: A plan row's fields (per-class vectors count one field per class).
ROW_FIELDS = ("kind", "n", "iter_cycles", "entry_cycles", "iter_class",
              "entry_class", "commit_cycles", "commit_class",
              "entry_seg_class", "entry_seg_cycles", "tile_flag")
#: The further fields of a TAILS row: one entry per calibration-ladder
#: tile.
TILE_FIELDS = ("tile_n", "tile_iter_cycles", "tile_iter_class",
               "tile_sel_cost")
BYTES_PER_FIELD = 4


def plan_rows(plan) -> dict:
    """The plan's row fields as a dict (the reference's input)."""
    fields = ROW_FIELDS
    if getattr(plan, "tile_sel_cost", None) is not None:
        fields = fields + TILE_FIELDS
    return {f: np.asarray(getattr(plan, f)) for f in fields}


def plan_digest(rows: dict) -> dict:
    """What pins a plan: its row count, its rows of each kind, its cycles
    under continuous power in all and by operation class, and a SHA-256 of
    its rows, each row its fields as float64 in field order.  The rows are
    hashed as a set, sorted: their order follows the positions of the
    pruned weights, which the seed draws, while the set is the same for
    every seed.  A configuration file holds its plan's digest, and a run
    whose plan differs is not correct."""
    n_rows = int(np.shape(rows["kind"])[0])
    mat = np.concatenate([np.asarray(v, np.float64).reshape(n_rows, -1)
                          for v in rows.values()], axis=1)
    mat = np.ascontiguousarray(mat[np.lexsort(mat.T[::-1])], "<f8")
    h = hashlib.sha256(",".join(rows).encode())
    h.update(mat.tobytes())
    kinds, counts = np.unique(np.asarray(rows["kind"]), return_counts=True)
    n = np.asarray(rows["n"], np.float64)
    cls = (np.asarray(rows["entry_class"], np.float64)
           + n[:, None] * np.asarray(rows["iter_class"], np.float64))
    return {"rows": n_rows,
            "rows_by_kind": {str(int(k)): int(c)
                             for k, c in zip(kinds, counts)},
            "total_cycles": float(np.sum(
                np.asarray(rows["entry_cycles"], np.float64)
                + n * np.asarray(rows["iter_cycles"], np.float64))),
            "class_cycles": [float(c) for c in cls.sum(axis=0)],
            "rows_sha256": h.hexdigest()}


def plan_fields_off(built: dict, pinned: dict) -> int:
    """Fields of a built plan's digest that differ from the pinned one."""
    return sum(built.get(k) != v for k, v in pinned.items()) + sum(
        k not in pinned for k in built)


def bytes_per_event(rows: dict) -> int:
    return BYTES_PER_FIELD * sum(int(np.prod(np.shape(v)[1:]))
                                 for v in rows.values())


def lane_events(n_rows: int, reboots) -> float:
    r = np.asarray(reboots, np.float64)
    return float(r.size * n_rows + r.sum())
