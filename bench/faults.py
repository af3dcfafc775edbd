"""Faults and lower-precision controls planted under a run, to show that
``correct`` catches them (``bench.control`` on the chip, the tests under
``tests/bench`` on the CPU).

* ``replay32``: the control -- the replay computed in float32, the
  nearest precision below the float64 state that the configurations state.
* ``fold32``: the statistics fold fed float32 lane outputs.
* ``state_unchanged``: every lane returns its initial state (no cycles,
  no reboots).
* ``half_batch``: the fold takes only the first half of each chunk's
  lanes, and its statistics are over those.
* ``no_exchange``: the meshed replay's gather left out: every shard's
  place holds chip 0's lanes.
* ``answer_altered``: one cycle added to every lane's live cycles where
  the replay's outputs reach the host.
* ``plan_altered``: one cycle added to the first row's entry cost where
  the plan is built; the reference, which replays the plan's rows, agrees
  with the altered plan, so only the configuration's pinned digest
  catches it.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

NAMES = ("replay32", "fold32", "state_unchanged", "half_batch",
         "no_exchange", "answer_altered", "plan_altered")


@contextlib.contextmanager
def _swap(mod, name: str, new):
    old = getattr(mod, name)
    setattr(mod, name, new(old))
    try:
        yield
    finally:
        setattr(mod, name, old)


@contextlib.contextmanager
def plant(name: str | None):
    """Run the body with fault ``name`` planted in the program (``None``:
    no fault)."""
    if name is None:
        yield
        return
    from repro.core import fleetsim as fs

    if name == "replay32":
        cm = _swap(fs, "_x64", lambda old: contextlib.nullcontext)
    elif name == "fold32":
        def new(old):
            def fold(out, *a, **kw):
                return old({k: np.asarray(v, np.float32).astype(v.dtype)
                            if np.asarray(v).dtype.kind == "f" else v
                            for k, v in out.items()}, *a, **kw)
            return fold
        cm = _swap(fs, "stats_from_outputs", new)
    elif name == "half_batch":
        def new(old):
            def fold(out, edges, group_id=None, n_groups=1, **kw):
                half = len(out["stuck"]) // 2
                gid = None if group_id is None else group_id[:half]
                return old({k: v[:half] for k, v in out.items()}, edges,
                           gid, n_groups, **kw)
            return fold
        cm = _swap(fs, "stats_from_outputs", new)
    elif name in ("state_unchanged", "answer_altered"):
        def new(old):
            def results(*a, **kw):
                out = old(*a, **kw)
                if name == "answer_altered":
                    return dict(out, live=out["live"] + 1.0)
                zero = {k: np.zeros_like(out[k]) for k in
                        ("live", "classes", "wasted", "reboots", "dead")}
                return dict(out, **zero)
            return results
        cm = _swap(fs, "_lane_results", new)
    elif name == "no_exchange":
        def new(old):
            def sharded(args, mesh, *rest):
                res = old(args, mesh, *rest)
                n = int(mesh.devices.size)
                out = {}
                for k, v in res.items():
                    v = np.asarray(v)
                    out[k] = np.concatenate([v[:v.shape[0] // n]] * n)
                return out
            return sharded
        cm = _swap(fs, "_sharded_replay", new)
    elif name == "plan_altered":
        import repro.core

        def new(old):
            def build(*a, **kw):
                plan = old(*a, **kw)
                entry = np.array(plan.entry_cycles, copy=True)
                entry[0] += 1.0
                return dataclasses.replace(plan, entry_cycles=entry)
            return build
        cm = _swap(repro.core, "build_plan", new)
    else:
        raise ValueError(f"unknown fault {name!r}; expected one of {NAMES}")
    with cm:
        yield
