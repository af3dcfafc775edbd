"""Driver of configurations of kind ``fleet_design``: GENESIS's design
sweep.  Every candidate of the configuration (a compression of the base
network, under a runtime, on a power system) is built into its plan, the
plans are stacked into one ``PlanSet``, and one
``repro.core.fleet_sweep(plan=PlanSet, reduce="stats", lane_chunk=C)``
prices them all: lanes are plan-major (``lane = p * D + d``, ``D`` lanes a
candidate), so a chunk of ``C`` lanes holds ``C / D`` candidates, and the
statistics come back as one ``FleetStats`` group a candidate.

The traffic file gives ``lanes_per_chip`` (the chunk, a chip) and
``candidates_per_chunk``; ``D = lanes_per_chip * chips /
candidates_per_chunk``, and the window sweeps ``D`` lanes of every
candidate.

Set-up: JAX start, each configuration's network built once from the seed,
``build_plan`` of every candidate, the ``PlanSet``, and one sweep of the
same set in chunks of ``C`` lanes, just over one chunk of lanes, which
compiles the window's program or loads it from the cache.  Window: one
whole design sweep, all candidates, all chunks.  The set-up sweep and the
window run with the host's allocator keeping what they free
(:class:`KeptHeap`), so the window's host copies of the stacked table reuse
pages the set-up sweep faulted in.

``correct`` holds the window to the yardstick, once it has closed: no
lane missing, ``D`` lanes in every candidate's group (the lane's candidate
derived here, ``lane // D``, not read from the program), a sample of lanes
of every candidate, with the lanes of most reboots and most live cycles,
replayed by the reference interpreter on the candidate's own rows and
capacity, on inputs rebuilt from the seed; every group refolded exactly
against its ``FleetStats`` group; every candidate's plan the digest its
entry pins; every channel of the replay whole to the configuration's
``state_exact_bits``.
"""

from __future__ import annotations

import copy
import ctypes
import glob
import os
import tempfile
import time

import numpy as np

from bench import events, fold, trace_reduce
from bench.drivers.fleet import (Compiles, FoldTap, ReplayTap,
                                 _trace_options, build_network,
                                 compare_reference)

#: The function that runs the timed window; the trace reduction finds the
#: window by its Python tracer event.
WINDOW_FN = "bench_window"


class KeptHeap:
    """While open, the C library's allocator keeps the memory that the
    sweep frees for the sweep's next allocations: no block gets a mapping
    of its own (``M_MMAP_MAX`` 0) and the heap is never trimmed
    (``M_TRIM_THRESHOLD``).  A design sweep makes and drops a few GB of
    host copies of the stacked row table; by default every one of them
    is mapped afresh and its pages faulted in again, and how long that
    takes on a shared host varies from run to run by more than the cell's
    bound allows.  Held over the set-up sweep and the window, the window
    reuses the pages the set-up sweep faulted in.  On leaving, glibc's
    default limits come back and the heap is trimmed.  Where the C
    library has no ``mallopt`` (not glibc), it does nothing."""

    M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4
    #: glibc's defaults
    TRIM_DEFAULT, MMAP_MAX_DEFAULT = 128 * 1024, 65536

    def __init__(self):
        self.mallopt = self.trim = None

    def __enter__(self):
        libc = ctypes.CDLL(None)
        if hasattr(libc, "mallopt") and hasattr(libc, "malloc_trim"):
            self.mallopt, self.trim = libc.mallopt, libc.malloc_trim
            self.mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
            self.trim.argtypes = [ctypes.c_size_t]
            # a trim threshold of -1 turns trimming off (mallopt(3))
            self._set(0, -1)
        return self

    def __exit__(self, *exc):
        if self.mallopt is not None:
            self._set(self.MMAP_MAX_DEFAULT, self.TRIM_DEFAULT)
            self.trim(0)

    def _set(self, mmap_max: int, trim: int) -> None:
        self.mallopt(self.M_MMAP_MAX, mmap_max)
        self.mallopt(self.M_TRIM_THRESHOLD, trim)


def candidate_network(base: dict, compress: dict) -> dict:
    """The base network's spec with a candidate's per-layer ``compress``
    entries in place of the base's."""
    spec = copy.deepcopy(base)
    names = {ly["name"] for ly in spec["layers"]}
    unknown = set(compress) - names
    if unknown:
        raise ValueError(f"compress names no layer of the network: "
                         f"{sorted(unknown)}")
    for ly in spec["layers"]:
        if ly["name"] in compress:
            ly["compress"] = list(compress[ly["name"]])
    return spec


def build_plans(cfg: dict, seed: int, build_plan) -> list:
    """The candidates' plans, in the configuration's order; each distinct
    network is built once, from the seed, and shared by its runtimes."""
    nets, plans = {}, []
    for cand in cfg["candidates"]:
        key = repr(sorted(cand["compress"].items()))
        if key not in nets:
            net = build_network(candidate_network(cfg["network"],
                                                  cand["compress"]), seed)
            x = np.random.default_rng([seed, 1]).normal(
                size=net.input_shape).astype(np.float32)
            nets[key] = (net, x)
        net, x = nets[key]
        plans.append(build_plan(net, x, cand["strategy"], cand["power"]))
    return plans


def lanes_per_candidate(traffic: dict, chips: int) -> int:
    chunk = traffic["lanes_per_chip"] * chips
    per, rest = divmod(chunk, traffic["candidates_per_chunk"])
    if rest or per < 1:
        raise ValueError(f"a chunk of {chunk} lanes does not split into "
                         f"{traffic['candidates_per_chunk']} candidates")
    return per


def bench_window(fleet_sweep, per_candidate: int, kw: dict):
    """The timed window: one whole design sweep, ``per_candidate`` lanes
    of every candidate.  Its result is a host ``FleetStats``, so the sweep
    has waited for every chunk's replay when it returns."""
    t = time.perf_counter()
    stats = fleet_sweep(n_devices=per_candidate, **kw)
    return stats, time.perf_counter() - t


def sample_lanes(seed: int, n_cand: int, per_candidate: int, count: int,
                 outs: dict) -> list[int]:
    """``count`` lanes drawn from the seed, as many of every candidate
    (at least one), plus the lanes of most reboots and most live
    cycles."""
    rng = np.random.default_rng([seed, 2])
    per = max(1, count // n_cand)
    lanes = []
    for p in range(n_cand):
        lanes += (p * per_candidate
                  + rng.integers(0, per_candidate, per)).tolist()
    for ch in ("reboots", "live"):
        if ch in outs and len(outs[ch]):
            lanes.append(int(np.argmax(outs[ch])))
    return sorted(set(lanes))


def flatten_group(stats, g: int) -> dict:
    """Group ``g`` of a ``FleetStats`` in the layout of ``bench.fold``."""
    flat = {"count": np.asarray(stats.count, np.float64)[g:g + 1],
            "completed": np.asarray(stats.completed, np.float64)[g:g + 1],
            "class_sums": np.asarray(stats.class_sums, np.float64)[g]}
    for ch in fold.STAT_CHANNELS:
        for f in fold.STAT_FIELDS:
            v = np.asarray(getattr(stats, f)[ch], np.float64)
            flat[f"{ch}:{f}"] = v[g] if f == "hists" else v[g:g + 1]
        flat[f"{ch}:edges"] = np.asarray(stats.edges[ch], np.float64)
    return flat


def run(cell, jax, devices, *, seed: int, seconds: float, trace: bool,
        t0: float, note) -> dict:
    import repro.core
    from repro.core import PlanSet, fleet_sweep
    from repro.core import fleetsim
    # what the sweep imports on its first call
    import repro.kernels.charge_replay  # noqa: F401
    import repro.runtime.failures  # noqa: F401
    import repro.runtime.radio  # noqa: F401

    cfg, tf = cell.config, cell.traffic
    cands = cfg["candidates"]
    n_cand = len(cands)
    per_cand = lanes_per_candidate(tf, cell.chips)
    chunk = tf["lanes_per_chip"] * cell.chips
    t_jax = time.perf_counter() - t0
    t = time.perf_counter()
    # looked up at run time, so that a fault planted in build_plan applies
    plans = build_plans(cfg, seed, repro.core.build_plan)
    plan_s = time.perf_counter() - t
    ps = PlanSet.from_plans(plans, labels=[c["label"] for c in cands])
    pins = [c["plan"] for c in cands]
    capacity = float(np.min(ps.capacity))
    edges = fold.stat_edges(max(p["total_cycles"] for p in pins), capacity,
                            float(np.max(ps.recharge_s)))
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(cell.chips)
    kw = dict(plan=ps, seed=seed, reduce="stats", lane_chunk=chunk,
              stats_edges=edges, mesh=mesh, **tf["sweep"])

    with Compiles(jax) as comp, FoldTap(fleetsim) as tap, \
            ReplayTap(fleetsim) as rtap, KeptHeap():
        comp.active = True
        t = time.perf_counter()
        # just over one chunk of lanes: a full chunk, then a padded one,
        # the window's shapes and pipeline
        fleet_sweep(n_devices=chunk // n_cand + 1, **kw)
        sweep_s = time.perf_counter() - t
        comp.active = False
        compile_s, setup_compiles = comp.seconds, comp.count
        n_lanes = n_cand * per_cand
        k = -(-n_lanes // chunk)
        tap.chunks.clear()
        rtap.dtypes.clear()
        comp.reset()
        tmp = tempfile.TemporaryDirectory() if trace else None
        comp.active = True
        setup_s = time.perf_counter() - t0
        if trace:
            jax.profiler.start_trace(tmp.name,
                                     profiler_options=_trace_options(jax))
        try:
            stats, window_s = bench_window(fleet_sweep, per_cand, kw)
        finally:
            if trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                note(f"stop_trace {time.perf_counter() - t:.3f} s")
        comp.active = False
        window_compiles = comp.count
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    table = ps.rows["kind"].size
    note(f"setup_s {setup_s:.3f}: jax start {t_jax:.3f}, build_plan "
         f"{plan_s:.3f} ({n_cand} plans, {int(ps.n_rows.sum())} rows, "
         f"{table} row slots stacked), set-up sweep {sweep_s:.3f} "
         f"(compile or cache load {compile_s:.3f} in {setup_compiles} "
         f"programs)")
    note(f"window {window_s:.3f} s: {k} chunks of {chunk} lanes, "
         f"{per_cand} lanes of each of {n_cand} candidates; compilations "
         f"in the window: {window_compiles}")
    # the replay's counters, where the program keeps them
    counts = {name: int(getattr(stats, name))
              for name in ("replay_events", "replay_event_slots")
              if hasattr(stats, name)}
    if hasattr(stats, "group_events"):
        note("events by candidate: " + ", ".join(
            f"{c['label']} {int(e)}"
            for c, e in zip(cands, np.asarray(stats.group_events))))

    outs = tap.lanes()
    n_out = len(outs.get("stuck", ()))
    rows = [events.plan_rows(p) for p in plans]
    plan_off = 0
    for c, r in zip(cands, rows):
        digest = events.plan_digest(r)
        off = events.plan_fields_off(digest, c["plan"])
        if off:
            note(f"candidate {c['label']}'s plan differs from its pin in "
                 f"{off} fields: {digest}")
        plan_off += off
    layer_ctx = None
    if trace:
        files = glob.glob(os.path.join(tmp.name, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        t = time.perf_counter()
        tr = trace_reduce.load(max(files, key=os.path.getsize), WINDOW_FN)
        note(f"trace of {sum(os.path.getsize(f) for f in files)} bytes read "
             f"in {time.perf_counter() - t:.3f} s: window {tr.window_s:.3f} "
             f"s on {tr.chips} chips, busy {tr.busy_s():.3f} s per chip")
        tmp.cleanup()
        # lane-events and the bytes they read, counted per candidate from
        # its pin and its lanes' reboots
        reboots = np.asarray(outs.get("reboots", ()))
        work = [events.lane_events(
            pin["rows"], reboots[p * per_cand:(p + 1) * per_cand])
            for p, pin in enumerate(pins)]
        lane_events = float(sum(work))
        need = sum(w * events.bytes_per_event(r)
                   for w, r in zip(work, rows))
        layer_ctx = {"trace": tr, "chunks": k, "lane_events": lane_events,
                     "bytes_per_event": need / lane_events
                     if lane_events else 0.0,
                     "counts": counts}

    # -- correct: the window's lanes against the yardstick -----------------
    limits = tf["limits"]
    t = time.perf_counter()
    count = np.asarray(stats.count, np.float64)
    missing = abs(n_lanes - n_out) + abs(n_lanes - int(count.sum()))
    group_off = sum(int(p >= count.shape[0] or count[p] != per_cand)
                    for p in range(n_cand)) + max(count.shape[0] - n_cand, 0)
    lanes = sample_lanes(seed, n_cand, per_cand, tf["ref_lanes"], outs)
    exact_off = off = 0
    gap = 0.0
    for p, (plan, r) in enumerate(zip(plans, rows)):
        mine = [lane for lane in lanes if lane // per_cand == p]
        if not mine:
            continue
        e, g, o = compare_reference(r, plan, mine, outs, seed, tf["sweep"],
                                    limits["ref_cycle_gap"])
        exact_off, gap, off = exact_off + e, max(gap, g), off + o
    fold_gap = 0.0
    if n_out != n_lanes or count.shape[0] != n_cand:
        fold_gap = 1.0
    else:
        for p in range(n_cand):
            lo, hi = p * per_cand, (p + 1) * per_cand
            want = fold.fold({ch: v[lo:hi] for ch, v in outs.items()}, edges)
            fold_gap = max(fold_gap, fold.max_rel_gap(flatten_group(stats, p),
                                                      want))
    note(f"reference check of {len(lanes)} lanes and the fold of {n_out} "
         f"lanes in {n_cand} groups took {time.perf_counter() - t:.3f} s")
    checks = {"lanes_missing": (missing, limits["lanes_missing"]),
              "group_lanes_off": (group_off, limits["group_lanes_off"]),
              "ref_exact_lanes_off": (exact_off,
                                      limits["ref_exact_lanes_off"]),
              "ref_cycle_gap": (gap, limits["ref_cycle_gap"]),
              "fold_rel_gap": (fold_gap, limits["fold_rel_gap"]),
              "plan_fields_off": (plan_off, limits["plan_fields_off"]),
              "replay_narrow_channels": (
                  rtap.narrow(cfg["state_exact_bits"]),
                  limits["replay_narrow_channels"])}
    return {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": n_lanes,
        "failed": int(missing + off),
        "metrics": {"sim_lanes_per_s": n_lanes / window_s,
                    "setup_s": setup_s},
        "memory_peak_bytes": max((m for m in mem if m is not None),
                                 default=None),
        "checks": checks,
        "layer_ctx": layer_ctx,
    }
