"""Driver of configurations of kind ``fleet``: one inference plan of the
intermittent-device simulator, replayed over a fleet of simulated devices
("lanes") by ``repro.core.fleet_sweep(plan=..., reduce="stats",
lane_chunk=C)``, meshed over the cell's chips when it has more than one.

Set-up: JAX start, the network built from the configuration and the
seed, ``build_plan``, and one sweep of exactly one chunk of ``C`` lanes,
which compiles the replay for its one shape or loads it from the cache.
Window: one sweep of ``k * C`` lanes, ``k = max(2, round(seconds /
t_chunk))`` with ``t_chunk`` the set-up sweep's time less its
compilation, or ``k = 2`` when the window is traced.  At least two chunks,
so the sweep's prefetch overlap is always inside the window.

``correct`` holds the window's own lanes to the benchmark's yardstick,
once the window has closed: the per-lane outputs that the sweep handed to
its statistics fold are kept as it runs; a sample of lanes drawn from the
seed (spread over every chip's shard), with the lanes of most reboots and
most live cycles, is replayed by the reference interpreter on inputs
rebuilt from the seed (replay channels and host dead time); every lane is
refolded exactly and held against the window's ``FleetStats`` (the fold
and, across chips, the gather); no lane may be missing; the plan that
``build_plan`` gave has the digest that the configuration pins (the
reference replays the plan's rows, so the pin is what holds the rows
themselves); and every channel the replay returns holds whole cycles to
the configuration's ``state_exact_bits``.
"""

from __future__ import annotations

import glob
import math
import os
import tempfile
import time

import numpy as np

from bench import events, fold, lane_inputs, trace_reduce
from bench.reference_replay import reference_replay

#: The function that runs the timed window; the trace reduction finds the
#: window by its Python tracer event.
WINDOW_FN = "bench_window"

#: Channels compared bit for bit, and channels of cycles compared by their
#: widest gap.
EXACT_CHANNELS = ("reboots", "stuck", "dead", "tx_bytes", "msgs_sent",
                  "msgs_deferred")
CYCLE_CHANNELS = ("live", "classes", "wasted", "belief")


def build_network(spec: dict, seed: int):
    """The configuration's network, weights drawn from the seed, then
    compressed layer by layer as the configuration states (GENESIS)."""
    from repro.compress import LayerChoice, apply_config
    from repro.core.inference import Conv2D, DenseFC, MaxPool2D, SimNet

    rng = np.random.default_rng([seed, 0])
    layers, choices = [], []
    for ly in spec["layers"]:
        if ly["type"] == "conv":
            co, ci, kh, kw = ly["shape"]
            w = rng.normal(size=(co, ci, kh, kw)) / np.sqrt(ci * kh * kw)
            layers.append(Conv2D(w.astype(np.float32),
                                 np.zeros(co, np.float32), name=ly["name"]))
        elif ly["type"] == "fc":
            m, n = ly["shape"]
            w = rng.normal(size=(m, n)) / np.sqrt(n)
            layers.append(DenseFC(w.astype(np.float32),
                                  np.zeros(m, np.float32),
                                  relu=ly.get("relu", True),
                                  name=ly["name"]))
        elif ly["type"] == "pool":
            kh, kw = ly["shape"]
            layers.append(MaxPool2D(kh=kh, kw=kw, name=ly["name"]))
        else:
            raise ValueError(f"unknown layer type {ly['type']!r}")
        choices.append(LayerChoice(*ly.get("compress", ["keep"])))
    net = SimNet(layers, input_shape=tuple(spec["input_shape"]),
                 name=spec["name"])
    return apply_config(net, tuple(choices))


class Compiles:
    """Counts JAX's compilations (a compile or a load from the persistent
    cache) and the seconds spent tracing, lowering and compiling, while
    ``active``."""

    def __init__(self, jax):
        self.jax = jax
        self.active = False
        self.count = 0
        self.seconds = 0.0

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.active and event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.count += 1

    def __enter__(self):
        self.jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        self.jax.monitoring.unregister_event_duration_listener(self._on)

    def reset(self) -> None:
        self.count, self.seconds = 0, 0.0


def exact_bits(dtype) -> float:
    """The bits of whole numbers that a type holds exactly (a flag holds
    all it needs)."""
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return math.inf
    if dt.kind == "f":
        return np.finfo(dt).nmant + 1
    if dt.kind in "iu":
        return np.iinfo(dt).bits - (dt.kind == "i")
    return 0


class ReplayTap:
    """Keeps the type of each channel that the replay returns, as it
    reaches the host."""

    def __init__(self, fleetsim):
        self.mod = fleetsim
        self.orig = fleetsim._lane_results
        self.dtypes: dict[str, set] = {}

    def __enter__(self):
        def tap(res, *args, **kw):
            for k, v in res.items():
                self.dtypes.setdefault(k, set()).add(np.dtype(v.dtype))
            return self.orig(res, *args, **kw)

        self.mod._lane_results = tap
        return self

    def __exit__(self, *exc):
        self.mod._lane_results = self.orig

    def narrow(self, bits: int) -> int:
        """Channels that some chunk returned in a type holding fewer than
        ``bits`` bits of whole numbers; every channel compared if none
        came this way (the replay's outputs took another path)."""
        if not self.dtypes:
            return len(EXACT_CHANNELS) + len(CYCLE_CHANNELS)
        return sum(any(exact_bits(d) < bits for d in ds)
                   for ds in self.dtypes.values())


class FoldTap:
    """Keeps what the sweep hands its statistics fold: each chunk's
    per-lane outputs, in chunk order."""

    def __init__(self, fleetsim):
        self.mod = fleetsim
        self.orig = fleetsim.stats_from_outputs
        self.chunks: list[dict] = []

    def __enter__(self):
        def tap(out, *args, **kw):
            self.chunks.append(out)
            return self.orig(out, *args, **kw)

        self.mod.stats_from_outputs = tap
        return self

    def __exit__(self, *exc):
        self.mod.stats_from_outputs = self.orig

    def lanes(self) -> dict:
        if not self.chunks:
            return {}
        return {k: np.concatenate([np.asarray(c[k]) for c in self.chunks])
                for k in self.chunks[0]}


def bench_window(fleet_sweep, n_lanes: int, kw: dict):
    """The timed window: one streamed sweep of ``n_lanes`` lanes.  Its
    result is a host ``FleetStats``, so the sweep has waited for every
    chunk's replay when it returns."""
    t = time.perf_counter()
    stats = fleet_sweep(n_devices=n_lanes, **kw)
    return stats, time.perf_counter() - t


def sample_lanes(seed: int, n_lanes: int, chunk: int, chips: int,
                 count: int, outs: dict) -> list[int]:
    """``count`` lanes drawn from the seed, as many from each chip's
    shard of a chunk, plus the lanes of most reboots and most live
    cycles."""
    rng = np.random.default_rng([seed, 2])
    shard = chunk // chips
    per = max(1, count // chips)
    lanes = []
    for s in range(chips):
        c = rng.integers(0, n_lanes // chunk, per)
        p = rng.integers(0, shard, per)
        lanes += (c * chunk + s * shard + p).tolist()
    for ch in ("reboots", "live"):
        if ch in outs and len(outs[ch]):
            lanes.append(int(np.argmax(outs[ch])))
    return sorted(set(lanes))


def compare_reference(rows: dict, plan, lanes, outs, seed: int,
                      sweep: dict, cycle_limit: float):
    """Replay ``lanes`` in the reference; returns (lanes whose exact
    channels differ, widest cycle gap, lanes off either way)."""
    n_out = len(outs.get("stuck", ()))
    ins = lane_inputs.lane_inputs(lanes, seed, plan.capacity,
                                  plan.recharge_s, sweep)
    exact_off, gap, off = 0, 0.0, 0
    for lane, kw in zip(lanes, ins):
        ref = reference_replay(rows, plan.capacity, **kw)
        if lane >= n_out:
            exact_off, gap, off = exact_off + 1, math.inf, off + 1
            continue
        bad = any(ch not in outs or not np.array_equal(
            np.asarray(outs[ch][lane], np.float64),
            np.asarray(ref[ch], np.float64)) for ch in EXACT_CHANNELS)
        lane_gap = 0.0
        for ch in CYCLE_CHANNELS:
            if ch not in outs:
                lane_gap = math.inf
                break
            d = np.abs(np.asarray(outs[ch][lane], np.float64)
                       - np.asarray(ref[ch], np.float64))
            lane_gap = max(lane_gap, float(np.nan_to_num(
                d, nan=math.inf).max()))
        exact_off += bad
        gap = max(gap, lane_gap)
        off += bad or lane_gap > cycle_limit
    return exact_off, gap, off


def _trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    opts.host_tracer_level = 1
    return opts


def run(cell, jax, devices, *, seed: int, seconds: float, trace: bool,
        t0: float, note) -> dict:
    from repro.core import build_plan, fleet_sweep
    from repro.core import fleetsim
    # what the sweep imports on its first call, so that the set-up
    # sweep's time less its compilation is one chunk's
    import repro.kernels.charge_replay  # noqa: F401
    import repro.runtime.failures  # noqa: F401
    import repro.runtime.radio  # noqa: F401

    cfg, tf = cell.config, cell.traffic
    t_jax = time.perf_counter() - t0
    net = build_network(cfg["network"], seed)
    x = np.random.default_rng([seed, 1]).normal(
        size=net.input_shape).astype(np.float32)
    t = time.perf_counter()
    plan = build_plan(net, x, cfg["strategy"], cfg["power"])
    plan_s = time.perf_counter() - t
    chunk = tf["lanes_per_chip"] * cell.chips
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(cell.chips)
    kw = dict(plan=plan, seed=seed, reduce="stats", lane_chunk=chunk,
              mesh=mesh, **tf["sweep"])

    with Compiles(jax) as comp, FoldTap(fleetsim) as tap, \
            ReplayTap(fleetsim) as rtap:
        comp.active = True
        t = time.perf_counter()
        fleet_sweep(n_devices=chunk, **kw)
        sweep_s = time.perf_counter() - t
        comp.active = False
        compile_s, setup_compiles = comp.seconds, comp.count
        t_chunk = max(sweep_s - compile_s, 1e-3)
        k = 2 if trace else max(2, round(seconds / t_chunk))
        n_lanes = k * chunk
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
            stats, window_s = bench_window(fleet_sweep, n_lanes, kw)
        finally:
            if trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                note(f"stop_trace {time.perf_counter() - t:.3f} s")
        comp.active = False
        window_compiles = comp.count
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    note(f"setup_s {setup_s:.3f}: jax start {t_jax:.3f}, build_plan "
         f"{plan_s:.3f} ({len(plan)} rows), one-chunk sweep {sweep_s:.3f} "
         f"(compile or cache load {compile_s:.3f} in {setup_compiles} "
         f"programs, warm chunk {t_chunk:.3f})")
    note(f"window {window_s:.3f} s: {k} chunks of {chunk} lanes, "
         f"{n_lanes} lanes; compilations in the window: {window_compiles}")

    layer_ctx = None
    outs = tap.lanes()
    rows = events.plan_rows(plan)
    pinned = cfg["plan"]
    digest = events.plan_digest(rows)
    plan_off = events.plan_fields_off(digest, pinned)
    if plan_off:
        note(f"the plan differs from the configuration's in {plan_off} "
             f"fields: {digest}")
    if trace:
        files = glob.glob(os.path.join(tmp.name, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        t = time.perf_counter()
        tr = trace_reduce.load(max(files, key=os.path.getsize), WINDOW_FN)
        note(f"trace of {sum(os.path.getsize(f) for f in files)} bytes read "
             f"in {time.perf_counter() - t:.3f} s: window {tr.window_s:.3f} "
             f"s on {tr.chips} chips, busy {tr.busy_s():.3f} s per chip")
        tmp.cleanup()
        layer_ctx = {"trace": tr, "chunks": k,
                     "lane_events": events.lane_events(
                         pinned["rows"], outs.get("reboots", ())),
                     "bytes_per_event": events.bytes_per_event(rows)}

    # -- correct: the window's lanes against the yardstick -----------------
    limits = tf["limits"]
    t = time.perf_counter()
    n_out = len(outs.get("stuck", ()))
    missing = abs(n_lanes - n_out) + abs(n_lanes - int(stats.count[0]))
    lanes = sample_lanes(seed, n_lanes, chunk, cell.chips, tf["ref_lanes"],
                         outs)
    exact_off, gap, off = compare_reference(
        rows, plan, lanes, outs, seed, tf["sweep"],
        limits["ref_cycle_gap"])
    edges = fold.stat_edges(pinned["total_cycles"], plan.capacity,
                            plan.recharge_s)
    fold_gap = (fold.max_rel_gap(fold.flatten(stats), fold.fold(outs, edges))
                if n_out else 1.0)
    note(f"reference check of {len(lanes)} lanes and the fold of {n_out} "
         f"lanes took {time.perf_counter() - t:.3f} s")
    checks = {"lanes_missing": (missing, limits["lanes_missing"]),
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
