"""Vectorized fleet-scale intermittent simulator (JAX ``lax.scan`` replay).

The scalar simulator (``energy.py`` + ``intermittent.py``) charges energy one
Python operation at a time and models power failure as an exception -- exact,
but serial and unjittable.  This module separates the *plan* from the
*execution*: every strategy's charge sequence is first flattened into a
:class:`FleetPlan` (a flat array of rows), and a jitted scan then replays the
plan, advancing ``(energy buffer, live cycles, reboot count, dead time,
per-class energy)`` row by row.  Power failure becomes a state transition
(cursor rollback to the last commit + recharge), not an exception, so the
whole Fig. 9 strategy x power matrix -- and million-device fleet sweeps with
per-device harvest traces -- run in one compiled ``vmap`` (optionally
``shard_map``) pass.

The plan is a *parameterized IR*: rows describe the work, while five
run-time decisions are taken per device lane **inside** ``_scan_step``:

1. **TAILS tile selection** -- parameterized rows carry a per-candidate
   table over the Sec. 7.1 calibration ladder
   (:func:`repro.core.inference.tails_tile_candidates`): iteration counts,
   per-iteration cycles, and per-class vectors for every candidate tile,
   plus the pure calibration cost from ``tails_tile_cost_from``.  The scan
   picks each lane's tile from its carried capacitor size (the first ladder
   entry whose one-tile cost fits a charge), so a single plan replays
   across arbitrary capacitor grids without re-extraction, and ``KIND_CALIB``
   rows charge the same discovery burns the scalar calibration pays.
2. **Commit granularity** -- rows carry the per-iteration commit portion of
   their cost (``commit_cycles``/``commit_class``, the loop-cursor FRAM
   write).  Under ``policy="adaptive"`` (the energy-adaptive checkpoint-free
   policy of Islam et al. 2025, arXiv:2503.06663) every *charge* branches on
   the measured buffer level: above ``theta * believed-budget`` the lane
   batches commits to one cursor write per charge chunk instead of one per
   iteration; below it (or under ``policy="fixed"``, the default) it keeps
   the paper's per-iteration commit.  The threshold is re-evaluated per
   charge -- the first visit of a row sees the carried buffer, every retry
   visit wakes at a (believed-)full buffer, so retries batch iff
   ``theta <= 1``.  ``policy`` is a replay-time axis orthogonal to the six
   strategies; ``theta`` is a traced operand, so sweeping it reuses one
   compilation.

   **Cross-charge batching** (``batch_rows > 1``) additionally defers the
   *row-boundary* cursor write: a looped row that completes within a charge
   while the lane is batching joins a *pending window* instead of
   committing, and one cursor write per charge -- at the believed end of
   the charge, or at the next per-iteration commit / atomic row -- makes
   the whole window durable at once (up to ``batch_rows`` rows per write).
   The price is **multi-row rollback**: a surprise-short charge that dies
   before that write loses every pending row; the lane re-enters the
   earliest uncommitted row and replays the lost cycles (the ``debt``
   mechanism below) through the ``wasted_cycles`` channel, re-committing
   replayed work once per charge so the rollback always converges.  With
   ``batch_rows=1`` (the default) every row commits at its boundary and the
   replay is bit-exact vs the single-row adaptive path.

   **EWMA belief recalibration** (``belief_alpha > 0``) replaces the static
   nominal per-charge budget with a carried believed budget ``bhat``,
   updated from *observed* charge lengths at every death of a
   refill-started charge: ``bhat += alpha * (observed - bhat)``.  The
   batching threshold becomes ``theta * bhat`` (a confidence margin) and
   every refill wakes believing ``bhat``, so a lane that keeps drawing
   short charges shrinks its batch window -- and its tear losses -- instead
   of planning against the nominal belief forever.  ``belief_alpha=0``
   keeps ``bhat`` pinned to the nominal capacity bit-exactly.
3. **Recharge dead time** -- the scan indexes a per-lane cumulative
   recharge-trace table (``runtime.failures.recharge_trace_cumulative`` over
   ``reboot_recharge_times``) by the lane's running reboot counter, so each
   reboot pays its *own* measured dead time; reboots past the trace fall
   back to the lane's mean (``tail_s``).  With no trace the same gather
   degenerates to the closed-form ``reboots x recharge_s``.
4. **Stochastic per-charge capacity** -- with a per-lane charge-capacity
   trace (``runtime.failures.charge_capacity_jitter`` prefix-summed by
   ``charge_trace_cumulative``), the closed-form ``ceil(remaining /
   affordable)`` reboot collapse is replaced by a charge-by-charge inner
   loop: refill ``r`` (indexed by the running reboot counter) delivers the
   traced capacity instead of the nominal one, while the lane keeps
   *believing* the nominal budget.  A surprise-short charge under batched
   commits dies before the chunk's cursor write lands, rolls back to the
   last committed cursor, and re-executes the lost iterations -- accounted
   in the ``wasted_cycles`` channel (exactly zero under per-iteration
   commits, which lose at most the torn partial iteration the deterministic
   model already burns).  A surprise-long charge's excess is drained: the
   lane cannot schedule work against energy it did not predict.  Charges
   past the trace deliver the nominal capacity.  This is the risk side of
   the energy-adaptive trade-off: with deterministic charges batching is a
   strict win, with jitter it pays for every mis-predicted commit.
5. **Uplink send/defer/compress** -- with a radio model live
   (``runtime.radio``) and a ``KIND_SEND`` row appended by
   :func:`with_uplink`, each completed inference takes a traced uplink
   decision from the lane's classifier confidence: ship the argmax class,
   ship top-k logits, or ship nothing (policy thresholds ``conf_hi`` /
   ``conf_lo``).  The transmission's cycles (fixed wakeup/preamble plus
   per-byte TX, booked to the ``radio`` op class) charge the *same*
   energy buffer as compute through the generic atomic-row machinery, so
   a send torn by power failure rolls back and retries the full preamble
   like any other row, and a send whose cost exceeds a nominal charge is
   ``stuck``.  A duty-cycled basestation (``window_period_s`` /
   ``window_duty``) adds the defer branch: a send waking into a closed
   listen window sleeps -- dead time, no energy -- until the window
   reopens (evaluated at the row's fresh entry only; a post-tear retry
   transmits as soon as the buffer recharges).  Shipped bytes, completed
   and deferred sends thread through the ``tx_bytes`` / ``msgs_sent`` /
   ``msgs_deferred`` result channels, the streaming ``FleetStats``
   reduction (plus derived ``tx_joules``), and the differential oracle.

Plan IR v2: the stacked candidate-plan axis (``PlanSet``)
---------------------------------------------------------
The parameterized IR above carries exactly one candidate axis inside a
row (TAILS tile tables).  Plan IR v2 generalizes it: a :class:`PlanSet`
stacks P whole candidate plans -- different GENESIS compression configs,
Tile-k task sizes, strategies, restamped capacitors -- into one
``(P, S, ...)`` row-table batch (per-plan row counts bucket-padded to
shared powers of two by the same machinery that buckets single plans)
plus a per-plan header (strategy, real row count, capacity, recharge,
nominal cycles).  ``fleet_sweep(plan=planset)`` threads the plan axis as
a broadcast operand: lanes are plan-major (``lane = p * n_devices + d``),
each lane carries an integer ``plan_idx``, and the fused event stream
reads lane rows from a packed ``(P, S, F)`` tensor with one two-index
dynamic slice per event (``kernels/charge_replay.py``), so the entire
(networks x tile-k x tiles x devices x capacitors) design space replays
under ONE compiled scan -- no per-candidate re-extraction or recompile.
Per-plan results come back as :class:`~repro.core.fleetstats.FleetStats`
groups (``reduce="stats"``, mesh sharding and ``lane_chunk`` streaming
included) or as a materialized :class:`DesignSweepResult`, and each
plan's lanes draw bit-identical sampler inputs to an individual
``fleet_sweep`` of that plan, so the stacked sweep is bit-exact against
replaying every candidate separately (pinned by
``tests/test_planset.py``).  ``compress/genesis.py`` prices its whole
accuracy-energy frontier through one such sweep.

The overlapped streaming pipeline (``lane_chunk`` + ``prefetch``)
-----------------------------------------------------------------
Chunked streaming (``lane_chunk=``) runs as a two-stage pipeline by
default (``prefetch=1``, :func:`_chunked_replay`): a bounded producer
thread builds chunk k+1's inputs -- Philox ``*_stream`` draws,
inert-lane padding, stochastic trace post-processing -- and uploads
them to the device while chunk k's replay is in flight, and the host
folds chunk k-1's outputs meanwhile (``reduce="stats"`` merges them
into one :class:`FleetStats`).  Host sampler time hides
under device compute instead of adding to it (the win scales with the
host's spare cores; a 1-core runner sees ~1x).  The peak-memory bound
is honest and recorded per sweep: ``peak_lane_bytes = (prefetch + 1) *
max-chunk-bytes + one stats partial``.  ``prefetch=0`` is the legacy
fully synchronous loop, bit-exact against the pipeline on every output
channel (same chunk partials, same left-fold merge order) -- it is the
differential oracle ``tests/test_overlap_pipeline.py`` pins, and the
right choice when the host has no spare core or jobs are
memory-squeezed to exactly one chunk.  Mesh-sharded and Pallas replays
keep their own dispatch and overlap stage 1 (input generation) only.

Plan rows and the paper's Sec. 6 commit protocol
------------------------------------------------
Each row models one committed unit of work as ``(kind, n, iter_cycles,
entry_cycles, commit_cycles)`` plus per-class cycle vectors
(:data:`repro.core.energy.OP_CLASSES` order) and a *charge-segment list*
``entry_seg_class``/``entry_seg_cycles`` -- the entry's cost blocks in the
exact order the scalar simulator charges them (one segment per
``device.charge(op, n)`` call).  A torn first attempt books its burned
prefix by walking this list, which stays exact even for rows merged from
multi-dict charge sequences (naive whole-net rows, Tile-k tasks spanning
segments) where one class appears in several constituent dicts and a
single per-class offset table would misattribute the burn:

``kind=WORK, n > 0``  -- a SONIC/TAILS *segment* under loop continuation
    (Sec. 6.1): ``n`` iterations of ``iter_cycles`` each, committed by the
    single atomic NV-cursor word write after every energy-affordable chunk.
    ``commit_cycles`` is the cursor write's share of ``iter_cycles`` (the
    part the adaptive policy can batch).  A/B buffer polarity is a pure
    function of the cursor (loop-ordered buffering, Sec. 6.2), so rollback
    is free.  ``entry_cycles`` is the segment (re-)entry cost, re-paid on
    every reboot into the segment.  Parameterized TAILS rows additionally
    carry ``tile_n/tile_iter_cycles/tile_iter_class/tile_sel_cost`` tables
    (one entry per calibration-ladder candidate) and set ``tile_flag``.

``kind=WORK, n = 0``  -- an *atomic* re-executable unit: one Alpaca Tile-k
    task (k redo-logged iterations + commit + transition), a layer-boundary
    commit (one atomic NV word), or a whole naive inference.
    ``entry_cycles`` carries the full cost.

``kind=BURN``  -- one failed TAILS tile-calibration attempt (Sec. 7.1) baked
    for the plan's nominal capacitor: the device dies mid-tile, burning the
    rest of the buffer (charged to ``lea_mac``), and halves the tile.

``kind=CALIB``  -- the parameterized form of the same calibration: the scan
    derives the burn count per lane from its capacitor (the number of ladder
    candidates that do not fit) and charges them in one step.

Equivalence guarantees (pinned by ``tests/test_fleetsim.py`` and
``tests/test_fleet_replay_decisions.py``):

* ``policy="fixed"`` replay of a non-parameterized plan is *exactly* the
  scalar simulator: all cost-table constants are integral, so every energy
  quantity is an integer represented exactly in float64, and the per-row
  closed forms reproduce the scalar chunk/retry arithmetic
  reboot-for-reboot across the full strategy x power matrix.
* A parameterized TAILS plan replayed at a fixed capacitor is bit-identical
  to the plan extracted for that capacitor, and the in-scan tile choice
  equals ``tails_tile_schedule`` run per device.
* The trace-driven dead-time path with every trace entry equal to
  ``recharge_s`` reduces to the closed-form model (completed / reboots /
  energy / outputs bit-exact; dead time to float tolerance).
* The stochastic charge-by-charge path with an all-nominal capacity trace
  (or ``charge_cv=0``) is bit-exact against the closed-form replay --
  completed / reboots / energy / per-class / outputs -- across the full
  strategy x power matrix, for both commit policies, and its
  ``wasted_cycles`` is exactly zero.
* Completion is decided by the in-scan ``stuck`` flag (a row whose entry
  plus one iteration -- at the lane's *selected* tile -- exceeds a nominal
  charge can never pass), which coincides with the scalar simulator's
  ``max_atomic`` bound for non-parameterized plans but is per-lane exact
  for parameterized ones, where ``max_atomic`` is sized with the
  continuously-calibrated tile and would falsely DNF small-capacitor lanes
  that select a smaller tile in-scan.
* Torn partial burns are attributed by charge order: when a lane dies
  before affording a row's entry, the burned prefix is booked to the entry
  ops' own classes by walking the row's charge-segment list (matching the
  scalar simulator's per-op accounting exactly, including rows merged from
  multi-dict charge sequences); only chunk-boundary drains are booked to
  ``control``.  Totals are exact in both schemes.
* ``batch_rows=1`` with ``belief_alpha=0`` reduces the cross-charge
  machinery to the single-row adaptive path bit-exactly (the pending
  window never opens, the believed budget stays nominal), and the whole
  decision surface is differentially tested against a slow pure-Python
  reference interpreter (``tests/reference_replay.py``) that replays the
  same plans charge by charge.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, NamedTuple

import numpy as np

from .energy import (CLOCK_HZ, Device, JOULES_PER_CYCLE, LEA_COSTS,
                     OP_CLASSES, SOFTWARE_COSTS, class_cycle_vector,
                     make_power_system, rf_recharge_seconds)
from .fleetstats import FleetStats, default_stat_edges, stats_from_outputs
from .inference import (Conv2D, DenseFC, SimNet, TAILS_FC_ENTRY_COSTS,
                        build_layer_segments, iter_task_spans,
                        naive_layer_cycles, run_naive, sonic_segments,
                        tails_conv_entry_costs, tails_stage_iter_costs,
                        tails_tile_candidates, tails_tile_cost_from,
                        tails_tile_index, tails_tile_schedule)
from .intermittent import (POWER_SYSTEMS, RunResult, STRATEGIES,
                           _alloc_activations, _run_layer_chain)
from .nvstore import NVStore

KIND_WORK = 0
KIND_BURN = 1
KIND_CALIB = 2
KIND_SEND = 3

REPLAY_POLICIES = ("fixed", "adaptive")

_N_CLASSES = len(OP_CLASSES)
_CONTROL_IDX = OP_CLASSES.index("control")
_BURN_IDX = OP_CLASSES.index("lea_mac")
_FRAM_WRITE_IDX = OP_CLASSES.index("fram_write")
_RADIO_IDX = OP_CLASSES.index("radio")
_K_TILES = len(tails_tile_candidates())

#: Scanned row fields shared by every plan.
_ROW_FIELDS = ("kind", "n", "iter_cycles", "entry_cycles", "iter_class",
               "entry_class", "commit_cycles", "commit_class",
               "entry_seg_class", "entry_seg_cycles", "tile_flag")
#: Additional scanned fields of parameterized (TAILS) plans.
_TILE_FIELDS = ("tile_n", "tile_iter_cycles", "tile_iter_class",
                "tile_sel_cost")

#: Replay backends: "auto" resolves to the fused XLA event stream for
#: stochastic replays (the deterministic closed form ignores the knob),
#: "pallas" opts into the Pallas lane kernel (interpret mode only: on a
#: TPU it raises, since Mosaic cannot lower its float64 state), and
#: "_while" keeps the legacy data-dependent while-loop for differential
#: testing (private; scheduled for removal once the fused path has been
#: the default for one release).
REPLAY_BACKENDS = ("auto", "xla", "pallas", "_while")

#: Output reductions: "none" materializes per-lane arrays (the bit-exact
#: legacy path and the differential oracle), "stats" stream-reduces lanes
#: into a fixed-size ``core.fleetstats.FleetStats`` chunk by chunk, so
#: the result (and, with ``lane_chunk=``, peak) memory is independent of
#: the fleet size.
REPLAY_REDUCES = ("none", "stats")

#: Default number of chunks the streamed replay's producer stage may run
#: ahead of the chunk currently replaying (the ``prefetch=`` knob on
#: ``fleet_sweep`` / ``capacitor_sweep`` / ``replay_plans``).  1 is
#: classic double buffering: while chunk k replays, chunk k+1's sampler
#: draws, padding and device upload happen on a producer thread, so at
#: most two chunks of lane buffers are alive at once.  0 is the legacy
#: fully synchronous loop -- the bit-compatible differential oracle and
#: the right choice when host memory, not wall clock, is the binding
#: constraint.
DEFAULT_PREFETCH = 1


class ScanState(NamedTuple):
    """Named carry of the row scan (previously a positional 13-tuple whose
    indices had to stay in sync with ``lambda s: ~s[15]``-style accessors
    by hand)."""
    rem: Any            # actual remaining budget this charge
    bel: Any            # believed remaining budget this charge
    live: Any
    reboots: Any
    dead: Any
    classes: Any
    wasted: Any
    stuck: Any
    pend: Any           # pending-window cycles (cross-charge batching)
    pend_class: Any
    pend_rows: Any
    bhat: Any           # EWMA believed per-charge budget
    chg: Any            # cycles spent so far in the current charge
    tx: Any             # uplink bytes shipped (decision 5)
    sent: Any           # uplink transmissions completed
    deferred: Any       # sends deferred past a closed window


# ==========================================================================
# Plan extraction
# ==========================================================================

@dataclass
class FleetPlan:
    """A (net, strategy, power) cell flattened into replayable rows."""

    network: str
    strategy: str
    power: str
    capacity: float              # cycles per charge (inf = continuous)
    recharge_s: float            # mean dead time per reboot
    kind: np.ndarray             # (S,) int32
    n: np.ndarray                # (S,) float64 iterations (0 for atomic rows)
    iter_cycles: np.ndarray      # (S,) float64 cycles per iteration
    entry_cycles: np.ndarray     # (S,) float64 (re-)entry / atomic-unit cost
    iter_class: np.ndarray       # (S, C) float64 per-iteration class cycles
    entry_class: np.ndarray      # (S, C) float64 per-entry class cycles
    commit_cycles: np.ndarray    # (S,) per-iteration commit share of iter
    commit_class: np.ndarray     # (S, C) class vector of that share
    entry_seg_class: np.ndarray  # (S, G) int32 class index per charge block
    entry_seg_cycles: np.ndarray  # (S, G) cycles per charge block (0 = pad)
    tile_flag: np.ndarray        # (S,) int32: 1 = row uses the tile tables
    max_atomic: float            # scalar simulator's non-termination bound
    ref_output: np.ndarray       # continuous-execution output (bit-exact)
    parametric: bool = False     # TAILS tile tables are live
    tile_n: np.ndarray | None = None            # (S, K) iters per candidate
    tile_iter_cycles: np.ndarray | None = None  # (S, K)
    tile_iter_class: np.ndarray | None = None   # (S, K, C)
    tile_sel_cost: np.ndarray | None = None     # (S, K) calibration fit cost

    def __len__(self) -> int:
        return self.kind.shape[0]

    @property
    def total_cycles(self) -> float:
        """Continuous-power cycles (every row completed on first try; for
        parameterized plans, at the nominal capacitor's tile)."""
        return float(np.sum(self.entry_cycles + self.n * self.iter_cycles))


class _RowBuffer:
    def __init__(self, costs, parametric: bool = False):
        self.costs = costs
        self.parametric = parametric
        self.rows: list[tuple] = []

    def _vec(self, counts: dict) -> np.ndarray:
        return np.asarray(class_cycle_vector(self.costs, counts))

    def _segments(self, entry_seq) -> tuple[list, list]:
        """Flatten a charge-ordered sequence of ``(counts, times)`` cost
        dicts into the row's charge-segment list: one ``(class, cycles)``
        block per ``device.charge(op, n * times)`` call the scalar executor
        performs, in execution order.  A torn first attempt walks this list,
        so the burned prefix lands on exactly the classes the scalar's
        per-op accounting charges -- even when one class recurs across the
        sequence's dicts (merged naive / Tile-k rows)."""
        cls, cyc = [], []
        for counts, times in entry_seq:
            for op, k in counts.items():
                c = getattr(self.costs, op) * k * times
                if c > 0:
                    cls.append(OP_CLASSES.index(op))
                    cyc.append(float(c))
        return (cls or [0]), (cyc or [0.0])

    def _append(self, kind, n, iv, ev, cv, segs, tile_flag=0, tile=None):
        if tile is None:
            tile = (np.zeros(_K_TILES), np.zeros(_K_TILES),
                    np.zeros((_K_TILES, _N_CLASSES)), np.zeros(_K_TILES))
        self.rows.append((kind, float(n), float(iv.sum()), float(ev.sum()),
                          iv, ev, float(cv.sum()), cv, segs,
                          int(tile_flag), *tile))

    def work(self, n: int, iter_counts: dict, entry_counts: dict,
             commit_counts: dict | None = None,
             entry_seq: list | None = None) -> None:
        """``entry_seq`` is the charge-ordered ``(counts, times)`` sequence
        the entry cost was merged from; defaults to the single merged dict
        (exact for single-dict rows)."""
        self._append(KIND_WORK, n, self._vec(iter_counts),
                     self._vec(entry_counts), self._vec(commit_counts or {}),
                     self._segments(entry_seq or [(entry_counts, 1.0)]))

    def burn(self) -> None:
        z = np.zeros(_N_CLASSES)
        self._append(KIND_BURN, 0.0, z, z, z, ([0], [0.0]))

    def calib(self, taps: int) -> None:
        """One parameterized calibration for ``taps``: the scan derives the
        per-lane burn count from the lane's capacitor."""
        z = np.zeros(_N_CLASSES)
        sel = np.asarray([tails_tile_cost_from(self.costs, taps, c)
                          for c in tails_tile_candidates()])
        self._append(KIND_CALIB, 0.0, z, z, z, ([0], [0.0]),
                     tile=(np.zeros(_K_TILES), np.zeros(_K_TILES),
                           np.zeros((_K_TILES, _N_CLASSES)), sel))

    def tails_work(self, total: int, taps: int, stage: str,
                   entry_counts: dict, commit_counts: dict,
                   nominal_k: int) -> None:
        """Parameterized TAILS row: one ``(n, iter)`` pair per calibration
        candidate; the direct fields carry the nominal capacitor's pick so
        ``total_cycles`` and non-parameterized consumers stay meaningful."""
        tile_n = np.zeros(_K_TILES)
        tile_ic = np.zeros(_K_TILES)
        tile_iv = np.zeros((_K_TILES, _N_CLASSES))
        sel = np.zeros(_K_TILES)
        for k, cand in enumerate(tails_tile_candidates()):
            t = max(1, min(cand, total))
            iv = self._vec(tails_stage_iter_costs(stage, t, taps))
            tile_n[k] = -(-total // t)
            tile_ic[k] = iv.sum()
            tile_iv[k] = iv
            sel[k] = tails_tile_cost_from(self.costs, taps, cand)
        ev = self._vec(entry_counts)
        cv = self._vec(commit_counts or {})
        self.rows.append((KIND_WORK, tile_n[nominal_k], tile_ic[nominal_k],
                          float(ev.sum()), tile_iv[nominal_k], ev,
                          float(cv.sum()), cv,
                          self._segments([(entry_counts, 1.0)]), 1,
                          tile_n, tile_ic, tile_iv, sel))

    def arrays(self) -> dict:
        cols = list(zip(*self.rows))
        g = max(len(c) for c, _cyc in cols[8])
        seg_cls = np.zeros((len(self.rows), g), np.int32)
        seg_cyc = np.zeros((len(self.rows), g), np.float64)
        for i, (c, cyc) in enumerate(cols[8]):
            seg_cls[i, :len(c)] = c
            seg_cyc[i, :len(cyc)] = cyc
        out = dict(kind=np.asarray(cols[0], np.int32),
                   n=np.asarray(cols[1], np.float64),
                   iter_cycles=np.asarray(cols[2], np.float64),
                   entry_cycles=np.asarray(cols[3], np.float64),
                   iter_class=np.stack(cols[4]).astype(np.float64),
                   entry_class=np.stack(cols[5]).astype(np.float64),
                   commit_cycles=np.asarray(cols[6], np.float64),
                   commit_class=np.stack(cols[7]).astype(np.float64),
                   entry_seg_class=seg_cls,
                   entry_seg_cycles=seg_cyc,
                   tile_flag=np.asarray(cols[9], np.int32))
        if self.parametric:
            out.update(tile_n=np.stack(cols[10]).astype(np.float64),
                       tile_iter_cycles=np.stack(cols[11]).astype(np.float64),
                       tile_iter_class=np.stack(cols[12]).astype(np.float64),
                       tile_sel_cost=np.stack(cols[13]).astype(np.float64))
        return out


#: Per-iteration commit share of SONIC/TAILS loop rows: the single atomic
#: cursor-word FRAM write (what the adaptive policy batches per chunk).
_CURSOR_COMMIT = {"fram_write": 1}


def _cycles(costs, counts: dict) -> float:
    return float(sum(class_cycle_vector(costs, counts)))


def _merge(into: dict, counts: dict, times: float = 1.0) -> None:
    for op, k in counts.items():
        into[op] = into.get(op, 0.0) + k * times


def _reference_run(net: SimNet, x, strategy: str):
    """Continuous-power scalar execution: bit-exact output + the scalar
    simulator's atomic-region bound (which, for TAILS, is sized with the
    continuously-calibrated tile -- mirroring ``evaluate``'s DNF check)."""
    costs = LEA_COSTS if strategy == "tails" else SOFTWARE_COSTS
    ref_dev = Device(make_power_system("continuous"), costs)
    if strategy == "naive":
        out = run_naive(net, x, ref_dev)
        return np.asarray(out), float(ref_dev.stats.live_cycles)
    out, max_atomic = _run_layer_chain(net, x, ref_dev, strategy)
    return np.asarray(out), float(max_atomic)


def _emit_parametric_tails_layer(buf: _RowBuffer, layer, in_shape,
                                 nominal_k: int) -> None:
    """Rows of one conv/FC layer with per-candidate tile tables, mirroring
    the segment order of ``inference.tails_segments`` exactly."""
    if isinstance(layer, Conv2D):
        co, ho, wo = layer.out_shape(in_shape)
        hw = ho * wo
        ci_n, kh, kw = layer.w.shape[1:]
        for _f in range(co):
            buf.tails_work(hw, kw, "init", {}, _CURSOR_COMMIT, nominal_k)
            for _s in range(ci_n * kh):
                buf.tails_work(hw, kw, "mac", tails_conv_entry_costs(kw),
                               _CURSOR_COMMIT, nominal_k)
            buf.tails_work(hw, kw, "store", {}, _CURSOR_COMMIT, nominal_k)
    else:
        m, n = layer.w.shape
        buf.tails_work(m, 1, "init", {}, _CURSOR_COMMIT, nominal_k)
        for _j in range(n):
            buf.tails_work(m, 1, "mac", dict(TAILS_FC_ENTRY_COSTS),
                           _CURSOR_COMMIT, nominal_k)
        buf.tails_work(m, 1, "store", {}, _CURSOR_COMMIT, nominal_k)


def build_plan(net: SimNet, x: np.ndarray, strategy: str, power,
               ref: tuple | None = None,
               parametric: bool = False) -> FleetPlan:
    """Flatten one (net, strategy, power) cell into a :class:`FleetPlan`.

    ``power`` is a system name or a :class:`~repro.core.energy.PowerSystem`
    (custom capacitors for sweeps).  ``ref`` is an optional precomputed
    ``(ref_output, max_atomic)`` pair (from :func:`_reference_run`) so
    callers building a whole power row can amortize the single continuous
    scalar pass per strategy.  ``parametric=True`` (TAILS only) emits
    per-candidate tile tables and ``CALIB`` rows instead of baking the
    nominal capacitor's tile, so one plan replays across capacitor grids.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}")
    if parametric and strategy != "tails":
        raise ValueError("parametric plans exist only for TAILS "
                         "(tile calibration is the power-dependent choice)")
    power_sys = make_power_system(power)
    costs = LEA_COSTS if strategy == "tails" else SOFTWARE_COSTS
    capacity = math.inf if power_sys.continuous else power_sys.cycles_per_charge
    ref_out, max_atomic = ref if ref is not None else \
        _reference_run(net, x, strategy)
    buf = _RowBuffer(costs, parametric=parametric)

    if strategy == "naive":
        # The whole inference is one atomic unit: naive accumulates in
        # registers and has no commits, so any power failure restarts it
        # from scratch (a single row re-paying everything on each retry).
        # The per-layer dicts are kept as the row's charge-segment list so
        # a torn attempt books its burned prefix to exactly the (layer, op)
        # blocks the scalar executor charges, in order.
        probe = Device(make_power_system("continuous"), costs)
        counts: dict = {}
        seq: list = []
        for layer, in_shape in zip(net.layers, net.shapes()):
            lc = naive_layer_cycles(probe, layer, in_shape)
            _merge(counts, lc)
            seq.append((lc, 1.0))
        buf.work(0, {}, counts, entry_seq=seq)
        return FleetPlan(net.name, strategy, power_sys.name, capacity,
                         power_sys.recharge_s, max_atomic=max_atomic,
                         ref_output=ref_out, **buf.arrays())

    nv = NVStore(None)
    names = _alloc_activations(nv, net, x)
    probe = Device(make_power_system("continuous"), costs)
    tile_k = int(strategy.split("-")[1]) if strategy.startswith("tile") else 0
    calibrated: dict[int, int] = {}      # taps -> burn count (tails)
    shapes = net.shapes()

    for pc, layer in enumerate(net.layers):
        if strategy == "tails":
            # Pre-seed the capacity-calibrated tile (pure schedule) and emit
            # the charge-burning discovery attempts -- as BURN rows baked for
            # this capacitor, or as one CALIB row whose burn count the scan
            # derives per lane -- in the first-use order the scalar executor
            # performs them.
            t = layer.w.shape[3] if isinstance(layer, Conv2D) else \
                1 if isinstance(layer, DenseFC) else None
            if t is not None and t not in calibrated:
                tile, burns = tails_tile_schedule(costs, capacity, t)
                calibrated[t] = burns
                if parametric:
                    buf.calib(t)
                else:
                    nv.alloc(f"tails/tile/{t}", (), np.int64, init=tile)
                    if not power_sys.continuous:
                        for _ in range(burns):
                            buf.burn()
        if parametric and isinstance(layer, (Conv2D, DenseFC)):
            t = layer.w.shape[3] if isinstance(layer, Conv2D) else 1
            _emit_parametric_tails_layer(
                buf, layer, shapes[pc],
                nominal_k=tails_tile_index(costs, capacity, t))
        else:
            if parametric:
                segs = sonic_segments(nv, layer, names[pc], names[pc + 1],
                                      f"L{pc}")
            else:
                segs = build_layer_segments(nv, probe, layer, names[pc],
                                            names[pc + 1], f"L{pc}", strategy)
            if strategy in ("sonic", "tails"):
                for s in segs:
                    buf.work(s.n, s.iter_costs, s.seg_costs, _CURSOR_COMMIT)
            else:
                # Tile-k: enumerate the actual tasks (a task may span segment
                # boundaries), each an atomic redo-log + commit + transition.
                # The span-ordered dicts are the row's charge-segment list
                # (the scalar runner charges seg entry, then iters, per
                # span, then the commit walk).
                for u, hi, spans in iter_task_spans(segs, tile_k):
                    counts = {}
                    seq = []
                    for seg, lo_l, hi_l in spans:
                        _merge(counts, seg.seg_costs)
                        seq.append((seg.seg_costs, 1.0))
                        _merge(counts, seg.iter_costs, hi_l - lo_l)
                        seq.append((seg.iter_costs, float(hi_l - lo_l)))
                    tail = {"commit_word": hi - u, "task_transition": 1}
                    _merge(counts, tail)
                    seq.append((tail, 1.0))
                    buf.work(0, {}, counts, entry_seq=seq)
        # Layer-boundary commit: one atomic NV word (the layer cursor).
        buf.work(0, {}, {"fram_write": 1})

    return FleetPlan(net.name, strategy, power_sys.name, capacity,
                     power_sys.recharge_s, max_atomic=max_atomic,
                     ref_output=ref_out, parametric=parametric,
                     **buf.arrays())


def with_uplink(plan: FleetPlan) -> FleetPlan:
    """Append the decision-5 uplink row: one ``KIND_SEND`` row whose cost
    the replay derives per lane at run time from the lane's classifier
    confidence and the packed radio vector (``runtime.radio``).

    The row's static cost fields are all zero (``entry_cycles=0``, so
    ``total_cycles`` and every non-uplink consumer are unchanged, and a
    replay without a radio model passes the row through as a no-op); its
    single charge segment is statically classed ``radio`` so a torn
    transmission's burned prefix books to the radio op class.  Idempotent:
    a plan already ending in a SEND row is returned as-is.  For a
    :class:`PlanSet`, apply per plan *before* ``from_plans``."""
    import dataclasses

    if len(plan) and plan.kind[-1] == KIND_SEND:
        return plan

    def app(a, row):
        a = np.asarray(a)
        return np.concatenate([a, np.asarray(row, a.dtype)[None]], axis=0)

    g = plan.entry_seg_class.shape[1]
    z = np.zeros(_N_CLASSES)
    seg_cls = np.zeros(g, np.int32)
    seg_cls[0] = _RADIO_IDX
    fields = dict(
        kind=app(plan.kind, KIND_SEND),
        n=app(plan.n, 0.0),
        iter_cycles=app(plan.iter_cycles, 0.0),
        entry_cycles=app(plan.entry_cycles, 0.0),
        iter_class=app(plan.iter_class, z),
        entry_class=app(plan.entry_class, z),
        commit_cycles=app(plan.commit_cycles, 0.0),
        commit_class=app(plan.commit_class, z),
        entry_seg_class=app(plan.entry_seg_class, seg_cls),
        entry_seg_cycles=app(plan.entry_seg_cycles, np.zeros(g)),
        tile_flag=app(plan.tile_flag, 0))
    if plan.parametric:
        fields.update(
            tile_n=app(plan.tile_n, np.zeros(_K_TILES)),
            tile_iter_cycles=app(plan.tile_iter_cycles,
                                 np.zeros(_K_TILES)),
            tile_iter_class=app(plan.tile_iter_class,
                                np.zeros((_K_TILES, _N_CLASSES))),
            tile_sel_cost=app(plan.tile_sel_cost, np.zeros(_K_TILES)))
    return dataclasses.replace(plan, **fields)


# ==========================================================================
# Jitted replay
# ==========================================================================

def _scan_step(cap, trace_cum, tail_s, charge_cum, theta, window, alpha,
               conf, radio, adaptive, parametric, stochastic, has_send,
               state, row):
    """Advance device state over one plan row.

    Power failure is a state transition: the buffer's remainder is burned
    (torn work re-runs from the last commit), the reboot counter advances,
    and the row resumes with a fresh charge.  Deterministic charges
    (``stochastic=False``) collapse an ``n``-iteration row's reboots to the
    closed form ``ceil(remaining / per-charge affordable iterations)``; with
    a charge-capacity trace -- or cross-charge batching, which needs the
    charge boundaries -- the row is replayed charge by charge instead,
    because refill ``r`` delivers ``charge_cum[r] - charge_cum[r-1]`` cycles
    while the lane still *believes* its budget ``bhat``.  The per-lane
    decisions (tile, commit granularity + cross-charge window, per-reboot
    dead time, per-charge capacity, belief recalibration) are taken here;
    ``adaptive``/``parametric``/``stochastic`` are static (``theta``,
    ``window`` and ``alpha`` are traced), so the default configuration
    compiles to exactly the legacy closed form (bit-exact vs the scalar
    simulator) and the theta x window x alpha frontier reuses ONE compile.

    Cross-charge state (all zero/nominal unless ``window > 1`` or
    ``alpha > 0``):

    ``pend``/``pend_class``/``pend_rows``
        the *pending window*: cycles, class vector and row count of
        completed-but-uncommitted rows deferred within the current charge.
        Every charge either commits the window (one cursor write, at the
        believed end of the charge or at any other durable commit) or
        tears it -- pending work never survives a reboot uncommitted.
    ``bhat``
        the EWMA believed per-charge budget (init: nominal capacity),
        updated at every death of a refill-started charge from the
        observed charge length; refills wake believing ``bhat``.
    ``chg``
        cycles spent so far in the current charge (the observation).
    ``debt``/``debt_class`` (charge-loop local)
        torn pending work being replayed: the lane re-entered the earliest
        uncommitted row and re-executes the lost cycles, committing once
        per replay charge so the rollback converges monotonically.
    """
    import jax.numpy as jnp  # deferred: keep `import repro.core` jax-free
    from jax import lax

    from repro.kernels.charge_replay import (ChargeState, charge_once,
                                             fast_forward, row_ctx,
                                             send_defer_wait, trace_window)

    # `bel` is the lane's *believed* remaining budget: the device counts
    # spent cycles against its believed capacity, so within one charge the
    # belief error (believed - actual delivery) persists across rows.  On
    # the deterministic path bel == rem always (zero belief error).
    (rem, bel, live, reboots, dead, classes, wasted, stuck,
     pend, pend_class, pend_rows, bhat, chg, tx, sent, deferred) = \
        ScanState(*state)

    # Decisions 1 + 2 (TAILS tile selection from the carried capacitor,
    # retry-side commit granularity + the nominal passability bound) are
    # shared with the fused event kernel -- one source of truth.
    ctx = row_ctx(row, cap, theta, adaptive, parametric,
                  conf=conf, radio=radio, has_send=has_send)
    k = ctx.k

    # decision 5: a SEND row waking into a closed basestation window
    # sleeps (dead time, no energy) until the window reopens.  Every
    # legacy row step is a fresh row entry, so the check is unconditional
    # here; the event stream applies it on fresh entries only.
    send_wait = jnp.zeros_like(dead)
    defer_now = jnp.asarray(False)
    if has_send:
        is_send = row["kind"] == KIND_SEND
        want_send = is_send & (ctx.send_bytes > 0.0) & ~ctx.row_stuck
        closed, wait = send_defer_wait(live, dead, radio)
        defer_now = want_send & closed
        send_wait = jnp.where(defer_now, wait, 0.0)

    # SEND rows ride the generic atomic-row machinery (row_ctx overrode
    # the entry cost/classes), so they enter the charge loop like WORK.
    passthrough = row["kind"] != KIND_WORK
    if has_send:
        passthrough = passthrough & (row["kind"] != KIND_SEND)
    cs0 = ChargeState(
        rem=rem, bel=bel, left=ctx.n, live=live, reboots=reboots,
        classes=classes, wasted=wasted, pend=pend, pend_class=pend_class,
        pend_rows=pend_rows, bhat=bhat, chg=chg,
        debt=jnp.zeros_like(rem), debt_class=jnp.zeros_like(pend_class),
        stuck=stuck, done=passthrough)

    if not stochastic:
        # -- closed form: every charge delivers exactly `cap` cycles.
        # The deterministic path IS the fast path: `fast_forward` is the
        # same chunk/retry algebra the fused kernel applies whenever a
        # lane's remaining trace is all-nominal, here applied to a fresh
        # row.  (Cross-charge state is inert on this path: it is only
        # selected when window == 1 and there is no capacity trace, where
        # the pending window never opens and the belief stays nominal.)
        out = fast_forward(ctx, cap, theta, adaptive, cs0)
    else:
        # -- decisions 4/5: charge-by-charge replay over the capacity
        # trace, with the cross-charge pending window and EWMA belief.
        # This data-dependent loop is the legacy backend="_while" form;
        # the default fused constant-trip event stream lives in
        # repro.kernels.charge_replay.event_replay and routes around
        # _scan_step entirely (see _scan_one).
        def refill_sum(r0, r1):
            """Total capacity of refills (r0, r1]; past-trace refills
            fall back to the nominal `cap`."""
            return trace_window(charge_cum, r0, r1, cap)

        out = lax.while_loop(
            lambda s: ~s.done,
            lambda s: charge_once(ctx, cap, charge_cum, theta, window,
                                  alpha, adaptive, s),
            cs0)
    (new_rem, new_bel, _, new_live, new_reboots, new_classes,
     new_wasted, new_pend, new_pend_class, new_pend_rows, new_bhat,
     new_chg, _debt, _dcls, new_stuck, _) = out

    # -- BURN rows: a failed calibration attempt drains the whole buffer ---
    # (calibration precedes any deferrable work, so the pending window is
    # empty here; the deliberate drain is not a budget observation)
    is_burn = row["kind"] == KIND_BURN
    if stochastic:
        new_rem = jnp.where(is_burn, refill_sum(reboots, reboots + 1.0),
                            new_rem)
    else:
        new_rem = jnp.where(is_burn, cap, new_rem)
    new_bel = jnp.where(is_burn, bhat, new_bel)
    new_live = jnp.where(is_burn, live + rem, new_live)
    new_reboots = jnp.where(is_burn, reboots + 1.0, new_reboots)
    burn_vec = jnp.zeros_like(classes).at[_BURN_IDX].add(rem)
    new_classes = jnp.where(is_burn, classes + burn_vec, new_classes)
    new_stuck = jnp.where(is_burn, stuck, new_stuck)
    new_wasted = jnp.where(is_burn, wasted, new_wasted)
    new_chg = jnp.where(is_burn, jnp.zeros_like(new_chg), new_chg)

    # -- CALIB rows: per-lane burn count from the capacitor (Sec. 7.1) -----
    if parametric:
        is_calib = row["kind"] == KIND_CALIB
        burns = k.astype(rem.dtype)     # ladder candidates that do not fit
        if stochastic:
            calib_live = jnp.where(
                burns > 0,
                rem + refill_sum(reboots, reboots + burns - 1.0), 0.0)
            calib_rem = jnp.where(
                burns > 0,
                refill_sum(reboots + burns - 1.0, reboots + burns), rem)
        else:
            calib_live = jnp.where(burns > 0, rem + (burns - 1.0) * cap,
                                   0.0)
            calib_rem = jnp.where(burns > 0, cap, rem)
        new_rem = jnp.where(is_calib, calib_rem, new_rem)
        new_bel = jnp.where(is_calib, jnp.where(burns > 0, bhat, bel),
                            new_bel)
        new_live = jnp.where(is_calib, live + calib_live, new_live)
        new_reboots = jnp.where(is_calib, reboots + burns, new_reboots)
        calib_vec = jnp.zeros_like(classes).at[_BURN_IDX].add(calib_live)
        new_classes = jnp.where(is_calib, classes + calib_vec, new_classes)
        new_stuck = jnp.where(is_calib, stuck, new_stuck)
        new_wasted = jnp.where(is_calib, wasted, new_wasted)
        new_chg = jnp.where(is_calib & (burns > 0),
                            jnp.zeros_like(new_chg), new_chg)

    # -- decision 3: per-reboot dead time from the lane's recharge trace ---
    # (the window wait adds first as its own float step, matching the
    # event stream's dead_base ordering bit-for-bit)
    new_dead = (dead + send_wait) + trace_window(trace_cum, reboots,
                                                 new_reboots, tail_s)

    # -- decision 5: book TX on row completion.  A stuck SEND row (cost
    # beyond a nominal charge) never gets its payload out.
    new_tx, new_sent, new_deferred = tx, sent, deferred
    if has_send:
        adv_tx = is_send & ~ctx.row_stuck
        new_tx = tx + jnp.where(adv_tx, ctx.send_bytes, 0.0)
        new_sent = sent + jnp.where(adv_tx & (ctx.send_bytes > 0.0),
                                    1.0, 0.0)
        new_deferred = deferred + jnp.where(defer_now, 1.0, 0.0)

    return ScanState(new_rem, new_bel, new_live, new_reboots, new_dead,
                     new_classes, new_wasted, new_stuck, new_pend,
                     new_pend_class, new_pend_rows, new_bhat,
                     new_chg, new_tx, new_sent, new_deferred), None


def _scan_one(rows, cap, rem0, trace_cum, tail_s, charge_cum,
              nominal_from, s_real, theta, window, alpha, conf, radio,
              adaptive, parametric, stochastic, backend, chunk,
              enable_fast, has_burn, has_send, plan_idx=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    # Stochastic replays default to the fused constant-trip event stream
    # (repro.kernels.charge_replay); backend="_while" keeps the legacy
    # row scan + data-dependent charge loop for differential testing.
    if stochastic and backend != "_while":
        from repro.kernels.charge_replay import event_replay
        return event_replay(rows, cap, rem0, trace_cum, tail_s,
                            charge_cum, nominal_from, s_real, theta,
                            window, alpha, adaptive=adaptive,
                            parametric=parametric,
                            enable_fast=enable_fast, has_burn=has_burn,
                            has_send=has_send, conf=conf, radio=radio,
                            chunk=chunk, plan_idx=plan_idx)

    # Plan IR v2 on the legacy paths: gather this lane's candidate from
    # the stacked (P, S, ...) row tables.  Under vmap this materializes a
    # per-lane copy of the rows, so the plan axis only rides the legacy
    # scan for small differential-oracle configs; real design sweeps are
    # stochastic and take the fused event stream above, which indexes the
    # packed (P, S, F) tensor in place.
    if plan_idx is not None:
        rows = jax.tree_util.tree_map(lambda a: a[plan_idx], rows)

    # NB: the wasted channel is zeros_like(rem0) (not a fresh constant) so
    # its shard_map replication matches the other carries even on the
    # deterministic path, where the scan never updates it.  The same holds
    # for every cross-charge carry (pend, pend_rows, bhat, chg).
    state0 = ScanState(
        rem=rem0, bel=rem0,           # actual + believed remaining budget
        live=jnp.asarray(0.0, rem0.dtype),
        reboots=jnp.asarray(0.0, rem0.dtype),
        dead=jnp.asarray(0.0, rem0.dtype),
        classes=jnp.zeros((_N_CLASSES,), rem0.dtype),
        wasted=jnp.zeros_like(rem0),
        stuck=jnp.asarray(False),
        pend=jnp.zeros_like(rem0),                    # pending cycles
        pend_class=jnp.zeros((_N_CLASSES,), rem0.dtype),
        pend_rows=jnp.zeros_like(rem0),               # pending rows
        bhat=cap + jnp.zeros_like(rem0),              # believed budget
        chg=jnp.zeros_like(rem0),                     # spent this charge
        tx=jnp.zeros_like(rem0),                      # uplink bytes
        sent=jnp.zeros_like(rem0),
        deferred=jnp.zeros_like(rem0))
    final, _ = lax.scan(
        lambda s, r: _scan_step(cap, trace_cum, tail_s, charge_cum, theta,
                                window, alpha, conf, radio, adaptive,
                                parametric, stochastic, has_send, s, r),
        state0, rows)
    return dict(live=final.live, reboots=final.reboots, dead=final.dead,
                classes=final.classes, wasted=final.wasted,
                stuck=final.stuck, rem=final.rem, belief=final.bhat,
                tx_bytes=final.tx, msgs_sent=final.sent,
                msgs_deferred=final.deferred)


@lru_cache(maxsize=None)
def _vmap_replay(shared_rows, adaptive: bool, parametric: bool,
                 stochastic: bool, backend: str, chunk: int,
                 enable_fast: bool, has_burn: bool,
                 has_send: bool = False):
    """The vmapped replay.  ``shared_rows=False``: rows, caps, rem0, traces
    all batched on axis 0 (one lane per plan -- the Fig. 9 matrix).
    ``shared_rows=True``: one plan broadcast across every device lane (fleet
    sweeps; avoids materializing D copies of the plan).
    ``shared_rows="plan"`` is Plan IR v2: a stacked (P, S, ...) candidate
    batch broadcast across every lane, plus a 12th per-lane operand --
    the lane's integer ``plan_idx`` into the candidate axis -- so one
    compiled replay prices a whole design space (``PlanSet``).
    ``adaptive``/
    ``parametric``/``stochastic``/``backend`` are static so the default
    configuration compiles to exactly the legacy closed form; ``theta``,
    ``window`` (the cross-charge commit window) and ``alpha`` (the EWMA
    belief rate) are traced operands, so sweeping any of them reuses one
    compilation.  ``nominal_from`` (fast-path switchover index) and
    ``s_real`` (real row count) are per-lane traced operands of the fused
    event stream; the legacy paths ignore them."""
    import jax

    def fleet_replay(rows, cap, rem0, tc, ts, ccum, nf, sr, theta, window,
                     alpha, conf, radio, pidx=None):
        return _scan_one(rows, cap, rem0, tc, ts, ccum, nf, sr, theta,
                         window, alpha, conf, radio, adaptive, parametric,
                         stochastic, backend, chunk, enable_fast, has_burn,
                         has_send, plan_idx=pidx)

    in_axes = ((None if shared_rows else 0), 0, 0, 0, 0, 0, 0, 0, None,
               None, None, 0, None)
    if shared_rows == "plan":
        in_axes += (0,)
    return jax.vmap(fleet_replay, in_axes=in_axes)


@lru_cache(maxsize=None)
def _jit_replay(shared_rows, adaptive: bool, parametric: bool,
                stochastic: bool, backend: str = "xla",
                chunk: int = 128, enable_fast: bool = False,
                has_burn: bool = False, has_send: bool = False):
    import jax
    return jax.jit(_vmap_replay(shared_rows, adaptive, parametric,
                                stochastic, backend, chunk, enable_fast,
                                has_burn, has_send))


@lru_cache(maxsize=None)
def _jit_sharded_replay(mesh, shared_rows, adaptive: bool,
                        parametric: bool, stochastic: bool,
                        backend: str = "xla", chunk: int = 128,
                        enable_fast: bool = False,
                        has_burn: bool = False, has_send: bool = False):
    """The replay wrapped in ``shard_map`` over the fleet's device axis:
    per-lane inputs/outputs split across the mesh, plan rows replicated
    (the whole stacked candidate batch under ``shared_rows="plan"``, with
    the per-lane ``plan_idx`` sharded like every other lane input).
    Lanes are independent, so no collectives are needed -- the mesh purely
    spreads lane memory and compute across chips."""
    import jax
    from jax.sharding import PartitionSpec as P

    fn = _vmap_replay(shared_rows, adaptive, parametric, stochastic,
                      backend, chunk, enable_fast, has_burn, has_send)
    lane = P("devices")
    rows_spec = lane if shared_rows is False else P()
    in_specs = (rows_spec, lane, lane, lane, lane, lane, lane, lane,
                P(), P(), P(), lane, P())
    if shared_rows == "plan":
        in_specs += (lane,)
    # check_vma=False: lanes never communicate here, so there is no
    # cross-shard value whose replication the type check could verify.
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=lane, check_vma=False)

    def fleet_replay_sharded(*args):
        return sharded(*args)

    return jax.jit(fleet_replay_sharded)


#: Measured event-chunk winners, keyed by (plan bucket shape x replay
#: static config x lane count).  Bucketed row tables make the key stable
#: across same-bucket plans, so one sweep's timing pays for every later
#: sweep of a similarly-shaped plan.
_EVENT_CHUNK_CACHE: dict = {}


def _autotune_event_chunk(key: tuple, s_bucket: int, dispatch) -> int:
    """Measured ``event_chunk="auto"`` resolution: time the candidate
    pow2 chunk lengths (``kernels.charge_replay.event_chunk_candidates``,
    the plan-shape default plus one octave either side) on the live
    first-chunk operands via ``dispatch(candidate)`` and cache the
    winner under ``key``.  Each candidate is dispatched twice
    (compile + warm) and the warm wall decides, so the tuner never picks
    a chunk on compile noise; the heuristic default is always among the
    candidates, bounding the worst case at "what the default already
    did" plus the one-off timing cost."""
    import jax

    from repro.kernels.charge_replay import event_chunk_candidates

    hit = _EVENT_CHUNK_CACHE.get(key)
    if hit is not None:
        return hit
    best, best_t = None, math.inf
    for cand in event_chunk_candidates(s_bucket):
        jax.block_until_ready(dispatch(cand))        # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(dispatch(cand))
        dt = time.perf_counter() - t0
        if dt < best_t:
            best, best_t = cand, dt
    _EVENT_CHUNK_CACHE[key] = best
    return best


def _validate_replay_knobs(policy: str, batch_rows: int,
                           belief_alpha: float, backend: str,
                           reduce: str) -> None:
    """Shared replay-knob validation for ``_run_replay`` and the
    overlapped chunk pipeline (which dispatches compiled replays without
    going through ``_run_replay``)."""
    if policy not in REPLAY_POLICIES:
        raise ValueError(f"unknown replay policy {policy!r}; "
                         f"expected one of {REPLAY_POLICIES}")
    if batch_rows < 1:
        raise ValueError(f"batch_rows must be >= 1, got {batch_rows}")
    if not 0.0 <= belief_alpha < 1.0:
        raise ValueError(f"belief_alpha must be in [0, 1), "
                         f"got {belief_alpha}")
    if backend not in REPLAY_BACKENDS:
        raise ValueError(f"unknown replay backend {backend!r}; "
                         f"expected one of {REPLAY_BACKENDS}")
    if reduce not in REPLAY_REDUCES:
        raise ValueError(f"unknown reduce mode {reduce!r}; "
                         f"expected one of {REPLAY_REDUCES}")


def _x64():
    """Context manager for 64-bit mode (thread-local: the overlapped
    pipeline's producer thread enters its own)."""
    import jax
    return jax.enable_x64(True)


def _pad_axis0(a: np.ndarray, pad: int) -> np.ndarray:
    return np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))


def _pad_stack(plans: list[FleetPlan]) -> dict:
    """Stack plans of different lengths; padding rows are no-op WORK rows.
    Trailing axes that vary per plan (the charge-segment axis) are padded
    to the batch maximum too (zero-length segments book nothing).  Tile
    tables are included iff any plan is parameterized (zero-filled for the
    rest: ``tile_flag=0`` rows never read them)."""
    smax = max(len(p) for p in plans)
    fields = _ROW_FIELDS + (_TILE_FIELDS if any(p.parametric for p in plans)
                            else ())
    out: dict[str, list] = {k: [] for k in fields}
    for p in plans:
        pad = smax - len(p)
        for k in fields:
            v = getattr(p, k)
            if v is None:      # fixed plan in a mixed batch: zero tables
                shape = ((len(p), _K_TILES, _N_CLASSES)
                         if k == "tile_iter_class" else (len(p), _K_TILES))
                v = np.zeros(shape)
            out[k].append(_pad_axis0(v, pad))
    stacked = {}
    for k, vs in out.items():
        if vs[0].ndim > 1:
            gmax = tuple(max(v.shape[i] for v in vs)
                         for i in range(1, vs[0].ndim))
            vs = [np.pad(v, [(0, 0)] + [(0, g - s) for g, s in
                                        zip(gmax, v.shape[1:])])
                  for v in vs]
        stacked[k] = np.stack(vs)
    return stacked


def _plan_rows(plan: FleetPlan) -> dict:
    fields = _ROW_FIELDS + (_TILE_FIELDS if plan.parametric else ())
    return {k: getattr(plan, k) for k in fields}


def _bucket_target(s: int, floor: int = 64) -> int:
    """The power-of-two row-bucket a plan of ``s`` rows is padded to."""
    return max(floor, 1 << max(s - 1, 0).bit_length())


def _bucket_rows(rows: dict, lane_axis) -> dict:
    """Pad the plan's row axis to a power-of-two bucket (>= 64) and the
    charge-segment axis to a power-of-two bucket (>= 4), so plans of
    similar size share one compiled replay (SONIC and TAILS land in the
    same bucket, halving the fleet bench's compile bill).  Padding rows
    are all-zero WORK rows -- both replay paths complete them for free
    without touching any output channel -- and the fused path's ``s_real``
    cursor bound never walks them anyway.  ``lane_axis`` is ``False`` for
    a single shared plan (row axis 0), and ``True`` or ``"plan"`` for a
    leading batch axis (per-plan lanes / the stacked candidate axis)."""
    ax = 0 if lane_axis is False else 1
    s = rows["kind"].shape[ax]
    target = _bucket_target(s)
    out = {}
    for k, v in rows.items():
        v = np.asarray(v)
        pads = [(0, 0)] * v.ndim
        pads[ax] = (0, target - s)
        if k in ("entry_seg_class", "entry_seg_cycles"):
            g = v.shape[-1]
            pads[-1] = (0, max(4, 1 << max(g - 1, 0).bit_length()) - g)
        out[k] = np.pad(v, pads)
    return out


def _reboot_upper_bound(rows: dict, caps: np.ndarray,
                        lane_axis) -> np.ndarray:
    """Cheap per-lane estimate of how many reboots a replay can plausibly
    take: nominal plan cycles over the nominal charge (with a 4x safety
    margin for jitter, torn-prefix re-execution and adaptive drains),
    plus one reboot per BURN row and a full ladder per CALIB row.  Used
    only to decide whether the fused replay's all-nominal fast path is
    *reachable* (``reboots >= nominal_from``); the flag is a pure
    compile-size knob -- an under-estimate never changes results, the
    charge-wise step just walks the nominal tail one charge at a time."""
    ax = 0 if lane_axis is False else 1
    work = np.sum(rows["entry_cycles"]
                  + rows["n"] * (rows["iter_cycles"]
                                 + rows["commit_cycles"]), axis=ax)
    if "tile_n" in rows:
        work = work + np.sum(
            np.max(rows["tile_n"] * rows["tile_iter_cycles"], axis=-1),
            axis=ax)
    burns = (np.sum(rows["kind"] == KIND_BURN, axis=ax)
             + _K_TILES * np.sum(rows["kind"] == KIND_CALIB, axis=ax))
    if lane_axis == "plan":
        # Stacked candidate axis: (P,) per-plan work against (n_lanes,)
        # caps.  The worst-case plan bounds every lane -- the flag is a
        # compile-size knob, so over-estimating merely keeps the fast
        # path compiled in.
        work = np.max(work)
        burns = np.max(burns)
    with np.errstate(invalid="ignore"):
        est = np.where(np.isinf(caps), 0.0, 4.0 * work / caps)
    return est + burns


#: The integer state's infinite capacity (continuous power, and the chunk
#: pipeline's inert pad lanes): far above any sum a replay reaches, and
#: mapped back to ``inf`` on the host (:func:`_lane_results`).
_INT_INF = 1 << 62
#: Finite quantities the integer state takes: whole numbers below 2**53
#: in magnitude, so that float64 holds each of them exactly too.
_INT_LIMIT = 1 << 53
#: The row bounds of the integer state's division
#: (``charge_replay.floor_div``): iterations a row, cycles an iteration.
_INT_MAX_ITERS, _INT_MAX_ITER_CYCLES = 1 << 21, 1 << 30


class _ChunkPrep(NamedTuple):
    """One chunk's stochastic lane operands, in the replay's state."""
    caps: np.ndarray
    rem0: np.ndarray
    charge_cum: np.ndarray
    nominal_from: np.ndarray
    enable_fast: bool
    state: np.dtype


def _whole(a) -> bool:
    """Is every entry of ``a`` a whole number below ``_INT_LIMIT`` in
    magnitude, or ``+inf``?"""
    a = np.asarray(a, np.float64)
    return bool(np.array_equal(np.floor(a), a)         # NaN is not
                and -_INT_LIMIT < a.min(initial=0)
                and np.max(a, where=a < np.inf, initial=0) < _INT_LIMIT)


def _int_rows_fit(rows: dict) -> bool:
    """Can the integer state replay these rows: every field whole, and
    every row within :func:`charge_replay.floor_div`'s bounds?"""
    if not all(_whole(v) for v in rows.values()):
        return False
    iters = [rows["n"]] + ([rows["tile_n"]] if "tile_n" in rows else [])
    cycles = [rows["iter_cycles"]] + (
        [rows["tile_iter_cycles"]] if "tile_iter_cycles" in rows else [])
    return (max(np.abs(v).max(initial=0) for v in iters) < _INT_MAX_ITERS
            and max(np.abs(v).max(initial=0) for v in cycles)
            < _INT_MAX_ITER_CYCLES)


def _as_int(a) -> np.ndarray:
    """Whole cycles as int64, ``+inf`` as :data:`_INT_INF`."""
    a = np.asarray(a, np.float64)
    inf = np.isposinf(a)
    with np.errstate(invalid="ignore"):
        out = a.astype(np.int64)
    out[inf] = _INT_INF
    return out


def _rows_as(rows: dict, state: np.dtype) -> dict:
    """The row table in the replay's state: as it is for float64; for
    int64, every field int32 where all of them fit (an int32 table
    replays 8% faster on a v5e than an int64 one), else int64."""
    if state.kind == "f":
        return rows
    ints = {k: np.asarray(v).astype(np.int64) for k, v in rows.items()}
    fits = all(np.abs(v).max(initial=0) < (1 << 31) for v in ints.values())
    return {k: v.astype(np.int32) if fits else v for k, v in ints.items()}


def _upload_rows(rows: dict, state: np.dtype) -> dict:
    """The row table in the replay's state (:func:`_rows_as`), put on the
    device, inside a ``fleet.rows`` span whose args are the table's row
    slots and bytes.  Call under :func:`_x64`."""
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("fleet.rows",
                         rows=int(np.asarray(rows["kind"]).size)) as span:
        table = _rows_as(rows, state)
        span.set_metadata(bytes=int(sum(np.asarray(v).nbytes
                                        for v in table.values())))
        return {k: jnp.asarray(v) for k, v in table.items()}


def _distinct_plans(shared_rows, plan_idx, keep: np.ndarray) -> int:
    """The candidates among a replay call's ``keep`` lanes: distinct
    indices on the stacked candidate axis (``shared_rows="plan"``), one a
    lane for per-lane rows, else 1."""
    if shared_rows == "plan":
        return int(np.unique(np.asarray(plan_idx)[:keep.shape[0]][keep])
                   .size)
    if shared_rows is False:
        return int(np.count_nonzero(keep))
    return 1


def _int64_available() -> bool:
    """Does this thread's JAX, in the replay's 64-bit mode, keep int64?"""
    import jax
    with _x64():
        return jax.dtypes.canonicalize_dtype(np.int64) == np.int64


def _stochastic_prep(rows: dict, caps, rem0, charge_cum, lane_axis, *,
                     fused: bool, adaptive: bool, has_send: bool,
                     belief_alpha: float) -> _ChunkPrep:
    """A stochastic replay's per-chunk lane prep, shared by
    :func:`_run_replay` and the overlapped pipeline's producer: the
    initial charge floored to whole cycles, the capacity trace
    pow2-padded and its all-nominal tail found (``nominal_from``),
    whether the fast path is reachable, and the replay's state.

    The state is int64 where the replay's arithmetic is provably
    integral: the fused event stream under XLA (``fused``), a fixed
    policy, no SEND rows, ``belief_alpha == 0``, int64 available, and
    every finite capacity, initial charge and charge-trace entry, and
    every field of the (bucketed) ``rows``, a whole number
    (:func:`_whole`, :func:`_int_rows_fit`).  The adaptive policy's
    debt-class ratio split, the EWMA update and the radio window division
    are not integral, so those replays stay float64, as does every other.
    The int64 operands hold an infinite capacity as :data:`_INT_INF`."""
    from repro.runtime.failures import (charge_trace_nominal_from,
                                        pad_charge_trace_columns)

    # Fractional initial charges are floored to whole cycles on the
    # charge-wise path: every cost and capacity is integral, so this keeps
    # the entire energy state integral -- the invariant that makes the
    # fused path's closed-form fast forward (and the charge-wise replay)
    # grouping-independent, i.e. bitwise identical however the charges
    # are batched.  The deterministic closed form does not need it and
    # keeps the caller's fractional charge (it is compared against
    # cycle-exact scalar simulators).
    rem0 = np.where(np.isinf(rem0), np.inf,
                    np.floor(np.asarray(rem0, np.float64)))
    nominal_from = np.zeros(caps.shape[0], np.float64)
    enable_fast = whole_trace = True
    if charge_cum is not None:
        # the padding adds whole multiples of whole caps to the last
        # column, so a whole trace stays whole where its last column does
        whole_trace = _whole(charge_cum)
        charge_cum = pad_charge_trace_columns(charge_cum, caps)
        whole_trace = whole_trace and _whole(charge_cum[:, -1])
        nominal_from = charge_trace_nominal_from(charge_cum, caps)
        enable_fast = bool(np.any(
            _reboot_upper_bound(rows, caps, lane_axis) >= nominal_from))
    else:
        charge_cum = np.zeros((caps.shape[0], 1), np.float64)
    exact = (fused and not adaptive and not has_send
             and belief_alpha == 0 and _whole(caps) and _whole(rem0)
             and whole_trace and _int_rows_fit(rows)
             and _int64_available())
    if not exact:
        return _ChunkPrep(caps, rem0, charge_cum, nominal_from,
                          enable_fast, np.dtype(np.float64))
    return _ChunkPrep(_as_int(caps), _as_int(rem0), _as_int(charge_cum),
                      nominal_from.astype(np.int64), enable_fast,
                      np.dtype(np.int64))


@dataclass
class PlanSet:
    """Plan IR v2: a stacked batch of candidate plans -- the design axis.

    Where :class:`FleetPlan` is one (network, strategy, power) cell, a
    ``PlanSet`` is P of them stacked into one ``(P, S, ...)`` row-table
    batch (per-plan row counts bucket-padded to shared powers of two by
    the same machinery that buckets single plans) plus a per-plan header:
    strategy, real row count, capacity, recharge, nominal cycles.
    ``fleet_sweep(plan=planset)`` replays the whole set -- GENESIS
    compression candidates, Tile-k task sizes, TAILS tiles, restamped
    capacitors -- under ONE compiled scan: lanes are plan-major
    (``lane = p * n_devices + d``), each lane carries its candidate index
    into the packed ``(P, S, F)`` row tensor, and per-plan statistics
    come back as :class:`~repro.core.fleetstats.FleetStats` groups or a
    :class:`DesignSweepResult`.

    The unchunked design sweep draws each plan's lanes with the same
    legacy samplers and seeds an individual ``fleet_sweep(plan=plans[p])``
    call uses, and every jitter multiplier is independent of the plan's
    nominal capacity/recharge, so the stacked sweep's per-plan outputs
    are bit-exact against replaying each plan separately
    (``tests/test_planset.py`` pins this)."""
    plans: tuple
    labels: tuple
    rows: dict                  # (P, S, ...) bucket-padded row tables
    n_rows: np.ndarray          # (P,) int32 real (pre-padding) row counts
    capacity: np.ndarray        # (P,) float64 cycles per full charge
    recharge_s: np.ndarray      # (P,) float64 mean dead time per reboot
    total_cycles: np.ndarray    # (P,) float64 nominal plan cycles
    strategies: tuple

    def __len__(self) -> int:
        return len(self.plans)

    @property
    def parametric(self) -> bool:
        return "tile_sel_cost" in self.rows

    @classmethod
    def from_plans(cls, plans, labels=None) -> "PlanSet":
        plans = tuple(plans)
        if not plans:
            raise ValueError("PlanSet needs at least one plan")
        if labels is None:
            labels = tuple(f"{p.network}/{p.strategy}/{p.power}"
                           for p in plans)
        labels = tuple(labels)
        if len(labels) != len(plans):
            raise ValueError(f"got {len(labels)} labels for "
                             f"{len(plans)} plans")
        rows = _bucket_rows(_pad_stack(list(plans)), lane_axis="plan")
        return cls(
            plans=plans, labels=labels, rows=rows,
            n_rows=np.asarray([len(p) for p in plans], np.int32),
            capacity=np.asarray([p.capacity for p in plans], np.float64),
            recharge_s=np.asarray([p.recharge_s for p in plans],
                                  np.float64),
            total_cycles=np.asarray([p.total_cycles for p in plans],
                                    np.float64),
            strategies=tuple(p.strategy for p in plans))


def _run_replay(rows: dict, caps: np.ndarray, rem0: np.ndarray,
                shared_rows, trace_cum: np.ndarray | None = None,
                tail_s: np.ndarray | None = None, policy: str = "fixed",
                theta: float = 0.5, batch_rows: int = 1,
                belief_alpha: float = 0.0,
                charge_cum: np.ndarray | None = None,
                mesh=None, backend: str = "auto",
                n_rows=None, chunk: int | None = None,
                reduce: str = "none",
                group_id: np.ndarray | None = None,
                valid: np.ndarray | None = None,
                edges: dict | None = None, n_groups: int = 1,
                plan_idx: np.ndarray | None = None,
                conf: np.ndarray | None = None, radio=None,
                config_out: dict | None = None,
                chunk_index: int = 0) -> dict | tuple:
    """One replay call, from host inputs to host outputs (or their
    ``FleetStats`` under ``reduce="stats"``).  ``chunk_index`` tags the
    call's profiler spans with the lane chunk it replays."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.runtime.radio import N_RADIO, radio_vector

    _validate_replay_knobs(policy, batch_rows, belief_alpha, backend,
                           reduce)
    if reduce == "stats" and edges is None:
        raise ValueError("reduce='stats' needs histogram edges")
    if backend == "auto":
        backend = "xla"
    plan_mode = shared_rows == "plan"
    if plan_mode and plan_idx is None:
        raise ValueError("shared_rows='plan' needs a per-lane plan_idx")
    if plan_mode and backend == "pallas":
        raise ValueError(
            "backend='pallas' does not support the stacked candidate-plan "
            "axis (the lane kernel's BlockSpecs cannot gather a per-lane "
            "plan index); use backend='xla' (or 'auto')")
    n_lanes = caps.shape[0]
    parametric = "tile_sel_cost" in rows
    adaptive = policy == "adaptive"
    # decision 5 is live iff a radio model is supplied AND the plan has
    # SEND rows; the static flag keeps radio arithmetic out of every
    # other replay's compiled body.
    has_send = radio is not None and bool(np.any(rows["kind"] == KIND_SEND))
    radio_vec = radio_vector(radio) if radio is not None \
        else np.zeros(N_RADIO, np.float64)
    if conf is None:
        conf = np.zeros(n_lanes, np.float64)
    # Cross-charge batching needs the charge boundaries even without a
    # capacity trace: route it through the charge-by-charge path, where a
    # missing trace degenerates to all-nominal refills.
    stochastic = charge_cum is not None or (adaptive and batch_rows > 1)
    # Per-lane real row count: the fused path's cursor bound (padding rows
    # past it are never walked).
    s_axis = 0 if shared_rows is True else 1
    lane_axis = "plan" if plan_mode else not (shared_rows is True)
    s_real = np.broadcast_to(
        np.asarray(n_rows if n_rows is not None
                   else rows["kind"].shape[s_axis], np.int32), (n_lanes,))
    enable_fast = has_burn = False
    nominal_from = np.zeros(n_lanes, np.float64)
    state = np.dtype(np.float64)
    if backend == "pallas" and mesh is not None:
        raise ValueError("backend='pallas' does not compose with mesh "
                         "sharding; use backend='xla' (or 'auto')")
    with TraceAnnotation("fleet.prep", chunk=chunk_index):
        if stochastic:
            # Shape-bucket the plan so similarly-sized plans (and
            # different trace lengths) share one compiled fused replay.
            has_burn = bool(np.any(rows["kind"] == KIND_BURN))
            rows = _bucket_rows(rows, lane_axis=lane_axis)
            caps, rem0, charge_cum, nominal_from, enable_fast, state = \
                _stochastic_prep(rows, caps, rem0, charge_cum, lane_axis,
                                 fused=backend == "xla", adaptive=adaptive,
                                 has_send=has_send,
                                 belief_alpha=belief_alpha)
        if trace_cum is None:
            trace_cum = np.zeros((n_lanes, 1), np.float64)
        if charge_cum is None:
            charge_cum = np.zeros((n_lanes, 1), np.float64)
        if tail_s is None:
            tail_s = np.zeros(n_lanes, np.float64)
        tail_s = np.broadcast_to(np.asarray(tail_s, np.float64),
                                 (n_lanes,))
        with _x64():
            import jax.numpy as jnp
            args = [_upload_rows(rows, state),
                    jnp.asarray(caps), jnp.asarray(rem0),
                    jnp.asarray(trace_cum), jnp.asarray(tail_s),
                    jnp.asarray(charge_cum),
                    jnp.asarray(nominal_from),
                    jnp.asarray(s_real),
                    jnp.asarray(float(theta), jnp.float64),
                    jnp.asarray(float(batch_rows), jnp.float64),
                    jnp.asarray(float(belief_alpha), jnp.float64),
                    jnp.asarray(np.broadcast_to(
                        np.asarray(conf, np.float64), (n_lanes,))),
                    jnp.asarray(radio_vec)]
            if plan_mode:
                args.append(jnp.asarray(np.asarray(plan_idx, np.int32)))
    autotune = chunk == "auto"
    if chunk is None or autotune:
        # Plan-shape-derived event-chunk default: size the inner scan to
        # the (bucketed) row axis so short plans do not pay a 128-event
        # trip per charge and the tile-8 ~30k-events/lane case amortizes
        # its outer while-loop (kernels/charge_replay.py).
        from repro.kernels.charge_replay import (EVENT_CHUNK,
                                                 default_event_chunk)
        chunk = (default_event_chunk(rows["kind"].shape[s_axis])
                 if stochastic else EVENT_CHUNK)
    # The measured tuner only applies where the fused event stream runs
    # (stochastic XLA, unmeshed); everywhere else "auto" falls back to
    # the plan-shape default above.
    autotune = (autotune and stochastic and mesh is None
                and backend == "xla")
    if config_out is not None:
        # The static compile key of the jit this call dispatches to, in
        # _jit_replay's parameter order -- lets callers pin "the whole
        # sweep was one compile" via _jit_replay(*key)._cache_size().
        config_out.update(
            shared_rows=shared_rows, adaptive=adaptive,
            parametric=parametric, stochastic=stochastic,
            backend="xla" if backend == "pallas" else backend,
            chunk=chunk, enable_fast=enable_fast, has_burn=has_burn,
            has_send=has_send, state=state.name)
    with _x64():
        if autotune:
            chunk = _autotune_event_chunk(
                (shared_rows, adaptive, parametric, stochastic, backend,
                 enable_fast, has_burn, has_send, rows["kind"].shape,
                 n_lanes, state.name), rows["kind"].shape[s_axis],
                lambda c: _jit_replay(shared_rows, adaptive, parametric,
                                      stochastic, backend, c, enable_fast,
                                      has_burn, has_send)(*args))
            if config_out is not None:
                config_out["chunk"] = chunk
        xla_backend = "xla" if backend == "pallas" else backend
        keep = (np.ones(n_lanes, bool) if valid is None
                else np.asarray(valid, bool))
        plans = _distinct_plans(shared_rows, plan_idx, keep)
        if backend == "pallas" and stochastic:
            # The Pallas lane kernel (interpret mode only); the
            # deterministic closed form has no charge loop to fuse, so a
            # non-stochastic replay under backend="pallas" falls through
            # to the XLA path below.
            from repro.kernels.ops import charge_replay as _pallas_replay
            res = _pallas_replay(*args, adaptive=adaptive,
                                 parametric=parametric,
                                 shared_rows=shared_rows,
                                 enable_fast=enable_fast,
                                 has_burn=has_burn, has_send=has_send,
                                 chunk=chunk)
        elif mesh is None:
            with TraceAnnotation("fleet.dispatch", chunk=chunk_index,
                                 state=state.name, plans=plans):
                res = _jit_replay(shared_rows, adaptive, parametric,
                                  stochastic, xla_backend, chunk,
                                  enable_fast, has_burn, has_send)(*args)
        else:
            with TraceAnnotation("fleet.dispatch", chunk=chunk_index,
                                 state=state.name, plans=plans):
                res = _sharded_replay(args, mesh, n_lanes, plan_mode,
                                      shared_rows, adaptive, parametric,
                                      stochastic, xla_backend, chunk,
                                      enable_fast, has_burn, has_send)
    jax.block_until_ready(res)
    shards = 1 if mesh is None else int(mesh.devices.size)
    gid = (np.zeros(n_lanes, np.int64) if group_id is None
           else np.asarray(group_id))
    counts = _pop_event_counts(res, keep, chunk, shards, chunk_index,
                               gid[keep], n_groups)
    out = _lane_results(res, n_lanes, trace_cum, tail_s, has_send,
                        chunk_index=chunk_index)
    if reduce == "stats":
        with TraceAnnotation("fleet.fold", chunk=chunk_index):
            stats = stats_from_outputs({k: v[keep] for k, v in out.items()},
                                       edges, gid[keep], n_groups)
        _set_event_counts(stats, *counts)
        return stats
    return out


def _sharded_replay(args: list, mesh, n_lanes: int, plan_mode: bool,
                    shared_rows, *key):
    """The replay under ``shard_map``: pad the lane axis to a mesh
    multiple with inert continuous lanes (cap = rem0 = inf completes every
    row in one pass); the caller strips the padding from the outputs."""
    import jax.numpy as jnp

    n_shards = int(mesh.devices.size)
    pad = (-n_lanes) % n_shards
    if pad:
        # caps, rem0, trace, tail, charge_cum, nominal_from, s_real
        # lane fills (s_real=0: the fused event stream skips the pad
        # lanes outright)
        inf = (_INT_INF if np.issubdtype(args[1].dtype, np.integer)
               else np.inf)
        fills = (inf, inf, 0, 0, 0, 0, 0)
        for i, fill in enumerate(fills, start=1):
            args[i] = jnp.concatenate(
                [args[i], jnp.full((pad,) + args[i].shape[1:], fill,
                                   args[i].dtype)], axis=0)
        # conf pads with zeros (s_real=0 lanes never take a decision)
        args[11] = jnp.concatenate(
            [args[11], jnp.zeros(pad, args[11].dtype)])
        if plan_mode:
            # pad lanes point at candidate 0; s_real=0 skips them
            args[13] = jnp.concatenate(
                [args[13], jnp.zeros(pad, args[13].dtype)])
        if shared_rows is False:
            args[0] = {k: jnp.concatenate(
                [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
                for k, v in args[0].items()}
    return _jit_sharded_replay(mesh, shared_rows, *key)(*args)


def _dead_time(reboots, trace_cum, tail_s) -> np.ndarray:
    """Each lane's dead time from its final reboot count: the recharge
    trace's window over reboots ``[0, R)`` plus the mean recharge for
    every reboot past the trace's end (``trace_cum`` ``(L, T + 1)``, or
    ``None`` for the mean alone).  This is what the replay's per-row
    windows add up to when no SEND row waits for a window; computed
    here, on the host, it is IEEE float64 on every platform (a TPU's
    emulated float64 is not) and bit-exact against the reference
    interpreter's ``trace_window(cum, 0, R, tail)``."""
    r = np.asarray(reboots, np.float64)
    tail = np.broadcast_to(np.asarray(tail_s, np.float64), r.shape)
    if trace_cum is None:
        return r * tail
    cum = np.asarray(trace_cum, np.float64)
    last = cum.shape[1] - 1
    i1 = np.minimum(np.maximum(r, 0.0), last).astype(np.int64)
    return (cum[np.arange(r.shape[0]), i1] - cum[:, 0]
            + np.maximum(r - last, 0.0) * tail)


def _lane_results(res: dict, m: int, trace_cum, tail_s,
                  has_send: bool, chunk_index: int = 0) -> dict:
    """A replay call's per-lane outputs as host arrays, cut to the first
    ``m`` (real) lanes.  Every channel the device returns is a whole
    number of cycles or events, exact in the device's float64 or int64.
    The integer state's channels come back as float64, exact below 2**53,
    with its infinite-capacity sentinel as ``inf`` again (``rem``,
    ``belief``), so every caller sees the float64 state's types and
    values.  Dead time in seconds is not whole, so without SEND rows it
    is recomputed here (:func:`_dead_time`; the integer state returns
    none).  With SEND rows the replay's own dead time stands: window
    waits depend on it mid-replay.  The caller has waited for ``res``, so
    the ``fleet.download`` span times the copy alone."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("fleet.download", chunk=chunk_index):
        out = {k: np.asarray(v)[:m] for k, v in res.items()}
    for k, v in out.items():
        if v.dtype.kind == "i":
            v = v.astype(np.float64)
            if k in ("rem", "belief"):
                v = np.where(v >= _INT_INF // 2, np.inf, v)
            out[k] = v
    if not has_send:
        with TraceAnnotation("fleet.fold", chunk=chunk_index):
            out["dead"] = _dead_time(out["reboots"], trace_cum[:m],
                                     tail_s[:m])
    return out


def _pop_event_counts(res: dict, keep: np.ndarray, chunk: int, shards: int,
                      chunk_index: int, group_id: np.ndarray,
                      n_groups: int) -> tuple[np.ndarray, int]:
    """Take the event replay's per-lane ``events`` counter off a replay
    result, so that only the lane channels go on to the host: returns the
    lane-events of the ``keep`` lanes (the first ``len(keep)`` of the
    batch, masked), folded by their ``group_id`` into an int64
    ``(n_groups,)`` vector, and the lane-event slots the whole batch ran
    (:func:`repro.kernels.charge_replay.event_slots`); zeros and 0 where
    the replay has no event loop."""
    events = res.pop("events", None)
    if events is None:
        return np.zeros(n_groups, np.int64), 0
    from jax.profiler import TraceAnnotation

    from repro.kernels.charge_replay import event_slots

    with TraceAnnotation("fleet.download", chunk=chunk_index):
        events = np.asarray(events)
    # float64 weights hold each group's sum exactly (below 2**53)
    per_group = np.bincount(group_id, weights=events[:keep.shape[0]][keep],
                            minlength=n_groups).astype(np.int64)
    return per_group, event_slots(events, chunk, shards)


def _set_event_counts(stats, group_events: np.ndarray, slots: int) -> None:
    """Book one replay call's event counts on its ``FleetStats``."""
    stats.group_events = group_events
    stats.replay_events = int(group_events.sum())
    stats.replay_event_slots = slots


def _lane_io_bytes(n_lanes: int, *arrays) -> int:
    """Host-visible per-lane buffer bytes of one replay call: the per-lane
    input arrays plus the per-lane output channels (9 f64 scalars
    -- including the three uplink channels -- the per-class cycle matrix,
    and the bool ``stuck`` flag).  This is the quantity the memory-flat
    bench asserts is a function of the chunk size, not the fleet size."""
    return (sum(a.nbytes for a in arrays if a is not None)
            + n_lanes * (8 * (9 + _N_CLASSES) + 1))


def _chunked_replay(plan_rows: dict, n_rows, n_lanes: int,
                    lane_chunk: int, make_inputs, group_id_of,
                    policy: str, theta: float, batch_rows: int,
                    belief_alpha: float, mesh, backend: str, reduce: str,
                    edges: dict | None, n_groups: int,
                    event_chunk=None, plan_idx_of=None,
                    config_out: dict | None = None,
                    prefetch: int = DEFAULT_PREFETCH, shared_rows=None,
                    conf_of=None, radio=None):
    """Drive one replay over the device axis in fixed-size lane chunks:
    per-chunk inputs are generated on demand by ``make_inputs(lane_lo,
    m)`` (chunk-invariant counter-based samplers, so the chunking never
    changes a lane's inputs), the final partial chunk is padded to
    ``lane_chunk`` with inert masked lanes so every chunk reuses one
    compiled program.  Under
    ``reduce="stats"`` chunk partials merge associatively into one
    :class:`FleetStats` -- peak lane memory is the chunk, not the fleet.
    Under ``reduce="none"`` per-chunk outputs are concatenated
    (bit-identical to the unchunked streamed call; used as the
    differential oracle, not for scale).  With ``plan_idx_of`` the
    chunks run in Plan IR v2 mode: ``plan_rows`` is the stacked
    (P, S, ...) batch, ``n_rows`` the per-plan (P,) row counts, and
    ``plan_idx_of(lane_lo, m)`` each chunk's per-lane candidate index.
    ``shared_rows=False`` instead streams a *per-lane* row batch
    (``replay_plans``): ``plan_rows`` carries a leading lane axis that
    is sliced -- and zero-row padded -- chunk by chunk, and ``n_rows``
    is the per-lane ``(n_lanes,)`` real row counts.

    ``prefetch >= 1`` turns the synchronous loop into a two-stage
    overlapped pipeline (:data:`DEFAULT_PREFETCH`).  Stage 1 (producer
    thread): chunk k+1's sampler draws, inert-lane padding, stochastic
    trace post-processing (column pow2-padding + ``nominal_from``) and
    non-blocking device upload run while chunk k's replay is in flight,
    with a token semaphore bounding the pipeline to ``prefetch + 1``
    chunks alive at once.  Stage 2 (host): chunk k-1's outputs come back
    and fold into the running :class:`FleetStats` while chunk k replays,
    so the device always has the next chunk queued.  Chunk partials fold
    left in chunk order, the additions the sequential loop performs, so
    ``prefetch=0`` (exactly the legacy loop) is the bit-compat
    differential oracle for the pipeline; ``peak_lane_bytes`` reports
    the honest pipeline bound: ``(prefetch + 1)`` chunk buffers plus one
    stats partial.  The mesh and Pallas paths keep their own dispatch
    (``_run_replay``) and overlap stage 1 only.

    Each stage runs in a ``jax.profiler`` span tagged with its chunk
    index, inside one ``fleet.sweep`` span: ``fleet.sample``,
    ``fleet.prep``, ``fleet.queue_wait``, ``fleet.dispatch``,
    ``fleet.download`` and ``fleet.fold``; ``fleet.rows`` times each
    conversion and upload of a row table.  ``fleet.sweep`` and
    ``fleet.dispatch`` count their candidate plans (``plans``): the
    stacked set's, and those among a chunk's real lanes."""
    from jax.profiler import TraceAnnotation

    if lane_chunk < 1:
        raise ValueError(f"lane_chunk must be >= 1, got {lane_chunk}")
    if prefetch < 0:
        raise ValueError(f"prefetch must be >= 0, got {prefetch}")
    _validate_replay_knobs(policy, batch_rows, belief_alpha, backend,
                           reduce)
    if reduce == "stats" and edges is None:
        raise ValueError("reduce='stats' needs histogram edges")
    plan_mode = plan_idx_of is not None
    if shared_rows is None:
        shared_rows = "plan" if plan_mode else True
    per_lane_rows = shared_rows is False
    if per_lane_rows:
        n_rows = np.asarray(n_rows, np.int32)
    stats = reduce == "stats"
    starts = list(range(0, n_lanes, lane_chunk))

    def build(lo):
        """Pipeline stage 1a (host): one chunk's numpy inputs -- sampler
        draws, grouping, inert-lane padding."""
        i = lo // lane_chunk
        m = min(lane_chunk, n_lanes - lo)
        pad = lane_chunk - m if n_lanes > lane_chunk else 0
        with TraceAnnotation("fleet.sample", chunk=i):
            caps, rem0, tail, cum, ccum = make_inputs(lo, m)
            gid = np.asarray(group_id_of(lo, m), np.int32)
            cnf = (np.asarray(conf_of(lo, m), np.float64)
                   if conf_of is not None else None)
            pidx = (np.asarray(plan_idx_of(lo, m), np.int32)
                    if plan_mode else None)
        nr = rows_c = None
        if plan_mode:
            nr = np.asarray(n_rows, np.int32)[pidx]
        elif per_lane_rows:
            rows_c = {k: np.asarray(v)[lo:lo + m]
                      for k, v in plan_rows.items()}
            nr = n_rows[lo:lo + m]
        else:
            nr = np.full(m, n_rows, np.int32)
        if pad:
            # inert lanes: continuous power, and s_real=0 so the fused
            # event stream never walks them (a walked one would take a
            # BURN row's refill from its zero charge trace); valid=False
            # masks them out of every statistic.
            caps = np.concatenate([caps, np.full(pad, np.inf)])
            rem0 = np.concatenate([rem0, np.full(pad, np.inf)])
            tail = np.concatenate([tail, np.zeros(pad)])
            if cum is not None:
                cum = np.concatenate(
                    [cum, np.zeros((pad, cum.shape[1]))])
            if ccum is not None:
                ccum = np.concatenate(
                    [ccum, np.zeros((pad, ccum.shape[1]))])
            gid = np.concatenate([gid, np.zeros(pad, np.int32)])
            if cnf is not None:
                cnf = np.concatenate([cnf, np.zeros(pad)])
            if plan_mode:
                pidx = np.concatenate([pidx, np.zeros(pad, np.int32)])
            if nr is not None:
                nr = np.concatenate([nr, np.zeros(pad, np.int32)])
            if rows_c is not None:
                # zero rows: no-op WORK rows the replay completes for
                # free (and s_real=0 never walks them on the fused path)
                rows_c = {k: _pad_axis0(v, pad)
                          for k, v in rows_c.items()}
        valid = np.arange(m + pad) < m
        return dict(i=i, lo=lo, m=m, pad=pad, caps=caps, rem0=rem0,
                    tail=tail, cum=cum, ccum=ccum, gid=gid, pidx=pidx,
                    nr=nr, rows=rows_c, valid=valid, conf=cnf)

    def chunk_bytes(c):
        extra = (tuple(c["rows"].values()) + (c["nr"],)
                 if c["rows"] is not None else ())
        return _lane_io_bytes(c["m"] + c["pad"], c["caps"], c["rem0"],
                              c["tail"], c["cum"], c["ccum"], c["gid"],
                              c["valid"], c["pidx"], c["conf"], *extra)

    def run_chunk(c):
        """The legacy per-chunk dispatch (prefetch=0 and the mesh /
        Pallas pipeline): full host prep + blocking replay via
        ``_run_replay``."""
        return _run_replay(
            c["rows"] if per_lane_rows else plan_rows, c["caps"],
            c["rem0"], shared_rows=shared_rows, trace_cum=c["cum"],
            tail_s=c["tail"], policy=policy, theta=theta,
            batch_rows=batch_rows, belief_alpha=belief_alpha,
            charge_cum=c["ccum"], mesh=mesh, backend=backend,
            n_rows=c["nr"],
            chunk=event_chunk, reduce=reduce, group_id=c["gid"],
            valid=c["valid"], edges=edges, n_groups=n_groups,
            plan_idx=c["pidx"], conf=c["conf"], radio=radio,
            config_out=config_out, chunk_index=c["i"])

    n_plans = (int(np.shape(n_rows)[0]) if plan_mode
               else n_lanes if per_lane_rows else 1)
    with TraceAnnotation("fleet.sweep", lanes=n_lanes, chunks=len(starts),
                         plans=n_plans):
        if prefetch and len(starts) > 1:
            return _overlapped_replay(
                plan_rows, n_rows, lane_chunk, starts, build, chunk_bytes,
                run_chunk, shared_rows, policy, theta, batch_rows,
                belief_alpha, mesh, backend, reduce, edges, n_groups,
                event_chunk, config_out, prefetch, radio)
        # -- the legacy fully synchronous loop: generate, replay, fold,
        # repeat.  Kept verbatim as the bit-compat differential oracle
        # for the overlapped pipeline.
        acc_stats = None
        outs: list[dict] = []
        peak = 0
        for lo in starts:
            c = build(lo)
            peak = max(peak, chunk_bytes(c))
            res = run_chunk(c)
            if stats:
                with TraceAnnotation("fleet.fold", chunk=c["i"]):
                    acc_stats = res if acc_stats is None \
                        else acc_stats.merge(res)
            else:
                outs.append({k: v[:c["m"]] for k, v in res.items()})
        if stats:
            acc_stats.peak_lane_bytes = peak
            return acc_stats
        return {k: np.concatenate([o[k] for o in outs])
                for k in outs[0]}, peak


def _overlapped_replay(plan_rows: dict, n_rows, lane_chunk: int,
                       starts: list, build, chunk_bytes, run_chunk,
                       shared_rows, policy: str, theta: float,
                       batch_rows: int, belief_alpha: float, mesh,
                       backend: str, reduce: str, edges: dict | None,
                       n_groups: int, event_chunk,
                       config_out: dict | None, prefetch: int,
                       radio=None):
    """The ``prefetch >= 1`` body of :func:`_chunked_replay`: a bounded
    producer thread runs chunk generation + device upload ahead of the
    replay, and (on the unmeshed XLA path) the host folds each chunk
    while the next one replays.  See :func:`_chunked_replay` for the
    contract; results are bit-exact against ``prefetch=0``."""
    import queue as queue_mod
    import threading

    import jax
    from jax.profiler import TraceAnnotation

    from repro.kernels.charge_replay import (EVENT_CHUNK,
                                             default_event_chunk)

    from .fleetstats import partial_nbytes

    plan_mode = shared_rows == "plan"
    per_lane_rows = shared_rows is False
    stats = reduce == "stats"
    # The overlapped dispatch runs _run_replay's prep (the shared
    # _stochastic_prep) on the producer thread; mesh and Pallas keep
    # their own dispatch (stage-1 overlap only).
    fast = mesh is None and backend != "pallas"
    depth = prefetch + 1                    # chunks alive at once
    tokens = threading.Semaphore(depth)
    q: "queue_mod.Queue" = queue_mod.Queue()
    fail = threading.Event()
    done_sentinel = object()

    first = build(starts[0])
    prep = lambda c: c                      # noqa: E731 -- fallback path
    dispatch = None
    if fast:
        import jax.numpy as jnp

        from repro.runtime.radio import N_RADIO, radio_vector

        adaptive = policy == "adaptive"
        parametric = "tile_sel_cost" in plan_rows
        stochastic = (first["ccum"] is not None
                      or (adaptive and batch_rows > 1))
        xla_backend = "xla" if backend == "auto" else backend
        # Uplink operands are chunk-invariant: the packed radio vector is
        # hoisted and reused across every chunk's call.
        has_send = (radio is not None
                    and bool(np.any(np.asarray(plan_rows["kind"])
                                    == KIND_SEND)))
        radio_vec = (radio_vector(radio) if radio is not None
                     else np.zeros(N_RADIO, np.float64))
        lane_axis = ("plan" if plan_mode
                     else (False if shared_rows is True else True))
        s_axis = 0 if shared_rows is True else 1
        has_burn = False
        rows_h = plan_rows
        if stochastic:
            has_burn = bool(np.any(np.asarray(plan_rows["kind"])
                                   == KIND_BURN))
            if not per_lane_rows:
                # chunk-invariant: bucket + upload the row tables ONCE
                # instead of per chunk (what _run_replay redoes per call)
                rows_h = _bucket_rows(plan_rows, lane_axis=lane_axis)
        s_bucket = rows_h["kind"].shape[s_axis]
        if per_lane_rows and stochastic:
            s_bucket = _bucket_target(s_bucket)
        autotune = event_chunk == "auto"
        echunk = event_chunk
        if echunk is None or autotune:
            echunk = (default_event_chunk(s_bucket) if stochastic
                      else EVENT_CHUNK)
        # the chunk-invariant row table, uploaded once for each state a
        # chunk replays in
        jrows: dict = {}

        def rows_on_device(rows_c, state):
            if per_lane_rows:
                return _upload_rows(rows_c, state)
            if state not in jrows:
                jrows[state] = _upload_rows(rows_h, state)
            return jrows[state]

        with _x64():
            jtheta = jnp.asarray(float(theta), jnp.float64)
            jwindow = jnp.asarray(float(batch_rows), jnp.float64)
            jalpha = jnp.asarray(float(belief_alpha), jnp.float64)
            jradio = jnp.asarray(radio_vec)

        def prep(c):  # noqa: F811
            """Pipeline stage 1b (producer thread): stochastic trace
            post-processing + non-blocking device upload of one built
            chunk."""
            with TraceAnnotation("fleet.prep", chunk=c["i"]):
                L = c["m"] + c["pad"]
                caps, rem0, ccum = c["caps"], c["rem0"], c["ccum"]
                rows_c = c["rows"]
                nominal_from = np.zeros(L, np.float64)
                enable_fast = False
                state = np.dtype(np.float64)
                if stochastic:
                    if per_lane_rows:
                        rows_c = _bucket_rows(rows_c, lane_axis=True)
                    caps, rem0, ccum, nominal_from, enable_fast, state = \
                        _stochastic_prep(
                            rows_c if per_lane_rows else rows_h, caps,
                            rem0, ccum, lane_axis,
                            fused=xla_backend == "xla",
                            adaptive=adaptive, has_send=has_send,
                            belief_alpha=belief_alpha)
                cum = c["cum"]
                if cum is None:
                    cum = np.zeros((L, 1), np.float64)
                if ccum is None:
                    ccum = np.zeros((L, 1), np.float64)
                tail = np.broadcast_to(
                    np.asarray(c["tail"], np.float64), (L,))
                sr = np.asarray(c["nr"], np.int32)
                cnf = (np.zeros(L, np.float64) if c["conf"] is None
                       else np.asarray(c["conf"], np.float64))
                with _x64():
                    args = [rows_on_device(rows_c, state),
                            jnp.asarray(caps), jnp.asarray(rem0),
                            jnp.asarray(cum), jnp.asarray(tail),
                            jnp.asarray(ccum), jnp.asarray(nominal_from),
                            jnp.asarray(sr), jtheta, jwindow, jalpha,
                            jnp.asarray(cnf), jradio]
                    if plan_mode:
                        args.append(jnp.asarray(
                            np.asarray(c["pidx"], np.int32)))
                return c, enable_fast, args, cum, tail, state

        def dispatch(item, ec):  # noqa: F811
            c, enable_fast, args, _, _, state = item
            with TraceAnnotation("fleet.dispatch", chunk=c["i"],
                                 state=state.name,
                                 plans=_distinct_plans(shared_rows, c["pidx"],
                                                       c["valid"])):
                return _jit_replay(shared_rows, adaptive, parametric,
                                   stochastic, xla_backend, ec,
                                   enable_fast, has_burn, has_send)(*args)

    tokens.acquire()                        # the first chunk's slot
    item0 = prep(first)
    if fast and autotune and stochastic and xla_backend == "xla":
        with _x64():
            echunk = _autotune_event_chunk(
                (shared_rows, adaptive, parametric, stochastic,
                 xla_backend, item0[1], has_burn, has_send,
                 item0[2][0]["kind"].shape, lane_chunk, item0[5].name),
                s_bucket,
                lambda c: dispatch(item0, c))
    if fast and config_out is not None:
        config_out.update(
            shared_rows=shared_rows, adaptive=adaptive,
            parametric=parametric, stochastic=stochastic,
            backend=xla_backend, chunk=echunk,
            enable_fast=item0[1], has_burn=has_burn,
            has_send=has_send, state=item0[5].name)

    def producer():
        try:
            with _x64():
                for lo in starts[1:]:
                    tokens.acquire()
                    if fail.is_set():
                        return
                    q.put(prep(build(lo)))
            q.put(done_sentinel)
        except BaseException as e:          # relay to the consumer
            q.put(e)

    thread = threading.Thread(target=producer, name="fleetsim-prefetch",
                              daemon=True)
    thread.start()
    acc = None
    outs: list[dict] = []
    peak_chunk = 0

    def keep(c, res):
        """Fold one chunk's results: stats merge left in chunk order."""
        nonlocal acc
        if stats:
            with TraceAnnotation("fleet.fold", chunk=c["i"]):
                acc = res if acc is None else acc.merge(res)
        else:
            outs.append({k: v[:c["m"]] for k, v in res.items()})

    def finish(item, res):
        c, _, _, cum, tail, _ = item
        jax.block_until_ready(res)
        counts = _pop_event_counts(res, c["valid"], echunk, 1, c["i"],
                                   c["gid"][:c["m"]], n_groups)
        out = _lane_results(res, c["m"], cum, tail, has_send,
                            chunk_index=c["i"])
        if stats:
            with TraceAnnotation("fleet.fold", chunk=c["i"]):
                out = stats_from_outputs(out, edges, c["gid"][:c["m"]],
                                         n_groups)
            _set_event_counts(out, *counts)
        keep(c, out)

    def next_chunk(i):
        with TraceAnnotation("fleet.queue_wait", chunk=i):
            item = q.get()
        if isinstance(item, BaseException):
            raise item
        return item

    try:
        if fast:
            with _x64():
                held = None                 # (item, result) still replaying
                for i in range(len(starts)):
                    item = item0 if i == 0 else next_chunk(i)
                    peak_chunk = max(peak_chunk, chunk_bytes(item[0]))
                    res = dispatch(item, echunk)
                    if held is not None:
                        # fold the previous chunk while this one replays
                        finish(*held)
                        tokens.release()
                    held = (item, res)
                finish(*held)
                tokens.release()
        else:
            for i in range(len(starts)):
                c = item0 if i == 0 else next_chunk(i)
                peak_chunk = max(peak_chunk, chunk_bytes(c))
                keep(c, run_chunk(c))
                tokens.release()
    except BaseException:
        fail.set()
        for _ in range(depth):              # unblock a waiting producer
            tokens.release()
        raise
    thread.join()
    peak = (peak_chunk * min(depth, len(starts))
            + (partial_nbytes(edges, n_groups) if stats else 0))
    if stats:
        acc.peak_lane_bytes = peak
        return acc
    return {k: np.concatenate([o[k] for o in outs])
            for k in outs[0]}, peak


@dataclass
class ReplayOut:
    """Raw replay state for one (plan, device) lane."""
    live_cycles: float
    reboots: int
    by_class: dict
    completed: bool
    dead_s: float = 0.0
    wasted_cycles: float = 0.0   # committed-work rollback re-execution
    belief_cycles: float = 0.0   # final EWMA believed per-charge budget
    tx_bytes: float = 0.0        # uplink bytes shipped (decision 5)
    msgs_sent: int = 0           # uplink transmissions completed
    msgs_deferred: int = 0       # sends deferred past a closed window

    @property
    def tx_joules(self) -> float:
        """Radio energy: the ``radio`` op class in joules."""
        return self.by_class.get("radio", 0.0) * JOULES_PER_CYCLE


def replay_plans(plans: list[FleetPlan],
                 init_frac: np.ndarray | None = None,
                 policy: str = "fixed", theta: float = 0.5,
                 batch_rows: int = 1, belief_alpha: float = 0.0,
                 recharge_traces: np.ndarray | None = None,
                 charge_traces: np.ndarray | None = None,
                 backend: str = "auto", reduce: str = "none",
                 stats_bins: int = 64,
                 stats_edges: dict | None = None, seed: int | None = None,
                 recharge_cv: float = 0.25, trace_reboots: int = 0,
                 charge_cv: float = 0.0, charge_bias_cv: float = 0.0,
                 charge_reboots: int = 0, lane_lo: int = 0,
                 event_chunk=None, lane_chunk: int | None = None,
                 prefetch: int = DEFAULT_PREFETCH,
                 radio=None, conf: np.ndarray | None = None
                 ) -> list[ReplayOut] | FleetStats:
    """Replay many plans in one jitted vmap'd call (one lane per plan).

    ``init_frac`` optionally scales each lane's initial buffer charge
    (default 1.0: every device starts a full charge, like the scalar
    ``evaluate``); on the stochastic charge-wise path fractional initial
    charges are floored to whole cycles so the replay's energy state
    stays exact-integer.  ``backend``
    selects the replay implementation (``REPLAY_BACKENDS``; every backend
    is bit-identical, the knob trades compile/runtime shape).  ``recharge_traces`` is an optional ``(len(plans), R)``
    matrix of per-reboot recharge times; reboots beyond ``R`` fall back to
    each plan's mean ``recharge_s``.  ``charge_traces`` is an optional
    ``(len(plans), R)`` matrix of per-charge capacities (cycles delivered
    by each lane's successive refills; see
    ``runtime.failures.charge_capacity_jitter``) that switches the replay
    to the stochastic charge-by-charge path; charges beyond the trace
    deliver the nominal capacity.  ``policy``/``theta`` select the
    commit-granularity policy, ``batch_rows`` the cross-charge commit
    window (rows per cursor write under ``policy="adaptive"``), and
    ``belief_alpha`` the EWMA belief-recalibration rate (see the module
    docstring).

    Completion is the in-scan ``stuck`` flag: per-lane exact for
    parameterized plans (where the static ``max_atomic`` bound is sized
    with the continuously-calibrated tile and would falsely DNF lanes that
    select a smaller tile), and identical to the scalar simulator's
    ``max_atomic`` check for everything else.

    ``reduce="stats"`` folds the lanes into one :class:`FleetStats`
    (``REPLAY_REDUCES``) instead of materializing
    :class:`ReplayOut` rows; ``stats_bins``/``stats_edges`` size its
    fixed histogram bins (defaults derived from the plans' nominal
    bounds).

    ``seed=`` switches the explicit-trace path onto the Philox
    counter-based ``*_stream`` samplers (``runtime.failures``), closing
    the chunk-invariance gap that previously covered only fleet/capacitor
    sweeps: lane ``lane_lo + i`` draws the same initial charge fraction,
    harvest multiplier, recharge trace (``trace_reboots``) and capacity
    trace (``charge_cv``/``charge_bias_cv``/``charge_reboots``) whether
    the plan batch is replayed whole or split into sub-batches at
    arbitrary ``lane_lo`` offsets.  Explicitly-passed ``init_frac``/
    ``recharge_traces``/``charge_traces`` override the corresponding
    drawn inputs.  ``event_chunk`` overrides the plan-shape-derived
    event-stream chunk length (``kernels.charge_replay``).

    ``lane_chunk=`` streams the plan-lane axis through that many lanes
    at a time (the memory-flat path of :func:`fleet_sweep`, here with a
    *per-lane* row batch): explicit ``recharge_traces``/
    ``charge_traces`` matrices -- and the drawn ``seed=`` streams --
    are sliced per chunk, so the chunked replay is bit-exact against
    the unchunked call on the same inputs.  ``prefetch`` selects the
    overlapped pipeline depth (see :func:`_chunked_replay`;
    ``prefetch=0`` is the synchronous loop).

    ``radio=`` (a ``(RadioModel, SendPolicy)`` pair or packed vector,
    see ``runtime.radio``) turns on the decision-5 uplink: every plan is
    run through :func:`with_uplink`, and each lane's send decision uses
    ``conf`` (one classifier confidence per plan lane; drawn from the
    Philox confidence stream under ``seed=``, zeros otherwise)."""
    from repro.runtime.failures import (charge_capacity_jitter_stream,
                                        charge_trace_cumulative,
                                        harvest_jitter_stream,
                                        inference_confidence_stream,
                                        initial_charge_fraction_stream,
                                        reboot_recharge_times_stream,
                                        recharge_trace_cumulative)

    if radio is not None:
        plans = [with_uplink(p) for p in plans]
        if conf is None and seed is not None:
            conf = inference_confidence_stream(len(plans), seed=seed,
                                               lane_lo=lane_lo)
    if reduce not in REPLAY_REDUCES:
        raise ValueError(f"unknown reduce mode {reduce!r}; "
                         f"expected one of {REPLAY_REDUCES}")
    caps = np.asarray([p.capacity for p in plans], np.float64)
    tail = np.asarray([p.recharge_s for p in plans], np.float64)
    if seed is not None:
        n = len(plans)
        if init_frac is None:
            init_frac = initial_charge_fraction_stream(n, seed=seed,
                                                       lane_lo=lane_lo)
        jm = harvest_jitter_stream(n, seed=seed, cv=recharge_cv,
                                   lane_lo=lane_lo)
        if trace_reboots > 0 and recharge_traces is None:
            recharge_traces = reboot_recharge_times_stream(
                n, trace_reboots, tail, seed=seed,
                lane_lo=lane_lo) * jm[:, None]
        if (charge_cv > 0 or charge_bias_cv > 0 or charge_reboots > 0) \
                and charge_traces is None:
            charge_traces = charge_capacity_jitter_stream(
                n, charge_reboots or 256, caps, seed=seed, cv=charge_cv,
                bias_cv=charge_bias_cv, lane_lo=lane_lo)
        tail = tail * jm
    rem0 = caps if init_frac is None else \
        np.where(np.isinf(caps), np.inf, caps * np.asarray(init_frac))
    cum = ccum = None
    if recharge_traces is not None:
        recharge_traces = np.asarray(recharge_traces)
        if recharge_traces.ndim != 2 or \
                recharge_traces.shape[0] != len(plans):
            raise ValueError(
                f"recharge_traces must be (len(plans), R) = "
                f"({len(plans)}, R), got {recharge_traces.shape}")
        cum = recharge_trace_cumulative(recharge_traces)
    if charge_traces is not None:
        charge_traces = np.asarray(charge_traces)
        if charge_traces.ndim != 2 or \
                charge_traces.shape[0] != len(plans):
            raise ValueError(
                f"charge_traces must be (len(plans), R) = "
                f"({len(plans)}, R), got {charge_traces.shape}")
        ccum = charge_trace_cumulative(charge_traces)
    n_rows_arr = np.asarray([len(p) for p in plans], np.int32)
    t0 = time.perf_counter()
    edges = None
    if reduce == "stats":
        edges = stats_edges if stats_edges is not None else \
            default_stat_edges(
                max(p.total_cycles for p in plans),
                np.asarray([p.capacity for p in plans]),
                np.asarray([p.recharge_s for p in plans]), stats_bins)
    if lane_chunk is not None:
        # Stream the plan-lane axis: every per-lane input -- the
        # explicit/drawn trace matrices included -- is built once for
        # the full batch above and sliced per chunk, so chunked results
        # are bit-exact against the unchunked call on the same inputs.
        tail_f = np.broadcast_to(np.asarray(tail, np.float64),
                                 (len(plans),))

        def make_inputs(lo, m):
            return (caps[lo:lo + m], rem0[lo:lo + m], tail_f[lo:lo + m],
                    None if cum is None else cum[lo:lo + m],
                    None if ccum is None else ccum[lo:lo + m])

        conf_f = (None if conf is None
                  else np.broadcast_to(np.asarray(conf, np.float64),
                                       (len(plans),)))
        res = _chunked_replay(
            _pad_stack(plans), n_rows_arr, len(plans), lane_chunk,
            make_inputs, lambda lo, m: np.zeros(m, np.int32), policy,
            theta, batch_rows, belief_alpha, None, backend, reduce,
            edges, 1, event_chunk=event_chunk, shared_rows=False,
            prefetch=prefetch, radio=radio,
            conf_of=(None if conf_f is None
                     else (lambda lo, m: conf_f[lo:lo + m])))
        if reduce == "stats":
            res.wall_s = time.perf_counter() - t0
            return res
        out, _peak = res
    elif reduce == "stats":
        stats = _run_replay(_pad_stack(plans), caps, rem0,
                            shared_rows=False, trace_cum=cum, tail_s=tail,
                            policy=policy, theta=theta,
                            batch_rows=batch_rows,
                            belief_alpha=belief_alpha, charge_cum=ccum,
                            backend=backend, n_rows=n_rows_arr,
                            chunk=event_chunk, reduce="stats",
                            edges=edges, conf=conf, radio=radio)
        stats.wall_s = time.perf_counter() - t0
        stats.peak_lane_bytes = _lane_io_bytes(len(plans), caps, rem0,
                                               tail, cum, ccum)
        return stats
    else:
        out = _run_replay(_pad_stack(plans), caps, rem0,
                          shared_rows=False, trace_cum=cum, tail_s=tail,
                          policy=policy, theta=theta,
                          batch_rows=batch_rows,
                          belief_alpha=belief_alpha, charge_cum=ccum,
                          backend=backend, n_rows=n_rows_arr,
                          chunk=event_chunk, conf=conf, radio=radio)
    results = []
    for i, p in enumerate(plans):
        by_class = {op: float(v) for op, v in
                    zip(OP_CLASSES, out["classes"][i]) if v > 0.0}
        results.append(ReplayOut(
            float(out["live"][i]),
            int(round(float(out["reboots"][i]))),
            by_class, bool(~out["stuck"][i]),
            dead_s=float(out["dead"][i]),
            wasted_cycles=float(out["wasted"][i]),
            belief_cycles=float(out["belief"][i]),
            tx_bytes=float(out.get("tx_bytes", np.zeros(len(plans)))[i]),
            msgs_sent=int(round(float(
                out.get("msgs_sent", np.zeros(len(plans)))[i]))),
            msgs_deferred=int(round(float(
                out.get("msgs_deferred", np.zeros(len(plans)))[i])))))
    return results


# ==========================================================================
# Fig. 9 matrix + fleet sweeps
# ==========================================================================

def fleet_evaluate(net: SimNet, x: np.ndarray,
                   strategies=STRATEGIES,
                   powers=POWER_SYSTEMS,
                   policy: str = "fixed", theta: float = 0.5,
                   batch_rows: int = 1, belief_alpha: float = 0.0,
                   recharge_traces: np.ndarray | None = None,
                   charge_traces: np.ndarray | None = None,
                   backend: str = "auto") -> list[RunResult]:
    """The full strategy x power matrix as one vectorized replay.

    Returns :class:`RunResult` rows interchangeable with the scalar
    ``evaluate`` (outputs are bit-identical: both execute the same plan;
    ``tests/test_fleetsim.py`` asserts field-level equivalence).
    ``recharge_traces`` (one row per matrix cell, in strategy-major order)
    switches dead time to trace replay; ``charge_traces`` (same layout)
    switches charge capacities to stochastic trace replay; ``policy``/
    ``theta``/``batch_rows``/``belief_alpha`` select the commit-granularity
    policy and its cross-charge window / belief recalibration."""
    import dataclasses

    plans = []
    for strat in strategies:
        ref = _reference_run(net, x, strat)
        # Only TAILS plans depend on the power system (tile calibration);
        # the other strategies' rows are built once and restamped with each
        # power's capacity/recharge (the replay's per-lane inputs).
        base = None
        for power in powers:
            if strat == "tails" or base is None:
                base = build_plan(net, x, strat, power, ref=ref)
                plans.append(base)
            else:
                ps = make_power_system(power)
                plans.append(dataclasses.replace(
                    base, power=ps.name, recharge_s=ps.recharge_s,
                    capacity=math.inf if ps.continuous
                    else ps.cycles_per_charge))
    outs = replay_plans(plans, policy=policy, theta=theta,
                        batch_rows=batch_rows, belief_alpha=belief_alpha,
                        recharge_traces=recharge_traces,
                        charge_traces=charge_traces, backend=backend)
    results = []
    for p, o in zip(plans, outs):
        if not o.completed:
            results.append(RunResult(
                p.network, p.strategy, p.power, False, None, 0.0, 0.0,
                float("inf"), float("inf"), 0, p.max_atomic,
                dnf_reason=f"atomic region of {p.max_atomic:.0f} cycles "
                           f"exceeds the {p.capacity:.0f}-cycle buffer"))
            continue
        live_s = o.live_cycles / CLOCK_HZ
        results.append(RunResult(
            p.network, p.strategy, p.power, True, p.ref_output, live_s,
            o.dead_s, live_s + o.dead_s, o.live_cycles * JOULES_PER_CYCLE,
            o.reboots, p.max_atomic, by_class=o.by_class))
    return results


@dataclass
class FleetSweepResult:
    """Per-device outcomes of one plan replayed across a fleet."""
    strategy: str
    power: str
    n_devices: int
    completed: np.ndarray        # (D,) bool
    live_s: np.ndarray           # (D,)
    dead_s: np.ndarray           # (D,)
    reboots: np.ndarray          # (D,)
    energy_j: np.ndarray         # (D,)
    wall_s: float                # build + replay wall-clock
    wasted_cycles: np.ndarray | None = None   # (D,) rollback re-execution
    belief_cycles: np.ndarray | None = None   # (D,) final EWMA budget
    policy: str = "fixed"        # commit policy the sweep ran under
    theta: float = 0.5
    batch_rows: int = 1
    belief_alpha: float = 0.0
    tx_bytes: np.ndarray | None = None       # (D,) uplink bytes shipped
    msgs_sent: np.ndarray | None = None      # (D,)
    msgs_deferred: np.ndarray | None = None  # (D,) closed-window defers
    tx_joules: np.ndarray | None = None      # (D,) radio energy burned
    #: The replay's raw per-lane channels (exact live cycles, the per-class
    #: cycle matrix, ...): what ``stats_from_outputs`` and the reference
    #: interpreter are compared against.
    outputs: dict | None = None

    @property
    def total_s(self) -> np.ndarray:
        return self.live_s + self.dead_s

    def summary(self) -> dict:
        done = self.completed
        out = {
            "devices": self.n_devices,
            "policy": self.policy,
            "completed": int(done.sum()),
            "mean_total_s": float(self.total_s[done].mean()) if done.any()
            else float("inf"),
            "p95_total_s": float(np.percentile(self.total_s[done], 95))
            if done.any() else float("inf"),
            "mean_reboots": float(self.reboots[done].mean()) if done.any()
            else 0.0,
            "mean_wasted_cycles":
                float(self.wasted_cycles[done].mean())
                if self.wasted_cycles is not None and done.any() else 0.0,
            "mean_belief_cycles":
                float(self.belief_cycles[done].mean())
                if self.belief_cycles is not None and done.any() else 0.0,
            "wall_s": round(self.wall_s, 3),
        }
        if self.tx_bytes is not None:
            out["uplink"] = {
                "tx_bytes": float(self.tx_bytes.sum()),
                "msgs_sent": int(round(float(self.msgs_sent.sum()))),
                "msgs_deferred":
                    int(round(float(self.msgs_deferred.sum()))),
                "tx_joules": float(self.tx_joules.sum())
                if self.tx_joules is not None else 0.0,
            }
        return out


@dataclass
class DesignSweepResult:
    """Per-candidate, per-device outcomes of one PlanSet design sweep."""
    labels: tuple
    strategies: tuple
    capacities: np.ndarray       # (P,) cycles per full charge
    n_devices: int               # devices per candidate plan
    completed: np.ndarray        # (P, D) bool
    live_s: np.ndarray           # (P, D)
    dead_s: np.ndarray           # (P, D)
    reboots: np.ndarray          # (P, D)
    energy_j: np.ndarray         # (P, D)
    wasted_cycles: np.ndarray    # (P, D)
    belief_cycles: np.ndarray    # (P, D)
    wall_s: float
    replay_config: tuple = ()    # _jit_replay static key of the one jit
    policy: str = "fixed"
    tx_bytes: np.ndarray | None = None       # (P, D) uplink bytes shipped
    msgs_sent: np.ndarray | None = None      # (P, D)
    msgs_deferred: np.ndarray | None = None  # (P, D) closed-window defers

    @property
    def total_s(self) -> np.ndarray:
        return self.live_s + self.dead_s

    @property
    def completion_rate(self) -> np.ndarray:
        return self.completed.mean(axis=1)

    def summary(self) -> list[dict]:
        """One dict per candidate: completion, mean energy over completed
        lanes, p95 wall-clock latency -- the per-plan numbers GENESIS's
        frontier selection consumes."""
        rows = []
        for p, label in enumerate(self.labels):
            done = self.completed[p]
            rows.append({
                "label": label,
                "strategy": self.strategies[p],
                "capacity": float(self.capacities[p]),
                "completion": float(done.mean()),
                "mean_energy_j": float(self.energy_j[p][done].mean())
                if done.any() else float("inf"),
                "p95_total_s": float(np.percentile(self.total_s[p][done],
                                                   95))
                if done.any() else float("inf"),
                "mean_reboots": float(self.reboots[p][done].mean())
                if done.any() else 0.0,
            })
        return rows


def _design_result(ps: PlanSet, n_devices: int, out: dict, t0: float,
                   config_out: dict, policy: str) -> DesignSweepResult:
    shape = (len(ps), n_devices)
    cfg = ()
    if config_out:
        cfg = (config_out["shared_rows"], config_out["adaptive"],
               config_out["parametric"], config_out["stochastic"],
               config_out["backend"], config_out["chunk"],
               config_out["enable_fast"], config_out["has_burn"],
               config_out.get("has_send", False))
    uplink = {}
    if "tx_bytes" in out:
        uplink = dict(
            tx_bytes=np.asarray(out["tx_bytes"]).reshape(shape),
            msgs_sent=np.asarray(out["msgs_sent"]).reshape(shape),
            msgs_deferred=np.asarray(out["msgs_deferred"]).reshape(shape))
    return DesignSweepResult(
        labels=ps.labels, strategies=ps.strategies,
        capacities=ps.capacity, n_devices=n_devices,
        completed=(~out["stuck"]).reshape(shape),
        live_s=(out["live"] / CLOCK_HZ).reshape(shape),
        dead_s=out["dead"].reshape(shape),
        reboots=out["reboots"].reshape(shape),
        energy_j=(out["live"] * JOULES_PER_CYCLE).reshape(shape),
        wasted_cycles=out["wasted"].reshape(shape),
        belief_cycles=out["belief"].reshape(shape),
        wall_s=time.perf_counter() - t0,
        replay_config=cfg, policy=policy, **uplink)


def _design_sweep(ps: PlanSet, n_devices: int, seed: int,
                  recharge_cv: float, policy: str, theta: float,
                  batch_rows: int, belief_alpha: float,
                  trace_reboots: int, charge_cv: float,
                  charge_bias_cv: float, charge_reboots: int, mesh,
                  backend: str, reduce: str, lane_chunk: int | None,
                  stats_bins: int, stats_edges: dict | None,
                  event_chunk, t0: float,
                  prefetch: int = DEFAULT_PREFETCH, radio=None,
                  conf=None):
    """One compiled replay over a whole :class:`PlanSet` design space.

    Lanes are plan-major (``lane = p * n_devices + d``).  Unchunked, each
    plan's ``n_devices`` lanes draw with the same legacy samplers and
    seeds an individual ``fleet_sweep(plan=plans[p])`` call uses, so
    per-plan outputs are bit-exact against replaying each candidate
    separately.  With ``lane_chunk`` the flat lane axis streams through
    the chunk-invariant ``*_stream`` samplers instead (chunking-
    independent, but a different draw stream).  Design sweeps always
    replay charge-wise -- an all-nominal capacity trace when the jitter
    knobs are off -- because the fused event stream is the path that
    indexes the packed (P, S, F) candidate tensor in place instead of
    materializing a per-lane gather of the stacked row tables."""
    from repro.runtime.failures import (charge_capacity_jitter,
                                        charge_capacity_jitter_stream,
                                        charge_trace_cumulative,
                                        harvest_jitter,
                                        harvest_jitter_stream,
                                        inference_confidence,
                                        inference_confidence_stream,
                                        initial_charge_fraction,
                                        initial_charge_fraction_stream,
                                        reboot_recharge_times,
                                        reboot_recharge_times_stream,
                                        recharge_trace_cumulative)

    n_plans, dev = len(ps), n_devices
    lanes = n_plans * dev
    use_charge = charge_cv > 0 or charge_bias_cv > 0 or charge_reboots > 0
    n_charges = charge_reboots or (256 if use_charge else 8)
    edges = None
    if reduce == "stats":
        edges = stats_edges if stats_edges is not None else \
            default_stat_edges(float(ps.total_cycles.max()), ps.capacity,
                               ps.recharge_s, stats_bins)
    config_out: dict = {}
    if lane_chunk is not None:
        def plan_of(lo, m):
            return (lo + np.arange(m)) // dev

        def make_inputs(lo, m):
            p = plan_of(lo, m)
            caps_c = ps.capacity[p]
            frac = initial_charge_fraction_stream(m, seed=seed,
                                                  lane_lo=lo)
            jm = harvest_jitter_stream(m, seed=seed, cv=recharge_cv,
                                       lane_lo=lo)
            rem0_c = np.where(np.isinf(caps_c), np.inf, caps_c * frac)
            tail_c = ps.recharge_s[p] * jm
            cum_c = None
            if trace_reboots > 0:
                tr = reboot_recharge_times_stream(
                    m, trace_reboots, ps.recharge_s[p], seed=seed,
                    lane_lo=lo)
                cum_c = recharge_trace_cumulative(tr * jm[:, None])
            ctr = charge_capacity_jitter_stream(
                m, n_charges, caps_c, seed=seed, cv=charge_cv,
                bias_cv=charge_bias_cv, lane_lo=lo)
            ccum_c = charge_trace_cumulative(ctr)
            return caps_c, rem0_c, tail_c, cum_c, ccum_c

        conf_of = None
        if conf is not None:
            conf_full = np.asarray(conf, np.float64)

            def conf_of(lo, m):
                return conf_full[lo:lo + m]
        elif radio is not None:
            def conf_of(lo, m):
                return inference_confidence_stream(m, seed=seed,
                                                   lane_lo=lo)

        res = _chunked_replay(
            ps.rows, ps.n_rows, lanes, lane_chunk, make_inputs, plan_of,
            policy, theta, batch_rows, belief_alpha, mesh, backend,
            reduce, edges, n_plans, event_chunk=event_chunk,
            plan_idx_of=plan_of, config_out=config_out,
            prefetch=prefetch, conf_of=conf_of, radio=radio)
        if reduce == "stats":
            res.group_labels = np.asarray(ps.labels)
            res.wall_s = time.perf_counter() - t0
            return res
        out, _peak = res
        return _design_result(ps, dev, out, t0, config_out, policy)
    pidx = np.repeat(np.arange(n_plans, dtype=np.int32), dev)
    caps = ps.capacity[pidx]
    # Per-plan legacy draws with per-plan seeds: the bit-exactness pin.
    frac = np.tile(initial_charge_fraction(dev, seed=seed), n_plans)
    jm = np.tile(harvest_jitter(dev, seed=seed + 1, cv=recharge_cv),
                 n_plans)
    rem0 = np.where(np.isinf(caps), np.inf, caps * frac)
    tail = ps.recharge_s[pidx] * jm
    cum = None
    if trace_reboots > 0:
        jm_d = jm[:dev]
        cum = recharge_trace_cumulative(np.concatenate(
            [reboot_recharge_times(dev, trace_reboots,
                                   float(ps.recharge_s[p]),
                                   seed=seed + 2) * jm_d[:, None]
             for p in range(n_plans)]))
    ccum = charge_trace_cumulative(np.concatenate(
        [charge_capacity_jitter(dev, n_charges, float(ps.capacity[p]),
                                seed=seed + 3, cv=charge_cv,
                                bias_cv=charge_bias_cv)
         for p in range(n_plans)]))
    if radio is not None and conf is None:
        # Per-plan legacy confidence draws, matching what each candidate
        # would see in a standalone fleet_sweep(plan=plans[p]) replay.
        conf = np.tile(inference_confidence(dev, seed=seed + 4), n_plans)
    common = dict(trace_cum=cum, tail_s=tail, policy=policy, theta=theta,
                  batch_rows=batch_rows, belief_alpha=belief_alpha,
                  charge_cum=ccum, mesh=mesh, backend=backend,
                  n_rows=ps.n_rows[pidx], chunk=event_chunk,
                  plan_idx=pidx, config_out=config_out,
                  conf=conf, radio=radio)
    if reduce == "stats":
        stats = _run_replay(ps.rows, caps, rem0, "plan", reduce="stats",
                            group_id=pidx, edges=edges, n_groups=n_plans,
                            **common)
        stats.group_labels = np.asarray(ps.labels)
        stats.wall_s = time.perf_counter() - t0
        stats.peak_lane_bytes = _lane_io_bytes(lanes, caps, rem0, tail,
                                               cum, ccum, pidx)
        return stats
    out = _run_replay(ps.rows, caps, rem0, "plan", **common)
    return _design_result(ps, dev, out, t0, config_out, policy)


def fleet_sweep(net: SimNet | None = None, x: np.ndarray | None = None,
                strategy: str | None = None, power=None,
                n_devices: int = 1000, seed: int = 0,
                recharge_cv: float = 0.25,
                plan: "FleetPlan | PlanSet | None" = None,
                policy: str = "fixed", theta: float = 0.5,
                batch_rows: int = 1, belief_alpha: float = 0.0,
                trace_reboots: int = 0, charge_cv: float = 0.0,
                charge_bias_cv: float = 0.0,
                charge_reboots: int = 0, mesh=None,
                backend: str = "auto", reduce: str = "none",
                lane_chunk: int | None = None, stats_bins: int = 64,
                stats_edges: dict | None = None,
                event_chunk=None, prefetch: int = DEFAULT_PREFETCH,
                radio=None, conf=None,
                ) -> "FleetSweepResult | DesignSweepResult | FleetStats":
    """Replay one (strategy, power) plan across ``n_devices`` simulated
    devices with per-device harvest-trace jitter, in one compiled pass.

    Each device wakes at a random buffer level and refills at its own
    harvest rate (lognormal recharge multiplier; the distributions live in
    ``repro.runtime.failures`` alongside the fleet failure traces).  With
    ``trace_reboots > 0`` each device additionally draws that many
    per-reboot recharge times (exponential around its mean) and the scan
    replays them reboot by reboot; beyond the trace it falls back to the
    device's mean.  With ``charge_cv > 0`` (or ``charge_reboots > 0``)
    each device draws a per-charge *capacity* trace
    (``charge_capacity_jitter``, truncated lognormal around the nominal
    budget, ``charge_reboots`` charges -- default 256) and the scan
    replays charges one by one, so surprise-short charges can tear batched
    commits (the ``wasted_cycles`` channel).  ``charge_bias_cv > 0``
    additionally gives each device a *persistent* capacity bias (a fixed
    lognormal multiplier on all of its charges -- a lane parked in a poor
    RF spot), the regime where EWMA belief recalibration
    (``belief_alpha > 0``) pays: the lane learns its own budget instead of
    planning against the fleet-nominal one.  ``policy="adaptive"`` turns
    on energy-adaptive commit batching, ``batch_rows`` stretches one
    cursor commit across up to that many rows per charge (multi-row
    rollback), ``mesh`` (e.g. ``repro.launch.mesh.make_fleet_mesh()``)
    shards the device axis across chips.  The plan is broadcast across
    device lanes, so memory scales with plan size + fleet size, not their
    product.

    ``reduce="stats"`` replaces the per-lane result arrays with one
    fixed-size :class:`FleetStats` (``REPLAY_REDUCES``), and
    ``lane_chunk=`` additionally streams the device axis through that
    many lanes at a time -- per-chunk inputs
    come from the chunk-invariant ``*_stream`` samplers in
    ``runtime.failures`` (so results do not depend on the chunking, but
    differ bitwise from the legacy unchunked draw stream), chunk partials
    merge associatively, and peak device-axis memory is a function of
    ``lane_chunk`` alone (``FleetStats.peak_lane_bytes`` records it) --
    this is the 1e7-device memory-flat path.  ``stats_bins``/
    ``stats_edges`` size the fixed histogram bins.

    ``plan=`` also accepts a :class:`PlanSet` (Plan IR v2): the whole
    stacked candidate batch replays with ``n_devices`` jittered lanes per
    candidate under ONE compiled scan, returning a
    :class:`DesignSweepResult` (``reduce="none"``) or a
    :class:`FleetStats` with one group per candidate
    (``reduce="stats"``); ``net``/``x``/``strategy``/``power`` are then
    unused.  ``event_chunk`` overrides the plan-shape-derived
    event-stream chunk length (``kernels.charge_replay``).

    ``radio=`` (a ``(RadioModel, SendPolicy)`` pair or a packed
    :func:`runtime.radio.pack_radio` vector) switches on the uplink
    decision: a :class:`FleetPlan` gets a SEND row appended
    (:func:`with_uplink`; a :class:`PlanSet` must carry its own SEND
    rows, applied per candidate before stacking) and each device draws a
    classifier confidence (``conf=`` overrides; default: the legacy
    ``inference_confidence`` draw at ``seed + 4`` unchunked, the
    chunk-invariant ``*_stream`` draw under ``lane_chunk``) that the
    in-scan send policy thresholds into ship-class / ship-topk / skip.
    Results then carry the ``tx_bytes`` / ``msgs_sent`` /
    ``msgs_deferred`` uplink channels.
    """
    from repro.runtime.failures import (charge_capacity_jitter,
                                        charge_capacity_jitter_stream,
                                        charge_trace_cumulative,
                                        harvest_jitter,
                                        harvest_jitter_stream,
                                        inference_confidence,
                                        inference_confidence_stream,
                                        initial_charge_fraction,
                                        initial_charge_fraction_stream,
                                        reboot_recharge_times,
                                        reboot_recharge_times_stream,
                                        recharge_trace_cumulative)

    if reduce not in REPLAY_REDUCES:
        raise ValueError(f"unknown reduce mode {reduce!r}; "
                         f"expected one of {REPLAY_REDUCES}")
    t0 = time.perf_counter()
    if isinstance(plan, PlanSet):
        return _design_sweep(plan, n_devices, seed, recharge_cv, policy,
                             theta, batch_rows, belief_alpha,
                             trace_reboots, charge_cv, charge_bias_cv,
                             charge_reboots, mesh, backend, reduce,
                             lane_chunk, stats_bins, stats_edges,
                             event_chunk, t0, prefetch, radio=radio,
                             conf=conf)
    if plan is None:
        if net is None or x is None or strategy is None or power is None:
            raise ValueError("fleet_sweep needs (net, x, strategy, power) "
                             "to build a plan, or an explicit plan= "
                             "FleetPlan / PlanSet")
        plan = build_plan(net, x, strategy, power)
    if radio is not None:
        plan = with_uplink(plan)
    if strategy is None:
        strategy = plan.strategy
    if power is None:
        power = plan.power
    use_charge = charge_cv > 0 or charge_bias_cv > 0 or charge_reboots > 0
    edges = None
    if reduce == "stats":
        edges = stats_edges if stats_edges is not None else \
            default_stat_edges(plan.total_cycles, plan.capacity,
                               plan.recharge_s, stats_bins)
    if lane_chunk is not None:
        def make_inputs(lo, m):
            frac = initial_charge_fraction_stream(m, seed=seed,
                                                  lane_lo=lo)
            jm = harvest_jitter_stream(m, seed=seed, cv=recharge_cv,
                                       lane_lo=lo)
            caps_c = np.full(m, plan.capacity, np.float64)
            rem0_c = np.where(np.isinf(caps_c), np.inf, caps_c * frac)
            tail_c = plan.recharge_s * jm
            cum_c = ccum_c = None
            if trace_reboots > 0:
                tr = reboot_recharge_times_stream(
                    m, trace_reboots, plan.recharge_s, seed=seed,
                    lane_lo=lo)
                cum_c = recharge_trace_cumulative(tr * jm[:, None])
            if use_charge:
                ctr = charge_capacity_jitter_stream(
                    m, charge_reboots or 256, plan.capacity, seed=seed,
                    cv=charge_cv, bias_cv=charge_bias_cv, lane_lo=lo)
                ccum_c = charge_trace_cumulative(ctr)
            return caps_c, rem0_c, tail_c, cum_c, ccum_c

        conf_of = None
        if conf is not None:
            conf_full = np.asarray(conf, np.float64)

            def conf_of(lo, m):
                return conf_full[lo:lo + m]
        elif radio is not None:
            def conf_of(lo, m):
                return inference_confidence_stream(m, seed=seed,
                                                   lane_lo=lo)

        res = _chunked_replay(
            _plan_rows(plan), len(plan), n_devices, lane_chunk,
            make_inputs, lambda lo, m: np.zeros(m, np.int32), policy,
            theta, batch_rows, belief_alpha, mesh, backend, reduce,
            edges, 1, event_chunk=event_chunk, prefetch=prefetch,
            conf_of=conf_of, radio=radio)
        if reduce == "stats":
            res.wall_s = time.perf_counter() - t0
            return res
        out, _peak = res
        return FleetSweepResult(
            strategy, power, n_devices,
            completed=~out["stuck"],
            live_s=out["live"] / CLOCK_HZ,
            dead_s=out["dead"],
            reboots=out["reboots"],
            energy_j=out["live"] * JOULES_PER_CYCLE,
            wall_s=time.perf_counter() - t0,
            wasted_cycles=out["wasted"],
            belief_cycles=out["belief"],
            policy=policy, theta=theta, batch_rows=batch_rows,
            belief_alpha=belief_alpha,
            tx_bytes=out.get("tx_bytes"),
            msgs_sent=out.get("msgs_sent"),
            msgs_deferred=out.get("msgs_deferred"),
            tx_joules=out["classes"][..., _RADIO_IDX] * JOULES_PER_CYCLE
            if "classes" in out else None,
            outputs=out)
    frac = initial_charge_fraction(n_devices, seed=seed)
    jit_mult = harvest_jitter(n_devices, seed=seed + 1, cv=recharge_cv)
    caps = np.full(n_devices, plan.capacity, np.float64)
    rem0 = np.where(np.isinf(caps), np.inf, caps * frac)
    tail = plan.recharge_s * jit_mult
    cum = ccum = None
    if trace_reboots > 0:
        traces = reboot_recharge_times(n_devices, trace_reboots,
                                       plan.recharge_s, seed=seed + 2)
        cum = recharge_trace_cumulative(traces * jit_mult[:, None])
    if use_charge:
        ctr = charge_capacity_jitter(n_devices, charge_reboots or 256,
                                     plan.capacity, seed=seed + 3,
                                     cv=charge_cv, bias_cv=charge_bias_cv)
        ccum = charge_trace_cumulative(ctr)
    if radio is not None and conf is None:
        conf = inference_confidence(n_devices, seed=seed + 4)
    if reduce == "stats":
        # Unchunked stats: same legacy input draws as reduce="none", so
        # the reduction is bit-exactly comparable to statistics computed
        # from the materialized outputs (the differential oracle).
        stats = _run_replay(_plan_rows(plan), caps, rem0,
                            shared_rows=True, trace_cum=cum, tail_s=tail,
                            policy=policy, theta=theta,
                            batch_rows=batch_rows,
                            belief_alpha=belief_alpha, charge_cum=ccum,
                            mesh=mesh, backend=backend, n_rows=len(plan),
                            chunk=event_chunk, reduce="stats",
                            edges=edges, conf=conf, radio=radio)
        stats.wall_s = time.perf_counter() - t0
        stats.peak_lane_bytes = _lane_io_bytes(n_devices, caps, rem0,
                                               tail, cum, ccum)
        return stats
    out = _run_replay(_plan_rows(plan), caps, rem0, shared_rows=True,
                      trace_cum=cum, tail_s=tail, policy=policy,
                      theta=theta, batch_rows=batch_rows,
                      belief_alpha=belief_alpha, charge_cum=ccum,
                      mesh=mesh, backend=backend, n_rows=len(plan),
                      chunk=event_chunk, conf=conf, radio=radio)
    return FleetSweepResult(
        strategy, power, n_devices,
        completed=~out["stuck"],
        live_s=out["live"] / CLOCK_HZ,
        dead_s=out["dead"],
        reboots=out["reboots"],
        energy_j=out["live"] * JOULES_PER_CYCLE,
        wall_s=time.perf_counter() - t0,
        wasted_cycles=out["wasted"],
        belief_cycles=out["belief"],
        policy=policy, theta=theta, batch_rows=batch_rows,
        belief_alpha=belief_alpha,
        tx_bytes=out.get("tx_bytes"),
        msgs_sent=out.get("msgs_sent"),
        msgs_deferred=out.get("msgs_deferred"),
        tx_joules=out["classes"][..., _RADIO_IDX] * JOULES_PER_CYCLE
        if "classes" in out else None,
        outputs=out)


@dataclass
class CapacitorSweepResult:
    """One parameterized plan replayed over a (capacitors x devices) grid."""
    strategy: str
    capacities: np.ndarray       # (P,) cycles per charge
    n_devices: int               # devices per capacitor
    completed: np.ndarray        # (P, D) bool
    live_s: np.ndarray           # (P, D)
    dead_s: np.ndarray           # (P, D)
    reboots: np.ndarray          # (P, D)
    energy_j: np.ndarray         # (P, D)
    wall_s: float
    wasted_cycles: np.ndarray | None = None   # (P, D)
    belief_cycles: np.ndarray | None = None   # (P, D) final EWMA budget
    policy: str = "fixed"
    theta: float = 0.5
    batch_rows: int = 1
    belief_alpha: float = 0.0

    @property
    def total_s(self) -> np.ndarray:
        return self.live_s + self.dead_s


def capacitor_sweep(net: SimNet, x: np.ndarray,
                    capacities, n_devices: int = 64, seed: int = 0,
                    recharge_cv: float = 0.25, strategy: str = "tails",
                    plan: FleetPlan | None = None, policy: str = "fixed",
                    theta: float = 0.5, batch_rows: int = 1,
                    belief_alpha: float = 0.0, charge_cv: float = 0.0,
                    charge_bias_cv: float = 0.0, charge_reboots: int = 0,
                    mesh=None, backend: str = "auto",
                    reduce: str = "none", lane_chunk: int | None = None,
                    stats_bins: int = 64, stats_edges: dict | None = None,
                    event_chunk=None,
                    prefetch: int = DEFAULT_PREFETCH
                    ) -> CapacitorSweepResult | FleetStats:
    """Sweep (capacitor size x device) in ONE vmapped/sharded replay of ONE
    parameterized plan -- no per-capacitor re-extraction.

    ``capacities`` are buffer sizes in cycles per charge; each gets
    ``n_devices`` jittered lanes.  TAILS tile calibration happens inside the
    scan per lane, so every capacitor picks its own tile (and pays its own
    discovery burns) from the shared plan; completion comes from the
    in-scan ``stuck`` flag, which respects the selected tile (the static
    ``max_atomic`` bound is sized with the continuously-calibrated tile and
    would falsely DNF small-capacitor lanes).  ``charge_cv``/
    ``charge_reboots`` switch on stochastic per-charge capacities (see
    :func:`fleet_sweep`), jittered around each lane's own nominal budget.

    ``reduce="stats"`` folds the grid into one :class:`FleetStats` with
    one statistics *group per capacitor* (``group_labels`` holds the
    capacities), and ``lane_chunk=`` streams the flat
    (capacitor-major) lane axis through that many lanes at a time with
    chunk-invariant samplers -- see :func:`fleet_sweep` for the
    memory-flat semantics.
    """
    from repro.runtime.failures import (charge_capacity_jitter,
                                        charge_capacity_jitter_stream,
                                        charge_trace_cumulative,
                                        harvest_jitter,
                                        harvest_jitter_stream,
                                        initial_charge_fraction,
                                        initial_charge_fraction_stream)

    if reduce not in REPLAY_REDUCES:
        raise ValueError(f"unknown reduce mode {reduce!r}; "
                         f"expected one of {REPLAY_REDUCES}")
    t0 = time.perf_counter()
    if plan is None:
        plan = build_plan(net, x, strategy, "1mF", parametric=True)
    if not plan.parametric:
        raise ValueError("capacitor_sweep needs a parametric plan "
                         "(build_plan(..., parametric=True))")
    capacities = np.asarray(capacities, np.float64)
    n_caps = capacities.shape[0]
    lanes = n_caps * n_devices
    use_charge = charge_cv > 0 or charge_bias_cv > 0 or charge_reboots > 0
    edges = None
    if reduce == "stats":
        fin = capacities[np.isfinite(capacities)]
        rec = (rf_recharge_seconds(fin) if fin.size
               else np.zeros(1))
        edges = stats_edges if stats_edges is not None else \
            default_stat_edges(plan.total_cycles, capacities, rec,
                               stats_bins)
    if lane_chunk is not None:
        def make_inputs(lo, m):
            caps_c = capacities[
                (lo + np.arange(m)) // n_devices]
            frac = initial_charge_fraction_stream(m, seed=seed,
                                                  lane_lo=lo)
            jm = harvest_jitter_stream(m, seed=seed, cv=recharge_cv,
                                       lane_lo=lo)
            rem0_c = np.where(np.isinf(caps_c), np.inf, caps_c * frac)
            tail_c = np.where(np.isinf(caps_c), 0.0,
                              rf_recharge_seconds(caps_c) * jm)
            ccum_c = None
            if use_charge:
                ctr = charge_capacity_jitter_stream(
                    m, charge_reboots or 256, caps_c, seed=seed,
                    cv=charge_cv, bias_cv=charge_bias_cv, lane_lo=lo)
                ccum_c = charge_trace_cumulative(ctr)
            return caps_c, rem0_c, tail_c, None, ccum_c

        res = _chunked_replay(
            _plan_rows(plan), len(plan), lanes, lane_chunk, make_inputs,
            lambda lo, m: (lo + np.arange(m)) // n_devices, policy,
            theta, batch_rows, belief_alpha, mesh, backend, reduce,
            edges, n_caps, event_chunk=event_chunk, prefetch=prefetch)
        if reduce == "stats":
            res.group_labels = capacities
            res.wall_s = time.perf_counter() - t0
            return res
        out, _peak = res
        shape = (n_caps, n_devices)
        return CapacitorSweepResult(
            strategy, capacities, n_devices,
            completed=(~out["stuck"]).reshape(shape),
            live_s=(out["live"] / CLOCK_HZ).reshape(shape),
            dead_s=out["dead"].reshape(shape),
            reboots=out["reboots"].reshape(shape),
            energy_j=(out["live"] * JOULES_PER_CYCLE).reshape(shape),
            wall_s=time.perf_counter() - t0,
            wasted_cycles=out["wasted"].reshape(shape),
            belief_cycles=out["belief"].reshape(shape),
            policy=policy, theta=theta, batch_rows=batch_rows,
            belief_alpha=belief_alpha)
    caps = np.repeat(capacities, n_devices)
    frac = initial_charge_fraction(lanes, seed=seed)
    jit_mult = harvest_jitter(lanes, seed=seed + 1, cv=recharge_cv)
    rem0 = np.where(np.isinf(caps), np.inf, caps * frac)
    tail = np.where(np.isinf(caps), 0.0, rf_recharge_seconds(caps) * jit_mult)
    ccum = None
    if use_charge:
        ctr = charge_capacity_jitter(lanes, charge_reboots or 256, caps,
                                     seed=seed + 3, cv=charge_cv,
                                     bias_cv=charge_bias_cv)
        ccum = charge_trace_cumulative(ctr)
    if reduce == "stats":
        gid = np.repeat(np.arange(n_caps, dtype=np.int32), n_devices)
        stats = _run_replay(_plan_rows(plan), caps, rem0,
                            shared_rows=True, tail_s=tail, policy=policy,
                            theta=theta, batch_rows=batch_rows,
                            belief_alpha=belief_alpha, charge_cum=ccum,
                            mesh=mesh, backend=backend, n_rows=len(plan),
                            chunk=event_chunk, reduce="stats",
                            group_id=gid, edges=edges,
                            n_groups=n_caps)
        stats.group_labels = np.asarray(capacities)
        stats.wall_s = time.perf_counter() - t0
        stats.peak_lane_bytes = _lane_io_bytes(lanes, caps, rem0, tail,
                                               ccum)
        return stats
    out = _run_replay(_plan_rows(plan), caps, rem0, shared_rows=True,
                      tail_s=tail, policy=policy, theta=theta,
                      batch_rows=batch_rows, belief_alpha=belief_alpha,
                      charge_cum=ccum, mesh=mesh, backend=backend,
                      n_rows=len(plan), chunk=event_chunk)
    shape = (n_caps, n_devices)
    return CapacitorSweepResult(
        strategy, capacities, n_devices,
        completed=(~out["stuck"]).reshape(shape),
        live_s=(out["live"] / CLOCK_HZ).reshape(shape),
        dead_s=out["dead"].reshape(shape),
        reboots=out["reboots"].reshape(shape),
        energy_j=(out["live"] * JOULES_PER_CYCLE).reshape(shape),
        wall_s=time.perf_counter() - t0,
        wasted_cycles=out["wasted"].reshape(shape),
        belief_cycles=out["belief"].reshape(shape),
        policy=policy, theta=theta, batch_rows=batch_rows,
        belief_alpha=belief_alpha)
