"""Fused constant-trip replay of the stochastic charge loop.

The stochastic energy model (``repro.core.fleetsim`` decision 4) originally
replayed each plan row with a data-dependent ``lax.while_loop`` -- one trip
per charge -- nested inside the row scan.  That shape is hostile to XLA:
every (plan length, charge count) pair is its own program, nothing is
shared between strategies, and the schema-3 bench lost ~30% of the fleet
axis' throughput to it.  This module restructures the loop into a single
flat *event* stream with a constant trip count:

* ``charge_once``    -- exactly one charge of one row (the old loop body,
  verbatim: rollback debt replay, batch/defer decision, row phase, EWMA
  belief update).
* ``fast_forward``   -- the closed-form remainder of a row when every
  future refill is nominal: the deterministic path's chunk/retry algebra,
  generalized from "fresh row" to "``left`` iterations remaining".  All
  energy quantities are integral (capacities are whole cycles and
  ``_run_replay`` floors the initial charge), so the grouped arithmetic is
  exact-integer and bit-identical to running the charges one by one.
* ``event_step``     -- one event: gather the lane's current row, take one
  charge *or* fast-forward the whole row when eligible, then apply the
  BURN/CALIB overrides and the per-row dead-time gather on row advance.
* ``event_replay``   -- drives ``event_step`` to completion with a bounded
  ``lax.scan`` (a plan-shape-derived chunk of events per trip; see
  :func:`default_event_chunk`) under an outer ``lax.while_loop`` on the
  lane's real row cursor.  With a stacked ``(P, S, F)`` pack and a
  per-lane plan index (Plan IR v2), the same loop replays a whole
  candidate design space from one broadcast row table.

Masking scheme
--------------
Trip counts must be static, but lanes finish at different event counts, so
every event is *masked* rather than counted: a lane whose row cursor ``i``
has reached its real row count ``s_real`` keeps its entire event state
bitwise unchanged (``tree_map(where(active, new, old))`` -- not arithmetic
no-ops, a literal select of the old state), and the chunked outer loop
stops only when every vmapped lane is done (JAX's batched ``while_loop``
applies the same per-lane select at chunk granularity).  Plan rows are
padded to shape buckets by the caller; padding rows are all-zero WORK rows,
which both execution paths complete for free without touching any output
channel, and the ``i >= s_real`` mask stops the cursor before them anyway.
The fast path is itself a masked event: eligibility (all remaining refills
nominal, belief exact, no pending window/debt, nothing the closed form
cannot express) selects between ``fast_forward`` and ``charge_once``
per event, so a lane crosses from traced charges to the closed form
mid-row without a control-flow boundary.

State type
----------
The body is generic over the state's dtype, which follows ``rem0``.
float64 serves every replay.  Where the arithmetic is provably integral
(fixed policy, no EWMA belief, no SEND rows, whole capacities, charges,
trace entries and row fields: ``fleetsim._stochastic_prep`` decides), the
dispatch passes int64 instead: exact to 2**63 where a TPU's emulated
float64 keeps about 48 bits, and cheaper there, as 64-bit integer adds
and compares take a few 32-bit ops.  Division is :func:`floor_div`, and
the carries that cannot move in that state (rollback debt, the pending
window, the EWMA belief, the charge length, the device-side dead time)
are ``None`` rather than carried.

The Pallas kernel (``pallas_replay``) runs the same ``event_replay`` body
one lane per grid step (scalar state in registers, the plan broadcast to
every program).  It runs only in interpret mode, which is how the
differential harness pins it against the XLA path: its state is float64,
and Mosaic, the TPU Pallas compiler, has no float64 tiling, so compiling
it for a TPU raises (:func:`pallas_replay`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.fleetsim import (KIND_BURN, KIND_CALIB, KIND_SEND,
                                 KIND_WORK, _BURN_IDX, _CONTROL_IDX,
                                 _K_TILES, _N_CLASSES, _RADIO_IDX)
from repro.runtime.radio import (N_RADIO, R_CLASS, R_CLK, R_CONF_HI,
                                 R_CONF_LO, R_CPB, R_DUTY, R_HDR, R_PERIOD,
                                 R_TOPK, R_WAKEUP)

#: Fallback events per inner ``lax.scan`` trip (the deterministic paths'
#: placeholder and the floor of :func:`default_event_chunk`'s clamp).  The
#: production chunk is *plan-shape-derived*: dispatch passes
#: ``default_event_chunk(bucketed_rows)`` unless the caller overrides it
#: (the ``event_chunk=`` knob on ``replay_plans`` / ``fleet_sweep`` /
#: ``capacitor_sweep``).
EVENT_CHUNK = 128

#: Clamp bounds of the derived chunk: below 64 the outer while-loop's
#: full-state select dominates, above 512 the compiled inner body bloats
#: and the final overshoot (up to ``chunk - 1`` masked no-op events per
#: lane) stops amortizing.
_MIN_EVENT_CHUNK, _MAX_EVENT_CHUNK = 64, 512


def default_event_chunk(plan_rows: int) -> int:
    """Plan-shape-derived inner-scan trip count for the fused event stream.

    A lane walks at least one event per real row, so short plans (sonic:
    tens of rows) want short chunks -- the tail overshoot is bounded by
    ``chunk - 1`` masked events and the outer ``while_loop`` already exits
    after one or two trips -- while long row tables (tile-8 walks ~30k
    events/lane on the bench capacitor) want long chunks to amortize the
    outer loop's per-trip full-state select.  The heuristic is simply the
    bucketed row count clamped to ``[64, 512]``: row tables are already
    power-of-two bucket-padded (``fleetsim._bucket_rows``), so every plan
    in a bucket derives the same chunk and keeps sharing one compiled
    replay.  What the overshoot and a batch's slowest lane cost shows in
    ``FleetStats.replay_events`` over ``replay_event_slots``
    (:func:`event_slots`)."""
    if plan_rows < 1:
        raise ValueError(f"plan_rows must be >= 1, got {plan_rows}")
    return int(min(_MAX_EVENT_CHUNK,
                   max(_MIN_EVENT_CHUNK,
                       1 << (int(plan_rows) - 1).bit_length())))


def event_chunk_candidates(plan_rows: int) -> tuple:
    """Candidate pow2 event-chunk lengths for the measured autotuner
    (``event_chunk="auto"`` on the replay surfaces): the plan-shape
    default plus one octave either side, clamped to the same
    ``[64, 512]`` window and deduplicated.  The heuristic default is
    always a member, so the autotuner can only match or beat it."""
    base = default_event_chunk(plan_rows)
    return tuple(sorted({
        max(_MIN_EVENT_CHUNK, min(_MAX_EVENT_CHUNK, c))
        for c in (base // 2, base, base * 2)}))


def _exact(x) -> bool:
    """Is ``x`` in the integer state (whole cycles in int64) rather than
    the float64 one?  Static: the dtype of the replay's ``rem0``."""
    return not jnp.issubdtype(x.dtype, jnp.floating)


def divisor(c):
    """A per-iteration cost as the divisor of :func:`floor_div`: the
    float state divides by at least 1e-30, so a zero cost affords as many
    iterations as the caller's clip allows; the integer state keeps the
    zero, which :func:`floor_div` maps to the same clipped result."""
    return c if _exact(c) else jnp.maximum(c, 1e-30)


def floor_div(a, b, hi):
    """``floor(a / b)`` in the state's arithmetic, for ``b`` from
    :func:`divisor` and ``hi = max(n, 1)``, ``n`` the row's iterations.

    Float state: ``jnp.floor(a / b)``.  Integer state: exactly
    ``clip(floor(a / b), -1, hi)``, with ``b == 0`` read as the float
    state's 1e-30 (``hi`` for ``a > 0``, else ``sign(a)``).  Every
    quotient is clipped to ``[0, left]`` (``left <= n``) or compared with
    1, so the clip changes no result; it keeps the numerators of the
    infinite-capacity sentinel (near 2**62) in range.

    The integer quotient is a float32 estimate, clamped to
    ``[-2, hi + 2]``, then corrected by one step on the remainder
    ``a - q * b``.  With ``hi < 2**21`` the estimate is within one of the
    true floor, so the remainder lies in ``[-b, 2b)`` and, for
    ``b < 2**30``, is exact in wrapping 32-bit arithmetic (the dispatch
    checks both bounds).  A clamped estimate is off by more than one only
    where the clipped answer is ``-1`` or ``hi``, which the correction
    keeps."""
    if not _exact(a):
        return jnp.floor(a / b)
    f32, i32 = jnp.float32, jnp.int32
    hi = jnp.asarray(hi).astype(a.dtype)
    bs = jnp.maximum(b, 1)
    q = jnp.clip(jnp.floor(a.astype(f32) / bs.astype(f32)), -2,
                 hi.astype(f32) + 2).astype(i32)
    b32 = bs.astype(i32)
    r = a.astype(i32) - q * b32
    q = q - (r < 0).astype(i32) + (r >= b32).astype(i32)
    q = jnp.clip(q.astype(a.dtype), -1, hi)
    return jnp.where(b > 0, q, jnp.where(a > 0, hi, jnp.sign(a)))


def ceil_div(a, b, hi):
    """``ceil(a / b)`` for ``0 <= a`` and ``1 <= b``, in the state's
    arithmetic (integer state: capped at ``hi``, see
    :func:`floor_div`)."""
    if not _exact(a):
        return jnp.ceil(a / b)
    return floor_div(a + b - 1, b, hi)


def trace_window(cum, r0, r1, fallback):
    """Windowed sum of a per-lane cumulative trace over reboots (r0, r1]:
    gather-subtract inside the trace, ``fallback`` per entry past its end.
    Serves the dead-time trace (fallback = mean recharge) and the
    charge-capacity trace (fallback = nominal capacity)."""
    last = cum.shape[0] - 1
    i0 = jnp.clip(r0, 0, last).astype(jnp.int32)
    i1 = jnp.clip(r1, 0, last).astype(jnp.int32)
    over = jnp.maximum(r1 - last, 0) - jnp.maximum(r0 - last, 0)
    return cum[i1] - cum[i0] + over * fallback


def torn_prefix(entry_class, seg_class, seg_cycles, p):
    """Charge-order attribution of a torn entry prefix: walk the row's
    charge-segment list and book ``clip(p - start, 0, len)`` of each block
    to its own class (what the scalar's per-op ``charge`` does).  Exact for
    multi-dict rows where one class recurs across blocks.

    The integer state adds one one-hot column a segment instead of
    scattering: on a TPU a scatter-add of int64 (two 32-bit words) runs
    serially, and it held 79% of the integer event's device time."""
    starts = jnp.cumsum(seg_cycles) - seg_cycles
    amt = jnp.clip(p - starts, 0, seg_cycles)
    out = jnp.zeros(entry_class.shape, amt.dtype)
    if not _exact(amt):
        return out.at[seg_class].add(amt)
    cls = jnp.arange(entry_class.shape[-1])
    for g in range(seg_class.shape[-1]):
        out = out + jnp.where(seg_class[g] == cls, amt[g], 0)
    return out


def send_message_bytes(conf, radio):
    """Decision 5 (uplink compress): bytes shipped for one lane's
    classifier confidence under the packed radio model/policy vector
    (``runtime.radio``): argmax class above ``conf_hi``, top-k logits
    above ``conf_lo``, nothing below.  Byte fields are pre-rounded to
    whole numbers by ``pack_radio``, so the result is exact in f64."""
    return jnp.where(conf >= radio[R_CONF_HI],
                     radio[R_HDR] + radio[R_CLASS],
                     jnp.where(conf >= radio[R_CONF_LO],
                               radio[R_HDR] + radio[R_TOPK], 0.0))


def send_cost_cycles(send_bytes, radio):
    """Cycles one transmission costs: fixed wakeup/preamble plus per-byte
    TX.  A skipped send (0 bytes) never wakes the radio."""
    return jnp.where(send_bytes > 0.0,
                     radio[R_WAKEUP] + send_bytes * radio[R_CPB], 0.0)


def send_defer_wait(live, dead, radio):
    """Decision 5 (uplink defer): is the duty-cycled basestation window
    closed at the lane's current wall-clock, and how long until it
    reopens?  The receiver listens for the first ``duty`` fraction of
    every ``period`` seconds (``period == 0``: always listening).  The
    lane's wall-clock is ``live / CLOCK_HZ + dead`` -- the same quantity
    the result channels report -- evaluated at the row's fresh entry;
    a deferring lane sleeps (dead time, no energy) until the window
    opens.  Shared by the event stream, the legacy scan and (through
    the reference interpreter's float mirror) the differential oracle,
    so every path performs the identical float ops.

    Two details pin the compiled arithmetic to the mirror's one-rounding-
    per-op sequence: the clock rate comes from the runtime ``radio``
    operand (``R_CLK``) so the divide stays a true division (a constant
    divisor gets rewritten into a reciprocal multiply that then FMA-
    contracts with the add), and the ``jnp.abs`` -- a value identity,
    ``floor * ps >= 0`` -- breaks the mul->sub adjacency the CPU backend
    would otherwise contract into an FMA."""
    period = radio[R_PERIOD]
    t = live / radio[R_CLK] + dead
    ps = jnp.maximum(period, 1e-30)
    phase = t - jnp.abs(jnp.floor(t / ps) * ps)
    closed = (period > 0.0) & (phase >= radio[R_DUTY] * period)
    return closed, period - phase


def pack_rows(rows: dict):
    """Flatten a plan's per-row field dict into one ``(S, F)`` matrix
    plus a static unpack layout.

    An event used to gather ~19 separate row fields (scalars, class
    vectors, segment lists, tile tables) with one dynamic index each --
    the dominant per-event cost on gather-bound plans (sonic, tile-8).
    Packing them column-wise means :func:`unpack_row` reads the entire
    row with a single ``dynamic_slice`` of one contiguous ``(1, F)``
    stripe.  A table with any float field is stored as f64: every integer
    field (``kind``, ``tile_flag``, the segment class ids) is a small
    whole number, exact in f64, and is cast back to its original dtype on
    unpack -- the round-trip is bitwise lossless, so the packed replay is
    bit-identical to the unpacked one.  An all-integer table (the integer
    state's, int32 where every field fits) keeps its integer type.  The
    pack itself is event-loop-invariant (built once per replay, hoisted
    out of the compiled loop).

    Plan IR v2: row dicts with a leading *candidate-plan* axis (every
    field shaped ``(P, S, ...)`` -- a stacked ``fleetsim.PlanSet``) pack
    to a ``(P, S, F)`` tensor the same way; :func:`unpack_row` then takes
    the lane's plan index and reads its row with one two-index
    ``dynamic_slice``, so a whole design space replays from one packed
    broadcast operand."""
    keys = tuple(sorted(rows))
    lead = int(jnp.asarray(rows["kind"]).ndim)   # 1 = (S,), 2 = (P, S)
    vals = [jnp.asarray(rows[k]) for k in keys]
    dt = (jnp.float64 if any(jnp.issubdtype(v.dtype, jnp.floating)
                             for v in vals) else jnp.result_type(*vals))
    cols, layout, off = [], [], 0
    for k, v in zip(keys, vals):
        flat = v.reshape(v.shape[:lead] + (-1,)).astype(dt)
        layout.append((k, off, v.shape[lead:], v.dtype))
        cols.append(flat)
        off += flat.shape[-1]
    return jnp.concatenate(cols, axis=lead), tuple(layout)


def unpack_row(packed, layout, i, plan=None) -> dict:
    """Rebuild row ``i``'s field dict from the packed matrix with one
    ``dynamic_slice`` (the static ``layout`` splits the stripe for
    free).  With a ``(P, S, F)`` pack, ``plan`` selects the candidate
    plan in the same slice."""
    if packed.ndim == 3:
        f = packed.shape[-1]
        stripe = lax.dynamic_slice(
            packed, (plan.astype(i.dtype) if hasattr(plan, "astype")
                     else jnp.asarray(plan, i.dtype), i,
                     jnp.asarray(0, i.dtype)), (1, 1, f))[0, 0]
    else:
        stripe = lax.dynamic_slice_in_dim(packed, i, 1, axis=0)[0]
    row = {}
    for k, off, shape, dtype in layout:
        w = math.prod(shape) if shape else 1
        v = stripe[off:off + w]
        row[k] = (v.reshape(shape) if shape else v[0]).astype(dtype)
    return row


class RowCtx(NamedTuple):
    """State-independent per-row decisions: the lane's selected tile
    (decision 1) and the retry-side commit granularity (the state-dependent
    first-visit side lives in :func:`fast_forward`)."""
    kind: jax.Array
    n: jax.Array
    c: jax.Array
    e: jax.Array
    cc: jax.Array
    iter_class: jax.Array
    entry_class: jax.Array
    commit_class: jax.Array
    seg_class: jax.Array
    seg_cycles: jax.Array
    er: jax.Array
    cr: jax.Array
    crs: jax.Array
    iter_vecr: jax.Array
    batchr: jax.Array
    afford_nom: jax.Array
    row_stuck: jax.Array
    has_iters: jax.Array
    k: jax.Array
    send_bytes: jax.Array


def row_ctx(row, cap, theta, adaptive: bool, parametric: bool,
            conf=None, radio=None, has_send: bool = False) -> RowCtx:
    """Decisions 1 + 2 (retry side) for one row on one lane.

    With ``has_send`` (static: the plan contains ``KIND_SEND`` rows and a
    radio model is live), a SEND row's cost fields are overridden from the
    lane's confidence and the packed radio vector *before* the passability
    bound is derived, so the generic atomic-row machinery -- torn-prefix
    rollback, full-preamble retry, the ``row_stuck`` bound -- applies to
    transmissions unchanged: the row becomes an atomic entry of
    ``wakeup + bytes * cycles_per_byte`` cycles booked to the radio class
    (its single charge segment), zero for a skipped send."""
    if parametric:
        sel = row["tile_sel_cost"]                       # (K,) fit costs
        k = jnp.clip(jnp.sum((sel > cap).astype(jnp.int32)), 0,
                     _K_TILES - 1)
        is_param = row["tile_flag"] > 0
        n = jnp.where(is_param, row["tile_n"][k], row["n"])
        c = jnp.where(is_param, row["tile_iter_cycles"][k],
                      row["iter_cycles"])
        iter_class = jnp.where(is_param, row["tile_iter_class"][k],
                               row["iter_class"])
    else:
        k = jnp.asarray(0, jnp.int32)
        n, c, iter_class = row["n"], row["iter_cycles"], row["iter_class"]
    e, entry_class = row["entry_cycles"], row["entry_class"]
    cc, commit_class = row["commit_cycles"], row["commit_class"]
    seg_cycles = row["entry_seg_cycles"]
    send_bytes = jnp.zeros((), cap.dtype)
    if has_send:
        is_send = row["kind"] == KIND_SEND
        send_bytes = jnp.where(is_send, send_message_bytes(conf, radio),
                               0.0)
        cost = send_cost_cycles(send_bytes, radio)
        e = jnp.where(is_send, cost, e)
        entry_class = jnp.where(
            is_send, jnp.zeros_like(entry_class).at[_RADIO_IDX].set(cost),
            entry_class)
        # the SEND row's single charge segment (class slot 0 is the radio
        # index, written by fleetsim.with_uplink) carries the whole cost
        # so a torn transmission's burned prefix books to the radio class
        seg_cycles = jnp.where(
            is_send, jnp.zeros_like(seg_cycles).at[0].set(cost),
            seg_cycles)
    has_iters = n > 0
    if adaptive:
        batchr = has_iters & (cc > 0.0) & (theta <= 1.0)
    else:
        batchr = jnp.asarray(False)
    er = jnp.where(batchr, e + cc, e)
    cr = jnp.where(batchr, c - cc, c)
    crs = divisor(cr)
    iter_vecr = jnp.where(batchr, iter_class - commit_class, iter_class)
    afford_nom = floor_div(cap - er, crs, jnp.maximum(n, 1))
    row_stuck = jnp.where(has_iters, afford_nom < 1, e > cap)
    return RowCtx(row["kind"], n, c, e, cc, iter_class, entry_class,
                  commit_class, row["entry_seg_class"], seg_cycles,
                  er, cr, crs, iter_vecr, batchr,
                  afford_nom, row_stuck, has_iters, k, send_bytes)


class ChargeState(NamedTuple):
    """Carry of the charge loop over one row (named form of the old
    positional 16-tuple; ``done`` replaces ``~s[15]``)."""
    rem: jax.Array          # actual deliverable left this charge
    bel: jax.Array          # believed budget left this charge
    left: jax.Array         # row iterations still to run
    live: jax.Array
    reboots: jax.Array
    classes: jax.Array
    wasted: jax.Array
    pend: jax.Array         # pending-window cycles (cross-charge batching)
    pend_class: jax.Array
    pend_rows: jax.Array
    bhat: jax.Array         # EWMA believed per-charge budget
    chg: jax.Array          # cycles spent so far in the current charge
    debt: jax.Array         # torn pending work being replayed
    debt_class: jax.Array
    stuck: jax.Array
    done: jax.Array


def charge_once(ctx: RowCtx, cap, charge_cum, theta, window, alpha,
                adaptive: bool, s: ChargeState) -> ChargeState:
    """Exactly one charge of the row: the stochastic loop body, verbatim.

    Phase 0 replays rollback debt, then the row phase schedules from the
    believed budget and executes against the actual delivery; a death
    without a durable cursor write tears the pending window into debt and
    updates the EWMA belief from the observed charge length.

    In the integer state the dispatch has proved the policy fixed and the
    belief static (``belief_alpha == 0``), so no debt, pending window or
    belief update ever arises: those carries are ``None``, phase 0 and
    their updates are left out, and the believed per-charge budget is
    ``cap``."""
    exact = _exact(s.rem)

    def refill_sum(r0, r1):
        return trace_window(charge_cum, r0, r1, cap)

    a0 = s.rem                     # actual deliverable this charge
    est0 = s.bel                   # the lane's believed budget
    hi = jnp.maximum(ctx.n, 1)     # quotients are clipped to `left` <= n

    if exact:
        dend = jnp.asarray(False)
        a1, est1 = a0, jnp.maximum(est0, 0)
        pnd1 = prw1 = jnp.zeros_like(a0)
    else:
        # ---- phase 0: multi-row rollback replay.  Torn pending work
        # (debt) is re-executed first, one believed-affordable slice per
        # charge, each slice sealed by its own cursor commit so a replay
        # never grows the rollback (it converges even when the charges
        # that tore it stay short).
        have_debt = s.debt > 0.0
        debt_s = jnp.maximum(s.debt, 1e-30)
        want = jnp.where(have_debt,
                         jnp.minimum(s.debt,
                                     jnp.maximum(est0 - ctx.cc, 0.0)), 0.0)
        dok = have_debt & (want > 0.0) & (a0 >= want + ctx.cc)
        dfail = have_debt & ~dok
        # a *partial* repay leaves the cursor still inside the rolled-back
        # rows: the lane cannot run the current row ahead of its own
        # replay, so the rest of the charge drains and the next charge
        # continues repaying.  `dend`: this charge ends inside the replay
        # phase and the row phase never runs.
        dpart = dok & ((s.debt - want) > 0.0)
        dend = dfail | dpart
        d_exec = jnp.where(dfail, jnp.minimum(want, a0), 0.0)
        d_spend = jnp.where(dok, want + ctx.cc, 0.0)
        a1 = a0 - d_spend
        est1 = jnp.maximum(est0 - d_spend, 0.0)
        debt1 = jnp.where(dok, s.debt - want, s.debt)
        dcls1 = jnp.where(dok, s.debt_class * ((s.debt - want) / debt_s),
                          s.debt_class)
        d_cls = jnp.where(dok,
                          s.debt_class * (want / debt_s) + ctx.commit_class,
                          jnp.zeros_like(ctx.commit_class))
        # a replay commit is a cursor write: it would also cover any
        # pending rows (pend is zero whenever debt is nonzero by
        # construction -- a tear converts the whole window to debt)
        pnd1 = jnp.where(dok, 0.0, s.pend)
        pcls1 = jnp.where(dok, jnp.zeros_like(s.pend_class), s.pend_class)
        prw1 = jnp.where(dok, 0.0, s.pend_rows)

    # ---- batch decision for this charge: the believed remaining budget
    # (post-replay) against the confidence margin theta * bhat; window > 1
    # additionally defers the row-boundary commit while the pending window
    # has room.
    if adaptive:
        batch = (ctx.has_iters & (ctx.cc > 0.0)
                 & (jnp.isinf(cap) | (est1 >= theta * s.bhat)))
        defer = batch & ((prw1 + 1.0) < window)
    else:
        batch = jnp.asarray(False)
        defer = jnp.asarray(False)
    e_b = jnp.where(batch, ctx.e + ctx.cc, ctx.e)
    c_b = jnp.where(batch, ctx.c - ctx.cc, ctx.c)
    c_bs = divisor(c_b)
    iv = jnp.where(batch, ctx.iter_class - ctx.commit_class,
                   ctx.iter_class)

    # ---- row phase: schedule from belief, execute against actual
    entered = a1 >= ctx.e
    # chunk the lane schedules from its believed budget
    k_est = jnp.clip(jnp.where(est1 >= e_b,
                               floor_div(est1 - e_b, c_bs, hi), 0),
                     0, s.left)
    # a deferred row completion schedules all remaining iterations with no
    # commit; otherwise the commit is reserved at the end
    fin_cost = (ctx.e + s.left * c_b
                + jnp.where(batch & ~defer, ctx.cc, 0))
    plan_fin = est1 >= fin_cost
    sched_i = jnp.where(batch & plan_fin, s.left, k_est)
    # iterations the actual charge affords (per-iteration commits run
    # until real death; entry first, batched commit last)
    k_act = jnp.clip(jnp.where(entered,
                               floor_div(a1 - e_b, c_bs, hi), 0),
                     0, s.left)
    k_exec = jnp.clip(jnp.where(entered,
                                floor_div(a1 - ctx.e, c_bs, hi), 0),
                      0, jnp.where(batch, sched_i, s.left))
    fin = jnp.where(batch, plan_fin & (a1 >= fin_cost),
                    a1 >= ctx.e + s.left * c_b)
    # boundary commit: believed end-of-charge at a row boundary with a
    # pending window and no schedulable chunk -- the lane writes the
    # deferred cursor commit *before* draining forward into the next
    # row's entry.
    boundary = batch & ~plan_fin & (k_est == 0) & (prw1 > 0)
    sched_commit = jnp.where(plan_fin, ~defer,
                             (k_est > 0) | (prw1 > 0))
    commit_ok = jnp.where(boundary, a1 >= ctx.cc,
                          a1 >= e_b + sched_i * c_b)
    # did a batched cursor write land before this charge died?
    land = batch & ~plan_fin & sched_commit & commit_ok

    # committed progress this charge: a batched chunk commits all or
    # nothing (surprise death -> rollback to the last cursor)
    exec_iters = jnp.where(batch,
                           jnp.where(land & ~boundary, sched_i, k_exec),
                           k_act)
    prog = jnp.where(batch,
                     jnp.where(land & ~boundary, sched_i, 0),
                     k_act)
    commit_n = land.astype(a0.dtype)

    # death-path entry burn (the boundary commit spends cc first; a failed
    # boundary commit never reaches the entry at all)
    p_entry = jnp.where(boundary,
                        jnp.where(land, a1 - ctx.cc, -1), a1)
    entered_d = p_entry >= ctx.e
    torn_v = jnp.where(entered_d, jnp.zeros_like(ctx.entry_class),
                       torn_prefix(ctx.entry_class, ctx.seg_class,
                                   ctx.seg_cycles, p_entry))
    entry_burn = jnp.where(entered_d, ctx.e,
                           jnp.clip(p_entry, 0, ctx.e))
    cls_burn = (jnp.where(entered_d, ctx.entry_class,
                          jnp.zeros_like(ctx.entry_class))
                + torn_v + exec_iters * iv
                + commit_n * ctx.commit_class)
    residue = (a1 - entry_burn - exec_iters * c_b - commit_n * ctx.cc)
    cls_death = cls_burn.at[_CONTROL_IDX].add(residue)
    spend_fin = fin_cost
    cls_fin = (ctx.entry_class + s.left * iv
               + (batch & ~defer).astype(a0.dtype) * ctx.commit_class)

    fin_ok = fin & ~dend
    stuck_now = (~fin_ok) & ctx.row_stuck
    if exact:
        return s._replace(
            rem=jnp.where(fin_ok, a1 - spend_fin,
                          refill_sum(s.reboots, s.reboots + 1)),
            bel=jnp.where(fin_ok, jnp.maximum(est1 - spend_fin, 0), cap),
            left=jnp.where(fin_ok, 0, s.left - prog),
            live=s.live + jnp.where(fin, spend_fin, a1),
            reboots=s.reboots + jnp.where(fin_ok, 0, 1),
            classes=s.classes + jnp.where(fin, cls_fin, cls_death),
            stuck=s.stuck | stuck_now,
            done=s.done | fin_ok | stuck_now)

    # a death without any durable cursor write tears the pending window:
    # those rows roll back and become replay debt
    committed = jnp.where(batch, land, k_act > 0.0)
    tear = (~fin_ok) & ~dend & ~committed & (pnd1 > 0.0)
    waste_add = (jnp.where((~fin_ok) & ~dend & batch & ~land,
                           k_exec * c_b, 0.0)
                 + jnp.where(tear, pnd1, 0.0)
                 + jnp.where(dfail, d_exec, 0.0))

    # pending-window updates at a deferred row completion
    pnd_fin = jnp.where(defer, pnd1 + spend_fin, 0.0)
    pcls_fin = jnp.where(defer, pcls1 + ctx.entry_class + s.left * iv,
                         jnp.zeros_like(s.pend_class))
    prw_fin = jnp.where(defer, prw1 + 1.0, 0.0)

    # belief recalibration (decision 4's EWMA side): update the believed
    # budget from the observed charge length (deaths of
    # refill-started charges only: the wake charge is partial and
    # calibration burns precede any work).  The belief is quantized to
    # whole cycles -- budgets are discrete everywhere else in the model,
    # and the rounding keeps the update reproducible bit-for-bit across
    # compilers (XLA may contract the multiply-add into an FMA).
    died = dend | ~fin
    obs = s.chg + a0
    bh_new = jnp.where((alpha > 0.0) & (s.reboots > 0.0) & died,
                       jnp.maximum(jnp.rint(s.bhat
                                            + alpha * (obs - s.bhat)),
                                   1.0),
                       s.bhat)

    dfail_cls = (s.debt_class * (d_exec / debt_s)
                 ).at[_CONTROL_IDX].add(a0 - d_exec)
    # a partial repay's drained remainder is a chunk-boundary drain
    dpart_cls = d_cls.at[_CONTROL_IDX].add(a1)
    dend_cls = jnp.where(dfail, dfail_cls, dpart_cls)
    return ChargeState(
        rem=jnp.where(fin_ok, a1 - spend_fin,
                      refill_sum(s.reboots, s.reboots + 1.0)),
        # a completing row decays the belief by what was spent (clamped:
        # the device may outlive its own forecast); a burned charge resets
        # it to the believed budget.
        bel=jnp.where(fin_ok, jnp.maximum(est1 - spend_fin, 0.0), bh_new),
        left=jnp.where(fin_ok, 0.0,
                       s.left - jnp.where(dend, 0.0, prog)),
        live=s.live + jnp.where(dend, a0,
                                d_spend + jnp.where(fin, spend_fin, a1)),
        reboots=s.reboots + jnp.where(fin_ok, 0.0, 1.0),
        classes=s.classes + jnp.where(dend, dend_cls,
                                      d_cls + jnp.where(fin, cls_fin,
                                                        cls_death)),
        wasted=s.wasted + waste_add,
        pend=jnp.where(dend, pnd1, jnp.where(fin, pnd_fin, 0.0)),
        pend_class=jnp.where(dend, pcls1,
                             jnp.where(fin, pcls_fin,
                                       jnp.zeros_like(s.pend_class))),
        pend_rows=jnp.where(dend, prw1, jnp.where(fin, prw_fin, 0.0)),
        bhat=bh_new,
        chg=jnp.where(fin_ok, s.chg + d_spend + spend_fin, 0.0),
        debt=debt1 + jnp.where(tear, pnd1, 0.0),
        debt_class=dcls1 + jnp.where(tear, pcls1,
                                     jnp.zeros_like(s.pend_class)),
        stuck=s.stuck | stuck_now,
        done=s.done | fin_ok | stuck_now)


def fast_forward(ctx: RowCtx, cap, theta, adaptive: bool,
                 s: ChargeState) -> ChargeState:
    """Closed-form completion of the row's remaining ``left`` iterations
    when every refill from here on delivers exactly ``cap``: the
    deterministic path's chunk/retry algebra (this *is* the deterministic
    path -- ``_scan_step`` calls it with a fresh row).  Integral energy
    state makes the grouped arithmetic exact, so the result is
    bit-identical to iterating :func:`charge_once` over nominal refills.
    The integer state carries no ``chg`` (see :func:`charge_once`)."""
    rem, left = s.rem, s.left
    hi = jnp.maximum(ctx.n, 1)
    if adaptive:
        lvl0 = jnp.where(jnp.isinf(cap), True, s.bel >= theta * s.bhat)
        batch0 = ctx.has_iters & (ctx.cc > 0.0) & lvl0
    else:
        batch0 = jnp.asarray(False)
    e0 = jnp.where(batch0, ctx.e + ctx.cc, ctx.e)
    c0 = jnp.where(batch0, ctx.c - ctx.cc, ctx.c)
    c0s = divisor(c0)
    iter_vec0 = jnp.where(batch0, ctx.iter_class - ctx.commit_class,
                          ctx.iter_class)

    needed = e0 + left * c0
    ok = rem >= needed

    # failure path (finite capacity; never selected when rem == inf)
    entered = rem >= ctx.e
    afford0 = jnp.clip(jnp.where(entered,
                                 floor_div(rem - e0, c0s, hi), 0),
                       0, left)
    rem_iters = left - afford0
    afford_full = jnp.maximum(ctx.afford_nom, 1)
    visits = jnp.where(ctx.has_iters,
                       jnp.maximum(ceil_div(rem_iters, afford_full, hi),
                                   1),
                       1)
    n_last = jnp.where(ctx.has_iters,
                       rem_iters - (visits - 1) * afford_full, 0)
    fail_live = rem + (visits - 1) * cap + ctx.er + n_last * ctx.cr
    fail_rem = cap - ctx.er - n_last * ctx.cr
    entries = visits + entered.astype(rem.dtype)

    # Batched-commit bookkeeping: one cursor write per visit that executed
    # iterations (+1 if attempt 0 entered and progressed).
    ok_commits = batch0.astype(rem.dtype)
    fail_commits = (jnp.where(ctx.batchr, visits, 0)
                    + (batch0 & (afford0 > 0)).astype(rem.dtype))

    fail_classes = (entries * ctx.entry_class + afford0 * iter_vec0
                    + rem_iters * ctx.iter_vecr
                    + fail_commits * ctx.commit_class)
    # Torn first-attempt burn: a lane that dies before affording the entry
    # books the burned prefix to the entry ops' own classes in charge
    # order (what the scalar's per-op `charge` does); only drains go to
    # control.
    torn = jnp.where(entered, jnp.zeros_like(ctx.entry_class),
                     torn_prefix(ctx.entry_class, ctx.seg_class,
                                 ctx.seg_cycles, rem))
    fail_classes = fail_classes + torn
    residue = (fail_live - entries * ctx.e - afford0 * c0
               - rem_iters * ctx.cr - fail_commits * ctx.cc
               - jnp.where(entered, 0, rem))
    fail_classes = fail_classes.at[_CONTROL_IDX].add(residue)

    ok_classes = (ctx.entry_class + left * iter_vec0
                  + ok_commits * ctx.commit_class)
    new_rem = jnp.where(ok, rem - needed, fail_rem)
    out = s._replace(
        rem=new_rem,
        bel=new_rem,         # nominal charges: belief is exact
        left=jnp.zeros_like(left),
        live=s.live + jnp.where(ok, needed, fail_live),
        reboots=s.reboots + jnp.where(ok, 0, visits),
        classes=s.classes + jnp.where(ok, ok_classes, fail_classes),
        stuck=s.stuck | ((~ok) & ctx.row_stuck),
        done=jnp.asarray(True) | s.done)
    if _exact(rem):
        return out
    return out._replace(
        chg=jnp.where(ok, s.chg + needed, ctx.er + n_last * ctx.cr))


class EventState(NamedTuple):
    """Per-lane carry of the flat event stream: the row cursor, the
    charge-loop state, and the per-row dead-time anchor.  The integer
    state carries neither the device-side dead time (the host recomputes
    it from the final reboots) nor what :func:`charge_once` leaves out
    there: those fields are ``None``."""
    i: jax.Array            # row cursor (int32)
    fresh: jax.Array        # next event starts a new row
    row_r0: jax.Array       # reboot counter at the current row's entry
    dead: jax.Array
    rem: jax.Array
    bel: jax.Array
    left: jax.Array
    live: jax.Array
    reboots: jax.Array
    classes: jax.Array
    wasted: jax.Array
    pend: jax.Array
    pend_class: jax.Array
    pend_rows: jax.Array
    bhat: jax.Array
    chg: jax.Array
    debt: jax.Array
    debt_class: jax.Array
    stuck: jax.Array
    tx_bytes: jax.Array     # uplink bytes shipped (decision 5)
    sent: jax.Array         # uplink transmissions completed
    deferred: jax.Array     # sends deferred past a closed window
    events: jax.Array       # events in which the lane was active (int32)


def _select(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b)


def event_step(packed, layout, cap, trace_cum, tail_s, charge_cum,
               nominal_from, theta, window, alpha, conf, radio,
               adaptive: bool, parametric: bool, enable_fast: bool,
               has_burn: bool, has_send: bool,
               st: EventState, active, plan=None) -> EventState:
    """One event: one charge of the current row, or the row's closed-form
    remainder when eligible, or a whole BURN/CALIB row.

    ``active`` is the lane's cursor mask (``i < s_real``): an inactive
    lane's state passes through bitwise (the mask is folded into every
    state-select rather than wrapped around the whole step, which would
    cost a second full-state select per event).  ``enable_fast`` /
    ``has_burn`` are dispatch-time data facts ("some lane can reach the
    all-nominal regime" / "the plan has BURN rows"): disabling either
    never changes results -- the fast path is a pure shortcut and the
    BURN override is dead code without BURN rows -- it only removes the
    corresponding per-event arithmetic from the compiled body.  With a
    ``(P, S, F)`` pack (Plan IR v2), ``plan`` is the lane's candidate
    index into the stacked row table.  In the integer state the believed
    per-charge budget is ``cap`` and ``alpha`` is 0 (see
    :func:`charge_once`)."""
    exact = _exact(st.rem)
    s_pad = packed.shape[-2]
    i = jnp.minimum(st.i, s_pad - 1)
    row = unpack_row(packed, layout, i, plan)
    ctx = row_ctx(row, cap, theta, adaptive, parametric,
                  conf=conf, radio=radio, has_send=has_send)
    bhat = cap if exact else st.bhat

    # Entering a row resets the row-local loop state (iterations left,
    # rollback debt -- a stuck row's discarded debt must not leak).
    fresh = st.fresh & active

    # decision 5: a fresh SEND row that wakes into a closed basestation
    # window sleeps (dead time, no energy) until the window opens.  Only
    # the *first* entry defers; a retry after a torn send transmits as
    # soon as the buffer recharges (documented simplification).
    defer_now = jnp.zeros_like(fresh)
    send_wait = None if exact else jnp.zeros_like(st.dead)
    if has_send:
        is_send = ctx.kind == KIND_SEND
        want_send = fresh & is_send & (ctx.send_bytes > 0.0) \
            & ~ctx.row_stuck
        closed, wait = send_defer_wait(st.live, st.dead, radio)
        defer_now = want_send & closed
        send_wait = jnp.where(defer_now, wait, 0.0)

    cs = ChargeState(
        rem=st.rem, bel=st.bel,
        left=jnp.where(fresh, ctx.n, st.left),
        live=st.live, reboots=st.reboots, classes=st.classes,
        wasted=st.wasted, pend=st.pend, pend_class=st.pend_class,
        pend_rows=st.pend_rows, bhat=st.bhat, chg=st.chg,
        debt=None if exact else jnp.where(fresh, 0.0, st.debt),
        debt_class=None if exact else jnp.where(
            fresh, jnp.zeros_like(st.debt_class), st.debt_class),
        stuck=st.stuck, done=jnp.asarray(False))

    slow = charge_once(ctx, cap, charge_cum, theta, window, alpha,
                       adaptive, cs)
    if enable_fast:
        # Fast-path eligibility: the closed form is exact iff every
        # refill from here on is nominal (the trace's all-nominal tail
        # starts at `nominal_from`), the belief carries no error, no
        # cross-charge state is in flight, and nothing the closed form
        # cannot express (stuck rows stop after one charge; deferral
        # under window > 1 opens the pending window; EWMA updates are
        # no-ops only while observed charges are exactly nominal --
        # chg + rem == cap -- or before any refill).  The integer state
        # has no cross-charge state and no EWMA.
        elig = ((st.reboots >= nominal_from)
                & (cs.bel == cs.rem) & ~ctx.row_stuck)
        if not exact:
            elig = (elig & (cs.bhat == cap)
                    & (cs.pend == 0.0) & (cs.pend_rows == 0.0)
                    & (cs.debt == 0.0)
                    & ((alpha <= 0.0) | (cs.chg + cs.rem == cap)
                       | (cs.reboots == 0.0)))
        if adaptive:
            elig = elig & (window <= 1.0)
        fast = fast_forward(ctx, cap, theta, adaptive, cs)
        work = _select(elig, fast, slow)
    else:
        work = slow
    is_work = ctx.kind == KIND_WORK
    if has_send:
        # SEND rows ride the generic atomic-row machinery (row_ctx
        # overrode the entry cost/classes): torn sends roll back and
        # retry the full preamble like any other atomic row.
        is_work = is_work | (ctx.kind == KIND_SEND)
    out = _select(active & is_work, work, cs)

    # -- BURN rows: a failed calibration attempt drains the whole buffer
    # (pre-row state feeds the overrides, as in the unfused path)
    if has_burn:
        is_burn = active & (ctx.kind == KIND_BURN)
        burn_vec = jnp.zeros_like(cs.classes).at[_BURN_IDX].add(cs.rem)
        out = out._replace(
            rem=jnp.where(is_burn,
                          trace_window(charge_cum, st.reboots,
                                       st.reboots + 1, cap), out.rem),
            bel=jnp.where(is_burn, bhat, out.bel),
            live=jnp.where(is_burn, st.live + cs.rem, out.live),
            reboots=jnp.where(is_burn, st.reboots + 1, out.reboots),
            classes=jnp.where(is_burn, st.classes + burn_vec,
                              out.classes),
            stuck=jnp.where(is_burn, st.stuck, out.stuck),
            wasted=jnp.where(is_burn, st.wasted, out.wasted))
        if not exact:
            out = out._replace(chg=jnp.where(
                is_burn, jnp.zeros_like(out.chg), out.chg))

    # -- CALIB rows: per-lane burn count from the capacitor (Sec. 7.1)
    if parametric:
        is_calib = active & (ctx.kind == KIND_CALIB)
        burns = ctx.k.astype(cs.rem.dtype)
        calib_live = jnp.where(
            burns > 0,
            cs.rem + trace_window(charge_cum, st.reboots,
                                  st.reboots + burns - 1, cap), 0)
        calib_rem = jnp.where(
            burns > 0,
            trace_window(charge_cum, st.reboots + burns - 1,
                         st.reboots + burns, cap), cs.rem)
        calib_vec = jnp.zeros_like(cs.classes).at[_BURN_IDX].add(
            calib_live)
        out = out._replace(
            rem=jnp.where(is_calib, calib_rem, out.rem),
            bel=jnp.where(is_calib,
                          jnp.where(burns > 0, bhat, cs.bel), out.bel),
            live=jnp.where(is_calib, st.live + calib_live, out.live),
            reboots=jnp.where(is_calib, st.reboots + burns, out.reboots),
            classes=jnp.where(is_calib, st.classes + calib_vec,
                              out.classes),
            stuck=jnp.where(is_calib, st.stuck, out.stuck),
            wasted=jnp.where(is_calib, st.wasted, out.wasted))
        if not exact:
            out = out._replace(chg=jnp.where(
                is_calib & (burns > 0), jnp.zeros_like(out.chg), out.chg))

    advance = active & jnp.where(is_work, out.done, True)
    row_r0 = dead = None
    if not exact:
        # decision 3: per-reboot dead time, booked once per row from the
        # reboot counter at the row's entry (the same single
        # gather-subtract the unfused path evaluates, for bitwise
        # identity).  The window wait is added first as its own float
        # step so the unfused path (which books the wait at row entry)
        # stays bitwise identical.
        dead_base = st.dead + send_wait
        dead = jnp.where(advance,
                         dead_base + trace_window(trace_cum, st.row_r0,
                                                  out.reboots, tail_s),
                         dead_base)
        row_r0 = jnp.where(advance, out.reboots, st.row_r0)
    tx_bytes, sent, deferred = st.tx_bytes, st.sent, st.deferred
    if has_send:
        # Book TX on row completion; a stuck SEND row (cost > capacity)
        # never gets its payload out, matching the reference interpreter.
        adv_tx = advance & is_send & ~ctx.row_stuck
        tx_bytes = tx_bytes + jnp.where(adv_tx, ctx.send_bytes, 0.0)
        sent = sent + jnp.where(adv_tx & (ctx.send_bytes > 0.0), 1.0, 0.0)
        deferred = deferred + jnp.where(defer_now, 1.0, 0.0)
    return EventState(
        i=st.i + advance.astype(jnp.int32),
        fresh=advance,
        row_r0=row_r0,
        dead=dead,
        rem=out.rem, bel=out.bel, left=out.left, live=out.live,
        reboots=out.reboots, classes=out.classes, wasted=out.wasted,
        pend=out.pend, pend_class=out.pend_class,
        pend_rows=out.pend_rows, bhat=out.bhat, chg=out.chg,
        debt=out.debt, debt_class=out.debt_class, stuck=out.stuck,
        tx_bytes=tx_bytes, sent=sent, deferred=deferred,
        events=st.events + active.astype(jnp.int32))


def event_replay(rows, cap, rem0, trace_cum, tail_s, charge_cum,
                 nominal_from, s_real, theta, window, alpha, *,
                 adaptive: bool, parametric: bool,
                 enable_fast: bool = True, has_burn: bool = True,
                 has_send: bool = False, conf=0.0, radio=None,
                 chunk: int = EVENT_CHUNK, plan_idx=None) -> dict:
    """Replay one lane's plan as a constant-trip masked event stream.

    ``s_real`` is the lane's real (pre-padding) row count: the cursor
    never walks padding rows, and once ``i == s_real`` every further event
    is a bitwise no-op (see the module docstring's masking scheme).
    Beside the lane's channels it returns ``events``, the events in which
    the lane was active (int32; :func:`event_slots`).

    The state's dtype is ``rem0``'s.  float64 serves every replay.  An
    integer ``rem0`` (int64) selects the integer state, which the
    dispatch (``fleetsim._stochastic_prep``) gives a replay only where
    its arithmetic is integral: fixed policy, ``alpha == 0``, no SEND
    rows, and ``cap``, ``charge_cum``, ``nominal_from`` and every row
    field whole numbers of the same integer kind, an infinite capacity
    held as a sentinel far above any reachable sum.  That state carries
    no debt, pending window, EWMA belief or charge length, none of which
    can arise, and no device-side dead time: its result has no ``dead``
    channel, and ``belief`` is ``cap``.

    Plan IR v2: with stacked ``(P, S, ...)`` rows and a per-lane
    ``plan_idx``, every event reads the lane's own candidate's row from
    the shared ``(P, S, F)`` pack -- the pack stays a broadcast
    loop-invariant, so a whole :class:`~repro.core.fleetsim.PlanSet`
    replays under ONE compiled scan."""
    packed, layout = pack_rows(rows)
    if plan_idx is not None:
        plan_idx = jnp.asarray(plan_idx, jnp.int32)
    exact = _exact(rem0)
    zero = jnp.zeros_like(rem0)
    vec = jnp.zeros((_N_CLASSES,), rem0.dtype)
    inert = None if exact else zero
    st0 = EventState(
        i=jnp.asarray(0, jnp.int32),
        fresh=jnp.asarray(True),
        row_r0=inert, dead=inert,
        rem=rem0, bel=rem0, left=zero, live=zero, reboots=zero,
        classes=vec, wasted=zero, pend=inert,
        pend_class=None if exact else vec,
        pend_rows=inert, bhat=None if exact else cap + zero, chg=inert,
        debt=inert, debt_class=None if exact else vec,
        stuck=jnp.asarray(False),
        tx_bytes=zero, sent=zero, deferred=zero,
        events=jnp.asarray(0, jnp.int32))

    def masked_event(st, _):
        return event_step(packed, layout, cap, trace_cum, tail_s,
                          charge_cum, nominal_from, theta, window, alpha,
                          conf, radio, adaptive, parametric, enable_fast,
                          has_burn, has_send,
                          st, active=st.i < s_real, plan=plan_idx), None

    st = lax.while_loop(
        lambda st: st.i < s_real,
        lambda st: lax.scan(masked_event, st, None, length=chunk)[0],
        st0)
    out = dict(live=st.live, reboots=st.reboots, dead=st.dead,
               classes=st.classes, wasted=st.wasted, stuck=st.stuck,
               rem=st.rem, belief=cap + zero if exact else st.bhat,
               tx_bytes=st.tx_bytes, msgs_sent=st.sent,
               msgs_deferred=st.deferred, events=st.events)
    if exact:
        del out["dead"]
    return out


def event_slots(events, chunk: int, shards: int = 1) -> int:
    """Lane-event slots that the batched loop of :func:`event_replay`
    executes for a vmapped batch whose lanes were active in ``events``
    events each: every shard of the batch (its contiguous ``1/shards`` of
    the lanes) runs the outer ``while_loop`` until its slowest lane is
    done, ``chunk`` events a trip for every lane of the shard, so a shard
    costs its lanes x ``chunk`` x its largest ``ceil(events / chunk)``.
    ``sum(events)`` over this is the share of the loop's work that was
    not masked."""
    ev = np.asarray(events, np.int64).reshape(shards, -1)
    trips = -(-ev.max(axis=1, initial=0) // chunk)
    return int(ev.shape[1] * chunk * trips.sum())


# ==========================================================================
# Pallas kernel: one lane per grid step
# ==========================================================================

def _lane_kernel(*refs, keys, n_row_refs, shared_rows, adaptive,
                 parametric, enable_fast, has_burn, has_send, chunk):
    row_refs = refs[:n_row_refs]
    (cap_ref, rem0_ref, tc_ref, ts_ref, cc_ref, nf_ref, sr_ref, th_ref,
     wi_ref, al_ref, cf_ref, rd_ref, live_ref, rb_ref, dead_ref, cls_ref,
     waste_ref, stuck_ref, rem_ref, bel_ref, txb_ref, snt_ref,
     dfr_ref) = refs[n_row_refs:]
    if shared_rows:
        rows = {k: r[...] for k, r in zip(keys, row_refs)}
    else:
        rows = {k: r[0] for k, r in zip(keys, row_refs)}
    out = event_replay(rows, cap_ref[0], rem0_ref[0], tc_ref[0],
                       ts_ref[0], cc_ref[0], nf_ref[0], sr_ref[0],
                       th_ref[0], wi_ref[0], al_ref[0],
                       adaptive=adaptive, parametric=parametric,
                       enable_fast=enable_fast, has_burn=has_burn,
                       has_send=has_send, conf=cf_ref[0],
                       radio=rd_ref[...], chunk=chunk)
    live_ref[0] = out["live"]
    rb_ref[0] = out["reboots"]
    dead_ref[0] = out["dead"]
    cls_ref[0, :] = out["classes"]
    waste_ref[0] = out["wasted"]
    stuck_ref[0] = out["stuck"]
    rem_ref[0] = out["rem"]
    bel_ref[0] = out["belief"]
    txb_ref[0] = out["tx_bytes"]
    snt_ref[0] = out["msgs_sent"]
    dfr_ref[0] = out["msgs_deferred"]


def pallas_replay(rows, caps, rem0, trace_cum, tail_s, charge_cum,
                  nominal_from, s_real, theta, window, alpha,
                  conf=None, radio=None, *,
                  adaptive: bool, parametric: bool, shared_rows: bool,
                  enable_fast: bool = True, has_burn: bool = True,
                  has_send: bool = False,
                  chunk: int = EVENT_CHUNK, interpret: bool = False) -> dict:
    """The fused replay as a Pallas kernel: grid over lanes, one program
    per lane running the scalar ``event_replay`` with the plan broadcast
    (``shared_rows``) or blocked per lane.  Scalar sweep knobs travel as
    (1,)-shaped operands.  Only ``interpret=True`` runs: the Pallas
    interpreter executes the kernel body, which is how the differential
    harness validates it against the XLA path.  Compiling it raises
    ``ValueError`` before lowering, because every operand and output is
    float64 and Mosaic cannot tile float64 blocks."""
    from jax.experimental import pallas as pl

    if not interpret:
        raise ValueError(
            "the Pallas lane replay kernel cannot be compiled: its "
            "operands and outputs are float64, which Mosaic (the TPU "
            "Pallas compiler) cannot lower; run it with interpret=True, "
            "or replay with backend='xla'")

    keys = tuple(sorted(rows))
    n_lanes = caps.shape[0]
    f64 = jnp.float64

    row_specs, row_args = [], []
    for k in keys:
        v = jnp.asarray(rows[k])
        if shared_rows:
            row_specs.append(
                pl.BlockSpec(v.shape,
                             lambda i, nd=v.ndim: (0,) * nd))
        else:
            row_specs.append(
                pl.BlockSpec((1,) + v.shape[1:],
                             lambda i, nd=v.ndim: (i,) + (0,) * (nd - 1)))
        row_args.append(v)

    lane = pl.BlockSpec((1,), lambda i: (i,))
    tc = jnp.asarray(trace_cum)
    cc = jnp.asarray(charge_cum)
    scalar = pl.BlockSpec((1,), lambda i: (0,))
    if conf is None:
        conf = jnp.zeros((n_lanes,), f64)
    if radio is None:
        radio = jnp.zeros((N_RADIO,), f64)
    in_specs = row_specs + [
        lane, lane,
        pl.BlockSpec((1, tc.shape[1]), lambda i: (i, 0)),
        lane,
        pl.BlockSpec((1, cc.shape[1]), lambda i: (i, 0)),
        lane, lane, scalar, scalar, scalar,
        lane, pl.BlockSpec((N_RADIO,), lambda i: (0,))]
    out_specs = [lane, lane, lane,
                 pl.BlockSpec((1, _N_CLASSES), lambda i: (i, 0)),
                 lane, lane, lane, lane, lane, lane, lane]
    out_shape = [jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes, _N_CLASSES), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), jnp.bool_),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64),
                 jax.ShapeDtypeStruct((n_lanes,), f64)]

    kernel = functools.partial(
        _lane_kernel, keys=keys, n_row_refs=len(keys),
        shared_rows=shared_rows, adaptive=adaptive, parametric=parametric,
        enable_fast=enable_fast, has_burn=has_burn, has_send=has_send,
        chunk=chunk)
    (live, reboots, dead, classes, wasted, stuck, rem, belief,
     tx_bytes, msgs_sent, msgs_deferred) = \
        pl.pallas_call(kernel, grid=(n_lanes,), in_specs=in_specs,
                       out_specs=out_specs, out_shape=out_shape,
                       interpret=interpret)(
            *row_args, jnp.asarray(caps), jnp.asarray(rem0), tc,
            jnp.asarray(tail_s), cc,
            jnp.asarray(nominal_from),
            jnp.asarray(s_real),
            jnp.asarray(theta, f64).reshape(1),
            jnp.asarray(window, f64).reshape(1),
            jnp.asarray(alpha, f64).reshape(1),
            jnp.asarray(conf, f64), jnp.asarray(radio, f64))
    return dict(live=live, reboots=reboots, dead=dead, classes=classes,
                wasted=wasted, stuck=stuck, rem=rem, belief=belief,
                tx_bytes=tx_bytes, msgs_sent=msgs_sent,
                msgs_deferred=msgs_deferred)
