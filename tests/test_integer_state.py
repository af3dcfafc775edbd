"""The replay's integer state: the fixed-policy stochastic event stream
runs on int64 whole cycles where its arithmetic is provably integral, and
gives every channel the float64 state gives, bit for bit.

* ``floor_div`` / ``ceil_div``, the integer state's exact division,
  against Python's integer division;
* ``event_replay`` on the same lanes with int64 and with float64
  operands: SONIC, a parametric TAILS plan, a plan with BURN rows, charge
  traces that run out, and continuous-power (infinite-capacity) lanes;
* the compiled loop of the integer replay holds no float64;
* which state the dispatch chooses (``config_out["state"]``).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Conv2D, DenseFC, MaxPool2D, SimNet, SparseFC,
                        build_plan, custom_power_system)
from repro.core import fleetsim
from repro.kernels.charge_replay import (ceil_div, default_event_chunk,
                                         event_replay, floor_div)
from repro.runtime.failures import (charge_capacity_jitter,
                                    charge_trace_cumulative)
from repro.runtime.radio import N_RADIO, SEND_POLICIES, RadioModel


@pytest.fixture(scope="module")
def small_net():
    rng = np.random.default_rng(0)
    net = SimNet([
        Conv2D(rng.normal(size=(3, 1, 3, 3)).astype(np.float32),
               rng.normal(size=3).astype(np.float32)),
        MaxPool2D(2),
        DenseFC((rng.normal(size=(8, 75)) * 0.1).astype(np.float32),
                rng.normal(size=8).astype(np.float32)),
        SparseFC((rng.normal(size=(5, 8)) * (rng.random((5, 8)) < 0.35)
                  ).astype(np.float32),
                 rng.normal(size=5).astype(np.float32), relu=False),
    ], input_shape=(1, 12, 12), name="intstate")
    x = rng.normal(size=(1, 12, 12)).astype(np.float32)
    return net, x


# ==========================================================================
# Exact division
# ==========================================================================

HI = (1 << 21) - 1
#: (numerator, divisor): multiples of the divisor, one below, divisor 1,
#: zero divisors, numerators near 2**40 (quotients under and over the
#: cap) and near the infinite-capacity sentinel, small negatives.
DIV_CASES = [
    (0, 7), (7, 7), (6, 7), (8, 7), (21, 7), (20, 7), (700, 7), (699, 7),
    (1, 1), (0, 1), (12345, 1), (HI, 1), (HI + 5, 1),
    (5, 0), (0, 0), (-3, 0),
    (-1, 7), (-7, 7), (-8, 7), (-1000, 7), (-(1 << 40), 3),
    ((1 << 40), (1 << 20) - 3), ((1 << 40) - 1, (1 << 20) - 3),
    ((1 << 40) + 1, 1 << 19), ((1 << 40) - 1, 1 << 19),
    ((1 << 40), 3), ((1 << 40) - 1, 854),
    ((1 << 29) * 1999, (1 << 29) - 1), ((1 << 29) - 2, (1 << 29) - 1),
    ((1 << 62) - 12345, 45), ((1 << 62) - 1, (1 << 29) + 7),
]


def _py_floor(a, b, hi):
    q = (hi if a > 0 else (0 if a == 0 else -1)) if b == 0 else a // b
    return max(-1, min(q, hi))


def test_floor_div_matches_python_integer_division():
    a = np.array([c[0] for c in DIV_CASES], np.int64)
    b = np.array([c[1] for c in DIV_CASES], np.int64)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(floor_div)(jnp.asarray(a), jnp.asarray(b),
                                            jnp.int64(HI)))
        got32 = np.asarray(jax.jit(floor_div)(
            jnp.asarray(a), jnp.asarray(np.clip(b, 0, (1 << 31) - 1),
                                        jnp.int32), jnp.int32(HI)))
    want = [_py_floor(int(x), int(y), HI) for x, y in zip(a, b)]
    assert got.dtype == np.int64
    assert got.tolist() == want
    assert got32.tolist() == want          # an int32 divisor


def test_floor_div_is_exact_over_a_dense_range():
    rng = np.random.default_rng(1)
    b = rng.integers(1, 1 << 29, 4096)
    q = rng.integers(0, HI, 4096)
    a = q * b + rng.integers(-2, 3, 4096) * rng.integers(0, 2, 4096) \
        + rng.integers(0, 2, 4096) * (b - 1)
    hi = rng.integers(1, HI + 1, 4096)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(floor_div)(jnp.asarray(a), jnp.asarray(b),
                                            jnp.asarray(hi)))
    want = [_py_floor(int(x), int(y), int(h)) for x, y, h in zip(a, b, hi)]
    assert got.tolist() == want


def test_ceil_div_matches_python_integer_division():
    cases = [(0, 1), (1, 1), (7, 7), (8, 7), (6, 7), (HI, 1), (HI, HI),
             (HI - 1, 2), (1, HI), (14, 7), (15, 7)]
    a = np.array([c[0] for c in cases], np.int64)
    b = np.array([c[1] for c in cases], np.int64)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(ceil_div)(jnp.asarray(a), jnp.asarray(b),
                                           jnp.int64(HI)))
    assert got.tolist() == [min(-(-int(x) // int(y)), HI)
                            for x, y in zip(a, b)]


def test_float_state_divides_as_before():
    """The float64 state keeps its floor/ceil of a true division."""
    a = np.array([7.0, 6.0, -1.0, 5.5, 1e6])
    b = np.array([7.0, 7.0, 7.0, 2.0, 1e-30])
    with jax.enable_x64(True):
        fl = np.asarray(floor_div(jnp.asarray(a), jnp.asarray(b), 1))
        ce = np.asarray(ceil_div(jnp.asarray(a), jnp.asarray(b), 1))
    np.testing.assert_array_equal(fl, np.floor(a / b))
    np.testing.assert_array_equal(ce, np.ceil(a / b))


# ==========================================================================
# The integer state against the float64 state
# ==========================================================================

LANES = 48


def _lanes(plan, caps, trace_len, seed):
    """Per-lane capacities, initial charges and charge traces; the last
    four lanes run on continuous power (infinite capacity and charge, a
    zero trace, as the chunk pipeline's pad lanes)."""
    rng = np.random.default_rng(seed)
    caps = np.asarray(caps, np.float64)
    rem0 = caps * rng.uniform(0.05, 1.0, LANES)
    ccum = charge_trace_cumulative(charge_capacity_jitter(
        LANES, trace_len, caps, seed=seed, cv=0.4))
    caps[-4:], rem0[-4:], ccum[-4:] = np.inf, np.inf, 0.0
    return caps, rem0, ccum


def _replay(plan, caps, rem0, ccum, state, s_real):
    rows = fleetsim._bucket_rows(fleetsim._plan_rows(plan), lane_axis=False)
    p = fleetsim._stochastic_prep(rows, caps, rem0, ccum, False,
                                  fused=state == "int64", adaptive=False,
                                  has_send=False, belief_alpha=0.0)
    assert p.state.name == state
    rows = fleetsim._rows_as(rows, p.state)
    has_burn = bool(np.any(plan.kind == fleetsim.KIND_BURN))
    fn = jax.vmap(functools.partial(
        event_replay, adaptive=False, parametric=plan.parametric,
        enable_fast=p.enable_fast, has_burn=has_burn,
        chunk=default_event_chunk(rows["kind"].shape[0])),
        in_axes=(None, 0, 0, 0, 0, 0, 0, 0, None, None, None))
    with jax.enable_x64(True):
        out = jax.jit(fn)(
            {k: jnp.asarray(v) for k, v in rows.items()},
            jnp.asarray(p.caps), jnp.asarray(p.rem0),
            jnp.zeros((LANES, 1)), jnp.zeros(LANES),
            jnp.asarray(p.charge_cum), jnp.asarray(p.nominal_from),
            jnp.asarray(s_real), jnp.float64(0.5), jnp.float64(1.0),
            jnp.float64(0.0))
    return p, {k: np.asarray(v) for k, v in out.items()}


SCENARIOS = {
    # name: (strategy, capacity or per-lane capacities, parametric, trace)
    "sonic": ("sonic", 2e3, False, 64),
    "tails-parametric": ("tails", (3e3, 5e3, 8e3, 2e4, 1e6), True, 64),
    "tails-burn": ("tails", 3e3, False, 64),
    "sonic-trace-runs-out": ("sonic", 2e3, False, 4),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_integer_state_equals_float_state(small_net, name):
    strategy, cap, parametric, trace_len = SCENARIOS[name]
    net, x = small_net
    plan = build_plan(net, x, strategy,
                      custom_power_system(np.max(cap)),
                      parametric=parametric)
    caps = np.resize(np.asarray(cap, np.float64), LANES)
    if parametric:
        assert np.any(plan.kind == fleetsim.KIND_CALIB)
    if name == "tails-burn":
        assert np.any(plan.kind == fleetsim.KIND_BURN)
    caps, rem0, ccum = _lanes(plan, caps, trace_len, seed=len(name))
    # A continuous lane walks the plan, except a BURN plan's: there it
    # is inert (s_real 0), as the pipeline's pad lanes are, since a BURN
    # row takes a refill from the lane's trace, zero here.
    s_real = np.full(LANES, len(plan), np.int32)
    if name == "tails-burn":
        s_real[-4:] = 0
    pf, ref = _replay(plan, caps, rem0, ccum, "float64", s_real)
    pi, got = _replay(plan, caps, rem0, ccum, "int64", s_real)
    assert pi.enable_fast == pf.enable_fast
    assert "dead" not in got
    assert {k: v.dtype.name for k, v in got.items()} == {
        **{k: "int64" for k in ref if k not in ("dead", "stuck", "events")},
        "stuck": "bool", "events": "int32"}
    # the host's conversion back: float64, the sentinel as inf
    host = fleetsim._lane_results(dict(got), LANES, np.zeros((LANES, 1)),
                                  np.zeros(LANES), has_send=False)
    for k in ref:
        if k == "dead":
            continue
        want = ref[k]
        have = got[k] if k in ("stuck", "events") else host[k]
        assert have.dtype == want.dtype, k
        assert np.array_equal(have, want), (k, have, want)
    # the scenario did what it names
    assert (ref["reboots"][:-4] > 0).mean() > 0.75
    assert np.isinf(ref["rem"][-4:]).all() and np.isinf(
        ref["belief"][-4:]).all()
    assert (ref["reboots"][-4:] == 0).all() and ref["stuck"][-4:].sum() == 0
    if name != "tails-burn":
        assert (ref["live"][-4:] > 0).all()
    if name == "sonic-trace-runs-out":
        assert ref["reboots"].max() > trace_len


def test_integer_replay_loop_holds_no_float64(small_net):
    """The compiled event loop of the fixed-policy stochastic replay has
    no float64 value in it: a float literal that promoted the int64
    state would put one there."""
    net, x = small_net
    plan = build_plan(net, x, "tails", custom_power_system(1e6),
                      parametric=True)
    caps = np.resize(np.asarray((3e3, 8e3, 1e6)), LANES)
    caps, rem0, ccum = _lanes(plan, caps, 16, seed=5)
    rows = fleetsim._bucket_rows(fleetsim._plan_rows(plan), lane_axis=False)
    p = fleetsim._stochastic_prep(rows, caps, rem0, ccum, False, fused=True,
                                  adaptive=False, has_send=False,
                                  belief_alpha=0.0)
    rows = fleetsim._rows_as(rows, p.state)
    fn = fleetsim._jit_replay(True, False, True, True, "xla",
                              default_event_chunk(rows["kind"].shape[0]),
                              True, True, False)
    with jax.enable_x64(True):
        args = ({k: jnp.asarray(v) for k, v in rows.items()},
                jnp.asarray(p.caps), jnp.asarray(p.rem0),
                jnp.zeros((LANES, 1)), jnp.zeros(LANES),
                jnp.asarray(p.charge_cum), jnp.asarray(p.nominal_from),
                jnp.full(LANES, len(plan), jnp.int32), jnp.float64(0.5),
                jnp.float64(1.0), jnp.float64(0.0), jnp.zeros(LANES),
                jnp.zeros(N_RADIO))
        hlo = fn.lower(*args).compiler_ir("hlo").as_hlo_text()
    comps = _computations(hlo)
    loops = _reachable(comps, re.findall(r"body=(%?[\w.\-]+)", hlo))
    assert loops, "no while loop in the replay"
    for name in loops:
        assert "f64" not in comps[name], (name, comps[name][:2000])
    assert "s64" in "".join(comps[n] for n in loops)


def _computations(hlo: str) -> dict:
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*$", line)
        if m:
            name = m.group(1).lstrip("%")
            comps[name] = ""
        elif name is not None:
            comps[name] += line + "\n"
    return comps


def _reachable(comps: dict, roots) -> set:
    seen, todo = set(), [r.lstrip("%") for r in roots]
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.add(n)
        todo += [c.lstrip("%") for c in re.findall(
            r"(?:body|condition|to_apply|calls|"
            r"true_computation|false_computation)=(%?[\w.\-]+)",
            comps[n])]
        for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                comps[n]):
            todo += [c.strip().lstrip("%") for c in group.split(",")]
    return seen


# ==========================================================================
# Which state the dispatch chooses
# ==========================================================================

def _state_of(plan, radio=None, **kw):
    n = 16
    rng = np.random.default_rng(2)
    caps = np.full(n, plan.capacity)
    rem0 = caps * rng.uniform(0.1, 1.0, n)
    ccum = charge_trace_cumulative(charge_capacity_jitter(
        n, 8, plan.capacity, seed=2, cv=0.3))
    if kw.pop("steady", False):
        ccum = None
    if radio is not None:
        plan = fleetsim.with_uplink(plan)
    cfg: dict = {}
    fleetsim._run_replay(fleetsim._plan_rows(plan), caps, rem0,
                         shared_rows=True, charge_cum=ccum,
                         n_rows=len(plan), config_out=cfg, radio=radio,
                         **kw)
    return cfg["state"]


def test_dispatch_chooses_int64_for_the_fixed_stochastic_replay(small_net):
    plan = build_plan(*small_net, "sonic", custom_power_system(2e3))
    assert _state_of(plan) == "int64"
    assert _state_of(build_plan(*small_net, "tails", "1mF",
                                parametric=True)) == "int64"


@pytest.mark.parametrize("case", ["adaptive", "send", "ewma", "steady",
                                  "while", "fractional-capacity"])
def test_dispatch_keeps_float64_elsewhere(small_net, case):
    plan = build_plan(*small_net, "sonic", custom_power_system(2e3))
    kw = {"adaptive": dict(policy="adaptive", batch_rows=2),
          "send": dict(radio=(RadioModel(), SEND_POLICIES[0])),
          "ewma": dict(belief_alpha=0.25),
          "steady": dict(steady=True),
          "while": dict(backend="_while"),
          "fractional-capacity": {}}[case]
    if case == "fractional-capacity":
        plan = build_plan(*small_net, "sonic", custom_power_system(2000.5))
    assert _state_of(plan, **kw) == "float64"


@pytest.mark.parametrize("policy", ["fixed", "adaptive"])
def test_partial_chunk_of_a_burn_plan_completes(small_net, policy):
    """The chunk pipeline's pad lanes are inert (s_real 0): a walked one
    took a BURN row's refill from its zero charge trace, and its float64
    state turned to NaN and never finished.  Both states now give what
    one chunk of every lane gives."""
    from repro.core import fleet_sweep

    plan = build_plan(*small_net, "tails", custom_power_system(3e3))
    assert np.any(plan.kind == fleetsim.KIND_BURN)
    kw = dict(plan=plan, n_devices=40, seed=1, trace_reboots=8,
              charge_cv=0.25, charge_reboots=16, policy=policy,
              batch_rows=2 if policy == "adaptive" else 1)
    whole = fleet_sweep(lane_chunk=40, **kw)
    streamed = fleet_sweep(lane_chunk=32, prefetch=1, **kw)
    for ch in ("reboots", "live_s", "wasted_cycles", "belief_cycles"):
        assert np.array_equal(getattr(streamed, ch), getattr(whole, ch)), ch
