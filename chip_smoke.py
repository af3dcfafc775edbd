"""Smoke run of the system's two entry paths on a TPU, checked on the chip.

Phases, all driven from this one process:

* ``fleet``: the paper's compressed MNIST network, built from seeds,
  replayed by ``fleet_sweep`` for SONIC and TAILS at 1 mF under the
  stochastic energy model at the fleet benchmark's settings, with
  ``reduce="stats"`` over ``lane_chunk``-sized chunks: 2**20 lanes per
  strategy, or as many chunks as fit ``SWEEP_BUDGET_S`` (an earlier line
  says so).  Checked in the same process: (a) the first 64 lanes, replayed
  with ``reduce="none"``, against the pure-Python reference interpreter
  (``tests/reference_replay.py``) on every channel, bit for bit; (b) the
  streamed statistics of an 8,192-lane sweep against
  ``stats_from_outputs`` over that sweep's ``reduce="none"`` outputs.
* ``serve``: qwen3-0.6b at its published widths in bf16, random weights
  from ``--seed``, 4 requests of 128 prompt tokens and 16 new tokens
  through ``ServeEngine``; the engine's logits at the last prompt position
  are checked against ``forward`` in float32 on the same weights.
* ``--chips 4`` (alone): the meshed sweep over four chips against the same
  sweep unmeshed on device 0; the two ``FleetStats`` must be identical.

Earlier lines of stdout are one JSON record per phase or check.  The last
line is ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it, and so does a run that finds no TPU.

  python chip_smoke.py                    # one TPU chip
  python chip_smoke.py --chips 4          # four chips: the meshed fleet
  JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for sub in ("src", "", "tests"):
    sys.path.insert(0, str(ROOT / sub))


@dataclasses.dataclass(frozen=True)
class Sizes:
    lanes: int            # per strategy, main sweep
    lane_chunk: int       # also the lanes of check (b)
    ref_lanes: int        # (a): lanes compared with the reference
    mesh_lanes: int       # --chips 4
    mesh_chunk: int
    requests: int
    prompt_len: int
    max_new: int
    full_model: bool


#: On a v5e the replay runs in emulated float64 at a few hundred lanes
#: per second, so chunks of 8,192 lanes keep each call short; check (b)
#: is one such chunk, on the main sweep's compiled replay.  The meshed
#: comparison streams two chunks, so it also merges chunk statistics.
CHIP = Sizes(lanes=1 << 20, lane_chunk=8192, ref_lanes=64,
             mesh_lanes=8192, mesh_chunk=4096, requests=4, prompt_len=128,
             max_new=16, full_model=True)
REHEARSAL = Sizes(lanes=4096, lane_chunk=1024, ref_lanes=8,
                  mesh_lanes=2048, mesh_chunk=1024, requests=4,
                  prompt_len=8, max_new=4, full_model=False)

#: The stochastic energy model of the fleet benchmark's stoch traffic.
STRATEGIES = ("sonic", "tails")
POWER = "1mF"
FLEET_KW = dict(trace_reboots=64, charge_cv=0.25, charge_reboots=256,
                recharge_cv=0.25)
#: Wall-clock share of the script's 1,200 s given to each strategy's
#: main sweep; a sweep projected not to fit runs fewer chunks.
SWEEP_BUDGET_S = 60.0

#: bf16 serving vs the float32 forward: a bf16 activation carries 8
#: mantissa bits (relative rounding 2**-8 = 0.4%) and 28 layers compound
#: it, so the logits are held to 5% relative RMS error and 10% of the
#: largest logit in any one entry.
SERVE_RMS_RTOL = 0.05
SERVE_MAX_RTOL = 0.10


def emit(record: dict) -> None:
    print(json.dumps(record, default=float), flush=True)


def device_record(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# fleet
# --------------------------------------------------------------------------

def fleet_inputs(seed: int, plan, n: int):
    """Lanes ``[0, n)`` of a chunked ``fleet_sweep`` as the reference
    interpreter takes them, rebuilt from the same counter-based samplers
    (lane ``i`` draws the same inputs in any chunk of any sweep)."""
    import numpy as np

    from repro.runtime.failures import (charge_capacity_jitter_stream,
                                        charge_trace_cumulative,
                                        harvest_jitter_stream,
                                        initial_charge_fraction_stream,
                                        reboot_recharge_times_stream,
                                        recharge_trace_cumulative)

    frac = initial_charge_fraction_stream(n, seed=seed)
    jm = harvest_jitter_stream(n, seed=seed, cv=FLEET_KW["recharge_cv"])
    tr = reboot_recharge_times_stream(n, FLEET_KW["trace_reboots"],
                                      plan.recharge_s, seed=seed)
    ctr = charge_capacity_jitter_stream(
        n, FLEET_KW["charge_reboots"], plan.capacity, seed=seed,
        cv=FLEET_KW["charge_cv"])
    return (plan.capacity * frac, plan.recharge_s * jm,
            recharge_trace_cumulative(tr * jm[:, None]),
            charge_trace_cumulative(ctr))


def channel_diffs(got: dict, want: dict, channels) -> dict:
    """Per channel: lanes that differ bitwise, and the largest absolute
    and relative difference among them."""
    import numpy as np

    diffs = {}
    for ch in channels:
        g = np.asarray(got[ch], np.float64)
        w = np.asarray(want[ch], np.float64)
        bad = ~((g == w) | (np.isnan(g) & np.isnan(w)))
        if bad.any():
            d = np.abs(g[bad] - w[bad])
            diffs[ch] = {"lanes": int(np.count_nonzero(bad.reshape(
                len(g), -1).any(axis=1))),
                "max_abs": float(d.max()),
                "max_rel": float((d / np.maximum(np.abs(w[bad]),
                                                 1e-300)).max())}
    return diffs


REF_CHANNELS = ("stuck", "reboots", "live", "wasted", "belief", "classes",
                "dead", "tx_bytes", "msgs_sent", "msgs_deferred")


def check_reference(plan, outputs: dict, seed: int, n: int) -> dict:
    """(a): every channel of lanes ``[0, n)`` against the reference."""
    import numpy as np
    from reference_replay import reference_replay

    from repro.core.fleetsim import _plan_rows

    rows = _plan_rows(plan)
    rem0, tail, cum, ccum = fleet_inputs(seed, plan, n)
    refs = [reference_replay(rows, plan.capacity, rem0[i], tail_s=tail[i],
                             recharge_cum=cum[i], charge_cum=ccum[i])
            for i in range(n)]
    want = {ch: np.asarray([r[ch] for r in refs]) for ch in REF_CHANNELS}
    got = {ch: np.asarray(outputs[ch])[:n] for ch in REF_CHANNELS}
    return channel_diffs(got, want, REF_CHANNELS)


def stats_diffs(a, b) -> dict:
    """Every statistic of two ``FleetStats``, compared bitwise."""
    import numpy as np

    from repro.core import STAT_CHANNELS

    got = {"count": a.count, "completed": a.completed,
           "class_sums": a.class_sums}
    want = {"count": b.count, "completed": b.completed,
            "class_sums": b.class_sums}
    for ch in STAT_CHANNELS:
        for k in ("sums", "sumsqs", "mins", "maxs", "hists"):
            got[f"{ch}:{k}"] = getattr(a, k)[ch]
            want[f"{ch}:{k}"] = getattr(b, k)[ch]
    return channel_diffs({k: np.atleast_1d(v) for k, v in got.items()},
                         {k: np.atleast_1d(v) for k, v in want.items()},
                         got)


def mnist_fleet(seed: int):
    import numpy as np

    from benchmarks.paper_figs import compressed_net

    net = compressed_net("mnist")
    x = np.random.default_rng(seed).normal(
        size=net.input_shape).astype(np.float32)
    return net, x


def fleet_phase(jax, sz: Sizes, seed: int) -> list[str]:
    import numpy as np

    from repro.core import build_plan, fleet_sweep, stats_from_outputs

    failed = []
    net, x = mnist_fleet(seed)
    for strategy in STRATEGIES:
        plan, build_s = timed(build_plan, net, x, strategy, POWER)
        kw = dict(plan=plan, seed=seed, **FLEET_KW)
        chunk = sz.lane_chunk
        one = dict(n_devices=chunk, lane_chunk=chunk, **kw)
        # One chunk streamed (compile included) and the same chunk
        # materialized (warm: the two share one compiled replay); check (b)
        # compares them and (a) reads the first lanes of the second.
        ss, cold_s = timed(fleet_sweep, reduce="stats", **one)
        rn, chunk_s = timed(fleet_sweep, **one)
        n_chunks = sz.lanes // chunk
        fit = max(1, int(SWEEP_BUDGET_S // max(chunk_s, 1e-9)))
        if fit < n_chunks:
            emit({"phase": "fleet", "strategy": strategy,
                  "reduced_lanes": fit * chunk, "planned_lanes": sz.lanes,
                  "reason": f"one {chunk}-lane chunk took {chunk_s:.3f} s "
                            f"warm; {n_chunks} chunks would overrun the "
                            f"{SWEEP_BUDGET_S:.0f} s sweep budget"})
            n_chunks = fit
        lanes = n_chunks * chunk
        st, sweep_s = timed(fleet_sweep, n_devices=lanes, lane_chunk=chunk,
                            reduce="stats", **kw)
        s = st.summary()
        ok_sweep = (st.count[0] == lanes and s["completed"] > 0
                    and all(np.isfinite(st.sums[ch][0]).all()
                            for ch in st.sums))
        emit({"phase": "fleet", "strategy": strategy, "power": POWER,
              "plan_rows": len(plan), "lanes": lanes, "lane_chunk": chunk,
              "build_plan_s": build_s, "compile_s": cold_s - chunk_s,
              "chunk_warm_s": chunk_s, "sweep_warm_s": sweep_s,
              "lanes_per_s": lanes / sweep_s,
              "completed": s["completed"], "mean_reboots": s["mean_reboots"],
              "mean_total_s": s["mean_total_s"],
              "peak_bytes_in_use": peak_bytes(jax), "ok": bool(ok_sweep)})
        if not ok_sweep:
            failed.append(f"fleet/{strategy}/sweep")

        bd = stats_diffs(ss, stats_from_outputs(rn.outputs, ss.edges))
        emit({"phase": "fleet", "check": "stats_vs_outputs",
              "strategy": strategy, "lanes": chunk, "diffs": bd,
              "ok": not bd})
        if bd:
            failed.append(f"fleet/{strategy}/stats_vs_outputs")

        # (a) every channel of the first lanes against the reference
        ad, ref_s = timed(check_reference, plan, rn.outputs, seed,
                          sz.ref_lanes)
        emit({"phase": "fleet", "check": "reference", "strategy": strategy,
              "lanes": sz.ref_lanes, "reference_s": ref_s, "diffs": ad,
              "ok": not ad})
        if ad:
            failed.append(f"fleet/{strategy}/reference")
    return failed


def mesh_phase(jax, sz: Sizes, seed: int, chips: int) -> list[str]:
    """The meshed sweep over ``chips`` devices vs the unmeshed sweep."""
    from repro.core import build_plan, fleet_sweep
    from repro.launch.mesh import make_fleet_mesh

    if len(jax.devices()) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"found {len(jax.devices())}")
    net, x = mnist_fleet(seed)
    plan = build_plan(net, x, "sonic", POWER)
    kw = dict(plan=plan, seed=seed, n_devices=sz.mesh_lanes,
              lane_chunk=sz.mesh_chunk, reduce="stats", **FLEET_KW)
    # one cold run each (compile included): four chips cost four times
    # as much per second, and only the comparison is needed here
    sm, mesh_s = timed(fleet_sweep, mesh=make_fleet_mesh(chips), **kw)
    su, one_s = timed(fleet_sweep, **kw)
    d = stats_diffs(sm, su)
    emit({"phase": "mesh", "strategy": "sonic", "chips": chips,
          "lanes": sz.mesh_lanes, "lane_chunk": sz.mesh_chunk,
          "mesh_cold_s": mesh_s, "one_chip_cold_s": one_s,
          "completed": int(sm.completed[0]), "diffs": d, "ok": not d})
    return ["mesh/stats_identical"] if d else []


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def serve_phase(jax, sz: Sizes, seed: int) -> list[str]:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import get_model
    from repro.serving import Request, ServeEngine

    cfg = get_config("qwen3-0.6b")
    if not sz.full_model:
        cfg = cfg.scaled_down(param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    api = get_model(cfg)
    params, init_s = timed(lambda: jax.block_until_ready(
        jax.jit(api.init_params, static_argnums=0)(cfg,
                                                   jax.random.key(seed))))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=sz.prompt_len).tolist()
               for _ in range(sz.requests)]
    with tempfile.TemporaryDirectory() as state_dir:
        eng = ServeEngine(cfg, params, state_dir,
                          max_len=sz.prompt_len + sz.max_new + 1)
        cold, cold_s = timed(eng.run, [Request(f"c{i}", p, sz.max_new)
                                       for i, p in enumerate(prompts)])
        warm, warm_s = timed(eng.run, [Request(f"w{i}", p, sz.max_new)
                                       for i, p in enumerate(prompts)])
        logits, _ = eng.prefill(prompts)
        logits = np.asarray(logits[:, :cfg.vocab_size], np.float32)
    answered = [cold[f"c{i}"] for i in range(sz.requests)]
    ok_answers = (all(len(t) == sz.max_new for t in answered)
                  and all(0 <= v < cfg.vocab_size for t in answered
                          for v in t)
                  and answered == [warm[f"w{i}"]
                                   for i in range(sz.requests)]
                  and [t[0] for t in answered]
                  == logits.argmax(-1).tolist())

    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: api.forward(cfg32, p, t))(
            p32, jnp.asarray(prompts, jnp.int32))
        ref = np.asarray(ref[:, -1, :cfg.vocab_size], np.float32)
    err = logits - ref
    rms_rel = float(np.sqrt((err ** 2).mean() / (ref ** 2).mean()))
    max_rel = float(np.abs(err).max() / np.abs(ref).max())
    ok_logits = rms_rel <= SERVE_RMS_RTOL and max_rel <= SERVE_MAX_RTOL
    emit({"phase": "serve", "arch": cfg.name, "layers": cfg.num_layers,
          "d_model": cfg.d_model, "dtype": cfg.compute_dtype,
          "requests": sz.requests, "prompt_len": sz.prompt_len,
          "max_new": sz.max_new, "init_s": init_s,
          "run_cold_s": cold_s, "run_warm_s": warm_s,
          "tokens_per_s_warm": sz.requests * sz.max_new / warm_s,
          "logits_rms_rel_err": rms_rel, "logits_max_rel_err": max_rel,
          "rms_rtol": SERVE_RMS_RTOL, "max_rtol": SERVE_MAX_RTOL,
          "answers_ok": bool(ok_answers), "logits_ok": bool(ok_logits),
          "peak_bytes_in_use": peak_bytes(jax)})
    return [] if ok_answers and ok_logits else ["serve"]


# --------------------------------------------------------------------------

def f64_probe(jax) -> None:
    """Record, without judging, whether the device's float64 arithmetic
    matches IEEE double on the host: transfers, + - * / and an in-jit
    scatter-add sum, each against numpy."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.random(1 << 16) * 1e3
    b = rng.random(1 << 16) * 1e3 + 1.0
    rec = {"phase": "f64_probe"}
    with jax.enable_x64(True):
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        rec["roundtrip_mismatch"] = int((np.asarray(ja) != a).sum())
        for name in ("add", "subtract", "multiply", "divide"):
            got = np.asarray(jax.jit(getattr(jnp, name))(ja, jb))
            rec[f"{name}_mismatch"] = int(
                (got != getattr(np, name)(a, b)).sum())
        gid = jnp.zeros(a.shape, jnp.int32)
        s = float(jax.jit(lambda v, g: jnp.zeros(1, jnp.float64)
                          .at[g].add(v))(ja, gid)[0])
    seq = float(np.bincount(np.zeros(a.shape, np.int64), weights=a)[0])
    rec.update(scatter_sum_equals_sequential=s == seq,
               scatter_sum_rel_to_exact=(s - math.fsum(a)) / math.fsum(a))
    emit(rec)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the meshed fleet against unmeshed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes, any platform (JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if args.cpu_rehearsal and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"pass --cpu-rehearsal for the tiny CPU run", file=sys.stderr)
        raise SystemExit(2)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sz = REHEARSAL if args.cpu_rehearsal else CHIP
    if args.chips > 1:
        failed = mesh_phase(jax, sz, args.seed, args.chips)
    else:
        f64_probe(jax)
        failed = fleet_phase(jax, sz, args.seed)
        failed += serve_phase(jax, sz, args.seed)
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": device_record(jax)}),
          flush=True)


if __name__ == "__main__":
    main()
