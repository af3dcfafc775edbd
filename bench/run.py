"""Run one benchmark cell on the accelerator and print its result.

  python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json``; the configuration's ``kind`` names the driver module
under ``bench/drivers/``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` traces the window with the JAX profiler and reports
its per-layer metrics, each read by ``bench/metrics/<metric>.py``.

Earlier lines of stdout are ``# ...`` notes (set-up split, window size,
compilations in the window); the last line is one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
optionally ``breakdown``, and last ``checks``: every number that decides
``correct`` beside its limit.  The same numbers end standard error.  A
run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

#: Set-up is timed from here, the first statement of the process's work.
T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at a fixed path in the checkout
#: (the path is part of the cache key), unless the environment names one.
CACHE_DIR = ROOT / ".jax_cache"
#: libtpu flags added for a traced run (see ``run_cell``).
TRACE_FLAGS = "--xla_enable_hlo_trace=false"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file, as run
    traffic: dict       # the traffic file
    driver: object      # module bench/drivers/<kind>.py


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its files and driver found by
    name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench.run: no workload {workload!r} in "
                         f"BENCHMARK.json ({', '.join(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    driver = load_module(root / "bench" / "drivers" / f"{cfg['kind']}.py")
    return Cell(cell["name"], int(cell["chips"]), cfg, traffic, driver)


def load_module(path: Path):
    """A module of the benchmark found by its file name (metric names
    hold dots, so these are loaded from their paths)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT):
    """The reader ``bench/metrics/<name>.py`` of a per-layer metric."""
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({', '.join(table)})")
    return table[device_kind]


def enable_compile_cache(jax) -> str:
    """Keep every compiled program in the persistent cache, so that only
    the first run of a cell in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def claim_devices(jax, chips: int, require_tpu: bool) -> list:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def applies(metric: dict, cell: str, reported: set) -> bool:
    """Whether a metric belongs in this cell's result line."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in reported
    return True


def note(text: str) -> None:
    print(f"# {text}", flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: Path = ROOT,
             t0: float = T0) -> dict:
    """One run of one cell; returns the result line's object."""
    sys.path.insert(0, str(root / "src"))
    spec = load_spec(root)
    cell = resolve(spec, workload, root)
    if trace:
        # Op-level trace points of a TPU program write millions of events
        # a second of replay, and collecting them outlasts a run; a traced
        # run compiles the program without them (a separate entry in the
        # compile cache, whose key holds these flags) and reads the
        # module-level device events.
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            [os.environ.get("LIBTPU_INIT_ARGS", ""), TRACE_FLAGS]).strip()

    import jax

    cache = enable_compile_cache(jax)
    devices = claim_devices(jax, cell.chips, require_tpu)
    note(f"devices {[d.device_kind for d in devices]} cache {cache}")
    out = cell.driver.run(cell, jax, devices, seed=seed, seconds=seconds,
                          trace=trace, t0=t0, note=note)

    e2e = [m for m in spec["end_to_end"] if applies(m, cell.name, set())]
    reported = {m["name"] for m in e2e}
    metrics = {}
    if trace:
        ctx = dict(out["layer_ctx"], peaks=lambda: peaks_for(
            devices[0].device_kind, root))
        for m in spec["per_layer"]:
            if not applies(m, cell.name, reported):
                continue
            value = load_reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        tr = out["layer_ctx"]["trace"]
        if require_tpu and not tr.busy_s() > 0:
            raise RuntimeError("the trace holds no device activity")
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_device_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
