"""BENCHMARK.json against the benchmark's files and its contract: every
cell resolves by name, names and units keep to their characters, every
per-layer metric moves an end-to-end metric its cells report, the harness
refuses to run off a TPU, and a configuration, traffic mix, driver kind
or metric is added by new files alone."""

import json
import re
import shutil

import pytest
from rehearsal import own_run_settings  # noqa: F401 (autouse)

from bench import run

SPEC = run.load_spec()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(cell):
    c = run.resolve(SPEC, cell)
    assert c.chips in (1, 4)
    assert callable(c.driver.run)
    assert c.config["kind"] and c.traffic


def test_names_units_and_keys():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.fullmatch(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.fullmatch(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in SPEC["configs"]:
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    assert len(set(n for _, n in names)) == len(names)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    four = sum(int(w["chips"]) == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_every_per_layer_metric_moves_what_its_cells_report():
    layers = {}
    for m in SPEC["per_layer"]:
        e2e = {e["name"]: e for e in SPEC["end_to_end"]}
        assert m["moves"] in e2e
        cells = m.get("workloads", [w["name"] for w in SPEC["workloads"]])
        for cell in cells:
            reported = {e["name"] for e in SPEC["end_to_end"]
                        if run.applies(e, cell, set())}
            assert m["moves"] in reported, (m["name"], cell)
        assert (run.ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) <= {"device", "replay kernel", "sweep host pipeline"}


def test_refuses_to_run_off_a_tpu(capsys):
    rc = run.main(["--workload", SPEC["workloads"][0]["name"], "--seed",
                   "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err
    assert not [ln for ln in out.out.splitlines() if ln.startswith("{")]


def test_unknown_device_has_no_peaks():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.peaks_for("TPU v99")


STUB_DRIVER = '''
"""A driver that does no work: it hands back a fixed result."""
from bench import trace_reduce


class _Ev:
    def __init__(self, name, a, b):
        self.name, self.start_ns, self.duration_ns = name, a, b - a


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def run(cell, jax, devices, *, seed, seconds, trace, t0, note):
    planes = [_Plane("/host:CPU", [_Line("python", [
                  _Ev("$stub.py:1 window", 0, 1000)])]),
              _Plane("/device:TPU:0", [_Line("XLA Modules", [
                  _Ev("m", 0, 600)])])]
    return {"correct": True, "attempted": 1, "failed": 0,
            "metrics": {"sim_lanes_per_s": 1.0 + cell.traffic["rate"],
                        "setup_s": 2.0},
            "memory_peak_bytes": 1,
            "checks": {"stub": (0, 0)},
            "layer_ctx": {"trace": trace_reduce.Trace(planes, "window"),
                          "chunks": 1, "lane_events": 10.0,
                          "bytes_per_event": 4}}
'''


def test_a_new_cell_needs_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(run.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads(json.dumps(SPEC))
    (root / "bench" / "configs" / "stub-config.json").write_text(
        json.dumps({"kind": "stub"}))
    (root / "bench" / "traffic" / "stub-mix.json").write_text(
        json.dumps({"rate": 41.0}))
    (root / "bench" / "drivers" / "stub.py").write_text(STUB_DRIVER)
    (root / "bench" / "metrics" / "stub.busy_ms.py").write_text(
        "def read(ctx):\n    return ctx['trace'].busy_s() * 1e3\n")
    spec["configs"].append({"name": "stub-config", "source": "none",
                            "file": "bench/configs/stub-config.json",
                            "reduced": [], "why": "a stub"})
    spec["workloads"].append({"name": "stub.cell", "config": "stub-config",
                              "traffic": "stub-mix", "chips": 1,
                              "why": "a stub"})
    spec["per_layer"].append({"name": "stub.busy_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "sim_lanes_per_s",
                              "workloads": ["stub.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = run.run_cell("stub.cell", 1, 1.0, False, require_tpu=False,
                       root=root)
    assert res["metrics"]["sim_lanes_per_s"]["value"] == 42.0
    traced = run.run_cell("stub.cell", 1, 1.0, True, require_tpu=False,
                          root=root)
    assert traced["metrics"] == {"stub.busy_ms": {
        "value": pytest.approx(0.6e-3), "unit": "ms"}}
    assert traced["device"]["busy_s"] == pytest.approx(600e-9)
    assert list(traced)[-1] == "checks"
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_configuration_pins_the_plan_of_every_seed(config):
    """The plan that ``build_plan`` gives has the configuration's pinned
    digest at any seed, and the lane-events are counted from it."""
    import numpy as np

    from bench import events
    from bench.drivers import fleet
    from repro.core import build_plan

    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    cfg = json.loads((run.ROOT / entry["file"]).read_text())
    for seed in (3, 2**33 + 5):
        net = fleet.build_network(cfg["network"], seed)
        x = np.random.default_rng([seed, 1]).normal(
            size=net.input_shape).astype(np.float32)
        plan = build_plan(net, x, cfg["strategy"], cfg["power"])
        digest = events.plan_digest(events.plan_rows(plan))
        assert events.plan_fields_off(digest, cfg["plan"]) == 0, digest
    assert cfg["plan"]["rows"] == len(plan)


def test_exact_bits_of_the_replay_types():
    from bench.drivers import fleet

    assert fleet.exact_bits("float64") == 53
    assert fleet.exact_bits("int64") == 63
    assert fleet.exact_bits("float32") == 24
    assert fleet.exact_bits("int32") == 31
    assert fleet.exact_bits("bool") > 64
