"""Structural HLO cost parser: validated against hand-computed cases.

These tests run in a subprocess with 8 forced host devices so the main
pytest process keeps its single real device (the dry-run-only rule).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.launch import hlo_costs

out = {}
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

# 1) nested scan: 3 x 5 = 15 matmuls of 64^3
W = jnp.zeros((64, 64), jnp.float32)
def inner(c, _): return c @ W, None
def outer(c, _):
    y, _ = lax.scan(inner, c, None, length=5)
    return y, None
def f(x):
    y, _ = lax.scan(outer, x, None, length=3)
    return y
c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
r = hlo_costs.analyze(c.as_text())
out["nested_flops"] = r.flops
out["nested_unresolved"] = r.unresolved_while

# 2) sharded row-parallel matmul: exact per-device flops + all-reduce bytes
def g(x, w):
    return x @ w
xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
ws = jax.ShapeDtypeStruct((128, 128), jnp.float32)
c2 = jax.jit(g, in_shardings=(NamedSharding(mesh, P("data", "model")),
                              NamedSharding(mesh, P("model", None)))
             ).lower(xs, ws).compile()
r2 = hlo_costs.analyze(c2.as_text())
out["sharded_flops"] = r2.flops
out["ar_bytes"] = r2.collectives.get("all-reduce", 0.0)

# 3) collective inside a scan body is multiplied by the trip count
def h(x, w):
    def step(c, _):
        return jnp.tanh(c @ w), None
    y, _ = lax.scan(step, x, None, length=7)
    return y
c3 = jax.jit(h, in_shardings=(NamedSharding(mesh, P("data", "model")),
                              NamedSharding(mesh, P("model", None)))
             ).lower(xs, ws).compile()
r3 = hlo_costs.analyze(c3.as_text())
out["scan_ar_bytes"] = r3.collectives.get("all-reduce", 0.0)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, src],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_nested_scan_trip_counts(results):
    assert results["nested_flops"] == 15 * 2 * 64**3
    assert results["nested_unresolved"] == 0


def test_sharded_per_device_flops(results):
    # lhs (32,32) x rhs (32,128) per device = 2*32*32*128
    assert results["sharded_flops"] == 2 * 32 * 32 * 128


def test_allreduce_bytes_exact(results):
    # partial-sum output (32,128) f32 = 16384 bytes
    assert results["ar_bytes"] == 32 * 128 * 4


def test_collective_inside_scan_multiplied(results):
    assert results["scan_ar_bytes"] == 7 * 32 * 128 * 4
