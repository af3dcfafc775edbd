"""Compiles for a described TPU v5e: the main path's kernels at real widths.

Nothing runs on a chip here.  JAX compiles each program for a v5e chip that
is described, not attached, and the TPU compiler raises what it would raise
on the chip: a Mosaic kernel it cannot lower, a tile that does not fit, a
program larger than the chip's 16 GB.  The topology is described inside a
module-scoped fixture (never at import), because only one process at a time
may load the TPU library.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


class _Captured(Exception):
    pass


def test_fleet_replay_compiles(one_chip, no_compile_cache, monkeypatch):
    """The fused replay (``_jit_replay``, float64 state) for the
    compressed HAR network's SONIC plan, 256 lanes of a streamed
    ``reduce="stats"`` sweep under the stochastic energy model, fits one
    chip."""
    import repro.core.fleetsim as fs
    from benchmarks.paper_figs import compressed_net
    from repro.core import fleet_sweep

    real = fs._jit_replay
    seen = {}

    def capture(*key):
        def call(*args):
            seen["key"], seen["args"] = key, args
            raise _Captured
        return call

    monkeypatch.setattr(fs, "_jit_replay", capture)
    net = compressed_net("har")
    x = np.random.default_rng(0).normal(
        size=net.input_shape).astype(np.float32)
    with pytest.raises(_Captured):
        fleet_sweep(net, x, "sonic", "1mF", n_devices=256, lane_chunk=256,
                    seed=0, trace_reboots=64, charge_cv=0.25,
                    charge_reboots=256, reduce="stats")
    with jax.enable_x64(True):
        specs = jax.tree_util.tree_map(
            lambda a: _spec(np.shape(a), jnp.asarray(a).dtype, one_chip),
            seen["args"])
        assert specs[1].shape == (256,)          # caps: one per lane
        compiled = real(*seen["key"]).lower(*specs).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


def test_pallas_lane_replay_refused_before_lowering():
    """``backend="pallas"`` off interpret mode raises the stated error
    instead of reaching Mosaic, which cannot tile its float64 blocks."""
    from repro.kernels.charge_replay import pallas_replay

    with pytest.raises(ValueError, match="float64"):
        pallas_replay({}, np.zeros(1), np.zeros(1), np.zeros((1, 1)),
                      np.zeros(1), np.zeros((1, 1)), np.zeros(1),
                      np.zeros(1, np.int32), 0.5, 1.0, 0.0,
                      adaptive=False, parametric=False, shared_rows=True)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_flash_attention_compiles_at_qwen3_widths(one_chip,
                                                  no_compile_cache):
    """qwen3-0.6b attention: 16 heads of 128 over 4,096 tokens, bf16."""
    from repro.kernels.ops import flash_attention

    q = _spec((1, 16, 4096, 128), jnp.bfloat16, one_chip)
    c = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 interpret=False), q, q, q)
    assert "tpu_custom_call" in c.as_text()


def test_dense_matmul_compiles_at_qwen3_mlp_widths(one_chip,
                                                   no_compile_cache):
    """One qwen3-0.6b MLP projection: 4,096 tokens x 1,024 -> 3,072."""
    from repro.kernels.ops import dense_matmul

    c = _compile(lambda x, w: dense_matmul(x, w, interpret=False),
                 _spec((4096, 1024), jnp.bfloat16, one_chip),
                 _spec((1024, 3072), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_fir_conv1d_compiles(one_chip, no_compile_cache):
    """The TAILS FIR kernel over 1,024 channels of 4,096 samples."""
    from repro.kernels.ops import fir_conv1d

    c = _compile(lambda x, t: fir_conv1d(x, t, interpret=False),
                 _spec((1024, 4096), jnp.float32, one_chip),
                 _spec((1024, 5), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()
