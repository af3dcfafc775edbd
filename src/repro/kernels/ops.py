"""Jitted public wrappers around the Pallas kernels.

Handles padding to block multiples, block-size calibration (the TAILS
analogue), and interpret-mode fallback on CPU (kernels target TPU; the
interpreter executes the same kernel body for validation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .calibrate import MatmulTiles, fir_tiles, matmul_tiles
from .dense_matmul import matmul as _matmul
from .fir_conv1d import fir_conv1d as _fir
from .flash_attention import flash_attention as _flash
from .sparse_fc import block_sparse_matvec as _bsmv, to_block_csr

_ON_TPU = None


def on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.devices()[0].platform == "tpu"
    return _ON_TPU


def _pad_to(x, mults):
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def dense_matmul(x, w, tiles: MatmulTiles | None = None,
                 interpret: bool | None = None):
    """x (M, K) @ w (K, N) through the tiled MXU kernel."""
    if interpret is None:
        interpret = not on_tpu()
    m, k = x.shape
    _, n = w.shape
    t = tiles or matmul_tiles(m, k, n, x.dtype.itemsize)
    bm = min(t.bm, m) or 1
    bk = min(t.bk, k) or 1
    bn = min(t.bn, n) or 1
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    out = _matmul(xp, wp, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:m, :n]


class BlockSparseFC:
    """Pruned FC layer compiled to the block-CSR kernel.

    Build once from the dense-with-zeros master weight; call on activations
    (N, K) -> (N, M)."""

    def __init__(self, w_dense: np.ndarray, bm: int = 128, bk: int = 128,
                 bn: int = 8):
        self.m, self.k = w_dense.shape
        self.bm, self.bk, self.bn = bm, bk, bn
        mp, kp = -(-self.m // bm) * bm, -(-self.k // bk) * bk
        wp = np.zeros((mp, kp), w_dense.dtype)
        wp[:self.m, :self.k] = w_dense
        self.vals, self.row_ptr, self.col_idx = to_block_csr(wp, bm, bk)
        self.padded_m, self.padded_k = mp, kp

    @property
    def density(self) -> float:
        nbr = (self.padded_m // self.bm) * (self.padded_k // self.bk)
        return self.vals.shape[0] / nbr

    def __call__(self, x, interpret: bool | None = None):
        if interpret is None:
            interpret = not on_tpu()
        n, k = x.shape
        assert k == self.k
        np_ = -(-n // self.bn) * self.bn
        xp = _pad_to(x, (self.bn, 1))
        xp = jnp.pad(xp, ((0, 0), (0, self.padded_k - k)))
        y = _bsmv(xp, self.vals, self.row_ptr, self.col_idx, self.padded_m,
                  bm=self.bm, bk=self.bk, bn=self.bn, interpret=interpret)
        return y[:n, :self.m]


def fir_conv1d(x, taps, interpret: bool | None = None):
    """Depthwise valid FIR conv: x (C, L), taps (C, K)."""
    if interpret is None:
        interpret = not on_tpu()
    c, length = x.shape
    cb = fir_tiles(c, length, x.dtype.itemsize)
    xp = _pad_to(x, (cb, 1))
    tp = _pad_to(taps, (cb, 1))
    out = _fir(xp, tp, cb=cb, interpret=interpret)
    return out[:c]


@functools.lru_cache(maxsize=None)
def _charge_replay_jit(adaptive: bool, parametric: bool,
                       shared_rows: bool, enable_fast: bool,
                       has_burn: bool, has_send: bool, chunk: int,
                       interpret: bool):
    from .charge_replay import pallas_replay
    return jax.jit(functools.partial(
        pallas_replay, adaptive=adaptive, parametric=parametric,
        shared_rows=shared_rows, enable_fast=enable_fast,
        has_burn=has_burn, has_send=has_send, chunk=chunk,
        interpret=interpret))


def charge_replay(rows, caps, rem0, trace_cum, tail_s, charge_cum,
                  nominal_from, s_real, theta, window, alpha,
                  conf=None, radio=None, *,
                  adaptive: bool, parametric: bool, shared_rows: bool,
                  enable_fast: bool = True, has_burn: bool = True,
                  has_send: bool = False, chunk: int = 128,
                  interpret: bool | None = None):
    """Fused stochastic charge-loop replay as a Pallas lane kernel (one
    grid step per device lane; ``repro.kernels.charge_replay``).  The
    default XLA event stream lives in ``repro.core.fleetsim``.  Off the
    TPU this runs in interpret mode; on the TPU it raises ``ValueError``,
    because Mosaic cannot lower the kernel's float64 state."""
    if interpret is None:
        interpret = not on_tpu()
    fn = _charge_replay_jit(adaptive, parametric, shared_rows,
                            enable_fast, has_burn, has_send, chunk,
                            interpret)
    return fn(rows, caps, rem0, trace_cum, tail_s, charge_cum,
              nominal_from, s_real, theta, window, alpha, conf, radio)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q: (B, H, Sq, d); k, v: (B, H, Sk, d) -- MHA layout (GQA callers
    expand KV first, as in models.layers.blockwise_attention)."""
    if interpret is None:
        interpret = not on_tpu()
    b, h, sq, d = q.shape
    _, _, sk, _ = k.shape
    bq_ = min(bq, sq)
    bk_ = min(bk, sk)
    pq = (-sq) % bq_
    pk = (-sk) % bk_
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))
                 ).reshape(b * h, sq + pq, d)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))
                 ).reshape(b * h, sk + pk, d)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))
                 ).reshape(b * h, sk + pk, d)
    out = _flash(qf, kf, vf, causal=causal, bq=bq_, bk=bk_, sk_valid=sk,
                 interpret=interpret)
    return out.reshape(b, h, sq + pq, d)[:, :, :sq, :]
