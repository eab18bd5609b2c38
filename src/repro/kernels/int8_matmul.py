"""Pallas TPU kernel: centered int8 matmul (paper Eq. 1, TPU-native).

    y[b, n] = sum_k x[b, k] * w_off[k, n]  +  (sum_k x[b, k]) * centers[n]

int8 operands feed the MXU (int8 x int8 -> int32); the rank-1 center term
is a VPU epilogue fused into the final K step. Tiled over (B, N, K) with
MXU-aligned blocks; the x-tile, w-tile, accumulator and row-sum scratch all
live in VMEM.

VMEM budget at defaults (bm=256, bk=512, bn=256):
  x tile 256*512 int8 = 128 KiB, w tile 512*256 int8 = 128 KiB,
  acc 256*256 int32 = 256 KiB, rowsum 256*1 int32 = 1 KiB  -> ~0.5 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256


def _kernel(x_ref, w_ref, c_ref, o_ref, acc_ref, xsum_ref, *, n_k: int):
    """Grid: (B/bm, N/bn, K/bk) — K innermost so the accumulator stays hot."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    x = x_ref[...]
    acc_ref[...] += jax.lax.dot(
        x, w_ref[...], preferred_element_type=jnp.int32)
    xsum_ref[...] += x.astype(jnp.int32).sum(axis=1, keepdims=True)

    @pl.when(k == n_k - 1)
    def _epilogue():
        centers = c_ref[...].astype(jnp.int32)  # (1, bn)
        o_ref[...] = acc_ref[...] + xsum_ref[...] * centers


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def centered_int8_matmul(x_q: jnp.ndarray, w_off: jnp.ndarray,
                         centers: jnp.ndarray, *,
                         bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                         bn: int = DEFAULT_BN,
                         interpret: bool = True) -> jnp.ndarray:
    """x_q (B, K) int8, w_off (K, N) int8, centers (N,) int32 -> (B, N) int32.

    Shapes are padded up to block multiples; zero padding is exact for this
    contraction (zero rows/cols contribute nothing, including to rowsum).
    """
    B, K = x_q.shape
    K2, N = w_off.shape
    assert K == K2, (K, K2)
    bm, bk, bn = min(bm, _rup(B, 8)), min(bk, _rup(K, 128)), min(bn, _rup(N, 128))
    Bp, Kp, Np = _rup(B, bm), _rup(K, bk), _rup(N, bn)
    x_p = jnp.pad(x_q, ((0, Bp - B), (0, Kp - K)))
    w_p = jnp.pad(w_off, ((0, Kp - K), (0, Np - N)))
    c_p = jnp.pad(centers.astype(jnp.int32), (0, Np - N))[None, :]  # (1, Np)
    n_k = Kp // bk
    grid = (Bp // bm, Np // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
        ],
        interpret=interpret,
        name="centered_int8_matmul",
    )(x_p, w_p, c_p)
    return out[:B, :N]


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
