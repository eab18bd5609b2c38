"""Pallas TPU kernel: the *whole* RAELLA exact datapath in one launch.

``sliced_crossbar.py`` fuses the (input-slice x weight-slice) contraction
but still expects pre-sliced inputs, leaves the digital center term to a
separate einsum, and throws the saturation counts away. This kernel goes
the rest of the way: one ``pallas_call`` performs

  1. temporal input slicing — the 8b input block is loaded once per
     (batch, segment) and the i-th slice is cropped *in-kernel* with a
     shift+mask (no (n_i, B, R) slice tensor ever materializes in HBM);
  2. the slice-plane matmul per 512-row crossbar segment (int8 MXU dots
     whenever every input-slice width is < 8);
  3. the per-segment signed ADC: an integer clamp to [adc_lo, adc_hi] —
     bit-identical to ``core.adc.convert`` at noise 0, because in-range
     column sums are < 2^24 so the float32 round there is exact;
  4. the digital shift+accumulate via a per-(i, j) multiplier table
     ``mults[i, j] = valid_j << (l_i + l_j)`` — ragged per-site plans
     (``slice_shifts`` / ``slice_valid`` from ``models.pim_compile``)
     just zero the padding multipliers, and zero planes clamp to 0, so
     the padding contract holds inside the kernel too;
  5. the digital center term ``phi * sum(x)``, accumulated once per
     segment from the already-resident input block;
  6. ADC saturation counting (clamp hit either bound), masked to the
     true (B, C) extent so tile padding never inflates the counters.

Everything downstream (``core.crossbar.forward`` stats, ``core.energy``,
``CompiledPim.report``) keys off the outputs, so the kernel returns both
the psum block and the scalar saturation count.

Grid: (B/bm, C/bn, n_seg, n_i, n_j) — output revisited across the last
three axes, accumulating in a VMEM scratch (per-chunk carries; column
sums never round-trip to HBM). The input block's index map ignores
(c, i, j), so Pallas keeps it resident while all slices are cropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS_PER_XBAR = 512
DEFAULT_BM = 128
DEFAULT_BN = 256
# whole-array SMEM operand: the per-slice scalar tables and the counters,
# indexed by grid position (a TPU block's last two dims must tile (8, 128)
# or span the array, which a (1, 1) VMEM block of a table does not)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(x_ref, w_ref, li_ref, mask_ref, mult_ref, cen_ref,
            o_ref, sat_ref, acc_ref, *,
            n_seg: int, n_i: int, n_j: int, adc_lo: int, adc_hi: int,
            bm: int, bn: int, b_true: int, c_true: int, narrow: bool):
    b = pl.program_id(0)
    c = pl.program_id(1)
    s = pl.program_id(2)
    i = pl.program_id(3)
    j = pl.program_id(4)
    first = (s == 0) & (i == 0) & (j == 0)

    @pl.when(first)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first & (b == 0) & (c == 0))
    def _init_sat():
        sat_ref[0, 0] = 0

    x = x_ref[...]  # (bm, rows_per_xbar) int32, unsigned 8b codes

    # digital center term: phi * sum_r(x), once per (b, c, s)
    @pl.when((i == 0) & (j == 0))
    def _center():
        acc_ref[...] += x.sum(axis=1, keepdims=True) * cen_ref[0]

    # temporal input slicing, in-register: (x >> l_i) & ((1 << w_i) - 1)
    x_i = jax.lax.shift_right_logical(x, li_ref[0, i]) & mask_ref[0, i]
    if narrow:  # every slice value < 128 -> int8 x int8 MXU dot
        cs = jax.lax.dot(x_i.astype(jnp.int8), w_ref[0],
                         preferred_element_type=jnp.int32)
    else:
        cs = jax.lax.dot(x_i, w_ref[0].astype(jnp.int32),
                         preferred_element_type=jnp.int32)
    cs = jnp.clip(cs, adc_lo, adc_hi)  # the per-segment signed ADC

    # saturation counter, masked to the true (B, C) extent
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + b * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + c * bn
    in_bounds = (rows < b_true) & (cols < c_true)
    sat = ((cs == adc_lo) | (cs == adc_hi)) & in_bounds
    sat_ref[0, 0] += sat.astype(jnp.int32).sum()

    acc_ref[...] += cs * mult_ref[i, j]  # digital shift+add

    last = (s == n_seg - 1) & (i == n_i - 1) & (j == n_j - 1)

    @pl.when(last)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "adc_lo", "adc_hi", "bm", "bn", "rows_per_xbar", "narrow", "interpret"))
def fused_crossbar(x_u8: jnp.ndarray, w_planes: jnp.ndarray,
                   in_li: jnp.ndarray, in_mask: jnp.ndarray,
                   mults: jnp.ndarray, centers: jnp.ndarray, *,
                   adc_lo: int = -64, adc_hi: int = 63,
                   bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   rows_per_xbar: int = ROWS_PER_XBAR,
                   narrow: bool = True,
                   interpret: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused exact-datapath forward.

    x_u8:     (B, R) int32 — unsigned 8b input codes (R = true rows).
    w_planes: (n_j, Rp, C) int8 — signed slice planes, Rp a multiple of
              ``rows_per_xbar`` >= R (zero row padding is exact).
    in_li:    (n_i,) int32 — per input slice, the low bit index l_i.
    in_mask:  (n_i,) int32 — per input slice, (1 << width_i) - 1.
    mults:    (n_i, n_j) int32 — recombination multipliers; 0 kills a
              padded weight slice entirely.
    centers:  (n_seg, C) int32 — per-segment Center+Offset phi.
    narrow:   every input-slice width < 8 (values fit int8) — lets the
              slice dots run int8 x int8 on the MXU.

    Returns (psum (B, C) int32 including the center term,
             saturations () int32 — ADC clamps that hit either bound).
    """
    B, R = x_u8.shape
    n_j, Rp, C = w_planes.shape
    assert Rp % rows_per_xbar == 0 and Rp >= R, (Rp, R)
    n_seg = Rp // rows_per_xbar
    n_i = in_li.shape[0]
    bm = min(bm, _rup(B, 8))
    bn = min(bn, _rup(C, 128))
    Bp, Cp = _rup(B, bm), _rup(C, bn)
    x_p = jnp.pad(x_u8.astype(jnp.int32), ((0, Bp - B), (0, Rp - R)))
    w_p = jnp.pad(w_planes, ((0, 0), (0, 0), (0, Cp - C)))
    cen_p = jnp.pad(centers.astype(jnp.int32), ((0, 0), (0, Cp - C)))
    grid = (Bp // bm, Cp // bn, n_seg, n_i, n_j)
    psum, sats = pl.pallas_call(
        functools.partial(_kernel, n_seg=n_seg, n_i=n_i, n_j=n_j,
                          adc_lo=adc_lo, adc_hi=adc_hi, bm=bm, bn=bn,
                          b_true=B, c_true=C, narrow=narrow),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, rows_per_xbar), lambda b, c, s, i, j: (b, s)),
            pl.BlockSpec((1, rows_per_xbar, bn), lambda b, c, s, i, j: (j, s, c)),
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((1, 1, bn), lambda b, c, s, i, j: (s, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda b, c, s, i, j: (b, c)),
            _SMEM,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Cp), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_p, w_p,
      in_li.astype(jnp.int32).reshape(1, n_i),
      in_mask.astype(jnp.int32).reshape(1, n_i),
      mults.astype(jnp.int32), cen_p[:, None, :])
    return psum[:B, :C], sats[0, 0]


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
