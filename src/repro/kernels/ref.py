"""Pure-jnp oracles for the Pallas kernels (ground truth for tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def centered_int8_matmul(x_q: jnp.ndarray, w_off: jnp.ndarray,
                         centers: jnp.ndarray) -> jnp.ndarray:
    """y = x_q @ w_off + rowsum(x_q) * centers   (all int32).

    x_q: (B, K) int8; w_off: (K, N) int8; centers: (N,) int32.
    The TPU-native form of the paper's Eq. 1: offsets on the MXU, the
    rank-1 center term digital.
    """
    acc = jnp.dot(x_q.astype(jnp.int32), w_off.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    xsum = x_q.astype(jnp.int32).sum(axis=-1, keepdims=True)
    return acc + xsum * centers[None, :].astype(jnp.int32)


def sliced_crossbar_matmul(x_slices: jnp.ndarray, w_planes: jnp.ndarray,
                           mults: jnp.ndarray, *,
                           rows_per_xbar: int = 512,
                           adc_lo: int = -64, adc_hi: int = 63) -> jnp.ndarray:
    """Bit-exact RAELLA crossbar contraction (the PIM-simulation hot spot).

    x_slices: (n_i, B, R) int8  — unsigned input-slice values (0..15).
    w_planes: (n_j, R, C) int8  — signed weight-slice values (-15..15).
    mults:    (n_i, n_j) int32  — 2**(l_i + l_j) recombination multipliers.
    Per 512-row segment, each (i, j) column sum is clamped by the ADC before
    the digital shift+add — the contraction is deliberately non-associative
    across segments (each segment has its own ADC).

    Returns (B, C) int32 psums of the offset term (no center term).
    """
    n_i, B, R = x_slices.shape
    n_j, _, C = w_planes.shape
    n_seg = -(-R // rows_per_xbar)
    pad = n_seg * rows_per_xbar - R
    x_p = jnp.pad(x_slices, ((0, 0), (0, 0), (0, pad)))
    w_p = jnp.pad(w_planes, ((0, 0), (0, pad), (0, 0)))
    xs = x_p.reshape(n_i, B, n_seg, rows_per_xbar)
    ws = w_p.reshape(n_j, n_seg, rows_per_xbar, C)
    out = jnp.zeros((B, C), jnp.int32)
    for i in range(n_i):
        for j in range(n_j):
            cs = jnp.einsum("bsr,src->bsc", xs[i].astype(jnp.int32),
                            ws[j].astype(jnp.int32),
                            preferred_element_type=jnp.int32)
            cs = jnp.clip(cs, adc_lo, adc_hi)  # per-segment ADC
            out = out + cs.sum(axis=1) * mults[i, j]
    return out


def fused_crossbar(x_u8: jnp.ndarray, w_planes: jnp.ndarray,
                   in_li: jnp.ndarray, in_mask: jnp.ndarray,
                   mults: jnp.ndarray, centers: jnp.ndarray, *,
                   rows_per_xbar: int = 512,
                   adc_lo: int = -64,
                   adc_hi: int = 63) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-XLA reference for ``fused_crossbar.fused_crossbar``.

    Same contract as the Pallas kernel: x_u8 (B, R) int32 unsigned 8b
    codes, w_planes (n_j, Rp, C) int8 with Rp a rows_per_xbar multiple,
    in_li / in_mask (n_i,) int32 input-slice crop tables, mults
    (n_i, n_j) int32 recombination multipliers (0 = padded slice),
    centers (n_seg, C) int32. Returns (psum (B, C) int32 including the
    digital center term, saturation count () int32).
    """
    B, R = x_u8.shape
    n_j, Rp, C = w_planes.shape
    n_seg = Rp // rows_per_xbar
    n_i = in_li.shape[0]
    xs = jnp.pad(x_u8.astype(jnp.int32), ((0, 0), (0, Rp - R)))
    xs = xs.reshape(B, n_seg, rows_per_xbar)
    ws = w_planes.reshape(n_j, n_seg, rows_per_xbar, C).astype(jnp.int32)
    out = jnp.einsum("bsr,sc->bc", xs, centers.astype(jnp.int32),
                     preferred_element_type=jnp.int32)  # center term
    sats = jnp.zeros((), jnp.int32)
    for i in range(n_i):
        x_i = jax.lax.shift_right_logical(xs, in_li[i]) & in_mask[i]
        for j in range(n_j):
            cs = jnp.einsum("bsr,src->bsc", x_i, ws[j],
                            preferred_element_type=jnp.int32)
            cs = jnp.clip(cs, adc_lo, adc_hi)  # per-segment ADC
            sats = sats + ((cs == adc_lo) | (cs == adc_hi)).sum()
            out = out + cs.sum(axis=1) * mults[i, j]
    return out, sats


def fused_spec_crossbar(x_u8: jnp.ndarray, w_planes: jnp.ndarray,
                        spec_li: jnp.ndarray, spec_mask: jnp.ndarray,
                        mults: jnp.ndarray, rmults: jnp.ndarray,
                        centers: jnp.ndarray, *,
                        rows_per_xbar: int = 512,
                        adc_lo: int = -64,
                        adc_hi: int = 63
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pure-XLA reference for ``fused_spec_crossbar.fused_spec_crossbar``.

    Speculation + recovery (paper §4.3): each spec slice i is converted
    once per weight plane j; conversions that clamp at an ADC bound are
    *failures* whose value is replaced by the 1b recovery recombination
    ``sum_t clip(x_bit_t @ w_j) * rmults[i, t]``. Failure counts are per
    spec slice (the host bills ``width_i`` recovery converts each);
    recovery saturations count only where recovery actually ran.

    Same contract as the Pallas kernel — see its docstring for the
    argument shapes. Returns (psum (B, C) int32, spec_failures (n_i,)
    int32, recovery_saturations () int32).
    """
    B, R = x_u8.shape
    n_j, Rp, C = w_planes.shape
    n_seg = Rp // rows_per_xbar
    n_i = spec_li.shape[0]
    max_w = rmults.shape[1]
    xs = jnp.pad(x_u8.astype(jnp.int32), ((0, 0), (0, Rp - R)))
    xs = xs.reshape(B, n_seg, rows_per_xbar)
    ws = w_planes.reshape(n_j, n_seg, rows_per_xbar, C).astype(jnp.int32)
    out = jnp.einsum("bsr,sc->bc", xs, centers.astype(jnp.int32),
                     preferred_element_type=jnp.int32)  # center term
    fails = []
    rsats = jnp.zeros((), jnp.int32)
    for i in range(n_i):
        x_i = jax.lax.shift_right_logical(xs, spec_li[i]) & spec_mask[i]
        fail_i = jnp.zeros((), jnp.int32)
        for j in range(n_j):
            cs = jnp.einsum("bsr,src->bsc", x_i, ws[j],
                            preferred_element_type=jnp.int32)
            cs = jnp.clip(cs, adc_lo, adc_hi)  # per-segment ADC
            sat = (cs == adc_lo) | (cs == adc_hi)
            fail_i = fail_i + sat.astype(jnp.int32).sum()
            rec = jnp.zeros_like(cs)
            for t in range(max_w):
                x_b = jax.lax.shift_right_logical(xs, spec_li[i] + t) & 1
                rcs = jnp.einsum("bsr,src->bsc", x_b, ws[j],
                                 preferred_element_type=jnp.int32)
                rcs = jnp.clip(rcs, adc_lo, adc_hi)
                rec = rec + rcs * rmults[i, t]
                r_sat = (rcs == adc_lo) | (rcs == adc_hi)
                cnt = (r_sat & sat).astype(jnp.int32).sum()
                rsats = rsats + jnp.where(rmults[i, t] > 0, cnt, 0)
            value = jnp.where(sat, rec, cs)
            out = out + value.sum(axis=1) * mults[i, j]
        fails.append(fail_i)
    return out, jnp.stack(fails), rsats


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: jnp.ndarray | int) -> jnp.ndarray:
    """One query per slot against a contiguous cache, softmax in f32.

    q: (B, 1, H, D); k, v: (B, Sk, K, D) with H = K * G; kv_len: int or
    (B,) valid entries. No chunk scan: it keeps the KV cache's sequence
    sharding intact (a scan would slice the sharded seq dim per step,
    forcing GSPMD to all-gather the whole cache every chunk); the softmax
    over the sharded seq dim lowers to partial max/sum + a tiny (B,H)
    all-reduce — flash-decode semantics.
    """
    B, _, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    kv_len_ = jnp.asarray(kv_len, jnp.int32)
    if kv_len_.ndim == 0:
        kv_len_ = jnp.broadcast_to(kv_len_[None], (B,))
    qg1 = q.reshape(B, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bckd->bkgc", qg1,
                   k.astype(jnp.float32)) * (D ** -0.5)
    kpos = jnp.arange(Sk)
    mask = kpos[None, :] < kv_len_[:, None]          # (B, Sk)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           kv_len: jnp.ndarray, *,
                           sentinel: int) -> jnp.ndarray:
    """Pure-XLA reference for ``paged_attention.paged_decode_attention``.

    Gathers each slot's whole ``max_blocks * block_size`` view through
    its table (sentinel entries read the scratch block, at or past
    ``kv_len``, so the mask zeroes them exactly) and attends over it:
    bit-identical to ``decode_attention`` over a contiguous cache of the
    same length. q (B, H, D) -> (B, H, D).
    """
    B, max_blocks = tables.shape
    view_shape = (B, max_blocks * k_pool.shape[1]) + k_pool.shape[2:]
    rows = jnp.clip(tables, 0, sentinel)
    k = k_pool[rows].reshape(view_shape)
    v = v_pool[rows].reshape(view_shape)
    return decode_attention(q[:, None], k, v, kv_len)[:, 0]
