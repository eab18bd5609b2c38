"""Kernel-backend registry + jit'd public wrappers for the Pallas kernels.

Every op is registered under one or more *backends*:

  xla         — the pure-jnp reference from ``ref`` (always available;
                what the 512-device dry-run lowers, since the host CPU
                backend does not lower Pallas TPU kernels).
  interpret   — the Pallas kernel in interpreter mode: bit-identical
                semantics on any backend, slow; what CI forces to catch
                kernel regressions without TPU runners.
  pallas-tpu  — the Pallas kernel lowered natively (requires a TPU).

Selection order, first match wins:

  1. the ``REPRO_KERNEL_BACKEND`` environment variable (CI override);
  2. the explicit ``backend=`` argument (plumbed from
     ``ArchConfig.pim_kernel_backend`` by the model dispatch path);
  3. ``auto``: ``pallas-tpu`` on TPU, else ``xla``.

``auto`` is the only alias. Every other name must be registered for the
op, or resolution raises: a request never falls silently to another
backend, so a run that asked for the chip's kernels gets them or fails.
"""

from __future__ import annotations

import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.dist.sharding import _active_mesh
from repro.kernels import fused_crossbar as _fx
from repro.kernels import fused_spec_crossbar as _fs
from repro.kernels import int8_matmul as _im
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref
from repro.kernels import sliced_crossbar as _sx

ENV_VAR = "REPRO_KERNEL_BACKEND"
BACKENDS = ("xla", "interpret", "pallas-tpu")

_REGISTRY: dict[str, dict[str, Callable]] = {}


def register(op: str, backend: str, fn: Callable) -> None:
    """Register ``fn`` as the ``backend`` implementation of ``op``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    _REGISTRY.setdefault(op, {})[backend] = fn


def backends(op: str) -> tuple[str, ...]:
    """Backends registered for ``op``."""
    return tuple(sorted(_REGISTRY[op]))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(op: str, request: str | None = None) -> str:
    """Resolve a backend request to a registered backend name for ``op``.

    ``request=None`` means ``auto``. Order: env override, request, auto.
    ``auto`` is ``pallas-tpu`` on TPU and ``xla`` elsewhere; any other
    name that is not registered for ``op`` raises ``ValueError``.
    """
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; have {sorted(_REGISTRY)}")
    name = os.environ.get(ENV_VAR) or request or "auto"
    if name == "auto":
        name = "pallas-tpu" if _on_tpu() else "xla"
    if name not in _REGISTRY[op]:
        raise ValueError(
            f"kernel backend {name!r} is not registered for {op!r}; "
            f"have {backends(op)} (or 'auto')")
    return name


def dispatch(op: str, request: str | None = None) -> Callable:
    return _REGISTRY[op][resolve_backend(op, request)]


# ------------------------------------------------------------------ ops
def centered_int8_matmul(x_q: jnp.ndarray, w_off: jnp.ndarray,
                         centers: jnp.ndarray, *,
                         backend: str | None = None) -> jnp.ndarray:
    """y_int32 = x_q @ w_off + rowsum(x_q) * centers (Eq. 1 fast path).

    ``backend`` follows the registry selection order."""
    return dispatch("centered_int8_matmul", backend)(x_q, w_off, centers)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           kv_len: jnp.ndarray, *, sentinel: int,
                           backend: str | None = None) -> jnp.ndarray:
    """One query token per slot over its paged KV blocks.

    q (B, H, D); k_pool, v_pool (pool_rows, block_size, K, D); tables
    (B, max_blocks) int32 pool rows, ``sentinel`` where unmapped; kv_len
    (B,) int32. Returns (B, H, D) in q's dtype. ``backend`` follows the
    registry selection order."""
    return dispatch("paged_decode_attention", backend)(
        q, k_pool, v_pool, tables, kv_len, sentinel=sentinel)


def sliced_crossbar_matmul(x_slices: jnp.ndarray, w_planes: jnp.ndarray,
                           mults: jnp.ndarray, *,
                           adc_lo: int = -64, adc_hi: int = 63,
                           rows_per_xbar: int = 512,
                           backend: str | None = None) -> jnp.ndarray:
    """RAELLA crossbar contraction with per-segment ADC clamp."""
    return dispatch("sliced_crossbar_matmul", backend)(
        x_slices, w_planes, mults, adc_lo=adc_lo, adc_hi=adc_hi,
        rows_per_xbar=rows_per_xbar)


def _input_bounds(input_slicing: tuple[int, ...],
                  total_bits: int = 8) -> list[tuple[int, int]]:
    """MSB-first (hi, lo) bit bounds — mirrors ``core.slicing.slice_bounds``
    (kept local so ``repro.kernels`` stays importable without ``repro.core``)."""
    if sum(input_slicing) != total_bits:
        raise ValueError(f"input slicing {input_slicing} must cover "
                         f"{total_bits} bits")
    out, hi = [], total_bits - 1
    for w in input_slicing:
        out.append((hi, hi - w + 1))
        hi -= w
    return out


def fused_crossbar_forward(x_u8: jnp.ndarray, planes: jnp.ndarray,
                           shifts, centers: jnp.ndarray, *,
                           input_slicing: tuple[int, ...],
                           adc_lo: int, adc_hi: int,
                           valid: jnp.ndarray | None = None,
                           rows_per_xbar: int = 512,
                           backend: str | None = None
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused exact-datapath forward: slice-plane matmul + per-segment ADC
    clamp + shift-and-accumulate + digital center term, one op.

    x_u8:     (B, R) unsigned 8b input codes (any int dtype).
    planes:   (n_j, n_seg, rows_per_xbar, C) int8 signed slice planes —
              the ``EncodedWeights.planes`` layout, possibly padded on
              the slice axis by the per-site compiler.
    shifts:   (n_j,) per-slice recombination shifts — a static tuple or
              a traced int32 array (ragged per-site plans).
    centers:  (n_seg, C) int32 Center+Offset phi.
    valid:    optional (n_j,) bool mask for padded slice planes; masked
              planes are zeroed and their multipliers killed, so the
              result is identical to running the unpadded encoding.

    Returns (psum (B, C) int32 including the center term, saturations
    () int32). Bit-exact vs the ``core.crossbar.forward`` Python loop at
    noise 0 for any ADC window containing 0 (the padding contract).
    """
    input_slicing = tuple(int(b) for b in input_slicing)
    bounds = _input_bounds(input_slicing)
    n_j, n_seg, rx, C = planes.shape
    if rx != rows_per_xbar:
        raise ValueError(f"planes rows {rx} != rows_per_xbar {rows_per_xbar}")
    if valid is not None:
        planes = planes * valid[:, None, None, None].astype(planes.dtype)
    w_flat = planes.reshape(n_j, n_seg * rows_per_xbar, C)
    in_li = jnp.asarray([lo for (_, lo) in bounds], jnp.int32)
    in_mask = jnp.asarray([(1 << (hi - lo + 1)) - 1 for (hi, lo) in bounds],
                          jnp.int32)
    shifts_arr = jnp.asarray(shifts, jnp.int32)
    mults = jnp.left_shift(jnp.int32(1),
                           in_li[:, None] + shifts_arr[None, :])
    if valid is not None:
        mults = mults * valid.astype(jnp.int32)[None, :]
    narrow = max(hi - lo + 1 for (hi, lo) in bounds) < 8
    fn = dispatch("fused_crossbar", backend)
    return fn(x_u8.astype(jnp.int32), w_flat, in_li, in_mask, mults,
              centers.astype(jnp.int32), adc_lo=adc_lo, adc_hi=adc_hi,
              rows_per_xbar=rows_per_xbar, narrow=narrow)


def fused_spec_crossbar_forward(x_u8: jnp.ndarray, planes: jnp.ndarray,
                                shifts, centers: jnp.ndarray, *,
                                spec_slicing: tuple[int, ...],
                                adc_lo: int, adc_hi: int,
                                valid: jnp.ndarray | None = None,
                                rows_per_xbar: int = 512,
                                backend: str | None = None
                                ) -> tuple[jnp.ndarray, jnp.ndarray,
                                           jnp.ndarray]:
    """Fused speculation/recovery forward (paper §4.3 Dynamic Input
    Slicing): speculative slice-plane matmul + per-segment ADC clamp +
    failure detection + 1b recovery converts + select + shift-and-
    accumulate + digital center term, one op.

    x_u8:     (B, R) unsigned 8b input codes (any int dtype).
    planes:   (n_j, n_seg, rows_per_xbar, C) int8 signed slice planes —
              the ``EncodedWeights.planes`` layout, possibly padded on
              the slice axis by the per-site compiler.
    shifts:   (n_j,) per-slice recombination shifts — a static tuple or
              a traced int32 array (ragged per-site plans).
    centers:  (n_seg, C) int32 Center+Offset phi.
    spec_slicing: the speculative input slicing, e.g. (4, 2, 2).
    valid:    optional (n_j,) bool mask for padded slice planes; masked
              planes are zeroed and their multipliers killed, so the
              psum is identical to running the unpadded encoding (work
              counters still see every plane — the Python-loop
              contract).

    Returns (psum (B, C) int32 including the center term,
    spec_failures (n_i,) int32 — failed conversions per spec slice, the
    analytic source for recovery-convert billing — and
    recovery_saturations () int32). Bit-exact vs the
    ``core.speculation.forward`` Python loop at noise 0 for any ADC
    window containing 0 (the padding contract).
    """
    spec_slicing = tuple(int(b) for b in spec_slicing)
    bounds = _input_bounds(spec_slicing)
    n_j, n_seg, rx, C = planes.shape
    if rx != rows_per_xbar:
        raise ValueError(f"planes rows {rx} != rows_per_xbar {rows_per_xbar}")
    if valid is not None:
        planes = planes * valid[:, None, None, None].astype(planes.dtype)
    w_flat = planes.reshape(n_j, n_seg * rows_per_xbar, C)
    spec_li = jnp.asarray([lo for (_, lo) in bounds], jnp.int32)
    spec_mask = jnp.asarray([(1 << (hi - lo + 1)) - 1 for (hi, lo) in bounds],
                            jnp.int32)
    shifts_arr = jnp.asarray(shifts, jnp.int32)
    mults = jnp.left_shift(jnp.int32(1),
                           spec_li[:, None] + shifts_arr[None, :])
    if valid is not None:
        mults = mults * valid.astype(jnp.int32)[None, :]
    widths = [hi - lo + 1 for (hi, lo) in bounds]
    max_w = max(widths)
    rmults = jnp.asarray([[(1 << t) if t < w else 0 for t in range(max_w)]
                          for w in widths], jnp.int32)
    narrow = max_w < 8
    fn = dispatch("fused_spec_crossbar", backend)
    return fn(x_u8.astype(jnp.int32), w_flat, spec_li, spec_mask, mults,
              rmults, centers.astype(jnp.int32), adc_lo=adc_lo, adc_hi=adc_hi,
              rows_per_xbar=rows_per_xbar, narrow=narrow)


# ------------------------------------------------------------- registry
def _whole_on_each_device(fn):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    mesh (``with mesh:``) the kernel runs whole on every device of it,
    its operands replicated. Outside a mesh, or on one device, the
    kernel is called as it is."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        mesh = _active_mesh()
        if mesh is None or mesh.size == 1:
            return fn(*args, **kwargs)
        P = jax.sharding.PartitionSpec
        return jax.shard_map(functools.partial(fn, **kwargs), mesh=mesh,
                             in_specs=(P(),) * len(args), out_specs=P(),
                             check_vma=False)(*args)
    return wrapped


def _pallas(fn, **kwargs):
    return _whole_on_each_device(functools.partial(fn, **kwargs))


def _drop_narrow(fn):
    """The XLA reference needs no narrow/int8 hint — accept and drop it."""
    @functools.wraps(fn)
    def wrapped(*args, narrow=True, **kwargs):
        del narrow
        return fn(*args, **kwargs)
    return wrapped


register("centered_int8_matmul", "xla", _ref.centered_int8_matmul)
register("centered_int8_matmul", "interpret",
         _pallas(_im.centered_int8_matmul, interpret=True))
register("centered_int8_matmul", "pallas-tpu",
         _pallas(_im.centered_int8_matmul, interpret=False))

register("sliced_crossbar_matmul", "xla", _ref.sliced_crossbar_matmul)
register("sliced_crossbar_matmul", "interpret",
         _pallas(_sx.sliced_crossbar_matmul, interpret=True))
register("sliced_crossbar_matmul", "pallas-tpu",
         _pallas(_sx.sliced_crossbar_matmul, interpret=False))

register("fused_crossbar", "xla", _drop_narrow(_ref.fused_crossbar))
register("fused_crossbar", "interpret",
         _pallas(_fx.fused_crossbar, interpret=True))
register("fused_crossbar", "pallas-tpu",
         _pallas(_fx.fused_crossbar, interpret=False))

register("fused_spec_crossbar", "xla", _drop_narrow(_ref.fused_spec_crossbar))
register("fused_spec_crossbar", "interpret",
         _pallas(_fs.fused_spec_crossbar, interpret=True))
register("fused_spec_crossbar", "pallas-tpu",
         _pallas(_fs.fused_spec_crossbar, interpret=False))

register("paged_decode_attention", "xla", _ref.paged_decode_attention)
register("paged_decode_attention", "interpret",
         _pallas(_pa.paged_decode_attention, interpret=True))
register("paged_decode_attention", "pallas-tpu",
         _pallas(_pa.paged_decode_attention, interpret=False))
