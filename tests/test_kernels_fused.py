"""Differential harness for the fused exact-datapath kernel.

``repro.kernels.fused_crossbar`` runs the whole RAELLA exact datapath
(in-kernel input slicing, slice-plane matmuls, per-segment signed ADC
clamp, shift-and-accumulate, digital center term, saturation counting)
in one launch. These tests lock it to three independent ground truths:

  1. the ``core.crossbar.forward`` Python loop (``backend='python'``) —
     the datapath the paper tables were produced with;
  2. the pure-jnp oracle ``kernels.ref.fused_crossbar``;
  3. standalone numpy loops written here (so a shared bug in the kernel
     *and* ``ref`` cannot hide).

Sweeps cover the 108 slicings on both operands, ADC bits 4..8, ragged
``slice_valid`` masks from adaptive per-site plans, and both interpret
and XLA backends — everything bit-exact, never approximate.
"""

import dataclasses
import os

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adc as adc_lib
from repro.core import center_offset as co
from repro.core import crossbar as xbar
from repro.core import pim_linear
from repro.core import slicing as sl
from repro.kernels import fused_crossbar as fx
from repro.kernels import ops, ref

BACKENDS = ("interpret", "xla")


def _mk_layer(rng, rows, cols, B, weight_slicing, mode="center"):
    w_u = rng.integers(0, 256, (rows, cols)).astype(np.int64)
    enc = co.encode(w_u, weight_slicing, mode=mode)
    x = jnp.asarray(rng.integers(0, 256, (B, rows)))
    return w_u, enc, x


def _np_fused(x, planes, shifts, centers, input_slicing, lo, hi,
              rows_per_xbar=512):
    """Independent numpy oracle: full datapath, plain loops."""
    x = np.asarray(x, np.int64)
    planes = np.asarray(planes, np.int64)  # (n_j, n_seg, R, C)
    centers = np.asarray(centers, np.int64)
    n_j, n_seg, R, C = planes.shape
    B = x.shape[0]
    xp = np.zeros((B, n_seg * R), np.int64)
    xp[:, :x.shape[1]] = x
    xs = xp.reshape(B, n_seg, R)
    psum = np.einsum("bsr,sc->bc", xs, centers)
    sats = 0
    hi_bit = 7
    for w in input_slicing:
        li = hi_bit - w + 1
        x_i = (xs >> li) & ((1 << w) - 1)
        for j in range(n_j):
            cs = np.einsum("bsr,src->bsc", x_i, planes[j])
            cv = np.clip(cs, lo, hi)
            sats += int(((cv == lo) | (cv == hi)).sum())
            psum = psum + cv.sum(axis=1) * (1 << (li + int(shifts[j])))
        hi_bit -= w
    return psum, sats


class TestFusedDifferential:
    """Hypothesis sweep: random layer shapes x the 108 slicings on both
    operands x ADC bits 4..8, fused (both backends) vs the Python
    datapath, the jnp oracle, and the numpy oracle."""

    @hypothesis.given(st.integers(0, 2 ** 31 - 1), st.integers(4, 8))
    @hypothesis.settings(max_examples=8, deadline=None)
    def test_vs_python_datapath_and_oracles(self, seed, adc_bits):
        rng = np.random.default_rng(seed)
        all_slicings = sl.enumerate_slicings()
        w_slicing = all_slicings[int(rng.integers(0, len(all_slicings)))]
        i_slicing = all_slicings[int(rng.integers(0, len(all_slicings)))]
        rows = int(rng.integers(1, 900))
        cols = int(rng.integers(1, 24))
        B = int(rng.integers(1, 5))
        _, enc, x = _mk_layer(rng, rows, cols, B, w_slicing)
        adc = adc_lib.ADCConfig(bits=adc_bits, signed=True)

        want, st_py = xbar.forward(x, enc, i_slicing, adc, backend="python")
        np_psum, np_sats = _np_fused(x, enc.planes, enc.shifts, enc.centers,
                                     i_slicing, adc.lo, adc.hi)
        np.testing.assert_array_equal(np.asarray(want, np.int64), np_psum)
        assert int(st_py.saturations) == np_sats
        for backend in BACKENDS:
            got, st_f = xbar.forward(x, enc, i_slicing, adc, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert int(st_f.saturations) == int(st_py.saturations)

    @hypothesis.given(st.integers(0, 2 ** 31 - 1))
    @hypothesis.settings(max_examples=6, deadline=None)
    def test_ragged_valid_masks(self, seed):
        """Adaptive per-site plans pad the slice axis: a padded encoding
        (zero planes + slice_valid mask + garbage padded shifts) must be
        bit-identical to the unpadded one, on every backend."""
        rng = np.random.default_rng(seed)
        all_slicings = sl.enumerate_slicings()
        w_slicing = all_slicings[int(rng.integers(0, len(all_slicings)))]
        rows = int(rng.integers(1, 700))
        cols = int(rng.integers(1, 16))
        _, enc, x = _mk_layer(rng, rows, cols, 3, w_slicing)
        n_s = enc.n_slices
        n_pad = int(rng.integers(1, 4))
        padded_planes = jnp.pad(jnp.asarray(enc.planes),
                                ((0, n_pad), (0, 0), (0, 0), (0, 0)))
        # padded shifts are arbitrary (the compiler writes 0; any value
        # must be inert because the multiplier is masked to 0)
        pad_shifts = rng.integers(0, 8, n_pad)
        shifts = jnp.asarray(list(enc.shifts) + list(pad_shifts), jnp.int32)
        valid = jnp.asarray([True] * n_s + [False] * n_pad)

        want, _ = ops.fused_crossbar_forward(
            x, jnp.asarray(enc.planes), jnp.asarray(enc.shifts, jnp.int32),
            jnp.asarray(enc.centers), input_slicing=(1,) * 8,
            adc_lo=-64, adc_hi=63, backend="xla")
        for backend in BACKENDS:
            got, sats = ops.fused_crossbar_forward(
                x, padded_planes, shifts, jnp.asarray(enc.centers),
                input_slicing=(1,) * 8, adc_lo=-64, adc_hi=63,
                valid=valid, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_vs_ref_oracle_direct(self, backend):
        """The registry's low-level impls agree with ``ref.fused_crossbar``
        on the raw (pre-wrapped) contract."""
        rng = np.random.default_rng(17)
        x = jnp.asarray(rng.integers(0, 256, (4, 1024)), jnp.int32)
        w = jnp.asarray(rng.integers(-15, 16, (3, 1024, 40)), jnp.int8)
        in_li = jnp.asarray([4, 2, 0], jnp.int32)
        in_mask = jnp.asarray([15, 3, 3], jnp.int32)
        mults = jnp.asarray(rng.choice([0, 1, 4, 64], (3, 3)), jnp.int32)
        cen = jnp.asarray(rng.integers(1, 256, (2, 40)), jnp.int32)
        want, wsat = ref.fused_crossbar(x, w, in_li, in_mask, mults, cen)
        got, gsat = ops.dispatch("fused_crossbar", backend)(
            x, w, in_li, in_mask, mults, cen, adc_lo=-64, adc_hi=63,
            rows_per_xbar=512, narrow=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(gsat) == int(wsat)


class TestFusedEdgeShapes:
    """Edge shapes on both backends vs the numpy oracle."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("rows,cols,B,w_slicing,i_slicing", [
        (1, 1, 1, (4, 4), (4, 4)),          # minimal everything
        (513, 3, 1, (4, 2, 2), (1,) * 8),   # R one past a segment (ragged)
        (512, 130, 2, (1,) * 8, (4, 4)),    # C off the 128 tile, max n_j
        (1025, 1, 4, (4, 2, 2), (2,) * 4),  # C=1, third ragged segment
        (300, 7, 1, (2, 2, 2, 2), (4, 2, 2)),  # everything off-tile
    ])
    def test_edges(self, rows, cols, B, w_slicing, i_slicing, backend):
        rng = np.random.default_rng(rows * 31 + cols * 7 + B)
        _, enc, x = _mk_layer(rng, rows, cols, B, w_slicing)
        got, st_f = xbar.forward(x, enc, i_slicing, backend=backend)
        np_psum, np_sats = _np_fused(x, enc.planes, enc.shifts, enc.centers,
                                     i_slicing, -64, 63)
        np.testing.assert_array_equal(np.asarray(got, np.int64), np_psum)
        assert int(st_f.saturations) == np_sats

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_n_slices_one(self, backend):
        """A single weight slice plane (n_j = 1), B = 1, via the raw op
        (a legal 8b weight slicing always has >= 2 slices, so this edge
        only exists at the kernel contract level)."""
        rng = np.random.default_rng(3)
        planes = rng.integers(-15, 16, (1, 1, 512, 6)).astype(np.int8)
        centers = rng.integers(1, 256, (1, 6)).astype(np.int32)
        x = jnp.asarray(rng.integers(0, 256, (1, 400)))
        psum, sats = ops.fused_crossbar_forward(
            x, jnp.asarray(planes), (0,), jnp.asarray(centers),
            input_slicing=(4, 2, 2), adc_lo=-64, adc_hi=63, backend=backend)
        np_psum, np_sats = _np_fused(x, planes, (0,), centers,
                                     (4, 2, 2), -64, 63)
        np.testing.assert_array_equal(np.asarray(psum, np.int64), np_psum)
        assert int(sats) == np_sats

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_all_padding_planes(self, backend):
        """Every slice plane masked invalid -> psum is exactly the digital
        center term and nothing saturates (signed ADC)."""
        rng = np.random.default_rng(4)
        _, enc, x = _mk_layer(rng, 300, 6, 2, (4, 2, 2))
        valid = jnp.zeros((enc.n_slices,), bool)
        psum, sats = ops.fused_crossbar_forward(
            x, jnp.asarray(enc.planes), jnp.asarray(enc.shifts, jnp.int32),
            jnp.asarray(enc.centers), input_slicing=(1,) * 8,
            adc_lo=-64, adc_hi=63, valid=valid, backend=backend)
        np.testing.assert_array_equal(np.asarray(psum),
                                      np.asarray(co.center_term(x, enc)))
        assert int(sats) == 0

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_saturation_at_clip_boundary(self, backend):
        """Column sums landing exactly on lo / hi count as saturated; one
        LSB inside the window does not (the paper's detection rule)."""
        adc = adc_lib.ADCConfig(bits=4, signed=True)  # [-8, 7]
        assert (adc.lo, adc.hi) == (-8, 7)
        # single row, x slice value 1 -> cs == plane value, exactly
        planes = np.zeros((1, 1, 512, 4), np.int8)
        planes[0, 0, 0] = [7, -8, 6, -7]  # hi, lo, hi-1, lo+1
        centers = np.zeros((1, 4), np.int32)
        x = jnp.ones((1, 1), jnp.int32)
        psum, sats = ops.fused_crossbar_forward(
            x, jnp.asarray(planes), (0,), jnp.asarray(centers),
            input_slicing=(8,), adc_lo=adc.lo, adc_hi=adc.hi,
            backend=backend)
        np.testing.assert_array_equal(np.asarray(psum),
                                      [[7, -8, 6, -7]])
        assert int(sats) == 2

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_saturating_segment_boundary(self, backend):
        """All-maximal inputs and weights: each segment (512 rows + the
        188-row ragged tail) must clamp independently."""
        w_u = np.full((700, 4), 255, np.int64)
        enc = co.encode(w_u, (4, 2, 2), mode="zero")  # residuals +127
        x = jnp.full((2, 700), 255, jnp.int32)
        got, st_f = xbar.forward(x, enc, (4, 2, 2), backend=backend)
        want, st_py = xbar.forward(x, enc, (4, 2, 2), backend="python")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(st_f.saturations) == int(st_py.saturations) > 0


class TestAccounting:
    """The fused path's counters must match the Python crossbar counters
    exactly on pinned shapes — ``core.energy`` and
    ``CompiledPim.report()`` price designs off these numbers."""

    @pytest.mark.parametrize("rows,cols,B,i_slicing", [
        (512, 16, 8, (1,) * 8),
        (700, 12, 5, (4, 2, 2)),
        (130, 3, 2, (2, 2, 2, 2)),
    ])
    def test_counters_match_python(self, rows, cols, B, i_slicing):
        rng = np.random.default_rng(rows + cols + B)
        # wide weights + real 7b ADC so saturations are plentiful
        w_u = np.clip(rng.normal(128, 70, (rows, cols)), 0, 255).astype(np.int64)
        enc = co.encode(w_u, (4, 2, 2))
        x = jnp.asarray(rng.integers(0, 256, (B, rows)))
        _, st_py = xbar.forward(x, enc, i_slicing, backend="python")
        for backend in BACKENDS:
            _, st_f = xbar.forward(x, enc, i_slicing, backend=backend)
            assert int(st_f.adc_converts) == int(st_py.adc_converts)
            assert int(st_f.conversions_possible) == \
                int(st_py.conversions_possible)
            assert int(st_f.saturations) == int(st_py.saturations)
            assert st_f.macs == st_py.macs

    def test_unsigned_adc_counters(self):
        """ISAAC-style unsigned window: 0 sits on the lo bound, so even
        zero sums count as saturated — both paths must agree on that."""
        rng = np.random.default_rng(9)
        w_u = rng.integers(0, 256, (256, 8)).astype(np.int64)
        enc = co.encode(w_u, (4, 4), mode="unsigned")
        x = jnp.asarray(rng.integers(0, 256, (3, 256)))
        _, st_py = xbar.forward(x, enc, (4, 4), adc_lib.ISAAC_ADC,
                                backend="python")
        for backend in BACKENDS:
            psum_f, st_f = xbar.forward(x, enc, (4, 4), adc_lib.ISAAC_ADC,
                                        backend=backend)
            assert int(st_f.saturations) == int(st_py.saturations)


class TestAdcZeroPoint:
    """Satellite: the padding contract is now an explicit invariant."""

    def test_zero_point_shifts_window(self):
        cfg = adc_lib.ADCConfig(bits=7, signed=True, zero_point=10)
        assert (cfg.lo, cfg.hi) == (-54, 73)
        assert cfg.zero_preserving

    def test_misconfigured_zero_point_breaks_zero(self):
        """A window excluding 0 maps analog 0 to a non-zero code — the
        hazard the invariant guards against."""
        bad = adc_lib.ADCConfig(bits=4, signed=True, zero_point=20)
        assert bad.lo > 0 and not bad.zero_preserving
        assert int(np.clip(0, bad.lo, bad.hi)) != 0

    def test_convert_refuses(self):
        bad = adc_lib.ADCConfig(bits=4, signed=True, zero_point=20)
        with pytest.raises(ValueError, match="padding contract"):
            adc_lib.convert(jnp.zeros((4,), jnp.int32), bad)

    @pytest.mark.parametrize("backend", ["python", "xla"])
    def test_crossbar_forward_refuses(self, backend):
        rng = np.random.default_rng(11)
        _, enc, x = _mk_layer(rng, 64, 4, 2, (4, 4))
        bad = adc_lib.ADCConfig(bits=7, signed=True, zero_point=100)
        with pytest.raises(ValueError, match="padding contract"):
            xbar.forward(x, enc, (4, 4), bad, backend=backend)

    def test_good_windows_pass(self):
        for cfg in (adc_lib.RAELLA_ADC, adc_lib.ISAAC_ADC,
                    adc_lib.ADCConfig(bits=5, signed=True, zero_point=-3)):
            adc_lib.check_zero_preserving(cfg)  # no raise


class TestBackendRegistry:
    def test_registered_ops_and_backends(self):
        for op in ("centered_int8_matmul", "sliced_crossbar_matmul",
                   "fused_crossbar"):
            assert set(ops.backends(op)) == \
                {"xla", "interpret", "pallas-tpu"}

    def test_resolution_order(self, monkeypatch):
        # CI's kernels-interpret leg pins the env override; the
        # resolution-order contract below is about the un-overridden path
        monkeypatch.delenv(ops.ENV_VAR, raising=False)
        assert ops.resolve_backend("fused_crossbar", "xla") == "xla"
        assert ops.resolve_backend("fused_crossbar", "interpret") == \
            "interpret"
        # auto on the CPU test host -> the XLA reference
        assert ops.resolve_backend("fused_crossbar") == "xla"
        assert ops.resolve_backend("fused_crossbar", "auto") == "xla"
        # the removed 'pallas' alias and the never-registered
        # 'pallas-gpu' raise: no request falls silently to another backend
        with pytest.raises(ValueError, match="not registered"):
            ops.resolve_backend("fused_crossbar", "pallas")
        with pytest.raises(ValueError, match="not registered"):
            ops.resolve_backend("fused_crossbar", "pallas-gpu")

    def test_env_override_wins(self):
        prev = os.environ.get(ops.ENV_VAR)
        os.environ[ops.ENV_VAR] = "interpret"
        try:
            assert ops.resolve_backend("fused_crossbar", "xla") == "interpret"
        finally:
            if prev is None:
                del os.environ[ops.ENV_VAR]
            else:
                os.environ[ops.ENV_VAR] = prev

    def test_unknown_names_raise(self, monkeypatch):
        monkeypatch.delenv(ops.ENV_VAR, raising=False)
        with pytest.raises(KeyError):
            ops.resolve_backend("no_such_op")
        with pytest.raises(ValueError):
            ops.resolve_backend("fused_crossbar", "triton")

    def test_blocked_kernel_matches_defaults(self):
        """Non-default tile sizes hit the revisit/accumulate logic."""
        rng = np.random.default_rng(21)
        x = jnp.asarray(rng.integers(0, 256, (20, 600)), jnp.int32)
        w = jnp.asarray(rng.integers(-15, 16, (2, 1024, 300)), jnp.int8)
        in_li = jnp.asarray([4, 0], jnp.int32)
        in_mask = jnp.asarray([15, 15], jnp.int32)
        mults = jnp.asarray([[16, 1], [256, 16]], jnp.int32)
        cen = jnp.asarray(rng.integers(1, 256, (2, 300)), jnp.int32)
        want, wsat = ref.fused_crossbar(x, w, in_li, in_mask, mults, cen)
        for bm, bn in [(8, 128), (16, 256)]:
            got, gsat = fx.fused_crossbar(
                x, w, in_li, in_mask, mults, cen, bm=bm, bn=bn,
                interpret=True)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert int(gsat) == int(wsat)


class TestEndToEndExactPath:
    """The wired dispatch: ``pim_linear.forward_exact`` (signed inputs,
    two unsigned passes, dequant) is bit-identical across kernel
    backends, so exact-mode prefill/decode runs at kernel speed without
    changing a single logit."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_forward_exact_bitwise(self, backend):
        rng = np.random.default_rng(31)
        w = jnp.asarray(rng.normal(0, 0.05, (300, 16)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 0.5, (4, 300)), jnp.float32)
        plan = pim_linear.prepare(w, x, weight_slicing=(4, 2, 2),
                                  speculation=False)
        y_py = pim_linear.forward_exact(
            x, dataclasses.replace(plan, kernel_backend="python"))
        y_be = pim_linear.forward_exact(
            x, dataclasses.replace(plan, kernel_backend=backend))
        np.testing.assert_array_equal(np.asarray(y_py), np.asarray(y_be))

    def test_forward_exact_under_jit(self):
        """The fused op must trace cleanly inside jit (the models call it
        from scanned/jitted forwards)."""
        rng = np.random.default_rng(33)
        w = jnp.asarray(rng.normal(0, 0.05, (130, 8)), jnp.float32)
        x = jnp.asarray(np.maximum(rng.normal(0.2, 0.3, (3, 130)), 0),
                        jnp.float32)
        plan = pim_linear.prepare(w, x, weight_slicing=(4, 4),
                                  speculation=False)
        plan = dataclasses.replace(plan, kernel_backend="interpret")
        eager = pim_linear.forward_exact(x, plan)
        jitted = jax.jit(lambda xi: pim_linear.forward_exact(xi, plan))(x)
        np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))
