"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests."""

import hypothesis
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import center_offset as co
from repro.core import crossbar as xbar
from repro.core import slicing as sl
from repro.kernels import ops, ref


class TestCenteredInt8Matmul:
    @pytest.mark.parametrize("B,K,N", [
        (1, 1, 1), (8, 128, 128), (37, 700, 45), (256, 512, 256),
        (3, 2048, 17), (130, 130, 130),
    ])
    def test_shapes(self, B, K, N):
        rng = np.random.default_rng(B * 1000 + K + N)
        x = jnp.asarray(rng.integers(-127, 128, (B, K)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
        c = jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int32)
        got = ops.centered_int8_matmul(x, w, c, backend="interpret")
        want = ref.centered_int8_matmul(x, w, c)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_block_sizes(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.integers(-127, 128, (100, 300)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (300, 200)), jnp.int8)
        c = jnp.asarray(rng.integers(-128, 128, (200,)), jnp.int32)
        from repro.kernels import int8_matmul as im
        for bm, bk, bn in [(8, 128, 128), (32, 256, 128), (256, 512, 256)]:
            got = im.centered_int8_matmul(x, w, c, bm=bm, bk=bk, bn=bn,
                                          interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref.centered_int8_matmul(x, w, c)))

    def test_reconstructs_uncentered_matmul(self):
        """x @ w == x @ (w - c) + sum(x) * c  — Eq. 1 exactness."""
        rng = np.random.default_rng(8)
        w_full = rng.integers(-100, 100, (64, 16))
        c = np.round(w_full.mean(axis=0)).astype(np.int32)
        w_off = (w_full - c[None, :]).astype(np.int8)
        x = jnp.asarray(rng.integers(-127, 128, (9, 64)), jnp.int8)
        got = ops.centered_int8_matmul(x, jnp.asarray(w_off), jnp.asarray(c),
                                       backend="interpret")
        want = np.asarray(x, np.int64) @ w_full
        np.testing.assert_array_equal(np.asarray(got, np.int64), want)

    @hypothesis.given(st.integers(0, 2**31 - 1))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_property_random_shapes(self, seed):
        rng = np.random.default_rng(seed)
        B, K, N = (int(rng.integers(1, 64)), int(rng.integers(1, 600)),
                   int(rng.integers(1, 300)))
        x = jnp.asarray(rng.integers(-127, 128, (B, K)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
        c = jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int32)
        got = ops.centered_int8_matmul(x, w, c, backend="interpret")
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.centered_int8_matmul(x, w, c)))


class TestSlicedCrossbarKernel:
    def _mk(self, rng, n_i, n_j, B, R, C):
        xs = jnp.asarray(rng.integers(0, 16, (n_i, B, R)), jnp.int8)
        wp = jnp.asarray(rng.integers(-15, 16, (n_j, R, C)), jnp.int8)
        mults = jnp.asarray(rng.choice([1, 2, 4, 16, 64], size=(n_i, n_j)),
                            jnp.int32)
        return xs, wp, mults

    @pytest.mark.parametrize("n_i,n_j,B,R,C", [
        (1, 1, 4, 512, 64), (3, 3, 8, 512, 128), (8, 2, 2, 1024, 32),
        (2, 4, 16, 300, 200), (3, 3, 1, 1500, 7),
    ])
    def test_shapes(self, n_i, n_j, B, R, C):
        rng = np.random.default_rng(n_i + 10 * n_j + B + R + C)
        xs, wp, m = self._mk(rng, n_i, n_j, B, R, C)
        got = ops.sliced_crossbar_matmul(xs, wp, m, backend="interpret")
        want = ref.sliced_crossbar_matmul(xs, wp, m)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_adc_bounds_respected(self):
        """Saturating inputs must clamp per segment, not per total."""
        rng = np.random.default_rng(5)
        xs, wp, m = self._mk(rng, 1, 1, 2, 1024, 8)
        xs = jnp.full_like(xs, 15)
        wp = jnp.full_like(wp, 15)
        got = ops.sliced_crossbar_matmul(xs, wp, m, backend="interpret")
        # two segments, each clamps at 63 -> 126 * mult
        np.testing.assert_array_equal(np.asarray(got),
                                      np.full((2, 8), 126 * int(m[0, 0])))

    def test_matches_crossbar_module(self):
        """Kernel path == repro.core.crossbar forward (offset term)."""
        rng = np.random.default_rng(6)
        w_u = rng.integers(0, 256, (700, 12)).astype(np.int64)
        slicing = (4, 2, 2)
        enc = co.encode(w_u, slicing)
        x = jnp.asarray(rng.integers(0, 256, (5, 700)))
        # core module full path
        psum, _ = xbar.forward(x, enc, (1,) * 8)
        # kernel path: input 1b slices x weight planes + digital center term
        n_seg, R = enc.n_segments, enc.rows_per_xbar
        x_pad = jnp.pad(x, ((0, 0), (0, n_seg * R - x.shape[1])))
        x_slices = jnp.stack([sl.crop_unsigned(x_pad, b, b).astype(jnp.int8)
                              for b in range(7, -1, -1)])
        w_planes = jnp.asarray(
            enc.planes.transpose(0, 1, 2, 3).reshape(enc.n_slices, n_seg * R,
                                                     enc.cols))
        mults = jnp.asarray(
            [[1 << (li + lw) for lw in enc.shifts] for li in range(7, -1, -1)],
            jnp.int32)
        offs = ops.sliced_crossbar_matmul(x_slices, w_planes, mults,
                                          backend="interpret")
        got = offs + co.center_term(x, enc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(psum))

    @hypothesis.given(st.integers(0, 2**31 - 1))
    @hypothesis.settings(max_examples=8, deadline=None)
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        n_i, n_j = int(rng.integers(1, 4)), int(rng.integers(1, 4))
        B, R, C = (int(rng.integers(1, 9)), int(rng.integers(1, 1200)),
                   int(rng.integers(1, 150)))
        xs, wp, m = self._mk(rng, n_i, n_j, B, R, C)
        got = ops.sliced_crossbar_matmul(xs, wp, m, backend="interpret")
        want = ref.sliced_crossbar_matmul(xs, wp, m)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestEdgeShapesBothPaths:
    """Edge shapes through BOTH dispatch paths (Pallas interpret and the
    XLA fallback), each checked against an independent numpy oracle — so
    a shared bug in kernel *and* ``ref`` cannot hide."""

    @staticmethod
    def _np_centered(x, w, c):
        xs = np.asarray(x, np.int64)
        y = xs @ np.asarray(w, np.int64)
        return y + xs.sum(axis=1, keepdims=True) * np.asarray(c, np.int64)

    @staticmethod
    def _np_sliced(xs, wp, m, rows_per_xbar=512, lo=-64, hi=63):
        n_i, B, R = xs.shape
        n_j, _, C = wp.shape
        n_seg = -(-R // rows_per_xbar)
        out = np.zeros((B, C), np.int64)
        for i in range(n_i):
            for j in range(n_j):
                for s in range(n_seg):
                    r0, r1 = s * rows_per_xbar, min((s + 1) * rows_per_xbar, R)
                    cs = (np.asarray(xs[i, :, r0:r1], np.int64)
                          @ np.asarray(wp[j, r0:r1], np.int64))
                    out += np.clip(cs, lo, hi) * int(m[i, j])
        return out

    @pytest.mark.parametrize("backend", ["interpret", "xla"],
                             ids=["interpret", "xla-fallback"])
    @pytest.mark.parametrize("B,K,N", [
        (1, 1, 1),       # full singleton
        (1, 513, 129),   # B=1, K/N one past a block multiple
        (5, 7, 1),       # single output column
        (2, 130, 257),   # N not a multiple of the 128 tile
        (9, 1, 130),     # K=1 (degenerate contraction)
    ])
    def test_centered_int8_edges(self, B, K, N, backend):
        rng = np.random.default_rng(B * 7919 + K * 31 + N)
        x = jnp.asarray(rng.integers(-127, 128, (B, K)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
        c = jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int32)
        got = ops.centered_int8_matmul(x, w, c, backend=backend)
        np.testing.assert_array_equal(np.asarray(got, np.int64),
                                      self._np_centered(x, w, c))

    @pytest.mark.parametrize("backend", ["interpret", "xla"],
                             ids=["interpret", "xla-fallback"])
    @pytest.mark.parametrize("n_i,n_j,B,R,C", [
        (1, 1, 1, 1, 1),      # minimal everything
        (1, 1, 1, 513, 3),    # R one past rows_per_xbar (2 ragged segments)
        (2, 3, 1, 700, 130),  # B=1, R and C both off-tile
        (1, 2, 4, 1025, 1),   # C=1, R spills into a third segment
    ])
    def test_sliced_crossbar_edges(self, n_i, n_j, B, R, C, backend):
        rng = np.random.default_rng(n_i * 131 + n_j * 17 + B + R + C)
        xs = jnp.asarray(rng.integers(0, 16, (n_i, B, R)), jnp.int8)
        wp = jnp.asarray(rng.integers(-15, 16, (n_j, R, C)), jnp.int8)
        m = jnp.asarray(rng.choice([1, 2, 4, 16, 64], size=(n_i, n_j)),
                        jnp.int32)
        got = ops.sliced_crossbar_matmul(xs, wp, m, backend=backend)
        np.testing.assert_array_equal(np.asarray(got, np.int64),
                                      self._np_sliced(xs, wp, m))

    def test_saturating_segment_boundary(self):
        """R not divisible by rows_per_xbar with saturating sums: the
        ragged tail segment must clamp independently of the full one."""
        xs = jnp.full((1, 2, 700), 15, jnp.int8)
        wp = jnp.full((1, 700, 4), 15, jnp.int8)
        m = jnp.ones((1, 1), jnp.int32)
        for backend in ("interpret", "xla"):
            got = ops.sliced_crossbar_matmul(xs, wp, m,
                                             backend=backend)
            # both segments (512 rows + 188-row tail) saturate at 63
            np.testing.assert_array_equal(np.asarray(got),
                                          np.full((2, 4), 126))
