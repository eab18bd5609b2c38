"""RaellaLinear: a DNN linear layer executed with RAELLA's arithmetic.

Three execution modes:

  exact — bit-exact functional simulation of the accelerator datapath
          (Center+Offset, sliced crossbars, 7b ADC, optional speculation,
          optional analog noise). Used for the paper's accuracy/fidelity
          experiments. Signed inputs run as two unsigned passes (paper §5.1).

  fast  — the TPU-native transfer of the paper's insight: Center+Offset is
          per-output-channel zero-point quantization, so the layer runs as an
          int8 MXU matmul on the *offsets* plus a digital rank-1 center term
          phi * sum(x) (Eq. 1). Backed by the Pallas kernel in
          repro.kernels.int8_matmul (XLA fallback with identical numerics).

  off   — plain float matmul (baseline / training path).

Preprocessing (= the paper's compile step, Algorithm 1) happens once in
``prepare``; the returned plan is reused for any number of inferences,
mirroring ReRAM's write-once/read-many amortization.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adc as adc_lib
from repro.core import backends as bk
from repro.core import center_offset as co
from repro.core import crossbar as xbar
from repro.core import slicing as sl
from repro.core import speculation as spec
from repro.quant import quantize as q


@dataclasses.dataclass
class PimPlan:
    """Compile-time artifact for one layer (paper: programmed crossbar state)."""
    enc: co.EncodedWeights          # Center+Offset encoded weight slices
    lq: q.LayerQuant                # quantization parameters
    w_q: np.ndarray                 # int8 weights (rows, cols) — reference path
    weight_slicing: tuple[int, ...] | None  # None: per-site (enc carries shifts)
    adc: adc_lib.ADCConfig
    speculation: bool
    spec_slicing: tuple[int, ...] = spec.SPEC_SLICING
    encode_mode: str = "center"     # "center" | "zero" (differential baseline)
    # kernel backend for the static-slicing exact path and the fast path
    # (repro.kernels.ops registry: 'auto' | 'xla' | 'interpret' |
    # 'pallas-tpu' | ...; 'python' forces the crossbar reference loop).
    # None defers to the call site / 'auto'.
    kernel_backend: str | None = None
    # analog array model for the exact path (repro.core.backends): None /
    # IdealSim = exact integer read (fused-kernel eligible); NonidealSim =
    # a ReRAM die with program noise / drift / stuck-ats / IR drop. A
    # nonideal device forces static input slicing — speculation's
    # recovery rule assumes an ideal saturation signal, so modelling it
    # on a faulty die is future work (ROADMAP).
    device: bk.CrossbarBackend | None = None
    # fast (TPU-native) path: asymmetric centered quantization, Eq. 1 in float
    fast_w_off: np.ndarray | None = None    # int8 offsets (rows, cols)
    fast_centers: np.ndarray | None = None  # int32 per-column centers
    fast_scale: np.ndarray | None = None    # fp32 per-column scale

    @property
    def w_u(self) -> np.ndarray:
        return np.asarray(self.w_q, np.int64) + 128


def prepare(w: jnp.ndarray,
            x_cal: jnp.ndarray,
            *,
            weight_slicing: Sequence[int] = (4, 2, 2),
            adc: adc_lib.ADCConfig = adc_lib.RAELLA_ADC,
            speculation: bool = True,
            encode_mode: str = "center",
            bias: jnp.ndarray | None = None,
            relu_out: bool = False,
            signed_inputs: bool | None = None) -> PimPlan:
    """Quantize + Center+Offset encode + slice a layer's weights.

    ``signed_inputs=None`` infers signedness from ``x_cal`` (requires
    concrete values); the model compile step passes ``True`` explicitly —
    transformer residual-stream activations are always signed.
    """
    lq, w_q = q.calibrate_layer(w, x_cal, bias=bias, relu_out=relu_out,
                                signed_inputs=signed_inputs)
    w_u = np.asarray(w_q, np.int64) + 128
    enc = co.encode(w_u, weight_slicing, mode=encode_mode)
    w_off, centers, fscale = q.quantize_weights_centered(w)
    return PimPlan(enc=enc, lq=lq, w_q=np.asarray(w_q),
                   weight_slicing=tuple(weight_slicing), adc=adc,
                   speculation=speculation, encode_mode=encode_mode,
                   fast_w_off=np.asarray(w_off), fast_centers=np.asarray(centers),
                   fast_scale=np.asarray(fscale))


def _unsigned_passes(x_q: jnp.ndarray, signed: bool) -> list[tuple[int, jnp.ndarray]]:
    """Signed inputs -> (sign, unsigned codes) passes; unsigned -> single pass."""
    if not signed:
        return [(1, x_q)]
    return [(1, jnp.maximum(x_q, 0)), (-1, jnp.maximum(-x_q, 0))]


def _accumulate_int(x_q: jnp.ndarray, plan: PimPlan, *,
                    input_slicing: Sequence[int] | None,
                    noise_level: float, key) -> tuple[jnp.ndarray, list]:
    """x_q (B, rows) int codes -> x_q @ w_q int32 via the crossbar sim."""
    stats = []
    acc = jnp.zeros((x_q.shape[0], plan.enc.cols), jnp.int32)
    passes = _unsigned_passes(x_q, plan.lq.x_signed)
    nonideal = isinstance(plan.device, bk.NonidealSim)
    for i, (sign, xp) in enumerate(passes):
        k = None if key is None else jax.random.fold_in(key, i)
        if plan.speculation and not nonideal:
            # noiseless: the fused speculate/recover kernel (the failure
            # mask prices recovery converts analytically); noisy: the
            # Python loop (the per-conversion noise model is stateful)
            psum, st = spec.forward(xp, plan.enc, plan.spec_slicing, plan.adc,
                                    noise_level=noise_level, key=k,
                                    backend=plan.kernel_backend)
        else:
            in_sl = (1,) * sl.INPUT_BITS if input_slicing is None \
                else input_slicing
            psum, st = xbar.forward(xp, plan.enc, in_sl, plan.adc,
                                    noise_level=noise_level, key=k,
                                    backend=plan.kernel_backend,
                                    device=plan.device)
        acc = acc + sign * psum
        stats.append(st)
    # unsigned-weight-domain -> signed int8 weight domain: w_q = w_u - 128
    x_sum = x_q.astype(jnp.int32).sum(axis=-1, keepdims=True)
    acc = acc - 128 * x_sum
    return acc, stats


def forward_exact(x: jnp.ndarray, plan: PimPlan, *,
                  input_slicing: Sequence[int] | None = None,
                  noise_level: float = 0.0,
                  key: jax.Array | None = None,
                  return_stats: bool = False):
    """Float-in / float-out exact accelerator simulation.

    ``plan.device`` picks the analog array model (``core.backends``): a
    ``NonidealSim`` die reads through its programmed nonidealities (and
    forces static input slicing — see ``PimPlan.device``); the default
    ideal device keeps the historical bit-exact datapath.
    """
    if plan.lq.x_signed:
        x_q = jnp.clip(jnp.round(x / plan.lq.x_scale), -127, 127).astype(jnp.int32)
    else:
        x_q = jnp.clip(jnp.round(x / plan.lq.x_scale), 0, 255).astype(jnp.int32)
    y_int, stats = _accumulate_int(x_q, plan, input_slicing=input_slicing,
                                   noise_level=noise_level, key=key)
    w_col_sum = jnp.asarray(plan.w_q.astype(np.int32).sum(axis=0))
    y = q.dequantize(y_int, plan.lq, x_q.sum(-1), w_col_sum)
    if return_stats:
        return y, stats
    return y


def forward_int_reference(x: jnp.ndarray, plan: PimPlan) -> jnp.ndarray:
    """Ideal 8b-quantized layer (no fidelity loss) — the paper's 'expected'."""
    if plan.lq.x_signed:
        x_q = jnp.clip(jnp.round(x / plan.lq.x_scale), -127, 127).astype(jnp.int32)
    else:
        x_q = jnp.clip(jnp.round(x / plan.lq.x_scale), 0, 255).astype(jnp.int32)
    y_int = jnp.einsum("br,rc->bc", x_q, jnp.asarray(plan.w_q, jnp.int32),
                       preferred_element_type=jnp.int32)
    w_col_sum = jnp.asarray(plan.w_q.astype(np.int32).sum(axis=0))
    return q.dequantize(y_int, plan.lq, x_q.sum(-1), w_col_sum)


def forward_fast(x: jnp.ndarray, plan: PimPlan, *,
                 backend: str | None = None) -> jnp.ndarray:
    """TPU-native centered-int8 path (no ADC model — deployment arithmetic).

    Implements Eq. 1 in the quantized-float domain:
        y = s_x * s_w ⊙ ( x_q @ W_off  +  sum(x_q) ⊗ phi )
    where (W_off, phi, s_w) come from asymmetric per-channel centered
    quantization — offsets guaranteed int8, centers digital.

    ``backend`` (or ``plan.kernel_backend``) names a registry backend;
    ``'python'`` (an exact-path choice) resolves like ``'auto'`` here.
    """
    from repro.kernels import ops as kops
    if plan.lq.x_signed:
        x_q = jnp.clip(jnp.round(x / plan.lq.x_scale), -127, 127).astype(jnp.int8)
        shift = 0
    else:
        # shift unsigned codes to the signed domain: u - 128 in [-128, 127]
        x_q = (jnp.clip(jnp.round(x / plan.lq.x_scale), 0, 255) - 128).astype(jnp.int8)
        shift = 128
    be = backend or plan.kernel_backend
    y_int = kops.centered_int8_matmul(
        x_q, jnp.asarray(plan.fast_w_off), jnp.asarray(plan.fast_centers),
        backend=None if be == "python" else be)
    if shift:
        # undo the input shift: u @ W = (u-128) @ W + 128 * colsum(W_off + phi)
        w_col = (plan.fast_w_off.astype(np.int64).sum(axis=0)
                 + plan.fast_w_off.shape[0] * plan.fast_centers.astype(np.int64))
        y_int = y_int + shift * jnp.asarray(w_col, jnp.int32)[None, :]
    y = plan.fast_scale[None, :] * plan.lq.x_scale * y_int.astype(jnp.float32)
    if plan.lq.bias is not None:
        y = y + plan.lq.bias[None, :]
    return y


def output_codes(y: jnp.ndarray, plan: PimPlan, relu: bool = False) -> jnp.ndarray:
    """8b requantized output codes (what flows between PIM tiles)."""
    return q.requantize_outputs(y, plan.lq, relu=relu)
