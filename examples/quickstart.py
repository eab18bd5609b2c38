"""Quickstart: RAELLA's arithmetic on one layer, end to end.

  PYTHONPATH=src python examples/quickstart.py

Walks the paper's full pipeline on a single linear layer: 8b quantization,
Center+Offset encoding (Eq. 1/2), adaptive weight slicing (Algorithm 1),
speculative crossbar execution with a 7b ADC, and the TPU-native centered
int8 fast path — comparing all of them against the float reference.
"""

import jax.numpy as jnp
import numpy as np

from repro.core import adaptive, pim_linear as plin
from repro.core import energy as en, workloads as wl
from repro.kernels import ops as kops


def main() -> None:
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0.01, 0.04, (512, 64)), jnp.float32)
    x = jnp.asarray(np.maximum(rng.normal(0.2, 0.4, (10, 512)), 0),
                    jnp.float32)
    y_ref = x @ w

    print("=== Algorithm 1: adaptive weight slicing ===")
    choice = adaptive.find_best_slicing(w, x, error_budget=0.09)
    print(f"chose {choice.slicing} ({choice.n_slices} slices/weight), "
          f"measured error {choice.error:.4f} (budget 0.09)")

    print("\n=== bit-exact accelerator simulation (7b ADC, speculation) ===")
    plan = plin.prepare(w, x, weight_slicing=choice.slicing, speculation=True)
    y_pim, stats = plin.forward_exact(x, plan, return_stats=True)
    rel = float(jnp.linalg.norm(y_pim - y_ref) / jnp.linalg.norm(y_ref))
    st = stats[0]
    print(f"rel error vs float: {rel:.4f}")
    print(f"ADC converts {int(st.adc_converts)} vs recovery-only "
          f"{int(st.no_spec_converts)} "
          f"({1 - int(st.adc_converts)/int(st.no_spec_converts):.0%} saved), "
          f"speculation failure rate {float(st.failure_rate):.1%}")

    print("\n=== TPU-native fast path (Eq. 1 as centered int8 matmul) ===")
    y_fast = plin.forward_fast(x, plan)
    rel = float(jnp.linalg.norm(y_fast - y_ref) / jnp.linalg.norm(y_ref))
    backend = kops.resolve_backend("centered_int8_matmul")
    print(f"rel error vs float: {rel:.4f} (kernel backend {backend})")

    print("\n=== Titanium Law: ResNet18 on RAELLA vs 8b ISAAC ===")
    layers = wl.resnet18()
    ri = en.analyze_dnn(en.ISAAC_8B, layers)
    rr = en.analyze_dnn(en.RAELLA, layers)
    print(f"converts/MAC {ri.converts_per_mac:.3f} -> "
          f"{rr.converts_per_mac:.3f}; energy {ri.energy/rr.energy:.1f}x "
          f"better, throughput {ri.latency_ns/rr.latency_ns:.1f}x")


if __name__ == "__main__":
    main()
