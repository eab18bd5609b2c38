"""Serve a small LM with batched requests, comparing the bf16 path against
the RAELLA fast path (centered int8, Eq. 1) on the same prompts.

  PYTHONPATH=src python examples/serve_quantized.py
"""


import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import pim_linear as plin
from repro.kernels import ops as kops
from repro.models import transformer as T
from repro.serve.engine import ServeEngine


def main() -> None:
    cfg = configs.get("yi-6b").reduced(d_model=128, d_ff=256, n_layers=2,
                                       vocab_size=512, n_heads=4,
                                       n_kv_heads=2, head_dim=32)
    params, _ = T.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, max_len=48)
    prompts = np.asarray(jax.random.randint(jax.random.key(1), (8, 8), 0,
                                            cfg.vocab_size))
    res = eng.generate(prompts, steps=16)
    print(f"bf16 engine: {res.tokens.shape} tokens for 8 requests")

    # RAELLA fast path on the LM head (the big static matmul at decode):
    head = params["embed"]["head"].astype(jnp.float32)
    x = jax.random.normal(jax.random.key(2), (32, cfg.d_model))
    plan = plin.prepare(head, x, speculation=False)
    y_ref = x @ head
    y_fast = plin.forward_fast(x, plan)
    rel = float(jnp.linalg.norm(y_fast - y_ref) / jnp.linalg.norm(y_ref))
    backend = kops.resolve_backend("centered_int8_matmul")
    print(f"centered-int8 LM head ({backend}): rel err {rel:.4f} vs bf16; "
          f"weights stored int8 = 2x HBM traffic saved at decode")


if __name__ == "__main__":
    main()
