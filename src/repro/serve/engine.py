"""Batched serving engine: chunked prefill + batched greedy/sampled decode.

The engine owns jitted prefill/decode functions for one (arch, batch,
max_len) bucket and exposes a request-batch API. RAELLA integration:
with ``cfg.pim_mode != 'off'`` the engine requires the compiled plan
pytree from ``repro.models.pim.prepare_pim_params`` (the per-site
architecture compiler, ``repro.models.pim_compile``) and passes it to
every jitted prefill/decode call — 'fast' runs the weight-static
projections on the centered int8 path (the paper's Eq. 1 on the MXU, see
``models.layers.pim_matmul``), 'exact' the bit-exact accelerator
simulation (small models only; each site runs its own compiled weight
slicing), 'int8' the ideal 8b-quantized reference.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T


def jit_step(fn):
    """``jax.jit`` for a serving step, with XLA's excess precision off.

    With it on, XLA may keep a bf16 intermediate in f32 inside a fusion,
    so a step's numerics depend on where fusions end. A Pallas kernel
    ends fusions where its XLA reference does not, and the int8
    quantizer of the ``fast`` path turns those last-bit differences into
    whole-code flips: on a TPU v5e, qwen1.5-0.5b's logits differed by 6%
    between the two backends of a bit-exact kernel. Off, every bf16
    value is rounded where the program says, so backends whose kernels
    agree bit for bit give identical steps."""
    return jax.jit(fn, compiler_options={"xla_allow_excess_precision": False})


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray        # (B, steps) generated ids
    prompt_len: int
    steps: int


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params: Any, *,
                 max_len: int = 512, temperature: float = 0.0,
                 plans: Any = None):
        if not cfg.causal:
            raise ValueError(f"{cfg.name} is encoder-only; no decode")
        if cfg.pim_mode != "off" and plans is None:
            raise ValueError(
                f"pim_mode={cfg.pim_mode!r} needs compiled plans — call "
                "repro.models.pim.prepare_pim_params(params, cfg, "
                "calib_tokens) and pass plans=")
        self.cfg = cfg
        self.params = params
        self.plans = plans
        self.max_len = max_len
        self.temperature = temperature
        self._prefill = jit_step(
            lambda p, pl, toks: T.prefill(p, cfg, toks, max_len=max_len,
                                          plans=pl))
        self._decode = jit_step(
            lambda p, pl, st, tok: T.decode_step(p, cfg, st, tok, plans=pl))

    def _pick(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        logits = logits[:, -1, :]
        if key is None:
            return jnp.argmax(logits, axis=-1)[:, None]
        return jax.random.categorical(
            key, logits / self.temperature, axis=-1)[:, None]

    def generate(self, prompts: np.ndarray, *, steps: int,
                 seed: int = 0) -> GenerationResult:
        """prompts: (B, prompt_len) int32 token ids.

        Seed reproducibility: at ``temperature == 0`` no PRNG key is ever
        created, split, or consumed — greedy outputs are deterministic and
        independent of ``seed``. At ``temperature > 0`` the stream is
        ``jax.random.key(seed)`` for the first token and
        ``fold_in(key(seed), i)`` for decode step ``i``, so a fixed seed
        replays the exact sample sequence (the continuous-batching engine
        uses the same per-request scheme — see repro.serve.scheduler).
        """
        toks = jnp.asarray(prompts, jnp.int32)
        B, plen = toks.shape
        if plen + steps > self.max_len:
            raise ValueError("prompt + steps exceeds engine max_len")
        key = None if self.temperature <= 0.0 else jax.random.key(seed)
        logits, state = self._prefill(self.params, self.plans, toks)
        out = []
        tok = self._pick(logits, key)
        out.append(tok)
        for i in range(steps - 1):
            step_key = None if key is None else jax.random.fold_in(key, i)
            logits, state = self._decode(self.params, self.plans, state, tok)
            tok = self._pick(logits, step_key)
            out.append(tok)
        gen = np.asarray(jnp.concatenate(out, axis=1))
        return GenerationResult(tokens=gen, prompt_len=plen, steps=steps)
