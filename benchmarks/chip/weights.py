"""Model configuration files and the weights made from a run's seed.

The harness makes the weights, not the program: the plain reference in
``reference.py`` may take nothing the program has made. They are drawn
on the device in one jitted call, in bfloat16 (the type they are served
in), laid out as the program's parameter tree:
``{"embed": {"embed", "head"}, "blocks": [{norm1, core, norm2, ffn}],
"final_norm"}`` with every block leaf stacked over the layers.
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent
BIAS_STD = 0.1     # q/k/v biases
SCALE_STD = 0.1    # norm scales are 1 + normal(0, SCALE_STD)

# config.json keys -> the program's ArchConfig fields
ARCH_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
}


def load_config(name: str, root: pathlib.Path = HERE) -> dict:
    cfg = json.loads((root / "configs" / f"{name}.json").read_text())
    if cfg.get("hidden_act") != "silu" or cfg.get("torch_dtype") != "bfloat16":
        raise ValueError(f"{name}: the harness models silu MLPs in bfloat16")
    return cfg


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree (without the layer axis)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hk = cfg["num_key_value_heads"] * head_dim(cfg)
    core = {"wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d)}
    if cfg["attention_bias"]:
        core.update(bq=(hq,), bk=(hk,), bv=(hk,))
    return {"core": core, "ffn": {"w1": (d, f), "w3": (d, f), "w2": (f, d)},
            "embed": (v, d), "d": d}


def make_weights(cfg: dict, seed: int):
    """The bfloat16 parameter tree for ``cfg``, drawn from ``seed``."""
    key = cfg_key(cfg)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda lo, hi: _draw(dict(key), lo, hi))
    return _JITTED[key](jnp.uint32(seed & 0xFFFFFFFF),
                        jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


_JITTED: dict = {}


def _draw(cfg: dict, lo, hi):
    bf16 = jnp.bfloat16
    n = cfg["num_hidden_layers"]
    sh = shapes(cfg)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    counter = iter(range(1 << 16))

    def nxt():
        return jax.random.fold_in(key, next(counter))

    def mat(shape):       # (n, fan_in, fan_out), normal(0, 1/fan_in)
        w = jax.random.normal(nxt(), (n,) + shape, jnp.float32)
        return (w * shape[0] ** -0.5).astype(bf16)

    def vec(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(nxt(), (n,) + shape,
                                               jnp.float32)).astype(bf16)

    d = sh["d"]
    core = {k: (mat(s) if len(s) == 2 else vec(s, BIAS_STD))
            for k, s in sh["core"].items()}
    block = {"norm1": {"scale": vec((d,), SCALE_STD, 1.0)},
             "core": core,
             "norm2": {"scale": vec((d,), SCALE_STD, 1.0)},
             "ffn": {k: mat(s) for k, s in sh["ffn"].items()}}
    emb = (jax.random.normal(nxt(), sh["embed"], jnp.float32)
           * d ** -0.5).astype(bf16)
    if cfg["tie_word_embeddings"]:
        head = emb.T
    else:
        head = (jax.random.normal(nxt(), sh["embed"][::-1], jnp.float32)
                * d ** -0.5).astype(bf16)
    final = (1.0 + SCALE_STD * jax.random.normal(nxt(), (d,), jnp.float32)
             ).astype(bf16)
    return {"embed": {"embed": emb, "head": head}, "blocks": [block],
            "final_norm": {"scale": final}}
