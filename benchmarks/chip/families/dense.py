"""The dense decoder: grouped-query attention with rotary embeddings and
optional q/k/v biases, a SiLU-gated MLP, RMSNorm, in bfloat16
(qwen1.5-0.5b, yi-6b). The interface is ``families/__init__.py``'s.

Configuration file: the model's public ``config.json`` keys that
``ARCH_FIELDS`` maps onto the program's config, an optional ``head_dim``
(``hidden_size // num_attention_heads`` where absent),
``tie_word_embeddings``, ``hidden_act`` ``"silu"`` and ``torch_dtype``
``"bfloat16"``, and bookkeeping keys (``BOOKKEEPING``). ``check`` refuses
any other key, so a file that describes experts, sliding windows or
latent attention is never served as a dense decoder.

Weights: drawn on the device in one jitted call, in bfloat16 (the type
they are served in), laid out as the program's parameter tree:
``{"embed": {"embed", "head"}, "blocks": [{norm1, core, norm2, ffn}],
"final_norm"}`` with every block leaf stacked over the layers.

Reference: the decoder written out in float32 ``jax.numpy`` at
``highest`` matmul precision: RMSNorm, rotary embeddings (half-split,
``rope_theta``), causal grouped-query attention with optional q/k/v
biases, a SiLU-gated MLP, a final RMSNorm and the output head. It imports
nothing of the program and takes only these weights and the served token
ids. It runs teacher-forced over each sampled request's prompt followed
by its served tokens, one layer at a time over a block of rows, and reads
at every served position the gap by which the served token's logit lies
below the reference's best (the readers under ``checks/`` reduce the
gaps to the numbers ``correct`` compares).

The controls (``CONTROLS``) are the same forward with every projection
and the head on int4 codes: weights symmetric per output column,
activations per row (``int4``) or per tensor (``int4_tensor``). For a
control the gap is read, in the float32 reference, for the token the
control itself puts first.

Work counts: the int8 projections and head of the ``fast`` path, and
bfloat16 attention over each token's real context (not the padded view
the program gathers); decode attention reads each key's K and V once in
bfloat16, plus the token's query and output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1     # q/k/v biases
SCALE_STD = 0.1    # norm scales are 1 + normal(0, SCALE_STD)
BF16_BYTES = 2
Q_BLOCK = 512       # query rows per attention block
HEAD_ROWS = 256     # positions per block of the output head
# int4 controls: activation scale per row, or one per tensor (as the
# program's fast path scales its int8 activations); weights per column
CONTROLS = {"int4": -1, "int4_tensor": None}

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
REQUIRED = set(ARCH_FIELDS) | {"tie_word_embeddings", "hidden_act",
                               "torch_dtype"}
BOOKKEEPING = {"source", "arch", "model_type", "reduced", "published",
               "assumed", "deployment", "max_position_embeddings", "family"}


# ---------------------------------------------------------- the file
def check(cfg: dict) -> None:
    missing = sorted(REQUIRED - set(cfg))
    if missing:
        raise ValueError(f"dense: missing keys {missing}")
    unknown = sorted(set(cfg) - REQUIRED - BOOKKEEPING - {"head_dim"})
    if unknown:
        raise ValueError(f"dense: keys it does not model {unknown}")
    if cfg["hidden_act"] != "silu" or cfg["torch_dtype"] != "bfloat16":
        raise ValueError("dense: the family models silu MLPs in bfloat16")
    hd = cfg.get("head_dim", 2)
    if not isinstance(hd, int) or hd <= 0 or hd % 2:
        raise ValueError(f"dense: head_dim {hd!r} is not a positive even "
                         "number (rotary embeddings split it in halves)")


def head_dim(cfg: dict) -> int:
    if "head_dim" in cfg:
        return cfg["head_dim"]
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def arch_overrides(cfg: dict) -> dict:
    over = {field: cfg[key] for key, field in ARCH_FIELDS.items()}
    over.update(head_dim=cfg.get("head_dim", 0),
                resolved_head_dim=head_dim(cfg), n_experts=0,
                block_pattern=("attn",), qk_norm=False, activation="silu",
                dtype="bfloat16")
    return over


# ----------------------------------------------------------- weights
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


# --------------------------------------------------------- reference
def _fake_quant(x, axis, levels):
    """Symmetric quantize-dequantize of ``x`` along ``axis`` (``None``:
    one scale for the whole tensor)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / levels
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -levels, levels) * s


def _proj(x, w, precision):
    w = w.astype(jnp.float32)
    if precision in CONTROLS:
        x = _fake_quant(x, CONTROLS[precision], 7)
        w = _fake_quant(w, 0, 7)
    return jnp.einsum("...d,df->...f", x, w)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention for one row. q (T, H, D), k/v (T, K, D)."""
    T, H, D = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    nb = T // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * D ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(T)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(T, H, D)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(x, lw, cfg_items, precision):
    """One decoder layer over rows ``x`` (R, T, d) in float32."""
    cfg = dict(cfg_items)
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    R, T, _ = x.shape
    pos = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, lw["norm1"]["scale"], eps)
        c = lw["core"]
        q = _proj(h, c["wq"], precision)
        k = _proj(h, c["wk"], precision)
        v = _proj(h, c["wv"], precision)
        if cfg["attention_bias"]:
            q = q + c["bq"].astype(jnp.float32)
            k = k + c["bk"].astype(jnp.float32)
            v = v + c["bv"].astype(jnp.float32)
        q = _rope(q.reshape(R, T, -1, hd), pos, cfg["rope_theta"])
        k = _rope(k.reshape(R, T, -1, hd), pos, cfg["rope_theta"])
        v = v.reshape(R, T, -1, hd)
        o = jax.lax.map(lambda a: _attention(*a), (q, k, v))
        x = x + _proj(o.reshape(R, T, -1), c["wo"], precision)
        h = _rmsnorm(x, lw["norm2"]["scale"], eps)
        f = lw["ffn"]
        g = jax.nn.silu(_proj(h, f["w1"], precision)) \
            * _proj(h, f["w3"], precision)
        return x + _proj(g, f["w2"], precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(h, final_scale, head, cfg_items, precision):
    """Logits of final hidden states ``h`` (n, d) in ``precision``."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(h, final_scale, cfg["rms_norm_eps"])
        return _proj(h, head, precision)


def _cfg_items(cfg: dict) -> tuple:
    keys = ("rms_norm_eps", "rope_theta", "attention_bias")
    return tuple((k, cfg[k]) for k in keys) + (("head_dim", head_dim(cfg)),)


def _hidden(cfg, weights, seqs, length, precision, rows):
    """Final hidden states (n, d) at every served position of ``seqs``.

    Rows are padded to ``length`` (causal attention keeps the padding out
    of every real position) and run ``rows`` at a time, layer by layer.
    """
    items = _cfg_items(cfg)
    length = -(-length // Q_BLOCK) * Q_BLOCK
    blocks = weights["blocks"][0]
    out = []
    for r0 in range(0, len(seqs), rows):
        part = seqs[r0:r0 + rows]
        toks = np.zeros((len(part), length), np.int32)
        for i, (p, s) in enumerate(part):
            full = np.concatenate([p, s[:-1]])
            toks[i, :full.size] = full
        x = weights["embed"]["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for li in range(cfg["num_hidden_layers"]):
            lw = jax.tree.map(lambda a, li=li: a[li], blocks)
            x = _layer(x, lw, items, precision)
        # position p - 1 + j produced served token j
        ri = np.concatenate([np.full(s.size, i) for i, (_, s) in
                             enumerate(part)])
        ci = np.concatenate([p.size - 1 + np.arange(s.size)
                             for p, s in part])
        out.append(x[jnp.asarray(ri), jnp.asarray(ci)])
        del x
    return jnp.concatenate(out)


def _blocks(h):
    """``h`` in blocks of ``HEAD_ROWS`` rows, the last one padded."""
    for h0 in range(0, h.shape[0], HEAD_ROWS):
        hb = h[h0:h0 + HEAD_ROWS]
        n = hb.shape[0]
        yield n, jnp.pad(hb, ((0, HEAD_ROWS - n), (0, 0)))


def served_gaps(cfg: dict, weights, seqs: list[tuple[np.ndarray, np.ndarray]],
                length: int, precision: str = "float32",
                rows: int = 4) -> np.ndarray:
    """Per served position of ``seqs`` ((prompt, served) pairs): the best
    reference logit minus the reference logit of the served token, or,
    for a control precision, of the token that precision puts first."""
    items = _cfg_items(cfg)
    fs, head = weights["final_norm"]["scale"], weights["embed"]["head"]
    h_ref = _hidden(cfg, weights, seqs, length, "float32", rows)
    if precision == "float32":
        picks = np.concatenate([s for _, s in seqs]).astype(np.int32)
    else:
        h_ctl = _hidden(cfg, weights, seqs, length, precision, rows)
        picks = np.concatenate([
            np.asarray(jnp.argmax(_head(hb, fs, head, items, precision),
                                  axis=-1))[:n]
            for n, hb in _blocks(h_ctl)]).astype(np.int32)
    gaps = []
    for (n, hb), p0 in zip(_blocks(h_ref), range(0, len(picks), HEAD_ROWS)):
        ref = _head(hb, fs, head, items, "float32")[:n]
        pick = jnp.asarray(picks[p0:p0 + n])
        gaps.append(np.asarray(
            ref.max(axis=-1)
            - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]))
    return np.concatenate(gaps) if gaps else np.zeros(0)


# ------------------------------------------------------- work counts
def proj_ops_per_token(cfg: dict) -> float:
    """int8 operations of one token through every layer's projections."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hk = cfg["num_key_value_heads"] * head_dim(cfg)
    per_layer = d * hq + 2 * d * hk + hq * d + 3 * d * f
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def head_ops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attn_ops(cfg: dict, n_keys: int) -> float:
    """bfloat16 operations of one query attending ``n_keys`` keys in every
    layer (scores and the weighted sum of values)."""
    return (4.0 * cfg["num_attention_heads"] * head_dim(cfg) * n_keys
            * cfg["num_hidden_layers"])


def model_ops(cfg: dict, prefill: list, decode: list) -> tuple[float, float]:
    """(int8 ops, bf16 ops) for prefill chunks ``(lo, hi, last)`` (``last``:
    the chunk ends the prompt, so the head runs once) and decode tokens
    given by their position."""
    i8 = bf = 0.0
    for lo, hi, last in prefill:
        i8 += (hi - lo) * proj_ops_per_token(cfg) + (head_ops(cfg) if last
                                                     else 0.0)
        # the token at position p attends p + 1 keys
        bf += attn_ops(cfg, (hi * (hi + 1) - lo * (lo + 1)) // 2)
    for pos in decode:
        i8 += proj_ops_per_token(cfg) + head_ops(cfg)
        bf += attn_ops(cfg, pos + 1)
    return i8, bf


def decode_attention_work(cfg: dict, positions: list[int]
                          ) -> tuple[float, float]:
    """(operations, bytes) of decode attention for tokens at
    ``positions``, over every layer: each key's K and V read once in
    bfloat16, plus each token's query and output."""
    hd, layers = head_dim(cfg), cfg["num_hidden_layers"]
    keys = sum(p + 1 for p in positions)
    kv = 2 * cfg["num_key_value_heads"] * hd * keys
    q_out = 2 * cfg["num_attention_heads"] * hd * len(positions)
    return (attn_ops(cfg, keys),
            float(layers * BF16_BYTES * (kv + q_out)))
