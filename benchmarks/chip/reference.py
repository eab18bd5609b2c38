"""Plain reference of the served model, and the comparison that decides
``correct``.

The reference is the decoder the configuration file describes, written
out in float32 ``jax.numpy`` at ``highest`` matmul precision: RMSNorm,
rotary embeddings (half-split, ``rope_theta``), causal grouped-query
attention with optional q/k/v biases, a SiLU-gated MLP, a final RMSNorm
and the output head. It imports nothing of the program and takes only the
harness's weights (``weights.py``) and the served token ids.

It runs teacher-forced over each sampled request's prompt followed by
its served tokens, one layer at a time over a block of rows, and reads
at every served position the gap by which the served token's logit lies
below the reference's best (the readers under ``checks/`` reduce the
gaps to the numbers ``correct`` compares).

The controls (``CONTROLS``) are the same forward with every projection
and the head on int4 codes: weights symmetric per output column,
activations per row (``int4``) or per tensor (``int4_tensor``). For a
control the gap is read, in the float32 reference, for the token the
control itself puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import head_dim

Q_BLOCK = 512       # query rows per attention block
HEAD_ROWS = 256     # positions per block of the output head
# int4 controls: activation scale per row, or one per tensor (as the
# program's fast path scales its int8 activations); weights per column
CONTROLS = {"int4": -1, "int4_tensor": None}


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
    eps, hd = cfg["rms_norm_eps"], head_dim(cfg)
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
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta", "attention_bias")
    return tuple((k, cfg[k]) for k in keys)


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
