"""Transformer building blocks, pure JAX.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
params pytree with tuples of *logical* sharding axes (resolved by
``repro.dist.sharding``). Attention is chunked (online softmax over KV
blocks) so no S x S score tensor ever materializes — required for the 32k
prefill shapes — plus a single-query flash-decode path that keeps the KV
cache's sequence sharding intact. MoE uses GShard-style sub-grouped one-hot
einsum dispatch (no scatter/gather: GSPMD partitions everything) with
experts sharded over the model axis.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import adc as adc_lib
from repro.core import backends as device_backends
from repro.core import center_offset as co
from repro.core import pim_linear
from repro.dist import shard
from repro.kernels import ref as kref
from repro.quant import quantize as quantlib

ATTN_CHUNK = 512


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------------ pim
# Trace-time work-stats collector (sow-style). ``collect_pim_stats()``
# pushes a sink; while one is active, every exact-mode ``pim_matmul``
# records its per-pass SpeculationStats / CrossbarStats into the
# *innermost* sink at trace time. Tracer hygiene: values created inside
# a ``lax.scan`` body belong to that sub-trace and must not leak to an
# outer sink — ``transformer.decode_step`` therefore opens its own sink
# inside the scanned block body and re-emits the summed totals as scan
# outputs (see its ``repeat_body``); other scanned/vmapped regions
# (prefill bodies, MoE expert vmap) *suspend* collection instead, so the
# collector reports decode-step work (the serve-time converts/token
# metric) plus any non-scanned projections.
_PIM_STATS_SINKS: list[list] = []

# total-able work-stat fields; ``conversions_possible`` is the static
# path's name for the no-speculation baseline
PIM_STAT_KEYS = ("adc_converts", "no_spec_converts", "spec_failures",
                 "spec_attempts", "recovery_saturations", "cycles", "macs")
_STAT_ALIASES = {"no_spec_converts": "conversions_possible"}


@contextlib.contextmanager
def collect_pim_stats():
    """Collect exact-path work stats from every ``pim_matmul`` traced in
    the body. Yields the sink list: raw stats objects and/or totals
    dicts (from scanned regions). Reduce with ``pim_stats_totals``."""
    sink: list = []
    _PIM_STATS_SINKS.append(sink)
    try:
        yield sink
    finally:
        _PIM_STATS_SINKS.remove(sink)


@contextlib.contextmanager
def suspend_pim_stats():
    """Mask all active sinks (scan/vmap bodies whose tracers must not
    escape into them)."""
    saved = _PIM_STATS_SINKS[:]
    _PIM_STATS_SINKS.clear()
    try:
        yield
    finally:
        _PIM_STATS_SINKS.extend(saved)


def pim_stats_active() -> bool:
    return bool(_PIM_STATS_SINKS)


def pim_stats_record(entry) -> None:
    """Append a stats object / totals dict to the innermost active sink."""
    if _PIM_STATS_SINKS:
        _PIM_STATS_SINKS[-1].append(entry)


def pim_stats_totals(stats) -> dict:
    """Sum a sink's entries into one ``{field: total}`` dict.

    Entries are SpeculationStats / CrossbarStats objects (exact-path
    per-pass stats) or dicts (pre-summed scan totals). Static fields
    stay exact Python ints; traced fields sum as arrays.
    """
    tot = dict.fromkeys(PIM_STAT_KEYS, 0)
    for st in stats:
        for k in PIM_STAT_KEYS:
            if isinstance(st, dict):
                v = st.get(k, 0)
            else:
                v = getattr(st, k, None)
                if v is None:
                    v = getattr(st, _STAT_ALIASES.get(k, k), 0)
            tot[k] = tot[k] + v
    return tot


def with_pim_stats(fn):
    """Wrap a traced function so it also returns summed work totals.

    ``fn``'s body runs under :func:`collect_pim_stats`; the wrapper
    appends the :func:`pim_stats_totals` dict to ``fn``'s return (tuple
    returns are extended, single returns become a pair). Jit the
    *wrapped* function — the totals then ride the jitted call as
    auxiliary outputs and can join the caller's existing
    ``jax.device_get`` (the serve engines fetch them with the same host
    sync that surfaces the logits, so stats collection adds no extra
    device round-trips).
    """
    def wrapped(*args, **kwargs):
        with collect_pim_stats() as acc:
            out = fn(*args, **kwargs)
            totals = pim_stats_totals(acc)
        if isinstance(out, tuple):
            return out + (totals,)
        return out, totals
    return wrapped


class PimTap:
    """Calibration recorder: stands in for a plan leaf during the capture
    forward of ``repro.models.pim.prepare_pim_params``. ``pim_matmul``
    records the projection's input activations and runs the float path, so
    calibration sees exactly the activations the real forward produces."""

    def __init__(self):
        self.x: list[np.ndarray] = []

    def record(self, x2d: jnp.ndarray) -> None:
        self.x.append(np.asarray(x2d, np.float32))


def _plan_to_pim_plan(plan: dict, cfg: ArchConfig, rows: int) -> pim_linear.PimPlan:
    """Rebuild a ``pim_linear.PimPlan`` from a plan-leaf dict + static cfg.

    Plan leaves carry only arrays (so they ride ``lax.scan`` / ``vmap``
    over the stacked block axis). The weight slicing is *per site*: exact
    plan leaves carry their own ``slice_shifts`` / ``slice_valid`` tables
    (padded to the site's max slice count by the compiler —
    ``repro.models.pim_compile``); ``cfg.pim_weight_slicing`` is never
    read here. Truly global statics — ADC resolution, speculation — are
    reconstructed from ``cfg``.
    """
    lq = quantlib.LayerQuant(
        w_scale=plan["w_scale"], x_scale=plan["x_scale"],
        x_zero_point=jnp.asarray(0, jnp.int32), x_signed=True,
        out_scale=jnp.asarray(1.0, jnp.float32),
        out_zero_point=jnp.asarray(0, jnp.int32), bias=None)
    enc = None
    if "planes" in plan:
        # zero padded slice planes so correctness never depends on what the
        # compiler stored beyond each instance's true slice count
        valid = plan["slice_valid"]
        planes = plan["planes"] * valid[:, None, None, None].astype(
            plan["planes"].dtype)
        enc = co.EncodedWeights(
            planes=planes, centers=plan["enc_centers"],
            slicing=None, shifts=plan["slice_shifts"].astype(jnp.int32),
            rows=rows, rows_per_xbar=co.ROWS_PER_CROSSBAR)
    return pim_linear.PimPlan(
        enc=enc, lq=lq, w_q=plan["w_q"], weight_slicing=None,
        adc=adc_lib.ADCConfig(bits=cfg.pim_adc_bits, signed=True),
        speculation=cfg.pim_speculation,
        kernel_backend=cfg.pim_kernel_backend,
        device=device_backends.make(cfg.pim_crossbar_backend,
                                    cfg.pim_device_corner,
                                    seed=cfg.pim_device_seed),
        fast_w_off=plan.get("w_off"), fast_centers=plan.get("centers"),
        fast_scale=plan.get("scale"))


def pim_matmul(x: jnp.ndarray, w: jnp.ndarray, plan,
               cfg: ArchConfig) -> jnp.ndarray:
    """One weight-static projection, routed through ``cfg.pim_mode``.

    ``x (..., R) @ w (R, C)``. ``plan`` is this projection's compiled leaf
    from ``repro.models.pim.prepare_pim_params`` (``None`` -> float path:
    training, rwkv blocks, or ``pim_mode == 'off'``). Modes:

      fast  — centered int8 MXU matmul (paper Eq. 1; the kernel backend
              ``cfg.pim_kernel_backend`` names, see ``repro.kernels.ops``).
      exact — bit-exact accelerator simulation (Center+Offset, sliced
              crossbars, ADC, speculation) via ``pim_linear.forward_exact``.
      int8  — ideal 8b-quantized reference (``forward_int_reference``);
              the dequant oracle ``exact`` matches bit-for-bit at a
              non-saturating ADC.
    """
    if isinstance(plan, PimTap):
        plan.record(x.reshape(-1, x.shape[-1]))
        plan = None
    if plan is None or cfg.pim_mode == "off":
        return jnp.einsum("...r,rc->...c", x, w)
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    pp = _plan_to_pim_plan(plan, cfg, rows=w.shape[0])
    if cfg.pim_mode == "fast":
        y = pim_linear.forward_fast(xb, pp)
    elif cfg.pim_mode == "exact":
        if pim_stats_active():
            y, st = pim_linear.forward_exact(xb, pp, return_stats=True)
            for s in st:
                pim_stats_record(s)
        else:
            y = pim_linear.forward_exact(xb, pp)
    elif cfg.pim_mode == "int8":
        y = pim_linear.forward_int_reference(xb, pp)
    else:
        raise ValueError(f"unknown pim_mode {cfg.pim_mode!r}")
    return y.reshape(lead + (w.shape[-1],)).astype(x.dtype)


def plan_leaf(plans, key: str):
    """``plans[key]`` tolerating an absent plan tree (float path)."""
    return None if plans is None else plans.get(key)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


# ------------------------------------------------------------------ norms
def init_rmsnorm(d: int, dtype) -> tuple[dict, dict]:
    return ({"scale": jnp.ones((d,), dtype)}, {"scale": (None,)})


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    # variance accumulated in f32 via the dot unit; the apply stays in the
    # input dtype. (A bare astype(f32) of the block input gets hoisted out
    # of the XLA while-loop, materializing an f32 copy of every saved
    # residual layer at once.)
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32) / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)[..., None].astype(x.dtype)
    return x * inv * params["scale"]


# ------------------------------------------------------------------ rope
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D) with D even; positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ attention
def init_attention(key, cfg: ArchConfig) -> tuple[dict, dict]:
    """QKV/O projections stored 2D with the (heads x head_dim) axis merged:
    the merged axis is always divisible by the model-axis size (40 heads x
    128 = 5120 splits 16 ways even though 40 heads do not)."""
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * sc,
        "wk": jax.random.normal(ks[1], (d, k * hd), dt) * sc,
        "wv": jax.random.normal(ks[2], (d, k * hd), dt) * sc,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * (h * hd) ** -0.5,
    }
    s = {
        "wq": ("fsdp", "heads"),
        "wk": ("fsdp", "kv_heads"),
        "wv": ("fsdp", "kv_heads"),
        "wo": ("heads", "fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((k * hd,), dt)
        p["bv"] = jnp.zeros((k * hd,), dt)
        s["bq"] = ("heads",)
        s["bk"] = ("kv_heads",)
        s["bv"] = ("kv_heads",)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
        s["q_norm"] = (None,)
        s["k_norm"] = (None,)
    return p, s


def qkv_project(params: dict, cfg: ArchConfig, x: jnp.ndarray,
                positions: jnp.ndarray, plans=None):
    """x (B, S, D) -> q (B,S,H,hd), k/v (B,S,K,hd), RoPE applied."""
    B, S, _ = x.shape
    nh, nk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = pim_matmul(x, params["wq"], plan_leaf(plans, "wq"), cfg)
    k = pim_matmul(x, params["wk"], plan_leaf(plans, "wk"), cfg)
    v = pim_matmul(x, params["wv"], plan_leaf(plans, "wv"), cfg)
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nk, hd)
    v = v.reshape(B, S, nk, hd)
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_positions: jnp.ndarray,
                      kv_len: jnp.ndarray | int,
                      causal: bool,
                      chunk: int = ATTN_CHUNK) -> jnp.ndarray:
    """Online-softmax attention, scanning KV in chunks (flash-style, exact).

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H = K * G.
    q_positions: (B, Sq) global positions of the queries (causal masking).
    kv_len: number of valid KV entries (int or (B,) — masks cache padding).
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    if Sq == 1:
        # decode fast path: one query, no chunk scan
        return kref.decode_attention(q, k, v, kv_len)
    nk = -(-Sk // chunk)
    pad = nk * chunk - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = jnp.moveaxis(kp.reshape(B, nk, chunk, K, D), 1, 0)
    vc = jnp.moveaxis(vp.reshape(B, nk, chunk, K, D), 1, 0)
    qg = q.reshape(B, Sq, K, G, D).astype(jnp.float32)
    scale = D ** -0.5
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.broadcast_to(kv_len[None], (B,))

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kb.astype(jnp.float32)) * scale
        kpos = ci * chunk + jnp.arange(chunk)  # (chunk,)
        valid = kpos[None, :] < kv_len[:, None]  # (B, chunk)
        mask = valid[:, None, None, None, :]
        if causal:
            cm = kpos[None, None, :] <= q_positions[:, :, None]  # (B, Sq, chunk)
            mask = mask & cm[:, :, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new = -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, K, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, D), jnp.float32)
    # flash-style backward: recompute scores/probs per chunk instead of
    # saving (B, Sq, K, G, chunk) residuals for every chunk step
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0), (kc, vc, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attention_block(params: dict, cfg: ArchConfig, x: jnp.ndarray,
                    positions: jnp.ndarray, plans=None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = qkv_project(params, cfg, x, positions, plans)
    q = shard(q, "batch", "seq", None, None)
    out = chunked_attention(q, k, v, q_positions=positions, kv_len=S,
                            causal=cfg.causal)
    return pim_matmul(out.reshape(B, S, -1), params["wo"],
                      plan_leaf(plans, "wo"), cfg)


# ------------------------------------------------------------------ mlp
def init_mlp(key, cfg: ArchConfig) -> tuple[dict, dict]:
    d, f = cfg.d_model, cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {
        "w1": jax.random.normal(ks[0], (d, f), dt) * d ** -0.5,   # gate
        "w3": jax.random.normal(ks[1], (d, f), dt) * d ** -0.5,   # up
        "w2": jax.random.normal(ks[2], (f, d), dt) * f ** -0.5,   # down
    }
    s = {"w1": ("fsdp", "tp"), "w3": ("fsdp", "tp"), "w2": ("tp", "fsdp")}
    return p, s


def mlp_block(params: dict, cfg: ArchConfig, x: jnp.ndarray,
              plans=None) -> jnp.ndarray:
    a = act_fn(cfg.activation)
    h = a(pim_matmul(x, params["w1"], plan_leaf(plans, "w1"), cfg)) \
        * pim_matmul(x, params["w3"], plan_leaf(plans, "w3"), cfg)
    h = shard(h, "batch", "seq", "tp")
    return pim_matmul(h, params["w2"], plan_leaf(plans, "w2"), cfg)


# ------------------------------------------------------------------ moe
def init_moe(key, cfg: ArchConfig) -> tuple[dict, dict]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * d ** -0.5,
        "w1": jax.random.normal(ks[1], (e, d, f), dt) * d ** -0.5,
        "w3": jax.random.normal(ks[2], (e, d, f), dt) * d ** -0.5,
        "w2": jax.random.normal(ks[3], (e, f, d), dt) * f ** -0.5,
    }
    s = {"router": (None, None),
         "w1": ("experts", "fsdp", "tp"),
         "w3": ("experts", "fsdp", "tp"),
         "w2": ("experts", "tp", "fsdp")}
    return p, s


def _moe_group_size(E: int) -> int:
    """Dispatch-group length (slots): large enough that per-group expert
    capacity is not over-quantized, small enough that the one-hot dispatch
    tensor stays a few percent of expert compute."""
    return 1024 if E >= 64 else 512


def _expert_matmul(x5: jnp.ndarray, w3: jnp.ndarray, plan,
                   cfg: ArchConfig, spec: str) -> jnp.ndarray:
    """Per-expert projection: ``x5`` with an expert axis at dim 2 contracted
    against ``w3 (E, d_in, d_out)``. ``plan`` leaves carry a leading expert
    axis; the 2D pim path is vmapped over it (each expert is its own
    crossbar-programmed layer)."""
    if isinstance(plan, PimTap):
        plan.record(jnp.moveaxis(x5, 2, 0).reshape(
            x5.shape[2], -1, x5.shape[-1]))
        plan = None
    if plan is None or cfg.pim_mode == "off":
        return jnp.einsum(spec, x5, w3)
    xt = jnp.moveaxis(x5, 2, 0)  # (E, B, nG, cap, d_in)
    # stats stay suspended under the expert vmap: batched tracers must
    # not leak into an outer sink (converts/token reporting covers dense
    # projections; per-expert billing is a ROADMAP follow-on)
    with suspend_pim_stats():
        yt = jax.vmap(lambda xe, we, pe: pim_matmul(xe, we, pe, cfg))(
            xt, w3, plan)
    return jnp.moveaxis(yt, 0, 2)


def moe_block(params: dict, cfg: ArchConfig, x: jnp.ndarray,
              plans=None) -> jnp.ndarray:
    """Top-k capacity-based MoE, EP over 'experts' (GShard-style).

    Dispatch and combine are *one-hot einsums over sub-groups of slots* —
    no scatter/gather anywhere, so GSPMD partitions everything (batch x
    seq-groups x experts) and the only data movement is the all-to-all
    class resharding around the expert einsums. The dispatch tensor is
    (B, groups, g, E, cap_g): a few percent of expert FLOPs/bytes.
    """
    B, S, D = x.shape
    if S == 1 and B > 1:
        # decode: merge the batch into one dispatch group — per-token groups
        # would give every token a private (E x cap) buffer, i.e. dense
        # compute over all experts for one active row each (E-fold waste)
        out = moe_block(params, cfg, x.reshape(1, B, D), plans)
        return out.reshape(B, 1, D)
    E, k = cfg.n_experts, cfg.experts_per_token
    a = act_fn(cfg.activation)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    vals, idx = jax.lax.top_k(logits, k)                # (B, S, k)
    if k == 1:
        weights = jax.nn.sigmoid(vals)                  # llama4-style gate
    else:
        weights = jax.nn.softmax(vals, axis=-1)

    slots = S * k
    g = min(_moe_group_size(E), slots)
    nG = -(-slots // g)
    pad = nG * g - slots
    cap = max(k, int(math.ceil(cfg.capacity_factor * g / E)))

    fe = idx.reshape(B, slots)
    fw = weights.reshape(B, slots).astype(x.dtype)
    xr = jnp.repeat(x, k, axis=1)                        # (B, slots, D)
    if pad:
        fe = jnp.pad(fe, ((0, 0), (0, pad)), constant_values=E)  # E = none
        fw = jnp.pad(fw, ((0, 0), (0, pad)))
        xr = jnp.pad(xr, ((0, 0), (0, pad), (0, 0)))
    fe = fe.reshape(B, nG, g)
    fw = fw.reshape(B, nG, g)
    xg = xr.reshape(B, nG, g, D)
    xg = shard(xg, "batch", "seq", None, None)

    # ranks in f32: group length can exceed bf16's exact-integer range
    eh32 = jax.nn.one_hot(fe, E, dtype=jnp.float32)      # (B, nG, g, E)
    ranks = jnp.cumsum(eh32, axis=2) - eh32              # rank within expert
    pos = jnp.einsum("bnge,bnge->bng", ranks, eh32).astype(jnp.int32)
    eh = eh32.astype(x.dtype)
    keep = (pos < cap).astype(x.dtype)
    ph = jax.nn.one_hot(pos, cap, dtype=x.dtype)         # (B, nG, g, cap)
    dispatch = eh[..., :, None] * ph[..., None, :] \
        * keep[..., None, None]                          # (B, nG, g, E, cap)
    dispatch = shard(dispatch, "batch", None, None, "experts", None)

    buf = jnp.einsum("bngec,bngd->bnecd", dispatch, xg)  # (B, nG, E, cap, D)
    buf = shard(buf, "batch", None, "experts", None, None)
    h = a(_expert_matmul(buf, params["w1"], plan_leaf(plans, "w1"), cfg,
                         "bnecd,edf->bnecf")) \
        * _expert_matmul(buf, params["w3"], plan_leaf(plans, "w3"), cfg,
                         "bnecd,edf->bnecf")
    h = shard(h, "batch", None, "experts", None, "tp")
    y = _expert_matmul(h, params["w2"], plan_leaf(plans, "w2"), cfg,
                       "bnecf,efd->bnecd")
    y = shard(y, "batch", None, "experts", None, None)

    combine = dispatch * fw[..., None, None]
    out = jnp.einsum("bngec,bnecd->bngd", combine, y)    # (B, nG, g, D)
    out = out.reshape(B, nG * g, D)[:, :slots]
    return out.reshape(B, S, k, D).sum(axis=2)


# ------------------------------------------------------------------ embedding
def init_embedding(key, cfg: ArchConfig) -> tuple[dict, dict]:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 2)
    p, s = {}, {}
    p["embed"] = jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), dt) \
        * cfg.d_model ** -0.5
    s["embed"] = ("vocab", "fsdp")
    p["head"] = jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size), dt) \
        * cfg.d_model ** -0.5
    s["head"] = ("fsdp", "vocab")
    return p, s


def embed(params: dict, cfg: ArchConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embed"][tokens]


def lm_head(params: dict, cfg: ArchConfig, x: jnp.ndarray,
            plan=None) -> jnp.ndarray:
    logits = pim_matmul(x, params["head"], plan, cfg)
    return shard(logits, "batch", "seq", "vocab")
