"""Model assembly: pattern-of-blocks transformer covering all 10 assigned
architectures (dense / MoE GQA transformers, Mamba+attn hybrid, RWKV6,
encoder-only, early-fusion VLM backbone).

Layers are stacked as `lax.scan` over *pattern repeats* (pattern length 1
for uniform archs, 8 for Jamba's 7:1 mamba:attn interleave), with
`jax.checkpoint` per repeat — HLO size stays flat in depth and activation
memory is one boundary tensor per repeat.

Public surface:
  init_params(cfg, key)            -> (params, specs)
  forward(params, cfg, batch)      -> logits            (train / no cache)
  init_decode_state(cfg, B, L)     -> state             (caches + position)
  prefill(params, cfg, tokens)     -> (logits, state)
  decode_step(params, cfg, state, tok) -> (logits, state)
  lm_loss(params, cfg, batch)      -> scalar

Paged KV (vLLM-style block tables — see ``repro.serve.paged``):
  PagedLayout(n_blocks, block_size)             pool geometry
  init_decode_state(..., paged=layout)          block-pool attn caches
  decode_step(..., block_tables=, paged=)       gather/write via tables
  prefill_chunk_paged(...)                      in-pool chunked prefill
  insert_request_paged(...)                     contiguous -> blocks scatter
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import shard
from repro.kernels import ops as kops
from repro.kernels.paged_attention import pool_view
from repro.models import layers as L
from repro.models import ssm as S


# ------------------------------------------------------------------ init
def _init_block(key, cfg: ArchConfig, pattern_idx: int) -> tuple[dict, dict]:
    kind = cfg.block_pattern[pattern_idx]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p, s = {}, {}
    p["norm1"], s["norm1"] = L.init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype))
    if kind == "attn":
        p["core"], s["core"] = L.init_attention(k1, cfg)
    elif kind == "mamba":
        p["core"], s["core"] = S.init_mamba(k1, cfg)
    elif kind == "rwkv":
        p["core"], s["core"] = S.init_rwkv(k1, cfg)
    else:
        raise ValueError(kind)
    p["norm2"], s["norm2"] = L.init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype))
    if kind == "rwkv":
        p["ffn"], s["ffn"] = S.init_rwkv_channel_mix(k2, cfg)
    elif cfg.moe_layer(pattern_idx):
        p["ffn"], s["ffn"] = L.init_moe(k2, cfg)
    else:
        p["ffn"], s["ffn"] = L.init_mlp(k2, cfg)
    return p, s


def param_specs(cfg: ArchConfig) -> dict:
    """Logical sharding axes mirroring the init_params pytree.

    Built from a reduced twin config (same structure, tiny shapes) so no
    full-size array is ever allocated — the dry-run calls this on 400B
    configs where a concrete init would not fit host memory.
    """
    tiny = cfg.reduced()
    specs = {}
    _, specs["embed"] = L.init_embedding(jax.random.key(0), tiny)
    bspecs = []
    for i in range(len(cfg.block_pattern)):
        _, s_one = _init_block(jax.random.key(0), tiny, i)
        s_stack = jax.tree.map(lambda ax: (None,) + tuple(ax), s_one,
                               is_leaf=lambda x: isinstance(x, tuple))
        bspecs.append(s_stack)
    specs["blocks"] = bspecs
    _, specs["final_norm"] = L.init_rmsnorm(tiny.d_model, jnp.dtype(tiny.dtype))
    return specs


def init_params(cfg: ArchConfig, key) -> tuple[dict, dict]:
    keys = jax.random.split(key, 2 + len(cfg.block_pattern))
    params = {}
    params["embed"], _ = L.init_embedding(keys[0], cfg)
    blocks = []
    for i in range(len(cfg.block_pattern)):
        rep_keys = jax.random.split(keys[1 + i], cfg.n_repeats)
        p_stack = jax.vmap(lambda k: _init_block(k, cfg, i)[0])(rep_keys)
        blocks.append(p_stack)
    params["blocks"] = blocks
    params["final_norm"], _ = L.init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype))
    return params, param_specs(cfg)


# ------------------------------------------------------------------ blocks
_subplan = L.plan_leaf  # ``plan[key]`` tolerating an absent plan tree


def _apply_block(kind: str, pattern_idx: int, bp: dict, cfg: ArchConfig,
                 x: jnp.ndarray, positions: jnp.ndarray,
                 plan=None) -> jnp.ndarray:
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        h = L.attention_block(bp["core"], cfg, h, positions,
                              plans=_subplan(plan, "core"))
    elif kind == "mamba":
        h = S.mamba_block(bp["core"], cfg, h, plans=_subplan(plan, "core"))
    elif kind == "rwkv":
        h = S.rwkv_time_mix(bp["core"], cfg, h)
    x = x + h
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    if kind == "rwkv":
        h = S.rwkv_channel_mix(bp["ffn"], cfg, h)
    elif cfg.moe_layer(pattern_idx):
        h = L.moe_block(bp["ffn"], cfg, h, plans=_subplan(plan, "ffn"))
    else:
        h = L.mlp_block(bp["ffn"], cfg, h, plans=_subplan(plan, "ffn"))
    x = x + h
    return shard(x, "batch", "seq", None)


# ------------------------------------------------------------------ forward
def embed_inputs(params: dict, cfg: ArchConfig, inputs: jnp.ndarray):
    if cfg.input_mode == "tokens":
        x = L.embed(params["embed"], cfg, inputs)
    else:
        x = inputs.astype(jnp.dtype(cfg.dtype))
    return shard(x, "batch", "seq", None)


def _plan_blocks(cfg: ArchConfig, plans) -> tuple:
    """Per-pattern-position plan trees (Nones when no plans ride along)."""
    if plans is None:
        return tuple([None] * len(cfg.block_pattern))
    return tuple(plans["blocks"])


def forward_hidden(params: dict, cfg: ArchConfig, inputs: jnp.ndarray,
                   plans=None) -> jnp.ndarray:
    """Full-sequence forward to final hidden states (B, S, D).

    ``plans`` is the compiled PIM-plan pytree from
    ``repro.models.pim.prepare_pim_params``; its stacked block plans ride
    the ``lax.scan`` next to the stacked params.
    """
    x = embed_inputs(params, cfg, inputs)
    B, Seq = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Seq, dtype=jnp.int32), (B, Seq))

    def repeat_body(carry, xs):
        h = carry
        rep_params, rep_plans = xs
        for i, kind in enumerate(cfg.block_pattern):
            if cfg.remat and len(cfg.block_pattern) > 1:
                # nested remat: backward re-gathers one block's weights at a
                # time instead of the whole pattern body's (Jamba: 8 layers)
                h = jax.checkpoint(
                    lambda bp, pl, hh, _i=i, _k=kind: _apply_block(
                        _k, _i, bp, cfg, hh, positions, plan=pl))(
                            rep_params[i], rep_plans[i], h)
            else:
                h = _apply_block(kind, i, rep_params[i], cfg, h, positions,
                                 plan=rep_plans[i])
        return h, None

    body = jax.checkpoint(repeat_body) if cfg.remat else repeat_body
    with L.suspend_pim_stats():  # tracer hygiene — see _run_prefill_body
        x, _ = jax.lax.scan(
            body, x, (tuple(params["blocks"]), _plan_blocks(cfg, plans)))
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward(params: dict, cfg: ArchConfig, inputs: jnp.ndarray,
            plans=None) -> jnp.ndarray:
    """Full-sequence forward to logits. inputs: tokens (B,S) or embeds (B,S,D)."""
    return L.lm_head(params["embed"], cfg,
                     forward_hidden(params, cfg, inputs, plans),
                     plan=_subplan(_subplan(plans, "embed"), "head"))


# ------------------------------------------------------------------ losses
LOSS_CHUNK = 512  # seq positions per logits chunk (vocab up to 202k)


def lm_loss(params: dict, cfg: ArchConfig, batch: dict) -> jnp.ndarray:
    """Causal LM loss (tokens mode) or full-position unit-prediction loss
    (embeddings mode — hubert-style masked-unit proxy).

    Cross-entropy is computed in sequence chunks under remat so the
    (B, S, vocab) logits tensor never materializes — at vocab 202k /
    1M tokens the full fp32 logits alone would be ~0.8 TB.
    """
    x = forward_hidden(params, cfg, batch["inputs"])  # (B, S, D)
    labels = batch["labels"]
    mask = batch.get("mask")
    B, S, D = x.shape
    if cfg.input_mode == "tokens" and cfg.causal:
        # position t predicts labels[t+1]; last position masked out
        labels = jnp.concatenate(
            [labels[:, 1:], jnp.zeros((B, 1), labels.dtype)], axis=1)
        last = (jnp.arange(S) < S - 1).astype(jnp.float32)[None, :]
        mask = last * (jnp.ones((B, S), jnp.float32) if mask is None else mask)

    C = min(LOSS_CHUNK, S)
    nc = -(-S // C)
    pad = nc * C - S
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = jnp.broadcast_to(mask, (B, S))
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)))
    mp = jnp.pad(mask, ((0, 0), (0, pad)))
    xs = (shard(jnp.moveaxis(xp.reshape(B, nc, C, D), 1, 0),
                None, "batch", None, None),
          jnp.moveaxis(lp.reshape(B, nc, C), 1, 0),
          jnp.moveaxis(mp.reshape(B, nc, C), 1, 0))

    def body(carry, chunk):
        tot, cnt = carry
        xc, lc, mc = chunk
        xc = shard(xc, "batch", None, None)
        logits = jnp.einsum("bsd,dv->bsv", xc,
                            params["embed"]["head"]).astype(jnp.float32)
        logits = shard(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mc
        return (tot + nll.sum(), cnt + mc.sum()), None

    z = jnp.zeros((), jnp.float32)
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body), (z, z), xs)
    return tot / jnp.maximum(cnt, 1.0)


# ------------------------------------------------------------------ decode
@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Geometry of a paged KV pool (vLLM-style block tables).

    Attention caches become one pool of ``n_blocks`` fixed-size blocks
    shared by every slot, plus one *scratch* row at index ``n_blocks``
    (the ``sentinel``). A slot's block table maps block index ->
    pool row; table entries equal to the sentinel land writes in scratch
    and gather garbage that the attention ``kv_len`` mask then zeroes
    exactly, so idle or mid-prefill slots stay no-ops without any
    conditional in the jitted step. Recurrent (mamba/rwkv) carries are
    per-slot, not paged — they have no sequence axis to page.
    """
    n_blocks: int
    block_size: int

    @property
    def sentinel(self) -> int:
        return self.n_blocks

    @property
    def pool_rows(self) -> int:
        return self.n_blocks + 1

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int, *,
                      per_slot_pos: bool = False,
                      paged: PagedLayout | None = None) -> dict:
    """Stacked per-repeat caches for every pattern position.

    With ``per_slot_pos`` the state carries one position per batch slot
    (shape ``(batch,)``) instead of a single scalar, so each slot can sit
    at a different sequence offset — the substrate for continuous
    batching (see ``repro.serve.scheduler``).

    With ``paged`` the attention caches are block pools
    ``(n_repeats, n_blocks + 1, block_size, kv_heads, head_dim)`` instead
    of per-slot ``(batch, max_len)`` regions; ``decode_step`` then needs
    per-slot ``block_tables`` to address them. Recurrent carries keep
    their per-slot ``batch`` axis either way.
    """
    hd = cfg.resolved_head_dim
    kv_dt = jnp.dtype(cfg.kv_cache_dtype)
    caches = []
    for kind in cfg.block_pattern:
        if kind == "attn":
            if paged is not None:
                shape = (cfg.n_repeats, paged.pool_rows, paged.block_size,
                         cfg.n_kv_heads, hd)
            else:
                shape = (cfg.n_repeats, batch, max_len, cfg.n_kv_heads, hd)
            c = {"k": jnp.zeros(shape, kv_dt), "v": jnp.zeros(shape, kv_dt)}
            if kv_dt == jnp.int8:
                c["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
                c["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        elif kind == "mamba":
            one = S.init_mamba_state(cfg, batch)
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None],
                                           (cfg.n_repeats,) + a.shape).copy(), one)
        else:  # rwkv
            one = S.init_rwkv_state(cfg, batch)
            c = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None],
                                           (cfg.n_repeats,) + a.shape).copy(), one)
        caches.append(c)
    pos = jnp.zeros((batch,) if per_slot_pos else (), jnp.int32)
    return {"caches": caches, "pos": pos}


def cache_specs(cfg: ArchConfig, *, paged: bool = False) -> dict:
    """Logical shardings for the decode state (KV cache seq-sharded).

    Paged pools shard their *block* axis on ``cache_batch`` (blocks play
    the role per-slot regions play contiguously: under
    ``MULTIPOD_SERVE_RULES`` the pool spreads over the decode slice's
    ``("pod", "data")`` product while weights stay stationary)."""
    caches = []
    for kind in cfg.block_pattern:
        if kind == "attn":
            if paged:
                c = {"k": (None, "cache_batch", None, "kv_heads", None),
                     "v": (None, "cache_batch", None, "kv_heads", None)}
            else:
                c = {"k": (None, "cache_batch", "seq", "kv_heads", None),
                     "v": (None, "cache_batch", "seq", "kv_heads", None)}
            if jnp.dtype(cfg.kv_cache_dtype) == jnp.int8:
                c["k_scale"] = c["k"][:-1]
                c["v_scale"] = c["v"][:-1]
        elif kind == "mamba":
            c = {"h": (None, "cache_batch", "tp", None),
                 "conv": (None, "cache_batch", None, "tp")}
        else:
            c = {"h": (None, "cache_batch", "heads", None, None),
                 "x_prev": (None, "cache_batch", None),
                 "cm_prev": (None, "cache_batch", None)}
        caches.append(c)
    return {"caches": caches, "pos": ()}


def _write_token(buf: jnp.ndarray, new: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """Write a one-token slice ``new`` (B, 1, ...) into a (B, L, ...) cache.

    Scalar ``pos`` keeps the lockstep dynamic-update path; a ``(B,)`` pos
    scatters each slot's row at its own offset (``mode="drop"``:
    out-of-range per-slot positions write nothing, so retired/idle slots
    are no-ops).
    """
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), pos, axis=1)
    B = buf.shape[0]
    return buf.at[jnp.arange(B), pos].set(new[:, 0].astype(buf.dtype),
                                          mode="drop")


def _paged_write_token(pool: jnp.ndarray, new: jnp.ndarray,
                       pos: jnp.ndarray, tables: jnp.ndarray,
                       paged: PagedLayout) -> jnp.ndarray:
    """Write each slot's one-token slice ``new`` (B, 1, ...) into its
    current block of a ``(pool_rows, block_size, ...)`` pool leaf.

    Slots whose table entry is the sentinel (idle, retired, or still
    mid-prefill — the scheduler hands ``decode_step`` a sentinel row for
    them) write into the scratch block, which no live gather ever
    unmasks. A K/V leaf is written through the lane-dense view of its
    blocks that the paged attention kernel reads, so on a TPU the layer's
    pool stays in one layout between the write and the read."""
    B = new.shape[0]
    bi = jnp.clip(pos // paged.block_size, 0, tables.shape[1] - 1)
    rows = tables[jnp.arange(B), bi]
    if pool.ndim == 4:
        c, lanes = pool_view(*pool.shape[2:])
        view = pool.reshape(pool.shape[:2] + (pool.shape[2] // c, lanes))
        tok = new[:, 0].reshape((B,) + view.shape[2:])
        return view.at[rows, pos % paged.block_size].set(
            tok.astype(pool.dtype)).reshape(pool.shape)
    return pool.at[rows, pos % paged.block_size].set(
        new[:, 0].astype(pool.dtype))


def _paged_gather(pool: jnp.ndarray, tables: jnp.ndarray,
                  paged: PagedLayout) -> jnp.ndarray:
    """(pool_rows, block_size, ...) pool + (B, max_blocks) tables -> a
    (B, max_blocks * block_size, ...) contiguous-cache view.

    Sentinel entries gather the scratch block; those positions sit at or
    beyond ``kv_len``, so the attention mask turns them into exact-zero
    contributions and the view reduces bit-identically to a contiguous
    ``(B, max_len, ...)`` cache of the same total length."""
    B, max_blocks = tables.shape
    view = pool[jnp.clip(tables, 0, paged.sentinel)]
    return view.reshape((B, max_blocks * paged.block_size) + pool.shape[2:])


def _quantize_kv(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(..., hd) -> int8 codes + per-(token, head) fp32 scale (RAELLA-style
    low-precision storage with a digital correction factor)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _attn_decode(bp: dict, cfg: ArchConfig, cache: dict, x: jnp.ndarray,
                 pos: jnp.ndarray, plans=None, tables=None,
                 paged: PagedLayout | None = None) -> tuple[dict, jnp.ndarray]:
    """Single-token attention against the (sequence-sharded) KV cache.

    ``pos`` is a scalar (lockstep: the whole batch shares one position) or
    a ``(B,)`` vector (continuous batching: one position per slot). With
    ``tables``/``paged`` the cache leaves are block pools: the new token
    scatters into each slot's current block, and attention reads the
    slot's live blocks in place (``kernels.ops.paged_decode_attention``)
    or, for an int8 pool, dequantizes a block-table gather of the slot's
    history.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], (B, 1))
    else:
        positions = pos[:, None]
    q, k_new, v_new = L.qkv_project(bp["core"], cfg, x, positions, plans)
    # align the query/new-KV batch with the cache's batch sharding so the
    # whole attention stays device-local (otherwise the dequantized cache
    # moves across the mesh every step)
    q = shard(q, "cache_batch", None, None, None)
    k_new = shard(k_new, "cache_batch", None, None, None)
    v_new = shard(v_new, "cache_batch", None, None, None)
    int8_cache = jnp.dtype(cfg.kv_cache_dtype) == jnp.int8
    if tables is not None:
        write = lambda buf, new: shard(  # noqa: E731
            _paged_write_token(buf, new, pos, tables, paged),
            "cache_batch", None, "kv_heads", None)
        gather = lambda buf: _paged_gather(buf, tables, paged)  # noqa: E731
        if int8_cache:
            write_s = lambda buf, new: _paged_write_token(  # noqa: E731
                buf, new, pos, tables, paged)
    else:
        write = write_s = lambda buf, new: _write_token(  # noqa: E731
            buf, new, pos)
        gather = lambda buf: buf  # noqa: E731
    if int8_cache:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        new_cache = {
            "k": write(cache["k"], kq),
            "v": write(cache["v"], vq),
            "k_scale": write_s(cache["k_scale"], ks),
            "v_scale": write_s(cache["v_scale"], vs),
        }
        k_cache = _dequantize_kv(gather(new_cache["k"]),
                                 gather(new_cache["k_scale"]), x.dtype)
        v_cache = _dequantize_kv(gather(new_cache["v"]),
                                 gather(new_cache["v_scale"]), x.dtype)
    else:
        new_cache = {"k": write(cache["k"], k_new),
                     "v": write(cache["v"], v_new)}
        k_cache, v_cache = new_cache["k"], new_cache["v"]
    if tables is not None and not int8_cache:
        out = kops.paged_decode_attention(q[:, 0], k_cache, v_cache, tables,
                                          pos + 1, sentinel=paged.sentinel)
    else:
        out = L.chunked_attention(q, k_cache, v_cache, q_positions=positions,
                                  kv_len=pos + 1, causal=True)
    y = L.pim_matmul(out.reshape(B, 1, -1), bp["core"]["wo"],
                     L.plan_leaf(plans, "wo"), cfg)
    return new_cache, y


def _decode_block(kind: str, pattern_idx: int, bp: dict, cfg: ArchConfig,
                  cache: dict, x: jnp.ndarray, pos: jnp.ndarray, plan=None,
                  tables=None, paged: PagedLayout | None = None):
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        cache, h = _attn_decode(bp, cfg, cache, h, pos,
                                plans=_subplan(plan, "core"),
                                tables=tables, paged=paged)
    elif kind == "mamba":
        cache, h = S.mamba_decode_step(bp["core"], cfg, cache, h,
                                       plans=_subplan(plan, "core"))
    else:
        cache, h = S.rwkv_time_mix_decode(bp["core"], cfg, cache, h)
    x = x + h
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    if kind == "rwkv":
        h = S.rwkv_channel_mix(bp["ffn"], cfg, h,
                               x_prev=cache["cm_prev"][:, None, :])
        cache = dict(cache, cm_prev=L.rmsnorm(bp["norm2"], x, cfg.norm_eps)[:, 0])
    elif cfg.moe_layer(pattern_idx):
        h = L.moe_block(bp["ffn"], cfg, h, plans=_subplan(plan, "ffn"))
    else:
        h = L.mlp_block(bp["ffn"], cfg, h, plans=_subplan(plan, "ffn"))
    return cache, x + h


def decode_step(params: dict, cfg: ArchConfig, state: dict,
                tokens: jnp.ndarray, plans=None, *, block_tables=None,
                paged: PagedLayout | None = None) -> tuple[jnp.ndarray, dict]:
    """One decode step. tokens: (B, 1) ids or (B, 1, D) embeds.

    ``state["pos"]`` may be a scalar (lockstep) or ``(B,)`` (per-slot,
    continuous batching); every slot's position advances by one.

    With a paged state, pass ``block_tables`` ((B, max_blocks) int32 pool
    rows, sentinel = ``paged.sentinel`` for unmapped entries) and the
    matching ``paged`` layout: each slot writes its token into its
    current block and attends over a block-table gather — bit-identical
    to the contiguous cache when ``max_blocks * block_size == max_len``
    (masked positions contribute exact zeros either way). Slots the
    scheduler does not want touched (mid-prefill) should be handed an
    all-sentinel table row, which turns their write into a scratch-block
    no-op.
    """
    if (block_tables is None) != (paged is None):
        raise ValueError("block_tables and paged must be passed together")
    x = embed_inputs(params, cfg, tokens)
    pos = state["pos"]
    if block_tables is not None and pos.ndim == 0:
        raise ValueError("paged decode needs per-slot positions "
                         "(init_decode_state(..., per_slot_pos=True))")
    # sow-style work-stats collection (see layers.collect_pim_stats):
    # stats tracers born inside the scanned block body belong to the
    # scan sub-trace, so the body opens its OWN sink and re-emits the
    # summed totals as scan outputs; the per-repeat stacks are reduced
    # below and recorded into the caller's sink as outer-trace values.
    collect = L.pim_stats_active()

    def repeat_body(carry, xs):
        h = carry
        rep_params, rep_caches, rep_plans = xs
        new_caches = []
        ctx = L.collect_pim_stats() if collect else contextlib.nullcontext([])
        with ctx as inner:
            for i, kind in enumerate(cfg.block_pattern):
                c, h = _decode_block(kind, i, rep_params[i], cfg,
                                     rep_caches[i], h, pos,
                                     plan=rep_plans[i],
                                     tables=block_tables, paged=paged)
                new_caches.append(c)
        if collect:
            totals = {k: jnp.asarray(v)
                      for k, v in L.pim_stats_totals(inner).items()}
            return h, (tuple(new_caches), totals)
        return h, tuple(new_caches)

    x, ys = jax.lax.scan(
        repeat_body, x, (tuple(params["blocks"]), tuple(state["caches"]),
                         _plan_blocks(cfg, plans)))
    if collect:
        new_caches, rep_totals = ys
        L.pim_stats_record({k: v.sum(axis=0) for k, v in rep_totals.items()})
    else:
        new_caches = ys
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(params["embed"], cfg, x,
                       plan=_subplan(_subplan(plans, "embed"), "head"))
    new_state = {"caches": list(new_caches), "pos": pos + 1}
    return logits, new_state


# ------------------------------------------------------------------ prefill
def _prefill_repeat_body(cfg: ArchConfig, B: int, C: int,
                         positions: jnp.ndarray, pos0: jnp.ndarray,
                         kv_len: jnp.ndarray, raw_attn: bool):
    """Shared per-repeat body for whole-prompt and chunked prefill.

    Consumes ``(rep_params, rep_caches)`` and writes the processed chunk
    into the caches at ``pos0``. ``raw_attn`` selects where attention
    reads K/V from: this call's raw projections (whole-prompt prefill —
    also the encoder path, honoring ``cfg.causal``) or the cache buffer
    (chunked continuation: earlier chunks are only available there).
    The recurrent mamba/rwkv branches continue from the cached carries
    either way — a zero-initialized state makes them identical to a
    fresh forward.
    """
    int8_cache = jnp.dtype(cfg.kv_cache_dtype) == jnp.int8

    def repeat_body(carry, xs):
        h = carry
        rep_params, rep_caches, rep_plans = xs
        new_caches = []
        for i, kind in enumerate(cfg.block_pattern):
            bp, cache, plan = rep_params[i], rep_caches[i], rep_plans[i]
            core_plan = _subplan(plan, "core")
            hn = L.rmsnorm(bp["norm1"], h, cfg.norm_eps)
            if kind == "attn":
                q, k, v = L.qkv_project(bp["core"], cfg, hn, positions,
                                        core_plan)
                if int8_cache:
                    kq, ks = _quantize_kv(k)
                    vq, vs = _quantize_kv(v)
                    cache = {
                        "k": jax.lax.dynamic_update_slice_in_dim(
                            cache["k"], kq, pos0, axis=1),
                        "v": jax.lax.dynamic_update_slice_in_dim(
                            cache["v"], vq, pos0, axis=1),
                        "k_scale": jax.lax.dynamic_update_slice_in_dim(
                            cache["k_scale"], ks, pos0, axis=1),
                        "v_scale": jax.lax.dynamic_update_slice_in_dim(
                            cache["v_scale"], vs, pos0, axis=1),
                    }
                else:
                    cache = {
                        "k": jax.lax.dynamic_update_slice_in_dim(
                            cache["k"], k.astype(cache["k"].dtype), pos0,
                            axis=1),
                        "v": jax.lax.dynamic_update_slice_in_dim(
                            cache["v"], v.astype(cache["v"].dtype), pos0,
                            axis=1),
                    }
                cache["k"] = shard(cache["k"], "cache_batch", "seq",
                                   "kv_heads", None)
                cache["v"] = shard(cache["v"], "cache_batch", "seq",
                                   "kv_heads", None)
                if raw_attn:
                    q = shard(q, "batch", "seq", None, None)
                    o = L.chunked_attention(q, k, v, q_positions=positions,
                                            kv_len=kv_len,
                                            causal=cfg.causal)
                else:
                    q = shard(q, "cache_batch", None, None, None)
                    if int8_cache:
                        k_all = _dequantize_kv(cache["k"], cache["k_scale"],
                                               hn.dtype)
                        v_all = _dequantize_kv(cache["v"], cache["v_scale"],
                                               hn.dtype)
                    else:
                        k_all = cache["k"].astype(hn.dtype)
                        v_all = cache["v"].astype(hn.dtype)
                    o = L.chunked_attention(q, k_all, v_all,
                                            q_positions=positions,
                                            kv_len=kv_len, causal=True)
                core_out = L.pim_matmul(o.reshape(B, C, -1),
                                        bp["core"]["wo"],
                                        L.plan_leaf(core_plan, "wo"), cfg)
            elif kind == "mamba":
                xc, z, dtf, bm, cm, new_conv = S._mamba_preprocess(
                    bp["core"], cfg, hn, conv_state=cache["conv"],
                    plans=core_plan)

                def step(hh, xs_t):
                    xt, bt, ct, dtt = xs_t
                    return S._mamba_step(bp["core"], cfg, hh, xt, bt, ct, dtt)

                xs_seq = tuple(jnp.moveaxis(a, 1, 0)
                               for a in (xc, bm, cm, dtf))
                h_fin, ys = S._chunked_scan(step, cache["h"], xs_seq,
                                            S.SCAN_CHUNK, cfg.remat)
                y = jnp.moveaxis(ys, 0, 1).astype(hn.dtype) * jax.nn.silu(z)
                core_out = L.pim_matmul(y, bp["core"]["out_proj"],
                                        L.plan_leaf(core_plan, "out_proj"),
                                        cfg)
                cache = {"h": h_fin, "conv": new_conv}
            else:  # rwkv
                x_prev = jnp.concatenate(
                    [cache["x_prev"][:, None].astype(hn.dtype), hn[:, :-1]],
                    axis=1)
                rh, kh, vh, wh, g = S._rwkv_project(bp["core"], cfg, hn,
                                                    x_prev)

                def step(hh, xs_t):
                    rt, kt, vt, wt = xs_t
                    return S._rwkv_step(bp["core"], hh, rt, kt, vt, wt)

                xs_seq = tuple(jnp.moveaxis(a, 1, 0)
                               for a in (rh, kh, vh, wh))
                h_fin, ys = S._chunked_scan(step, cache["h"], xs_seq,
                                            S.SCAN_CHUNK, cfg.remat)
                y = jnp.moveaxis(ys, 0, 1).reshape(hn.shape).astype(hn.dtype)
                y = y * jax.lax.rsqrt(
                    jnp.mean(jnp.square(y), -1, keepdims=True) + cfg.norm_eps)
                y = y * bp["core"]["ln_x"] * jax.nn.silu(g)
                core_out = jnp.einsum("bsd,de->bse", y, bp["core"]["wo"])
                cm_prev_in = cache["cm_prev"]
                cache = {"h": h_fin, "x_prev": hn[:, -1]}
            h = h + core_out
            hn2 = L.rmsnorm(bp["norm2"], h, cfg.norm_eps)
            if kind == "rwkv":
                cm_hist = jnp.concatenate(
                    [cm_prev_in[:, None].astype(hn2.dtype), hn2[:, :-1]],
                    axis=1)
                ffn_out = S.rwkv_channel_mix(bp["ffn"], cfg, hn2,
                                             x_prev=cm_hist)
                cache["cm_prev"] = hn2[:, -1]
            elif cfg.moe_layer(i):
                ffn_out = L.moe_block(bp["ffn"], cfg, hn2,
                                      plans=_subplan(plan, "ffn"))
            else:
                ffn_out = L.mlp_block(bp["ffn"], cfg, hn2,
                                      plans=_subplan(plan, "ffn"))
            h = shard(h + ffn_out, "batch", "seq", None)
            new_caches.append(cache)
        return h, tuple(new_caches)

    return repeat_body


def _run_prefill_body(params: dict, cfg: ArchConfig, x: jnp.ndarray,
                      caches, body, plans=None) -> tuple[jnp.ndarray, list]:
    body = jax.checkpoint(body) if cfg.remat else body
    # work-stats collection is decode-focused: suspend sinks while the
    # scan traces its body so block-internal stats tracers cannot leak
    # (the converts/token metric bills decode steps; lm_head below still
    # records — it sits outside the scan)
    with L.suspend_pim_stats():
        x, new_caches = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(caches),
                      _plan_blocks(cfg, plans)))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(params["embed"], cfg, x[:, -1:],
                       plan=_subplan(_subplan(plans, "embed"), "head"))
    return logits, list(new_caches)


def prefill(params: dict, cfg: ArchConfig, inputs: jnp.ndarray,
            max_len: int | None = None,
            plans=None) -> tuple[jnp.ndarray, dict]:
    """Process a prompt, returning last-position logits + a filled decode
    state. Cache buffers sized to max_len (default: prompt length).
    Attention runs over this call's raw K/V (``causal=cfg.causal``, so
    encoder-only archs work too); K/V are then stored into the cache."""
    x = embed_inputs(params, cfg, inputs)
    B, Seq = x.shape[0], x.shape[1]
    max_len = max_len or Seq
    state = init_decode_state(cfg, B, max_len)
    positions = jnp.broadcast_to(jnp.arange(Seq, dtype=jnp.int32), (B, Seq))
    body = _prefill_repeat_body(cfg, B, Seq, positions,
                                pos0=jnp.zeros((), jnp.int32),
                                kv_len=Seq, raw_attn=True)
    logits, caches = _run_prefill_body(params, cfg, x, state["caches"], body,
                                       plans=plans)
    return logits, {"caches": caches, "pos": jnp.asarray(Seq, jnp.int32)}


def prefill_chunk(params: dict, cfg: ArchConfig, state: dict,
                  tokens: jnp.ndarray, plans=None) -> tuple[jnp.ndarray, dict]:
    """Process the next prompt chunk of an in-flight (chunked) prefill.

    ``state`` is a scalar-pos decode state whose caches hold positions
    ``[0, state["pos"])``; ``tokens`` (B, C) ids — or (B, C, D) embeds —
    continue the prompt at that offset. Returns last-position logits and
    the advanced state, exactly like ``prefill``.

    ``init_decode_state`` followed by ``prefill_chunk`` over the whole
    prompt reproduces ``prefill`` bit-for-bit for float KV caches (the
    recurrent mamba/rwkv states continue their scans from the cached
    carry; attention reads earlier chunks back out of the cache, which is
    value-preserving when the cache dtype holds K/V exactly). With an
    int8 KV cache each chunk boundary inserts one quantize/dequantize
    round-trip that whole-prompt ``prefill`` does not have.
    """
    x = embed_inputs(params, cfg, tokens)
    B, C = x.shape[0], x.shape[1]
    pos0 = jnp.asarray(state["pos"], jnp.int32)
    positions = jnp.broadcast_to(pos0 + jnp.arange(C, dtype=jnp.int32),
                                 (B, C))
    body = _prefill_repeat_body(cfg, B, C, positions, pos0=pos0,
                                kv_len=pos0 + C, raw_attn=False)
    logits, caches = _run_prefill_body(params, cfg, x, state["caches"], body,
                                       plans=plans)
    return logits, {"caches": caches, "pos": pos0 + C}


def insert_request(state: dict, one: dict, slot: jnp.ndarray) -> dict:
    """Splice a batch-1 decode state into slot ``slot`` of a batched state.

    ``state`` must carry per-slot positions (``init_decode_state(...,
    per_slot_pos=True)``); ``one`` is a scalar-pos state produced by
    ``prefill``/``prefill_chunk`` at batch 1. Every cache leaf is written
    along the batch axis (axis 1 — leaves are stacked per repeat), so the
    slot's previous contents are fully replaced.
    """
    caches = jax.tree.map(
        lambda c, o: jax.lax.dynamic_update_slice_in_dim(
            c, o.astype(c.dtype), slot, axis=1),
        state["caches"], one["caches"])
    pos = state["pos"].at[slot].set(jnp.asarray(one["pos"], jnp.int32))
    return {"caches": caches, "pos": pos}


# ----------------------------------------------------------------- paged
def prefill_chunk_paged(params: dict, cfg: ArchConfig, state: dict,
                        tokens: jnp.ndarray, *, slot, table_row, pos0,
                        paged: PagedLayout,
                        plans=None) -> tuple[jnp.ndarray, dict]:
    """Advance one slot's in-flight prefill *inside* the shared block pool
    (copy-free admission: the prompt streams straight into the slot's
    claimed blocks, never through a contiguous staging region).

    ``state`` is the batched paged decode state; ``tokens`` (1, C) is the
    next prompt chunk for ``slot``, whose earlier context — including
    refcount-shared prefix blocks — is read back through ``table_row``
    ((max_blocks,) int32 pool rows). ``pos0`` is the chunk's absolute
    start offset, passed explicitly because the batched ``pos[slot]``
    keeps advancing with every interleaved decode step while this slot is
    still prefilling (those decode writes land in the sentinel scratch
    block); the final value ``pos0 + C`` is written back into
    ``pos[slot]`` so a completed prefill leaves the slot decode-ready.

    Bit-identity with the contiguous ``prefill_chunk`` path follows from
    the gather argument in ``_paged_gather``; the shared-prefix case
    additionally relies on chunked prefill being boundary-independent for
    float KV caches (the chunk after a shared prefix starts at a block
    boundary, not necessarily a ``prefill_chunk`` multiple).

    Attention-only patterns: recurrent (mamba/rwkv) carries cannot be
    rebuilt from paged context — recurrent archs stage their prefill at
    B=1 and hand the result over via ``insert_request_paged``.
    """
    bad = [k for k in cfg.block_pattern if k != "attn"]
    if bad:
        raise ValueError(
            f"prefill_chunk_paged supports attention-only patterns; "
            f"{cfg.name} has {bad} blocks — stage the prefill at B=1 and "
            f"use insert_request_paged")
    int8_cache = jnp.dtype(cfg.kv_cache_dtype) == jnp.int8
    x = embed_inputs(params, cfg, tokens)
    B, C = x.shape[0], x.shape[1]
    pos0 = jnp.asarray(pos0, jnp.int32)
    abs_pos = pos0 + jnp.arange(C, dtype=jnp.int32)          # (C,)
    positions = jnp.broadcast_to(abs_pos[None], (B, C))
    max_blocks = table_row.shape[0]
    rows_c = table_row[jnp.clip(abs_pos // paged.block_size, 0,
                                max_blocks - 1)]             # (C,)
    offs_c = abs_pos % paged.block_size
    tables1 = table_row[None]                                # (1, max_blocks)

    def repeat_body(carry, xs):
        h = carry
        rep_params, rep_caches, rep_plans = xs
        new_caches = []
        for i, _ in enumerate(cfg.block_pattern):
            bp, cache, plan = rep_params[i], rep_caches[i], rep_plans[i]
            core_plan = _subplan(plan, "core")
            hn = L.rmsnorm(bp["norm1"], h, cfg.norm_eps)
            q, k, v = L.qkv_project(bp["core"], cfg, hn, positions, core_plan)
            if int8_cache:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                cache = {
                    "k": cache["k"].at[rows_c, offs_c].set(kq[0]),
                    "v": cache["v"].at[rows_c, offs_c].set(vq[0]),
                    "k_scale": cache["k_scale"].at[rows_c, offs_c].set(ks[0]),
                    "v_scale": cache["v_scale"].at[rows_c, offs_c].set(vs[0]),
                }
                k_all = _dequantize_kv(
                    _paged_gather(cache["k"], tables1, paged),
                    _paged_gather(cache["k_scale"], tables1, paged), hn.dtype)
                v_all = _dequantize_kv(
                    _paged_gather(cache["v"], tables1, paged),
                    _paged_gather(cache["v_scale"], tables1, paged), hn.dtype)
            else:
                cache = {
                    "k": cache["k"].at[rows_c, offs_c].set(
                        k[0].astype(cache["k"].dtype)),
                    "v": cache["v"].at[rows_c, offs_c].set(
                        v[0].astype(cache["v"].dtype)),
                }
                k_all = _paged_gather(cache["k"], tables1, paged).astype(
                    hn.dtype)
                v_all = _paged_gather(cache["v"], tables1, paged).astype(
                    hn.dtype)
            cache = {kk: shard(vv, "cache_batch", None, "kv_heads", None)
                     if vv.ndim == 4 else vv for kk, vv in cache.items()}
            q = shard(q, "cache_batch", None, None, None)
            o = L.chunked_attention(q, k_all, v_all, q_positions=positions,
                                    kv_len=pos0 + C, causal=True)
            h = h + L.pim_matmul(o.reshape(B, C, -1), bp["core"]["wo"],
                                 L.plan_leaf(core_plan, "wo"), cfg)
            hn2 = L.rmsnorm(bp["norm2"], h, cfg.norm_eps)
            if cfg.moe_layer(i):
                ffn_out = L.moe_block(bp["ffn"], cfg, hn2,
                                      plans=_subplan(plan, "ffn"))
            else:
                ffn_out = L.mlp_block(bp["ffn"], cfg, hn2,
                                      plans=_subplan(plan, "ffn"))
            h = shard(h + ffn_out, "batch", "seq", None)
            new_caches.append(cache)
        return h, tuple(new_caches)

    logits, caches = _run_prefill_body(params, cfg, x, state["caches"],
                                       repeat_body, plans=plans)
    pos = state["pos"].at[jnp.asarray(slot, jnp.int32)].set(pos0 + C)
    return logits, {"caches": caches, "pos": pos}


def insert_request_paged(state: dict, one: dict, slot, table_row,
                         paged: PagedLayout) -> dict:
    """Scatter a contiguous B=1 prefilled state into a slot's pool blocks.

    The staged-admission / cross-slice handoff path: recurrent archs
    prefill at B=1 off-pool (their carries cannot be rebuilt from paged
    context), and disaggregated serving prefills on a separate mesh slice
    before handing the filled blocks to the decode slice. Attention
    leaves scatter every position ``p`` of the contiguous cache into pool
    row ``table_row[p // block_size]`` at offset ``p % block_size``
    (sentinel rows absorb the unused tail in scratch); recurrent carries
    and ``pos[slot]`` splice exactly like ``insert_request``.
    """
    slot = jnp.asarray(slot, jnp.int32)
    bs = paged.block_size
    new_caches = []
    for cache, cone in zip(state["caches"], one["caches"]):
        if "k" in cache:  # attn: pool (R, rows, bs, K[, hd])
            max_len = cone["k"].shape[2]
            p = jnp.arange(max_len, dtype=jnp.int32)
            rows = jnp.clip(
                table_row[jnp.clip(p // bs, 0, table_row.shape[0] - 1)],
                0, paged.sentinel)
            c = {kk: cache[kk].at[:, rows, p % bs].set(
                     cone[kk][:, 0].astype(cache[kk].dtype))
                 for kk in cache}
        else:  # recurrent carries: per-slot batch axis
            c = jax.tree.map(
                lambda cc, oo: jax.lax.dynamic_update_slice_in_dim(
                    cc, oo.astype(cc.dtype), slot, axis=1), cache, cone)
        new_caches.append(c)
    pos = state["pos"].at[slot].set(jnp.asarray(one["pos"], jnp.int32))
    return {"caches": new_caches, "pos": pos}
