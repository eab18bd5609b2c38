"""Paged decode attention: the Pallas kernel (interpret) vs the XLA reference.

Both backends accumulate in f32 and round the result to bf16 once, so
they may differ by one bf16 ulp of the output: ``RTOL = 2**-7`` relative
(one ulp is at most 2**-7 of the value), with ``ATOL`` for outputs near 0.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import paged_attention as pa

RTOL = 2.0 ** -7
ATOL = 1e-5
BS = 4           # tokens per block


def _case(lens, *, H, K, D, max_blocks, n_blocks=None, seed=0,
          sentinel_slots=(), scramble=True):
    """Pools, tables and lengths for slots of ``lens`` tokens; slots in
    ``sentinel_slots`` get an all-sentinel table row."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    need = sum(-(-L // BS) for L in lens)
    n_blocks = n_blocks or need + 3
    k_pool = jnp.asarray(rng.standard_normal((n_blocks + 1, BS, K, D)),
                         jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((n_blocks + 1, BS, K, D)),
                         jnp.bfloat16)
    q = jnp.asarray(2.0 * rng.standard_normal((B, H, D)), jnp.bfloat16)
    order = rng.permutation(n_blocks) if scramble else np.arange(n_blocks)
    tables = np.full((B, max_blocks), n_blocks, np.int32)
    used = 0
    for b, L in enumerate(lens):
        n = -(-L // BS)
        if b not in sentinel_slots:
            tables[b, :n] = order[used:used + n]
        used += n
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32), n_blocks)


def _both(q, k_pool, v_pool, tables, kv_len, sentinel, pages=None):
    got = pa.paged_decode_attention(q, k_pool, v_pool, tables, kv_len,
                                    sentinel=sentinel, pages=pages,
                                    interpret=True)
    want = ops.paged_decode_attention(q, k_pool, v_pool, tables, kv_len,
                                      sentinel=sentinel, backend="xla")
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("H,K,D", [(4, 4, 64), (32, 4, 128)],
                         ids=["mha_d64", "gqa_g8_d128"])
@pytest.mark.parametrize("pages", [None, 1, 3], ids=["auto", "p1", "p3"])
def test_ragged_lengths_match_reference(H, K, D, pages):
    max_blocks = 6
    lens = [1, BS, 2 * BS - 1, 2 * BS, 2 * BS + 1, max_blocks * BS]
    q, kp, vp, tables, kv_len, sentinel = _case(lens, H=H, K=K, D=D,
                                                max_blocks=max_blocks)
    got, want = _both(q, kp, vp, tables, kv_len, sentinel, pages=pages)
    assert got.shape == (len(lens), H, D)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("H,K,D", [(4, 4, 64), (32, 4, 128)],
                         ids=["mha_d64", "gqa_g8_d128"])
def test_scrambled_tables_equal_identity_tables(H, K, D):
    """The result depends on a block's contents, not on where it lives."""
    lens = [7, 13, 24]
    args = dict(H=H, K=K, D=D, max_blocks=6, seed=3)
    q, kp, vp, tables, kv_len, s = _case(lens, scramble=False, **args)
    ident, _ = _both(q, kp, vp, tables, kv_len, s, pages=2)
    order = np.random.default_rng(9).permutation(s)
    kp2 = kp.at[order].set(kp[:s])       # block r moves to row order[r]
    vp2 = vp.at[order].set(vp[:s])
    t2 = np.asarray(tables).copy()
    live = t2 != s
    t2[live] = order[t2[live]]
    assert not np.array_equal(t2, np.asarray(tables))
    scrambled, want = _both(q, kp2, vp2, jnp.asarray(t2), kv_len, s, pages=2)
    np.testing.assert_array_equal(scrambled, ident)
    np.testing.assert_allclose(scrambled, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("H,K,D", [(4, 4, 64), (32, 4, 128)],
                         ids=["mha_d64", "gqa_g8_d128"])
def test_all_sentinel_slots_are_exact_zeros(H, K, D):
    """Idle and mid-prefill slots read no block and return exact zeros;
    the other slots' rows are what they are without them."""
    lens = [9, 17, 5, 22]
    args = dict(H=H, K=K, D=D, max_blocks=6, seed=5)
    q, kp, vp, tables, kv_len, s = _case(lens, sentinel_slots=(0, 2), **args)
    got, want = _both(q, kp, vp, tables, kv_len, s, pages=2)
    assert not np.isnan(got).any()
    np.testing.assert_array_equal(got[[0, 2]], 0.0)
    alone, _ = _both(q[1::2], kp, vp, tables[1::2], kv_len[1::2], s, pages=2)
    np.testing.assert_array_equal(got[[1, 3]], alone)
    np.testing.assert_allclose(got[[1, 3]], want[[1, 3]], rtol=RTOL,
                               atol=ATOL)


def test_sentinel_block_inside_live_range_is_skipped():
    """A sentinel entry below ``kv_len`` is not read: its keys leave the
    softmax, as if the slot's context lacked that block."""
    lens = [4 * BS]
    q, kp, vp, tables, kv_len, s = _case(lens, H=4, K=4, D=64, max_blocks=4,
                                         seed=8)
    holed = tables.at[0, 1].set(s)
    got, _ = _both(q, kp, vp, holed, kv_len, s, pages=2)
    keep = np.r_[0:BS, 2 * BS:4 * BS]
    rows = np.asarray(tables[0])
    k = np.asarray(kp, np.float64)[rows].reshape(-1, 4, 64)[keep]
    v = np.asarray(vp, np.float64)[rows].reshape(-1, 4, 64)[keep]
    qh = np.asarray(q, np.float64)[0]
    sc = np.einsum("hd,thd->ht", qh, k) / 8.0
    p = np.exp(sc - sc.max(axis=1, keepdims=True))
    want = np.einsum("ht,thd->hd", p / p.sum(axis=1, keepdims=True), v)
    np.testing.assert_allclose(got[0], want, rtol=RTOL, atol=1e-2)


def test_more_pages_than_one_step_holds():
    """Many DMA steps per slot, slots of different step counts in a row
    (each slot's last step prefetches the next slot's first)."""
    max_blocks = 16
    lens = [max_blocks * BS, 3, 11 * BS + 2, 1, 6 * BS]
    q, kp, vp, tables, kv_len, s = _case(lens, H=8, K=2, D=64,
                                         max_blocks=max_blocks, seed=11)
    for pages in (1, 2, 5):
        got, want = _both(q, kp, vp, tables, kv_len, s, pages=pages)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_dma_schedule_has_no_races_and_reads_no_uninitialised_memory():
    """Under the TPU interpreter, which runs DMAs asynchronously and fills
    fresh buffers with NaN: no data race between a step's reads and the
    next step's (or slot's) DMAs, and no NaN from a buffer never written."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu
    lens = [9 * BS, 1, 2 * BS + 3, 5]
    q, kp, vp, tables, kv_len, s = _case(lens, H=8, K=2, D=64, max_blocks=9,
                                         seed=13, sentinel_slots=(3,))
    params = pltpu.InterpretParams(detect_races=True,
                                   uninitialized_memory="nan")
    got = np.asarray(pa.paged_decode_attention(
        q, kp, vp, tables, kv_len, sentinel=s, pages=2, interpret=params),
        np.float32)
    assert not ipc.races.races_found
    _, want = _both(q, kp, vp, tables, kv_len, s, pages=2)
    np.testing.assert_allclose(got[:3], want[:3], rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(got[3], 0.0)


@pytest.mark.parametrize("K,D,c,lanes", [(16, 64, 2, 128), (4, 128, 1, 128),
                                         (3, 64, 1, 64), (8, 32, 4, 128)])
def test_pool_view(K, D, c, lanes):
    assert pa.pool_view(K, D) == (c, lanes)


# ------------------------------------------------------------ decode_step
def _paged_state(cfg, params, prompt_lens, layout, max_blocks, seed=0):
    """A paged decode state whose slots hold prompts of ``prompt_lens``
    tokens (0: a slot still mid-prefill, given an all-sentinel table row),
    each in scrambled blocks; returns (state, tables, next tokens)."""
    from repro.models import transformer as T
    rng = np.random.default_rng(seed)
    B = len(prompt_lens)
    st = T.init_decode_state(cfg, B, max_blocks * layout.block_size,
                             per_slot_pos=True, paged=layout)
    free = list(rng.permutation(layout.n_blocks))
    tables = np.full((B, max_blocks), layout.sentinel, np.int32)
    toks = np.zeros((B, 1), np.int32)
    for b, n in enumerate(prompt_lens):
        if not n:
            continue
        row = np.full(max_blocks, layout.sentinel, np.int32)
        row[:layout.blocks_for(n + 1)] = [free.pop()
                                          for _ in range(layout.blocks_for(n + 1))]
        prompt = rng.integers(0, cfg.vocab_size, (1, n)).astype(np.int32)
        logits, st = T.prefill_chunk_paged(
            params, cfg, st, jnp.asarray(prompt), slot=jnp.asarray(b),
            table_row=jnp.asarray(row), pos0=jnp.asarray(0, jnp.int32),
            paged=layout)
        tables[b] = row
        toks[b, 0] = int(jnp.argmax(logits[0, -1]))
    return st, jnp.asarray(tables), jnp.asarray(toks)


@pytest.mark.parametrize("arch", ["yi-6b", "qwen1.5-0.5b"])
def test_paged_decode_step_interpret_matches_xla(arch, monkeypatch):
    """One paged decode step with the attention op forced to the Pallas
    interpreter gives the ``xla`` step's logits within the bf16 model's
    rounding and writes the same pool; slot 1 is mid-prefill."""
    import jax
    from repro import configs
    from repro.models import transformer as T
    cfg = configs.get(arch).reduced()
    params, _ = T.init_params(cfg, jax.random.key(0))
    layout = T.PagedLayout(n_blocks=24, block_size=4)
    st, tables, toks = _paged_state(cfg, params, [5, 0, 13, 1], layout,
                                    max_blocks=6)
    step = lambda: T.decode_step(params, cfg, st, toks,  # noqa: E731
                                 block_tables=tables, paged=layout)
    monkeypatch.setenv(ops.ENV_VAR, "xla")
    want, st_x = step()
    monkeypatch.setenv(ops.ENV_VAR, "interpret")
    got, st_i = step()
    live = [0, 2, 3]
    g, w = (np.asarray(a, np.float32)[live] for a in (got, want))
    # a bf16 model: an attention output one bf16 ulp away may move a
    # logit by an ulp of the largest logit
    np.testing.assert_allclose(g, w, rtol=RTOL, atol=RTOL * np.abs(w).max())
    assert (g.argmax(-1) == w.argmax(-1)).all()
    for a, b in zip(jax.tree.leaves(st_i), jax.tree.leaves(st_x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_kv_paged_decode_step_still_equals_contiguous(monkeypatch):
    """int8 KV pools keep the dequantizing gather (the op is for bf16
    pools): a paged decode step over scrambled blocks is bit-identical to
    the contiguous cache, even with the kernel backend forced."""
    import dataclasses
    import jax
    from repro import configs
    from repro.models import transformer as T
    monkeypatch.setenv(ops.ENV_VAR, "interpret")
    cfg = dataclasses.replace(configs.get("yi-6b").reduced(),
                              kv_cache_dtype="int8")
    params, _ = T.init_params(cfg, jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(1), (1, 9), 0,
                                           cfg.vocab_size), np.int32)
    st_c = T.init_decode_state(cfg, 1, 16)
    logits_c, st_c = T.prefill_chunk(params, cfg, st_c, jnp.asarray(prompt))
    layout = T.PagedLayout(n_blocks=7, block_size=4)
    st_p = T.init_decode_state(cfg, 1, 16, per_slot_pos=True, paged=layout)
    table = jnp.asarray([5, 2, 6, 0], jnp.int32)
    logits_p, st_p = T.prefill_chunk_paged(
        params, cfg, st_p, jnp.asarray(prompt), slot=jnp.asarray(0),
        table_row=table, pos0=jnp.asarray(0, jnp.int32), paged=layout)
    tok = jnp.argmax(logits_c[:, -1], -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        l_c, st_c = T.decode_step(params, cfg, st_c, tok)
        l_p, st_p = T.decode_step(params, cfg, st_p, tok,
                                  block_tables=table[None], paged=layout)
        assert jnp.array_equal(l_c, l_p)
        tok = jnp.argmax(l_c, -1).astype(jnp.int32)
