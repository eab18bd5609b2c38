"""Paged KV cache: model-layer bit-identity vs the contiguous cache,
engine bit-identity vs lockstep (attention + recurrent archs), prefix
sharing refcounts, admission under memory pressure, block-granular free,
eviction-by-recompute, and disaggregated prefill/decode mesh slices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_disaggregated_meshes
from repro.models import transformer as T
from repro.serve import (
    BlockAllocator,
    EngineStats,
    PagedServeEngine,
    Request,
    ServeEngine,
    ServeStats,
)

_CACHE: dict = {}


def setup(arch: str):
    if arch not in _CACHE:
        cfg = configs.get(arch).reduced()
        params, _ = T.init_params(cfg, jax.random.key(0))
        _CACHE[arch] = (cfg, params)
    return _CACHE[arch]


def mixed_requests(cfg, n=5, seed=0, **overrides):
    rng = np.random.default_rng(seed)
    plens = [3, 7, 5, 9, 4, 6, 8][:n]
    steps = [6, 3, 9, 4, 7, 2, 5][:n]
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        plens[i]).astype(np.int32),
                    max_new_tokens=steps[i], **overrides)
            for i in range(n)]


def lockstep_refs(cfg, params, reqs, max_len):
    eng = ServeEngine(cfg, params, max_len=max_len)
    return {r.uid: eng.generate(r.prompt[None, :],
                                steps=r.max_new_tokens).tokens[0]
            for r in reqs}


# ----------------------------------------------------------- model layer
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_prefill_decode_matches_contiguous(kv_dtype):
    """prefill_chunk_paged + block-table decode == whole-prompt prefill +
    contiguous decode, bit-for-bit, with a scrambled (non-identity) block
    table and a garbage-filled pool."""
    cfg, params = setup("yi-6b")
    cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    prompt = np.asarray(jax.random.randint(
        jax.random.key(1), (1, 9), 0, cfg.vocab_size), np.int32)
    # reference: the contiguous cache walked with the SAME chunking —
    # chunked == whole-prompt prefill is already the float contract
    # (prefill_chunk docstring); int8 round-trips per chunk, so the
    # paged/contiguous comparison must share chunk boundaries.
    chunks = [(0, 4), (4, 8), (8, 9)]
    st_ref = T.init_decode_state(cfg, 1, 16)
    for lo, hi in chunks:
        logits_ref, st_ref = T.prefill_chunk(params, cfg, st_ref,
                                             jnp.asarray(prompt[:, lo:hi]))
    layout = T.PagedLayout(n_blocks=7, block_size=4)   # 4 needed of 7
    st = T.init_decode_state(cfg, 1, 16, per_slot_pos=True, paged=layout)
    # garbage in the pool must be masked out by kv_len, never read
    st = {k: (jax.tree.map(lambda a: a + (7 if a.dtype == jnp.int8
                                          else 7.0), v)
              if isinstance(v, dict) else v) for k, v in st.items()}
    table = jnp.asarray([5, 2, 6, 0], jnp.int32)       # scrambled
    for lo, hi in chunks:
        logits, st = T.prefill_chunk_paged(
            params, cfg, st, jnp.asarray(prompt[:, lo:hi]),
            slot=jnp.asarray(0, jnp.int32), table_row=table,
            pos0=jnp.asarray(lo, jnp.int32), paged=layout)
    assert jnp.array_equal(logits_ref[:, -1], logits[:, -1])
    assert st["pos"].tolist() == [9]
    tok = jnp.argmax(logits_ref[:, -1], -1)[:, None].astype(jnp.int32)
    l_ref, _ = T.decode_step(params, cfg, st_ref, tok)
    l_pg, st = T.decode_step(params, cfg, st, tok, block_tables=table[None],
                             paged=layout)
    assert jnp.array_equal(l_ref, l_pg)
    assert st["pos"].tolist() == [10]


@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-3b"])
def test_insert_request_paged_matches_contiguous_decode(arch):
    """A contiguous B=1 prefill scattered into pool blocks decodes
    identically to the contiguous batched path (the staged-prefill and
    disaggregated-handoff primitive; recurrent carries ride along)."""
    cfg, params = setup(arch)
    prompt = np.arange(2, 9, dtype=np.int32)[None]
    logits, one = T.prefill(params, cfg, jnp.asarray(prompt), max_len=16)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    ref = T.decode_step(params, cfg, one, tok)[0]
    layout = T.PagedLayout(n_blocks=9, block_size=4)
    st = T.init_decode_state(cfg, 2, 16, per_slot_pos=True, paged=layout)
    table = jnp.asarray([4, 1, 7, 0], jnp.int32)
    st = T.insert_request_paged(st, one, jnp.asarray(1, jnp.int32), table,
                                layout)
    assert st["pos"].tolist() == [0, 7]
    tables = jnp.stack([jnp.full((4,), layout.sentinel, jnp.int32), table])
    toks = jnp.concatenate([jnp.zeros((1, 1), jnp.int32), tok])
    out, _ = T.decode_step(params, cfg, st, toks, block_tables=tables,
                           paged=layout)
    assert jnp.array_equal(out[1:2], ref)


def test_decode_step_paged_arg_validation():
    cfg, params = setup("yi-6b")
    layout = T.PagedLayout(n_blocks=4, block_size=4)
    st = T.init_decode_state(cfg, 1, 16, per_slot_pos=True, paged=layout)
    tok = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="block_tables"):
        T.decode_step(params, cfg, st, tok, paged=layout)
    st_scalar = T.init_decode_state(cfg, 1, 16)
    with pytest.raises(ValueError, match="per_slot_pos"):
        T.decode_step(params, cfg, st_scalar, tok,
                      block_tables=jnp.zeros((1, 4), jnp.int32),
                      paged=layout)


# ---------------------------------------------------------------- engine
@pytest.mark.parametrize("arch", ["yi-6b", "jamba-1.5-large-398b",
                                  "rwkv6-3b"])
def test_paged_engine_matches_lockstep(arch):
    """Greedy paged-engine outputs are bit-identical to solo lockstep runs
    — copy-free in-place prefill for attention-only stacks, staged B=1
    prefill + paged insert for recurrent (mamba/rwkv) stacks."""
    cfg, params = setup(arch)
    reqs = mixed_requests(cfg)
    eng = PagedServeEngine(cfg, params, n_slots=2, max_len=32,
                           prefill_chunk=4, block_size=4)
    attn_only = all(k == "attn" for k in cfg.block_pattern)
    assert eng.staged_prefill == (not attn_only)
    outs = eng.run(reqs)
    refs = lockstep_refs(cfg, params, reqs, 32)
    for o in outs:
        assert np.array_equal(o.tokens, refs[o.uid]), f"uid {o.uid}"
    assert eng.stats.completed == 5
    assert eng.stats.blocks_in_use == 0          # block-granular free
    assert eng.stats.peak_blocks_in_use > 0
    assert len(eng.alloc.free) == eng.alloc.n_blocks


def test_prefix_sharing_refcounts():
    """A live request's full prompt blocks register for sharing; later
    admissions with the same system prompt claim them (refcount, no
    copy), and draining returns the pool with an empty prefix index."""
    cfg, params = setup("yi-6b")
    sys_prompt = np.arange(1, 9, dtype=np.int32)       # 2 full blocks
    rng = np.random.default_rng(2)
    reqs = [Request(uid=u, prompt=np.concatenate(
                [sys_prompt,
                 rng.integers(0, cfg.vocab_size, x).astype(np.int32)]),
                max_new_tokens=s)
            for u, (x, s) in enumerate([(3, 6), (5, 4), (2, 5)])]
    eng = PagedServeEngine(cfg, params, n_slots=3, max_len=32,
                           prefill_chunk=4, block_size=4)
    assert eng.prefix_sharing
    eng.submit(reqs[0])
    while not eng.alloc.prefix_index:                  # until uid 0 is live
        eng.step()
    shared = list(eng.alloc.prefix_index.values())
    assert len(shared) == 2
    eng.submit(reqs[1])
    eng.submit(reqs[2])
    eng.step()                                         # both admitted
    assert [int(eng.alloc.refcount[b]) for b in shared] == [3, 3]
    assert eng.stats.prefix_block_hits == 4            # 2 blocks x 2 reqs
    outs = eng.run([])
    refs = lockstep_refs(cfg, params, reqs, 32)
    for o in outs:                                     # sharing is exact
        assert np.array_equal(o.tokens, refs[o.uid]), f"uid {o.uid}"
    assert eng.stats.blocks_in_use == 0
    assert not eng.alloc.prefix_index                  # unregistered on free


def test_admission_waits_under_memory_pressure():
    """With a pool too small for both prompts the head of the queue waits
    (strict FIFO — admission order is arrival order), is admitted once
    blocks free, and every output still matches lockstep."""
    cfg, params = setup("yi-6b")
    rng = np.random.default_rng(3)
    reqs = [Request(uid=u,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        9).astype(np.int32),
                    max_new_tokens=3) for u in range(3)]
    eng = PagedServeEngine(cfg, params, n_slots=3, max_len=16,
                           prefill_chunk=16, block_size=4, n_blocks=4,
                           prefix_sharing=False)      # one request at a time
    for r in reqs:
        eng.submit(r)
    admitted_order = []
    outs = []
    while eng.has_work:
        before = set(eng.active_uids)
        outs.extend(eng.step())
        admitted_order += [u for u in eng.active_uids if u not in before]
    assert admitted_order == [0, 1, 2]                 # FIFO held
    assert eng.stats.admission_waits > 0
    assert eng.stats.evictions == 0                    # waiters, not victims
    refs = lockstep_refs(cfg, params, reqs, 16)
    for o in outs:
        assert np.array_equal(o.tokens, refs[o.uid])
    assert eng.stats.blocks_in_use == 0


def test_blocks_free_on_stop_token():
    """A stop token frees the slot's blocks the same iteration — memory
    tracks actual generated length, not max_new_tokens."""
    cfg, params = setup("yi-6b")
    [req] = mixed_requests(cfg, n=1)
    eng = PagedServeEngine(cfg, params, n_slots=1, max_len=32,
                           prefill_chunk=8, block_size=4)
    [full] = eng.run([req])
    stop = int(full.tokens[2])
    eng2 = PagedServeEngine(cfg, params, n_slots=1, max_len=32,
                            prefill_chunk=8, block_size=4)
    eng2.submit(Request(uid=0, prompt=req.prompt,
                        max_new_tokens=req.max_new_tokens,
                        stop_tokens=(stop,)))
    outs = []
    while eng2.has_work:
        done = eng2.step()
        if done:
            assert done[0].finish_reason == "stop"
            assert eng2.stats.blocks_in_use == 0       # freed this iteration
            outs += done
    first = int(np.argmax(full.tokens == stop))
    assert np.array_equal(outs[0].tokens, full.tokens[:first + 1])
    assert eng2.stats.peak_blocks_in_use >= 1
    assert len(eng2.alloc.free) == eng2.alloc.n_blocks


def test_eviction_recompute_is_bit_identical():
    """Pool exhaustion mid-decode preempts the youngest request (blocks
    freed, requeued at the front); its recompute replays identical greedy
    tokens, so eviction is invisible in the outputs."""
    cfg, params = setup("yi-6b")
    rng = np.random.default_rng(4)
    reqs = [Request(uid=0, prompt=rng.integers(0, cfg.vocab_size,
                                               9).astype(np.int32),
                    max_new_tokens=14),
            Request(uid=1, prompt=rng.integers(0, cfg.vocab_size,
                                               9).astype(np.int32),
                    max_new_tokens=8)]
    eng = PagedServeEngine(cfg, params, n_slots=2, max_len=24,
                           prefill_chunk=16, block_size=4, n_blocks=6,
                           prefix_sharing=False)
    outs = eng.run(reqs)
    assert eng.stats.evictions >= 1
    refs = lockstep_refs(cfg, params, reqs, 24)
    for o in outs:
        assert np.array_equal(o.tokens, refs[o.uid]), f"uid {o.uid}"
    assert eng.stats.blocks_in_use == 0


def test_engine_validation():
    cfg, params = setup("yi-6b")
    with pytest.raises(ValueError, match="divide"):
        PagedServeEngine(cfg, params, max_len=30, block_size=4)
    with pytest.raises(ValueError, match="never fit"):
        PagedServeEngine(cfg, params, max_len=32, block_size=4, n_blocks=4)
    with pytest.raises(ValueError, match="both prefill_mesh"):
        PagedServeEngine(cfg, params, max_len=32, block_size=4,
                         decode_mesh=object())


# ---------------------------------------------------------- disaggregated
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 (fake) devices for two (1,2,2) slices")
def test_disaggregated_prefill_decode_slices():
    """Prefill on one (pod, data, model) slice, decode on a disjoint one,
    params/plans replicated to both, finished blocks handed over — still
    bit-identical to lockstep, with decode state on the decode slice."""
    cfg, params = setup("yi-6b")
    pm, dm = make_disaggregated_meshes()
    assert not (set(pm.devices.flat) & set(dm.devices.flat))
    reqs = mixed_requests(cfg, n=3)
    eng = PagedServeEngine(cfg, params, n_slots=2, max_len=32,
                           prefill_chunk=4, block_size=4,
                           prefill_mesh=pm, decode_mesh=dm)
    assert eng.staged_prefill                          # handoff path
    outs = eng.run(reqs)
    refs = lockstep_refs(cfg, params, reqs, 32)
    for o in outs:
        assert np.array_equal(o.tokens, refs[o.uid]), f"uid {o.uid}"
    leaf = jax.tree_util.tree_leaves(eng.state)[0]
    assert set(leaf.devices()) <= set(dm.devices.flat)
    assert eng.stats.blocks_in_use == 0


def test_disaggregated_fast_pim_runs_kernels_under_meshes():
    """The fast path's Pallas kernel inside both mesh slices: GSPMD
    cannot partition a Mosaic kernel, so the registry runs it whole on
    every device of the slice. Greedy tokens match the one-device engine
    on the same plans."""
    from repro.models import pim
    cfg, params = setup("yi-6b")
    cfg = dataclasses.replace(cfg, pim_mode="fast",
                              pim_kernel_backend="interpret")
    calib = np.asarray(jax.random.randint(jax.random.key(7), (2, 8), 0,
                                          cfg.vocab_size))
    plans = pim.compile_pim_params(params, cfg, calib).plans
    reqs = mixed_requests(cfg, n=2)
    kw = dict(n_slots=2, max_len=16, prefill_chunk=4, block_size=4,
              plans=plans)
    want = PagedServeEngine(cfg, params, **kw).run(reqs)
    pm, dm = make_disaggregated_meshes()
    got = PagedServeEngine(cfg, params, prefill_mesh=pm, decode_mesh=dm,
                           **kw).run(reqs)
    for a, b in zip(got, want):
        assert np.array_equal(a.tokens, b.tokens), f"uid {a.uid}"


def test_disaggregated_mesh_validation():
    with pytest.raises(ValueError, match="devices"):
        make_disaggregated_meshes(prefill=(2, 16, 16), decode=(2, 16, 16))


# ------------------------------------------------------------------ stats
def test_servestats_defaults_and_alias():
    """Satellite: decode_utilization on a fresh engine is 0.0 (not a
    ZeroDivisionError), the pool counters start at zero, and the old
    EngineStats name still resolves."""
    st = ServeStats()
    assert st.decode_utilization == 0.0
    assert st.blocks_in_use == st.evictions == st.prefix_block_hits == 0
    assert st.admission_waits == st.peak_blocks_in_use == 0
    assert EngineStats is ServeStats


def test_block_allocator_unit():
    alloc = BlockAllocator(4, 2)
    a, b = alloc.alloc(2)
    assert alloc.blocks_in_use == 2
    key = alloc.prefix_key(np.asarray([1, 2, 3, 4], np.int32), 0)
    alloc.register(a, key)
    prompt = np.asarray([1, 2, 9], np.int32)
    assert alloc.match_prefix(prompt) == [a]
    alloc.claim(a)
    alloc.release(a)
    assert alloc.match_prefix(prompt) == [a]           # still refcounted
    alloc.release(a)
    assert alloc.match_prefix(prompt) == []            # unregistered
    alloc.release(b)
    assert alloc.blocks_in_use == 0
    with pytest.raises(RuntimeError, match="pool exhausted"):
        alloc.alloc(5)
