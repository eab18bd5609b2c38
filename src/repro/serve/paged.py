"""Paged-KV continuous batching + disaggregated prefill/decode serving.

The contiguous ``ContinuousServeEngine`` reserves one ``max_len`` KV
region per slot up front, so a pod's serving capacity is bounded by
``n_slots * max_len`` tokens of cache *whether or not the requests use
them* — the binding constraint once the PIM datapath runs at kernel
speed. This engine replaces the per-slot regions with a vLLM-style paged
cache:

- **Block pool + tables.** Attention KV lives in one fixed pool of
  ``n_blocks`` blocks of ``block_size`` tokens
  (``models.transformer.PagedLayout``); each slot owns a block *table*.
  Admission claims ``ceil(prompt_len / block_size)`` free blocks instead
  of a whole region — copy-free for attention-only archs, whose prompt
  streams straight into the claimed blocks (``prefill_chunk_paged``).
- **Block-granular free.** A request that stops early returns its blocks
  the same iteration; decode grows a slot one block at a time, so memory
  tracks *actual* lengths, not ``max_len`` worst cases.
- **Prefix sharing.** A fully-written prompt block is registered under
  the bytes of the prompt up to and including it; later admissions share
  the longest common whole-block prefix by refcount (common system
  prompts are stored once while any sharer is live).
- **Queue-until-blocks-free + eviction.** Admission is strict FIFO under
  memory pressure (the head of the queue waits; nothing overtakes it).
  If decode needs a block and the pool is dry, the *youngest* other
  request is evicted — its blocks freed, the request requeued at the
  front — and recomputed later; determinism of both greedy decoding and
  the per-request ``fold_in`` sampling stream makes the recompute replay
  the identical tokens, so eviction never changes outputs.
- **Disaggregated prefill/decode.** With ``prefill_mesh``/``decode_mesh``
  (see ``repro.launch.mesh.make_disaggregated_meshes``) prefill runs on
  its own mesh slice under ``MULTIPOD_SERVE_RULES`` and the finished
  B=1 state is handed to the decode slice, where
  ``insert_request_paged`` scatters it into the slot's pool blocks.
  Params and compiled PIM plan pytrees (weights are PIM-static —
  write-once crossbars) are replicated to both slices at construction.

Greedy outputs stay bit-identical to the contiguous and lockstep engines
(see ``_paged_gather``: masked gather positions contribute exact zeros);
the MoE capacity carve-out of ``repro.serve.scheduler`` applies
unchanged.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.dist import MULTIPOD_SERVE_RULES, axis_rules
from repro.models import transformer as T
from repro.obs.serve import NULL_TELEMETRY
from repro.serve.engine import jit_step
from repro.serve.scheduler import (
    ContinuousServeEngine,
    RequestOutput,
    ServeStats,
    _Slot,
)


class BlockAllocator:
    """Host-side free list, refcounts, and prefix index over a fixed pool.

    Pool row ``n_blocks`` (the scratch/sentinel row) is never allocated.
    Prefix sharing is hash-chained like vLLM's: block ``j`` of a prompt
    registers under ``prompt[:(j + 1) * block_size].tobytes()``, so a
    lookup walks the chain and shares the longest common *whole-block*
    prefix. Registration lives exactly as long as some request refcounts
    the block — releasing the last reference unregisters it.
    """

    def __init__(self, n_blocks: int, block_size: int):
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.free: collections.deque[int] = collections.deque(range(n_blocks))
        self.refcount = np.zeros(n_blocks, np.int32)
        self.prefix_index: dict[bytes, int] = {}
        self._block_key: dict[int, bytes] = {}

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - len(self.free)

    def prefix_key(self, prompt: np.ndarray, j: int) -> bytes:
        return np.ascontiguousarray(
            prompt[:(j + 1) * self.block_size], dtype=np.int32).tobytes()

    def match_prefix(self, prompt: np.ndarray) -> list[int]:
        """Registered blocks covering the longest whole-block prefix of
        ``prompt`` (non-mutating — claim the result to actually share)."""
        out = []
        for j in range(len(prompt) // self.block_size):
            bid = self.prefix_index.get(self.prefix_key(prompt, j))
            if bid is None:
                break
            out.append(bid)
        return out

    def claim(self, bid: int) -> int:
        """Take a shared reference on an in-use block."""
        assert self.refcount[bid] > 0, "claim() of a free block"
        self.refcount[bid] += 1
        return bid

    def alloc(self, n: int) -> list[int]:
        if n > len(self.free):
            raise RuntimeError(
                f"pool exhausted: want {n}, have {len(self.free)} free")
        out = [self.free.popleft() for _ in range(n)]
        for b in out:
            self.refcount[b] = 1
        return out

    def release(self, bid: int) -> None:
        self.refcount[bid] -= 1
        assert self.refcount[bid] >= 0, "double free"
        if self.refcount[bid] == 0:
            key = self._block_key.pop(bid, None)
            if key is not None:
                self.prefix_index.pop(key, None)
            self.free.append(bid)

    def register(self, bid: int, key: bytes) -> None:
        """Publish a fully-written prompt block for prefix sharing."""
        if key not in self.prefix_index and bid not in self._block_key:
            self.prefix_index[key] = bid
            self._block_key[bid] = key


@dataclasses.dataclass
class _PagedSlot(_Slot):
    blocks: list = dataclasses.field(default_factory=list)  # table order
    n_shared: int = 0              # leading blocks claimed via prefix index
    live: bool = False             # prefill finished, decoding
    host_pos: int = 0              # authoritative position (device pos for
    seq: int = 0                   # mid-prefill slots drifts — see
                                   # prefill_chunk_paged); seq: admission
                                   # order, eviction takes the youngest


class PagedServeEngine(ContinuousServeEngine):
    """Continuous batching over a paged KV pool (+ optional disaggregated
    prefill/decode mesh slices). See the module docstring for semantics;
    the scheduler loop, sampling streams, and stop handling are inherited
    from ``ContinuousServeEngine``.
    """

    def __init__(self, cfg: ArchConfig, params: Any, *, n_slots: int = 4,
                 max_len: int = 512, prefill_chunk: int = 64,
                 block_size: int = 16, n_blocks: int | None = None,
                 prefix_sharing: bool = True, plans: Any = None,
                 prefill_mesh=None, decode_mesh=None, telemetry=None):
        if not cfg.causal:
            raise ValueError(f"{cfg.name} is encoder-only; no decode")
        if n_slots < 1 or prefill_chunk < 1:
            raise ValueError("n_slots and prefill_chunk must be >= 1")
        if cfg.pim_mode != "off" and plans is None:
            raise ValueError(
                f"pim_mode={cfg.pim_mode!r} needs compiled plans — call "
                "repro.models.pim.prepare_pim_params(params, cfg, "
                "calib_tokens) and pass plans=")
        if block_size < 1 or max_len % block_size != 0:
            raise ValueError(
                f"block_size ({block_size}) must be >= 1 and divide "
                f"max_len ({max_len}) — the gathered per-slot view must "
                f"equal one contiguous max_len cache for bit-identity")
        max_blocks = max_len // block_size
        if n_blocks is None:
            n_blocks = n_slots * max_blocks    # no memory pressure
        if n_blocks < max_blocks:
            raise ValueError(
                f"n_blocks ({n_blocks}) under max_len/block_size "
                f"({max_blocks}): one max-length request could never fit "
                f"even after evicting everything else")
        if (prefill_mesh is None) != (decode_mesh is None):
            raise ValueError(
                "pass both prefill_mesh and decode_mesh, or neither")
        self.cfg = cfg
        self.params = params
        self.plans = plans
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.max_blocks = max_blocks
        self.layout = T.PagedLayout(n_blocks=n_blocks, block_size=block_size)
        self.alloc = BlockAllocator(n_blocks, block_size)
        self.prefill_mesh = prefill_mesh
        self.decode_mesh = decode_mesh
        # recurrent carries cannot be rebuilt from paged context, and a
        # disaggregated prefill must not touch the decode slice's pool —
        # both stage at B=1 and hand over via insert_request_paged
        attn_only = all(k == "attn" for k in cfg.block_pattern)
        self.staged_prefill = (not attn_only) or (prefill_mesh is not None)
        # int8 KV quantizes per chunk, so a shared block written under one
        # chunking is not bit-identical under another — no sharing there
        self.prefix_sharing = (prefix_sharing and not self.staged_prefill
                               and cfg.kv_cache_dtype != "int8")
        self.slots: list[_PagedSlot | None] = [None] * n_slots
        self.queue: collections.deque = collections.deque()
        self.stats = ServeStats()
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self._seq = 0
        # host-authoritative block tables (sentinel = unmapped)
        self.tables = np.full((n_slots, max_blocks), self.layout.sentinel,
                              np.int32)

        layout = self.layout
        self._chunk = jit_step(
            lambda p, pl, st, toks: T.prefill_chunk(p, cfg, st, toks,
                                                    plans=pl))
        self._chunk_paged = jit_step(
            lambda p, pl, st, toks, slot, row, pos0: T.prefill_chunk_paged(
                p, cfg, st, toks, slot=slot, table_row=row, pos0=pos0,
                paged=layout, plans=pl))
        self._decode = jit_step(self._wrap_decode(
            lambda p, pl, st, tok, tb: T.decode_step(
                p, cfg, st, tok, plans=pl, block_tables=tb, paged=layout)))
        self._insert = jit_step(
            lambda st, one, slot, row: T.insert_request_paged(
                st, one, slot, row, layout))

        state = T.init_decode_state(cfg, n_slots, max_len, per_slot_pos=True,
                                    paged=layout)
        self._template1 = T.init_decode_state(cfg, 1, max_len)
        if decode_mesh is not None:
            P = jax.sharding.PartitionSpec
            rep_p = jax.sharding.NamedSharding(prefill_mesh, P())
            rep_d = jax.sharding.NamedSharding(decode_mesh, P())
            self._params_p = jax.device_put(params, rep_p)
            self._params_d = jax.device_put(params, rep_d)
            self._plans_p = None if plans is None else jax.device_put(
                plans, rep_p)
            self._plans_d = None if plans is None else jax.device_put(
                plans, rep_d)
            self._template1 = jax.device_put(self._template1, rep_p)
            self._rep_d = rep_d
            state = jax.device_put(state, rep_d)
        else:
            self._params_p = self._params_d = params
            self._plans_p = self._plans_d = plans
            self._rep_d = None
        self.state = state

    # ----------------------------------------------------------- helpers
    @contextlib.contextmanager
    def _on(self, mesh):
        """Run under one slice's mesh + the MULTIPOD_SERVE rule set (a
        no-op for single-host paged serving: mesh is None)."""
        if mesh is None:
            yield
        else:
            with mesh, axis_rules(MULTIPOD_SERVE_RULES):
                yield

    def _note_blocks(self) -> None:
        used = self.alloc.blocks_in_use
        self.stats.blocks_in_use = used
        self.stats.peak_blocks_in_use = max(self.stats.peak_blocks_in_use,
                                            used)
        self.tel.on_pool(used, self.stats.peak_blocks_in_use)

    def _drain_budget(self) -> int:
        # evicted requests recompute from scratch; in the worst case each
        # of the other slots' requests preempts a victim once
        return super()._drain_budget() * (1 + self.n_slots)

    # ---------------------------------------------------------- lifecycle
    def _try_admit(self, i: int) -> bool:
        """Admit the queue head into free slot ``i`` if its prompt blocks
        fit (strict FIFO: on a miss the head keeps waiting — nothing
        overtakes it, so admission order is deterministic)."""
        req = self.queue[0]
        prompt = np.asarray(req.prompt, np.int32)
        plen = prompt.shape[0]
        bs = self.layout.block_size
        shared: list[int] = []
        if self.prefix_sharing:
            # cap: at least one prompt token must be prefilled (first-token
            # logits need a forward pass), and decode must never write into
            # a shared block
            shared = self.alloc.match_prefix(prompt)[:(plen - 1) // bs]
        need = self.layout.blocks_for(plen) - len(shared)
        if need > len(self.alloc.free):
            self.stats.admission_waits += 1
            self.tel.on_admission_wait(req.uid)
            return False
        self.queue.popleft()
        blocks = [self.alloc.claim(b) for b in shared] + self.alloc.alloc(need)
        self.stats.prefix_block_hits += len(shared)
        self.tel.on_admit(req.uid, plen)
        if shared:
            self.tel.on_prefix_hits(req.uid, len(shared))
        slot = _PagedSlot(req=req,
                          state1=self._template1 if self.staged_prefill
                          else None,
                          blocks=blocks, n_shared=len(shared),
                          n_prefilled=len(shared) * bs, seq=self._seq)
        self._seq += 1
        self.slots[i] = slot
        self.tables[i, :] = self.layout.sentinel
        self.tables[i, :len(blocks)] = blocks
        self._note_blocks()
        return True

    def _free_slot(self, i: int, slot: _PagedSlot) -> None:
        for b in slot.blocks:
            self.alloc.release(b)
        slot.blocks = []
        self.tables[i, :] = self.layout.sentinel

    def _commit(self, idx: int, slot: _Slot, tok: int,
                finished: list[RequestOutput]) -> None:
        super()._commit(idx, slot, tok, finished)
        if self.slots[idx] is None:        # retired: block-granular free
            self._free_slot(idx, slot)
            self._note_blocks()

    def _evict_youngest(self, protect: int) -> None:
        """Preempt the youngest other request: free its blocks, requeue it
        at the front (FIFO by admission order is preserved — it was
        admitted before everything still queued). Greedy decoding and the
        seeded ``fold_in`` sampling stream both replay identically on
        recompute, so outputs are unchanged."""
        victims = [(s.seq, j) for j, s in enumerate(self.slots)
                   if s is not None and j != protect]
        if not victims:
            raise RuntimeError(
                "pool exhausted with no evictable request — unreachable "
                "when n_blocks * block_size >= max_len")
        _, j = max(victims)
        slot = self.slots[j]
        self._free_slot(j, slot)
        self.queue.appendleft(slot.req)
        self.slots[j] = None
        self.stats.evictions += 1
        self.tel.on_eviction(slot.req.uid)

    def _ensure_decode_block(self, i: int) -> None:
        """Grow slot ``i``'s table to cover its next write position,
        evicting (youngest-first) under pressure."""
        slot = self.slots[i]
        bi = slot.host_pos // self.layout.block_size
        while len(slot.blocks) <= bi:
            while not self.alloc.free:
                self._evict_youngest(protect=i)
            slot.blocks.extend(self.alloc.alloc(1))
            self.tables[i, len(slot.blocks) - 1] = slot.blocks[-1]
        self._note_blocks()

    def _register_prompt_blocks(self, slot: _PagedSlot) -> None:
        """Publish the finished prompt's full private blocks for sharing."""
        if not self.prefix_sharing:
            return
        prompt = np.asarray(slot.req.prompt, np.int32)
        for j in range(slot.n_shared,
                       prompt.shape[0] // self.layout.block_size):
            self.alloc.register(slot.blocks[j],
                                self.alloc.prefix_key(prompt, j))

    # ------------------------------------------------------------- engine
    def step(self) -> list[RequestOutput]:
        """One iteration: admit (FIFO, queue-until-blocks-free) → prefill
        one chunk per admitted-but-not-live slot → one batched paged
        decode for live slots (lazy block growth, eviction under
        pressure). Host syncs are batched as in the parent engine; the
        telemetry spans name the same phases as the parent's ``step``,
        plus ``grow_blocks``."""
        tel = self.tel
        finished: list[RequestOutput] = []
        with tel.span("step"):
            # 1. admission
            with tel.span("admission"):
                free_idx = [i for i, s in enumerate(self.slots) if s is None]
                while free_idx and self.queue:
                    if not self._try_admit(free_idx[0]):
                        break               # head waits; FIFO holds
                    free_idx.pop(0)
            # 2. prefill: one chunk per mid-prefill slot
            done: list[tuple[int, _PagedSlot, Any]] = []
            for i, slot in enumerate(self.slots):
                if slot is None or slot.live:
                    continue
                prompt = np.asarray(slot.req.prompt, np.int32)
                lo, hi = slot.n_prefilled, min(slot.n_prefilled
                                               + self.prefill_chunk,
                                               prompt.shape[0])
                with tel.span("prefill_chunk", uid=slot.req.uid, lo=lo,
                              hi=hi):
                    self._prefill_one(i, slot, prompt, lo, hi, done)
            if done:
                with tel.span("first_token"):
                    rows = jax.device_get([lg for _, _, lg in done])
                    for (i, slot, _), row in zip(done, rows):
                        self._commit(i, slot, self._sample(
                            slot, row, int(np.argmax(row))), finished)
            # 3. batched paged decode over live slots
            live = [i for i, s in enumerate(self.slots)
                    if s is not None and s.live]
            if live:
                with tel.span("grow_blocks"):
                    for i in sorted(live, key=lambda j: self.slots[j].seq):
                        # an eviction may have taken it
                        if self.slots[i] is not None:
                            self._ensure_decode_block(i)
                    live = [i for i, s in enumerate(self.slots)
                            if s is not None and s.live]
            if live:
                with tel.span("decode_inputs"):
                    toks = np.zeros((self.n_slots, 1), np.int32)
                    tables = np.full_like(self.tables, self.layout.sentinel)
                    for i in live:
                        toks[i, 0] = self.slots[i].next_tok
                        tables[i] = self.tables[i]  # others stay sentinel
                    toks, tables = jnp.asarray(toks), jnp.asarray(tables)
                with tel.span("decode_step", n_live=len(live)):
                    with tel.annotate_step("decode_step",
                                           self.stats.decode_steps), \
                            self._on(self.decode_mesh):
                        out = self._decode(self._params_d, self._plans_d,
                                           self.state, toks, tables)
                    with tel.span("fetch"):
                        rows, self.state = self._decode_fetch(out, len(live))
                    self.stats.decode_steps += 1
                    self.stats.decode_slot_tokens += len(live)
                    tel.on_decode_step(len(live))
                    with tel.span("sample"):
                        greedy = np.argmax(rows, axis=-1)
                    with tel.span("commit"):
                        for i in live:
                            slot = self.slots[i]
                            slot.host_pos += 1
                            self._commit(i, slot, self._sample(
                                slot, rows[i], int(greedy[i])), finished)
        return finished

    def _prefill_one(self, i: int, slot: _PagedSlot, prompt: np.ndarray,
                     lo: int, hi: int, done: list) -> None:
        """Dispatch prompt tokens ``[lo, hi)`` of slot ``i``; a finished
        prompt goes live and queues its last logits row on ``done``."""
        toks = jnp.asarray(prompt[None, lo:hi])
        with self.tel.annotate_step("prefill_chunk",
                                    self.stats.prefill_chunks):
            if self.staged_prefill:
                with self._on(self.prefill_mesh):
                    logits, slot.state1 = self._chunk(
                        self._params_p, self._plans_p, slot.state1, toks)
            else:
                with self._on(self.decode_mesh):
                    logits, self.state = self._chunk_paged(
                        self._params_d, self._plans_d, self.state, toks,
                        jnp.asarray(i, jnp.int32),
                        jnp.asarray(self.tables[i]),
                        jnp.asarray(lo, jnp.int32))
        slot.n_prefilled = hi
        self.stats.prefill_chunks += 1
        self.tel.on_prefill_chunk(slot.req.uid, lo, hi)
        if hi == prompt.shape[0]:
            if self.staged_prefill:
                one = slot.state1
                if self._rep_d is not None:       # hand blocks to the
                    one = jax.device_put(one, self._rep_d)  # decode slice
                with self._on(self.decode_mesh):
                    self.state = self._insert(
                        self.state, one, jnp.asarray(i, jnp.int32),
                        jnp.asarray(self.tables[i]))
                slot.state1 = None
            slot.live = True
            slot.host_pos = hi
            self._register_prompt_blocks(slot)
            done.append((i, slot, logits[0, -1]))
