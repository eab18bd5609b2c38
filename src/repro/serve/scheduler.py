"""Continuous-batching serve engine (iteration-level scheduling).

The lockstep ``ServeEngine`` pads every request in a batch to one prompt
length and decodes until the *slowest* request finishes — a slot that
retired early still burns a decode-step's FLOPs (and, under a non-'off'
``cfg.pim_mode``, PIM-path work: both engines thread the compiled plan
pytree from ``repro.models.pim.prepare_pim_params`` — the per-site
architecture compiler, so each projection site runs its own compiled
weight slicing — through every jitted prefill/decode call, so the
weight-static projections actually run the centered-int8 /
exact-simulation path) on padding. RAELLA's economy is
converts per *useful* output, so the serving layer admits and retires
requests independently instead:

- the batched decode state holds ``n_slots`` KV-cache slots with
  *per-slot* positions (``init_decode_state(..., per_slot_pos=True)``);
- each engine iteration admits queued requests into free slots, advances
  at most one *prefill chunk* per prefilling slot (long prompts never
  stall decode for the other slots), then runs one batched
  ``decode_step`` for every slot that is mid-generation;
- a finished request frees its slot immediately; the next queued request
  is spliced in with ``insert_request`` (a batch-axis
  ``dynamic_update_slice``), so the cache sharding (``cache_batch`` under
  ``SERVE_RULES``) is untouched.

Determinism contract: greedy (``temperature == 0``) outputs are
bit-identical to running each request alone through the lockstep engine
— decode math is per-slot independent, and chunked prefill reproduces
whole-prompt prefill for float KV caches (see ``prefill_chunk``). One
carve-out: MoE decode merges the batch into a single dispatch group
(``moe_block``), so if any token hits expert capacity the drop pattern
depends on batch composition — including the garbage tokens idle or
mid-prefill slots feed through decode — and *any* batched run (lockstep
or continuous) can diverge from the solo run. The contract therefore
holds for MoE configs only while nothing hits capacity; the reduced
smoke configs pin ``capacity_factor`` high enough to guarantee that,
and production MoE serving should size ``capacity_factor`` (or group
size) for drop-free decode. Sampled requests replay the lockstep
per-request stream: ``key(seed)`` for the first token,
``fold_in(key(seed), i)`` for decode step ``i`` — temperature-0
requests never touch a PRNG key.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.obs.serve import NULL_TELEMETRY, ServeTelemetry
from repro.serve.engine import jit_step


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request with its own sampling/stop parameters."""
    uid: int
    prompt: np.ndarray                     # (prompt_len,) int32 token ids
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    stop_tokens: tuple[int, ...] = ()      # stop after emitting any of these


@dataclasses.dataclass
class RequestOutput:
    uid: int
    prompt_len: int
    tokens: np.ndarray                     # (n_generated,) int32, includes
    finish_reason: str                     # the stop token if one fired
                                           # "stop" | "length"


@dataclasses.dataclass
class ServeStats:
    decode_steps: int = 0                  # batched decode_step calls
    decode_slot_tokens: int = 0            # useful tokens over those calls
    prefill_chunks: int = 0
    completed: int = 0
    # block-pool accounting (paged engine — repro.serve.paged; zero on
    # the contiguous engine, whose per-slot regions are never shared)
    blocks_in_use: int = 0                 # current pool occupancy
    peak_blocks_in_use: int = 0
    evictions: int = 0                     # preempt-by-recompute events
    prefix_block_hits: int = 0             # shared-prefix blocks reused
    admission_waits: int = 0               # iterations head-of-queue waited

    @property
    def decode_utilization(self) -> float:
        """Average useful (non-padding) tokens per decode step.

        Absolute tokens/step in ``[0, n_slots]`` — divide by the
        engine's ``n_slots`` for a 0..1 fraction (as
        ``benchmarks/serve_continuous.py`` does). A fresh engine
        (``decode_steps == 0``) reports 0.0, never a division error."""
        return 0.0 if self.decode_steps == 0 else (
            self.decode_slot_tokens / self.decode_steps)

    def snapshot(self) -> dict:
        """Every counter plus the derived utilization, as plain scalars.

        This is the ONE stats schema both engines expose — the paged
        engine shares this dataclass rather than growing its own, so
        exporters (``repro.obs``), benchmarks, and the serve launcher
        all read the same keys (``tests/test_obs.py`` asserts parity).
        """
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)}
        d["decode_utilization"] = self.decode_utilization
        return d


EngineStats = ServeStats   # back-compat alias (pre-paged-KV name)


@dataclasses.dataclass
class _Slot:
    req: Request
    state1: Any                    # B=1 partial prefill state, until inserted
    n_prefilled: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    next_tok: int = 0
    key: Any = None                # PRNG key, only if temperature > 0
    n_sampled: int = 0


class ContinuousServeEngine:
    """Slot-based continuous batching over the jitted prefill/decode.

    All jitted computations have fixed shapes — (n_slots, 1) decode, and
    prefill chunks of ``prefill_chunk`` tokens (plus one shorter
    remainder shape per distinct prompt-length residue), so steady-state
    serving never recompiles.
    """

    def __init__(self, cfg: ArchConfig, params: Any, *, n_slots: int = 4,
                 max_len: int = 512, prefill_chunk: int = 64,
                 plans: Any = None,
                 telemetry: ServeTelemetry | None = None):
        if not cfg.causal:
            raise ValueError(f"{cfg.name} is encoder-only; no decode")
        if n_slots < 1 or prefill_chunk < 1:
            raise ValueError("n_slots and prefill_chunk must be >= 1")
        if cfg.pim_mode != "off" and plans is None:
            raise ValueError(
                f"pim_mode={cfg.pim_mode!r} needs compiled plans — call "
                "repro.models.pim.prepare_pim_params(params, cfg, "
                "calib_tokens) and pass plans=")
        self.cfg = cfg
        self.params = params
        self.plans = plans
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.state = T.init_decode_state(cfg, n_slots, max_len,
                                         per_slot_pos=True)
        self.slots: list[_Slot | None] = [None] * n_slots
        self.queue: collections.deque[Request] = collections.deque()
        self.stats = ServeStats()
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self._chunk = jit_step(
            lambda p, pl, st, toks: T.prefill_chunk(p, cfg, st, toks,
                                                    plans=pl))
        self._decode = jit_step(self._wrap_decode(
            lambda p, pl, st, tok: T.decode_step(p, cfg, st, tok, plans=pl)))
        self._insert = jit_step(
            lambda st, one, slot: T.insert_request(st, one, slot))
        # jax arrays are immutable, so one zero template serves every
        # admission (prefill_chunk returns fresh state pytrees)
        self._template1 = T.init_decode_state(cfg, 1, max_len)

    # ---------------------------------------------------------- telemetry
    def _wrap_decode(self, decode_fn):
        """With PIM stats requested (``cfg.pim_mode == 'exact'`` + an
        enabled telemetry), the jitted decode also returns the summed
        work totals (``layers.with_pim_stats`` — the PR 7 scan-safe
        collector), which join the iteration's one ``device_get``.
        Decode *math* is untouched either way: greedy outputs are
        bit-identical with telemetry on or off."""
        self._collect_pim = self.tel.wants_pim_stats(self.cfg)
        if not self._collect_pim:
            return decode_fn
        self.tel.pim_adc_bits = self.cfg.pim_adc_bits
        return L.with_pim_stats(decode_fn)

    def _decode_fetch(self, out, n_live: int):
        """Unpack a decode-jit result: all slot logits (and the PIM work
        totals, when collected) come back in ONE ``jax.device_get`` —
        the same single host sync per iteration as before telemetry."""
        if self._collect_pim:
            logits, state, tot = out
            rows, tot = jax.device_get((logits[:, -1, :], tot))
            self.tel.on_pim_totals({k: int(v) for k, v in tot.items()},
                                   n_live)
        else:
            logits, state = out
            rows = jax.device_get(logits[:, -1, :])
        return rows, state

    # ------------------------------------------------------------- intake
    def submit(self, req: Request) -> None:
        plen = int(np.asarray(req.prompt).shape[0])
        if plen < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens < 1")
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds engine max_len "
                f"({self.max_len})")
        self.queue.append(req)
        self.tel.on_submit(req.uid)

    @property
    def active_uids(self) -> tuple[int, ...]:
        return tuple(s.req.uid for s in self.slots if s is not None)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    # ------------------------------------------------------------- engine
    def _sample(self, slot: _Slot, logits_row: np.ndarray,
                greedy_tok: int) -> int:
        """Pick slot's next token. logits_row: host (vocab,) for this slot
        (the scheduler pulls all slots' logits in ONE ``jax.device_get``
        per iteration — see ``step`` — so the sampling path never adds a
        per-slot sync)."""
        if slot.req.temperature <= 0.0:
            return greedy_tok
        if slot.key is None:
            slot.key = jax.random.key(slot.req.seed)
        key = slot.key if slot.n_sampled == 0 else jax.random.fold_in(
            slot.key, slot.n_sampled - 1)
        slot.n_sampled += 1
        return int(jax.random.categorical(
            key, jnp.asarray(logits_row) / slot.req.temperature))

    def _commit(self, idx: int, slot: _Slot, tok: int,
                finished: list[RequestOutput]) -> None:
        """Record a generated token; retire the slot if the request is done."""
        slot.tokens.append(tok)
        slot.next_tok = tok
        self.tel.on_token(slot.req.uid)
        reason = None
        if tok in slot.req.stop_tokens:
            reason = "stop"
        elif len(slot.tokens) >= slot.req.max_new_tokens:
            reason = "length"
        if reason is not None:
            finished.append(RequestOutput(
                uid=slot.req.uid,
                prompt_len=int(np.asarray(slot.req.prompt).shape[0]),
                tokens=np.asarray(slot.tokens, np.int32),
                finish_reason=reason))
            self.slots[idx] = None
            self.stats.completed += 1
            self.tel.on_finish(slot.req.uid, reason, len(slot.tokens))

    def step(self) -> list[RequestOutput]:
        """One scheduler iteration: admit → prefill one chunk → decode.

        Returns the requests that finished during this iteration. Host
        syncs are batched: all prefill-completion logits come back in one
        ``jax.device_get``, and the decode step pulls every slot's last
        logits row at once (greedy argmax then runs host-side —
        ``np.argmax`` and ``jnp.argmax`` both take the first maximum, so
        the tie-break is bit-identical). Telemetry spans tile the
        iteration: ``step`` holds ``admission``, ``prefill_chunk``,
        ``first_token``, ``decode_inputs`` and ``decode_step``, which
        holds ``fetch``, ``sample`` and ``commit``.
        """
        tel = self.tel
        finished: list[RequestOutput] = []
        with tel.span("step"):
            # 1. admit queued requests into free slots
            with tel.span("admission"):
                for i in range(self.n_slots):
                    if self.slots[i] is None and self.queue:
                        req = self.queue.popleft()
                        self.slots[i] = _Slot(req=req,
                                              state1=self._template1)
                        tel.on_admit(req.uid,
                                     int(np.asarray(req.prompt).shape[0]))
            # 2. advance each prefilling slot by one chunk
            done: list[tuple[int, _Slot, Any]] = []
            for i, slot in enumerate(self.slots):
                if slot is None or slot.state1 is None:
                    continue
                prompt = np.asarray(slot.req.prompt, np.int32)
                lo = slot.n_prefilled
                hi = min(lo + self.prefill_chunk, prompt.shape[0])
                with tel.span("prefill_chunk", uid=slot.req.uid, lo=lo,
                              hi=hi):
                    toks = jnp.asarray(prompt[None, lo:hi])
                    with tel.annotate_step("prefill_chunk",
                                           self.stats.prefill_chunks):
                        logits, slot.state1 = self._chunk(
                            self.params, self.plans, slot.state1, toks)
                    slot.n_prefilled = hi
                    self.stats.prefill_chunks += 1
                    tel.on_prefill_chunk(slot.req.uid, lo, hi)
                    if hi == prompt.shape[0]:
                        # prompt done: splice into the batch; first-token
                        # logits are committed below, after ONE batched
                        # device_get
                        self.state = self._insert(self.state, slot.state1,
                                                  jnp.asarray(i, jnp.int32))
                        slot.state1 = None
                        done.append((i, slot, logits[0, -1]))
            if done:
                with tel.span("first_token"):
                    rows = jax.device_get([lg for _, _, lg in done])
                    for (i, slot, _), row in zip(done, rows):
                        self._commit(i, slot, self._sample(
                            slot, row, int(np.argmax(row))), finished)
            # 3. one batched decode step for every mid-generation slot
            live = [i for i, s in enumerate(self.slots)
                    if s is not None and s.state1 is None]
            if live:
                with tel.span("decode_inputs"):
                    toks = np.zeros((self.n_slots, 1), np.int32)
                    for i in live:
                        toks[i, 0] = self.slots[i].next_tok
                    toks = jnp.asarray(toks)
                with tel.span("decode_step", n_live=len(live)):
                    with tel.annotate_step("decode_step",
                                           self.stats.decode_steps):
                        out = self._decode(self.params, self.plans,
                                           self.state, toks)
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
                            self._commit(i, slot, self._sample(
                                slot, rows[i], int(greedy[i])), finished)
        return finished

    def _drain_budget(self) -> int:
        """Iteration cap for ``run`` (the paged engine widens it: evicted
        requests recompute from scratch)."""
        return ((len(self.queue) + len(self.active_uids) + 1)
                * (self.max_len + self.max_len // self.prefill_chunk + 2))

    def run(self, requests: list[Request] | None = None,
            max_iters: int | None = None) -> list[RequestOutput]:
        """Drain: submit ``requests`` and step until everything finishes.

        Outputs are returned ordered by ``uid`` for stable comparison.
        """
        for r in requests or ():
            self.submit(r)
        budget = max_iters if max_iters is not None else self._drain_budget()
        outputs: list[RequestOutput] = []
        it = 0
        while self.has_work:
            if it >= budget:
                raise RuntimeError(
                    f"scheduler did not drain within {budget} iterations")
            outputs.extend(self.step())
            it += 1
        return sorted(outputs, key=lambda o: o.uid)
