"""Pallas TPU kernel: paged decode attention, read in place from the pool.

One query token per slot attends over the slot's live KV blocks, which
the kernel DMAs straight out of the block pools by the slot's block
table: only blocks ``j < ceil(kv_len / block_size)`` whose table entry
is not the sentinel are read, several per DMA step, double-buffered
across steps and across slots. Nothing of the ``max_len`` view is
gathered, and nothing is cast to f32 in HBM.

Each block of a ``(pool_rows, block_size, K, D)`` pool is viewed as
``rows`` lane-dense rows of ``lanes = c * D`` elements (``c = 128 // D``
heads share a row when ``D`` divides 128), so a block is one contiguous
``(rows, lanes)`` DMA. Within a step the ``pages * rows`` rows meet every
query head in one MXU product (bf16 operands, f32 accumulation); a row
that holds another KV head's keys, a position at or past ``kv_len``, or
a block that was not read is masked out of the online softmax (m, l and
the accumulator in f32). The probabilities reach the MXU as three bf16
terms, ``p = hi + mid + lo``, so the value sum keeps every bit of ``p``,
as XLA's f32 reference does where it computes on the vector unit.

A slot with no live block (idle, or mid-prefill behind an all-sentinel
table row) returns exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TARGET_ROWS = 1024   # rows of K (and of V) per DMA step


def pool_view(K: int, D: int) -> tuple[int, int]:
    """(heads per lane-dense row ``c``, row width ``lanes``) for head_dim D."""
    c = LANES // D if LANES % D == 0 and K % (LANES // D) == 0 else 1
    return c, c * D


def _kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, buf_ref, *, n_slots: int, max_blocks: int,
            pages: int, block_size: int, rows_per_token: int, c: int,
            group: int, sentinel: int, scale: float):
    """Grid: (n_slots,), in order. Slot b's last step starts slot b+1's
    first DMA, so the pool reads of consecutive slots overlap."""
    b = pl.program_id(0)
    heads, lanes = q_ref.shape[1:]
    rows = kbuf.shape[2]
    n = pages * rows

    def n_live(i):
        # an idle or mid-prefill slot's row is all sentinel: no steps to
        # walk, whatever its length
        blocks = jnp.minimum(pl.cdiv(lens_ref[i], block_size), max_blocks)
        return jnp.where(tables_ref[i * max_blocks] == sentinel, 0, blocks)

    def n_steps(i):
        return jnp.maximum(pl.cdiv(n_live(i), pages), 1)

    def page(i, j):
        """(pool row, read?) of slot i's block j."""
        row = tables_ref[i * max_blocks + jnp.minimum(j, max_blocks - 1)]
        return row, (j < n_live(i)) & (row != sentinel)

    def copies(i, step, buf):
        out = []
        for p in range(pages):
            row, ok = page(i, step * pages + p)
            out.append((ok, (
                pltpu.make_async_copy(k_hbm.at[row], kbuf.at[buf, p],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[row], vbuf.at[buf, p],
                                      sems.at[1, buf]))))
        return out

    def start(i, step, buf):
        for ok, pair in copies(i, step, buf):
            @pl.when(ok)
            def _():
                for cp in pair:
                    cp.start()

    def wait(i, step, buf):
        for ok, pair in copies(i, step, buf):
            @pl.when(ok)
            def _():
                for cp in pair:
                    cp.wait()

    @pl.when(b == 0)
    def _prologue():
        # a block that is not read leaves its buffer as it was: make that
        # zeros, so a masked probability never meets uninitialised memory
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        buf_ref[0] = 0
        start(0, 0, 0)

    kv_len = lens_ref[b]
    steps = n_steps(b)
    q = q_ref[0]                                             # (heads, lanes)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    col_page, col_row = col // rows, col % rows
    col_tok = col_row // rows_per_token
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
    same_head = (col_row % rows_per_token) == (head // group) // c

    def body(step, carry):
        m, l, acc = carry
        buf = buf_ref[0]
        last = step + 1 == steps
        nxt_b = jnp.where(last, b + 1, b)
        nxt_step = jnp.where(last, 0, step + 1)

        @pl.when(nxt_b < n_slots)
        def _():
            start(nxt_b, nxt_step, 1 - buf)

        wait(b, step, buf)
        read = jnp.zeros((1, n), jnp.bool_)
        for j in range(pages):
            read = read | ((col_page == j) & page(b, step * pages + j)[1])
        pos = (step * pages + col_page) * block_size + col_tok
        valid = same_head & read & (pos < kv_len)            # (heads, n)

        k = kbuf[buf].reshape(n, lanes)
        v = vbuf[buf].reshape(n, lanes)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m - m_safe)
        # p as three bf16 terms holds all 24 bits of its f32 mantissa;
        # stacked, they share one pass of v through the MXU
        hi = p.astype(v.dtype).astype(jnp.float32)
        mid = (p - hi).astype(v.dtype).astype(jnp.float32)
        terms = jnp.concatenate([hi, mid, p - hi - mid]).astype(v.dtype)
        pv3 = jnp.dot(terms, v, preferred_element_type=jnp.float32)
        pv = pv3[:heads] + pv3[heads:2 * heads] + pv3[2 * heads:]
        buf_ref[0] = 1 - buf
        return m_new, l * corr + p.sum(axis=1, keepdims=True), acc * corr + pv

    m0 = jnp.full((heads, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    a0 = jnp.zeros((heads, lanes), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, steps, body, (m0, l0, a0))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit,
                   static_argnames=("sentinel", "pages", "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           kv_len: jnp.ndarray, *, sentinel: int,
                           pages: int | None = None,
                           interpret: bool = True) -> jnp.ndarray:
    """q (B, H, D), pools (pool_rows, block_size, K, D), tables
    (B, max_blocks) int32 pool rows (``sentinel`` = unmapped), kv_len (B,)
    int32 -> (B, H, D) in q's dtype.

    ``pages`` (blocks per DMA step) defaults to what fills ``TARGET_ROWS``
    rows of the pool view; tests set it to force many steps per slot.
    """
    B, H, D = q.shape
    R, block_size, K, _ = k_pool.shape
    max_blocks = tables.shape[1]
    G = H // K
    c, lanes = pool_view(K, D)
    rows = block_size * K // c
    if pages is None:
        pages = max(1, min(TARGET_ROWS // rows, max_blocks))
    # query head h (KV head h // G) sits in lane block (h // G) % c of its row
    lane_block = (jnp.arange(H) // G) % c
    pick = lane_block[:, None] == jnp.arange(c)[None, :]     # (H, c)
    q_rows = jnp.where(pick[None, :, :, None], q[:, :, None, :],
                       jnp.zeros((), q.dtype)).reshape(B, H, lanes)
    kernel = functools.partial(
        _kernel, n_slots=B, max_blocks=max_blocks, pages=pages,
        block_size=block_size, rows_per_token=K // c, c=c, group=G,
        sentinel=sentinel, scale=D ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, lanes), lambda b, t, n: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, lanes), lambda b, t, n: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, rows, lanes), k_pool.dtype),
                pltpu.VMEM((2, pages, rows, lanes), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.reshape(-1).astype(jnp.int32), kv_len.astype(jnp.int32),
      q_rows, k_pool.reshape(R, rows, lanes), v_pool.reshape(R, rows, lanes))
    out = jnp.take_along_axis(out.reshape(B, H, c, D),
                              lane_block[None, :, None, None], axis=2)
    return out[:, :, 0].astype(q.dtype)
