"""Pallas TPU kernel: paged attention — KV pages read in place.

The serving pool keeps KV as ``(num_pages, Hkv, page_size, D)``; each lane's
logical sequence is its page table row.  The grid is

    (batch, kv_head, page_block)          page_block innermost, sequential

where one page block is ``bp = min(block_pages, P)`` consecutive table
slots.  The pools stay in HBM (``memory_space=pl.ANY``) and the *page table
is a scalar-prefetch operand*: each grid step DMAs the ``bp`` physical pages
``tbl[b, j·bp + i]`` of its kv head into a double-buffered
``(2, bp, page_size, D)`` VMEM block, starting the next block's copies
before it computes the current one — no gathered contiguous copy of the
cache is ever built in HBM.  Each step computes the
``(G·Lq, bp·page_size)`` logits tile for the lane's G grouped query heads ×
Lq query rows and folds it into the online-softmax carry ``(m, l, acc)`` in
VMEM scratch — the paper's multicore partial-max/partial-sum gather
(§III-B2) across page blocks.  At ``bp·page_size >= 128`` the tile fills
every lane of a vreg, and the carry's rescale runs once per block, not
once per page.  The last page block normalises and emits.

One kernel serves both serving phases:

- **decode** (``Lq == 1``): the query row sits at ``kv_len - 1`` and the
  live-length mask is the causal mask;
- **chunked prefill** (``Lq > 1``): query row ``i`` sits at absolute
  position ``kv_len - Lq + i`` (the chunk is the tail of the live rows,
  already written to its pages), so the mask is the per-row causal bound
  ``row ≤ kv_len - Lq + i`` — intra-chunk causal on the diagonal pages,
  plain length gating before them.

Dead pages cost no copy and no compute: a page block at or past the lane's
live length is skipped whole, and inside the last live block only the live
pages are copied (the rest of the buffer is masked, and zeroed on the V
side, so stale VMEM never reaches the sum).  A table width that ``bp`` does
not divide leaves the last block short; its missing slots are dead pages.

The INT8 variant copies the int8 pages the same way and dequantises per
KV row inside the step: row c's scale multiplies logits column c, and p's
column c before p·V.  A page's scales (one f32 per row) are narrower than
the 128-lane tile an HBM DMA must slice whole, so the lane's scales are
gathered outside the kernel into ``(1, bp·page_size)`` rows that ride a
BlockSpec.  Quantised serving keeps its 2×-smaller resident cache *and*
the in-place read path for K and V.

Like the streaming kernel, the exponential is the paper's LUT decomposition
(``lut_exp_block``).  VMEM holds the double-buffered K/V page blocks, the
(G·Lq, bp·page_size) logits and the carry; ``tests/test_tpu_compile.py``
compiles the serving widths for a v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut_exp import K as LUT_K
from repro.core.lut_softmax import NEG_INF
from repro.kernels.lut_exp.kernel import lut_exp_block

LANES = 128  # m/l carries are broadcast across one lane register
# The kernel's name in compiled programs and profiler traces.  Its device
# ops are found by the substring "paged_attention", which no other op of
# the serving step holds.
KERNEL_NAME = "paged_attention_varlen"


def _exp_fn(mode: str, table):
    if mode == "lut":
        return lambda x: lut_exp_block(x, table, order=1)
    if mode == "lut0":
        return lambda x: lut_exp_block(x, table, order=0)
    return jnp.exp


def _live_blocks(kv_len, *, page_size: int, block_pages: int,
                 num_slots: int):
    """(rows the walk may attend, page blocks holding any of them): the
    live length cut at the table's end, and its page-block count."""
    lim = jnp.minimum(kv_len, num_slots * page_size)
    rows = block_pages * page_size
    return lim, (lim + rows - 1) // rows


def paged_attention_kernel(tbl_ref, len_ref, q_ref, table_ref, *refs,
                           scale: float, cap: Optional[float],
                           window: Optional[int], exp_mode: str,
                           page_size: int, block_pages: int, num_slots: int,
                           q_len: int, quantized: bool):
    if quantized:
        (k_hbm, v_hbm, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
         k_buf, v_buf, sem) = refs
    else:
        (k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf,
         sem) = refs
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ps, bp = page_size, block_pages
    kv_len = len_ref[b]
    lim, live_blocks = _live_blocks(kv_len, page_size=ps, block_pages=bp,
                                    num_slots=num_slots)

    def page_copies(blk, slot):
        """(live, copies) of each table slot of page block ``blk``."""
        for i in range(bp):
            s = blk * bp + i
            page = tbl_ref[b, jnp.minimum(s, num_slots - 1)]
            yield s * ps < lim, [
                pltpu.make_async_copy(src.at[page, h], dst.at[slot, i],
                                      sem.at[slot])
                for src, dst in pools]

    def start(blk, slot):
        for live, copies in page_copies(blk, slot):
            @pl.when(live)
            def _():
                for c in copies:
                    c.start()

    def wait(blk, slot):
        for live, copies in page_copies(blk, slot):
            @pl.when(live)
            def _():
                for c in copies:
                    c.wait()

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start(0, 0)

    # Live-block gate: blocks at or past the lane's length hold no rows.
    @pl.when(j < live_blocks)
    def _step():
        slot = j % 2

        @pl.when(j + 1 < live_blocks)
        def _prefetch():
            start(j + 1, 1 - slot)

        wait(j, slot)
        exp = _exp_fn(exp_mode, table_ref[...])
        q = q_ref[...].astype(jnp.float32)                   # (G·Lq, D)
        k = k_buf[slot].astype(jnp.float32)                  # (bp, ps, D)
        v = v_buf[slot].astype(jnp.float32)
        k = k.reshape(bp * ps, k.shape[-1])                  # (bp·ps, D)
        v = v.reshape(bp * ps, v.shape[-1])
        # Uncopied pages hold stale VMEM: their logits are masked below,
        # and their values are zeroed so 0·stale never enters the sum.
        v_row = j * bp * ps + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_row < lim, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G·Lq, bp·ps)
        if quantized:
            # Per-row dequant: KV row c's scale multiplies logits column c
            # (and, below, p's column c before p·V).
            s = s * ks_ref[...]                              # (1, bp·ps)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        # Structural column index == absolute position (pages are in table
        # order); logits row r covers query index r % Lq, whose position is
        # kv_len - Lq + (r % Lq) — its own causal bound, cut at the table's
        # end.  Decode (Lq == 1) degenerates to the plain kv_len length mask.
        row = j * bp * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % q_len
        q_pos = kv_len - q_len + qi
        mask = (row <= q_pos) & (row < lim)
        if window is not None:
            mask &= (q_pos - row) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                # (G·Lq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, exp(s - m_new), 0.0)
        alpha = exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p * vs_ref[...] if quantized else p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (G·Lq, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "cap", "window", "exp_mode", "group", "q_len",
                     "block_pages", "interpret"))
def paged_attention_4d(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       k_scale: Optional[jax.Array],
                       v_scale: Optional[jax.Array],
                       page_table: jax.Array, kv_len: jax.Array,
                       table: jax.Array, *, scale: float,
                       cap: Optional[float], window: Optional[int],
                       exp_mode: str, group: int, block_pages: int,
                       q_len: int = 1,
                       interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G·Lq, D) with row r ↔ (head group r // Lq, query index
    r % Lq); pools: (N, Hkv, ps, D); page_table: (B, P) int32; kv_len: (B,)
    int32; ``block_pages`` pages per grid step, clamped to P.
    → (B, Hkv, G·Lq, D) in q's dtype."""
    b, hkv, rows, d = q.shape
    n, _, ps, dv = v_pool.shape
    p = page_table.shape[1]
    assert rows == group * q_len, (rows, group, q_len)
    bp = min(block_pages, p)
    nblk = -(-p // bp)
    quantized = k_scale is not None
    inputs = [k_pool, v_pool]
    specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if quantized:
        # A page's scales are a (ps,)-row, narrower than the 128-lane tile a
        # DMA out of HBM must slice whole, so the scales of the lane's table
        # are gathered here, (B, Hkv, page_block, 1, bp·ps), and ride a
        # BlockSpec.  Dead blocks repeat the last live index: no refetch.
        def gather(sc):
            g = jnp.moveaxis(jnp.take(sc, page_table, axis=0), 2, 1)
            g = jnp.pad(g, ((0, 0), (0, 0), (0, nblk * bp - p), (0, 0)))
            return g.reshape(b, hkv, nblk, 1, bp * ps)

        def scale_map(b_, h, j, tbl, lens):
            del tbl
            _, live = _live_blocks(lens[b_], page_size=ps, block_pages=bp,
                                   num_slots=p)
            return (b_, h, jnp.clip(j, 0, jnp.maximum(live - 1, 0)), 0, 0)

        inputs += [gather(k_scale), gather(v_scale)]
        specs += [pl.BlockSpec((None, None, None, 1, bp * ps),
                               scale_map)] * 2

    kernel = functools.partial(
        paged_attention_kernel, scale=scale, cap=cap, window=window,
        exp_mode=exp_mode, page_size=ps, block_pages=bp, num_slots=p,
        q_len=q_len, quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page table + per-lane lengths
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda b_, h, j, tbl, lens: (b_, h, 0, 0)),
            pl.BlockSpec((1, LUT_K),
                         lambda b_, h, j, tbl, lens: (0, 0)),
        ] + specs,
        out_specs=pl.BlockSpec((None, None, rows, dv),
                               lambda b_, h, j, tbl, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),  # running max
            pltpu.VMEM((rows, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((rows, dv), jnp.float32),     # weighted accumulator
            pltpu.VMEM((2, bp, ps, d), k_pool.dtype),  # K page blocks
            pltpu.VMEM((2, bp, ps, dv), v_pool.dtype),  # V page blocks
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q, table.reshape(1, LUT_K), *inputs)
