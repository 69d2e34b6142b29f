"""Pallas TPU kernel: fine-grained-pipelined (streaming) attention, paper §IV.

The paper streams one input row at a time through ``QKᵀ → softmax → ·V`` so
the l×l logit matrix never exists.  On TPU the pipeline unit is an MXU tile,
not an SRAM word line: the grid is

    (q_head, q_block, kv_block)           kv innermost, sequential

and each step computes a ``(block_q, block_k)`` logits tile, updates the
online-softmax carry ``(m, l, acc)`` held in VMEM scratch, and emits the
normalised output on the last kv step.  VMEM working set per step:

    q tile        block_q × d        (revisited across kv steps — stays put)
    k,v tiles     block_k × d        (the "vector" flowing through the pipe)
    logits tile   block_q × block_k
    carry         block_q × (2·128 + d)

With block_q = block_k = 512 and d = 128 that is ~1.8 MiB — far under the
~16 MiB v5e VMEM budget and all matmul dims are multiples of 128 (MXU
aligned).  The exponential inside the softmax is the UCLM LUT decomposition
(``lut_exp_block``, an exact select over the 128-entry table), so this
kernel is the full HASTILY story in one place: attention whose softmax
*and* whose memory footprint are both restructured.

GQA: q heads are enumerated as B·Hq programs; the k/v index maps divide by
the group size so each kv head's tiles are shared by its G query heads.
Causal/window masking supports fully-masked-block *skipping*: the kv grid
axis still visits the block, but ``@pl.when`` guards the matmuls so the MXU
does no work for blocks strictly above the causal diagonal or outside the
sliding window.
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


def _exp_fn(mode: str, table):
    if mode == "lut":
        return lambda x: lut_exp_block(x, table, order=1)
    if mode == "lut0":
        return lambda x: lut_exp_block(x, table, order=0)
    return jnp.exp


def attention_kernel(q_ref, k_ref, v_ref, table_ref, o_ref,
                     m_ref, l_ref, acc_ref, *,
                     scale: float, causal: bool, window: Optional[int],
                     cap: Optional[float], exp_mode: str,
                     block_q: int, block_k: int, kv_len: int,
                     q_offset: int, num_kv_blocks: int):
    _, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    exp = _exp_fn(exp_mode, table_ref[...])

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- static-shape index vectors for this (q_block, kv_block) pair ---
    q_idx = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kv_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Fully-masked-block skip: with causal masking, any kv block whose first
    # index exceeds the last q position contributes nothing.
    run = jnp.asarray(True)
    if causal:
        run &= (j * block_k) <= (q_offset + (i + 1) * block_q - 1)
    if window is not None:
        # block entirely left of every q position's window start
        run &= ((j + 1) * block_k - 1) >= (q_offset + i * block_q - window + 1)
    run &= (j * block_k) < kv_len

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)                  # (bq, d)
        k = k_ref[...].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        mask = kv_idx < kv_len
        if causal:
            mask &= kv_idx <= q_idx
        if window is not None:
            mask &= (q_idx - kv_idx) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = exp(s - m_new)                                   # LUT softmax numerator
        p = jnp.where(mask, p, 0.0)
        alpha = exp(m_prev - m_new)                          # (bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)                   # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_kv_blocks - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "cap", "exp_mode",
                     "block_q", "block_k", "kv_len", "q_offset", "group",
                     "interpret"))
def attention_3d(q: jax.Array, k: jax.Array, v: jax.Array, table: jax.Array,
                 *, scale: float, causal: bool, window: Optional[int],
                 cap: Optional[float], exp_mode: str, block_q: int,
                 block_k: int, kv_len: int, q_offset: int, group: int,
                 interpret: bool = False) -> jax.Array:
    """q: (BHq, Lq, D), k/v: (BHkv, Lkv, D); Lq % block_q == Lkv % block_k == 0."""
    bhq, lq, d = q.shape
    bhkv, lkv, dv = k.shape
    assert bhq == bhkv * group and lq % block_q == 0 and lkv % block_k == 0
    nq, nk = lq // block_q, lkv // block_k

    kernel = functools.partial(
        attention_kernel, scale=scale, causal=causal, window=window, cap=cap,
        exp_mode=exp_mode, block_q=block_q, block_k=block_k, kv_len=kv_len,
        q_offset=q_offset, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(bhq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, LUT_K), lambda b, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, lq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, dv), jnp.float32),      # weighted accumulator
        ],
        interpret=interpret,
    )(q, k, v, table.reshape(1, LUT_K))
