"""Pallas TPU kernel for the UCLM LUT-exponential (paper §III-A/B).

The paper's UCLM performs the ``2^(d/K)`` table lookup inside the same SRAM
array that does the MVMs.  K = 128 is exactly one TPU lane width, so the
table occupies a single (1, 128) VMEM row (one VREG row), mirroring the
paper's "one table per 64×64 array" layout (Fig. 4a).

The lookup itself is a select over the K entries on the VPU
(:func:`table_lookup`).  Mosaic lowers neither a vector gather nor the
lane-flattening reshape a one-hot × table MXU matmul needs, and a
default-precision f32 matmul on the chip rounds ``T[d]`` to bf16, where a
select copies it exactly.  Whether the LUT pays against ``jnp.exp`` on the
chip is measured, not assumed.

Blocking: the input is viewed as (M, 128) lanes; each grid step processes a
``(block_m, 128)`` VMEM tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lut_exp import K, LN2, LOG2E, UNDERFLOW_X


def _pow2_int_f32(n: jax.Array) -> jax.Array:
    """Exact 2^n by exponent-field construction (kernel-local copy)."""
    n_i = jnp.clip(n, -127.0, 127.0).astype(jnp.int32)
    bits = jnp.where(n_i <= -127, 0, (n_i + 127) << 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def table_lookup(d_i: jax.Array, table: jax.Array) -> jax.Array:
    """Exactly ``T[d]`` for an int32 index block of any 2D shape.

    A select over the K table entries: ``out = where(d == k, T[k], out)``.
    Each ``T[k]`` is a static (1, 1) lane slice broadcast against the block,
    so the lowering needs no gather, no dynamic slice of a value and no
    reshape across lanes — the constructs Mosaic refuses.  A select copies
    the entry bit for bit, where a default-precision one-hot matmul on the
    MXU would round it to bf16.  Shared by all three LUT kernels.
    """
    table = table.reshape(1, K).astype(jnp.float32)
    out = jnp.zeros(d_i.shape, jnp.float32)
    for k in range(K):
        out = jnp.where(d_i == k, table[:, k:k + 1], out)
    return out


def lut_exp_block(x: jax.Array, table: jax.Array, *,
                  order: int = 1) -> jax.Array:
    """e^x for a 2D f32 block — the kernel-side LUT-exp decomposition."""
    t = x * LOG2E
    n = jnp.floor(t)
    fk = (t - n) * K
    d = jnp.clip(jnp.floor(fk), 0.0, float(K - 1))
    r = fk - d
    looked = table_lookup(d.astype(jnp.int32), table)
    corr = 1.0 if order == 0 else 1.0 + r * (LN2 / K)
    out = _pow2_int_f32(n) * looked * corr
    return jnp.where(x < UNDERFLOW_X, 0.0, out)


def lut_exp_kernel(x_ref, table_ref, o_ref, *, order: int, block_m: int):
    """One (block_m, K) tile: e^x = 2^n · T[d] · (1 + r·ln2/K)."""
    x = x_ref[...].astype(jnp.float32)                       # (bm, K)
    o_ref[...] = lut_exp_block(x, table_ref[...], order=order)


@functools.partial(jax.jit, static_argnames=("order", "block_m", "interpret"))
def lut_exp_2d(x: jax.Array, table: jax.Array, *, order: int = 1,
               block_m: int = 256, interpret: bool = False) -> jax.Array:
    """e^x for an (M, 128) f32 array, M a multiple of ``block_m``."""
    m, k = x.shape
    assert k == K and m % block_m == 0, (x.shape, block_m)
    kernel = functools.partial(lut_exp_kernel, order=order, block_m=block_m)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, K), jnp.float32),
        interpret=interpret,
    )(x, table.reshape(1, K))
