"""LUT-based exponential — the math at the heart of HASTILY's UCLM (paper §III-B1).

The paper computes ``e^x = 2^n · 2^(d/K) · e^r`` (Harrison/Tak/Tang decomposition)
with a K=128-entry lookup table of ``2^(d/K)`` values stored *inside* the SRAM
compute array.  ``n = ⌊x/ln2⌋`` selects a bit-shift, ``d`` indexes the table, and
the residual ``e^r`` (``0 ≤ r < ln2/K``) is approximated as ``1`` (order 0,
error < 0.54%) or ``1 + r`` (order 1, error < 0.0015%).

TPU adaptation: ``2^n`` is an exact exponent-field bit-twiddle, the table lives in
VMEM (one 128-lane VREG row — K=128 is exactly the TPU lane width), and the lookup
is a gather.  The Pallas kernels (``repro.kernels.lut_exp``) perform it as an exact
select over the 128 table entries, which Mosaic lowers.

This module is the pure-jnp shared math: both the kernel and the reference oracle
import from here, so there is a single source of truth for the decomposition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

K = 128  # table entries; == TPU lane width (paper uses K=128 as well)
LN2 = float(np.log(2.0))
LOG2E = float(1.0 / np.log(2.0))
# Below this input, e^x underflows f32 anyway; used to make exp(-inf) == 0 exact.
UNDERFLOW_X = -87.0


@functools.lru_cache(maxsize=None)
def _table_np(k: int = K) -> np.ndarray:
    return (2.0 ** (np.arange(k, dtype=np.float64) / k)).astype(np.float32)


def make_table(k: int = K, dtype=jnp.float32) -> jax.Array:
    """The 128-entry ``2^(d/K)`` table the paper stores in each SRAM array."""
    return jnp.asarray(_table_np(k), dtype=dtype)


def pow2_int(n: jax.Array) -> jax.Array:
    """Exact ``2^n`` for integer-valued f32 ``n`` via exponent-field construction.

    The CIM analogue is the paper's "bit-shift decided by n"; on TPU we build the
    float directly: ``bitcast((n + 127) << 23)``.  ``n`` is clamped to the normal
    range; n <= -127 flushes to 0 which is the correct softmax behaviour for
    heavily-masked logits.
    """
    n_i = jnp.clip(n, -127.0, 127.0).astype(jnp.int32)
    bits = jnp.where(n_i <= -127, 0, (n_i + 127) << 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def decompose(x: jax.Array, k: int = K):
    """Split ``x`` into (n, d, r_scaled) s.t. e^x = 2^n · 2^(d/k) · e^(r_scaled·ln2/k).

    r_scaled ∈ [0, 1) is the residual in units of ln2/k.
    """
    t = x.astype(jnp.float32) * LOG2E
    n = jnp.floor(t)
    f = t - n  # ∈ [0, 1)
    fk = f * k
    d = jnp.floor(fk)
    # Guard the d == k corner from f rounding up to 1.0.
    d = jnp.clip(d, 0.0, float(k - 1))
    r_scaled = fk - d
    return n, d.astype(jnp.int32), r_scaled


def residual_correction(r_scaled: jax.Array, k: int = K, order: int = 1) -> jax.Array:
    """e^r for r = r_scaled · ln2/k.  order 0 → 1 (paper err<0.54%); 1 → 1+r."""
    if order == 0:
        return jnp.ones_like(r_scaled)
    return 1.0 + r_scaled * (LN2 / k)


def lut_exp(x: jax.Array, *, k: int = K, order: int = 1,
            table: jax.Array | None = None) -> jax.Array:
    """LUT exponential, pure-jnp path (the oracle; used by the model code on CPU).

    The Pallas kernel in ``repro.kernels.lut_exp`` computes the same function,
    bit for bit, with the table lookup performed as a select.
    """
    dtype = x.dtype
    if table is None:
        table = make_table(k)
    xf = x.astype(jnp.float32)
    n, d, r = decompose(xf, k)
    looked = jnp.take(table.astype(jnp.float32), d, axis=0)
    out = pow2_int(n) * looked * residual_correction(r, k, order)
    # exp(-inf) and deep-underflow inputs → exactly 0 (masked attention positions).
    out = jnp.where(xf < UNDERFLOW_X, 0.0, out)
    return out.astype(dtype)


def lut_exp2(x: jax.Array, *, k: int = K, order: int = 1) -> jax.Array:
    """LUT ``2^x`` — handy for bases already in log2 domain."""
    return lut_exp(x * LN2, k=k, order=order)
