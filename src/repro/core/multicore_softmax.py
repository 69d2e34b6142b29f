"""Multi-core softmax — HASTILY §III-B2 mapped onto the TPU mesh.

The paper parallelises the softmax of one long row across CIM *cores*: each core
computes a local maximum and a partial exp-sum, then the partials are gathered in a
**binary tree** (O(log n) depth) through shared memory.  On a TPU pod the cores are
chips and the shared memory is the ICI: ``jax.lax.pmax / psum`` over a mesh axis are
tree/ring all-reduces with exactly that O(log n) combine depth.

Two implementations are provided:

* ``sharded_softmax`` — the production path: local max/exp/sum + ``pmax``/``psum``.
* ``tree_allreduce`` — a literal recursive-doubling butterfly built from
  ``ppermute`` rounds, mirroring the paper's Fig. 5 gather; used in tests to show
  it is step-for-step equivalent to the collective (and to count the log₂(n)
  rounds explicitly).

Both must be called inside ``shard_map`` with the reduced axis sharded.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.lut_exp import lut_exp


def tree_allreduce(x: jax.Array, op: Callable, axis_name: str) -> jax.Array:
    """Recursive-doubling all-reduce via ppermute — the paper's binary-tree gather.

    O(log₂ n) rounds; after round i every device holds the reduction over its
    2^(i+1)-device group.  Requires the axis size to be a power of two.
    """
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0, f"tree_allreduce needs power-of-two axis, got {n}"
    dist = 1
    while dist < n:
        perm = [(i, i ^ dist) for i in range(n)]  # butterfly partner exchange
        other = jax.lax.ppermute(x, axis_name, perm)
        x = op(x, other)
        dist *= 2
    return x


def sharded_softmax(x_local: jax.Array, axis_name: str, *,
                    exp_fn=lut_exp, axis: int = -1) -> jax.Array:
    """Softmax over a dimension sharded across ``axis_name``.

    Each shard: local max → subtract → LUT-exp → local sum; the global max and
    denominator are combined with tree all-reduces (paper Fig. 5 right).
    """
    m_local = jnp.max(x_local, axis=axis, keepdims=True)
    m = jax.lax.pmax(m_local, axis_name)
    e = exp_fn(x_local - m)
    s_local = jnp.sum(e, axis=axis, keepdims=True)
    s = jax.lax.psum(s_local, axis_name)
    return e / jnp.maximum(s, 1e-30)


def sharded_softmax_tree(x_local: jax.Array, axis_name: str, *,
                         exp_fn=lut_exp, axis: int = -1) -> jax.Array:
    """Same as ``sharded_softmax`` but with the explicit ppermute butterfly."""
    m_local = jnp.max(x_local, axis=axis, keepdims=True)
    m = tree_allreduce(m_local, jnp.maximum, axis_name)
    e = exp_fn(x_local - m)
    s_local = jnp.sum(e, axis=axis, keepdims=True)
    s = tree_allreduce(s_local, jnp.add, axis_name)
    return e / jnp.maximum(s, 1e-30)
