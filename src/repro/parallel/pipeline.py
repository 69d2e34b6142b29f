"""Pipeline parallelism: GPipe-style stage schedule over a mesh axis.

This is the TPU expression of HASTILY §IV's *inter-layer* fine-grained
pipelining: encoder N's first output vector feeds encoder N+1 immediately.
On a mesh, "vector" becomes "microbatch" and "encoder" becomes "stage": each
device along ``axis`` holds one stage's layers; microbatches flow through
the stage ring via ``ppermute``.  For M microbatches and S stages the bubble
fraction is (S−1)/(M+S−1) — the paper's (N+1)·seqLen fill cost in TPU form
(DESIGN.md §2).

Implementation: ``shard_map`` over ``axis``; each step of the schedule loop
computes the resident stage on its current activation and rotates
activations one stage forward.  Stage s processes microbatch m at step
t = s + m, so the loop runs M + S − 1 steps; outputs are collected on the
last stage and rotated back to stage order at the end.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Params = Any


def pipeline_apply(stage_fn: Callable[[Params, jax.Array], jax.Array],
                   stage_params: Params, x: jax.Array, mesh: Mesh,
                   axis: str = "pod") -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline over mesh ``axis``.

    stage_params: pytree whose leaves have leading dim S (one slice per
    stage, sharded over ``axis``).  x: (M, mb, ...) microbatched input,
    replicated over ``axis``.  Returns (M, mb, ...) outputs.
    """
    s = mesh.shape[axis]
    m = x.shape[0]

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(p_spec, P()), out_specs=P(),
        check_vma=False)
    def run(params, xs):
        params = jax.tree.map(lambda a: a[0], params)   # this stage's slice
        stage = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % s) for i in range(s)]      # stage ring
        n_steps = m + s - 1

        def body(carry, t):
            act, outs = carry
            # microbatch index this stage would start at step t
            mb_idx = t - stage
            fresh = jnp.where((mb_idx >= 0) & (mb_idx < m),
                              jnp.clip(mb_idx, 0, m - 1), 0)
            # stage 0 ingests a fresh microbatch; others use the rotated act
            inp = jnp.where(stage == 0, xs[fresh], act)
            active = (mb_idx >= 0) & (mb_idx < m)
            out = stage_fn(params, inp)
            out = jnp.where(active, out, act)
            # last stage emits: store finished microbatch
            done_idx = t - (s - 1)
            emit = (stage == s - 1) & (done_idx >= 0) & (done_idx < m)
            outs = jax.lax.cond(
                emit,
                lambda o: o.at[jnp.clip(done_idx, 0, m - 1)].set(out),
                lambda o: o, outs)
            # rotate activations one stage forward
            act_next = jax.lax.ppermute(out, axis, fwd)
            return (act_next, outs), None

        init_act = jnp.zeros_like(xs[0])
        init_out = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(body, (init_act, init_out),
                                    jnp.arange(n_steps))
        # Only the last stage accumulated into ``outs``; everyone else holds
        # zeros, so a psum replicates the result (out_specs=P()).
        return jax.lax.psum(outs, axis)

    return run(stage_params, x)


def stack_stages(layer_params: Params, num_stages: int) -> Params:
    """Regroup a leading layers dim L into (S, L/S) stage slices."""
    def regroup(a):
        l = a.shape[0]
        assert l % num_stages == 0, (l, num_stages)
        return a.reshape((num_stages, l // num_stages) + a.shape[1:])
    return jax.tree.map(regroup, layer_params)
