"""GPipe-style pipeline parallelism over a mesh axis.

The layer stack is split into ``S`` equal stages along a mesh axis (the
``pod`` axis at production scale); microbatches stream through with
``collective_permute`` moving activations stage-to-stage.  The schedule is
the classic GPipe fill-drain loop expressed as one ``lax.scan`` over
``n_micro + S - 1`` ticks inside ``shard_map`` — fully differentiable
(collective_permute has a transpose rule: the reverse permute), so
``jax.grad`` through the pipelined forward just works; bubble overhead is
the usual (S-1)/(S-1+n_micro).

This module is deliberately model-agnostic: it pipelines any per-stage
``block_fn(stage_params, x) -> x``.  tests/test_pipeline.py checks exact
equivalence (fwd + grads) with the sequential stack on an 8-device host
mesh.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn: Callable, stage_params, x_micro, *,
                   mesh: Mesh, axis: str = "pod"):
    """Run microbatches through pipeline stages.

    block_fn: (params_for_one_stage, x) -> x          (pure)
    stage_params: pytree whose leaves have leading dim = n_stages (sharded
        over ``axis`` outside; inside the shard each device sees its own
        stage's slice with leading dim 1)
    x_micro: (n_micro, mb, ...) microbatched activations (replicated)

    Returns (n_micro, mb, ...) outputs (replicated over ``axis``).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def stage_fn(params, xm):
        params = jax.tree.map(lambda v: v[0], params)   # this stage's slice
        idx = jax.lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # select the incoming microbatch for stage 0 at tick t
            mb_in = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(idx == 0, mb_in, buf)
            y = block_fn(params, x_in)
            # last stage emits microbatch t - (S-1) at tick t
            out_t = t - (n_stages - 1)
            outs = jax.lax.cond(
                out_t >= 0,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_t, 0, n_micro - 1), axis=0),
                lambda o: o, outs)
            # rotate activations to the next stage
            buf_next = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xm[0])
        outs0 = jnp.zeros_like(xm)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(ticks))
        # `outs` is valid only on the LAST stage; mask + psum replicates it.
        last = n_stages - 1
        outs = jax.lax.psum(
            jnp.where(idx == last, outs, jnp.zeros_like(outs)), axis)
        return outs

    in_specs = (P(axis), P())        # params sharded by stage; acts replicated
    out_specs = P()
    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(stage_params, x_micro)


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major."""
    def f(v):
        l = v.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return v.reshape(n_stages, l // n_stages, *v.shape[1:])
    return jax.tree.map(f, stacked_params)
