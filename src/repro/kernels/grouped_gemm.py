"""Grouped (per-expert) GEMM for MoE layers (Pallas TPU).

Computes ``y[e] = x[e] @ w[e]`` for all experts in one kernel, tiling the
capacity and feature dims.  The expert dim is the outermost grid axis so the
kernel composes with expert-parallel sharding via ``shard_map`` (each shard
runs its local experts).  Tiles follow TileTuner's choices for the
per-expert GEMM shape — the small ``moe_d_ff`` GEMMs of granite (512) vs the
wide ones of kimi (2048) land on different tiles, exactly the shape
sensitivity the paper's Table 2 documents for MobileNet layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import vmem_budget


def _grouped_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_gemm_kernel(x, w, *, block_c: int = 128, block_f: int = 128,
                        block_k: int = 512, interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    e, c, d = x.shape
    e2, d2, f = w.shape
    assert e == e2 and d == d2
    bc, bf, bk = min(block_c, c), min(block_f, f), min(block_k, d)
    assert c % bc == 0 and f % bf == 0 and d % bk == 0, (x.shape, w.shape)
    grid = (e, c // bc, f // bf, d // bk)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, k_steps=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda g, i, j, kk: (g, i, kk)),
            pl.BlockSpec((1, bk, bf), lambda g, i, j, kk: (g, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda g, i, j, kk: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=vmem_budget()),
        interpret=interpret,
    )(x, w)
