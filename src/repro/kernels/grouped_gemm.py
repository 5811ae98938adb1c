"""Grouped (per-expert) GEMM for MoE layers (Pallas TPU), token-major.

``y[r] = x[r] @ w[e(r)]`` for rows sorted by expert: expert ``e``'s group
holds rows ``[sum(group_sizes[:e]), sum(group_sizes[:e + 1]))``, each
group a whole number of ``block_m`` row tiles, and the rows past the last
group are zero in the result.  The expert of each row tile and the number
of live tiles come in by scalar prefetch, so the weights' block index map
reads them before the body runs: consecutive tiles of one expert keep its
weight block in VMEM (fetched once where the block covers the whole
``(D, F)`` matrix, as at granite's widths), and the tiles past the last
live one fetch nothing and write zeros.  This is the dropless layout of
``models/moe.py``'s single-device path: at decode, each expert a token
routes to costs one pass over its weights, whatever the batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import vmem_budget

#: the largest weight block edges tried before tiling a dimension
_MAX_BLOCK_K = 2048
_MAX_BLOCK_N = 2048


def row_tile(assignments: int, experts: int) -> int:
    """Rows per tile for ``assignments`` routed rows over ``experts``: the
    power of two from 16 to 128 nearest above the mean group, so that a
    decode step pads each expert's few rows to 16 and a long prefill runs
    full MXU tiles."""
    mean = max(1, -(-assignments // experts))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def padded_rows(assignments: int, experts: int, block_m: int) -> int:
    """The static row count that holds ``assignments`` rows in
    ``experts`` groups each padded to a multiple of ``block_m``."""
    rows = assignments + experts * (block_m - 1)
    return block_m * -(-rows // block_m)


def _block(dim: int, cap: int) -> int:
    """``dim`` whole where it fits under ``cap``, else the largest lane
    multiple under ``cap`` that divides it."""
    if dim <= cap:
        return dim
    for b in range(cap, 127, -128):
        if dim % b == 0:
            return b
    return dim


def _kernel(tile_expert_ref, live_ref, x_ref, w_ref, o_ref, acc_ref, *,
            k_steps: int):
    del tile_expert_ref                  # read by the index maps only
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(pl.program_id(0) < live_ref[0])
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_gemm_kernel(x, w, group_sizes, *, block_m: int,
                        interpret: bool = False):
    """x: (R, D) rows sorted by expert; w: (E, D, F); group_sizes: (E,)
    int32, each a multiple of ``block_m``, summing to at most R -> (R, F)
    in x's dtype (f32 accumulation)."""
    r, d = x.shape
    e, d2, f = w.shape
    assert d == d2 and r % block_m == 0, (x.shape, w.shape, block_m)
    bk, bn = _block(d, _MAX_BLOCK_K), _block(f, _MAX_BLOCK_N)
    n_tiles, k_steps, n_steps = r // block_m, d // bk, f // bn
    tile_end = jnp.cumsum(group_sizes.astype(jnp.int32)) // block_m
    live = tile_end[-1:]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right"), e - 1).astype(jnp.int32)

    def at(i, j, kk, live_ref):
        """The block a tile reads: its own while live; past the last live
        tile, the last one's final block, so that nothing is fetched."""
        dead = i >= live_ref[0]
        last = jnp.maximum(live_ref[0] - 1, 0)
        return (jnp.where(dead, last, i), jnp.where(dead, n_steps - 1, j),
                jnp.where(dead, k_steps - 1, kk))

    def x_map(i, j, kk, te, lv):
        ii, _, kb = at(i, j, kk, lv)
        return ii, kb

    def w_map(i, j, kk, te, lv):
        ii, jb, kb = at(i, j, kk, lv)
        return te[ii], kb, jb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_steps, k_steps),
        in_specs=[pl.BlockSpec((block_m, bk), x_map),
                  pl.BlockSpec((1, bk, bn), w_map)],
        out_specs=pl.BlockSpec((block_m, bn),
                               lambda i, j, kk, te, lv: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget()),
        interpret=interpret,
        name="grouped_gemm",
    )(tile_expert, live, x, w)
