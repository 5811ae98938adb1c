"""Pallas TPU GEMM kernels — the paper's algorithm family on real hardware.

Two kernels realise the two cost-model variants (core/tpu_model.GridOrder):

* ``gemm_k_inner`` — grid ``(M/bm, N/bn, K/bk)``, k innermost: the C block
  accumulates in a VMEM scratch and is written to HBM once — the **B3A2C0
  analogue** (output-stationary; "reduces the number of stores of C",
  paper §4).
* ``gemm_k_outer`` — k outermost: one aliased ``C += A_k @ B_k`` pass per k
  block, so C is re-fetched / re-written from HBM on every k step — the
  **C3B2A0/B3C2A0 analogue** (C streamed).  Strictly more HBM traffic; it
  exists so the simulator's predictions are observable in real artifacts,
  and because it needs no f32 accumulator resident in VMEM.

Kernels require tile-divisible shapes; ``ops.matmul`` pads (zero K-padding
is exact) and slices.  Block shapes come from TileTuner (core/autotune) —
the paper's "simulate-before-implement" workflow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import vmem_budget
from repro.core.tpu_model import GridOrder, TileConfig


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _check_divisible(m, n, k, bm, bn, bk):
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) not divisible by tile ({bm},{bn},{bk}); "
        "use kernels.ops.matmul which pads")


def _k_inner_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_k_inner(a, b, *, tile: TileConfig, interpret: bool = False):
    """C = A @ B with the output-stationary grid (B3A2C0 analogue)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(tile.bm, m), min(tile.bn, n), min(tile.bk, k)
    _check_divisible(m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)
    acc = _acc_dtype(a.dtype)
    out_dtype = acc if jnp.issubdtype(a.dtype, jnp.integer) else a.dtype
    return pl.pallas_call(
        functools.partial(_k_inner_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget()),
        interpret=interpret,
    )(a, b)


def _k_step_kernel(a_ref, b_ref, c_ref, o_ref):
    acc = _acc_dtype(a_ref.dtype)
    part = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=acc)
    o_ref[...] = (c_ref[...].astype(acc) + part).astype(o_ref.dtype)


@functools.lru_cache(maxsize=512)
def _k_step_call(m: int, n: int, bk: int, bm: int, bn: int,
                 out_dtype: str, interpret: bool, donate: bool = False):
    """One ``C += A_k @ B_k`` pass over the full C (grid (M/bm, N/bn)),
    built once per (shape, tile, dtype) configuration and jitted so the
    tracing/lowering cost is paid once, then reused across every k step of
    every call with that configuration."""
    call = pl.pallas_call(
        _k_step_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_budget()),
        interpret=interpret,
    )
    return jax.jit(call, donate_argnums=(2,) if donate else ())


def gemm_k_outer(a, b, c, *, tile: TileConfig, interpret: bool = False):
    """C += A @ B with C streamed per k block (C3B2A0/B3C2A0 analogue)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and c.shape == (m, n)
    bm, bn, bk = min(tile.bm, m), min(tile.bn, n), min(tile.bk, k)
    _check_divisible(m, n, k, bm, bn, bk)
    dt = jnp.dtype(c.dtype).name
    # Step 0 must not donate: c is the caller's array there.  Later steps
    # rebind c to the previous step's output, which is dead after the call —
    # donating it lets XLA honour the in-place input_output_aliases update
    # instead of copying C per step (donation is a no-op under interpret).
    first = _k_step_call(m, n, bk, bm, bn, dt, interpret)
    rest = first if interpret else \
        _k_step_call(m, n, bk, bm, bn, dt, interpret, donate=True)
    for kk in range(k // bk):
        a_k = jax.lax.slice_in_dim(a, kk * bk, (kk + 1) * bk, axis=1)
        b_k = jax.lax.slice_in_dim(b, kk * bk, (kk + 1) * bk, axis=0)
        c = (first if kk == 0 else rest)(a_k, b_k, c)
    return c


def gemm(a, b, c=None, *, tile: TileConfig, interpret: bool = False):
    if tile.order is GridOrder.K_INNER:
        out = gemm_k_inner(a, b, tile=tile, interpret=interpret)
        return out if c is None else c + out
    if c is None:
        dt = (_acc_dtype(a.dtype)
              if jnp.issubdtype(a.dtype, jnp.integer) else a.dtype)
        c = jnp.zeros((a.shape[0], b.shape[1]), dt)
    return gemm_k_outer(a, b, c, tile=tile, interpret=interpret)
