"""Public jit'd wrappers for the Pallas kernels.

``matmul`` routes through the unified plan/execute API (``repro.gemm``): it
plans on the ``pallas`` backend — the paper's analytical tile selection,
memoised in the process-level plan cache — and executes the frozen plan.
``grouped_gemm`` / ``flash_attention`` dispatch on backend directly:

* on TPU (``jax.default_backend() == 'tpu'``) or with ``interpret=True``
  they run the Pallas kernels;
* otherwise (CPU container, 512-device dry-run) they fall back to the
  pure-jnp reference path so XLA-native SPMD lowering stays clean
  (DESIGN.md §3).

Padding to tile multiples happens inside the pallas backend's execute (zero
K-padding is mathematically exact; M/N padding is sliced off).
"""
from __future__ import annotations

import warnings

import jax

from repro import gemm as gemm_api
from repro.core.tpu_model import GridOrder, TileConfig
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.grouped_gemm import grouped_gemm_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pick_tile(m: int, n: int, k: int, dtype: str,
              order: GridOrder | None = None) -> TileConfig:
    """Deprecated shim: use ``repro.gemm.plan(...).selection`` instead."""
    warnings.warn(
        "kernels.ops.pick_tile is deprecated; use "
        "repro.gemm.plan((m, n, k), backend='analytic-tpu').selection",
        DeprecationWarning, stacklevel=2)
    t = gemm_api.plan((m, n, k), backend="analytic-tpu", dtype=dtype).selection
    if order is not None and t.order is not order:
        t = TileConfig(t.bm, t.bn, t.bk, order)
    return t


def matmul(a, b, *, tile: TileConfig | None = None,
           interpret: bool = False, force_pallas: bool = False):
    """C = A @ B through the planned Pallas kernel (TPU) or jnp (elsewhere).

    The TPU/interpret-vs-reference dispatch lives in one place: the pallas
    backend's ``execute`` (off-TPU without interpret it runs the jnp
    reference), so every call routes through the plan cache.
    """
    m, k = a.shape
    n = b.shape[1]
    options = {} if tile is None else {"tile": tile}
    plan = gemm_api.plan((m, n, k), backend="pallas",
                         dtype=gemm_api.dtype_tag(a.dtype), **options)
    return plan.execute(a, b, interpret=interpret, force=force_pallas)


def grouped_gemm(x, w, group_sizes, *, block_m: int,
                 interpret: bool = False):
    """x: (R, D) rows sorted by expert @ w: (E, D, F) -> (R, F), expert
    ``e`` on its ``group_sizes[e]`` rows (multiples of ``block_m``); the
    MoE expert FFN (``kernels.grouped_gemm``)."""
    if not (_on_tpu() or interpret):
        return ref.grouped_gemm_ref(x, w, group_sizes)
    return grouped_gemm_kernel(x, w, group_sizes, block_m=block_m,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q,k,v: (B, S, H, D) -> (B, S, H, D)."""
    if not (_on_tpu() or interpret):
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)
