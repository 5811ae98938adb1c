"""Blocked causal flash attention (Pallas TPU).

Online-softmax over KV blocks with running (max, sum, accumulator) held in
VMEM scratch — the attention instance of the paper's blocking methodology:
the score matrix never touches HBM, so the HBM term of the roofline drops
from O(S^2) to O(S * D).  Causal block skipping prunes fully-masked blocks'
contributions via masking (the grid is still full; Mosaic handles the
revisit pipeline).

Grid: (batch*heads, S/block_q, S/block_k), k innermost.  Shapes must divide
the blocks (ops.flash_attention handles padding upstream by construction —
model sequence lengths are block-multiples).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotune import vmem_budget

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, block_q: int, block_k: int, k_steps: int,
                  scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1)[:, None])   # (bq, 1)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)[:, None]
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q,k,v: (B, S, H, D) -> (B, S, H, D)."""
    b, s, h, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, skv)
    assert s % block_q == 0 and skv % block_k == 0, (s, skv, block_q, block_k)
    # fold batch and heads: (B*H, S, D)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    grid = (b * h, s // block_q, skv // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, k_steps=grid[2],
                          scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget()),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
