"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gemm_ref(a, b, c=None):
    acc = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    out = jnp.dot(a.astype(acc) if jnp.issubdtype(a.dtype, jnp.integer) else a,
                  b.astype(acc) if jnp.issubdtype(b.dtype, jnp.integer) else b,
                  preferred_element_type=acc)
    out = out.astype(acc if jnp.issubdtype(a.dtype, jnp.integer) else a.dtype)
    return out if c is None else c + out


def gemm_ref_streamed(a, b, c, bk: int):
    """Oracle for the C-streamed (k-outer) variant: C is rounded to its
    storage dtype after every k-block pass — the exact function
    ``gemm_k_outer`` computes (and the numerical price of the paper's
    C3B2A0/B3C2A0 loop orders on reduced-precision storage)."""
    k = a.shape[1]
    acc = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    for kk in range(0, k, bk):
        part = jnp.dot(a[:, kk:kk + bk], b[kk:kk + bk],
                       preferred_element_type=acc)
        c = (c.astype(acc) + part).astype(c.dtype)
    return c


def grouped_gemm_ref(x, w, group_sizes):
    """x: (R, D) rows sorted by expert; w: (E, D, F); group_sizes: (E,) ->
    (R, F): each group's rows times its expert's weights, zero past the
    last group (``grouped_gemm_kernel``'s contract; differentiable)."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """q,k,v: (B, S, H, D) -> (B, S, H, D), plain softmax attention."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[1]), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
