"""Core layers: norms, rotary embeddings, MLPs, embedding / logits heads.

All matmul-shaped operations route through the unified plan/execute API
(``repro.gemm.matmul``) so the analytic tile decisions (the paper's
technique) apply framework-wide; on the CPU/dry-run path the planner picks
the ``reference`` backend (XLA-native jnp dot), keeping 512-device SPMD
lowering clean (DESIGN.md §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import gemm as gemm_api
from repro.models.common import (
    MeshInfo,
    Param,
    dense_init,
    embed_init,
    ones_init,
    zeros_init,
)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg, mesh: MeshInfo, dtype):
    p = {"scale": ones_init((cfg.d_model,), P(None), dtype)}
    if cfg.norm_type == "layernorm":
        p["bias"] = zeros_init((cfg.d_model,), P(None), dtype)
    return p


def apply_norm(params, x, cfg):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * params["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_tables(positions, head_dim: int, theta: float):
    """positions: (...,) int32 -> (sin, cos) of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, sin, cos):
    """x: (..., seq, heads, head_dim); sin/cos: (..., seq, head_dim//2).
    Rotation in f32, result cast back to x.dtype."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    s = sin[..., None, :]  # broadcast over heads axis
    c = cos[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, mesh: MeshInfo, dtype, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ff_ax = mesh.shard_if(f)
    fsdp = mesh.fsdp_if(d)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(k1, d, (d, f), P(fsdp, ff_ax), dtype),
        "w_down": dense_init(k2, f, (f, d), P(ff_ax, fsdp), dtype),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(k3, d, (d, f), P(fsdp, ff_ax), dtype)
    return p


def apply_mlp(params, x, cfg):
    up = gemm_api.matmul(x, params["w_up"])
    if cfg.act == "swiglu":
        h = jax.nn.silu(gemm_api.matmul(x, params["w_gate"])) * up
    elif cfg.act == "geglu":
        h = jax.nn.gelu(gemm_api.matmul(x, params["w_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    return gemm_api.matmul(h, params["w_down"])


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def init_embedding(key, cfg, mesh: MeshInfo, dtype):
    v = cfg.padded_vocab
    vax = mesh.shard_if(v)
    fsdp = mesh.fsdp_if(cfg.d_model)
    k1, k2 = jax.random.split(key)
    p = {"table": embed_init(k1, v, cfg.d_model, P(vax, fsdp), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, cfg.d_model, (cfg.d_model, v),
                                  P(fsdp, vax), dtype)
    return p


def embed_tokens(params, token_ids, cfg):
    with jax.named_scope("embed"):
        return jnp.take(params["table"], token_ids, axis=0)


def logits_head(params, x, cfg):
    """x: (..., d) -> (..., padded_vocab); soft-capped if configured."""
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = gemm_api.matmul(x, params["table"].T)
        else:
            logits = gemm_api.matmul(x, params["unembed"])
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


def cross_entropy(logits, labels, vocab_size: int, z_coef: float = 1e-4,
                  mask=None):
    """Next-token CE over the *logical* vocab (padded tail masked out).

    logits: (B, S, Vp) f32/bf16; labels: (B, S) int32.  Returns scalar mean
    loss (+ small z-loss for logit drift) over unmasked positions.
    """
    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    if vp > vocab_size:
        neg = jnp.full((vp - vocab_size,), -1e9, dtype=logits.dtype)
        logits = logits.at[..., vocab_size:].set(neg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    z = z_coef * jnp.square(lse)
    per_tok = nll + z
    if mask is None:
        return per_tok.mean()
    mask = mask.astype(jnp.float32)
    return (per_tok * mask).sum() / jnp.maximum(mask.sum(), 1.0)
