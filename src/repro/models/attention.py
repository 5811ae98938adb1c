"""GQA attention with RoPE, prefix-LM masks, KV caches and long-context decode.

Scalability decisions (DESIGN.md §5):

* **Head padding for TP** — query heads are padded up to a multiple of the
  model axis (qwen2-7b's 28 heads -> 32 on a 16-way axis).  Padded heads have
  zero output-projection rows, so results are exact; the cost is the padded
  fraction of attention FLOPs, far cheaper than replicating attention.
* **KV replication for narrow GQA** — when kv_heads doesn't divide the model
  axis (kv=1..8 vs 16), KV projections/caches replicate across TP, the
  standard Megatron GQA treatment.
* **Blockwise softmax** — the full-sequence path processes KV in chunks with
  a running (max, sum, acc) online softmax, so 32k-token prefill never
  materialises an S x S score matrix.  This is the pure-jnp twin of
  ``kernels/flash_attention.py`` (used on the dry-run path).
* **Sequence-parallel decode** — for ``long_500k`` the KV cache's sequence
  axis is sharded over the data axis; softmax over the sharded axis lowers to
  partial reductions + a tiny all-reduce (flash-decoding's LSE combine, done
  by the SPMD partitioner).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import MeshInfo, Param, dense_init, zeros_init
from repro.models.layers import apply_rope, rope_tables

NEG_INF = -1e30


def head_layout(cfg, mesh: MeshInfo) -> tuple[int, int]:
    """(hq_padded, hkv_padded) for TP.

    * both divisible by the model axis -> no padding;
    * MHA (kv == q heads) -> pad both to the axis multiple;
    * GQA -> replicate KV, pad query heads *per KV group* so the grouping
      ``q_head -> q_head // n_rep`` survives padding (n_rep stays integral).
    Padded positions are zero-initialised in wq/bq/wo (and wk/wv for padded
    KV), so forward results are exactly the unpadded model's.
    """
    tp = mesh.model
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    if hq % tp == 0 and (hkv % tp == 0 or hkv == hq):
        return hq, hkv
    if hkv == hq:                                   # MHA: pad both
        h = tp * math.ceil(hq / tp)
        return h, h
    g = math.gcd(hkv, tp)
    step = tp // g
    r = hq // hkv                                   # reps per KV group
    rp = step * math.ceil(r / step)
    return hkv * rp, hkv


def _scatter_heads(out, w, idx, axis):
    """Place w's head slices at positions ``idx`` along ``axis`` of out."""
    return out.at[(slice(None),) * axis + (idx,)].set(w)


def init_attention(key, cfg, mesh: MeshInfo, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    hq0, hkv0 = cfg.n_heads, cfg.n_kv_heads
    hq, hkv = head_layout(cfg, mesh)
    h_ax = mesh.shard_if(hq)                  # always shardable after padding
    kv_ax = mesh.shard_if(hkv)                # may be None (replicated KV)
    fsdp = mesh.fsdp_if(d)
    ks = jax.random.split(key, 8)

    r0 = hq0 // hkv0
    rp = hq // hkv if hkv else 1

    def pad_q(w, head_axis):
        """w has hq0 logical heads on ``head_axis``; insert zero heads at the
        end of each KV group (and append zero groups if hkv > hkv0)."""
        if hq == hq0:
            return w
        shape = list(w.shape)
        shape[head_axis] = hq
        out = jnp.zeros(shape, w.dtype)
        # grouped layout: logical head (g, i) -> padded index g * rp + i
        idx = (jnp.arange(hq0) // r0) * rp + (jnp.arange(hq0) % r0)
        return _scatter_heads(out, w, idx, head_axis)

    def pad_kv(w, head_axis):
        if hkv == hkv0:
            return w
        shape = list(w.shape)
        shape[head_axis] = hkv
        out = jnp.zeros(shape, w.dtype)
        idx = jnp.arange(hkv0)
        return _scatter_heads(out, w, idx, head_axis)

    wq = dense_init(ks[0], d, (d, hq0, hd), P(fsdp, h_ax, None), dtype)
    wk = dense_init(ks[1], d, (d, hkv0, hd), P(fsdp, kv_ax, None), dtype)
    wv = dense_init(ks[2], d, (d, hkv0, hd), P(fsdp, kv_ax, None), dtype)
    wo = dense_init(ks[3], hq0 * hd, (hq0, hd, d), P(h_ax, None, fsdp), dtype)
    p = {
        "wq": Param(pad_q(wq.value, 1), wq.spec),
        "wk": Param(pad_kv(wk.value, 1), wk.spec),
        "wv": Param(pad_kv(wv.value, 1), wv.spec),
        "wo": Param(pad_q(wo.value, 0), wo.spec),
    }
    if cfg.qkv_bias:
        p["bq"] = Param(jnp.zeros((hq, hd), dtype), P(h_ax, None))
        p["bk"] = Param(jnp.zeros((hkv, hd), dtype), P(kv_ax, None))
        p["bv"] = Param(jnp.zeros((hkv, hd), dtype), P(kv_ax, None))
    return p


def _project_qkv(params, x, cfg, positions):
    """x: (B, S, D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), with RoPE applied."""
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    return q, k, v


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def blockwise_attention(q, k, v, *, chunk: int, causal: bool,
                        scale: float, prefix_len: int = 0,
                        q_offset: int = 0):
    """Online-softmax attention over KV chunks; O(S*chunk) memory.

    q: (B, Sq, H, hd); k, v: (B, Skv, H, hd) (KV already repeated to H).
    ``causal`` masks with query positions offset by ``q_offset``;
    ``prefix_len`` positions attend bidirectionally (prefix-LM / PaliGemma).
    Scores are scaled by ``scale`` (``cfg.attention_multiplier``).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    qf = (q * scale).astype(jnp.float32)
    chunk = min(chunk, skv)
    n_chunks = math.ceil(skv / chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, h, hd).astype(jnp.float32)
    vc = v.reshape(b, n_chunks, chunk, h, hd).astype(jnp.float32)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        m, l, acc = carry
        idx, kb, vb = inputs
        kv_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)
        mask = kv_pos[None, :] < skv                      # padding
        if causal:
            vis = kv_pos[None, :] <= q_pos[:, None]
            if prefix_len:
                vis = vis | (kv_pos[None, :] < prefix_len)
            mask = mask & vis
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)        # (B, Sq, H, hd)


def apply_attention(params, x, cfg, mesh: MeshInfo, *, positions=None,
                    prefix_len: int = 0):
    """Full-sequence (training / prefill) attention.  x: (B, S, D)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, x, cfg, positions)
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    out = blockwise_attention(q, k, v, chunk=cfg.attn_chunk, causal=True,
                              prefix_len=prefix_len,
                              scale=cfg.attention_multiplier)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Decode path (KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, mesh: MeshInfo, batch: int, max_len: int, dtype,
                  seq_shard: bool = False, batch_shard: bool = True):
    """Cache arrays + their specs.  ``seq_shard`` turns on SP for long decode
    (KV sequence axis over the data axis; batch is then unsharded).

    With ``cfg.kv_cache_dtype == "int8"`` the cache stores int8 entries plus
    one f32 scale per (position, head) — 2.2x less HBM read per decoded
    token (the dominant real decode cost; EXPERIMENTS.md §Perf D2)."""
    _, hkv = head_layout(cfg, mesh)
    kv_ax = mesh.shard_if(hkv)
    if seq_shard:
        spec = P(None, mesh.dp(), kv_ax, None)
        sspec = P(None, mesh.dp(), kv_ax)
    else:
        bspec = mesh.dp() if batch_shard else None
        spec = P(bspec, None, kv_ax, None)
        sspec = P(bspec, None, kv_ax)
    shape = (batch, max_len, hkv, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": Param(jnp.zeros(shape, dtype=jnp.int8), spec),
            "v": Param(jnp.zeros(shape, dtype=jnp.int8), spec),
            "k_scale": Param(jnp.zeros(shape[:3], jnp.float32), sspec),
            "v_scale": Param(jnp.zeros(shape[:3], jnp.float32), sspec),
        }
    return {
        "k": Param(jnp.zeros(shape, dtype=dtype), spec),
        "v": Param(jnp.zeros(shape, dtype=dtype), spec),
    }


def _quant_kv(x):
    """x: (..., hd) -> (int8 values, f32 scale over the last dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_attention(params, cache, x, cfg, mesh: MeshInfo, *, pos):
    """One-token decode.  x: (B, 1, D); pos: scalar int32 (current length).
    Scores are scaled by ``cfg.attention_multiplier``.

    Returns (out (B, 1, D), new_cache).  Softmax over the (possibly
    data-sharded) cache sequence axis — XLA's SPMD partitioner lowers the
    max/sum to partial reductions + all-reduce, i.e. flash-decoding's LSE
    combine (DESIGN.md §5).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, dtype=jnp.int32)
    per_slot = pos.ndim == 1                  # continuous batching: (B,) pos
    positions = (pos[:, None] if per_slot
                 else jnp.full((b, 1), pos, dtype=jnp.int32))
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    quant = "k_scale" in cache                # int8 KV cache (D2)
    if quant:
        k_q, k_s = _quant_kv(k_new)           # (B,1,H,hd) int8, (B,1,H) f32
        v_q, v_s = _quant_kv(v_new)
        k_new, v_new = k_q, v_q
    new_cache = {}
    if per_slot:
        idx = jnp.arange(b)
        k_cache = cache["k"].at[idx, pos].set(k_new[:, 0])
        v_cache = cache["v"].at[idx, pos].set(v_new[:, 0])
        if quant:
            new_cache["k_scale"] = cache["k_scale"].at[idx, pos].set(k_s[:, 0])
            new_cache["v_scale"] = cache["v_scale"].at[idx, pos].set(v_s[:, 0])
    else:
        # scalar path: dynamic_update_slice stays partitioner-friendly for
        # the seq-sharded long_500k cache.
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, pos,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, pos,
                                                      axis=1)
        if quant:
            new_cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], k_s, pos, axis=1)
            new_cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], v_s, pos, axis=1)

    hq = q.shape[2]
    hkv = k_cache.shape[2]
    n_rep = hq // hkv
    skv = k_cache.shape[1]
    scale = cfg.attention_multiplier
    qg = (q * scale).reshape(b, 1, hkv, n_rep, cfg.head_dim
                             ).astype(jnp.float32)
    if quant:
        kf = k_cache.astype(jnp.float32) * new_cache["k_scale"][..., None]
        vf = v_cache.astype(jnp.float32) * new_cache["v_scale"][..., None]
    else:
        kf = k_cache.astype(jnp.float32)
        vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kf)            # (B,Hkv,rep,1,Skv)
    valid = jnp.arange(skv)[None, :] <= positions            # (B, Skv)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, vf)
    out = out.reshape(b, 1, hq, cfg.head_dim).astype(x.dtype)
    out = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    new_cache["k"] = k_cache
    new_cache["v"] = v_cache
    return out, new_cache
