"""The plain reference: a full-sequence forward in float32 ``jax.numpy``,
with no kernels, no cache and no batching, written from the published
description of the block and importing nothing of the program.

Block (Qwen2 / Granite MoE as run): token embedding times
``embedding_multiplier``; per layer, pre-RMSNorm GQA attention with rotary
embeddings (rotate-half, base ``rope_theta``), optional QKV bias and scale
``attention_multiplier``, then pre-RMSNorm SiLU-gated MLP, or a top-k
mixture of SiLU-gated experts (softmax over all experts, the top k
renormalised, no token dropped); both branches added to the residual times
``residual_multiplier``; final RMSNorm and the tied head over the real
vocabulary, divided by ``logits_scaling``.

Every product runs at ``Precision.HIGHEST``: on a TPU a float32 matmul is
otherwise computed in bf16 passes.

``quantize`` gives the control: the same forward with every weight matrix
rounded to float8 (e4m3, one scale per tensor and layer), the precision
below the bf16 that the configurations serve in.

A configuration runs against the reference module its file names
(``"reference": "<module>"``, ``lib/<module>.py``; this one by default).
Such a module owns what is particular to the block it runs:

- ``logits_at(weights, spec, tokens, rows, quant=)``, the forward;
- its weight table: ``shapes(spec, vocab_rows)``, ``init(spec, name,
  shape)`` (the mean and the standard deviation of each weight's normal
  draw) and ``PROGRAM_LEAF``, the program's parameter of each weight;
- ``PROGRAM_FIELD``, the published keys it runs beyond ``lib.spec``'s,
  each with the program field that runs it;
- ``program_config(spec, base, sets)``, the program's configuration with
  ``sets`` applied, refusing a block that the module cannot run.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0

#: no published keys beyond ``lib.spec.PROGRAM_FIELD``
PROGRAM_FIELD = {}
#: (parent key, leaf key) of a program parameter -> the weight's name here
PROGRAM_LEAF = {
    ("embed", "table"): "embed",
    ("final_norm", "scale"): "final_norm",
    ("norm1", "scale"): "ln1",
    ("norm2", "scale"): "ln2",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("attn", "bq"): "bq", ("attn", "bk"): "bk",
    ("attn", "bv"): "bv",
    ("mlp", "w_gate"): "mlp_gate", ("mlp", "w_up"): "mlp_up",
    ("mlp", "w_down"): "mlp_down",
    ("moe", "router"): "router", ("moe", "w_gate"): "expert_gate",
    ("moe", "w_up"): "expert_up", ("moe", "w_down"): "expert_down",
}


def program_config(spec, base, sets: dict):
    """``base`` (the program's registered configuration) with ``sets`` and
    a uniform block pattern at the file's depth; refuses what this module
    does not run: a mixed block pattern, or a block that is not the
    RMSNorm, SiLU-gated, text-only one."""
    kind = base.block_pattern[0]
    if set(base.block_pattern) != {kind}:
        raise ValueError(f"{spec.name}: only a uniform block pattern "
                         f"can be run at another depth")
    cfg = dataclasses.replace(
        base, block_pattern=(kind,) * sets["n_layers"], **sets)
    if (spec.data.get("hidden_act", "silu") != "silu"
            or cfg.act != "swiglu" or cfg.norm_type != "rmsnorm"
            or cfg.logit_softcap or cfg.frontend != "none"):
        raise ValueError(f"{spec.name}: the reference runs the "
                         f"RMSNorm, SiLU-gated, text-only block only")
    return cfg


def shapes(spec, vocab_rows: int) -> dict[str, tuple]:
    """The weight shapes for ``spec``; the embedding holds ``vocab_rows >=
    vocab_size`` rows (the rows past the vocabulary are never looked up
    and never ranked)."""
    L, d = spec.num_hidden_layers, spec.hidden_size
    h, hkv, hd = (spec.num_attention_heads, spec.num_key_value_heads,
                  spec.head_dim)
    f = spec.intermediate_size
    out = {"embed": (vocab_rows, d), "final_norm": (d,),
           "ln1": (L, d), "ln2": (L, d),
           "wq": (L, d, h, hd), "wk": (L, d, hkv, hd), "wv": (L, d, hkv, hd),
           "wo": (L, h, hd, d)}
    if spec.data.get("attention_bias"):
        out.update(bq=(L, h, hd), bk=(L, hkv, hd), bv=(L, hkv, hd))
    if spec.is_moe:
        e = spec.num_local_experts
        out.update(router=(L, d, e), expert_gate=(L, e, d, f),
                   expert_up=(L, e, d, f), expert_down=(L, e, f, d))
    else:
        out.update(mlp_gate=(L, d, f), mlp_up=(L, d, f), mlp_down=(L, f, d))
    return out


def init(spec, name: str, shape: tuple) -> tuple[float, float]:
    """(mean, standard deviation) of the weight ``name``'s normal draw.
    Norm scales about 1; fan-in scaled matrices; the embedding at the
    published ``initializer_range``: with the head tied, an embedding of
    unit scale makes every position's top logit its own input token, and
    greedy decoding then repeats it whatever the arithmetic."""
    if name in ("ln1", "ln2", "final_norm"):
        return 1.0, 0.1
    if name == "embed":
        return 0.0, spec.initializer_range
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if name in ("wq", "wk", "wv"):           # (L, d, heads, head_dim)
        fan_in = shape[-3]
    elif name == "wo":                       # (L, heads, head_dim, d)
        fan_in = shape[-3] * shape[-2]
    else:                                    # (..., fan_in, fan_out)
        fan_in = shape[-2]
    return 0.0, fan_in ** -0.5


def fp8_round(w):
    """``w`` through float8 e4m3 with one scale for the whole array."""
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / FP8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, hd) rotated by position, halves (rotate-half)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lw, h, spec, pos):
    q = jnp.einsum("sd,dhe->she", h, lw["wq"], precision=HI)
    k = jnp.einsum("sd,dhe->she", h, lw["wk"], precision=HI)
    v = jnp.einsum("sd,dhe->she", h, lw["wv"], precision=HI)
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
    group = spec.num_attention_heads // spec.num_key_value_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhe,khe->hqk", q, k, precision=HI)
    s = s * spec.attention_multiplier
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khe->qhe", p, v, precision=HI)
    return jnp.einsum("qhe,hed->qd", o, lw["wo"], precision=HI)


def _swiglu(x, wg, wu, wd):
    g = jnp.einsum("sd,df->sf", x, wg, precision=HI)
    u = jnp.einsum("sd,df->sf", x, wu, precision=HI)
    return jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u, wd, precision=HI)


def _experts(lw, x, spec):
    """Dropless top-k mixture: every expert on every token, weighted by the
    renormalised top-k gate (zero for the others)."""
    logits = jnp.einsum("sd,de->se", x, lw["router"], precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, spec.num_experts_per_tok)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)
    g = jnp.einsum("sd,edf->sef", x, lw["expert_gate"], precision=HI)
    u = jnp.einsum("sd,edf->sef", x, lw["expert_up"], precision=HI)
    y = jnp.einsum("sef,efd->sed", jax.nn.silu(g) * u, lw["expert_down"],
                   precision=HI)
    return jnp.einsum("se,sed->sd", gates, y, precision=HI)


def _layer(spec, quant, x, lw, pos):
    if quant:
        lw = {k: (fp8_round(v) if v.ndim >= 2 else v)
              for k, v in lw.items()}
    eps, res = spec.rms_norm_eps, spec.multiplier("residual_multiplier")
    h = _rms(x, lw["ln1"], eps)
    x = x + res * _attention(lw, h, spec, pos)
    h = _rms(x, lw["ln2"], eps)
    if spec.is_moe:
        y = _experts(lw, h, spec)
    else:
        y = _swiglu(h, lw["mlp_gate"], lw["mlp_up"], lw["mlp_down"])
    return x + res * y


@functools.lru_cache(maxsize=None)
def _forward(spec, quant: bool):
    """jitted (weights, tokens (S,), rows (G,)) -> logits (G, vocab) at
    the positions ``rows``; one compile per (S, G)."""

    def fwd(w, tokens, rows):
        emb = w["embed"][:spec.vocab_size]
        if quant:
            emb = fp8_round(emb)
        x = emb[tokens] * spec.multiplier("embedding_multiplier")
        pos = jnp.arange(tokens.shape[0])
        per_layer = {k: v for k, v in w.items()
                     if k not in ("embed", "final_norm")}
        x, _ = jax.lax.scan(lambda x, lw: (_layer(spec, quant, x, lw, pos),
                                           None), x, per_layer)
        x = _rms(x[rows], w["final_norm"], spec.rms_norm_eps)
        logits = jnp.einsum("gd,vd->gv", x, emb, precision=HI)
        return logits / spec.multiplier("logits_scaling")

    return jax.jit(fwd)


def logits_at(weights: dict, spec, tokens, rows, *, quant: bool = False):
    """Reference logits (G, vocab) at positions ``rows`` of ``tokens``."""
    return _forward(spec, quant)(weights, jnp.asarray(tokens, jnp.int32),
                                 jnp.asarray(rows, jnp.int32))
