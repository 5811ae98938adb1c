"""Full language-model assembly: blocks -> stacks -> train / prefill / decode.

Layer stacking uses *period scanning*: the per-layer block pattern is
factored into the smallest repeating period (dense archs: period ["attn"];
zamba2: 5 x mamba2 + 1 shared-attn site; xlstm: [mlstm, slstm]), the stack is
a ``lax.scan`` over stacked period parameters (bounded HLO size for 61-layer
models), and any non-periodic tail is unrolled.  zamba2's shared attention
block lives *outside* the scanned params and is closed over — weight tying
for free (DESIGN.md §4).

Three entry points per architecture:
  ``loss_fn``      — training forward + CE loss (train_4k cells)
  ``prefill``      — full-sequence forward emitting decode caches (prefill_32k)
  ``decode_step``  — one token against caches (decode_32k / long_500k)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import frontends, layers, moe, ssm, xlstm
from repro.models.common import MeshInfo, Param, cast_for_compute, split_params


# ---------------------------------------------------------------------------
# Pattern factoring
# ---------------------------------------------------------------------------


def factor_pattern(pattern: tuple) -> tuple[tuple, int, tuple]:
    """pattern -> (period, n_periods, tail). Chooses the smallest period that
    covers a maximal prefix of the pattern."""
    n = len(pattern)
    for plen in range(1, n + 1):
        period = pattern[:plen]
        k = n // plen
        if k >= 1 and tuple(period * k) == pattern[:plen * k]:
            tail = pattern[plen * k:]
            # accept only if tail shorter than one period
            if len(tail) < plen:
                return tuple(period), k, tuple(tail)
    return tuple(pattern), 1, ()


# ---------------------------------------------------------------------------
# Single blocks (norm + mixer (+ mlp)), init / apply / prefill / decode
# ---------------------------------------------------------------------------


def _init_block(key, kind: str, cfg, mesh, dtype):
    ks = jax.random.split(key, 4)
    if kind in ("attn", "shared_attn"):
        p = {"norm1": layers.init_norm(cfg, mesh, dtype),
             "attn": attn.init_attention(ks[0], cfg, mesh, dtype)}
        if cfg.d_ff:
            p["norm2"] = layers.init_norm(cfg, mesh, dtype)
            p["mlp"] = layers.init_mlp(ks[1], cfg, mesh, dtype)
        return p
    if kind == "moe":
        return {"norm1": layers.init_norm(cfg, mesh, dtype),
                "attn": attn.init_attention(ks[0], cfg, mesh, dtype),
                "norm2": layers.init_norm(cfg, mesh, dtype),
                "moe": moe.init_moe(ks[1], cfg, mesh, dtype)}
    if kind == "mamba2":
        return {"norm1": layers.init_norm(cfg, mesh, dtype),
                "mamba": ssm.init_mamba2(ks[0], cfg, mesh, dtype)}
    if kind == "mlstm":
        return {"norm1": layers.init_norm(cfg, mesh, dtype),
                "mlstm": xlstm.init_mlstm(ks[0], cfg, mesh, dtype)}
    if kind == "slstm":
        return {"norm1": layers.init_norm(cfg, mesh, dtype),
                "slstm": xlstm.init_slstm(ks[0], cfg, mesh, dtype)}
    raise ValueError(kind)


def _residual(x, branch, cfg):
    """x plus an attention, MLP or MoE branch times
    ``cfg.residual_multiplier`` (nothing applied where it is 1)."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * cfg.residual_multiplier
    return x + branch


def _apply_block(params, kind: str, x, cfg, mesh, *, prefix_len=0):
    """Training/prefill-forward; returns (x, aux_loss, cache_out)."""
    aux = 0.0
    cache = None
    if kind in ("attn", "shared_attn", "moe"):
        h = layers.apply_norm(params["norm1"], x, cfg)
        b, s, _ = h.shape
        with jax.named_scope("attention"):
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            q, k, v = attn._project_qkv(params["attn"], h, cfg, positions)
            n_rep = q.shape[2] // k.shape[2]
            out = attn.blockwise_attention(
                q, attn._repeat_kv(k, n_rep), attn._repeat_kv(v, n_rep),
                chunk=cfg.attn_chunk, causal=True, prefix_len=prefix_len,
                scale=cfg.attention_multiplier)
            out = jnp.einsum("bshe,hed->bsd", out, params["attn"]["wo"])
        x = _residual(x, out, cfg)
        cache = {"k": k, "v": v}
        if kind == "moe":
            h2 = layers.apply_norm(params["norm2"], x, cfg)
            with jax.named_scope("moe"):
                if moe.ep_applicable(cfg, mesh, h2.shape[1]):
                    y, aux = moe.apply_moe_ep(params["moe"], h2, cfg, mesh)
                else:
                    y, aux = moe.apply_moe(params["moe"], h2, cfg, mesh)
            x = _residual(x, y, cfg)
        elif cfg.d_ff:
            h2 = layers.apply_norm(params["norm2"], x, cfg)
            with jax.named_scope("mlp"):
                y = layers.apply_mlp(params["mlp"], h2, cfg)
            x = _residual(x, y, cfg)
        return x, aux, cache
    if kind == "mamba2":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, h_last, conv_tail = ssm.apply_mamba2(params["mamba"], h, cfg)
        return x + y, aux, {"h": h_last, "conv": conv_tail}
    if kind == "mlstm":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, h_last, conv_tail = xlstm.apply_mlstm(params["mlstm"], h, cfg)
        return x + y, aux, {"h": h_last, "conv": conv_tail}
    if kind == "slstm":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, (hs, cs, ns) = xlstm.apply_slstm(params["slstm"], h, cfg)
        return x + y, aux, {"h": hs, "c": cs, "n": ns}
    raise ValueError(kind)


def _decode_block(params, kind: str, cache, x, cfg, mesh, *, pos):
    if kind in ("attn", "shared_attn", "moe"):
        h = layers.apply_norm(params["norm1"], x, cfg)
        with jax.named_scope("attention"):
            out, cache = attn.decode_attention(params["attn"], cache, h, cfg,
                                               mesh, pos=pos)
        x = _residual(x, out, cfg)
        if kind == "moe":
            h2 = layers.apply_norm(params["norm2"], x, cfg)
            with jax.named_scope("moe"):
                y, _ = moe.apply_moe(params["moe"], h2, cfg, mesh)
            x = _residual(x, y, cfg)
        elif cfg.d_ff:
            h2 = layers.apply_norm(params["norm2"], x, cfg)
            with jax.named_scope("mlp"):
                y = layers.apply_mlp(params["mlp"], h2, cfg)
            x = _residual(x, y, cfg)
        return x, cache
    if kind == "mamba2":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, cache = ssm.decode_mamba2(params["mamba"], cache, h, cfg)
        return x + y, cache
    if kind == "mlstm":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, cache = xlstm.decode_mlstm(params["mlstm"], cache, h, cfg)
        return x + y, cache
    if kind == "slstm":
        h = layers.apply_norm(params["norm1"], x, cfg)
        y, cache = xlstm.decode_slstm(params["slstm"], cache, h, cfg)
        return x + y, cache
    raise ValueError(kind)


def _init_block_cache(kind: str, cfg, mesh, batch: int, max_len: int, dtype,
                      seq_shard: bool, batch_shard: bool = True):
    if kind in ("attn", "shared_attn", "moe"):
        return attn.init_kv_cache(cfg, mesh, batch, max_len, dtype,
                                  seq_shard=seq_shard,
                                  batch_shard=batch_shard)
    if kind == "mamba2":
        return ssm.init_mamba2_cache(cfg, mesh, batch, dtype,
                                     batch_shard=batch_shard)
    if kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, mesh, batch, dtype,
                                      batch_shard=batch_shard)
    if kind == "slstm":
        return xlstm.init_slstm_cache(cfg, mesh, batch, dtype,
                                      batch_shard=batch_shard)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LM:
    cfg: ModelConfig
    mesh: MeshInfo
    # unroll=True replaces the layer-period lax.scan with a Python loop —
    # used by the roofline probes (XLA cost_analysis counts a while-loop
    # body once regardless of trip count; see launch/roofline_probe.py).
    unroll: bool = False

    # -- init ---------------------------------------------------------------
    def init(self, key) -> dict:
        cfg, mesh = self.cfg, self.mesh
        dtype = jnp.dtype(cfg.param_dtype)
        period, k, tail = factor_pattern(cfg.block_pattern)
        keys = jax.random.split(key, 4 + k * len(period) + len(tail))
        p: dict[str, Any] = {
            "embed": layers.init_embedding(keys[0], cfg, mesh, dtype),
            "final_norm": layers.init_norm(cfg, mesh, dtype),
            "frontend": frontends.init_frontend(keys[1], cfg, mesh, dtype),
        }
        if cfg.shared_block:
            p["shared"] = _init_block(keys[2], "attn", cfg, mesh, dtype)

        def period_params(i):
            out = {}
            for j, kind in enumerate(period):
                if kind == "shared_attn" and cfg.shared_block:
                    continue  # tied weights live in p["shared"]
                out[f"b{j}_{kind}"] = _init_block(
                    keys[4 + i * len(period) + j], kind, cfg, mesh, dtype)
            return out

        if k > 0 and period:
            per = [period_params(i) for i in range(k)]

            # stack Param leaves: value -> stacked, spec -> (None, *spec)
            def stack_params(*ps):
                vals = jnp.stack([q.value for q in ps])
                spec = P(*((None,) + tuple(ps[0].spec)))
                return Param(vals, spec)

            p["stack"] = jax.tree.map(
                stack_params, *per,
                is_leaf=lambda x: isinstance(x, Param))
        p["tail"] = {
            f"t{j}_{kind}": _init_block(keys[3 + k * len(period) + j], kind,
                                        cfg, mesh, dtype)
            for j, kind in enumerate(tail)
        }
        return p

    # -- shared helpers -------------------------------------------------------
    def _embed_inputs(self, params, batch) -> tuple[jax.Array, int]:
        """Returns (x (B,S,D), prefix_len)."""
        cfg = self.cfg
        parts = []
        prefix_len = 0
        if cfg.frontend == "vision_stub":
            patches = frontends.apply_frontend(params["frontend"],
                                               batch["patches"], cfg)
            parts.append(patches)
            prefix_len = patches.shape[1]
        if cfg.frontend == "audio_stub":
            frames = frontends.apply_frontend(params["frontend"],
                                              batch["frames"], cfg)
            parts.append(frames)
        if "tokens" in batch:
            parts.append(layers.embed_tokens(params["embed"],
                                             batch["tokens"], cfg))
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        return x.astype(jnp.dtype(cfg.compute_dtype)), prefix_len

    def _run_stack(self, params, x, *, prefix_len: int, want_caches: bool,
                   remat: bool):
        """Forward through periods + tail; returns (x, aux, caches|None)."""
        cfg, mesh = self.cfg, self.mesh
        period, k, tail = factor_pattern(cfg.block_pattern)

        def period_body(x, pparams):
            aux_p = 0.0
            caches = {}
            for j, kind in enumerate(period):
                if kind == "shared_attn" and cfg.shared_block:
                    bp = params["shared"]
                else:
                    bp = pparams[f"b{j}_{kind}"]
                x, aux, cache = _apply_block(bp, kind, x, cfg, mesh,
                                             prefix_len=prefix_len)
                aux_p = aux_p + aux
                if want_caches:
                    caches[f"b{j}_{kind}"] = cache
            return x, aux_p, caches

        if remat == "dots":
            period_body = jax.checkpoint(
                period_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif remat:  # "block" / True: full recompute
            period_body = jax.checkpoint(period_body)

        aux_total = 0.0
        caches_out: dict[str, Any] = {}
        if k > 0 and period:
            stack_vals = params["stack"]
            if self.unroll:
                percaches = []
                for i in range(k):
                    pparams = jax.tree.map(lambda v: v[i], stack_vals)
                    x, aux_p, caches = period_body(x, pparams)
                    aux_total = aux_total + aux_p
                    percaches.append(caches)
                if want_caches:
                    caches_out["stack"] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *percaches)
            else:
                def scan_body(x, pparams):
                    x, aux_p, caches = period_body(x, pparams)
                    return x, (aux_p, caches)

                x, (aux_periods, period_caches) = jax.lax.scan(
                    scan_body, x, stack_vals)
                aux_total = aux_total + jnp.sum(aux_periods)
                if want_caches:
                    caches_out["stack"] = period_caches  # leading axis = period
        if want_caches:
            caches_out.setdefault("tail", {})
        for j, kind in enumerate(tail):
            x, aux, cache = _apply_block(params["tail"][f"t{j}_{kind}"], kind,
                                         x, cfg, mesh, prefix_len=prefix_len)
            aux_total = aux_total + aux
            if want_caches:
                caches_out["tail"][f"t{j}_{kind}"] = cache
        return x, aux_total, (caches_out if want_caches else None)

    # -- training -------------------------------------------------------------
    def logits(self, params, batch, *, remat=False):
        """Full-sequence forward: (logits (B, S, V) over the token
        positions, aux loss)."""
        cfg = self.cfg
        with jax.named_scope("cast_weights"):
            params = cast_for_compute(params, jnp.dtype(cfg.compute_dtype))
        x, prefix_len = self._embed_inputs(params, batch)
        x, aux, _ = self._run_stack(params, x, prefix_len=prefix_len,
                                    want_caches=False, remat=remat)
        x = layers.apply_norm(params["final_norm"], x, cfg)
        if prefix_len:
            x = x[:, prefix_len:]
        return layers.logits_head(params["embed"], x, cfg), aux

    def loss_fn(self, params, batch, *, remat="block"):
        cfg = self.cfg
        logits, aux = self.logits(params, batch, remat=remat)
        loss = layers.cross_entropy(logits, batch["labels"], cfg.vocab_size,
                                    mask=batch.get("loss_mask"))
        return loss + aux, {"ce_loss": loss, "aux_loss": aux}

    # -- serving: prefill -------------------------------------------------------
    def prefill(self, params, batch):
        """Full-sequence forward; returns (last_logits, caches)."""
        cfg = self.cfg
        with jax.named_scope("cast_weights"):
            params = cast_for_compute(params, jnp.dtype(cfg.compute_dtype))
        x, prefix_len = self._embed_inputs(params, batch)
        x, _, caches = self._run_stack(params, x, prefix_len=prefix_len,
                                       want_caches=True, remat=False)
        x = layers.apply_norm(params["final_norm"], x, cfg)
        logits = layers.logits_head(params["embed"], x[:, -1:], cfg)
        return logits[:, 0], caches

    # -- serving: decode ---------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *, seq_shard: bool = False,
                   batch_shard: bool = True):
        cfg, mesh = self.cfg, self.mesh
        dtype = jnp.dtype(cfg.compute_dtype)
        period, k, tail = factor_pattern(cfg.block_pattern)
        out: dict[str, Any] = {}
        if k > 0 and period:
            def one_period():
                return {f"b{j}_{kind}": _init_block_cache(
                    kind, cfg, mesh, batch, max_len, dtype, seq_shard,
                    batch_shard)
                    for j, kind in enumerate(period)}
            per = [one_period() for _ in range(k)]

            def stack_caches(*cs):
                vals = jnp.stack([c.value for c in cs])
                spec = P(*((None,) + tuple(cs[0].spec)))
                return Param(vals, spec)
            out["stack"] = jax.tree.map(stack_caches, *per,
                                        is_leaf=lambda x: isinstance(x, Param))
        out["tail"] = {f"t{j}_{kind}": _init_block_cache(
            kind, cfg, mesh, batch, max_len, dtype, seq_shard, batch_shard)
            for j, kind in enumerate(tail)}
        return out

    def decode_step(self, params, caches, token, pos):
        """token: (B, 1) int32 (or (B,1,D) frames for audio); pos: scalar.
        Returns (logits (B, V), new caches)."""
        cfg, mesh = self.cfg, self.mesh
        with jax.named_scope("cast_weights"):
            params = cast_for_compute(params, jnp.dtype(cfg.compute_dtype))
        period, k, tail = factor_pattern(cfg.block_pattern)
        if token.ndim == 3:  # audio frames passthrough
            x = frontends.apply_frontend(params["frontend"], token, cfg)
        else:
            x = layers.embed_tokens(params["embed"], token, cfg)
        x = x.astype(jnp.dtype(cfg.compute_dtype))

        new_caches: dict[str, Any] = {}
        if k > 0 and period:
            def scan_body(x, inp):
                pparams, pcache = inp
                new_c = {}
                for j, kind in enumerate(period):
                    bp = (params["shared"] if kind == "shared_attn"
                          and cfg.shared_block else pparams[f"b{j}_{kind}"])
                    x, c = _decode_block(bp, kind, pcache[f"b{j}_{kind}"], x,
                                         cfg, mesh, pos=pos)
                    new_c[f"b{j}_{kind}"] = c
                return x, new_c

            if self.unroll:
                outs = []
                for i in range(k):
                    inp = jax.tree.map(lambda v: v[i],
                                       (params["stack"], caches["stack"]))
                    x, new_c = scan_body(x, inp)
                    outs.append(new_c)
                stacked_new = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            else:
                x, stacked_new = jax.lax.scan(
                    scan_body, x, (params["stack"], caches["stack"]))
            new_caches["stack"] = stacked_new
        new_caches["tail"] = {}
        for j, kind in enumerate(tail):
            x, c = _decode_block(params["tail"][f"t{j}_{kind}"], kind,
                                 caches["tail"][f"t{j}_{kind}"], x, cfg, mesh,
                                 pos=pos)
            new_caches["tail"][f"t{j}_{kind}"] = c
        x = layers.apply_norm(params["final_norm"], x, cfg)
        logits = layers.logits_head(params["embed"], x, cfg)
        return logits[:, 0], new_caches
