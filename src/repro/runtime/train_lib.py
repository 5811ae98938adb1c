"""Training-step builder: pjit-able (params, opt_state, batch) -> updated.

Features (DESIGN.md §5):
* microbatch gradient accumulation (``ParallelConfig.microbatches``) via
  ``lax.scan`` — shrinks activation memory and collective payload bursts;
* remat per layer-period (``ParallelConfig.remat``);
* optional int8 error-feedback gradient compression on the cross-pod axis
  (``grad_compression='int8_ef'``) via ``shard_map`` around the grad sync;
* DP gradient reduction otherwise implicit in the sharded backward pass.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.models.model import LM
from repro.optim import (
    AdamWConfig,
    adamw_update,
    init_opt_state,
    lr_schedule,
    opt_state_specs,
)
from repro.optim.compression import compress_tree, decompress_tree, init_error_buffer


def make_adamw_config(cfg: ModelConfig, tcfg: TrainConfig) -> AdamWConfig:
    return AdamWConfig(b1=tcfg.b1, b2=tcfg.b2,
                       weight_decay=tcfg.weight_decay,
                       grad_clip=tcfg.grad_clip,
                       moment_dtype=cfg.opt_state_dtype)


def _split_microbatches(batch, k: int):
    def split(x):
        b = x.shape[0]
        assert b % k == 0, (b, k)
        return x.reshape(k, b // k, *x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(lm: LM, tcfg: TrainConfig, pcfg: ParallelConfig
                    ) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  With ``pcfg.grad_compression == "int8_ef"`` the opt state must
    carry an error buffer (see ``init_train_state``)."""
    ocfg = make_adamw_config(lm.cfg, tcfg)
    remat = False if pcfg.remat == "none" else pcfg.remat

    def loss_fn(params, mb):
        loss, metrics = lm.loss_fn(params, mb, remat=remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if pcfg.microbatches > 1:
            mbs = _split_microbatches(batch, pcfg.microbatches)

            def acc_step(carry, mb):
                g_acc, l_acc = carry
                (loss, _), grads = grad_fn(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss_sum), _ = jax.lax.scan(acc_step, (g0, 0.0), mbs)
            k = float(pcfg.microbatches)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss = loss_sum / k
            metrics = {}
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        if pcfg.grad_compression == "int8_ef":
            # int8 + error feedback applied to the synchronised gradient.
            # (On hardware the quantisation rides the cross-pod all-reduce —
            # optim/compression.psum_compressed inside shard_map; numerically
            # the round-trip below is the same signal the optimizer sees.)
            qtree, ebuf = compress_tree(grads, opt_state["err"])
            grads = decompress_tree(qtree, grads)
        lr = lr_schedule(opt_state["step"], base_lr=tcfg.lr,
                         warmup=tcfg.warmup_steps, total=tcfg.total_steps)
        new_params, new_opt, om = adamw_update(grads, opt_state, params, lr,
                                               ocfg)
        if pcfg.grad_compression == "int8_ef":
            new_opt["err"] = ebuf
        out_metrics = {"loss": loss, "lr": lr, **om}
        for k_, v in (metrics or {}).items():
            out_metrics[k_] = v
        return new_params, new_opt, out_metrics

    return train_step


def init_train_state(lm: LM, tcfg: TrainConfig, key,
                     pcfg: ParallelConfig | None = None, mesh=None):
    """(param values, param specs, opt state, opt specs).

    With ``mesh`` every array is created already sharded by its spec, so
    the state is never whole on one device — at published widths it may
    not fit one (qwen2-1.5b's f32 params + AdamW moments take ~18.6 GB)."""
    from repro.models.common import split_params
    from repro.runtime.sharding import shardings_for
    _, specs = split_params(jax.eval_shape(lm.init, key))
    ospecs = opt_state_specs(specs)
    init_params = lambda k: split_params(lm.init(k))[0]  # noqa: E731
    init_opt = functools.partial(init_opt_state,
                                 cfg=make_adamw_config(lm.cfg, tcfg))
    if mesh is not None:
        init_params = jax.jit(init_params,
                              out_shardings=shardings_for(mesh, specs))
        init_opt = jax.jit(init_opt, out_shardings=shardings_for(mesh, ospecs))
    values = init_params(key)
    opt = init_opt(values)
    if pcfg is not None and pcfg.grad_compression == "int8_ef":
        opt["err"] = init_error_buffer(values)
        ospecs = dict(ospecs)
        ospecs["err"] = specs
    return values, specs, opt, ospecs


def abstract_train_state(lm: LM, tcfg: TrainConfig, key):
    """ShapeDtypeStruct state + spec trees — the dry-run path (Param is a
    registered pytree with the spec as static aux, so eval_shape returns
    abstract values *and* concrete PartitionSpecs with no allocation)."""
    from repro.models.common import split_params

    tree = jax.eval_shape(lm.init, key)
    values, specs = split_params(tree)
    ocfg = make_adamw_config(lm.cfg, tcfg)
    opt = jax.eval_shape(functools.partial(init_opt_state, cfg=ocfg), values)
    ospecs = opt_state_specs(specs)
    return values, specs, opt, ospecs
