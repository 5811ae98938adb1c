"""Multi-device distribution tests (run with
XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, input_specs
from repro.configs.base import SHAPES
from repro.models.common import MeshInfo, split_params
from repro.models.moe import (
    apply_moe,
    apply_moe_ep,
    ep_applicable,
    init_moe,
    padded_experts,
)
from repro.launch.mesh import make_host_mesh
from repro.runtime.sharding import batch_specs, mesh_info, use_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >=8 host devices")


def _mesh24():
    return make_host_mesh(2, 4)


def test_moe_ep_matches_baseline_exactly():
    """The shard_map EP path computes the same function as the pjit path
    (generous capacity so neither drops tokens)."""
    mesh = _mesh24()
    minfo = MeshInfo(data=2, model=4, data_axes=("data",))
    cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b", smoke=True),
                              capacity_factor=64.0)
    values, _ = split_params(init_moe(jax.random.key(0), cfg, minfo,
                                      jnp.float32))
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                          jnp.float32)
    assert ep_applicable(cfg, minfo, 16)
    with use_mesh(mesh):
        y1, _ = jax.jit(lambda v, x: apply_moe(v, x, cfg, minfo))(values, x)
        y2, _ = jax.jit(lambda v, x: apply_moe_ep(v, x, cfg, minfo))(values, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


def test_moe_ep_grads_match_baseline():
    mesh = _mesh24()
    minfo = MeshInfo(data=2, model=4, data_axes=("data",))
    cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b", smoke=True),
                              capacity_factor=64.0)
    values, _ = split_params(init_moe(jax.random.key(0), cfg, minfo,
                                      jnp.float32))
    x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                          jnp.float32)

    def loss(fn, v):
        y, aux = fn(v, x, cfg, minfo)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    with use_mesh(mesh):
        g1 = jax.jit(jax.grad(lambda v: loss(apply_moe, v)))(values)
        g2 = jax.jit(jax.grad(lambda v: loss(apply_moe_ep, v)))(values)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_expert_padding_exact():
    """Padding 5 experts -> 8 on a 4-way axis must not change outputs
    (dead experts masked to -inf in the router)."""
    minfo_pad = MeshInfo(data=2, model=4, data_axes=("data",))
    minfo_host = MeshInfo(data=1, model=1)
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m", smoke=True),
                              capacity_factor=64.0)
    assert padded_experts(cfg, minfo_pad) == 8 and cfg.n_experts == 5
    v_pad, _ = split_params(init_moe(jax.random.key(7), cfg, minfo_pad,
                                     jnp.float32))
    v_host, _ = split_params(init_moe(jax.random.key(7), cfg, minfo_host,
                                      jnp.float32))
    # same logical weights: padded arrays extend the expert dim
    np.testing.assert_allclose(np.asarray(v_pad["w_up"][:5]),
                               np.asarray(v_host["w_up"]))
    x = jax.random.normal(jax.random.key(2), (2, 8, cfg.d_model), jnp.float32)
    y_host, _ = apply_moe(v_host, x, cfg, None)
    mesh = _mesh24()
    with use_mesh(mesh):
        y_pad, _ = jax.jit(lambda v, x: apply_moe(v, x, cfg, minfo_pad)
                           )(v_pad, x)
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_host),
                               rtol=1e-5, atol=1e-5)


def test_batch_specs_long_500k_replicates_batch():
    cfg = get_config("zamba2-1.2b")
    minfo = MeshInfo(data=16, model=16, data_axes=("data",))
    specs = batch_specs(cfg, SHAPES["long_500k"], minfo)
    assert specs["token"] == P(None, None)      # batch=1: no DP sharding
    specs4k = batch_specs(cfg, SHAPES["train_4k"], minfo)
    assert specs4k["tokens"] == P("data", None)


def test_mesh_info_from_mesh():
    mesh = _mesh24()
    mi = mesh_info(mesh, fsdp=True)
    assert mi.data == 2 and mi.model == 4 and mi.fsdp
    assert mi.data_axes == ("data",)


def test_sharded_train_step_runs():
    """A real sharded train step on the 2x4 mesh executes and improves."""
    from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
    from repro.data import make_batch
    from repro.models.model import LM
    from repro.runtime.train_lib import init_train_state, make_train_step

    mesh = _mesh24()
    minfo = mesh_info(mesh, fsdp=True)
    cfg = get_config("qwen2-7b", smoke=True)     # 6 heads -> padded to 8
    lm = LM(cfg, minfo)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=10)
    shape = ShapeConfig("t", "train", 32, 8)
    with use_mesh(mesh):
        params, pspecs, opt, ospecs = init_train_state(
            lm, tcfg, jax.random.key(0), mesh=mesh)
        step = jax.jit(make_train_step(lm, tcfg, ParallelConfig(fsdp=True)))
        losses = []
        for i in range(8):
            batch = make_batch(cfg, shape, i, seed=4)
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
    # the state was created sharded: an FSDP+model-sharded leaf spans the
    # whole mesh
    w = params["stack"]["b0_attn"]["mlp"]["w_up"]
    assert len(w.sharding.device_set) == 8


def test_default_execute_backend_under_a_mesh():
    """On a TPU the planned GEMM runs the Pallas kernel, except under an
    ambient mesh of more than one device: the SPMD partitioner cannot split
    a Pallas kernel, so the sharded path runs the jnp reference."""
    from unittest import mock
    from repro import gemm
    assert gemm.default_execute_backend() == "reference"      # the CPU
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        assert gemm.default_execute_backend() == "pallas"
        with use_mesh(make_host_mesh(1, 1)):
            assert gemm.default_execute_backend() == "pallas"
        with use_mesh(_mesh24()):
            assert gemm.default_execute_backend() == "reference"
