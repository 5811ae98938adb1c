"""Pipeline-parallel correctness: fwd + grads == sequential stack."""
import os

# 8 placeholder devices BEFORE jax init (this file must run in its own
# process group when mixed with single-device tests; pytest-forked not
# available, so we guard on device count instead).
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.runtime.pipeline_parallel import pipeline_apply, split_stages

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 host devices")


def _setup(n_layers=8, d=16, n_micro=4, mb=2, seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w": jnp.array(rng.normal(size=(n_layers, d, d)) * 0.2, jnp.float32),
        "b": jnp.array(rng.normal(size=(n_layers, d)) * 0.1, jnp.float32),
    }
    x = jnp.array(rng.normal(size=(n_micro, mb, d)), jnp.float32)
    return params, x


def _block(params, x):
    # one stage = a chunk of layers applied sequentially
    def layer(x, wl):
        return jnp.tanh(x @ wl[0] + wl[1]), None
    y, _ = jax.lax.scan(layer, x, (params["w"], params["b"]))
    return y


def _sequential(params, x_micro):
    def one(x):
        def layer(x, wl):
            return jnp.tanh(x @ wl[0] + wl[1]), None
        y, _ = jax.lax.scan(layer, x, (params["w"], params["b"]))
        return y
    return jax.vmap(one)(x_micro)


def test_pipeline_forward_matches_sequential():
    n_stages = 4
    mesh = make_mesh((n_stages,), ("pod",))
    params, x = _setup()
    staged = split_stages(params, n_stages)
    got = pipeline_apply(_block, staged, x, mesh=mesh, axis="pod")
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_grads_match_sequential():
    n_stages = 4
    mesh = make_mesh((n_stages,), ("pod",))
    params, x = _setup()

    def loss_pipe(p):
        staged = split_stages(p, n_stages)
        y = pipeline_apply(_block, staged, x, mesh=mesh, axis="pod")
        return jnp.sum(jnp.square(y))

    def loss_seq(p):
        return jnp.sum(jnp.square(_sequential(p, x)))

    g1 = jax.grad(loss_pipe)(params)
    g2 = jax.grad(loss_seq)(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-4, atol=1e-5), k


def test_pipeline_two_stages():
    mesh = make_mesh((2,), ("pod",))
    params, x = _setup(n_layers=6, n_micro=3)
    staged = split_stages(params, 2)
    got = pipeline_apply(_block, staged, x, mesh=mesh, axis="pod")
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
