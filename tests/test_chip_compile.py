"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs.  The TPU compiler installed with jax compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse: blocks not aligned to the tiling, a kernel that claims
more VMEM than its limit.  Interpret-mode tests cannot see either.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the worker that runs
this file keeps it until it exits.  Keep every such compile in this one
file.  Code that asks ``jax.default_backend()`` still sees the CPU here, so
the tests steer the Pallas dispatch (``_on_tpu``) themselves.
"""
import importlib
import os
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCH_IDS, get_config
from repro.core.tpu_model import LANE, GridOrder, TileConfig
from repro.gemm import plan, plan_model_gemms

backends = importlib.import_module("repro.gemm.backends")
ops = importlib.import_module("repro.kernels.ops")

_DT = {"bf16": jnp.bfloat16, "int8": jnp.int8, "f32": jnp.float32}
_QWEN = get_config("qwen2-1.5b")
_GRANITE = get_config("granite-moe-3b-a800m")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a described device is written but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_plan(one_chip, m, n, k, dtype="bf16", **options):
    p = plan((m, n, k), backend="pallas", dtype=dtype, **options)
    a = jax.ShapeDtypeStruct((m, k), _DT[dtype], sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), _DT[dtype], sharding=one_chip)
    with mock.patch.object(backends, "_on_tpu", return_value=True):
        compiled = jax.jit(p.execute).lower(a, b).compile()
    assert "tpu_custom_call" in compiled.as_text(), p.describe()
    return p


_QWEN_GEMMS = {          # (n, k) of the matmuls qwen2-1.5b executes
    "mlp_up": (_QWEN.d_ff, _QWEN.d_model),
    "mlp_down": (_QWEN.d_model, _QWEN.d_ff),
    "logits": (_QWEN.padded_vocab, _QWEN.d_model),
}


@pytest.mark.parametrize("gemm", sorted(_QWEN_GEMMS))
@pytest.mark.parametrize("m", [4, 2048], ids=["decode", "prefill"])
def test_qwen2_planned_gemm_compiles(one_chip, m, gemm):
    n, k = _QWEN_GEMMS[gemm]
    _compile_plan(one_chip, m, n, k)


def test_xlstm_decode_gemm_compiles(one_chip):
    """(1, 768, 768) used to plan bn=64, a block the lowering refuses."""
    p = _compile_plan(one_chip, 1, 768, 768)
    assert p.selection.bn % LANE == 0


def test_k_outer_tile_compiles(one_chip):
    _compile_plan(one_chip, 256, 1024, 2048,
                  tile=TileConfig(256, 512, 512, GridOrder.K_OUTER))


def test_budget_edge_tile_compiles(one_chip):
    """2048^3 bf16 needs 64 MiB of VMEM by the planner's count: feasible
    under its 96 MiB budget, and compiles only because the kernel passes
    that budget to the compiler as its VMEM limit."""
    _compile_plan(one_chip, 2048, 2048, 2048,
                  tile=TileConfig(2048, 2048, 2048, GridOrder.K_INNER))


@pytest.mark.parametrize("tokens", [16, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_granite_grouped_gemm_compiles(one_chip, tokens, proj):
    """The token-major grouped GEMM at granite's widths, for a decode step
    of 16 slots and a 1024-token prefill bucket, top-8 over 40 experts."""
    from repro.kernels.grouped_gemm import padded_rows, row_tile
    e, d, f = _GRANITE.n_experts, _GRANITE.d_model, _GRANITE.moe_d_ff
    d_in, d_out = (d, f) if proj == "up" else (f, d)
    a = tokens * _GRANITE.experts_per_token
    bm = row_tile(a, e)
    x = jax.ShapeDtypeStruct((padded_rows(a, e, bm), d_in), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((e, d_in, d_out), jnp.bfloat16,
                             sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)
    with mock.patch.object(ops, "_on_tpu", return_value=True):
        compiled = jax.jit(lambda x, w, s: ops.grouped_gemm(
            x, w, s, block_m=bm)).lower(x, w, sizes).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# Plain CPU: every selection is a block the lowering accepts
# ---------------------------------------------------------------------------


def _aligned(block: int, dim: int, mult: int) -> bool:
    eff = min(block, dim)                  # the kernel clamps to the dim
    return eff % mult == 0 or eff == dim


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_selections_meet_alignment_rule(arch):
    cfg = get_config(arch)
    for tokens in (1, 4, 8, 64, 2048, 4096):
        for p in plan_model_gemms(cfg, tokens=tokens, backend="pallas"):
            t, q = p.selection, p.problem
            assert _aligned(t.bm, q.m, 8), p.describe()
            assert _aligned(t.bn, q.n, LANE), p.describe()
            assert _aligned(t.bk, q.k, LANE), p.describe()


@pytest.mark.parametrize("machine,lane,bn", [
    ("tpu-v5e", LANE, LANE), ("tpu-v5e-bw-half", LANE, LANE),
    ("gap9-fc", 8, 64), ("cortex-m7", 4, 64)])
def test_alignment_rule_binds_lane_wide_machines_only(machine, lane, bn):
    """The lowering's 128-lane rule binds the TPUs; machines with narrow
    vector registers, priced by the same tile model as a what-if, keep the
    sub-128 blocks — and the scalar and batched searches agree on both."""
    from repro.core.autotune import block_lane, tune_batch, tune_scalar
    from repro.core.tpu_model import GemmShape
    from repro.machines import get
    spec = get(machine)
    assert block_lane(spec) == lane
    shape = GemmShape(1, 1536, 512, dtype="bf16")
    tile = tune_batch([shape], machine=spec, cache=False)[0].tile
    assert tile == tune_scalar(shape, machine=spec).tile
    assert tile.bn == bn


def test_kernel_vmem_limit_is_planner_budget(one_chip):
    """The compiler's VMEM limit and the planner's feasibility bound are
    one value."""
    from repro.core.autotune import vmem_budget
    from repro.machines import get
    budget = vmem_budget(get("tpu-v5e").capacity("L1"))
    p = plan((256, 256, 256), backend="pallas", dtype="bf16")
    a = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=one_chip)
    with mock.patch.object(backends, "_on_tpu", return_value=True):
        text = jax.jit(p.execute).lower(a, a).as_text()
    # the kernel's backend config carries the limit as a scoped VMEM size
    assert f"scoped_memory_configs\\22: [{{\\22memory_space\\22:1, " \
        f"\\22offset\\22: 0, \\22size\\22: {budget}}}]" in text
