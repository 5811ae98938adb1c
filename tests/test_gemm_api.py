"""Unified ``repro.gemm`` plan/execute API: backends, round trips, cache."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import gemm
from repro.core import GAP8_FC
from repro.core.mobilenet import LAYER10, TABLE2
from repro.core.simulator import CostBreakdown, best_microkernel
from repro.core.tpu_model import GridOrder, TileConfig, TpuCost
from repro.core.variants import MicroKernel, Problem, Variant
from repro.kernels import ref

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _fresh_cache():
    gemm.clear_plan_cache()
    yield
    gemm.clear_plan_cache()


def _ab(m, n, k, dtype=jnp.float32):
    a = jnp.array(RNG.normal(size=(m, k)), dtype)
    b = jnp.array(RNG.normal(size=(k, n)), dtype)
    return a, b


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_all_four_backends_registered():
    assert gemm.backends() == ["analytic-gap8", "analytic-tpu", "pallas",
                               "reference"]


def test_unknown_backend_raises():
    with pytest.raises(gemm.UnknownBackendError):
        gemm.plan((8, 8, 8), backend="cuda")


def test_plan_works_for_every_backend():
    for name in gemm.backends():
        p = gemm.plan((64, 96, 128), backend=name)
        assert p.backend == name
        assert p.problem.m == 64 and p.problem.n == 96 and p.problem.k == 128
        assert p.estimate() is not None and p.predicted_seconds > 0
        assert p.executable == gemm.get_backend(name).executable


# ---------------------------------------------------------------------------
# Problem coercion
# ---------------------------------------------------------------------------


def test_problem_coercion_and_dtype_defaults():
    assert gemm.plan((8, 8, 8), backend="analytic-gap8").problem.dtype == \
        "int8"
    assert gemm.plan((8, 8, 8), backend="analytic-tpu").problem.dtype == \
        "bf16"
    p = gemm.plan(Problem(16, 24, 32), backend="analytic-gap8")
    assert (p.problem.m, p.problem.n, p.problem.k) == (16, 24, 32)
    assert gemm.plan((8, 8, 8), backend="pallas",
                     dtype="f32").problem.dtype == "f32"
    with pytest.raises(TypeError):
        gemm.plan("512x512", backend="reference")


# ---------------------------------------------------------------------------
# Round trips: plan -> estimate -> execute
# ---------------------------------------------------------------------------


def test_reference_roundtrip():
    m, n, k = 96, 80, 64
    p = gemm.plan((m, n, k), backend="reference", dtype="f32")
    assert isinstance(p.estimate(), TpuCost)
    a, b = _ab(m, n, k)
    np.testing.assert_allclose(np.asarray(p.execute(a, b)),
                               np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (100, 70, 130),
                                   (1, 300, 17)])
def test_pallas_interpret_roundtrip_matches_ref(m, n, k):
    """Acceptance: a cached plan's execute() == kernels.ref on CPU
    interpret mode (pad-and-slice handles non-divisible shapes)."""
    p1 = gemm.plan((m, n, k), backend="pallas", dtype="f32")
    p2 = gemm.plan((m, n, k), backend="pallas", dtype="f32")
    assert p2 is p1                       # the executed plan IS the cached one
    a, b = _ab(m, n, k)
    np.testing.assert_allclose(np.asarray(p2.execute(a, b, interpret=True)),
                               np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-5, atol=1e-4)


def test_pallas_k_outer_accumulate_matches_streamed_ref():
    m, n, k = 128, 128, 256
    a, b = _ab(m, n, k)
    c0 = jnp.array(RNG.normal(size=(m, n)), jnp.float32)
    p = gemm.plan((m, n, k), backend="pallas", dtype="f32",
                  tile=TileConfig(64, 64, 64, GridOrder.K_OUTER))
    got = p.execute(a, b, c0, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.gemm_ref_streamed(a, b, c0,
                                                                bk=64)),
                               rtol=1e-5, atol=1e-5)


def test_pallas_execute_validates_operand_shapes():
    p = gemm.plan((32, 32, 32), backend="pallas", dtype="f32")
    a, b = _ab(16, 32, 32)
    with pytest.raises(ValueError, match="do not match the planned"):
        p.execute(a, b, interpret=True)


def test_analytic_backends_raise_not_executable():
    for name in ("analytic-gap8", "analytic-tpu"):
        p = gemm.plan((64, 64, 64), backend=name)
        assert not p.executable
        with pytest.raises(gemm.NotExecutableError):
            p.execute(None, None)


# ---------------------------------------------------------------------------
# Plan cache semantics
# ---------------------------------------------------------------------------


def test_cache_hit_miss_semantics():
    s0 = gemm.plan_cache_stats()
    assert s0["size"] == 0
    p1 = gemm.plan((256, 256, 256), backend="analytic-tpu")
    s1 = gemm.plan_cache_stats()
    assert s1["misses"] == 1 and s1["hits"] == 0 and s1["size"] == 1
    p2 = gemm.plan((256, 256, 256), backend="analytic-tpu")
    s2 = gemm.plan_cache_stats()
    assert p2 is p1 and s2["hits"] == 1 and s2["size"] == 1
    # a different key dimension (backend / dtype / policy / options) misses
    gemm.plan((256, 256, 256), backend="pallas")
    gemm.plan((256, 256, 256), backend="analytic-tpu", dtype="int8")
    gemm.plan((256, 256, 256), backend="analytic-tpu", overlap=False)
    assert gemm.plan_cache_stats()["size"] == 4


def test_cache_false_bypasses():
    p1 = gemm.plan((128, 128, 128), backend="analytic-tpu", cache=False)
    p2 = gemm.plan((128, 128, 128), backend="analytic-tpu", cache=False)
    assert p1 is not p2 and p1.selection == p2.selection
    assert gemm.plan_cache_stats()["size"] == 0


def test_manifest_is_the_persistence_layer(tmp_path):
    path = str(tmp_path / "tiles.json")
    fresh = gemm.plan((1024, 512, 2048), backend="pallas")
    assert fresh.provenance["source"] == "search"
    assert gemm.save_cache(path) == 1
    gemm.clear_plan_cache()
    assert gemm.warm_cache(path) == 1
    warmed = gemm.plan((1024, 512, 2048), backend="pallas")
    assert warmed.provenance["source"] == "manifest"
    assert warmed.selection == fresh.selection
    assert isinstance(warmed.cost, TpuCost)
    # the manifest-restored plan still executes correctly
    a, b = _ab(64, 64, 64)
    p = gemm.plan((64, 64, 64), backend="pallas", dtype="f32")
    np.testing.assert_allclose(np.asarray(p.execute(a, b, interpret=True)),
                               np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-5, atol=1e-4)


def test_manifest_does_not_shadow_explicit_options(tmp_path):
    """A warmed manifest only answers option-free plans: a tile searched
    under the default overlap=True must not satisfy overlap=False, whose
    cost composition (and possibly optimal tile) differs."""
    path = str(tmp_path / "tiles.json")
    gemm.plan((512, 2048, 1024), backend="analytic-tpu")
    gemm.save_cache(path)
    gemm.clear_plan_cache()
    gemm.warm_cache(path)
    p = gemm.plan((512, 2048, 1024), backend="analytic-tpu", overlap=False)
    assert p.provenance["source"] == "search"
    assert p.provenance["overlap"] is False
    assert p.predicted_seconds == pytest.approx(p.cost.total_no_overlap)


# ---------------------------------------------------------------------------
# Regression: analytic-gap8 == the paper's Table-2 search
# ---------------------------------------------------------------------------


def test_gap8_reproduces_best_microkernel_layer10():
    for v in Variant:
        p = gemm.plan(LAYER10, backend="analytic-gap8", variant=v)
        cb = best_microkernel(GAP8_FC, v, LAYER10)
        assert isinstance(p.estimate(), CostBreakdown)
        assert p.selection.variant is v
        assert p.selection.micro_kernel == cb.micro_kernel
        assert p.predicted_seconds == pytest.approx(cb.total)


def test_gap8_reproduces_table2_winners_sample():
    for row in TABLE2[:4]:
        for v in Variant:
            p = gemm.plan(row.problem, backend="analytic-gap8", variant=v)
            cb = best_microkernel(GAP8_FC, v, row.problem)
            assert p.selection.micro_kernel == cb.micro_kernel, \
                (row.layer, v)


def test_gap8_variant_search_picks_global_best():
    p = gemm.plan(LAYER10, backend="analytic-gap8")
    per_variant = [best_microkernel(GAP8_FC, v, LAYER10).total
                   for v in Variant]
    assert p.predicted_seconds == pytest.approx(min(per_variant))
    assert set(p.provenance["variants"]) == {v.value for v in Variant}


def test_gap8_explicit_microkernel_override():
    mk = MicroKernel(4, 8)
    p = gemm.plan(LAYER10, backend="analytic-gap8",
                  variant=Variant.B3C2A0, micro_kernel=mk)
    assert p.selection.micro_kernel == mk
    assert p.provenance["source"] == "explicit"
    with pytest.raises(ValueError, match="requires an explicit variant"):
        gemm.plan(LAYER10, backend="analytic-gap8", micro_kernel=mk)


# ---------------------------------------------------------------------------
# Framework helpers
# ---------------------------------------------------------------------------


def test_matmul_helper_folds_leading_dims():
    x = jnp.array(RNG.normal(size=(2, 5, 48)), jnp.float32)
    w = jnp.array(RNG.normal(size=(48, 32)), jnp.float32)
    got = gemm.matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_grouped_matmul_helper_matches_einsum():
    """The grouped GEMM's one dispatch (``kernels.ops.grouped_gemm``, the
    jnp reference off a TPU): rows sorted by expert (groups of 16, 0 and
    32 rows, then rows past the groups), each group times its expert's
    weights, zeros after."""
    from repro.kernels import ops

    x = jnp.array(RNG.normal(size=(64, 24)), jnp.float32)
    w = jnp.array(RNG.normal(size=(3, 24, 8)), jnp.float32)
    sizes = jnp.array([16, 0, 32], jnp.int32)
    got = ops.grouped_gemm(x, w, sizes, block_m=16)
    want = jnp.concatenate([x[:16] @ w[0], x[16:48] @ w[2],
                            jnp.zeros((16, 8))])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_plan_model_gemms_and_engine_report():
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b", smoke=True)
    plans = gemm.plan_model_gemms(cfg, tokens=8, backend="analytic-tpu")
    assert plans and all(p.backend == "analytic-tpu" for p in plans)
    assert all(p.problem.m == 8 for p in plans[:2])   # QKV / O proj rows
    assert sum(p.predicted_seconds for p in plans) > 0
