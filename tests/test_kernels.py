"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True (CPU container; TPU is the deploy target)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.tpu_model import GridOrder, TileConfig
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.gemm import gemm_k_inner, gemm_k_outer
from repro.kernels.grouped_gemm import grouped_gemm_kernel
from repro.kernels.ops import matmul

RNG = np.random.default_rng(7)


def _rand(shape, dtype):
    if dtype == "int8":
        return jnp.array(RNG.integers(-100, 100, size=shape), jnp.int8)
    return jnp.array(RNG.normal(size=shape), dtype=dtype)


# ---------------------------------------------------------------------------
# GEMM: divisible-shape kernel sweeps
# ---------------------------------------------------------------------------

GEMM_CASES = [
    (128, 128, 128, "float32"), (256, 128, 512, "float32"),
    (128, 384, 256, "bfloat16"), (512, 256, 128, "bfloat16"),
    (128, 128, 256, "int8"), (256, 512, 128, "int8"),
]


@pytest.mark.parametrize("m,n,k,dt", GEMM_CASES)
def test_gemm_k_inner_matches_ref(m, n, k, dt):
    a, b = _rand((m, k), dt), _rand((k, n), dt)
    got = gemm_k_inner(a, b, tile=TileConfig(64, 128, 64), interpret=True)
    want = ref.gemm_ref(a, b)
    if dt == "int8":
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2 if dt == "bfloat16" else 1e-5,
                                   atol=2e-2 if dt == "bfloat16" else 1e-4)


@pytest.mark.parametrize("m,n,k,dt", GEMM_CASES[:4])
def test_gemm_k_outer_matches_streamed_ref(m, n, k, dt):
    """k_outer == the streamed oracle exactly (same per-pass rounding)."""
    a, b = _rand((m, k), dt), _rand((k, n), dt)
    c0 = _rand((m, n), dt)
    got = gemm_k_outer(a, b, c0, tile=TileConfig(64, 128, 64,
                                                 GridOrder.K_OUTER),
                       interpret=True)
    want = ref.gemm_ref_streamed(a, b, c0, bk=64)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_k_outer_step_kernel_constructed_once_and_reused():
    """The k-outer step kernel is built once per (shape, tile, dtype) config
    and reused across the k loop and across calls."""
    from repro.kernels import gemm as gemm_mod
    gemm_mod._k_step_call.cache_clear()
    m, n, k = 128, 128, 256
    a, b = _rand((m, k), "float32"), _rand((k, n), "float32")
    c0 = _rand((m, n), "float32")
    tile = TileConfig(64, 64, 64, GridOrder.K_OUTER)
    got = gemm_k_outer(a, b, c0, tile=tile, interpret=True)
    info = gemm_mod._k_step_call.cache_info()
    assert info.misses == 1 and info.hits == 0  # 4 k-steps, one constructor
    got2 = gemm_k_outer(a, b, c0, tile=tile, interpret=True)
    info = gemm_mod._k_step_call.cache_info()
    assert info.misses == 1 and info.hits == 1  # second call reuses it
    want = ref.gemm_ref_streamed(a, b, c0, bk=64)
    for out in (got, got2):
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_k_outer_streaming_costs_precision_in_bf16():
    """Numerical finding: the C-streamed variant rounds C to bf16 every k
    pass; the output-stationary variant (f32 VMEM accumulator) does not —
    the numerical face of the paper's 'B3A2C0 reduces stores of C'."""
    m, n, k = 128, 256, 512
    a, b = _rand((m, k), "bfloat16"), _rand((k, n), "bfloat16")
    c0 = jnp.zeros((m, n), jnp.bfloat16)
    exact = np.asarray(ref.gemm_ref(a, b), np.float32)
    inner = np.asarray(gemm_k_inner(a, b, tile=TileConfig(64, 128, 64),
                                    interpret=True), np.float32)
    outer = np.asarray(gemm_k_outer(a, b, c0,
                                    tile=TileConfig(64, 128, 64,
                                                    GridOrder.K_OUTER),
                                    interpret=True), np.float32)
    err_inner = np.abs(inner - exact).max()
    err_outer = np.abs(outer - exact).max()
    assert err_outer > 2 * err_inner


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300),
       order=st.sampled_from(list(GridOrder)))
def test_matmul_wrapper_pads_any_shape(m, n, k, order):
    a = jnp.array(np.arange(m * k).reshape(m, k) % 7, jnp.float32)
    b = jnp.array(np.arange(k * n).reshape(k, n) % 5, jnp.float32)
    got = matmul(a, b, tile=TileConfig(64, 128, 64, order), interpret=True)
    want = ref.gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_k_inner_int8_exact_vs_k_outer():
    """int8 path: k_inner accumulates in int32 exactly."""
    a, b = _rand((128, 256), "int8"), _rand((256, 128), "int8")
    got = gemm_k_inner(a, b, tile=TileConfig(64, 64, 128), interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.gemm_ref(a, b)))


# ---------------------------------------------------------------------------
# Grouped GEMM (MoE)
# ---------------------------------------------------------------------------

def _routed(experts, n_experts, d, dt, seed=0):
    """Rows in the token-major layout for the assignments ``experts``:
    (x rows, the row of each assignment, group sizes, block_m)."""
    from repro.kernels.grouped_gemm import padded_rows, row_tile
    from repro.models.moe import token_major_layout
    experts = jnp.asarray(experts, jnp.int32)
    a = experts.shape[0]
    bm = row_tile(a, n_experts)
    rows = padded_rows(a, n_experts, bm)
    row_of, sizes = token_major_layout(experts, n_experts, bm)
    v = jnp.array(np.random.default_rng(seed).normal(size=(a, d)), dt)
    x = jnp.zeros((rows, d), dt).at[row_of].set(v)
    return x, v, row_of, sizes, bm


def _check_grouped(experts, e, d, f, dt):
    """The kernel against its jnp reference on every row (zeros past the
    groups included), and each assignment's row against its own expert's
    product."""
    x, v, row_of, sizes, bm = _routed(experts, e, d, dt)
    w = _rand((e, d, f), dt)
    got = grouped_gemm_kernel(x, w, sizes, block_m=bm, interpret=True)
    want = ref.grouped_gemm_ref(x, w, sizes)
    tol = 2e-2 if dt == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    own = jnp.einsum("ad,adf->af", v.astype(jnp.float32),
                     w.astype(jnp.float32)[jnp.asarray(experts)])
    np.testing.assert_allclose(np.asarray(got[row_of], np.float32),
                               np.asarray(own), rtol=tol, atol=tol * 10)
    return got, sizes


@pytest.mark.parametrize("a,e,d,f,dt", [
    (512, 4, 256, 128, "float32"),
    (2048, 8, 128, 256, "bfloat16"),
    (256, 2, 512, 384, "float32"),
])
def test_grouped_gemm_matches_ref(a, e, d, f, dt):
    _check_grouped(RNG.integers(0, e, size=a), e, d, f, dt)


@pytest.mark.parametrize("case", ["empty_experts", "one_expert_all_rows",
                                  "groups_not_tile_multiples",
                                  "decode_16x8", "prefill_bucket"])
def test_grouped_gemm_routing(case):
    """The routings the MoE layer hands the kernel: experts with no row,
    one expert with every row, groups that do not fill their last tile,
    a decode step (16 tokens x top-8 over 40 experts) and a prefill bucket
    (128 tokens x top-8)."""
    e, d, f = 40, 256, 128
    if case == "empty_experts":
        experts = RNG.choice([3, 17, 39], size=64)
    elif case == "one_expert_all_rows":
        e, experts = 8, np.full(96, 5)
    elif case == "groups_not_tile_multiples":
        e, experts = 4, np.repeat([0, 1, 2, 3], [1, 17, 15, 33])
    elif case == "decode_16x8":
        experts = np.stack([RNG.choice(e, 8, replace=False)
                            for _ in range(16)]).ravel()
    else:
        experts = np.stack([RNG.choice(e, 8, replace=False)
                            for _ in range(128)]).ravel()
    got, sizes = _check_grouped(experts, e, d, f, "bfloat16")
    counts = np.bincount(experts, minlength=e)
    bm = int(max(sizes)) if case == "one_expert_all_rows" else None
    assert (np.asarray(sizes) >= counts).all()
    assert ((np.asarray(sizes) == 0) == (counts == 0)).all()
    live = int(np.asarray(sizes).sum())
    assert not np.asarray(got[live:], np.float32).any()
    if bm is not None:
        assert live == bm and np.asarray(sizes)[5] == live


def test_grouped_gemm_expert_isolation():
    """Each expert's rows depend only on its own weights."""
    e, d, f = 4, 128, 128
    experts = RNG.integers(0, e, size=128)
    x, _, row_of, sizes, bm = _routed(experts, e, d, "float32")
    w = _rand((e, d, f), "float32")
    w2 = w.at[2].set(0.0)
    y1 = np.asarray(grouped_gemm_kernel(x, w, sizes, block_m=bm,
                                        interpret=True))[row_of]
    y2 = np.asarray(grouped_gemm_kernel(x, w2, sizes, block_m=bm,
                                        interpret=True))[row_of]
    assert np.allclose(y2[experts == 2], 0.0)
    np.testing.assert_allclose(y1[experts != 2], y2[experts != 2])


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,d,bq,bk,dt", [
    (2, 256, 3, 64, 64, 64, "float32"),
    (1, 512, 2, 128, 128, 128, "float32"),
    (2, 256, 4, 64, 128, 64, "bfloat16"),
])
def test_flash_attention_matches_ref(b, s, h, d, bq, bk, dt):
    q, k, v = (_rand((b, s, h, d), dt) for _ in range(3))
    got = flash_attention_fwd(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dt == "bfloat16" else 1e-5,
                               atol=3e-2 if dt == "bfloat16" else 1e-5)


def test_flash_attention_non_causal():
    q, k, v = (_rand((1, 128, 2, 64), "float32") for _ in range(3))
    got = flash_attention_fwd(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_matches_model_blockwise():
    """The Pallas kernel and the model's pure-jnp blockwise path agree —
    kernel-on-TPU and reference-on-dry-run compute the same function."""
    from repro.models.attention import blockwise_attention
    q, k, v = (_rand((2, 128, 2, 64), "float32") for _ in range(3))
    a = flash_attention_fwd(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
    b = blockwise_attention(q, k, v, chunk=64, causal=True,
                            scale=64 ** -0.5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Fused RMSNorm kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dt,br", [
    ((4, 64, 128), "float32", 64),
    ((512, 256), "bfloat16", 128),
    ((2, 128, 512), "bfloat16", 32),
])
def test_rmsnorm_kernel_matches_ref(shape, dt, br):
    from repro.kernels.rmsnorm import rmsnorm
    x = _rand(shape, dt)
    s = jnp.array(RNG.normal(size=shape[-1]), jnp.float32)
    got = rmsnorm(x, s, block_rows=br, interpret=True)
    xf = x.astype(jnp.float32)
    want = (xf * jax.lax.rsqrt(jnp.mean(xf ** 2, -1, keepdims=True) + 1e-5)
            * s).astype(dt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_rmsnorm_kernel_matches_model_norm():
    from repro.kernels.rmsnorm import rmsnorm
    from repro.models import layers
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b", smoke=True)
    x = _rand((4, 16, cfg.d_model), "float32")
    scale = jnp.array(RNG.normal(size=cfg.d_model), jnp.float32)
    got = rmsnorm(x, scale, block_rows=32, eps=cfg.norm_eps, interpret=True)
    want = layers.apply_norm({"scale": scale}, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
