"""Model substrate tests: per-arch smoke, decode==prefill, SSD equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config, input_specs, shape_cells
from repro.configs.base import SHAPES
from repro.models import layers
from repro.models.common import HOST_MESH, MeshInfo, Param, is_param, split_params
from repro.models.model import LM, factor_pattern
from repro.models.ssm import ssd_chunked, ssd_decode_step
from repro.models.moe import apply_moe


def _batch_for(cfg, b, s, key):
    if cfg.frontend == "audio_stub":
        return {"frames": jax.random.normal(key, (b, s, cfg.d_model)
                                            ).astype(jnp.bfloat16),
                "labels": jnp.zeros((b, s), jnp.int32)}
    if cfg.frontend == "vision_stub":
        st_ = s - cfg.num_prefix_tokens
        return {"patches": jax.random.normal(
                    key, (b, cfg.num_prefix_tokens, cfg.d_model)
                ).astype(jnp.bfloat16),
                "tokens": jax.random.randint(key, (b, st_), 0, cfg.vocab_size),
                "labels": jnp.zeros((b, st_), jnp.int32)}
    return {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
            "labels": jnp.zeros((b, s), jnp.int32)}


# ---------------------------------------------------------------------------
# Per-arch smoke: one forward/train step on CPU, shapes + no NaNs (assignment)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_grad(arch):
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, HOST_MESH)
    values, specs = split_params(lm.init(jax.random.key(0)))
    # spec tree mirrors value tree exactly
    assert jax.tree.structure(values) == jax.tree.structure(specs)
    batch = _batch_for(cfg, 2, 32, jax.random.key(1))

    def loss(v):
        l, m = lm.loss_fn(v, batch)
        return l

    val, grads = jax.jit(jax.value_and_grad(loss))(values)
    assert jnp.isfinite(val), arch
    gleaves = jax.tree.leaves(grads)
    assert all(jnp.all(jnp.isfinite(g)) for g in gleaves), arch
    # at least one grad must be nonzero
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in gleaves), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_full_config_exact_hparams(arch):
    """The full configs carry the assignment's exact hyper-parameters."""
    expect = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 0, 163840),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 0, 49155),
    }[arch]
    c = get_config(arch)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == expect
    if arch == "kimi-k2-1t-a32b":
        assert (c.n_experts, c.experts_per_token, c.moe_d_ff) == (384, 8, 2048)
        # ~1T total, ~32B active
        assert 0.8e12 < c.param_count() < 1.3e12
        assert c.active_param_count() < 0.06 * c.param_count()
    if arch == "granite-moe-3b-a800m":
        assert (c.n_experts, c.experts_per_token, c.moe_d_ff) == (40, 8, 512)
    if arch == "zamba2-1.2b":
        assert c.ssm_state == 64 and c.shared_block


def test_param_counts_in_expected_range():
    approx = {"qwen2-7b": 7.6e9, "qwen2-1.5b": 1.5e9, "qwen2.5-32b": 32.5e9,
              "stablelm-12b": 12.1e9, "paligemma-3b": 2.9e9,
              "musicgen-medium": 1.5e9, "xlstm-125m": 0.125e9}
    for arch, n in approx.items():
        got = get_config(arch).param_count()
        assert 0.55 * n < got < 1.6 * n, (arch, got, n)


# ---------------------------------------------------------------------------
# decode == prefill (cache-path correctness)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-7b", "stablelm-12b", "musicgen-medium",
                                  "granite-moe-3b-a800m"])
def test_decode_matches_prefill_attention_archs(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.n_experts:  # avoid capacity-drop divergence: generous capacity
        cfg = dataclasses.replace(cfg, capacity_factor=64.0)
    lm = LM(cfg, HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(1)))
    b, s = 2, 12
    if cfg.frontend == "audio_stub":
        frames = jax.random.normal(jax.random.key(2), (b, s, cfg.d_model)
                                   ).astype(jnp.bfloat16)
        lg_full, _ = lm.prefill(values, {"frames": frames})
        caches, _ = split_params(lm.init_cache(b, max_len=s + 4))
        for t in range(s):
            lg, caches = lm.decode_step(values, caches, frames[:, t:t + 1],
                                        jnp.int32(t))
    else:
        toks = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab_size)
        lg_full, _ = lm.prefill(values, {"tokens": toks})
        caches, _ = split_params(lm.init_cache(b, max_len=s + 4))
        for t in range(s):
            lg, caches = lm.decode_step(values, caches, toks[:, t:t + 1],
                                        jnp.int32(t))
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_full, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "xlstm-125m"])
def test_decode_matches_prefill_recurrent_archs(arch):
    """Recurrent archs: chunked-parallel vs step recurrence agree within
    bf16 accumulation tolerance."""
    cfg = get_config(arch, smoke=True)
    lm = LM(cfg, HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(1)))
    b, s = 2, 12
    toks = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab_size)
    lg_full, _ = lm.prefill(values, {"tokens": toks})
    caches, _ = split_params(lm.init_cache(b, max_len=s + 4))
    for t in range(s):
        lg, caches = lm.decode_step(values, caches, toks[:, t:t + 1],
                                    jnp.int32(t))
    scale = float(jnp.max(jnp.abs(lg_full.astype(jnp.float32)))) + 1e-6
    err = float(jnp.max(jnp.abs(lg.astype(jnp.float32)
                                - lg_full.astype(jnp.float32))))
    assert err / scale < 0.06, (arch, err, scale)


# ---------------------------------------------------------------------------
# SSD core: chunked == recurrent (exact, f32)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), s=st.integers(3, 40),
       chunk=st.sampled_from([4, 8, 16]))
def test_ssd_chunked_equals_recurrence(seed, s, chunk):
    rng = np.random.default_rng(seed)
    B, H, P, N = 2, 3, 5, 4
    xh = jnp.array(rng.normal(size=(B, s, H, P)), jnp.float32)
    a = -jnp.abs(jnp.array(rng.normal(size=(B, s, H)), jnp.float32)) * 0.3
    dt = jnp.abs(jnp.array(rng.normal(size=(B, s, H)), jnp.float32))
    Bm = jnp.array(rng.normal(size=(B, s, H, N)), jnp.float32)
    Cm = jnp.array(rng.normal(size=(B, s, H, N)), jnp.float32)
    y_chunk, h_chunk = ssd_chunked(xh, a, dt, Bm, Cm, chunk=chunk)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(s):
        y_t, h = ssd_decode_step(h, xh[:, t], a[:, t], dt[:, t], Bm[:, t],
                                 Cm[:, t])
        ys.append(y_t)
    y_rec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_rec),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h),
                               rtol=1e-4, atol=1e-4)


def test_ssd_state_passing_across_calls():
    """Splitting a sequence across two chunked calls with carried state must
    equal one call — the invariant behind multi-segment prefill."""
    rng = np.random.default_rng(3)
    B, S, H, P, N = 1, 24, 2, 4, 4
    xh = jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32)
    a = -jnp.abs(jnp.array(rng.normal(size=(B, S, H)), jnp.float32)) * 0.2
    dt = jnp.abs(jnp.array(rng.normal(size=(B, S, H)), jnp.float32))
    Bm = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    Cm = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
    y_all, h_all = ssd_chunked(xh, a, dt, Bm, Cm, chunk=8)
    half = S // 2
    y1, h1 = ssd_chunked(xh[:, :half], a[:, :half], dt[:, :half],
                         Bm[:, :half], Cm[:, :half], chunk=8)
    y2, h2 = ssd_chunked(xh[:, half:], a[:, half:], dt[:, half:],
                         Bm[:, half:], Cm[:, half:], chunk=8, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_all), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_all),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Attention: blockwise == naive softmax; prefix mask; head padding exactness
# ---------------------------------------------------------------------------


def _naive_attention(q, k, v, causal=True, prefix_len=0):
    b, s, h, d = q.shape
    s_kv = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    mask = jnp.ones((s, s_kv), bool)
    if causal:
        mask = jnp.tril(jnp.ones((s, s_kv), bool))
        if prefix_len:
            mask = mask | (jnp.arange(s_kv)[None, :] < prefix_len)
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), s=st.integers(2, 33),
       chunk=st.sampled_from([4, 8, 64]), prefix=st.integers(0, 6))
def test_blockwise_attention_matches_naive(seed, s, chunk, prefix):
    from repro.models.attention import blockwise_attention
    rng = np.random.default_rng(seed)
    b, h, d = 2, 3, 8
    q = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.array(rng.normal(size=(b, s, h, d)), jnp.float32)
    got = blockwise_attention(q, k, v, chunk=chunk, causal=True,
                              scale=d ** -0.5, prefix_len=min(prefix, s))
    want = _naive_attention(q, k, v, causal=True, prefix_len=min(prefix, s))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch,tp", [("qwen2-7b", 4),       # GQA group pad
                                     ("musicgen-medium", 4),  # MHA both pad
                                     ("paligemma-3b", 8)])    # MQA extreme
def test_head_padding_is_exact(arch, tp):
    """Padding heads to the TP multiple (grouped per KV head) must not
    change attention outputs: same init key -> identical logical weights,
    zero-filled pad positions."""
    from repro.models.attention import apply_attention, head_layout, init_attention
    cfg = get_config(arch, smoke=True)
    x = jax.random.normal(jax.random.key(0), (2, 16, cfg.d_model),
                          jnp.float32)
    lm_plain = MeshInfo(data=1, model=1)
    lm_pad = MeshInfo(data=1, model=tp)
    hq_p, hkv_p = head_layout(cfg, lm_pad)
    assert hq_p % tp == 0
    assert hq_p >= cfg.n_heads and hkv_p >= 1
    p1, _ = split_params(init_attention(jax.random.key(5), cfg, lm_plain,
                                        jnp.float32))
    p2, _ = split_params(init_attention(jax.random.key(5), cfg, lm_pad,
                                        jnp.float32))
    y1 = apply_attention(p1, x, cfg, lm_plain)
    y2 = apply_attention(p2, x, cfg, lm_pad)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------


def test_moe_gates_normalised_and_capacity_exact():
    cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b", smoke=True),
                              capacity_factor=64.0)
    from repro.models.moe import init_moe
    p, _ = split_params(init_moe(jax.random.key(0), cfg, HOST_MESH,
                                 jnp.float32))
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    y, aux = apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    assert float(aux) > 0


def test_moe_capacity_drops_bounded():
    """On one device the expert layer drops no token: where per-sequence
    capacity would have overflowed (16 slots per expert for 32 tokens x
    top-2), every token's output is its k experts' gated sum, never the
    residual alone."""
    import numpy as np
    from repro.models.moe import _capacity, _route, init_moe
    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    p, _ = split_params(init_moe(jax.random.key(0), cfg, HOST_MESH,
                                 jnp.float32))
    x = jax.random.normal(jax.random.key(1), (4, 32, cfg.d_model), jnp.float32)
    r0 = p["router"][:, 0]                       # skew every token to expert 0
    x = x + 4.0 * r0 / jnp.linalg.norm(r0)
    y, aux = apply_moe(p, x, cfg)
    gates, experts, _ = _route(p, x, cfg)
    loads = np.bincount(np.asarray(experts[0]).ravel(), minlength=5)
    assert loads.max() > _capacity(32, cfg)      # capacity would drop here
    xf = x.reshape(-1, cfg.d_model)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"])) * \
        jnp.einsum("td,edf->tef", xf, p["w_up"])
    per_expert = jnp.einsum("tef,efd->ted", h, p["w_down"])
    picked = jnp.take_along_axis(per_expert,
                                 experts.reshape(-1, 2)[..., None], axis=1)
    want = (picked * gates.reshape(-1, 2, 1)).sum(1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert (np.abs(np.asarray(y)).max(-1) > 0).all()
    assert jnp.all(jnp.isfinite(y)) and float(aux) > 0


# ---------------------------------------------------------------------------
# Pattern factoring
# ---------------------------------------------------------------------------


def test_factor_pattern():
    assert factor_pattern(("attn",) * 8) == (("attn",), 8, ())
    assert factor_pattern(("mlstm", "slstm") * 6) == (("mlstm", "slstm"), 6, ())
    p = ("mamba2",) * 5 + ("shared_attn",)
    assert factor_pattern(p * 6 + ("mamba2", "mamba2")) == (p, 6, ("mamba2", "mamba2"))
    assert factor_pattern(("a", "b", "a")) == (("a", "b"), 1, ("a",))


def test_cross_entropy_masks_padded_vocab():
    logits = jnp.zeros((1, 3, 8))
    labels = jnp.array([[1, 2, 3]])
    l1 = layers.cross_entropy(logits, labels, vocab_size=8)
    l2 = layers.cross_entropy(jnp.pad(logits, ((0, 0), (0, 0), (0, 4)),
                                      constant_values=5.0),
                              labels, vocab_size=8)
    assert jnp.allclose(l1, l2, atol=1e-5)


def test_int8_kv_cache_decode_close_to_bf16():
    """EXPERIMENTS.md §Perf D2: int8 KV entries with per-(pos, head) scales
    stay within ~2% of the bf16-cache decode."""
    import dataclasses as _dc
    cfg = get_config("qwen2-7b", smoke=True)
    cfg8 = _dc.replace(cfg, kv_cache_dtype="int8")
    lm, lm8 = LM(cfg, HOST_MESH), LM(cfg8, HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(1)))
    toks = jax.random.randint(jax.random.key(2), (2, 10), 0, cfg.vocab_size)
    c1, _ = split_params(lm.init_cache(2, 16))
    c2, _ = split_params(lm8.init_cache(2, 16))
    assert c2["stack"]["b0_attn"]["k"].dtype == jnp.int8
    for t in range(10):
        lg1, c1 = lm.decode_step(values, c1, toks[:, t:t + 1], jnp.int32(t))
        lg2, c2 = lm8.decode_step(values, c2, toks[:, t:t + 1], jnp.int32(t))
    scale = float(jnp.max(jnp.abs(lg1.astype(jnp.float32)))) + 1e-9
    err = float(jnp.max(jnp.abs(lg1.astype(jnp.float32)
                                - lg2.astype(jnp.float32)))) / scale
    assert err < 0.05, err
