"""The program against the benchmark's plain reference on a small Granite
mixture with the four published multipliers (embeddings x12, attention
x1/64, residual branches x0.22, logits /6): the prefill's logits, and a
prefill then decode steps through the cache, against the reference's full
forward; a routing that sends every token to one expert, which per-sequence
capacity would have cut to a quarter; and a configuration that states the
plain block's values, which runs the very program of one that states none;
and the benchmark's Granite weight table (``lib/granite_reference.py``),
under which greedy decoding varies and the float8 control reads far wider
gaps than the program.

The weights are the benchmark's (``lib.weights`` from a seed, nested into
the program's tree), so both sides run the same model.
"""
import dataclasses
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
sys.path.insert(0, str(BENCH))

from lib.spec import ModelSpec  # noqa: E402
from lib.weights import make_weights, program_tree  # noqa: E402

from repro.models.common import HOST_MESH, split_params  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.models.moe import _capacity  # noqa: E402

#: a small Granite mixture: 16 experts, top-2, the published multipliers
GRANITE = {"program_arch": "granite-moe-3b-a800m", "hidden_size": 64,
           "intermediate_size": 32, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
           "num_local_experts": 16, "num_experts_per_tok": 2,
           "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": True, "attention_bias": False,
           "hidden_act": "silu", "initializer_range": 0.02,
           "embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
           "residual_multiplier": 0.22, "logits_scaling": 6.0}
PROMPT = 32
#: routing and heads as published (40 experts, top-8, heads of 64) at a
#: small width, under the benchmark's Granite weight table
WIDE = dict(GRANITE, hidden_size=128, intermediate_size=32,
            num_attention_heads=2, num_key_value_heads=1, head_dim=64,
            num_hidden_layers=4, num_local_experts=40,
            num_experts_per_tok=8, reference="granite_reference")
#: both sides in float32: they differ by summation order alone (the
#: blockwise softmax, the grouped GEMM's rows), a few ulps of logits of
#: order 1, so 1e-4 of the largest logit leaves two orders of room
F32_TOL = 1e-4
#: the program's bf16 compute: the residual stream holds the x12
#: embeddings at 8-bit mantissas (2^-9 of their size), which moves logits
#: whose largest is about 0.2 here by up to 0.02 (0.018 read): 15% of the
#: largest leaves room for other seeds and says the model is the same
BF16_TOL = 0.15


def _setup(data, seed=11, compute_dtype="float32"):
    spec = ModelSpec("g", data)
    cfg = dataclasses.replace(spec.program_config(),
                              compute_dtype=compute_dtype)
    lm = LM(cfg, HOST_MESH)
    shapes = jax.eval_shape(lambda k: split_params(lm.init(k))[0],
                            jax.random.key(0))
    w = make_weights(spec, shapes["embed"]["table"].shape[0], seed)
    params = program_tree(w, shapes, spec.reference.PROGRAM_LEAF)
    return spec, lm, w, params


def _tokens(n, seed=5, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _reference(spec, w, tokens, rows):
    return np.asarray(spec.reference.logits_at(w, spec, tokens, rows))


def _close(got, want, tol):
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got, np.float32)[..., :want.shape[-1]],
                               want, rtol=0, atol=tol * scale)


def _prefill_then_decode(lm, params, tokens, prefix: int, max_len: int):
    """The prefill of ``tokens[:prefix]``, its caches padded to
    ``max_len`` as the engine inserts them, then one decode step per
    remaining token: the logits at every position from ``prefix - 1``."""
    first, caches = lm.prefill(params, {"tokens": jnp.asarray(
        tokens[None, :prefix])})

    def pad(c):
        return jnp.pad(c, [(0, 0)] * 2 + [(0, max_len - prefix)]
                       + [(0, 0)] * (c.ndim - 3))
    caches = {"stack": jax.tree.map(pad, caches["stack"]), "tail": {}}
    out = [first[0]]
    for pos in range(prefix, len(tokens)):
        logits, caches = lm.decode_step(
            params, caches, jnp.asarray(tokens[None, pos:pos + 1]),
            jnp.array([pos], jnp.int32))
        out.append(logits[0])
    return jnp.stack(out)


def test_program_config_runs_the_published_multipliers():
    _, lm, _, _ = _setup(GRANITE)
    cfg = lm.cfg
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.015625, 0.22, 6.0)
    assert cfg.attention_multiplier == 0.015625 != cfg.head_dim ** -0.5


@pytest.mark.parametrize("compute_dtype, tol", [("float32", F32_TOL),
                                                ("bfloat16", BF16_TOL)])
def test_prefill_logits_match_reference(compute_dtype, tol):
    spec, lm, w, params = _setup(GRANITE, compute_dtype=compute_dtype)
    tokens = _tokens(PROMPT)
    logits = lm.logits(params, {"tokens": jnp.asarray(tokens[None])})[0][0]
    want = _reference(spec, w, tokens, np.arange(PROMPT))
    _close(logits, want, tol)
    last, _ = lm.prefill(params, {"tokens": jnp.asarray(tokens[None])})
    _close(last[0], want[-1], tol)


def test_prefill_then_decode_through_the_cache_matches_reference():
    spec, lm, w, params = _setup(GRANITE)
    tokens = _tokens(PROMPT + 8, seed=6)
    got = _prefill_then_decode(lm, params, tokens, PROMPT, max_len=64)
    want = _reference(spec, w, tokens, np.arange(PROMPT - 1, len(tokens)))
    _close(got, want, F32_TOL)


def test_one_expert_takes_every_token(monkeypatch):
    """Every token of a 32-token prompt routes to expert 5 in every layer
    (a constant feature of the embeddings that only expert 5's router
    column reads): the program still agrees with the reference, where
    per-sequence capacity (8 slots for 32 tokens) would have dropped three
    tokens in four to the residual."""
    from repro.models import moe

    spec, lm, w, params = _setup(GRANITE)
    w = dict(w, embed=w["embed"].at[:, 0].set(0.5),
             router=w["router"].at[:, 0, :].set(0.0).at[:, 0, 5].set(1.0))
    params = jax.tree.map(lambda x: x, params)
    params["embed"]["table"] = w["embed"]
    params["stack"]["b0_moe"]["moe"]["router"] = w["router"]
    assert _capacity(PROMPT, lm.cfg) == 8
    seen = []
    real = moe._route

    def spy(p, x, cfg):
        out = real(p, x, cfg)
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), out[1])
        return out
    monkeypatch.setattr(moe, "_route", spy)
    tokens = _tokens(PROMPT, seed=7)
    logits = lm.logits(params, {"tokens": jnp.asarray(tokens[None])})[0]
    assert len(seen) == 2 and all((e == 5).any(-1).all() for e in seen)
    want = _reference(spec, w, tokens, np.arange(PROMPT))
    _close(logits[0], want, F32_TOL)


def test_plain_values_run_the_unchanged_program():
    """A configuration stating the plain block's four values runs the
    very program of one that states none: the same HLO, bit-identical
    logits (qwen2's block; its file states none)."""
    from repro.configs import get_config

    base = get_config("qwen2-1.5b", smoke=True)
    plain = dataclasses.replace(base, embedding_multiplier=1.0,
                                attention_multiplier=base.head_dim ** -0.5,
                                residual_multiplier=1.0, logits_scaling=1.0)
    tokens = jnp.asarray(_tokens(24, vocab=base.vocab_size)[None])
    lms = [LM(c, HOST_MESH) for c in (base, plain)]
    params, _ = split_params(lms[0].init(jax.random.key(3)))
    texts = [jax.jit(lm.prefill).lower(params, {"tokens": tokens}).as_text()
             for lm in lms]
    assert texts[0] == texts[1]
    outs = [np.asarray(lm.prefill(params, {"tokens": tokens})[0])
            for lm in lms]
    np.testing.assert_array_equal(outs[0], outs[1])
    caches = split_params(lms[0].init_cache(1, 32))[0]
    steps = [np.asarray(lm.decode_step(params, caches, tokens[:, :1],
                                       jnp.array([0], jnp.int32))[0])
             for lm in lms]
    np.testing.assert_array_equal(steps[0], steps[1])


def test_granite_table_decodes_apart_from_float8():
    """Greedy decoding in bf16 under ``lib/granite_reference.py``'s table:
    each answer holds many tokens (``reference.init`` repeats one), and
    the reference's gaps of the served tokens lie far below those of the
    float8 control's choices, so the benchmark's check can fail.  Read
    over weight seeds 1-3: 6-15 distinct tokens in 16; the mean gap 12x,
    165x and 35x below the control's, the widest 3.7x, 41x and 9.4x;
    seed 1, the closest, is the one run, asserted at 5x and 2x."""
    from lib import check

    spec, lm, w, params = _setup(WIDE, seed=1, compute_dtype="bfloat16")
    assert spec.reference.__name__.endswith("granite_reference")
    batch, new, max_len = 4, 16, 64
    prompts = np.stack([_tokens(PROMPT, seed=20 + i) for i in range(batch)])
    logits, caches = lm.prefill(params, {"tokens": jnp.asarray(prompts)})

    def pad(c):
        return jnp.pad(c, [(0, 0)] * 2 + [(0, max_len - PROMPT)]
                       + [(0, 0)] * (c.ndim - 3))
    caches = {"stack": jax.tree.map(pad, caches["stack"]), "tail": {}}
    step = jax.jit(lm.decode_step)
    served = [np.asarray(jnp.argmax(logits[:, :512], -1))]
    for pos in range(PROMPT, PROMPT + new - 1):
        logits, caches = step(params, caches, jnp.asarray(served[-1][:, None]),
                              jnp.full((batch,), pos, jnp.int32))
        served.append(np.asarray(jnp.argmax(logits[:, :512], -1)))
    served = np.stack(served, 1)
    assert all(len(set(row)) >= 4 for row in served)
    requests = [types.SimpleNamespace(
        engine_req=types.SimpleNamespace(prompt=list(p)), tokens=list(t))
        for p, t in zip(prompts, served)]
    program = check.gaps(w, spec, requests, out_rows=new)
    control = check.gaps(w, spec, requests, out_rows=new, control=True)
    assert check.mean_gap(program) < check.mean_gap(control) / 5
    assert check.widest_gap(program) < check.widest_gap(control) / 2
