"""Runtime tests: optimizer, schedule, compression, data determinism,
checkpointing (atomic/keep-N/preemption/elastic), training integration,
watchdog, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.data import DataIterator, make_batch
from repro.models.common import HOST_MESH, split_params
from repro.models.model import LM
from repro.optim import (
    AdamWConfig,
    adamw_update,
    global_norm,
    init_opt_state,
    lr_schedule,
    quantize_int8,
)
from repro.optim.compression import compress_tree, decompress_tree, init_error_buffer
from repro.runtime.fault import StepWatchdog
from repro.runtime.train_lib import init_train_state, make_train_step
from repro.serving.engine import Request, ServingEngine


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------


def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(weight_decay=0.0, grad_clip=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(grads, opt, params, 0.1, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clip_caps_update_norm():
    cfg = AdamWConfig(grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    opt = init_opt_state(params, cfg)
    g = {"w": jnp.full(4, 1e6)}
    _, _, m = adamw_update(g, opt, params, 1e-3, cfg)
    assert m["grad_norm"] > 1e6  # reported pre-clip


def test_lr_schedule_shape():
    s = jnp.arange(0, 1000)
    lr = jax.vmap(lambda t: lr_schedule(t, base_lr=1.0, warmup=100,
                                        total=1000))(s)
    assert float(lr[0]) == 0.0
    assert float(lr[99]) == pytest.approx(0.99, abs=0.02)
    assert float(jnp.max(lr)) <= 1.0 + 1e-6
    assert float(lr[-1]) == pytest.approx(0.1, abs=0.01)   # min_ratio floor
    assert bool(jnp.all(lr[100:] >= 0.1 - 1e-6))


def test_moment_dtype_configurable():
    cfg = AdamWConfig(moment_dtype="bfloat16")
    opt = init_opt_state({"w": jnp.zeros((4, 4))}, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Gradient compression (int8 + error feedback)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_quantize_int8_bounded_error(seed):
    rng = np.random.default_rng(seed)
    x = jnp.array(rng.normal(size=128) * rng.uniform(0.01, 100), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(x - q.astype(jnp.float32) * s)
    assert float(err.max()) <= float(s) * 0.5 + 1e-9


def test_error_feedback_converges():
    """Repeatedly compressing the same gradient with error feedback must
    transmit the full signal over time (mean reconstructed -> true grad)."""
    g = {"w": jnp.array([1e-4, 3e-2, -0.7, 0.9])}
    ebuf = init_error_buffer(g)
    acc = jnp.zeros(4)
    n = 50
    for _ in range(n):
        q, ebuf = compress_tree(g, ebuf)
        deq = decompress_tree(q, g)
        acc = acc + deq["w"]
    # converges to within a small fraction of the int8 quantisation step
    # (scale = max|g|/127); components far below the step need ~1/eps rounds
    step = float(jnp.max(jnp.abs(g["w"]))) / 127
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g["w"]),
                               rtol=5e-2, atol=step / 10)


# ---------------------------------------------------------------------------
# Data pipeline determinism
# ---------------------------------------------------------------------------


def test_data_deterministic_and_resumable():
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 32, 4)
    it1 = DataIterator(cfg, shape, seed=7)
    batches = [next(it1) for _ in range(5)]
    # resume from state at step 3
    it2 = DataIterator(cfg, shape, seed=0)
    it2.load_state_dict({"step": 3, "seed": 7})
    b3 = next(it2)
    np.testing.assert_array_equal(np.asarray(b3["tokens"]),
                                  np.asarray(batches[3]["tokens"]))


def test_data_host_sharding_disjoint():
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 16, 8)
    b0 = make_batch(cfg, shape, step=0, seed=1, host_id=0, num_hosts=2)
    b1 = make_batch(cfg, shape, step=0, seed=1, host_id=1, num_hosts=2)
    assert b0["tokens"].shape == (4, 16)
    assert not np.array_equal(np.asarray(b0["tokens"]),
                              np.asarray(b1["tokens"]))


def test_data_has_learnable_structure():
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 256, 8)
    b = make_batch(cfg, shape, step=0, seed=0)
    toks = np.asarray(b["tokens"])
    copies = (toks[:, 1:] == toks[:, :-1]).mean()
    assert 0.3 < copies < 0.7        # the copy-process signal


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {"a": jnp.array(rng.normal(size=(4, 4)), jnp.float32),
            "b": {"c": jnp.array(rng.normal(size=3), jnp.float32)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree(0)
    mgr.save(10, t, extra={"data": {"step": 10, "seed": 0}})
    step, restored, extra = mgr.restore_latest(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t))
    assert step == 10 and extra["data"]["step"] == 10
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(t["a"]))


def test_checkpoint_keep_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_atomic_no_partial(tmp_path):
    """A .tmp directory without the commit marker is never listed."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _tree(1))
    os.makedirs(tmp_path / "step_00000002")   # crash-simulated partial
    assert mgr.all_steps() == [1]


def test_elastic_restore_across_meshes(tmp_path):
    """A checkpoint written under one topology restores under another
    (shardings arg re-places arrays) — the elastic-scaling contract."""
    mgr = CheckpointManager(str(tmp_path))
    t = _tree(3)
    mgr.save(5, t)
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"a": NamedSharding(mesh, P()), "b": {"c": NamedSharding(mesh, P())}}
    _, restored, _ = mgr.restore_latest(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t),
        shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(t["a"]))
    assert restored["a"].sharding == sh["a"]


def test_preemption_flag(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    assert not mgr.preempted
    mgr.simulate_preemption()
    assert mgr.preempted


# ---------------------------------------------------------------------------
# Training integration (loss decreases; resume == uninterrupted)
# ---------------------------------------------------------------------------


def test_train_loop_improves_loss():
    from repro.launch.train import train
    out = train("qwen2-1.5b", steps=30, batch=8, seq=64, lr=3e-3)
    assert np.mean(out["losses"][-5:]) < np.mean(out["losses"][:5]) * 0.7


def test_resume_reproduces_uninterrupted_run(tmp_path):
    """Train 6 steps; vs train 3, 'crash', resume 3 — identical params."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 32, 4)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=6)
    pcfg = ParallelConfig()
    lm = LM(cfg, HOST_MESH)
    step_fn = jax.jit(make_train_step(lm, tcfg, pcfg))

    def run(n_steps, params, opt, start=0):
        data = DataIterator(cfg, shape, seed=3)
        data.load_state_dict({"step": start, "seed": 3})
        for _ in range(n_steps):
            params, opt, _ = step_fn(params, opt, next(data))
        return params, opt

    p0, _, o0, _ = init_train_state(lm, tcfg, jax.random.key(0))
    pa, oa = run(6, p0, o0)

    p1, _, o1, _ = init_train_state(lm, tcfg, jax.random.key(0))
    pb, ob = run(3, p1, o1)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, {"params": pb, "opt": ob}, extra={"data": {"step": 3, "seed": 3}})
    _, state, extra = mgr.restore_latest(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     {"params": pb, "opt": ob}))
    pc, oc = run(3, state["params"], state["opt"], start=extra["data"]["step"])

    for va, vc in zip(jax.tree.leaves(pa), jax.tree.leaves(pc), strict=True):
        np.testing.assert_allclose(np.asarray(va), np.asarray(vc),
                                   rtol=1e-6, atol=1e-7)


def test_microbatched_grads_match_full_batch():
    """Gradient accumulation must equal the full-batch gradient (mean CE
    over equal-sized microbatches is exact)."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 32, 8)
    tcfg = TrainConfig(lr=0.0, warmup_steps=1, total_steps=2, grad_clip=0.0)
    lm = LM(cfg, HOST_MESH)
    p, _, o, _ = init_train_state(lm, tcfg, jax.random.key(1))
    batch = make_batch(cfg, shape, 0, seed=5)
    f1 = jax.jit(make_train_step(lm, tcfg, ParallelConfig(microbatches=1)))
    f4 = jax.jit(make_train_step(lm, tcfg, ParallelConfig(microbatches=4)))
    _, o1, m1 = f1(p, o, batch)
    _, o4, m4 = f4(p, o, batch)
    # same loss and same first-moment buffers (loss is mean over tokens)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-3)
    assert float(m1["grad_norm"]) == pytest.approx(float(m4["grad_norm"]),
                                                   rel=1e-3)
    l1 = jax.tree.leaves(o1["m"])
    l4 = jax.tree.leaves(o4["m"])
    # bf16 forward/backward: accumulation order differs between the two
    # paths; agreement is to bf16 resolution, not f32
    worst = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(l1, l4))
    assert worst < 8e-3


def test_watchdog_flags_stragglers():
    import time
    wd = StepWatchdog(threshold=3.0)
    for _ in range(5):
        wd.start(); time.sleep(0.01); wd.stop()
    wd.start(); time.sleep(0.2); slow = wd.stop()
    assert slow and wd.straggler_steps == 1


# ---------------------------------------------------------------------------
# Serving engine == sequential greedy decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "zamba2-1.2b"])
def test_engine_matches_sequential_greedy(arch):
    # f32 compute: greedy equivalence needs argmax stability, and bf16
    # leaves near-ties one ulp apart that flip with the batch shape (the
    # engine decodes B=3, the reference B=1).
    import dataclasses
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              compute_dtype="float32",
                              kv_cache_dtype="float32")
    lm = LM(cfg, HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(3)))

    def reference(prompt, n_new):
        caches, _ = split_params(lm.init_cache(1, 128))
        toks = list(prompt)
        for t in range(len(prompt) + n_new - 1):
            tok = jnp.array([[toks[t]]], jnp.int32)
            logits, caches = lm.decode_step(values, caches, tok, jnp.int32(t))
            if t >= len(prompt) - 1:
                logits = logits.astype(jnp.float32
                                       ).at[..., cfg.vocab_size:].set(-1e9)
                toks.append(int(jnp.argmax(logits, axis=-1)[0]))
        return toks[len(prompt):]

    eng = ServingEngine(lm, values, max_batch=3, max_len=128)
    prompts = [[5, 6, 7, 8], [1, 2, 3], [9, 4, 2, 7, 5, 3], [11, 12]]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    for r in done:
        assert r.generated == reference(prompts[r.rid], 5), r.rid


def test_train_with_int8_ef_compression_converges():
    """End-to-end training with int8 error-feedback gradient compression in
    the loop still reduces loss at a comparable rate."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    shape = ShapeConfig("t", "train", 32, 8)
    tcfg = TrainConfig(lr=3e-3, warmup_steps=2, total_steps=20)
    lm = LM(cfg, HOST_MESH)
    from repro.runtime.train_lib import init_train_state, make_train_step

    def run(pcfg):
        p, _, o, _ = init_train_state(lm, tcfg, jax.random.key(0), pcfg)
        step = jax.jit(make_train_step(lm, tcfg, pcfg))
        losses = []
        from repro.data import DataIterator
        it = DataIterator(cfg, shape, seed=11)
        for _ in range(15):
            p, o, m = step(p, o, next(it))
            losses.append(float(m["loss"]))
        return losses

    plain = run(ParallelConfig())
    comp = run(ParallelConfig(grad_compression="int8_ef"))
    assert comp[-1] < comp[0] * 0.8          # still learns
    # compressed run tracks the plain run loosely
    assert abs(comp[-1] - plain[-1]) / plain[-1] < 0.5


def test_compile_cache_defaults_to_checkout_root(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to <checkout>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import CHECKOUT_ROOT, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(CHECKOUT_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (CHECKOUT_ROOT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_compile_cache_env_dir_wins(tmp_path):
    """Set JAX_COMPILATION_CACHE_DIR: the cache is written there, and the
    checkout's .jax_cache is neither set nor created."""
    import subprocess
    import sys
    from repro.launch.compile_cache import CHECKOUT_ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               PYTHONPATH=str(CHECKOUT_ROOT / "src"))
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    before = sorted((CHECKOUT_ROOT / ".jax_cache").glob("*"))
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    assert out == [str(tmp_path / "cc")] * 2
    assert any((tmp_path / "cc").iterdir())
    assert sorted((CHECKOUT_ROOT / ".jax_cache").glob("*")) == before
