#!/usr/bin/env python3
"""Bring-up check: the main path runs on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, on four chips

One chip:

* kernels: every GEMM shape qwen2-1.5b's decode step (M = max_batch) and
  prefill (M = the prompt bucket) plan on the ``pallas`` backend, plus one
  ``K_OUTER`` tile and one int8 case, runs compiled on the chip.  Each result
  is compared with ``jnp.dot`` (bf16 within 1e-2 of the largest |ref|, int8
  exact), and the lowered call must hold ``tpu_custom_call``: the Pallas
  kernel ran, not the jnp reference.
* serving: ``launch.serve.serve_demo`` serves qwen2-1.5b at its published
  widths (random weights from a seed): 8 requests of 16 new tokens each.
  Every request must finish with 16 in-vocab tokens, serving must plan no
  Pallas shape the kernel phase did not check, and a full-sequence forward
  of the same weights on the jnp reference GEMMs, with no KV cache, must
  rank every served token as its argmax (within ``ARGMAX_TOL``).

Four chips (``--chips 4``): the FSDP x model-sharded train step of
qwen2-1.5b at published widths on a (data 2, model 2) mesh, 3 steps on one
batch of 8 x 512.  The state must be spread over all four devices, the
losses finite and falling, and the step-0 loss must agree with the
unsharded forward on one device for the same parameters and batch.

Everything runs in this one process.  A platform other than ``tpu``, an
unknown device kind or any failed check exits non-zero; compile seconds and
rates are printed as information only.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: device_kind -> the machine-zoo manifest the planner prices against
MACHINE_FOR_KIND = {"TPU v5 lite": "tpu-v5e"}

ARCH = "qwen2-1.5b"
SEED = 0
# serving
N_REQUESTS, MAX_NEW, MAX_BATCH, MAX_LEN = 8, 16, 4, 256
ARGMAX_TOL = 2e-2
# sharded training
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3
# The tied embedding's unit-std rows give logits of std ~sqrt(d_model), so
# the step-0 loss is ~107; AdamW at 1e-3 overshoots (107 -> 345 -> 263 on
# four v5e chips), and 1e-5 keeps every step falling.
TRAIN_LR = 1e-5
LOSS_RTOL = 1e-2


class CompileClock:
    """Seconds XLA spent compiling, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.seconds += duration_secs


def device_or_exit(chips: int):
    """(devices, zoo machine name); exits unless JAX sees enough TPUs of a
    known kind."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d0.platform!r})")
    if d0.device_kind not in MACHINE_FOR_KIND:
        sys.exit(f"chip_smoke: unknown device kind {d0.device_kind!r}; "
                 f"known: {sorted(MACHINE_FOR_KIND)}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    return devs, MACHINE_FOR_KIND[d0.device_kind]


# ---------------------------------------------------------------------------
# One chip: kernels
# ---------------------------------------------------------------------------


def model_pallas_plans(lm, bucket: int):
    """Trace qwen2's decode step and prefill without running them; the
    planned matmuls on the way fill the plan cache."""
    import jax
    import jax.numpy as jnp
    from repro import gemm
    from repro.models.common import split_params

    gemm.clear_plan_cache()
    params = jax.eval_shape(lambda k: split_params(lm.init(k))[0],
                            jax.random.key(SEED))
    caches = jax.eval_shape(
        lambda: split_params(lm.init_cache(MAX_BATCH, MAX_LEN))[0])
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    jax.eval_shape(lm.decode_step, params, caches, i32((MAX_BATCH, 1)),
                   i32((MAX_BATCH,)))
    jax.eval_shape(lm.prefill, params, {"tokens": i32((1, bucket))})
    return gemm.cached_plans("pallas")


def check_plan(plan, key) -> str:
    """Run one plan compiled on the chip against ``jnp.dot``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = plan.problem
    ka, kb = jax.random.split(key)
    if p.dtype == "int8":
        a = jax.random.randint(ka, (p.m, p.k), -128, 128).astype(jnp.int8)
        b = jax.random.randint(kb, (p.k, p.n), -128, 128).astype(jnp.int8)
        acc = jnp.int32
    else:
        dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[p.dtype]
        a = jax.random.normal(ka, (p.m, p.k), jnp.float32).astype(dt)
        b = jax.random.normal(kb, (p.k, p.n), jnp.float32).astype(dt)
        acc = jnp.float32
    fn = jax.jit(functools.partial(plan.execute, interpret=False))
    if "tpu_custom_call" not in fn.lower(a, b).as_text():
        raise AssertionError(f"{plan.describe()}: no Pallas kernel in the "
                             f"lowered call")
    got = np.asarray(fn(a, b))
    ref = np.asarray(jnp.dot(a, b, preferred_element_type=acc))
    if got.shape != ref.shape:
        raise AssertionError(f"{p}: shape {got.shape} != {ref.shape}")
    if p.dtype == "int8":
        if got.dtype != np.int32 or not np.array_equal(got, ref):
            raise AssertionError(f"{plan.describe()}: int8 result differs")
        return "exact"
    err = float(np.max(np.abs(got.astype(np.float32) - ref)))
    scale = float(np.max(np.abs(ref)))
    if not err <= 1e-2 * scale:
        raise AssertionError(f"{plan.describe()}: max |err| {err} > 1e-2 x "
                             f"max |ref| {scale}")
    return f"max|err|/max|ref|={err / scale:.2e}"


def kernels(machine: str, clock: CompileClock):
    """Phase 1.  Returns the (m, n, k, dtype) problems checked."""
    import jax
    from repro import gemm
    from repro.configs import get_config
    from repro.core.tpu_model import GridOrder, TileConfig
    from repro.models.common import HOST_MESH
    from repro.models.model import LM
    from repro.serving.buckets import PREFILL_BUCKETS

    t0, c0 = time.perf_counter(), clock.seconds
    cfg = get_config(ARCH)
    bucket = PREFILL_BUCKETS[0]
    plans = model_pallas_plans(LM(cfg, HOST_MESH), bucket)
    if not plans:
        raise AssertionError("the model planned no GEMM on the pallas "
                             "backend")
    d, f = cfg.d_model, cfg.d_ff
    # K_OUTER keeps C in the input dtype between k steps, so a bf16 result
    # is rounded once per step: the MLP up projection's 3 steps of 512 stay
    # inside the bf16 bound (the down projection's 9 steps of 1024 reached
    # 1.06e-2 of max |ref| on a v5e).
    plans.append(gemm.plan((bucket, f, d), backend="pallas", machine=machine,
                           dtype="bf16",
                           tile=TileConfig(bucket, 512, 512,
                                           GridOrder.K_OUTER)))
    plans.append(gemm.plan((MAX_BATCH, f, d), backend="pallas",
                           machine=machine, dtype="int8"))
    checked = set()
    key = jax.random.key(SEED)
    for i, plan in enumerate(plans):
        if plan.machine != machine:
            raise AssertionError(f"{plan.describe()} priced against "
                                 f"{plan.machine}, the chip is {machine}")
        verdict = check_plan(plan, jax.random.fold_in(key, i))
        print(f"  kernel {plan.describe()}: {verdict}", flush=True)
        p = plan.problem
        checked.add((p.m, p.n, p.k, p.dtype))
    print(f"kernels: {len(plans)} plans ok; compile "
          f"{clock.seconds - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s",
          flush=True)
    return checked


# ---------------------------------------------------------------------------
# One chip: serving
# ---------------------------------------------------------------------------


def reference_shortfall(cfg, out) -> float:
    """Every served token against a full-sequence forward of the same
    weights with no KV cache, on the jnp reference GEMMs instead of the
    Pallas ones.  Fed each served sequence, the reference must rank every
    served token within ``ARGMAX_TOL`` x max |logit| of its best logit
    (near-ties may flip under bf16).  Returns the largest such shortfall."""
    import gc
    import importlib
    from unittest import mock

    import jax
    import numpy as np
    from repro.models.common import HOST_MESH, split_params
    from repro.models.model import LM

    planner = importlib.import_module("repro.gemm.planner")
    lm = LM(cfg, HOST_MESH)
    # the served engine's weights and caches are garbage, held by reference
    # cycles: free them before the same weights are made again
    gc.collect()
    params = split_params(lm.init(jax.random.key(SEED)))[0]
    seqs = [out["prompts"][r] + out["generated"][r]
            for r in range(N_REQUESTS)]
    # right padding: the causal forward leaves the earlier positions alone
    tokens = np.zeros((N_REQUESTS, max(map(len, seqs))), np.int32)
    for r, s in enumerate(seqs):
        tokens[r, :len(s)] = s
    with mock.patch.object(planner, "default_execute_backend",
                           lambda: "reference"):
        fwd = jax.jit(lambda p, t: lm.logits(p, {"tokens": t})[0])
        logits = np.asarray(fwd(params, tokens), np.float32)
    worst = 0.0
    for r, s in enumerate(seqs):
        plen = len(out["prompts"][r])
        # row i predicts token i + 1
        rows = logits[r, plen - 1:len(s) - 1, :cfg.vocab_size]
        got = rows[np.arange(len(rows)), s[plen:]]
        short = (rows.max(-1) - got) / np.abs(rows).max(-1)
        worst = max(worst, float(short.max()))
    if not worst <= ARGMAX_TOL:
        raise AssertionError(f"served tokens differ from the reference "
                             f"forward's argmax by {worst:.3g} x max |logit| "
                             f"> {ARGMAX_TOL}")
    return worst


def serving(checked: set, clock: CompileClock) -> None:
    """Phase 2: serve qwen2-1.5b at published widths."""
    from repro import gemm
    from repro.configs import get_config
    from repro.launch.serve import serve_demo

    t0, c0 = time.perf_counter(), clock.seconds
    out = serve_demo(ARCH, smoke=False, n_requests=N_REQUESTS,
                     max_new=MAX_NEW, max_batch=MAX_BATCH, max_len=MAX_LEN,
                     seed=SEED)
    if out["requests"] != N_REQUESTS:
        raise AssertionError(f"served {out['requests']} of {N_REQUESTS}")
    cfg = get_config(ARCH)
    for rid in range(N_REQUESTS):
        toks = out["generated"][rid]
        if len(toks) != MAX_NEW or not all(0 <= t < cfg.vocab_size
                                           for t in toks):
            raise AssertionError(f"request {rid}: {toks}")
    unchecked = {(p.problem.m, p.problem.n, p.problem.k, p.problem.dtype)
                 for p in gemm.cached_plans("pallas")} - checked
    if unchecked:
        raise AssertionError(f"serving planned Pallas shapes the kernel "
                             f"phase did not check: {sorted(unchecked)}")
    print(f"serving: {N_REQUESTS} requests x {MAX_NEW} tokens at published "
          f"widths; compile {clock.seconds - c0:.1f}s, wall "
          f"{time.perf_counter() - t0:.1f}s, "
          f"{out['tokens'] / out['seconds']:.1f} tok/s incl. compile",
          flush=True)
    t0, c0 = time.perf_counter(), clock.seconds
    worst = reference_shortfall(cfg, out)
    print(f"serving vs reference forward: all {out['tokens']} tokens within "
          f"{worst:.2e} x max |logit| of the argmax; compile "
          f"{clock.seconds - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s",
          flush=True)


# ---------------------------------------------------------------------------
# Four chips: the sharded train step
# ---------------------------------------------------------------------------


def device_bytes(tree) -> dict:
    """Bytes each device holds of ``tree``."""
    import jax
    out = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] += shard.data.nbytes
    return out


def one_device_loss(lm, params, batch) -> float:
    """The unsharded forward on one device, outside any mesh (so on the
    planned Pallas GEMMs), one sequence at a time; every row has the same
    token count, so the mean of row losses is the batch loss."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models.common import cast_for_compute

    one = SingleDeviceSharding(jax.devices()[0])
    # the forward's own compute cast, applied first: the same numerics, at
    # half the bytes on the one device
    cast = jax.jit(functools.partial(
        cast_for_compute, dtype=jnp.dtype(lm.cfg.compute_dtype)))
    p1 = jax.device_put(cast(params), one)
    fwd = jax.jit(lambda p, b: lm.loss_fn(p, b)[0])
    rows = [float(fwd(p1, jax.device_put(
        {k: v[i:i + 1] for k, v in batch.items()}, one)))
        for i in range(TRAIN_BATCH)]
    return sum(rows) / len(rows)


def sharded_train(clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
    from repro.data import make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import LM
    from repro.runtime.sharding import mesh_info, use_mesh
    from repro.runtime.train_lib import init_train_state, make_train_step

    t0, c0 = time.perf_counter(), clock.seconds
    mesh = make_host_mesh(2, 2)
    minfo = mesh_info(mesh, fsdp=True)
    cfg = get_config(ARCH)
    lm = LM(cfg, minfo)
    tcfg = TrainConfig(lr=TRAIN_LR, warmup_steps=0, total_steps=TRAIN_STEPS)
    batch = make_batch(cfg, ShapeConfig("chip-smoke", "train", TRAIN_SEQ,
                                        TRAIN_BATCH), 0, seed=SEED)
    with use_mesh(mesh):
        params, _, opt, _ = init_train_state(lm, tcfg, jax.random.key(SEED),
                                             mesh=mesh)
    held = device_bytes((params, opt))
    total = sum(held.values())
    print("state: " + ", ".join(f"{d.id}:{b / 1e9:.2f}GB"
                                for d, b in held.items())
          + f" of {total / 1e9:.2f}GB", flush=True)
    if min(held.values()) < 0.2 * total or max(held.values()) > 0.35 * total:
        raise AssertionError("train state is not spread over the four "
                             "devices")
    ref = one_device_loss(lm, params, batch)
    with use_mesh(mesh):
        step = jax.jit(make_train_step(lm, tcfg, ParallelConfig(fsdp=True)),
                       donate_argnums=(0, 1))
        losses = []
        for _ in range(TRAIN_STEPS):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    print(f"sharded losses {losses}; one-device step-0 loss {ref}",
          flush=True)
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise AssertionError(f"losses not finite and falling: {losses}")
    if abs(losses[0] - ref) > LOSS_RTOL * abs(ref):
        raise AssertionError(f"step-0 loss {losses[0]} vs one-device "
                             f"forward {ref}")
    print(f"sharded train: {TRAIN_STEPS} steps of {TRAIN_BATCH}x{TRAIN_SEQ} "
          f"on a (data 2, model 2) mesh; compile "
          f"{clock.seconds - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded train step and its "
                         "one-device comparison")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    devs, machine = device_or_exit(args.chips)
    print(f"machine: {machine}; compile cache {enable_compile_cache()}",
          flush=True)
    clock = CompileClock()
    if args.chips == 4:
        sharded_train(clock)
    else:
        serving(kernels(machine, clock), clock)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
