"""Planner-throughput benchmark: scalar loops vs the batched sweep engine.

The design-space search is the repo's hottest non-JAX path: TileTuner walks
up to ~810 candidate tiles per GEMM shape and the GAP8 simulator scores 14
micro-kernels x 3 variants per layer.  This benchmark times the pre-batching
scalar loops (``tune_scalar`` / ``best_microkernel_scalar``, the preserved
reference oracles) against the vectorized batch engine on the combined
Table-2 + all-arch planning workload, asserts the selections are identical,
and records the speedups.

Workloads:

  table2_gap8  the paper's Table-2 grid — 19 MobileNetV1 layers x 3
               variants; scalar = per-candidate ``simulate`` loop, batched =
               ``best_microkernel_batch`` per variant.
  allarch_tpu  every arch config's GEMM shapes through TileTuner; scalar =
               per-shape ``candidate_tiles`` + ``estimate`` loop, batched =
               one deduped ``tune_batch`` lattice evaluation.
  cold_tune    single-shape planning latency (scalar loop vs 1-shape batch).
  sim_latency  serving-simulator smoke — 2000 Poisson requests through an
               analytically priced tpu-v5e cell (``repro.simulate``);
               asserts a finite p99 and records events/second.
  sim_faults   overload-resilience smoke — the same cell driven at 2.5x its
               sustainable rate under the ``storm`` fault scenario with a
               per-request deadline; asserts the shedder keeps the run
               finite (shed > 0, unfinished == 0) and records the shed
               fraction and survivor tail.
  design_frontier
               design-space exploration smoke (``repro.design``) — scores
               the 64-point ``gap9-sweep`` generated space on the Table-2
               grid and reduces it to the Pareto frontier twice; asserts
               the two frontiers are byte-identical (determinism) and
               records designs/second so frontier-scoring cost is tracked
               per SHA.
  obs_overhead observability tax (``repro.obs``) — the Table-2 sweep with
               span tracing disabled vs the span entry point stubbed out;
               asserts the disabled instrumentation costs < 2% and records
               the enabled-mode cost alongside.
  mixed_precision_sweep
               the Table-2 grid re-planned under three mixed-precision
               configs on gap9-fc (``PrecisionConfig`` axis); scalar =
               per-problem ``best_microkernel_scalar`` loop, batched =
               ``best_microkernel_batch`` with quantize-traffic lattice
               rows; asserts batched selections match the scalar oracle
               and records the speedup plus the aggregate quantize share.

``BENCH_planner.json`` at the repo root is an **append-only perf
trajectory**: every run appends one record keyed by the current git SHA
(re-runs at the same SHA replace that SHA's record), so the file accumulates
one point per PR instead of overwriting history.  CI runs this script and
separately asserts the file parses.

  PYTHONPATH=src python benchmarks/bench_planner.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import ARCH_IDS, get_config
from repro.core.autotune import (
    clear_tune_cache,
    model_gemm_shapes,
    tune_batch,
    tune_scalar,
)
from repro.core.hardware import GAP8_FC
from repro.core.mobilenet import TABLE2
from repro.core.simulator import (
    best_microkernel_batch,
    best_microkernel_scalar,
)
from repro.core.variants import Variant

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_planner.json")
TRAJECTORY_SCHEMA = "bench_planner/trajectory-v1"


def git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True, stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def load_trajectory(path: str) -> dict:
    """Read the trajectory; a legacy single-snapshot file (pre-trajectory
    format: the report dict itself) migrates to the first record."""
    if not os.path.exists(path):
        return {"schema": TRAJECTORY_SCHEMA, "records": []}
    with open(path) as f:
        data = json.load(f)
    if "records" not in data:
        data = {"schema": TRAJECTORY_SCHEMA,
                "records": [{"sha": "pre-trajectory", **data}]}
    return data


def _best_of(fn, reps=3):
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return out, min(times)


def bench_table2_gap8() -> dict:
    probs = [row.problem for row in TABLE2]

    def scalar():
        return [[best_microkernel_scalar(GAP8_FC, v, p) for p in probs]
                for v in Variant]

    def batched():
        return [best_microkernel_batch(GAP8_FC, v, probs) for v in Variant]

    s_out, s_t = _best_of(scalar)
    b_out, b_t = _best_of(batched)
    for srow, brow in zip(s_out, b_out):
        for s, b in zip(srow, brow):
            assert s.micro_kernel == b.micro_kernel, "selection drift"
    return {"scalar_s": s_t, "batched_s": b_t, "speedup": s_t / b_t,
            "problems": len(probs), "grid_points": len(probs) * 3}


def bench_allarch_tpu() -> dict:
    shapes = []
    for arch in ARCH_IDS:
        shapes += model_gemm_shapes(get_config(arch))
    unique = list(dict.fromkeys(shapes))

    def scalar():
        return [tune_scalar(s) for s in unique]

    def batched():
        clear_tune_cache()  # cold: time the lattice evaluation, not the memo
        return tune_batch(shapes)

    s_out, s_t = _best_of(scalar)
    b_out, b_t = _best_of(batched)
    got = {s: d.tile for s, d in zip(shapes, b_out)}
    for s, d in zip(unique, s_out):
        assert got[s] == d.tile, f"selection drift on {s}"
    return {"scalar_s": s_t, "batched_s": b_t, "speedup": s_t / b_t,
            "shapes": len(shapes), "unique_shapes": len(unique)}


def bench_cold_tune() -> dict:
    from repro.core.tpu_model import GemmShape
    shape = GemmShape(4096, 11008, 4096, "bf16")
    _, s_t = _best_of(lambda: tune_scalar(shape), reps=5)

    def batched():
        clear_tune_cache()
        return tune_batch([shape])

    _, b_t = _best_of(batched, reps=5)
    return {"scalar_s": s_t, "batched_s": b_t, "speedup": s_t / b_t}


def bench_measure_fidelity() -> dict:
    """Host measure→fit→validate smoke loop (repro.measure): the fitted
    host MAPE joins the per-SHA trajectory, so model-accuracy regressions
    show up next to planner-perf regressions."""
    import tempfile

    from repro import measure

    with tempfile.TemporaryDirectory() as td:
        store = measure.SampleStore(os.path.join(td, "smoke.jsonl"))
        t0 = time.perf_counter()
        camp = measure.run_campaign("smoke", machine="host-cpu",
                                    harness="host-numpy", store=store)
        campaign_s = time.perf_counter() - t0
        spec, fit = measure.fit_from_store(store, "host-cpu",
                                           name="host-cpu-bench", date=None,
                                           on_nonpositive="free")
        val = measure.validate_spec(spec, store)
        return {
            "samples": len(camp.samples),
            "campaign_s": campaign_s,
            "fit_residual_rms_s": fit.residual_rms_s,
            "dropped_columns": list(fit.dropped),
            "mape_pct": val.mape,
            "median_ape_pct": val.median_ape,
            "worst_ape_pct": 100.0 * val.worst.ape,
        }


def bench_sim_latency() -> dict:
    """Serving-simulator smoke (repro.simulate): Poisson traffic through an
    analytically priced tpu-v5e cell.  Asserts the tail is finite (every
    request finished) and records the event-loop throughput so simulator
    perf regressions land in the trajectory."""
    from repro.simulate import PoissonTraffic, ServiceModel, simulate_serving

    cfg = get_config("qwen2-1.5b")
    service = ServiceModel.from_plans(cfg, batch=8, machine="tpu-v5e")
    traffic = PoissonTraffic(rate=500, prompt_len=(8, 200), decode_len=16,
                             seed=0)

    def run():
        return simulate_serving(service, traffic, max_batch=8,
                                requests=2000,
                                config={"machine": "tpu-v5e",
                                        "dtype": "bf16"})
    rep, t = _best_of(run)
    assert rep.finite, "simulated p99 latency must be finite"
    events = rep.steps + 2 * rep.requests["submitted"]
    return {
        "requests": rep.requests["submitted"],
        "steps": rep.steps,
        "wall_s": t,
        "events_per_s": events / t,
        "p99_latency_s": rep.latency["p99"],
        "goodput_tps": rep.goodput_tps,
    }


def bench_sim_faults() -> dict:
    """Overload-resilience smoke (repro.simulate.faults): the tpu-v5e cell
    from ``sim_latency`` driven at 2.5x its sustainable arrival rate under
    the ``storm`` scenario (throttle windows + slot failures + a flash
    crowd) with a per-request deadline.  Without shedding the queue would
    grow without bound; the deadline-armed simulator must shed the excess
    and finish everything else."""
    from repro.simulate import PoissonTraffic, ServiceModel, simulate_serving

    cfg = get_config("qwen2-1.5b")
    service = ServiceModel.from_plans(cfg, batch=8, machine="tpu-v5e")
    decode_len = 16
    sustainable_rps = 8 / (service.decode_step_s * decode_len)
    deadline_s = 5 * decode_len * service.decode_step_s
    traffic = PoissonTraffic(rate=2.5 * sustainable_rps, prompt_len=(8, 200),
                             decode_len=decode_len, seed=0)

    def run():
        return simulate_serving(service, traffic, max_batch=8,
                                requests=2000, deadline_s=deadline_s,
                                faults="storm",
                                config={"machine": "tpu-v5e",
                                        "dtype": "bf16"})
    rep, t = _best_of(run)
    assert rep.shed_count > 0, "a 2.5x overload must shed"
    assert rep.requests["unfinished"] == 0, "shedding must keep the run finite"
    assert rep.finite, "survivor tail must be finite"
    events = rep.steps + 2 * rep.requests["submitted"]
    return {
        "requests": rep.requests["submitted"],
        "finished": rep.requests["finished"],
        "shed": rep.shed_count,
        "shed_fraction": rep.shed_fraction,
        "shed_causes": rep.shed["causes"],
        "slot_failures": rep.faults.get("slot_failures", 0),
        "throttled_steps": rep.faults.get("throttled_steps", 0),
        "deadline_s": deadline_s,
        "overload_factor": 2.5,
        "wall_s": t,
        "events_per_s": events / t,
        "p99_latency_s": rep.latency["p99"],
    }


def bench_design_frontier() -> dict:
    """Design-space frontier smoke (repro.design): score the 64-point
    gap9-sweep generated space on the Table-2 grid and take the Pareto
    frontier.  Runs the scoring twice and asserts the frontiers are
    identical — the determinism the subsystem promises — while the
    trajectory records how much a 64-design sweep costs."""
    from repro.design import get_space, pareto, score_designs

    space = get_space("gap9-sweep")

    def run():
        return pareto(score_designs(space), workload="table2")

    front, t = _best_of(run, reps=2)
    again = pareto(score_designs(space), workload="table2")
    assert front.as_dict() == again.as_dict(), "frontier must be deterministic"
    assert front.frontier, "empty frontier on the gap9-sweep space"
    return {
        "designs": len(space),
        "frontier": len(front.frontier),
        "dominated": len(front.dominated),
        "wall_s": t,
        "designs_per_s": len(space) / t,
        "top_gops": front.frontier[0].throughput,
    }


class _Untraced:
    """A span's surface (context manager + ``set``) that does nothing: the
    un-instrumented baseline of ``bench_obs_overhead``."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


def bench_obs_overhead() -> dict:
    """Observability tax (repro.obs): the Table-2 GEMM sweep timed with
    span tracing disabled (the shipping default) against the same sweep
    with the span entry point stubbed out entirely — the closest reachable
    approximation of un-instrumented code.  Disabled tracing must cost
    under 2% on the planner's hottest loop; the enabled-mode cost rides
    along in the trajectory (recorded, not asserted) so trace-buffer
    regressions show up per SHA too."""
    from repro import obs
    from repro.gemm import sweep
    probs = [row.problem for row in TABLE2]
    reps_inner = 25  # one sweep is ~4ms; batch them so 2% is above noise

    def run_sweeps():
        for _ in range(reps_inner):
            sweep(probs, backends=("analytic-gap8",), machines="gap8-fc",
                  cache=False)

    obs.disable()
    _, disabled_t = _best_of(run_sweeps, reps=5)
    untraced = _Untraced()
    stub, orig = (lambda *a, **k: untraced), obs.span
    try:
        obs.span = stub
        _, stub_t = _best_of(run_sweeps, reps=5)
    finally:
        obs.span = orig
    obs.enable()
    try:
        _, enabled_t = _best_of(run_sweeps, reps=5)
    finally:
        obs.disable()
        obs.clear()
    overhead_pct = 100.0 * (disabled_t - stub_t) / stub_t
    assert overhead_pct < 2.0, (
        f"disabled-tracing overhead {overhead_pct:.2f}% >= 2% budget "
        f"(disabled {disabled_t:.4f}s vs stubbed {stub_t:.4f}s)")
    return {
        "sweeps": reps_inner,
        "stubbed_s": stub_t,
        "disabled_s": disabled_t,
        "enabled_s": enabled_t,
        "disabled_overhead_pct": overhead_pct,
        "enabled_overhead_pct": 100.0 * (enabled_t - stub_t) / stub_t,
        "budget_pct": 2.0,
    }


def bench_mixed_precision_sweep() -> dict:
    """Mixed-precision planning throughput (repro.core.precision): the
    Table-2 grid under three per-operand dtype configs on gap9-fc.  The
    quantize-traffic rows ride the same vectorized lattice, so the batch
    engine must keep both its speedup and its bit-identical selections."""
    from repro import machines
    from repro.core.precision import PrecisionConfig
    from repro.gemm.api import GemmProblem

    gap9 = machines.get("gap9-fc")
    configs = ["int8xint8", "int4xint8->int32", "f32xint8->int32"]
    probs = [GemmProblem.coerce((r.m, r.n, r.k), default_dtype="int8")
             .with_precision(PrecisionConfig.parse(c)).as_problem()
             for c in configs for r in TABLE2]

    def scalar():
        return [[best_microkernel_scalar(gap9, v, p) for p in probs]
                for v in Variant]

    def batched():
        return [best_microkernel_batch(gap9, v, probs) for v in Variant]

    s_out, s_t = _best_of(scalar)
    b_out, b_t = _best_of(batched)
    quant_s = total_s = 0.0
    for srow, brow in zip(s_out, b_out):
        for s, b in zip(srow, brow):
            assert s.micro_kernel == b.micro_kernel, "selection drift"
            assert s.total == b.total, "cost drift"
            quant_s += s.grouped()["quantize"]
            total_s += s.total
    return {"scalar_s": s_t, "batched_s": b_t, "speedup": s_t / b_t,
            "problems": len(probs), "grid_points": len(probs) * 3,
            "precision_configs": configs,
            "quantize_share": quant_s / total_s}


def main() -> None:
    table2 = bench_table2_gap8()
    allarch = bench_allarch_tpu()
    cold = bench_cold_tune()
    fidelity = bench_measure_fidelity()
    sim = bench_sim_latency()
    faults = bench_sim_faults()
    frontier = bench_design_frontier()
    obs_tax = bench_obs_overhead()
    mixed = bench_mixed_precision_sweep()
    combined_scalar = table2["scalar_s"] + allarch["scalar_s"]
    combined_batched = table2["batched_s"] + allarch["batched_s"]
    report = {
        "workloads": {
            "table2_gap8": table2,
            "allarch_tpu": allarch,
            "cold_tune": cold,
            "sim_latency": sim,
            "sim_faults": faults,
            "design_frontier": frontier,
            "obs_overhead": obs_tax,
            "mixed_precision_sweep": mixed,
        },
        "measure_fidelity": fidelity,
        "combined": {
            "scalar_s": combined_scalar,
            "batched_s": combined_batched,
            "speedup": combined_scalar / combined_batched,
        },
    }
    sha = git_sha()
    trajectory = load_trajectory(OUT_PATH)
    trajectory["records"] = (
        [r for r in trajectory["records"] if r.get("sha") != sha]
        + [{"sha": sha, **report}])
    tmp = OUT_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(trajectory, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, OUT_PATH)
    print(json.dumps(report, indent=1, sort_keys=True))
    print(f"\ncombined Table-2 + all-arch speedup: "
          f"{report['combined']['speedup']:.1f}x; smoke-campaign host MAPE "
          f"{fidelity['mape_pct']:.1f}%; sim {sim['events_per_s']:,.0f} "
          f"events/s; storm overload shed {faults['shed_fraction']:.0%} "
          f"with 0 unfinished; design frontier "
          f"{frontier['designs_per_s']:.0f} designs/s "
          f"({frontier['frontier']}/{frontier['designs']} on frontier); "
          f"obs tax {obs_tax['disabled_overhead_pct']:.2f}% disabled / "
          f"{obs_tax['enabled_overhead_pct']:.1f}% enabled; "
          f"mixed-precision sweep {mixed['speedup']:.1f}x batched "
          f"({mixed['quantize_share']:.0%} quantize share) "
          f"(record {sha[:12]} appended to {os.path.abspath(OUT_PATH)}; "
          f"{len(trajectory['records'])} records in trajectory)")


if __name__ == "__main__":
    main()
