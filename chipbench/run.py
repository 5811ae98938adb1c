#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs the cell of ``BENCHMARK.json`` named ``<cell>`` on the chip this
process sees: set-up (weights from the seed, warm-up, pre-roll), then the
cell's open-loop traffic for ``--seconds`` against the program's
``ServingEngine``, then the output check against the plain reference.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``; its per-layer metrics, read from a profiler trace of the
window, with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each compared number beside its limit.  The same
numbers are the last lines of standard error.

No result is printed, and the exit code is not 0, when JAX finds no TPU, a
device kind missing from ``lib/peaks.py``, or fewer chips than the cell
asks for.  JAX's compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache/`` at the checkout's root.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

#: fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run's profile goes; removed once reduced
TRACE_DIR = ROOT / ".chipbench_trace"


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip_or_exit(chips: int):
    """(devices, peaks); exits non-zero unless JAX sees ``chips`` TPUs of
    a kind in the peaks table."""
    import jax

    from lib.peaks import peaks_for

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chipbench: JAX found no TPU (platform {d0.platform!r})")
    try:
        peaks = peaks_for(d0.device_kind)
    except LookupError as e:
        sys.exit(f"chipbench: {e}")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs, peaks


def enable_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, the small eager ones too, so that a second run of a
    # cell finds all it needs in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def result(cell, bench, out, devs, trace, checks) -> dict:
    from lib import measure
    from lib.spec import metric_entries

    run = out.run
    metrics = {}
    for m in metric_entries(bench, cell.name, trace is not None):
        if trace is None:
            value = measure.END_TO_END[m["name"]](run)
        else:
            value = measure.reader(HERE / "metrics", m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    from lib.check import correct

    res = {"correct": correct(checks),
           "attempted": out.attempted, "failed": 0, "metrics": metrics,
           "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        res["breakdown"] = trace.breakdown()
    res["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return res


def main(argv=None) -> None:
    args = parse(argv)
    from lib import harness
    from lib.spec import load_benchmark, load_cell

    bench = load_benchmark(ROOT)
    cell = load_cell(args.workload, ROOT)
    devs, peaks = chip_or_exit(cell.chips)
    enable_cache()
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace_dir=trace_dir, peaks=peaks,
                           t_start=T_START)
    trace = None
    if trace_dir is not None:
        from lib.trace import find_xplane, reduce_file
        t0 = time.perf_counter()
        try:
            trace = out.run.trace = reduce_file(find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        note(f"trace read in {time.perf_counter() - t0:.2f} s")
    from lib import check, measure
    lag = measure.reader(HERE / "metrics", "gen.lag_p95_ms")(out.run)
    both = check.evaluate(out.gaps, {k: None for k in check.NUMBERS})
    note(f"window: {out.attempted} requests due, {out.compiles_in_window} "
         f"compiles in the window, generator lateness p95 {lag} ms, "
         f"{out.sampled_tokens} served tokens checked, "
         + ", ".join(f"{k} {v}" for k, (v, _) in both.items()))
    checks = check.evaluate(out.gaps, cell.model.data["check"])
    res = result(cell, bench, out, devs, trace, checks)
    for name, c in res["checks"].items():
        note(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
