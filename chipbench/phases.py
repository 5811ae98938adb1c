#!/usr/bin/env python3
"""Where the chip's time goes in a cell, by the program's spans and scopes.

    python chipbench/phases.py --workload W --seed N --seconds S \
        [--export FILE]
    python chipbench/phases.py --read PROFILE_DIR_OR_EXPORT

The first form runs one traced window of the cell, as ``run.py --trace 1``
does, and prints one JSON line: the end-to-end metrics of the traced run,
the per-layer metrics and breakdown that ``run.py`` reads from it
(``lib.trace``), and ``lib.phases``' reduction of the same trace: device
idle time by innermost program span (``idle_gaps``, beside the harness's
rows), the per-step numbers, the spans, and the device time per decode run
of each scope of the decode program, and the seconds the trace took to
read.  ``--export`` writes what the reductions read to ``FILE``
(``lib.trace.export``: gzipped JSON, small enough to keep as a test's data
for a window of a few seconds).  The second form prints the
reduction of a kept profile (``calibrate.py trace --out``) or of such a
file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402
from lib.phases import DECODE  # noqa: E402
from lib.trace import (export, find_xplane, reduce_export,  # noqa: E402
                       reduce_file)


def summary(red) -> dict:
    """``lib.trace``'s idle rows and ``lib.phases``' reduction
    (``red.phases``) of one trace, side by side."""
    ph = red.phases
    runs = sum(c for m, (c, _) in ph.modules.items() if DECODE in m)
    scopes = {}
    for m, by in ph.scopes.items():
        if DECODE in m:
            for sc, (_, sec) in by.items():
                scopes[sc] = scopes.get(sc, 0.0) + 1e3 * sec / runs
    return {"window_s": ph.window_s, "busy_s": ph.busy_s,
            "per_step": ph.per_step(),
            "idle_gaps": red.breakdown(top=64)["idle_gaps"],
            "spans": ph.spans,
            "decode_ms_by_scope": sorted(([k, v] for k, v in scopes.items()),
                                         key=lambda kv: -kv[1]),
            "modules": ph.modules}


def traced_run(args) -> dict:
    from lib import harness, measure
    from lib.spec import load_benchmark, load_cell, metric_entries

    bench = load_benchmark(bench_run.ROOT)
    cell = load_cell(args.workload, bench_run.ROOT)
    _, peaks = bench_run.chip_or_exit(cell.chips)
    bench_run.enable_cache()
    trace_dir = str(bench_run.TRACE_DIR)
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace_dir=trace_dir, peaks=peaks,
                               t_start=T_START)
        path = find_xplane(trace_dir)
        t0 = time.perf_counter()
        out.run.trace = reduce_file(path)
        read_s = time.perf_counter() - t0
        if args.export:
            export(path, args.export)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = out.run
    return {"seed": args.seed, "correct_gap": out.gap,
            "compiles_in_window": out.compiles_in_window,
            "end_to_end": {k: f(run) for k, f in measure.END_TO_END.items()},
            "per_layer": {m["name"]: measure.reader(
                HERE / "metrics", m["name"])(run)
                for m in metric_entries(bench, cell.name, True)},
            "breakdown": run.trace.breakdown(),
            "trace_read_s": read_s,
            "phases": summary(run.trace)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--export")
    ap.add_argument("--read")
    args = ap.parse_args(argv)
    if args.read:
        src = Path(args.read)
        red = (reduce_file(find_xplane(str(src))) if src.is_dir()
               else reduce_export(str(src)))
        print(json.dumps(summary(red)), flush=True)
        return
    if None in (args.workload, args.seed, args.seconds):
        ap.error("--workload, --seed and --seconds, or --read")
    print(json.dumps(traced_run(args)), flush=True)


if __name__ == "__main__":
    main()
