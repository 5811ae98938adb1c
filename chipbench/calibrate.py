#!/usr/bin/env python3
"""Measurements that define a cell, made once on the chip; the benchmark's
own runs never make them.

    python chipbench/calibrate.py sweep --workload W --seed N \
        --seconds S --rates 3,4,5,6
    python chipbench/calibrate.py control --workload W --seeds 1,2,3 \
        --seconds S
    python chipbench/calibrate.py trace --workload W --seed N --seconds S \
        --out DIR

``sweep``: the knee.  One process; the cell's mix at each offered rate in
turn (ascending), on a fresh engine, with its pre-roll and a window of
``--seconds``; per rate one JSON line with the queue at the window's start
and end, the output rate and the tails.  The knee is the highest rate at
which the queue does not grow across the window.

``control``: the readings a check limit is set from.  A whole run of the
cell per seed, at the cell's own load; per seed one JSON line with the
program's widest logit gap and the control's (the reference on float8
weights ranking the same positions), and the end-to-end metrics.

``trace``: a traced run whose profile is kept in ``DIR``, with a summary of
its planes, lines and busiest events (``describe.json``) and the per-layer
metrics read from it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def sweep(cell, args, peaks) -> None:
    import gc

    from lib import harness, measure, traffic

    for rate in [float(r) for r in args.rates.split(",")]:
        setup = harness.build(cell, args.seed)
        mix = dataclasses.replace(cell.mix, rate=rate)
        sched = traffic.schedule(mix, seed=args.seed, seconds=args.seconds,
                                 vocab_size=cell.model.vocab_size)
        rec, start, end, compiles = harness.serve_window(
            setup, sched, mix, args.seconds, None)
        run = measure.Run(record=rec, start=start, end=end,
                          spec=cell.model, max_batch=setup.engine.max_batch,
                          peaks=peaks, setup_s=0.0)

        def waiting(t):
            return sum(1 for s in rec.served.values()
                       if s.due <= t and (s.admit is None or s.admit > t))

        steps = run.window_steps()
        plain = sorted(st.end - st.start for st in steps if not st.admitted)
        _emit({"rate": rate, "due": len(run.due_in_window()),
               "queue_start": waiting(start), "queue_end": waiting(end),
               "output_tok_per_s": measure.output_tok_per_s(run),
               "ttft_p75_s": measure.ttft_p75_s(run),
               "itl_p95_ms": measure.itl_p95_ms(run),
               "occupancy": measure.reader(HERE / "metrics",
                                           "engine.occupancy")(run),
               "decode_step_wall_ms_median":
                   1e3 * plain[len(plain) // 2] if plain else None,
               "compiles_in_window": compiles})
        grew = waiting(end) > waiting(start) + setup.engine.max_batch
        del setup, rec, run
        gc.collect()
        if grew:
            break


def control(cell, args, peaks) -> None:
    import numpy as np

    from lib import harness, measure

    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace_dir=None, peaks=peaks, t_start=t0,
                               control=True)
        flat = np.concatenate(out.gaps)
        cflat = np.concatenate(out.control_gaps)
        _emit({"seed": seed, "gap": out.gap, "control_gap": out.control_gap,
               "off_argmax": float((flat > 0).mean()),
               "control_off_argmax": float((cflat > 0).mean()),
               "mean_gap": float(flat.mean()),
               "control_mean_gap": float(cflat.mean()),
               "sampled_tokens": out.sampled_tokens,
               "attempted": out.attempted,
               "compiles_in_window": out.compiles_in_window,
               "memory_peak_bytes": out.memory_peak_bytes,
               "check_s": time.perf_counter() - out.run.end,
               **{k: f(out.run) for k, f in measure.END_TO_END.items()}})


def trace(cell, args, peaks, bench) -> None:
    from lib import harness, measure
    from lib.spec import metric_entries
    from lib.trace import describe, export, find_xplane, reduce_file

    out_dir = Path(args.out).resolve()
    out_dir.mkdir(parents=True, exist_ok=True)
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace_dir=str(out_dir / "profile"), peaks=peaks,
                           t_start=T_START)
    path = find_xplane(str(out_dir / "profile"))
    t0 = time.perf_counter()
    with open(out_dir / "describe.json", "w") as f:
        json.dump(describe(path), f, indent=1)
    export(path, str(out_dir / "events.json.gz"))
    red = reduce_file(path)
    out.run.trace = red
    metrics = {m["name"]: measure.reader(HERE / "metrics", m["name"])(out.run)
               for m in metric_entries(bench, cell.name, True)}
    _emit({"seed": args.seed, "trace_bytes": os.path.getsize(path),
           "reduce_s": time.perf_counter() - t0, "busy_s": red.busy_s,
           "window_s": red.window_s, "metrics": metrics,
           "modules": red.modules, "breakdown": red.breakdown(),
           "gap": out.gap, "compiles_in_window": out.compiles_in_window,
           "memory_peak_bytes": out.memory_peak_bytes})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("sweep", "control", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="calibrate_trace")
    args = ap.parse_args()

    from lib.spec import load_benchmark, load_cell

    bench = load_benchmark(bench_run.ROOT)
    cell = load_cell(args.workload, bench_run.ROOT)
    _, peaks = bench_run.chip_or_exit(cell.chips)
    bench_run.enable_cache()
    if args.what == "sweep":
        sweep(cell, args, peaks)
    elif args.what == "control":
        control(cell, args, peaks)
    else:
        trace(cell, args, peaks, bench)


if __name__ == "__main__":
    main()
