"""One run of one cell: set-up, the measured window, the output check.

``run_cell`` does everything after the look for a chip, so a test can run
it on the CPU at a tiny size with the timed path broken underneath.

Set-up (``setup_s``, from process start to the window's start):
the configuration's weights made on the device from the seed in one jitted
call; the engine built on them; a warm-up that runs, through the engine's
public ``submit``/``step``, every prompt length of this run's schedule once
and every prefill length bucket in every slot, so that nothing compiles in
the window; and ``preroll_s`` of the schedule's own arrivals, so that slot
occupancy is steady when the window opens.

After the window the loop stops.  ``memory_peak_bytes`` is read, the
program's engine and caches are freed, and the reference runs over a
sample of the finished requests (``lib.check``).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import random
import time

import jax

from lib import check, measure, serve, traffic
from lib.trace import WINDOW

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileLog:
    """perf_counter times of every compile or compile-cache load."""

    def __init__(self):
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


@dataclasses.dataclass
class Outcome:
    run: measure.Run
    compiles_in_window: int
    memory_peak_bytes: int | None
    gap: float | None            # widest logit gap over the sample
    control_gap: float | None    # the control's, when asked for
    gaps: list                   # per sampled request, the gap per token
    control_gaps: list
    sampled_tokens: int
    attempted: int


def _warm_up(engine, schedule, request_type, max_batch: int,
             bucket_of) -> None:
    """Every prompt length of ``schedule`` once, then ``max_batch`` copies
    of one length per prefill bucket (every slot then has run the insert of
    every bucket); one new token each, ``max_batch`` to a step."""
    lengths = sorted({len(r.prompt) for r in schedule})
    reps = {}
    for n in lengths:
        reps.setdefault(bucket_of(n - 1), n)
    todo = lengths + [n for n in reps.values() for _ in range(max_batch)]
    rng = random.Random("chipbench-warm-up")
    for i in range(0, len(todo), max_batch):
        for j, n in enumerate(todo[i:i + max_batch]):
            engine.submit(request_type(
                rid=-(i + j + 1),
                prompt=[rng.randrange(100) for _ in range(n)],
                max_new_tokens=1))
        done = 0
        while done < len(todo[i:i + max_batch]):
            done += len(engine.step())


@dataclasses.dataclass
class Setup:
    """The program built for one cell and seed."""

    cell: object
    weights: dict
    engine: object
    request_type: type
    bucket_of: object
    compile_log: CompileLog


def build(cell, seed: int) -> Setup:
    """Weights from ``seed`` and the engine on them."""
    from repro.models.common import HOST_MESH, split_params
    from repro.models.model import LM
    from repro.serving.buckets import bucket_len
    from repro.serving.engine import Request, ServingEngine

    from lib.weights import make_weights, program_tree

    log = CompileLog()
    lm = LM(cell.model.program_config(), HOST_MESH)
    shapes = jax.eval_shape(lambda k: split_params(lm.init(k))[0],
                            jax.random.key(0))
    weights = make_weights(cell.model, shapes["embed"]["table"].shape[0],
                           seed)
    tree = program_tree(weights, shapes, cell.model.reference.PROGRAM_LEAF)
    engine = ServingEngine(lm, tree,
                           max_batch=cell.engine["max_batch"],
                           max_len=cell.engine["max_len"])
    return Setup(cell=cell, weights=weights, engine=engine,
                 request_type=Request, bucket_of=bucket_len,
                 compile_log=log)


def serve_window(setup: Setup, sched, mix, seconds: float,
                 trace_dir: str | None):
    """Warm up for ``sched``, run its pre-roll, then the measured window;
    ``trace_dir`` set traces the window into it.  Returns (record, window
    start, window end, compiles in the window)."""
    from jax.profiler import TraceAnnotation

    eng, req = setup.engine, setup.request_type
    _warm_up(eng, sched, req, eng.max_batch, setup.bucket_of)
    record = serve.Record()
    pending = collections.deque(sched)
    t0 = time.perf_counter()
    inflight = serve.drive(eng, pending, t0=t0, until=t0 + mix.preroll_s,
                           record=record, request_type=req)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    start = max(time.perf_counter(), t0 + mix.preroll_s)
    end = start + seconds
    with TraceAnnotation(WINDOW):
        serve.drive(eng, pending, t0=t0, until=end, record=record,
                    request_type=req, inflight=inflight)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return record, start, end, setup.compile_log.between(start, end)


def run_cell(cell, *, seed: int, seconds: float, trace_dir: str | None,
             peaks: dict, t_start: float, control: bool = False) -> Outcome:
    """One run of ``cell`` (a ``lib.spec.Cell``); ``trace_dir`` set traces
    the window into it; ``control`` also reads the control's gap."""
    setup = build(cell, seed)
    sched = traffic.schedule(cell.mix, seed=seed, seconds=seconds,
                             vocab_size=cell.model.vocab_size)
    record, start, end, compiles = serve_window(setup, sched, cell.mix,
                                                seconds, trace_dir)
    stats = jax.devices()[0].memory_stats() or {}
    run = measure.Run(record=record, start=start, end=end, spec=cell.model,
                      max_batch=setup.engine.max_batch, peaks=peaks,
                      setup_s=start - t_start)
    weights = setup.weights
    del setup
    gc.collect()
    gaps, ctl, picked = outputs(weights, cell, record, seed, control)
    return Outcome(run=run, compiles_in_window=compiles,
                   memory_peak_bytes=stats.get("peak_bytes_in_use"),
                   gap=check.widest_gap(gaps) if gaps else None,
                   control_gap=check.widest_gap(ctl) if ctl else None,
                   gaps=gaps, control_gaps=ctl,
                   sampled_tokens=sum(len(s.tokens) for s in picked),
                   attempted=len(run.due_in_window()))


def outputs(weights, cell, record, seed: int, control: bool):
    """(per-token gaps, the control's, the sampled requests) over a sample
    of the finished requests; empty lists where there is nothing."""
    finished = [s for s in record.served.values() if s.done]
    picked = check.sample(finished, seed)
    if not picked:
        return [], [], picked
    rows = cell.mix.output.bounds()[1]
    gaps = check.gaps(weights, cell.model, picked, out_rows=rows)
    ctl = (check.gaps(weights, cell.model, picked, out_rows=rows,
                      control=True) if control else [])
    return gaps, ctl, picked
