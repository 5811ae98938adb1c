"""From a run's record (and its trace) to the metrics.

``Run`` is what every reader gets: the loop's record, the measured window,
the configuration, the chip's peaks and, in a traced run, the trace's
reduction.  The end-to-end metrics are computed here; each per-layer metric
is read by its own ``metrics/<name>.py``, a module with one function
``read(run)`` that returns the value, or None where the run has nothing to
read (the metric is then left out of the result).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from lib.stats import percentile


@dataclasses.dataclass
class Run:
    record: object               # lib.serve.Record
    start: float                 # the measured window, perf_counter s
    end: float
    spec: object                 # lib.spec.ModelSpec
    max_batch: int
    peaks: dict
    setup_s: float
    trace: object = None         # lib.trace.Reduction in a traced run

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def due_in_window(self) -> list:
        return [s for s in self.record.served.values()
                if self.start <= s.due < self.end]

    def window_steps(self) -> list:
        return [st for st in self.record.steps
                if st.start >= self.start and st.end <= self.end]

    def window_prefills(self) -> list[int]:
        """Prompt lengths of the requests admitted in the window's steps."""
        return [n for st in self.window_steps() for _, n in st.admitted]


def ttft_p75_s(run: Run) -> float:
    """Due time to the end of the step that produced the first token, over
    every request due in the window; a request with no token by the
    window's end counts at its elapsed time.  The 75th percentile: the
    highest with ten requests beyond it at the ~40 a chat window holds."""
    vals = []
    for s in run.due_in_window():
        first = s.stamps[0] if s.stamps and s.stamps[0] <= run.end else None
        vals.append((first if first is not None else run.end) - s.due)
    return percentile(vals, 75)


def itl_p95_ms(run: Run) -> float:
    vals = []
    for s in run.record.served.values():
        st = [t for t in s.stamps if run.start <= t <= run.end]
        vals.extend(b - a for a, b in zip(st, st[1:]))
    return 1e3 * percentile(vals, 95)


def output_tok_per_s(run: Run) -> float:
    n = sum(1 for s in run.record.served.values() for t in s.stamps
            if run.start <= t <= run.end)
    return n / run.seconds


def setup_s(run: Run) -> float:
    return run.setup_s


END_TO_END = {f.__name__: f for f in (ttft_p75_s, itl_p95_ms,
                                      output_tok_per_s, setup_s)}


def reader(metrics_dir: Path, name: str):
    """``read`` of ``metrics_dir/<name>.py``."""
    from lib.spec import load_module

    return load_module(metrics_dir / f"{name}.py").read
