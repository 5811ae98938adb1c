"""The open loop that drives the program's ``ServingEngine``.

Before each ``step()`` the loop submits every request whose due time has
passed; when nothing is in flight it sleeps until the next due time; after
each ``step()`` it stamps the tokens that step produced (the engine
materialises tokens at the step boundary).  Every call into the engine,
every wait and the loop's own bookkeeping run under a
``jax.profiler.TraceAnnotation`` (``submit``, ``step``, ``wait_arrival``,
``bookkeeping``), so that the trace can say what the host did in each idle
gap of the device.

All times are ``time.perf_counter()`` seconds, the engine's clock.
"""
from __future__ import annotations

import dataclasses
import time

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Served:
    """One request as the loop saw it."""

    rid: int
    due: float
    prompt_len: int
    max_new: int
    submit: float | None = None
    admit: float | None = None
    stamps: list = dataclasses.field(default_factory=list)
    done: bool = False
    engine_req: object = None

    @property
    def tokens(self) -> list:
        return list(self.engine_req.generated) if self.engine_req else []


@dataclasses.dataclass
class Step:
    start: float
    end: float
    tokens: int                  # tokens this step produced (active slots)
    admitted: list               # (rid, prompt length) admitted in the step
    positions: list              # cache position each produced token read


@dataclasses.dataclass
class Record:
    """What one run of the loop saw."""

    served: dict = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)
    lateness: list = dataclasses.field(default_factory=list)  # (due, lag)


def drive(engine, requests, *, t0: float, until: float, record: Record,
          request_type, inflight: dict | None = None) -> dict:
    """Run the open loop from now until ``until``.  ``requests`` are the
    not yet submitted ones, due at ``t0 + due_s``, in due order; it is
    consumed from the front.  ``inflight`` (rid -> Served) carries the
    submitted, unfinished requests across calls; returned."""
    inflight = {} if inflight is None else inflight
    while True:
        now = time.perf_counter()
        if now >= until:
            return inflight
        if requests and t0 + requests[0].due_s <= now:
            with TraceAnnotation("submit"):
                while requests and t0 + requests[0].due_s <= now:
                    r = requests.popleft()
                    s = Served(rid=r.rid, due=t0 + r.due_s,
                               prompt_len=len(r.prompt), max_new=r.max_new)
                    s.engine_req = request_type(rid=r.rid,
                                                prompt=list(r.prompt),
                                                max_new_tokens=r.max_new)
                    engine.submit(s.engine_req)
                    s.submit = time.perf_counter()
                    record.lateness.append((s.due, s.submit - s.due))
                    record.served[r.rid] = s
                    inflight[r.rid] = s
        if not inflight:
            nxt = t0 + requests[0].due_s if requests else until
            with TraceAnnotation("wait_arrival"):
                time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))
            continue
        start = time.perf_counter()
        with TraceAnnotation("step"):
            finished = engine.step()
        end = time.perf_counter()
        with TraceAnnotation("bookkeeping"):
            _account(record, inflight, finished, start, end)


def _account(record: Record, inflight: dict, finished, start: float,
             end: float) -> None:
    produced, admitted, positions = 0, [], []
    for s in inflight.values():
        req = s.engine_req
        if s.admit is None and req.t_admit is not None:
            s.admit = req.t_admit
            admitted.append((s.rid, s.prompt_len))
        new = len(req.generated) - len(s.stamps)
        for _ in range(new):
            # the token read the cache up to its own input's position
            positions.append(s.prompt_len - 1 + len(s.stamps))
            s.stamps.append(end)
        produced += new
    for req in finished:
        s = inflight.pop(req.rid)
        s.done = True
    record.steps.append(Step(start=start, end=end, tokens=produced,
                             admitted=admitted, positions=positions))
