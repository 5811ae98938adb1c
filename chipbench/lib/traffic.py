"""Open-loop traffic for the benchmark: arrival laws and length laws.

The generator reads one traffic mix (a ``traffic/<mix>.json`` file) and
turns it into a request schedule: due time, prompt length, output length
and prompt token ids.

Adapted from the program's ``repro.simulate.traffic`` (Poisson and bursty
arrivals), kept here so that a program change cannot move the yardstick,
with a heavy-tailed ``lognormal`` length law (median, sigma, clipped to
``[lo, hi]``), as in the Azure LLM inference conversation traces, and a
``backlog`` law.

Arrival laws: ``poisson`` at ``rate``, ``bursty`` (bursts of ``burst``
requests ``intra_gap_s`` apart, burst starts Poisson at ``rate / burst``)
and ``backlog`` (``backlog`` requests, every one due when the window
opens).  Poisson arrivals are drawn given their count: the pre-roll and
the window each hold their expected count (rate x seconds, rounded), at
independent uniform times, which is how a Poisson process's arrivals lie
once their count is known (clusters and long gaps as in the process).
Lengths are the law at as many mid-quantiles as there are requests in the
segment, in an order drawn at random: the offered work is the law's.

One draw, every seed.  The due times and the order of the sizes are drawn
once from the mix's name: every seed offers the same arrivals and sizes,
and so the same work in the window.  ``--seed`` draws the prompts' token
ids (and, outside this module, the weights and the sample the output check
reads).  A fresh draw per seed moves the offered work from run to run by
more than any bound the benchmark may set.
"""
from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist

ARRIVAL_KINDS = ("poisson", "bursty", "backlog")


@dataclasses.dataclass(frozen=True)
class LengthLaw:
    """A log-normal token-length law: ``median * exp(sigma * z)`` for a
    standard normal ``z``, rounded and clipped to ``[lo, hi]``."""

    median: float
    sigma: float
    lo: int
    hi: int
    kind: str = "lognormal"

    def __post_init__(self):
        if self.kind != "lognormal":
            raise ValueError(f"unknown length law {self.kind!r}")
        if not (self.median > 0 and self.sigma > 0):
            raise ValueError(f"lognormal length needs median, sigma > 0: "
                             f"{self}")
        if not 1 <= self.lo <= self.hi:
            raise ValueError(f"length needs 1 <= lo <= hi: {self}")

    def bounds(self) -> tuple[int, int]:
        return (self.lo, self.hi)


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix, as its file states it."""

    name: str
    arrival: str
    rate: float | None           # requests per second; None for backlog
    prompt: LengthLaw
    output: LengthLaw
    preroll_s: float = 0.0       # arrivals before the window opens
    burst: int = 1
    intra_gap_s: float = 0.0
    backlog: int = 0             # requests due at window start (backlog)

    def __post_init__(self):
        if self.arrival not in ARRIVAL_KINDS:
            raise ValueError(f"unknown arrival law {self.arrival!r}; "
                             f"have {ARRIVAL_KINDS}")
        if self.arrival == "backlog":
            if self.backlog < 1:
                raise ValueError("a backlog mix needs backlog >= 1")
        elif not (self.rate or 0) > 0:
            raise ValueError(f"{self.arrival} arrivals need rate > 0")
        if self.preroll_s < 0 or self.burst < 1 or self.intra_gap_s < 0:
            raise ValueError(f"bad mix parameters: {self}")

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        d = dict(d)
        d.pop("why", None)
        return cls(name=name, arrival=d.pop("arrival"),
                   rate=d.pop("rate", None),
                   prompt=LengthLaw(**d.pop("prompt")),
                   output=LengthLaw(**d.pop("output")), **d)


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    due_s: float                 # seconds after the pre-roll starts
    prompt: tuple                # token ids
    max_new: int


def _quantiles(law: LengthLaw, n: int) -> list[int]:
    """The law at its n mid-quantiles ``(i + 0.5) / n``."""
    nd = NormalDist()
    return [max(law.lo, min(law.hi, round(
        law.median * math.exp(law.sigma * nd.inv_cdf((i + 0.5) / n)))))
        for i in range(n)]


def _segment(mix: Mix, lo: float, hi: float, rng: random.Random,
             window: bool) -> list[tuple]:
    """(due, prompt length, output length) of the arrivals in [lo, hi),
    the pre-roll or the ``window``."""
    if mix.arrival == "backlog":
        dues = [lo] * (mix.backlog if window else 0)
    else:
        starts = round((hi - lo) * mix.rate / mix.burst)
        dues = sorted(t + k * mix.intra_gap_s
                      for t in (rng.uniform(lo, hi) for _ in range(starts))
                      for k in range(mix.burst))
    prompts, outputs = (_quantiles(law, len(dues))
                        for law in (mix.prompt, mix.output))
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(dues, prompts, outputs))


def schedule(mix: Mix, *, seed: int, seconds: float,
             vocab_size: int) -> list[Request]:
    """The run's requests in due order.  Due times count from the pre-roll's
    start; the window opens at ``mix.preroll_s``."""
    shape = random.Random(f"chipbench-traffic-{mix.name}")
    rows = (_segment(mix, 0.0, mix.preroll_s, shape, window=False)
            + _segment(mix, mix.preroll_s, mix.preroll_s + seconds, shape,
                       window=True))
    rng = random.Random(f"chipbench-prompts-{seed}")
    return [Request(rid=rid, due_s=due,
                    prompt=tuple(rng.randrange(vocab_size)
                                 for _ in range(n_prompt)),
                    max_new=n_out)
            for rid, (due, n_prompt, n_out) in enumerate(rows)]
