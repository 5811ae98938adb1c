"""The benchmark's traffic generator: deterministic per seed, the same
arrivals and sizes for every seed, Poisson-like gaps, and every length
inside its law's clip."""
import statistics
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib.traffic import LengthLaw, Mix, schedule  # noqa: E402

CHAT = {"arrival": "poisson", "rate": 4.0, "preroll_s": 10.0,
        "prompt": {"kind": "lognormal", "median": 200, "sigma": 0.8,
                   "lo": 32, "hi": 1024},
        "output": {"kind": "lognormal", "median": 128, "sigma": 0.7,
                   "lo": 32, "hi": 512}}


def _mix(**kw):
    return Mix.from_dict("chat", {**CHAT, **kw})


def test_same_seed_same_schedule():
    a = schedule(_mix(), seed=2**33 + 7, seconds=20, vocab_size=1000)
    b = schedule(_mix(), seed=2**33 + 7, seconds=20, vocab_size=1000)
    assert a == b
    c = schedule(_mix(), seed=2**33 + 8, seconds=20, vocab_size=1000)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_offers_the_same_work():
    runs = [schedule(_mix(), seed=s, seconds=20, vocab_size=1000)
            for s in (1, 2, 2**40)]
    for r in runs:
        assert [(x.due_s, len(x.prompt), x.max_new) for x in r] == \
            [(x.due_s, len(x.prompt), x.max_new) for x in runs[0]]
    # the expected count in the pre-roll and in the window
    dues = [x.due_s for x in runs[0]]
    assert sum(d < 10 for d in dues) == 40
    assert sum(10 <= d < 30 for d in dues) == 80
    assert dues == sorted(dues)


def test_offered_work_is_the_laws():
    """The sizes of a segment are its law's mid-quantiles, in some order."""
    mix = _mix(preroll_s=0.0)
    reqs = schedule(mix, seed=1, seconds=25, vocab_size=10)
    law = mix.output
    assert len(reqs) == 100
    assert sorted(r.max_new for r in reqs)[49] == pytest.approx(
        law.median, abs=2)
    outs = [r.max_new for r in reqs]
    assert outs != sorted(outs)


@pytest.mark.parametrize("law", [
    {"kind": "lognormal", "median": 200, "sigma": 0.8, "lo": 32, "hi": 1024},
    {"kind": "lognormal", "median": 128, "sigma": 0.7, "lo": 32, "hi": 512},
    {"kind": "lognormal", "median": 1000, "sigma": 0.5, "lo": 512,
     "hi": 1984},
    {"kind": "lognormal", "median": 16, "sigma": 0.6, "lo": 8, "hi": 32},
])
def test_lengths_stay_in_their_clip(law):
    mix = _mix(prompt=law, output=law)
    reqs = schedule(mix, seed=3, seconds=60, vocab_size=50)
    lo, hi = LengthLaw(**law).bounds()
    for r in reqs:
        assert lo <= len(r.prompt) <= hi and lo <= r.max_new <= hi
        assert all(0 <= t < 50 for t in r.prompt)


def test_lognormal_median_and_heavy_tail():
    reqs = schedule(_mix(), seed=5, seconds=200, vocab_size=10)
    lens = sorted(len(r.prompt) for r in reqs)
    assert abs(lens[len(lens) // 2] - 200) <= 3
    assert lens[-1] == 1024 and lens[0] == 32


def test_poisson_gaps_are_as_irregular_as_exponential_ones():
    """Given their count, the arrivals lie at independent uniform times:
    the gaps' spread is the exponential law's (coefficient of variation
    1), with clusters, and no gap is dealt by a fixed pattern."""
    reqs = schedule(_mix(preroll_s=0.0), seed=1, seconds=500, vocab_size=2)
    gaps = [b.due_s - a.due_s for a, b in zip(reqs, reqs[1:])]
    mean = statistics.mean(gaps)
    assert mean == pytest.approx(0.25, rel=0.02)
    assert 0.9 < statistics.stdev(gaps) / mean < 1.1
    # an exponential law puts 1 - exp(-0.1) = 9.5% of gaps under a tenth
    # of the mean
    short = sum(g < 0.1 * mean for g in gaps) / len(gaps)
    assert 0.07 < short < 0.12


def test_arrival_laws():
    bursty = schedule(_mix(arrival="bursty", burst=4, intra_gap_s=0.001),
                      seed=1, seconds=20, vocab_size=10)
    gaps = [b.due_s - a.due_s for a, b in zip(bursty, bursty[1:])]
    assert sum(g == pytest.approx(0.001) for g in gaps) >= len(gaps) * 0.7
    assert len(bursty) == 4 * (10 + 20)
    backlog = schedule(_mix(arrival="backlog", rate=None, backlog=30,
                            preroll_s=0.0), seed=1, seconds=20,
                       vocab_size=10)
    assert len(backlog) == 30 and {r.due_s for r in backlog} == {0.0}


@pytest.mark.parametrize("bad", [
    {"arrival": "zipf"}, {"rate": 0.0}, {"preroll_s": -1.0},
    {"prompt": {"kind": "lognormal", "median": 0, "sigma": 1, "lo": 1,
                "hi": 9}},
    {"output": {"kind": "lognormal", "median": 5, "sigma": 1, "lo": 9,
                "hi": 3}},
    {"output": {"kind": "geometric", "median": 5, "sigma": 1, "lo": 1,
                "hi": 9}},
])
def test_bad_mix_is_refused(bad):
    with pytest.raises(ValueError):
        _mix(**bad)
