"""The serving engine's ``serve.*`` spans on a profiler trace's host plane.

A smoke ``ServingEngine`` runs a few steps under ``jax.profiler.trace``; the
``.xplane.pb`` is read back with ``ProfileData``.  Every phase of ``step()``
is one span, nested and ordered as the engine runs them, and each carries
the engine's step number (the per-request ones the request id too), so that
a reducer of the trace can put the device's idle time down to a phase.
"""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs import get_config
from repro.models.common import HOST_MESH, split_params
from repro.models.model import LM
from repro.serving.engine import Request, ServingEngine

PHASES = ["serve.admit", "serve.pack", "serve.decode", "serve.sync",
          "serve.unpack", "serve.account"]


def _engine():
    lm = LM(get_config("qwen2-1.5b", smoke=True), HOST_MESH)
    values, _ = split_params(lm.init(jax.random.key(0)))
    return ServingEngine(lm, values, max_batch=2, max_len=64)


def _host_spans(log_dir):
    """(name, start, end, stats) of every ``serve.*`` host event."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {str(k): str(v) for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(outer, spans):
    _, lo, hi, _ = outer
    return [s for s in spans if s is not outer and lo <= s[1] and s[2] <= hi]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans of three steps: the first admits two requests."""
    obs.disable()
    eng = _engine()
    warm = Request(rid=-1, prompt=[1, 2, 3, 4, 5], max_new_tokens=1)
    eng.submit(warm)
    eng.drain()                      # compiles outside the trace
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        for rid in (7, 8):
            eng.submit(Request(rid=rid, prompt=[3, 1, 4, 1, 5],
                               max_new_tokens=3))
        for _ in range(3):
            eng.step()
    return eng, _host_spans(log_dir)


def test_step_phases_nest_in_order(traced):
    eng, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == 3
    assert [s[3]["step"] for s in steps] == [
        str(n) for n in range(eng.steps - 2, eng.steps + 1)]
    for st in steps:
        inner = _inside(st, spans)
        direct = [s for s in inner if s[0] in PHASES]
        # one span per phase, in the order step() runs them
        assert [s[0] for s in direct] == PHASES
        assert all(a[2] <= b[1] for a, b in zip(direct, direct[1:]))
        assert {s[3]["step"] for s in inner} == {st[3]["step"]}
        assert st[3]["active"] == "2"
    first = steps[0]
    assert first[3]["admitted"] == "2"
    (admit,) = [s for s in _inside(first, spans) if s[0] == "serve.admit"]
    per_request = _inside(admit, spans)
    # the prefill, then the insert into the slot, for each request in turn
    assert [(s[0], s[3]["rid"]) for s in per_request] == [
        ("serve.prefill", "7"), ("serve.insert", "7"),
        ("serve.prefill", "8"), ("serve.insert", "8")]
    assert per_request[0][3]["slot"] == per_request[1][3]["slot"] == "0"
    for st in steps[1:]:
        assert not [s for s in _inside(st, spans)
                    if s[0] in ("serve.prefill", "serve.insert")]


def test_submit_spans_carry_rid_and_step(traced):
    eng, spans = traced
    subs = [s for s in spans if s[0] == "serve.submit"]
    assert [s[3]["rid"] for s in subs] == ["7", "8"]
    # submitted after the warm-up's steps, before the traced ones
    assert {s[3]["step"] for s in subs} == {str(eng.steps - 3)}
    first_step = min(s[1] for s in spans if s[0] == "serve.step")
    assert all(s[2] <= first_step for s in subs)


def test_spans_do_not_change_the_engine(traced):
    """The trace-v1 ``step`` event keeps its duration; no per-step
    histogram grows in the metrics registry."""
    eng, _ = traced
    steps = [e for e in eng.trace_events if e["type"] == "step"]
    assert len(steps) == eng.steps
    assert all(e["dt"] > 0 for e in steps)
    assert "serving.step_dt_s" not in obs.metrics.snapshot()["histograms"]
