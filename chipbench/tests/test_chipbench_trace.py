"""The trace reduction: device busy and idle time, time per module and per
operation, and idle gaps labelled by the host annotation they fall in; on a
trace made by hand, and on a small trace recorded on a TPU v5e."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib.trace import load_export, reduce_planes  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "qwen2-1.5b.chat.events.json.gz"


FUSION = "%fusion.1 = bf16[16,1536]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop"
GEMM = ('%closed_call.23 = bf16[16,9216]{1,0:T(8,128)(2,1)} custom-call('
        'bf16[16,1536]{1,0} %fusion.143, bf16[1536,9216]{1,0} %pad.46), '
        'custom_call_target="tpu_custom_call"')
GROUPED = ('%vmap__.20 = bf16[16,40,8,1536]{3,2,1,0:T(8,128)(2,1)S(1)} '
           'custom-call(bf16[16,40,8,512]{3,2,1,0} %m, bf16[40,512,1536] %d), '
           'custom_call_target="tpu_custom_call"')
COPY = "%copy.31 = bf16[152064,1536]{0,1:T(8,128)(2,1)} copy(f32[152064,1536] %t)"
LOOP = ("%while.3 = (s32[]{:T(128)}, bf16[16,1,1536]{2,0,1:T(8,128)(2,1)S(1)}) "
        "while((s32[]{:T(128)}, bf16[16,1,1536]) %tuple.47), condition=%c")


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                                 for n, s, e in events])


def _hand_made():
    device = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_step(12)", 0, 15), ("jit_step(12)", 20, 30),
                              ("jit_early(3)", -10, -5)]),
        _line("XLA Ops", [(FUSION, 0, 10), (GEMM, 5, 15), (GEMM, 20, 30),
                          (GROUPED, 30, 31), (COPY, 38, 50), (COPY, -10, -5),
                          (LOOP, 0, 15)]),
        _line("Steps", [("0", 0, 40)])])
    host = NS(name="/host:CPU", lines=[
        _line("python", [("window", 0, 40), ("step", 0, 16),
                         ("wait_arrival", 16, 35), ("bookkeeping", 35, 37),
                         ("PjitFunction(x)", 1, 2)])])
    return [NS(name="/host:metadata", lines=[]), host, device]


def test_hand_made_trace():
    r = reduce_planes(_hand_made())
    ns = 1e-9
    assert r.window_s == pytest.approx(40 * ns)
    # busy: [0, 15] + [20, 31] + [38, 40] (clipped to the window)
    assert r.busy_s == pytest.approx(28 * ns)
    assert r.modules == {"jit_step": [2, pytest.approx(25 * ns)]}
    assert r.pallas((2,)) == (2, pytest.approx(20 * ns))
    assert r.pallas((3, 4)) == (1, pytest.approx(1 * ns))
    assert r.ops[COPY] == [1, pytest.approx(2 * ns)]
    # gaps [15, 20] and [31, 38]
    assert r.idle_by_label == {"step": pytest.approx(1 * ns),
                               "wait_arrival": pytest.approx(8 * ns),
                               "bookkeeping": pytest.approx(2 * ns),
                               "other": pytest.approx(1 * ns)}
    assert sum(r.idle_by_label.values()) == pytest.approx(r.window_s
                                                          - r.busy_s)
    b = r.breakdown()
    # the loop that holds the others is left out of the operations' list
    assert [n for n, _ in b["device_ops"]] == [
        "closed_call.23 pallas bf16[16,9216]", "fusion.1 fusion bf16[16,1536]",
        "copy.31 copy bf16[152064,1536]", "vmap__.20 pallas bf16[16,40,8,1536]"]
    assert b["device_ops"][0][1] == pytest.approx(20 * ns)
    assert b["idle_gaps"][0] == ["wait_arrival", pytest.approx(8 * ns)]


def test_no_window_or_no_device_is_an_error():
    planes = _hand_made()
    with pytest.raises(ValueError, match="window"):
        reduce_planes([p for p in planes if p.name != "/host:CPU"])
    with pytest.raises(ValueError, match="device"):
        reduce_planes([p for p in planes if "TPU" not in p.name])


def test_recorded_trace():
    """Events of a 0.3 s window of qwen2-1.5b.chat on a TPU v5e: three
    decode steps, each of 28 layers x 3 MLP GEMMs and the head."""
    r = reduce_planes(load_export(str(RECORDED)))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.pallas((2,))[0] == 3 * (28 * 3 + 1)
    assert r.pallas((3, 4))[0] == 0
    n, sec = r.module(r"_decode_impl")
    assert n > 0 and 0 < sec < r.window_s
    labels = set(r.idle_by_label)
    assert labels <= {"submit", "step", "wait_arrival", "bookkeeping",
                      "other"}
    assert sum(r.idle_by_label.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)


def _nested_labels():
    """The hand-made trace with host annotations that nest and overlap."""
    planes = _hand_made()
    host = planes[1]
    host.lines[0].events += [NS(name="submit", start_ns=2, duration_ns=30),
                             NS(name="bookkeeping", start_ns=14,
                                duration_ns=4)]
    return planes


@pytest.mark.parametrize("planes", [
    lambda: load_export(str(RECORDED)),
    lambda: load_export(str(RECORDED).replace("events", "phases")),
    _nested_labels])
def test_idle_labels_as_a_full_scan(monkeypatch, planes):
    """Each idle gap's scan of the annotations starts past those that end
    before it; the reduction is that of a scan from the first."""
    from lib import trace

    fast = reduce_planes(planes())
    monkeypatch.setattr(trace.bisect, "bisect_right", lambda *args: 0)
    assert reduce_planes(planes()) == fast
