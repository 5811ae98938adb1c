"""The device's idle time by the program's innermost span, and the decode's
device time by named scope (``lib.phases``); on events made by hand, on a
2.56 s window of qwen2-1.5b.chat recorded on a TPU v5e, and on the xplane
of a span recorded here.  Also pins what ``lib.trace`` reads of the
benchmark's first recorded trace, so that a change there shows."""
import dataclasses
import glob
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import phases as phases_cli  # noqa: E402
from lib import measure, trace  # noqa: E402
from lib.phases import (Events, _nest, export, load_export,  # noqa: E402
                        read_xplane, reduce_phases, scope_of)

DATA = BENCH / "tests" / "data"
RECORDED = DATA / "qwen2-1.5b.chat.phases.json.gz"
FIRST = DATA / "qwen2-1.5b.chat.events.json.gz"

OPS = [  # (name, start, end, module, scope)
    ("%fusion.1 = f32[4] fusion(...)", 4, 7, "jit_fn", "attention"),
    ("%scatter.2 = s32[16,1] scatter(...)", 12, 13, "jit_scatter", ""),
    ("%while.3 = (s32[]) while(...)", 22, 48, "jit__decode_impl", ""),
    ("%convert.4 = bf16[28,8,8] convert(...)", 22, 30, "jit__decode_impl",
     "cast_weights"),
    ("%dot.5 = bf16[16,8] dot(...)", 30, 32, "jit__decode_impl",
     "attention/bsd,dhe->bshe"),
    ("%slice.6 = bf16[8,8] slice(...)", 32, 40, "jit__decode_impl", ""),
    ("%gemm_k_inner.7 = bf16[16,8] custom-call(...)", 40, 48,
     "jit__decode_impl", "mlp/gemm_k_inner"),
]


def _hand_made():
    host = [("step", 0, 60), ("serve.step", 1, 59), ("serve.admit", 2, 10),
            ("serve.prefill", 3, 8), ("serve.pack", 10, 20),
            ("serve.decode", 20, 22), ("serve.sync", 22, 50),
            ("serve.unpack", 50, 58), ("bookkeeping", 60, 62)]
    modules = [("jit_fn(1)", 4, 7), ("jit_scatter(2)", 12, 13),
               ("jit__decode_impl(3)", 22, 48)]
    return Events(window=(0, 100), host=host,
                  devices={"/device:TPU:0": {"modules": modules,
                                             "ops": OPS}})


def test_hand_made_events():
    ph = reduce_phases(_hand_made())
    ns = 1e-9
    # busy [4, 7] + [12, 13] + [22, 48]; idle [0, 4], [7, 12], [13, 22],
    # [48, 100], each instant put down to the innermost span open over it
    assert ph.busy_s == pytest.approx(30 * ns)
    assert ph.idle_self == {
        "serve.step": pytest.approx(2 * ns), "serve.admit": pytest.approx(3 * ns),
        "serve.prefill": pytest.approx(2 * ns),
        "serve.pack": pytest.approx(9 * ns), "serve.decode": pytest.approx(2 * ns),
        "serve.sync": pytest.approx(2 * ns), "serve.unpack": pytest.approx(8 * ns)}
    assert ph.idle_in["serve.step"] == pytest.approx(28 * ns)
    assert ph.idle_in["serve.admit"] == pytest.approx(5 * ns)
    assert ph.spans["serve.sync"] == [1, pytest.approx(28 * ns)]
    assert ph.modules["jit__decode_impl"] == [1, pytest.approx(26 * ns)]
    # the loop that holds the body's operations is left out
    assert ph.scopes["jit__decode_impl"] == {
        "cast_weights": [1, pytest.approx(8 * ns)],
        "attention/bsd,dhe->bshe": [1, pytest.approx(2 * ns)],
        "": [1, pytest.approx(8 * ns)],
        "mlp/gemm_k_inner": [1, pytest.approx(8 * ns)]}
    assert ph.per_step() == {
        "engine.step_idle_ms": pytest.approx(28e-6),
        "engine.programs_per_step": 3.0,
        "model.decode_attention_share": pytest.approx(100 * 2 / 26),
        "model.decode_weight_cast_ms": pytest.approx(8e-6)}
    assert [n for n, _ in ph.idle_rows()] == [
        "serve.pack", "serve.unpack", "serve.admit", "serve.step",
        "serve.prefill", "serve.decode", "serve.sync"]
    # the harness's step row, as lib.trace reads the same events
    red = trace.reduce_planes(_hand_made().planes())
    assert red.idle_by_label["step"] == pytest.approx(30 * ns)
    assert red.busy_s == ph.busy_s


def test_nest_and_scopes():
    assert _nest([(0, 10, "a"), (2, 4, "b"), (5, 15, "c")]) == [
        (0, 2, "a"), (2, 4, "b"), (4, 5, "a"), (5, 10, "c")]
    assert scope_of("jit(_decode_impl)/while/body/closed_call/mlp/gemm.prep/"
                    "jit(_pad)/pad") == "mlp/gemm.prep"
    assert scope_of("jit(_decode_impl)/cast_weights/convert_element_type:"
                    ) == "cast_weights"
    assert scope_of("params['embed']['table']") == ""


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="device"):
        reduce_phases(Events(window=(0, 1), host=[], devices={}))


@pytest.fixture(scope="module")
def recorded():
    ev = load_export(str(RECORDED))
    return ev, reduce_phases(ev), trace.reduce_planes(ev.planes())


def test_recorded_window_per_step(recorded):
    """33 decode steps at 10-16 busy slots: the per-step numbers the
    engine's and the decode's readers read."""
    _, ph, red = recorded
    assert ph.busy_s == red.busy_s and ph.window_s == red.window_s
    runs = ph.modules["jit__decode_impl"][0]
    assert runs == ph.spans["serve.step"][0] == ph.spans["serve.decode"][0]
    assert runs == 33
    per = ph.per_step()
    assert 35 <= per["engine.step_idle_ms"] <= 55
    assert 90 <= per["engine.programs_per_step"] <= 170
    assert 0 < per["model.decode_attention_share"] < 100
    assert 10 <= per["model.decode_weight_cast_ms"] <= 16
    # the dense GEMMs: three MLP projections a layer and the tied head
    gemms = sum(c for sc, (c, _) in ph.scopes["jit__decode_impl"].items()
                if sc.endswith("gemm_k_inner"))
    assert gemms == runs * (28 * 3 + 1)


def test_recorded_idle_rows_within_the_step_row(recorded):
    _, ph, red = recorded
    rows = dict(ph.idle_rows())
    step, submit = red.idle_by_label["step"], red.idle_by_label["submit"]
    assert sum(v for k, v in rows.items() if k != "serve.submit") <= step
    assert rows["serve.submit"] <= submit
    # the engine's spans hold nearly all of the harness's step row
    assert sum(v for k, v in rows.items() if k != "serve.submit") \
        >= 0.9 * step
    assert ph.idle_in["serve.step"] <= step


def test_export_round_trip(recorded, tmp_path):
    ev, ph, _ = recorded
    out = tmp_path / "again.json.gz"
    export(ev, str(out))
    assert reduce_phases(load_export(str(out))) == ph


def test_cli_reads_an_export(capsys):
    phases_cli.main(["--read", str(RECORDED)])
    out = json.loads(capsys.readouterr().out)
    assert out["per_step"]["engine.programs_per_step"] > 0
    names = [n for n, _ in out["idle_gaps"]]
    assert names[0] == "step" and "serve.pack" in names
    assert out["decode_ms_by_scope"][0][0] == "cast_weights"


def test_read_xplane_host_events(tmp_path):
    """A real .xplane.pb: the window and the program's spans, at the times
    jax.profiler.ProfileData gives them."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation(trace.WINDOW):
            with TraceAnnotation("step"):
                with TraceAnnotation("serve.step", step=1):
                    with TraceAnnotation("serve.pack", step=1):
                        pass
            with TraceAnnotation("not.a_span"):
                pass
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = read_xplane(path)
    want = sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/host:") for line in p.lines
                  for e in line.events
                  if e.name in ("step", "serve.step", "serve.pack"))
    assert sorted(ev.host) == want
    (win,) = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name == trace.WINDOW]
    assert ev.window == win
    assert ev.devices == {}


def test_first_recorded_trace_reduces_as_before():
    """``lib.trace`` on the benchmark's first recorded trace (three decode
    steps): every number its metrics and breakdown read, as recorded when
    ``lib.phases`` was added beside it."""
    r = trace.reduce_planes(trace.load_export(str(FIRST)))
    assert (r.window_s, r.busy_s, r.devices) == (
        0.306280115, 0.10278160300000001, 1)
    assert r.idle_by_label == {"step": 0.203376112, "other": 1.796e-05,
                               "bookkeeping": 0.00010444000000000001}
    assert r.module(r"_decode_impl") == (3, 0.10261409099999999)
    assert r.module(r"^jit_fn$") == (0, 0.0)
    assert r.pallas((2,)) == (255, 0.014347505)
    assert r.pallas((3, 4)) == (0, 0.0)
    assert (sum(c for c, _ in r.modules.values()), len(r.ops)) == (498, 166)
    b = r.breakdown()
    assert b["idle_gaps"] == [["step", 0.203376112],
                              ["bookkeeping", 0.00010444000000000001],
                              ["other", 1.796e-05]]
    assert b["device_ops"][:3] == [
        ["convert_element_type.60 convert bf16[28,1536,8960]",
         0.010775047000000001],
        ["convert_element_type.59 convert bf16[28,1536,8960]",
         0.010771771000000001],
        ["convert_element_type.58 convert bf16[28,8960,1536]", 0.010564152]]


#: the per-layer metrics read from the program's spans and scopes
PHASE_METRICS = ("engine.step_idle_ms", "engine.programs_per_step",
                 "model.decode_attention_share",
                 "model.decode_weight_cast_ms")


def _run_of(red):
    from types import SimpleNamespace

    return SimpleNamespace(trace=red)


def test_export_reduces_with_its_phases(recorded):
    """A kept export with the program's spans and scopes reduces to the
    same ``phases`` as its events, and the idle rows of its breakdown are
    the harness's, then the spans', ten at most; the first recorded
    trace, which has neither, reduces with none."""
    ev, ph, red = recorded
    got = trace.reduce_export(str(RECORDED))
    assert got.phases == ph
    got.phases = None
    assert got == red
    got.phases = ph
    rows = got.breakdown()["idle_gaps"]
    harness = red.breakdown()["idle_gaps"]
    assert rows == (harness + ph.idle_rows())[:10]
    assert len(harness) < len(rows) == 10
    first = trace.reduce_export(str(FIRST))
    assert first.phases is None
    assert first == trace.reduce_planes(trace.load_export(str(FIRST)))


def test_a_reader_added_as_a_file_reads_the_phases(tmp_path, recorded):
    """A reader added as a new file finds the program's phases on the
    run's trace: the recorded window's per-step values."""
    _, ph, _ = recorded
    (tmp_path / "steps.py").write_text(
        "def read(run):\n    return run.trace.phases.per_step()\n")
    red = trace.reduce_export(str(RECORDED))
    assert measure.reader(tmp_path, "steps")(_run_of(red)) == ph.per_step()


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_readers(recorded, name):
    """Each reader of the program's phases gives the recorded window's
    per-step value, and nothing where the trace has no spans."""
    _, ph, _ = recorded
    read = measure.reader(BENCH / "metrics", name)
    assert read(_run_of(trace.reduce_export(str(RECORDED)))) == \
        ph.per_step()[name]
    assert read(_run_of(trace.reduce_export(str(FIRST)))) is None
    assert read(_run_of(None)) is None


def _write_xplane(ev: Events, path) -> None:
    """``ev`` as a profiler's ``.xplane.pb``: the host events on one line,
    each device's modules and operations on theirs, every operation's
    scope path and program id as stats of its metadata."""
    from lib.phases import _xspace_type

    space = _xspace_type()()

    def add_event(plane, line, name, s, e, stats=()):
        key = len(plane.event_metadata) + 1
        md = plane.event_metadata.add(key=key)
        md.value.name = name
        for stat, kind, value in stats:
            md.value.stats.add(metadata_id=stat, **{kind: value})
        line.events.add(metadata_id=key, offset_ps=1000 * s,
                        duration_ps=1000 * (e - s))

    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="python", timestamp_ns=0)
    for name, s, e in [(trace.WINDOW, *ev.window)] + ev.host:
        add_event(host, line, name, s, e)
    for pname, dev in ev.devices.items():
        plane = space.planes.add(name=pname)
        plane.stat_metadata.add(key=1).value.name = "tf_op"
        plane.stat_metadata.add(key=2).value.name = "program_id"
        ids = {}
        mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
        for name, s, e in dev["modules"]:
            base, _, pid = name.rstrip(")").rpartition("(")
            ids[base] = int(pid)
            add_event(plane, mods, name, s, e)
        ops = plane.lines.add(name="XLA Ops", timestamp_ns=0)
        for name, s, e, module, scope in dev["ops"]:
            tf_op = "/".join(["jit(f)"] + [scope] * bool(scope) + ["op"])
            add_event(plane, ops, name, s, e,
                      [(1, "str_value", tf_op),
                       (2, "uint64_value", ids[module])])
    path.write_bytes(space.SerializeToString())


def test_profile_and_its_export_reduce_with_phases(tmp_path):
    """``reduce_file`` of a profile keeps ``lib.phases``' reduction of it
    as ``phases``, beside the reduction ``jax.profiler.ProfileData``'s
    planes give; its ``export`` reduces to the same."""
    ev = _hand_made()
    path = tmp_path / "hand.xplane.pb"
    _write_xplane(ev, path)
    red = trace.reduce_file(str(path))
    assert red.phases == reduce_phases(ev)
    assert red.busy_s == red.phases.busy_s
    assert red.idle_by_label == trace.reduce_planes(ev.planes()).idle_by_label
    # the same reduction as of the planes jax.profiler.ProfileData reads
    from jax.profiler import ProfileData
    plain = trace.reduce_planes(ProfileData.from_file(str(path)).planes)
    assert red == dataclasses.replace(plain, phases=red.phases)
    out = tmp_path / "hand.json.gz"
    trace.export(str(path), str(out))
    assert trace.reduce_export(str(out)) == red
