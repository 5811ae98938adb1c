"""Tests for ``repro.obs`` — tracing, metrics, drift, and explain().

Covers the observability contract end to end: span nesting, the
disabled fast path (a bare profiler annotation) and spans on a profiler
trace's host plane, the ``repro.obs/v1`` metrics snapshot
round-trip and its agreement with the legacy plan-cache/sweep counters,
DriftMonitor's ok → warn → stale transitions (including the simulator
integration where a throttle fault flips the verdict), ``explain()``'s
partition-of-total guarantee on every Table-2 cell, Chrome-trace export
validity, and the ``python -m repro.obs`` CLI.
"""
import glob
import json
import statistics

import jax
import pytest

from repro import obs
from repro.obs.trace import Recorder, chrome_trace_from_serving
from repro.obs.drift import DriftMonitor
from repro.obs.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test sees (and leaves behind) a pristine process recorder."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


# -- span channel -------------------------------------------------------------

class TestSpans:
    def test_disabled_span_is_shared_noop(self):
        # disabled, a span is a bare profiler annotation: nothing recorded
        assert not obs.enabled()
        s = obs.span("anything", attr=1)
        assert isinstance(s, jax.profiler.TraceAnnotation)
        # it supports the full call-site surface
        with s as inner:
            inner.set(more=2)
        assert obs.recorder.spans == []

    @pytest.mark.parametrize("enabled", [False, True])
    def test_span_reaches_profiler_session(self, tmp_path, enabled):
        """Recorded or not, a span is on the trace's host plane, with its
        attributes, those set while it was open too."""
        if enabled:
            obs.enable()
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("obs.outer", step=3) as sp:
                with obs.span("obs.inner", rid=7):
                    pass
                sp.set(active=2)
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        found = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("obs."):
                        found[e.name] = (e.start_ns, e.duration_ns,
                                         {str(k): str(v) for k, v in e.stats})
        assert set(found) == {"obs.outer", "obs.inner"}
        (o0, od, ostats), (i0, idur, istats) = (found["obs.outer"],
                                                found["obs.inner"])
        assert o0 <= i0 and i0 + idur <= o0 + od
        assert ostats == {"step": "3", "active": "2"}
        assert istats == {"rid": "7"}
        assert [s.name for s in obs.recorder.spans] == (
            ["obs.outer", "obs.inner"] if enabled else [])

    def test_disabled_add_span_records_nothing(self):
        assert obs.add_span("x", 0.0, 1.0) is None
        assert obs.recorder.spans == []

    def test_nesting_and_attrs(self):
        rec = Recorder(enabled=True)
        with rec.span("outer", a=1) as outer:
            with rec.span("inner"):
                pass
            outer.set(b=2)
        assert [s.name for s in rec.spans] == ["outer", "inner"]
        out, inn = rec.spans
        assert inn.parent == out.sid
        assert out.parent is None
        assert out.attrs == {"a": 1, "b": 2}
        assert out.t1 >= inn.t1 >= inn.t0 >= out.t0
        assert out.duration_s >= 0

    def test_exception_closes_span_and_tags_error(self):
        rec = Recorder(enabled=True)
        with pytest.raises(ValueError):
            with rec.span("boom"):
                raise ValueError("x")
        (s,) = rec.spans
        assert s.t1 is not None
        assert s.attrs["error"] == "ValueError"
        assert rec._stack == []

    def test_out_of_order_exit_tolerated(self):
        rec = Recorder(enabled=True)
        a = rec.span("a")
        b = rec.span("b")
        a.__exit__(None, None, None)  # closes a, unwinds b off the stack
        assert rec._stack == []
        b.__exit__(None, None, None)  # already unwound: harmless
        assert all(s.t1 is not None for s in rec.spans)

    def test_retroactive_add_span(self):
        rec = Recorder(enabled=True)
        s = rec.add_span("serve.step", 10.0, 10.5, track="sim", active=3)
        assert s.duration_s == pytest.approx(0.5)
        assert s.track == "sim"
        assert s.attrs == {"active": 3}

    def test_overhead_disabled_vs_stubbed(self):
        # the hard <2% assert lives in benchmarks/bench_planner.py on the
        # real Table-2 sweep; here just bound the per-call cost sanely:
        # open and close one span, its profiler annotation included
        import timeit
        n = 20000

        def hot():
            with obs.span("hot", i=0):
                pass
        disabled = timeit.timeit(hot, number=n) / n
        assert disabled < 5e-6  # single-digit microseconds at worst


# -- event channel + Chrome export --------------------------------------------

class TestChromeTrace:
    def test_events_always_on_and_tag_filtered(self):
        assert not obs.enabled()
        obs.recorder.add_event({"type": "submit", "rid": 0, "t": 1.0},
                               tag="engine-a")
        obs.recorder.add_event({"type": "submit", "rid": 1, "t": 2.0},
                               tag="engine-b")
        a = obs.recorder.events_for(tag="engine-a")
        assert [e["rid"] for e in a] == [0]
        # private routing keys never leak to consumers
        assert "_tag" not in a[0] and "_track" not in a[0]

    def test_chrome_trace_shape(self):
        obs.enable()
        with obs.span("outer", machine="gap9-fc"):
            with obs.span("inner"):
                pass
        obs.recorder.add_event({"type": "finish", "rid": 7, "t": 0.5})
        doc = obs.to_chrome_trace()
        assert doc["metadata"]["schema"] == "repro.obs/chrome-trace-v1"
        assert doc["metadata"]["spans"] == 2
        assert doc["metadata"]["events"] == 1
        evs = doc["traceEvents"]
        assert {e["ph"] for e in evs} == {"X", "i", "M"}
        slices = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)
        assert {e["name"] for e in slices} == {"outer", "inner"}
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert inst["name"] == "event.finish"
        assert inst["args"] == {"rid": 7}  # type/t hoisted, privates dropped
        names = [e["args"]["name"] for e in evs if e["ph"] == "M"]
        assert names == ["wall"]
        json.dumps(doc)  # must be valid JSON end to end

    def test_save_chrome_trace_round_trip(self, tmp_path):
        obs.enable()
        with obs.span("s"):
            pass
        path = tmp_path / "trace.json"
        doc = obs.save_chrome_trace(path)
        assert json.loads(path.read_text()) == json.loads(json.dumps(doc))

    def test_nonjson_attrs_stringified(self):
        rec = Recorder(enabled=True)
        with rec.span("s", obj=object(), seq=(1, object())):
            pass
        args = rec.to_chrome_trace()["traceEvents"][0]["args"]
        assert isinstance(args["obj"], str)
        assert args["seq"][0] == 1 and isinstance(args["seq"][1], str)

    def test_chrome_trace_from_serving(self):
        trace = {"schema": "repro.serving/trace-v1", "events": [
            {"type": "submit", "rid": 0, "t": 0.0, "prompt_len": 4},
            {"type": "submit", "rid": 1, "t": 0.1, "prompt_len": 4},
            {"type": "step", "t": 0.2, "dt": 0.05, "active": 2,
             "admitted": [0, 1], "queue_depth": 0},
            {"type": "first_token", "rid": 0, "t": 0.25},
            {"type": "finish", "rid": 0, "t": 0.3},
            {"type": "shed", "rid": 1, "t": 0.35, "cause": "deadline"},
        ]}
        doc = chrome_trace_from_serving(trace)
        assert doc["metadata"]["source_schema"] == "repro.serving/trace-v1"
        by_name = {e["name"]: e for e in doc["traceEvents"]
                   if e["ph"] == "X"}
        assert set(by_name) == {"serve.step", "request.0", "request.1"}
        r0 = by_name["request.0"]
        assert r0["args"]["outcome"] == "finish"
        assert r0["args"]["ttft_s"] == pytest.approx(0.25)
        assert r0["dur"] == pytest.approx(0.3e6)
        assert by_name["request.1"]["args"] == {"outcome": "shed",
                                                "cause": "deadline"}

    def test_unfinished_requests_get_horizon_slices(self):
        trace = {"events": [
            {"type": "submit", "rid": 9, "t": 1.0},
            {"type": "step", "t": 2.0, "dt": 0.1, "active": 1,
             "admitted": [9], "queue_depth": 0},
        ]}
        doc = chrome_trace_from_serving(trace)
        (req,) = [e for e in doc["traceEvents"]
                  if e["ph"] == "X" and e["name"] == "request.9"]
        assert req["args"]["outcome"] == "unfinished"


# -- metrics registry ---------------------------------------------------------

class TestMetrics:
    def test_snapshot_schema_round_trip(self):
        m = MetricsRegistry()
        assert m.counter("a.hits") == 1
        assert m.counter("a.hits", 4) == 5
        m.gauge("a.depth", 3.5)
        for v in (0.1, 0.2, 0.3):
            m.observe("a.dt_s", v)
        snap = json.loads(json.dumps(m.snapshot()))
        assert snap["schema"] == "repro.obs/v1"
        assert snap["counters"] == {"a.hits": 5}
        assert snap["gauges"] == {"a.depth": 3.5}
        h = snap["histograms"]["a.dt_s"]
        assert h["count"] == 3
        assert h["sum"] == pytest.approx(0.6)
        assert h["min"] == 0.1 and h["max"] == 0.3
        assert h["p50"] == pytest.approx(0.2)

    def test_reset_and_delta(self):
        m = MetricsRegistry()
        m.counter("x", 2)
        before = m.snapshot()["counters"]
        m.counter("x", 3)
        m.counter("y")
        assert m.delta_since(before) == {"x": 3, "y": 1}
        m.reset()
        assert m.snapshot() == {"schema": "repro.obs/v1", "counters": {},
                                "gauges": {}, "histograms": {}}

    def test_plan_cache_counters_match_legacy_stats(self):
        from repro.gemm import plan, plan_cache_stats

        plan_cache_stats(reset=True)
        before = obs.metrics.snapshot()["counters"]
        plan((64, 64, 64), dtype="bf16", backend="analytic-tpu")
        plan((64, 64, 64), dtype="bf16", backend="analytic-tpu")  # hit
        legacy = plan_cache_stats()
        delta = obs.metrics.delta_since(before)
        assert delta.get("plan_cache.hits", 0) == legacy["hits"]
        assert delta.get("plan_cache.misses", 0) == legacy["misses"]
        assert legacy["hits"] >= 1 and legacy["misses"] >= 1

    def test_plan_cache_stats_reset_semantics(self):
        # satellite bugfix: back-to-back experiments need per-run numbers
        from repro.gemm import plan, plan_cache_stats

        plan((48, 48, 48), dtype="bf16", backend="analytic-tpu")
        first = plan_cache_stats(reset=True)
        assert first["misses"] >= 1
        zeroed = plan_cache_stats()
        assert zeroed["hits"] == zeroed["misses"] == 0
        assert zeroed["manifest_hits"] == zeroed["deduped"] == 0
        # the cache itself survives a stats reset: replanning hits
        plan((48, 48, 48), dtype="bf16", backend="analytic-tpu")
        assert plan_cache_stats()["hits"] == 1

    def test_sweep_stats_are_deltas_for_all_counters(self):
        # satellite bugfix: manifest_hits was cumulative, not a delta
        from repro.core.mobilenet import TABLE2
        from repro.gemm import plan_cache_stats, sweep

        probs = [row.problem for row in TABLE2[:4]]
        plan_cache_stats(reset=True)
        r1 = sweep(probs, backends=("analytic-gap8",), machines="gap8-fc")
        r2 = sweep(probs, backends=("analytic-gap8",), machines="gap8-fc")
        for key in ("cache_hits", "cache_misses", "manifest_hits",
                    "deduped", "pruned"):
            assert key in r1.stats and key in r2.stats
        # second sweep re-plans the same cells: all hits, no new misses —
        # and crucially its stats are its OWN deltas, not cumulative
        assert r1.stats["cache_misses"] > 0
        assert r2.stats["cache_misses"] == 0
        assert r2.stats["cache_hits"] > 0
        # cumulative == sum of per-sweep deltas, for EVERY counter —
        # manifest_hits used to leak the process-cumulative value
        cum = plan_cache_stats()
        for legacy, delta in (("hits", "cache_hits"),
                              ("misses", "cache_misses"),
                              ("manifest_hits", "manifest_hits"),
                              ("deduped", "deduped")):
            assert cum[legacy] == r1.stats[delta] + r2.stats[delta], legacy

    def test_sweep_metrics_counters(self):
        from repro.core.mobilenet import TABLE2
        from repro.gemm import sweep

        before = obs.metrics.snapshot()["counters"]
        res = sweep([row.problem for row in TABLE2[:3]],
                    backends=("analytic-gap8",), machines="gap8-fc")
        delta = obs.metrics.delta_since(before)
        assert delta["sweep.cells_scored"] == len(res.rows)


# -- drift monitor ------------------------------------------------------------

class TestDrift:
    def test_thresholds_validated(self):
        with pytest.raises(ValueError):
            DriftMonitor(warn_drift=0.3, max_drift=0.2)
        with pytest.raises(ValueError):
            DriftMonitor(warn_drift=0.0)

    def test_ok_warn_stale_transitions(self):
        mon = DriftMonitor(window=8, min_samples=4)
        # too few samples: verdict withheld
        for _ in range(3):
            mon.observe(1.0, 1.5)
        assert mon.status() == "ok"
        mon.observe(1.0, 1.05)
        # median of [1.5 1.5 1.5 1.05] -> warn territory? median=1.5 ->
        # stale; refill with mild drift instead
        mon.reset()
        for _ in range(8):
            mon.observe(1.0, 1.05)
        assert mon.status() == "ok"
        for _ in range(8):  # window=8: fully replaces the ok ratios
            mon.observe(1.0, 1.15)
        assert mon.status() == "warn"
        for _ in range(8):
            mon.observe(1.0, 1.5)
        assert mon.status() == "stale"
        # recovery: the window ages the fault out again
        for _ in range(8):
            mon.observe(1.0, 1.0)
        assert mon.status() == "ok"

    def test_slowdown_and_speedup_both_drift(self):
        mon = DriftMonitor(min_samples=2)
        for _ in range(4):
            mon.observe(1.0, 0.5)  # machine twice as fast as predicted
        assert mon.drift() == pytest.approx(0.5)
        assert mon.status() == "stale"

    def test_degenerate_inputs_ignored(self):
        mon = DriftMonitor()
        assert mon.observe(0.0, 1.0) is None
        assert mon.observe(1.0, -1.0) is None
        assert mon.keys() == []
        assert mon.median_ratio() is None
        assert mon.drift() is None
        assert mon.status() == "ok"

    def test_report_worst_of_keys(self):
        mon = DriftMonitor(min_samples=1)
        mon.observe(1.0, 1.0, key="a@f1")
        mon.observe(1.0, 1.15, key="b@f2")
        rep = mon.report()
        assert rep["schema"] == "repro.obs/drift-v1"
        assert rep["status"] == "warn"
        assert rep["keys"]["a@f1"]["status"] == "ok"
        assert rep["keys"]["b@f2"]["status"] == "warn"
        assert rep["keys"]["b@f2"]["median_ratio"] == pytest.approx(1.15)
        assert rep["warn_drift"] == 0.1 and rep["max_drift"] == 0.2
        json.dumps(rep)

    def test_window_median_matches_statistics(self):
        mon = DriftMonitor(window=4, min_samples=1)
        for m in (1.0, 2.0, 3.0, 4.0, 5.0):  # 1.0 ages out
            mon.observe(1.0, m)
        assert mon.median_ratio() == statistics.median([2.0, 3.0, 4.0, 5.0])

    def test_check_raises_offline_error_type(self):
        from repro.measure.campaign import CalibrationDriftError

        mon = DriftMonitor(min_samples=1)
        mon.observe(1.0, 1.0, key="fine")
        assert mon.check("fine") is None
        mon.observe(1.0, 2.0, key="bad")
        with pytest.raises(CalibrationDriftError) as ei:
            mon.check("bad")
        d = ei.value.as_dict()
        assert d["median_ratio"] == pytest.approx(2.0)
        assert d["max_drift"] == 0.2

    def test_simulator_throttle_flips_drift_stale(self):
        """Acceptance: an injected throttle flips the online verdict while
        the un-faulted control stays ok — the simulator's analytic costs
        make the control ratio exactly 1.0."""
        from repro.configs import get_config
        from repro.simulate import (
            PoissonTraffic,
            ServiceModel,
            simulate_serving,
        )

        cfg = get_config("qwen2-1.5b", smoke=True)
        service = ServiceModel.from_plans(cfg, batch=4, machine="gap9-fc",
                                          dtype="int8")
        kw = dict(max_batch=4, requests=60, deadline_s=5.0,
                  config={"machine": "gap9-fc", "dtype": "int8"})
        traffic = PoissonTraffic(rate=5, prompt_len=16, decode_len=8, seed=0)
        control = simulate_serving(service, traffic, **kw)
        assert control.drift["status"] == "ok"
        assert control.drift["keys"]["gap9-fc"]["median_ratio"] == 1.0
        # a throttle window covering the whole run scales every step, so
        # the median ratio sits at the factor wherever the run ends
        from repro.simulate.faults import FaultScenario, ThrottleWindow
        slow = FaultScenario(name="constant-throttle", throttles=(
            ThrottleWindow(start_s=0.0, duration_s=1e9, factor=1.5),))
        faulted = simulate_serving(service, traffic, faults=slow, **kw)
        assert faulted.drift["status"] == "stale"
        assert faulted.drift["keys"]["gap9-fc"]["median_ratio"] == \
            pytest.approx(1.5)
        # and the verdict round-trips with the report
        doc = json.loads(json.dumps(faulted.to_json()))
        assert doc["drift"]["status"] == "stale"


# -- explain() ----------------------------------------------------------------

class TestExplain:
    def test_table2_fractions_partition_estimate(self):
        """Acceptance: on every Table-2 cell the per-term seconds sum to
        estimate()'s total and the fractions sum to 1."""
        from repro.core.mobilenet import TABLE2
        from repro.gemm import plan

        for row in TABLE2:
            p = plan(row.problem, backend="analytic-gap8",
                     machine="gap8-fc")
            ex = p.explain()
            assert ex["schema"] == "repro.obs/explain-v1"
            assert ex["composition"] == "sum"
            assert sum(t["seconds"] for t in ex["terms"]) == pytest.approx(
                p.estimate().total, rel=1e-9)
            assert sum(t["fraction"] for t in ex["terms"]) == pytest.approx(
                1.0, rel=1e-9)
            assert ex["total_s"] == pytest.approx(ex["sum_s"], rel=1e-9)
            assert ex["terms"] == sorted(ex["terms"],
                                         key=lambda t: -t["seconds"])

    def test_tpu_overlapped_semantics(self):
        from repro.gemm import plan

        p = plan((512, 512, 512), dtype="bf16", backend="analytic-tpu")
        ex = p.explain()
        assert ex["composition"] == "overlapped"
        assert ex["total_s"] == pytest.approx(p.predicted_seconds)
        # fractions still partition the no-overlap sum
        assert sum(t["fraction"] for t in ex["terms"]) == pytest.approx(1.0)
        assert ex["sum_s"] >= ex["total_s"]
        levels = {t["name"]: t["level"] for t in ex["terms"]}
        assert levels == {"compute": "MXU", "stream_hbm": "HBM",
                          "stream_vmem": "VMEM"}
        traffic = [t for t in ex["terms"] if t["kind"] == "traffic"]
        assert all(t["bytes"] > 0 and t["rate"] > 0 for t in traffic)

    def test_tpu_no_overlap_sums_exactly(self):
        from repro.gemm import plan

        p = plan((256, 256, 256), dtype="bf16", backend="analytic-tpu",
                 overlap=False)
        ex = p.explain()
        assert ex["composition"] == "sum"
        assert ex["total_s"] == pytest.approx(ex["sum_s"], rel=1e-9)
        assert ex["total_s"] == pytest.approx(p.predicted_seconds, rel=1e-9)


# -- CLI ----------------------------------------------------------------------

class TestCli:
    def _trace_doc(self):
        return {"schema": "repro.serving/trace-v1", "max_batch": 2,
                "max_len": 64, "predicted_step_s": 0.05, "events": [
                    {"type": "submit", "rid": 0, "t": 0.0, "prompt_len": 4},
                    {"type": "step", "t": 0.1, "dt": 0.05, "active": 1,
                     "admitted": [0], "queue_depth": 0},
                    {"type": "step", "t": 0.2, "dt": 0.055, "active": 1,
                     "admitted": [], "queue_depth": 0},
                    {"type": "finish", "rid": 0, "t": 0.3},
                ]}

    def test_report(self, tmp_path, capsys):
        from repro.obs.__main__ import main

        path = tmp_path / "t.json"
        path.write_text(json.dumps(self._trace_doc()))
        assert main(["report", "--trace", str(path)]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["schema"] == "repro.obs/report-v1"
        assert out["events_by_type"] == {"submit": 1, "step": 2,
                                         "finish": 1}
        assert out["steps"]["count"] == 2
        assert out["drift"]["schema"] == "repro.obs/drift-v1"

    def test_export(self, tmp_path, capsys):
        from repro.obs.__main__ import main

        src = tmp_path / "t.json"
        out = tmp_path / "chrome.json"
        src.write_text(json.dumps(self._trace_doc()))
        assert main(["export", "--trace", str(src),
                     "--out", str(out)]) == 0
        doc = json.loads(out.read_text())
        assert doc["metadata"]["schema"] == "repro.obs/chrome-trace-v1"
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert names == {"serve.step", "request.0"}

    def test_drift_strict_exit_code(self, tmp_path):
        from repro.obs.__main__ import main

        doc = self._trace_doc()
        # steps run 10x the predicted price: stale under any window
        doc["events"] = [
            {"type": "step", "t": 0.1 * i, "dt": 0.5, "active": 1,
             "admitted": [], "queue_depth": 0} for i in range(10)]
        path = tmp_path / "t.json"
        path.write_text(json.dumps(doc))
        assert main(["drift", "--trace", str(path)]) == 0
        assert main(["drift", "--trace", str(path), "--strict"]) == 3

    def test_rejects_non_trace_input(self, tmp_path):
        from repro.obs.__main__ import main

        path = tmp_path / "nope.json"
        path.write_text(json.dumps({"schema": "other"}))
        with pytest.raises(SystemExit):
            main(["report", "--trace", str(path)])
