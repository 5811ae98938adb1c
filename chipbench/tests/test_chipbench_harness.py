"""A whole run of the harness on the CPU at a tiny size: the look for a chip
skipped, the rest as on the chip.  A sound run is correct; the control
(the reference on float8 weights) and each fault planted in the timed path
are not; and no run compiles inside its window.

The tiny limit below was set as the chip's are (PERF.md): the program's
widest gap over 14 seeds read at most 0.0080, the control's at least
0.0325; the limit sits between them.
"""
import json
import shutil
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import check, harness, measure  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402
from lib.spec import load_cell  # noqa: E402

TINY_LIMIT = 0.02
#: the per-layer metrics read from the program's spans and scopes
PHASE_METRICS = ("engine.step_idle_ms", "engine.programs_per_step",
                 "model.decode_attention_share",
                 "model.decode_weight_cast_ms")
TINY = {"program_arch": "qwen2-1.5b", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "attention_bias": True,
        "hidden_act": "silu", "initializer_range": 0.02,
        "engine": {"max_batch": 4, "max_len": 96},
        "check": {"logit_gap": TINY_LIMIT}}
#: the tiny mixture compares the mean gap, whose widest swings with near
#: ties among its experts; its sound runs read at most 0.00386 over 14
#: seeds (its control, from 0.00455, does not separate from them at this
#: size, so only the faults are tested against it)
TINY_MOE_LIMIT = 0.005
TINY_MOE = dict(TINY, program_arch="granite-moe-3b-a800m",
                intermediate_size=32, num_local_experts=16,
                num_experts_per_tok=4, capacity_factor=4.0,
                attention_bias=False,
                check={"mean_logit_gap": TINY_MOE_LIMIT})
MIX = {"arrival": "poisson", "rate": 20.0, "preroll_s": 0.5,
       "prompt": {"kind": "lognormal", "median": 20, "sigma": 0.5, "lo": 4,
                  "hi": 60},
       "output": {"kind": "lognormal", "median": 8, "sigma": 0.5, "lo": 2,
                  "hi": 24}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    configs, cells = [], []
    for name, data in (("tiny", TINY), ("tiny-moe", TINY_MOE)):
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(data))
        configs.append({"name": name, "source": "test",
                        "file": f"chipbench/configs/{name}.json",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.chat", "config": name,
                      "traffic": "chat", "chips": 1, "why": "test"})
    (root / "chipbench" / "traffic" / "chat.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["chipbench"], "configs": configs, "workloads": cells,
        "end_to_end": [], "per_layer": []}))
    return root


def _run(root, cell="tiny.chat", seed=3, control=False):
    return harness.run_cell(load_cell(cell, root), seed=seed, seconds=1.0,
                            trace_dir=None, peaks=PEAKS["TPU v5 lite"],
                            t_start=0.0, control=control)


def test_sound_run_is_correct(root):
    out = _run(root)
    assert out.compiles_in_window == 0
    assert out.attempted >= 10 and out.sampled_tokens >= 40
    assert out.gap <= TINY_LIMIT
    run = out.run
    for name, f in measure.END_TO_END.items():
        assert f(run) > 0, name
    assert measure.output_tok_per_s(run) > 0


def _correct(root, out, cell):
    limits = load_cell(cell, root).model.data["check"]
    return check.correct(check.evaluate(out.gaps, limits))


def test_mixture_of_experts_runs(root):
    out = _run(root, "tiny-moe.chat")
    assert out.compiles_in_window == 0 and out.sampled_tokens >= 40
    assert _correct(root, out, "tiny-moe.chat")


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_is_not_correct(root, seed):
    out = _run(root, seed=seed, control=True)
    assert out.gap <= TINY_LIMIT < out.control_gap


def _altered_token(orig):
    """The decode step's token of slot 0 altered where it is produced."""
    def decode(self, params, caches, tokens, pos_vec, active):
        nxt, caches = orig(self, params, caches, tokens, pos_vec, active)
        vocab = self.lm.cfg.vocab_size
        return nxt.at[0].set((nxt[0] + 1) % vocab), caches
    return decode


def _state_unchanged(orig):
    """The decode step hands back the caches it was given."""
    def decode(self, params, caches, tokens, pos_vec, active):
        nxt, _ = orig(self, params, caches, tokens, pos_vec, active)
        return nxt, caches
    return decode


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny-moe.chat"])
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault,
                                                cell):
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ServingEngine, "_decode_impl",
                        fault(ServingEngine._decode_impl))
    assert not _correct(root, _run(root, cell), cell)


def test_reference_matches_program_in_float32(root, monkeypatch):
    """With the program computing in float32 the served tokens are the
    reference's argmax exactly: the reference runs the same model."""
    import dataclasses

    from lib.spec import ModelSpec

    orig = ModelSpec.program_config
    monkeypatch.setattr(ModelSpec, "program_config", lambda self: dataclasses
                        .replace(orig(self), compute_dtype="float32"))
    for cell in ("tiny.chat", "tiny-moe.chat"):
        assert _run(root, cell).gap == pytest.approx(0.0, abs=1e-5)


def test_reference_logits_are_float32_and_tied(root):
    import numpy as np

    from lib.reference import logits_at
    from lib.weights import make_weights

    cell = load_cell("tiny.chat", root)
    w = make_weights(cell.model, 4096, 5)
    out = logits_at(w, cell.model, np.arange(8), np.array([7]))
    assert out.shape == (1, 4096) and out.dtype == jnp.float32


def test_result_line(root):
    """The last line's keys, in order, with ``checks`` last; per-layer
    metrics from a trace reduction in a traced run."""
    from types import SimpleNamespace

    import run as bench_run
    from lib.phases import Phases
    from lib.trace import Reduction

    out = _run(root)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny.chat" if w == "qwen2-1.5b.chat" else w
                          for w in m["workloads"]]
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    cell = load_cell("tiny.chat", root)
    checks = check.evaluate(out.gaps, {"logit_gap": TINY_LIMIT})
    res = bench_run.result(cell, bench, out, [dev], None, checks)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "ttft_p75_s", "itl_p95_ms",
                                   "output_tok_per_s"}
    assert res["checks"]["logit_gap"] == {"value": out.gap,
                                          "limit": TINY_LIMIT}
    red = Reduction(window_s=out.run.seconds, busy_s=0.5 * out.run.seconds,
                    modules={"jit__decode_impl": [10, 0.1],
                             "jit_fn": [3, 0.05]},
                    ops={'%c.1 = bf16[4,128]{1,0} custom-call(%a, %b), '
                         'custom_call_target="tpu_custom_call"': [30, 0.05],
                         "%f.2 = bf16[4,64]{1,0} fusion(%a)": [9, 0.1]},
                    idle_by_label={"step": 0.2}, devices=1)
    out.run.trace = red
    res = bench_run.result(cell, bench, out, [dev], red, checks)
    assert list(res)[-2:] == ["breakdown", "checks"]
    assert res["device"]["busy_s"] == red.busy_s
    got = res["metrics"]
    assert got["model.decode_step_ms"]["value"] == pytest.approx(10.0)
    assert got["device.idle_share.chat"]["value"] == pytest.approx(50.0)
    assert 0 < got["gemm_roofline"]["value"]
    assert "grouped_gemm_roofline" not in got
    # a trace with no program spans leaves their readers silent
    assert not set(PHASE_METRICS) & set(got)
    assert res["breakdown"]["idle_gaps"] == [["step", 0.2]]
    red.phases = Phases(
        window_s=red.window_s, busy_s=red.busy_s,
        spans={"serve.step": [10, 0.5], "serve.decode": [10, 0.01]},
        idle_self={"serve.sync": 0.02}, idle_in={"serve.step": 0.03},
        modules={"jit__decode_impl": [10, 0.1], "jit_fn": [3, 0.05]},
        scopes={"jit__decode_impl": {"attention": [10, 0.01],
                                     "cast_weights": [10, 0.04],
                                     "": [5, 0.05]}})
    res = bench_run.result(cell, bench, out, [dev], red, checks)
    got = {k: res["metrics"][k]["value"] for k in PHASE_METRICS}
    assert got == pytest.approx(dict(zip(PHASE_METRICS,
                                         (3.0, 1.3, 10.0, 4.0))))
    assert res["breakdown"]["idle_gaps"] == [["step", 0.2],
                                             ["serve.sync", 0.02]]
    res = bench_run.result(cell, bench, out, [dev], None,
                           {"logit_gap": (None, TINY_LIMIT)})
    assert res["correct"] is False


def _digest(weights) -> str:
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for name in sorted(weights):
        h.update(name.encode())
        h.update(np.asarray(weights[name]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("data, digest", [
    (TINY, "12873ee67856ec8b5de2165316c441c8c1202898aaa65f6ed1a0399fefa93287"),
    (TINY_MOE,
     "005c6c08c9acaf0fd958746f75e75c2230e347679fc951f7615377d927ac1cf7")])
def test_weights_from_the_seed_are_pinned(data, digest):
    """The weights of a seed past 32 bits, bit for bit as the benchmark
    made them before the reference module owned their table."""
    from lib.spec import ModelSpec
    from lib.weights import make_weights

    w = make_weights(ModelSpec("t", data), 4096, 2**33 + 7)
    assert _digest(w) == digest


def test_a_reference_added_as_files_only(tmp_path):
    """A configuration that names a reference module of its own (here a
    copy of ``lib/reference.py`` under another name), added as new files
    and entries beside the checkout's, with no file under ``lib/``
    changed: the harness runs it end to end, correct, and its gaps are
    those of the default reference on the same served tokens."""
    from lib.weights import make_weights

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    lib = root / "chipbench" / "lib"
    before = {p.name: p.read_bytes() for p in lib.glob("*.py")}
    # the copy counts its calls, so that a run that used another shows
    (lib / "reference_copy.py").write_bytes(before["reference.py"] + b"""
CALLS = []
_logits_at, _shapes = logits_at, shapes


def logits_at(*args, **kwargs):
    CALLS.append("logits_at")
    return _logits_at(*args, **kwargs)


def shapes(*args):
    CALLS.append("shapes")
    return _shapes(*args)
""")
    configs, cells = [], []
    for name, data in (("tiny", TINY),
                       ("tiny-copy", dict(TINY, reference="reference_copy"))):
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(data))
        configs.append({"name": name, "source": "test",
                        "file": f"chipbench/configs/{name}.json",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.chat", "config": name,
                      "traffic": "chat", "chips": 1, "why": "test"})
    (root / "chipbench" / "traffic" / "chat.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["chipbench"], "configs": configs, "workloads": cells,
        "end_to_end": [], "per_layer": []}))
    copy = load_cell("tiny-copy.chat", root)
    assert copy.model.reference.__file__ == str(lib / "reference_copy.py")
    out = _run(root, "tiny-copy.chat")
    assert out.compiles_in_window == 0 and out.sampled_tokens >= 40
    assert _correct(root, out, "tiny-copy.chat")
    assert {"logits_at", "shapes"} <= set(copy.model.reference.CALLS)
    plain = load_cell("tiny.chat", root)
    w = make_weights(copy.model, 4096, 3)
    assert _digest(w) == _digest(make_weights(plain.model, 4096, 3))
    mine, _, _ = harness.outputs(w, copy, out.run.record, 3, False)
    theirs, _, _ = harness.outputs(w, plain, out.run.record, 3, False)
    for a, b, c in zip(out.gaps, mine, theirs, strict=True):
        assert (a == b).all() and (b == c).all()
    assert {p.name: p.read_bytes() for p in lib.glob("*.py")
            if p.name != "reference_copy.py"} == before


@pytest.mark.parametrize("lengths, size", [
    ([512, 100, 90, 80, 70, 60], 4),       # the longest alone holds 384
    ([60] * 12, 7),                       # 384 tokens take seven
    ([10] * 12, 8),                       # never more than eight
    ([30, 20], 2),                        # all there is
])
def test_sample_holds_the_longest_and_several_slots(lengths, size):
    from types import SimpleNamespace

    finished = [SimpleNamespace(rid=i, tokens=[0] * n)
                for i, n in enumerate(lengths)]
    picked = check.sample(finished, seed=2**40 + 1)
    assert len(picked) == size
    assert len(picked[0].tokens) == max(lengths)
    assert len({s.rid for s in picked}) == size
