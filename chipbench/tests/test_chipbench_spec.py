"""BENCHMARK.json and the files it names: names and units keep to the
allowed characters, every entry has its files, a cell added as files only
is found, and each configuration file is what the program runs."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from lib.spec import (NAME, UNIT, load_benchmark, load_cell,  # noqa: E402
                      metric_entries, validate)

KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return load_benchmark(ROOT)


def test_benchmark_is_valid(bench):
    assert set(bench) == KEYS
    assert validate(bench, ROOT) == []
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names) - {w["traffic"] for w in bench["workloads"]}) == \
        len(names) - len(bench["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for text in ([w["why"] for w in bench["workloads"]]
                 + [c["why"] for c in bench["configs"]]
                 + [m["layer"] for m in bench["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    assert len(json.dumps(bench)) <= 64 * 1024


def test_every_cell_reports_what_it_must(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in metric_entries(bench, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert metric_entries(bench, w["name"], True)


def test_roofline_and_mfu_shares(bench):
    """A kernel's roofline share is named <kernel>_roofline, in %, beside a
    whole-step mfu moving the same metric."""
    roof = [m for m in bench["per_layer"] if m["name"].endswith("_roofline")]
    mfu = [m for m in bench["per_layer"] if "mfu" in m["name"]]
    assert roof and mfu
    assert all(m["unit"] == "%" for m in roof + mfu)
    assert {m["moves"] for m in roof} <= {m["moves"] for m in mfu}


def test_a_cell_added_as_files_only(tmp_path, bench):
    """A new mix file and a new workload entry: found and valid, with no
    change to any code."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    mix = json.loads((BENCH / "traffic" / "chat.qwen2-1.5b.json")
                     .read_text())
    mix.update(arrival="bursty", burst=8, intra_gap_s=0.001)
    (root / "chipbench" / "traffic" / "chat-bursty.json").write_text(
        json.dumps(mix))
    bench = json.loads(json.dumps(bench))
    bench["workloads"].append({
        "name": "qwen2-1.5b.chat-bursty", "config": "qwen2-1.5b",
        "traffic": "chat-bursty", "chips": 1,
        "why": "bursts of 8 at the chat rate"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert validate(bench, root) == []
    cell = load_cell("qwen2-1.5b.chat-bursty", root)
    assert cell.mix.arrival == "bursty" and cell.mix.burst == 8
    assert cell.model.name == "qwen2-1.5b" and cell.chips == 1
    bench["workloads"].append({
        "name": "qwen2-1.5b.nothing", "config": "qwen2-1.5b",
        "traffic": "nothing", "chips": 2, "why": "no such mix"})
    faults = validate(bench, root)
    assert any("no traffic file" in f for f in faults)
    assert any("chips must be 1 or 4" in f for f in faults)
    conf = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())
    (root / "chipbench" / "configs" / "q.json").write_text(
        json.dumps(dict(conf, reference="nothing")))
    bench["configs"].append(dict(bench["configs"][0], name="q",
                                 file="chipbench/configs/q.json"))
    assert any("no reference lib/nothing.py" in f
               for f in validate(bench, root))


#: the configurations of BENCHMARK.json, read as the tests are collected
CONFIGS = [c["name"] for c in load_benchmark(ROOT)["configs"]]
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_what_the_program_runs(bench, name):
    from repro.configs import get_config

    entry = {c["name"]: c for c in bench["configs"]}[name]
    data = json.loads((ROOT / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(data["reduced"])
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == name)
    spec = load_cell(cell, ROOT).model
    cfg = spec.program_config()
    published = get_config(data["program_arch"])
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim",
                  "vocab_size", "d_ff", "moe_d_ff", "n_experts",
                  "experts_per_token", "qkv_bias", "tie_embeddings"):
        assert getattr(cfg, field) == getattr(published, field), field
    # a multiplier the program has runs as the file states it, which is
    # what the program registers for the architecture
    for key in MULTIPLIERS:
        if hasattr(published, key):
            assert getattr(cfg, key) == pytest.approx(spec.multiplier(key))
            assert getattr(cfg, key) == pytest.approx(getattr(published,
                                                              key)), key
    changed = {"n_layers": "num_hidden_layers"}
    for field, key in changed.items():
        if getattr(cfg, field) != getattr(published, field):
            assert key in data["reduced"]
    assert cfg.n_layers == data["num_hidden_layers"]
    for key in data["reduced"]:
        assert key in data.get("published", {}), key


#: granite-3.0-3b-a800m as published, at 16 of its 32 layers
GRANITE = {"program_arch": "granite-moe-3b-a800m", "hidden_size": 1536,
           "intermediate_size": 512, "num_attention_heads": 24,
           "num_key_value_heads": 8, "head_dim": 64,
           "num_hidden_layers": 16, "num_local_experts": 40,
           "num_experts_per_tok": 8, "vocab_size": 49155,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": True, "attention_bias": False,
           "hidden_act": "silu", "embedding_multiplier": 12.0,
           "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
           "logits_scaling": 6.0}
#: the plain pre-norm block's values of the four (attention: 1/sqrt(64))
PLAIN_BLOCK = {"embedding_multiplier": 1.0, "attention_multiplier": 0.125,
               "residual_multiplier": 1.0, "logits_scaling": 1.0}


def _stand_in(monkeypatch, multipliers: bool):
    """Make ``program_config`` obtain, for Granite, a stand-in of the
    program's configuration: the registered one's fields without the four
    multipliers, or with them at the plain block's values."""
    import dataclasses

    import repro.configs
    from repro.configs.base import ModelConfig

    real = repro.configs.get_config("granite-moe-3b-a800m")
    fields = [(f.name, f.type) for f in dataclasses.fields(ModelConfig)
              if f.name not in MULTIPLIERS]
    values = {name: getattr(real, name) for name, _ in fields}
    if multipliers:
        fields += [(key, float) for key in MULTIPLIERS]
        values.update(PLAIN_BLOCK)
    cls = dataclasses.make_dataclass("StandIn", fields, frozen=True,
                                     kw_only=True)
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch: cls(**values))


@pytest.mark.parametrize("key, plain", list(PLAIN_BLOCK.items()))
def test_program_that_cannot_run_a_key_is_refused(monkeypatch, key, plain):
    """A program with no Granite multipliers: each one as published is
    refused, and the plain block's value of all four runs."""
    from lib.spec import ModelSpec

    _stand_in(monkeypatch, multipliers=False)
    with pytest.raises(ValueError, match="the program has no"):
        ModelSpec("g", GRANITE).program_config()
    data = dict(GRANITE, **PLAIN_BLOCK)
    cfg = ModelSpec("g", data).program_config()
    assert cfg.n_layers == 16 and not hasattr(cfg, key)
    with pytest.raises(ValueError, match=key):
        ModelSpec("g", dict(data, **{key: 2 * plain})).program_config()


@pytest.mark.parametrize("key", MULTIPLIERS)
def test_program_that_has_a_key_runs_it_as_published(monkeypatch, key):
    """A program with the four multipliers: each published value reaches
    its field unchanged, and a file without the key runs the plain
    block's value."""
    from lib.spec import ModelSpec

    _stand_in(monkeypatch, multipliers=True)
    cfg = ModelSpec("g", GRANITE).program_config()
    assert getattr(cfg, key) == GRANITE[key]
    assert cfg.n_layers == 16 and cfg.moe_d_ff == 512
    data = {k: v for k, v in GRANITE.items() if k != key}
    assert getattr(ModelSpec("g", data).program_config(), key) == \
        PLAIN_BLOCK[key]


@pytest.mark.parametrize("key", ["sliding_window", "partial_rotary_factor"])
def test_unknown_published_key_is_refused(key):
    """A published key that neither the benchmark nor the configuration's
    reference runs is refused, not dropped: the program would run its own
    registered value while the reference runs the file's."""
    from lib.spec import ModelSpec

    spec = ModelSpec("g", dict(GRANITE, **PLAIN_BLOCK, **{key: 4096}))
    with pytest.raises(ValueError, match=f"published keys \\['{key}'\\]"):
        spec.program_config()


def test_reference_module_is_named_by_the_configuration(tmp_path):
    """``reference`` names a module under the checkout's ``lib/``; one
    that is not there, or is no module name, is refused."""
    from lib.spec import ModelSpec

    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "other_ref.py").write_text("PROGRAM_FIELD = {'window': 'w'}\n")
    spec = ModelSpec("g", dict(GRANITE, reference="other_ref"), lib_dir=lib)
    assert spec.reference.PROGRAM_FIELD == {"window": "w"}
    assert ModelSpec("g", GRANITE).reference.__file__ == str(
        BENCH / "lib" / "reference.py")
    with pytest.raises(FileNotFoundError):
        ModelSpec("g", dict(GRANITE, reference="nothing"),
                  lib_dir=lib).reference
    with pytest.raises(ValueError, match="not a module name"):
        ModelSpec("g", dict(GRANITE, reference="../reference"),
                  lib_dir=lib).reference


def test_run_fails_off_a_tpu():
    """No result, and a non-zero exit, where JAX finds no TPU."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "{" not in p.stdout


@pytest.mark.parametrize("kind, count, message", [
    ("TPU v4", 1, "no peaks"), ("TPU v5 lite", 1, None),
    ("TPU v5 lite", 0, "needs 4 chips")])
def test_chip_look(monkeypatch, kind, count, message):
    from types import SimpleNamespace

    import jax
    import run

    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices",
                        lambda: [dev] * max(count, 1) if count else [dev])
    chips = 4 if count == 0 else 1
    if message is None:
        devs, peaks = run.chip_or_exit(chips)
        assert peaks["bf16_flops_per_s"] == 197e12
    else:
        with pytest.raises(SystemExit, match=message):
            run.chip_or_exit(chips)
