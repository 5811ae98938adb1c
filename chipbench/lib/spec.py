"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's file is the one its ``configs`` entry names; the mix is
``traffic/<traffic>.<config>.json`` when the cell has a mix of its own, else
``traffic/<traffic>.json``; a per-layer metric's reader is
``metrics/<metric>.py``; a configuration's plain reference is the module
``lib/<reference>.py`` that its file names (``lib/reference.py`` where it
names none).  Adding a cell, a mix, a configuration, its reference or a
metric is adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path

from lib.traffic import Mix

#: chipbench/ -- every path the benchmark reads is under it or is the
#: BENCHMARK.json beside it
BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

#: published (Hugging Face config.json) key -> the program's ModelConfig
#: field that runs it; a configuration file gives the published keys as run
PROGRAM_FIELD = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
}
#: the Granite family's multipliers at the values that a configuration
#: without the key means (the plain pre-norm block); the reference runs
#: every one as the file states it
PLAIN = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "logits_scaling": 1.0}
#: published keys that ``program_config`` runs itself
OWN_KEYS = {"intermediate_size", "capacity_factor", "attention_multiplier",
            *PLAIN}
#: keys that describe a configuration and set no program field
DESCRIPTIVE = {"source", "paper", "reduced", "assumed", "deployment",
               "engine", "check", "reference", "published",
               "initializer_range", "hidden_act", "torch_dtype",
               "program_arch"}
MODULE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python module in the file ``path``, loaded once per process."""
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod          # where a dataclass looks up its module
    mod_spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True, eq=False)
class ModelSpec:
    """A configuration as it is run (its file's keys); ``lib_dir`` holds
    the reference modules it may name."""

    name: str
    data: dict
    lib_dir: Path = BENCH_DIR / "lib"

    def __getattr__(self, key):
        try:
            return self.data[key]
        except KeyError:
            raise AttributeError(key) from None

    @property
    def is_moe(self) -> bool:
        return bool(self.data.get("num_local_experts"))

    @property
    def attention_multiplier(self) -> float:
        return self.data.get("attention_multiplier", self.head_dim ** -0.5)

    def multiplier(self, key: str) -> float:
        if key == "attention_multiplier":
            return self.attention_multiplier
        return float(self.data.get(key, PLAIN[key]))

    @property
    def reference(self):
        """The plain reference this configuration runs against: the module
        ``lib/<reference>.py`` that its file names, ``reference`` where it
        names none (see that module for what one holds)."""
        name = self.data.get("reference", "reference")
        if not isinstance(name, str) or not MODULE.match(name):
            raise ValueError(f"{self.name}: reference {name!r} is not a "
                             f"module name")
        return load_module(self.lib_dir / f"{name}.py")

    def program_config(self):
        """The program's ``ModelConfig``: its registered architecture
        (block kind, activation, norm) with every size of this file.
        Refuses a published key that nothing here or in the reference
        runs, a multiplier the program cannot run, and a block the
        reference does not run."""
        from repro.configs import get_config

        ref = self.reference
        fields = {**PROGRAM_FIELD, **ref.PROGRAM_FIELD}
        unknown = sorted(set(self.data) - set(fields) - OWN_KEYS
                         - DESCRIPTIVE)
        if unknown:
            raise ValueError(f"{self.name}: no program field runs the "
                             f"published keys {unknown}")
        base = get_config(self.data["program_arch"])
        sets = {fields[k]: v for k, v in self.data.items() if k in fields}
        if self.is_moe:
            sets.update(moe_d_ff=self.intermediate_size, d_ff=0)
        else:
            sets["d_ff"] = self.intermediate_size
        if "capacity_factor" in self.data:
            sets["capacity_factor"] = self.data["capacity_factor"]
        # a multiplier goes to the program's field of the same name; where
        # the program has none, only the plain block's value can run
        program = {f.name for f in dataclasses.fields(base)}
        plain = dict(PLAIN, attention_multiplier=self.head_dim ** -0.5)
        for key, value in plain.items():
            if key in program:
                sets[key] = self.multiplier(key)
            elif abs(self.multiplier(key) - value) > 1e-12:
                raise ValueError(f"{self.name}: the program has no "
                                 f"{key} to run {self.multiplier(key)}")
        return ref.program_config(self, base, sets)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    model: ModelSpec
    mix: Mix
    chips: int
    engine: dict


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def mix_path(bench_dir: Path, traffic: str, config: str) -> Path:
    own = bench_dir / "traffic" / f"{traffic}.{config}.json"
    return own if own.exists() else bench_dir / "traffic" / f"{traffic}.json"


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    data = _read_json(root / centry["file"])
    bench_dir = root / bench["paths"][0]
    mix = Mix.from_dict(w["traffic"],
                        _read_json(mix_path(bench_dir, w["traffic"],
                                            w["config"])))
    model = ModelSpec(w["config"], data, lib_dir=bench_dir / "lib")
    return Cell(name=name, model=model, mix=mix,
                chips=int(w["chips"]), engine=dict(data["engine"]))


def metric_entries(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones with
    ``trace`` 0, the per-layer ones with 1, each only in its ``workloads``
    where it lists them."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def validate(bench: dict, root: Path = ROOT) -> list[str]:
    """Faults of ``bench`` against the names, units and files it must keep
    to; empty when there are none."""
    faults = []

    def name_ok(kind, value):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{kind} {value!r} is not a valid name")

    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        name_ok("config", c["name"])
        for key in c["reduced"]:
            name_ok("reduced key", key)
        if not (root / c["file"]).is_file():
            faults.append(f"config {c['name']}: no file {c['file']}")
            continue
        ref = _read_json(root / c["file"]).get("reference", "reference")
        if not (root / bench["paths"][0] / "lib" / f"{ref}.py").is_file():
            faults.append(f"config {c['name']}: no reference lib/{ref}.py")
    cells = set()
    for w in bench["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        cells.add(w["name"])
        if w["config"] not in configs:
            faults.append(f"{w['name']}: unknown config {w['config']!r}")
            continue
        if w["chips"] not in (1, 4):
            faults.append(f"{w['name']}: chips must be 1 or 4")
        bench_dir = root / bench["paths"][0]
        if not mix_path(bench_dir, w["traffic"], w["config"]).is_file():
            faults.append(f"{w['name']}: no traffic file for "
                          f"{w['traffic']!r}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok("metric", m["name"])
        if not UNIT.match(m["unit"]):
            faults.append(f"{m['name']}: unit {m['unit']!r} is not valid")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"{m['name']}: better must be lower or higher")
        if m["source"] not in SOURCES:
            faults.append(f"{m['name']}: unknown source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                faults.append(f"{m['name']}: unknown workload {c!r}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            faults.append(f"{m['name']}: moves unknown {m['moves']!r}")
        if not (root / bench["paths"][0] / "metrics"
                / f"{m['name']}.py").is_file():
            faults.append(f"{m['name']}: no reader metrics/{m['name']}.py")
    if "setup_s" not in e2e:
        faults.append("no setup_s among the end-to-end metrics")
    return faults
