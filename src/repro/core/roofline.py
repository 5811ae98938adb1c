"""Three-term roofline analysis from compiled XLA artifacts.

For each (architecture x input shape x mesh) dry-run cell we derive:

    compute term    = HLO_FLOPs   / (chips x peak_FLOP/s)
    memory term     = HLO_bytes   / (chips x HBM_bw)
    collective term = coll_bytes  / (chips x link_bw)

``cost_analysis()`` supplies FLOPs and bytes; collective bytes are *not* in
cost_analysis, so we parse the optimized HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (prompt-specified methodology).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Mapping

from repro.core.hardware import V5E_HBM_BW, V5E_ICI_BW, V5E_PEAK_BF16

def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict; ``{}`` when analysis is
    unavailable."""
    return dict(compiled.cost_analysis() or {})


COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "e4m3": 1, "e5m2": 1,
}

# shape literal, e.g. "bf16[256,4096,512]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims_str: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0  # token/opaque types
    n = 1
    if dims_str:
        for d in dims_str.split(","):
            n *= int(d)
    return n * nb


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:                         # iota form: [n_groups, group_size]<=[...]
        return max(int(m.group(2)), 1)
    m = _GROUPS_BRACE_RE.search(line)
    if m:                         # explicit form: {{0,1,...},{...}}
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum *operand* bytes of every collective in an (optimized) HLO dump.

    XLA's text dumps print operands as bare names (no types), so operand
    sizes are derived from the RESULT shape left of ``=`` and each op's
    semantics (group size G parsed from ``replica_groups``):

        all-reduce / all-to-all / collective-permute: operand == result
        all-gather:      operand = result / G
        reduce-scatter:  operand = result * G

    ``fusion`` bodies can't contain collectives, so a line-wise scan is safe.
    """
    totals: dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    counts: dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", ls)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.match(r"^(?:\([^)]*\)|[a-z0-9\[\],{}\s]*?)\s*"
                       r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                       r"collective-permute)(-start|-done)?\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        suffix = opm.group(2) or ""
        if suffix == "-done":
            continue  # the -start line already carries the result shape
        # result type(s): everything before the op name
        head = rhs[:rhs.index(op + suffix + "(")]
        b = 0
        for dm in _SHAPE_RE.finditer(head):
            b += shape_bytes(dm.group(1), dm.group(2))
        if suffix == "-start" and head.lstrip().startswith("("):
            b //= 2               # async start returns (operand, result)
        g = _group_size(line)
        if op == "all-gather":
            b = b / g
        elif op == "reduce-scatter":
            b = b * g
        totals[op] += b
        counts[op] += 1
    totals["_total"] = sum(totals[o] for o in COLLECTIVE_OPS)
    totals["_count"] = float(sum(counts.values()))
    return totals


@dataclasses.dataclass(frozen=True)
class RooflineReport:
    """Roofline terms from a compiled SPMD artifact.

    IMPORTANT: ``compiled.cost_analysis()`` on a partitioned module reports
    the *per-device* program (verified in tests/test_roofline.py), so the
    assignment's ``X / (chips x rate)`` is realised as ``X_perdev / rate`` —
    numerically identical for perfectly-sharded ops and *more honest* for
    replicated ones (replicated compute costs every chip its full time).
    ``model_flops`` stays global and is divided by chips for the ideal.
    """
    arch: str
    shape_name: str
    mesh: str
    chips: int
    hlo_flops: float              # per-device
    hlo_bytes: float              # per-device
    coll_bytes: float             # per-device
    model_flops: float            # GLOBAL: 6 N D (dense) / 6 N_active D (MoE)
    coll_detail: Mapping[str, float]

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / V5E_PEAK_BF16

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / V5E_HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / V5E_ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Lower-bound step time: overlapped resources -> max of the terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the step at the dominant bottleneck:
        MODEL_FLOPs-at-peak over the bound step time."""
        ideal = self.model_flops / (self.chips * V5E_PEAK_BF16)
        return ideal / self.step_time if self.step_time > 0 else 0.0

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS — catches remat/redundant compute."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape_name, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "hlo_gflops": self.hlo_flops / 1e9,
            "hlo_gbytes": self.hlo_bytes / 1e9,
            "coll_gbytes": self.coll_bytes / 1e9,
            "model_gflops": self.model_flops / 1e9,
            "useful_flop_ratio": self.useful_flop_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def from_compiled(arch: str, shape_name: str, mesh_name: str, chips: int,
                  cost: dict, hlo_text: str, model_flops: float
                  ) -> RooflineReport:
    """Build a report from ``compiled.cost_analysis()`` + HLO text.

    cost_analysis flops/bytes are per-device on SPMD modules; the term
    properties use per-chip rates accordingly (see class docstring).
    """
    coll = collective_bytes(hlo_text)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    return RooflineReport(
        arch=arch, shape_name=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes, coll_bytes=coll["_total"],
        model_flops=model_flops, coll_detail=coll,
    )
