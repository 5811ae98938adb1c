"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics
read: device busy and idle time, device time per XLA module and per
operation, and the device's idle gaps labelled by the host annotation they
fall in.

Planes named ``/device:TPU:<n>`` are the chips; on each, the ``XLA Ops``
line holds one event per operation executed and ``XLA Modules`` one per
jitted program run.  The harness's host annotations (``lib.serve``) are
events of the host plane; the one named ``window`` spans the measured
window, and only device time inside it counts.

A reduction of a profile (``reduce_file``) or of an export that keeps the
program's spans and scopes (``reduce_export``) also holds, as ``phases``,
``lib.phases``' reduction of the same trace by those spans and scopes, for
the readers that read the program's own phases.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import itertools
import os
import re
from collections import defaultdict

HOST_LABELS = ("submit", "step", "wait_arrival", "bookkeeping")
WINDOW = "window"
PALLAS = 'custom_call_target="tpu_custom_call"'


def _opcode(op: str) -> str:
    """The HLO opcode of an operation's text (``%x = type opcode(...)``)."""
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", op.partition(" = ")[2])
    return m.group(1) if m else ""


def _result_rank(op: str) -> int:
    m = re.match(r"[a-z0-9]+\[([0-9,]*)\]", op.partition(" = ")[2])
    if not m:
        return -1
    return len(m.group(1).split(",")) if m.group(1) else 0


def short_name(op: str) -> str:
    """``%convert.60 = bf16[28,1536,8960]{...} convert(...)`` ->
    ``convert.60 convert bf16[28,1536,8960]``; Pallas calls say so."""
    lhs, _, rhs = op.partition(" = ")
    if not rhs:
        return op[:120]
    m = re.match(r"[a-z0-9]+\[[0-9,]*\]", rhs)
    kind = "pallas" if PALLAS in rhs else _opcode(op)
    return f"{lhs.lstrip('%')} {kind} {m.group(0) if m else 'tuple'}"[:120]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # mean over the devices
    modules: dict                        # name -> [count, seconds]
    ops: dict                            # name -> [count, seconds]
    idle_by_label: dict                  # host label -> seconds
    devices: int
    phases: object = None                # lib.phases.Phases, where the
    #                                      trace has the program's spans

    def module(self, pattern: str) -> tuple[int, float]:
        """(count, seconds) of the modules whose name matches ``pattern``
        (a regular expression)."""
        n, s = 0, 0.0
        for name, (c, sec) in self.modules.items():
            if re.search(pattern, name):
                n, s = n + c, s + sec
        return n, s

    def pallas(self, ranks) -> tuple[int, float]:
        """(count, seconds) of the Pallas calls (``tpu_custom_call``) whose
        result has one of ``ranks`` dimensions.  The trace names an
        operation by its HLO instruction, not by its kernel's function
        (``kernel_metadata={}``), so the dense GEMM (a rank-2 result) and
        the grouped GEMM (rank 3, or 4 under ``vmap``) are told apart by
        rank: the only Pallas kernels on the serving path."""
        n, s = 0, 0.0
        for op, (c, sec) in self.ops.items():
            if PALLAS in op and _result_rank(op) in ranks:
                n, s = n + c, s + sec
        return n, s

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (the loops that hold
        other operations left out) and the idle time by host annotation,
        then by the innermost program span (``Phases.idle_rows``), at most
        ``top`` of each."""
        ops = sorted(((short_name(k), v[1]) for k, v in self.ops.items()
                      if _opcode(k) != "while"), key=lambda kv: -kv[1])
        gaps = [[k, v] for k, v in sorted(self.idle_by_label.items(),
                                          key=lambda kv: -kv[1])]
        if self.phases is not None:
            gaps += self.phases.idle_rows()
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": gaps[:top]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes) -> Reduction:
    """Reduce ``planes`` (anything with ``.name`` and ``.lines``, each line
    with ``.name`` and ``.events`` of ``.name``, ``.start_ns`` and
    ``.duration_ns``, as ``jax.profiler.ProfileData`` gives them)."""
    host_events = []
    devices = []
    for p in planes:
        if re.fullmatch(r"/device:TPU:\d+", p.name):
            devices.append(p)
        elif p.name.startswith("/host:"):
            for line in p.lines:
                host_events.extend(
                    ev for ev in _events(line)
                    if ev[0] in HOST_LABELS or ev[0] == WINDOW)
    windows = [ev for ev in host_events if ev[0] == WINDOW]
    if not windows:
        raise ValueError("the trace has no 'window' annotation")
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    _, lo, hi = windows[0]
    labels = sorted(((s, e, n) for n, s, e in host_events if n != WINDOW),
                    key=lambda t: t[0])
    # reach[i]: the latest end among labels[:i + 1]
    reach = list(itertools.accumulate((e for _, e, _ in labels), max))
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    idle = defaultdict(float)
    busy_total = 0.0
    for dev in devices:
        busy = []
        for line in dev.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for name, s, e in _events(line):
                s, e = _clip(s, e, lo, hi)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    m = modules[_module_name(name)]
                else:
                    m = ops[name]
                    busy.append((s, e))
                m[0] += 1
                m[1] += (e - s) * 1e-9
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                _label_gap(gs, ge, labels, reach, idle)
    nd = len(devices)
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_total / nd,
                     modules=dict(modules), ops=dict(ops),
                     idle_by_label={k: v / nd for k, v in idle.items()},
                     devices=nd)


def _label_gap(gs, ge, labels, reach, idle):
    """Split the idle gap [gs, ge) over the host annotations it overlaps;
    what no annotation covers is the harness's own (``other``).  The scan
    starts at the first annotation whose ``reach`` passes ``gs``: every
    one before it ends by the gap's start."""
    covered = 0
    for i in range(bisect.bisect_right(reach, gs), len(labels)):
        s, e, name = labels[i]
        if s >= ge:
            break
        a, b = _clip(s, e, gs, ge)
        if b > a:
            idle[name] += (b - a) * 1e-9
            covered += b - a
    # nested annotations can double-count; never report more than the gap
    rest = (ge - gs) - covered
    if rest > 0:
        idle["other"] += rest * 1e-9


def reduce_events(ev) -> Reduction:
    """The reduction of ``ev`` (``lib.phases.Events``: a profile as
    ``read_xplane`` reads it, or an export), with ``phases``."""
    from lib.phases import reduce_phases

    red = reduce_planes(ev.planes())
    red.phases = reduce_phases(ev)
    return red


def reduce_file(path: str) -> Reduction:
    """The reduction of the ``.xplane.pb`` at ``path``, with ``phases``.
    The file is read once, by ``lib.phases.read_xplane``, whose events
    reduce as ``jax.profiler.ProfileData``'s do."""
    from lib.phases import read_xplane

    return reduce_events(read_xplane(path))


def describe(path: str, top: int = 40) -> dict:
    """Planes, lines, event counts and the busiest event names of a trace,
    with the stats of one event per line: for looking at a trace by hand."""
    from jax.profiler import ProfileData
    out = {}
    for p in ProfileData.from_file(path).planes:
        lines = {}
        for line in p.lines:
            tot = defaultdict(lambda: [0, 0])
            first = None
            for e in line.events:
                t = tot[e.name]
                t[0] += 1
                t[1] += e.duration_ns
                if first is None:
                    first = {"name": e.name, "start_ns": e.start_ns,
                             "stats": [[str(k), str(v)[:200]]
                                       for k, v in e.stats]}
            lines[line.name] = {
                "events": sum(c for c, _ in tot.values()),
                "top": sorted(([k, c, d] for k, (c, d) in tot.items()),
                              key=lambda x: -x[2])[:top],
                "first": first}
        out[p.name] = lines
    return out


def export(path: str, out: str) -> None:
    """Write what ``reduce_file`` reads of the trace at ``path`` (the
    device operations with their programs and scopes, the harness's
    annotations and the program's spans: ``lib.phases.export``) to a
    gzipped JSON file: a small trace that tests can reduce."""
    from lib import phases
    phases.export(phases.read_xplane(path), out)


def _read_export(path: str) -> dict:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        return json.load(f)


def _first_form(doc: dict) -> list:
    """The planes of an export written before exports kept the program's
    spans and scopes (``{"planes": [...]}``)."""
    from types import SimpleNamespace as NS

    return [NS(name=p["name"], lines=[
        NS(name=line["name"], events=[
            NS(name=n, start_ns=s, duration_ns=d) for n, s, d in line["events"]])
        for line in p["lines"]]) for p in doc["planes"]]


def load_export(path: str) -> list:
    """The planes of an ``export`` file, shaped as ``reduce_planes`` reads
    them."""
    from lib.phases import events_of

    doc = _read_export(path)
    return _first_form(doc) if "planes" in doc else events_of(doc).planes()


def reduce_export(path: str) -> Reduction:
    """The reduction of an ``export`` file; ``phases`` is None for the
    first form, which has no spans or scopes."""
    from lib.phases import events_of

    doc = _read_export(path)
    if "planes" in doc:
        return reduce_planes(_first_form(doc))
    return reduce_events(events_of(doc))
