"""Where the device's time goes, by the program's own spans and scopes.

The program opens a ``repro.obs`` span (a ``jax.profiler.TraceAnnotation``)
around each phase of its serving step -- ``serve.step`` and, inside it,
``serve.admit`` (``serve.prefill``, ``serve.insert``), ``serve.pack``,
``serve.decode``, ``serve.sync``, ``serve.unpack``, ``serve.account`` -- and
names the parts of its models with ``jax.named_scope`` (``cast_weights``,
``embed``, ``attention``, ``mlp``, ``moe``, ``head``, ``sample``,
``gemm.prep``).  In a profiler trace the spans are events of the host plane,
on the clock of the device planes; each device operation's scope path is
its ``tf_op`` stat (``jit(_decode_impl)/while/body/closed_call/attention/
bsd,dhe->bshe/dot_general``), kept with the operation's metadata, where
``jax.profiler.ProfileData`` does not reach.  So ``read_xplane`` reads the
``.xplane.pb`` itself, with a schema of the few fields it needs.

``reduce_phases`` puts the device's idle time in the window down to the
innermost program span open over it (the span's self time), and each
program's device time down to its operations' scopes.  ``lib.trace``
reads a profile through ``read_xplane`` too, reduces the same events for
the benchmark's metrics, and keeps this reduction beside its own
(``Reduction.phases``), where the readers of the program's phases find it.
``export`` keeps what both reduce in a small gzipped file that tests can
reduce; ``phases.py`` beside ``run.py`` runs a traced window and prints
the reduction.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from collections import defaultdict
from types import SimpleNamespace as NS

from lib.trace import HOST_LABELS, WINDOW, _module_name, _opcode, _union

#: the program's span namespaces (``repro.obs`` spans)
PROGRAM_SPAN = re.compile(r"^(serve|gemm|sim|calibrate)\.")
#: the metadata stat of a device operation that holds its scope path
SCOPE_STAT = "tf_op"
#: operations that hold others (a loop's body runs as its own operations)
CONTAINERS = ("while", "conditional", "call")
#: parts of a scope path that name a transformation, not a scope
_TRANSFORM = re.compile(r"^(\w+\(.*\)|while|body|cond|closed_call|"
                        r"checkpoint|remat|branch_\d+)$")
DEVICE = re.compile(r"/device:TPU:\d+")
DECODE = r"_decode_impl"


def scope_of(path: str) -> str:
    """``jit(f)/while/body/closed_call/attention/dot_general:`` ->
    ``attention``: the named scopes of an operation's path, without the
    transformations and the primitive's own name."""
    parts = path.split("/")[:-1]
    return "/".join(p for p in parts if p and not _TRANSFORM.match(p))


@dataclasses.dataclass
class Events:
    """What ``reduce_phases`` reads of a trace; times in ns."""

    window: tuple | None   # the host annotation ``window``
    host: list             # (name, start, end): harness labels, spans
    devices: dict          # plane -> {"modules": [(name, start, end)],
    #                        "ops": [(name, start, end, module, scope)]}

    def planes(self) -> list:
        """The same events as ``lib.trace.reduce_planes`` reads them."""
        host = list(self.host)
        if self.window:
            host.append((WINDOW, *self.window))

        def line(name, evs):
            return NS(name=name, events=[
                NS(name=ev[0], start_ns=ev[1], duration_ns=ev[2] - ev[1])
                for ev in evs])
        return [NS(name="/host:CPU", lines=[line("python", host)])] + [
            NS(name=p, lines=[line("XLA Modules", d["modules"]),
                              line("XLA Ops", d["ops"])])
            for p, d in self.devices.items()]


def _xspace_type():
    """The message class of an ``XSpace`` (tsl's ``xplane.proto``), cut to
    the fields read here; protobuf skips the others."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="xp", syntax="proto2")
    i64, u64, s = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_STRING
    schema = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, s), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, i64), ("value", 2, "Metadata")],
        "StatMetadataEntry": [("key", 1, i64), ("value", 2, "Metadata")],
        "Metadata": [("name", 2, s), ("stats", 5, "XStat")],
        "XLine": [("name", 2, s), ("timestamp_ns", 3, i64),
                  ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64)],
        "XStat": [("metadata_id", 1, i64), ("uint64_value", 3, u64),
                  ("str_value", 5, s), ("ref_value", 7, u64)],
    }
    repeated = {"planes", "lines", "event_metadata", "stat_metadata",
                "stats", "events"}
    for name, fields in schema.items():
        m = f.message_type.add(name=name)
        for fname, num, typ in fields:
            field = m.field.add(name=fname, number=num, label=(
                fd.LABEL_REPEATED if fname in repeated
                else fd.LABEL_OPTIONAL))
            if isinstance(typ, str):
                field.type, field.type_name = fd.TYPE_MESSAGE, f".xp.{typ}"
            else:
                field.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xp.XSpace"))


def read_xplane(path: str) -> Events:
    """The events of the ``.xplane.pb`` at ``path`` that ``reduce_phases``
    and ``lib.trace.reduce_planes`` read.  Times as
    ``jax.profiler.ProfileData`` gives them: the line's ns plus whole ns
    of offset."""
    with open(path, "rb") as fh:
        space = _xspace_type().FromString(fh.read())
    win, host, devices = None, [], {}
    for p in space.planes:
        device = DEVICE.fullmatch(p.name)
        if not (device or p.name.startswith("/host:")):
            continue
        meta = {m.key: m.value for m in p.event_metadata}
        stat_names = {m.key: m.value.name for m in p.stat_metadata}
        lines = {ln.name: ln for ln in p.lines}

        def events(line):
            t0 = line.timestamp_ns
            for ev in line.events:
                s = t0 + ev.offset_ps // 1000
                yield meta[ev.metadata_id], s, s + ev.duration_ps // 1000
        if not device:
            for line in p.lines:
                for md, s, e in events(line):
                    if md.name == WINDOW and win is None:
                        win = (s, e)
                    elif md.name in HOST_LABELS or PROGRAM_SPAN.match(
                            md.name):
                        host.append((md.name, s, e))
            continue

        def stat(md, key):
            for st in md.stats:
                if stat_names.get(st.metadata_id) == key:
                    if st.HasField("ref_value"):
                        return stat_names.get(st.ref_value, "")
                    if st.HasField("uint64_value"):
                        return st.uint64_value
                    return st.str_value
            return ""
        mods = [(md.name, s, e) for md, s, e in
                events(lines["XLA Modules"])] if "XLA Modules" in lines \
            else []
        program = {int(m.group(1)): _module_name(n) for n, _, _ in mods
                   if (m := re.search(r"\((\d+)\)$", n))}
        seen, ops = {}, []
        for md, s, e in (events(lines["XLA Ops"]) if "XLA Ops" in lines
                         else ()):
            if id(md) not in seen:
                seen[id(md)] = (program.get(stat(md, "program_id"), ""),
                                scope_of(str(stat(md, SCOPE_STAT))))
            ops.append((md.name, s, e, *seen[id(md)]))
        devices[p.name] = {"modules": mods, "ops": ops}
    return Events(window=win, host=host, devices=devices)


@dataclasses.dataclass
class Phases:
    window_s: float
    busy_s: float                # mean over the devices
    spans: dict                  # program span -> [count, seconds]
    idle_self: dict              # innermost program span -> idle seconds
    idle_in: dict                # program span -> idle seconds in its spans
    modules: dict                # module -> [count, seconds]
    scopes: dict                 # module -> {scope: [count, seconds]}

    def scope_s(self, module: str, scope: str | None = None) -> float:
        """Device seconds of the operations (not the loops that hold
        others) of the modules matching ``module`` (a regular expression)
        whose scope path holds ``scope``; all of them for None."""
        return sum(sec for m, by in self.scopes.items()
                   if re.search(module, m)
                   for sc, (_, sec) in by.items()
                   if scope is None or scope in sc.split("/"))

    def per_step(self) -> dict:
        """Device idle ms inside ``serve.step`` per step that decoded; XLA
        programs run per step; attention's share (%) of the decode
        program's operation time; the weight casts' ms per decode run."""
        steps = self.spans.get("serve.step", [0])[0]
        decodes = self.spans.get("serve.decode", [0])[0]
        runs = sum(c for m, (c, _) in self.modules.items()
                   if re.search(DECODE, m))
        dec = self.scope_s(DECODE)
        return {
            "engine.step_idle_ms": (1e3 * self.idle_in.get("serve.step", 0.0)
                                    / decodes if decodes else None),
            "engine.programs_per_step": (
                sum(c for c, _ in self.modules.values()) / steps
                if steps else None),
            "model.decode_attention_share": (
                100.0 * self.scope_s(DECODE, "attention") / dec
                if dec else None),
            "model.decode_weight_cast_ms": (
                1e3 * self.scope_s(DECODE, "cast_weights") / runs
                if runs else None),
        }

    def idle_rows(self) -> list:
        """[span, idle seconds] for every program span that holds idle
        time as the innermost one, most first."""
        return sorted(([k, v] for k, v in self.idle_self.items() if v > 0),
                      key=lambda kv: -kv[1])


def _nest(spans) -> list:
    """(start, end, name) spans -> disjoint (start, end, name) segments in
    time order, each under the innermost span open over it.  A span that
    outlives the one it opened in is cut at that one's end."""
    out, stack, t = [], [], None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if end > t:
                out.append((t, end, nm))
                t = end
        if stack:
            if s > t:
                out.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        end, nm = stack.pop()
        if end > t:
            out.append((t, end, nm))
            t = end
    return out


def _overlap(gaps, intervals, into) -> None:
    """Add to ``into[key]`` the ns of ``gaps`` that each (start, end, key)
    of ``intervals`` covers; both in time order, each disjoint."""
    j = 0
    for gs, ge in gaps:
        while j < len(intervals) and intervals[j][1] <= gs:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < ge:
            s, e, key = intervals[k]
            into[key] += min(ge, e) - max(gs, s)
            k += 1


def reduce_phases(ev: Events) -> Phases:
    """Reduce ``ev`` over its window (the whole trace where it has none),
    as ``lib.trace`` does: busy is the union of the operations' intervals,
    and a module or operation counts where it overlaps the window."""
    if not ev.devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = ev.window or (
        min(o[1] for d in ev.devices.values() for o in d["ops"]),
        max(o[2] for d in ev.devices.values() for o in d["ops"]))
    spans = [(max(s, lo), min(e, hi), n) for n, s, e in ev.host
             if PROGRAM_SPAN.match(n) and min(e, hi) > max(s, lo)]
    counts = defaultdict(lambda: [0, 0.0])
    by_name = defaultdict(list)
    for s, e, n in spans:
        counts[n][0] += 1
        counts[n][1] += (e - s) * 1e-9
        by_name[n].append((s, e))
    segments = _nest(spans)
    unions = [[(s, e, n) for s, e in _union(iv)]
              for n, iv in by_name.items()]
    modules = defaultdict(lambda: [0, 0.0])
    scopes = defaultdict(lambda: defaultdict(lambda: [0, 0.0]))
    idle_self, idle_in = defaultdict(float), defaultdict(float)
    busy_ns = 0
    for dev in ev.devices.values():
        for name, s, e in dev["modules"]:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                modules[_module_name(name)][0] += 1
                modules[_module_name(name)][1] += (b - a) * 1e-9
        busy = []
        for name, s, e, module, scope in dev["ops"]:
            a, b = max(s, lo), min(e, hi)
            if b <= a:
                continue
            busy.append((a, b))
            if _opcode(name) not in CONTAINERS:
                c = scopes[module][scope]
                c[0] += 1
                c[1] += (b - a) * 1e-9
        merged = _union(busy)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        _overlap(gaps, segments, idle_self)
        for iv in unions:
            _overlap(gaps, iv, idle_in)
    nd = len(ev.devices)
    return Phases(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / nd,
        spans=dict(counts),
        idle_self={k: v * 1e-9 / nd for k, v in idle_self.items()},
        idle_in={k: v * 1e-9 / nd for k, v in idle_in.items()},
        modules=dict(modules),
        scopes={m: dict(by) for m, by in scopes.items()})


def _columns(rows, keys: int) -> dict:
    """Rows (key fields..., start, end) -> a table of the distinct keys and,
    in time order, three columns: key index, start minus the previous
    row's end (small on a busy device), duration."""
    table, prev = {}, 0
    out = {"keys": [], "k": [], "gap": [], "d": []}
    for r in sorted(rows, key=lambda r: r[keys]):
        key = r[:keys]
        if key not in table:
            table[key] = len(table)
            out["keys"].append(list(key))
        out["k"].append(table[key])
        out["gap"].append(r[keys] - prev)
        out["d"].append(r[keys + 1] - r[keys])
        prev = r[keys + 1]
    return out


def _rows(col: dict) -> list:
    t, rows = 0, []
    for k, gap, d in zip(col["k"], col["gap"], col["d"]):
        rows.append((*col["keys"][k], t + gap, t + gap + d))
        t += gap + d
    return rows


def export(ev: Events, out: str) -> None:
    """Write ``ev`` to a gzipped JSON file, as ``load_export`` reads it."""
    doc = {"window": ev.window,
           "host": _columns([(n, s, e) for n, s, e in ev.host], 1),
           "devices": {p: {
               "modules": _columns(d["modules"], 1),
               "ops": _columns([(n, m, sc, s, e)
                                for n, s, e, m, sc in d["ops"]], 3)}
               for p, d in ev.devices.items()}}
    with gzip.open(out, "wt") as fh:
        json.dump(doc, fh, separators=(",", ":"))


def load_export(path: str) -> Events:
    with gzip.open(path, "rt") as fh:
        return events_of(json.load(fh))


def events_of(doc: dict) -> Events:
    """The events of an ``export`` file's document."""
    return Events(
        window=tuple(doc["window"]) if doc["window"] else None,
        host=_rows(doc["host"]),
        devices={p: {"modules": _rows(d["modules"]),
                     "ops": [(n, s, e, m, sc)
                             for n, m, sc, s, e in _rows(d["ops"])]}
                 for p, d in doc["devices"].items()})
