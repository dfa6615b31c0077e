"""Reduce a profiler trace by the program's own names: the named scopes of
its device operations and the ``engine.*`` spans of its host loop.

Each operation of a TPU's ``XLA Ops`` line carries the JAX name stack it
was traced under as the ``tf_op`` stat of its event metadata, e.g.
``jit(chunk)/decode.chunk/while/body/decode.step/decode.layers/while/body/
decode.layer/kv.write/dynamic_update_slice:``.  ``jax.profiler.ProfileData``
does not show event metadata, so this module reads the ``.xplane.pb``
itself, with the few XPlane messages it needs defined below (field numbers
as in ``tsl/profiler/protobuf/xplane.proto``).

From one traced window it keeps:

* ``scope_s``: device seconds per (program, scope), where the scope of an
  operation is the innermost component of its ``tf_op`` that is one of
  :data:`SCOPES` or a ``repro.*`` kernel scope, else ``unscoped``; an
  operation whose ``tf_op`` holds no name stack of the program (empty, or
  an argument's name) was added by XLA itself: a layout copy of an
  argument, a copy into a loop's carry, a prefetch.  No scope can reach
  those, and they count as ``xla.inserted``;
* ``engine_idle``: the device's idle gaps, each charged to the innermost
  ``engine.*`` host span open at its midpoint (``engine.none`` where none
  is), and the ``engine.*`` spans that start in the window, with their
  attributes.  The engine writes these spans through ``repro.obs`` while
  tracing is enabled.

Times are means over chips, as in ``bench/lib/trace.py``, whose window,
program and gap rules this follows.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools

from bench.lib.stats import percentile
from bench.lib.trace import CONTAINERS, _union, module_name, op_name

#: the scopes the serving programs put on their device operations
SCOPES = frozenset({
    "decode.chunk", "decode.step", "decode.layers", "decode.layer",
    "attn.decode", "kv.view", "kv.commit", "kv.write", "kv.prefill_write",
})

#: the scopes of ``jit_chunk`` that move the KV cache rather than compute:
#: the gather of the paged view, the commit back to the pages, each
#: layer's row write, the layer scan's slicing and restacking of the
#: stacked cache, and the chunk scan's own carry
KV_MOVE_SCOPES = ("kv.view", "kv.commit", "kv.write", "decode.layers",
                  "decode.chunk")

DECODE_PROGRAM = "jit_chunk"


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message, with only the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench.xplane", syntax="proto3")

    def message(name, fields, parent=fd.message_type):
        m = parent.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench.xplane." + ftype
            else:
                f.type = ftype
        return m

    I64, U64, STR, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_DOUBLE
    message("XSpace", [("planes", 1, "XPlane", True)])
    plane = message("XPlane", [
        ("name", 2, STR, False), ("lines", 3, "XLine", True),
        ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
        ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, I64, False),
                            ("value", 2, value, False)], plane.nested_type)
        e.options.map_entry = True
    message("XLine", [("name", 2, STR, False), ("timestamp_ns", 3, I64, False),
                      ("events", 4, "XEvent", True)])
    message("XEvent", [("metadata_id", 1, I64, False),
                       ("offset_ps", 2, I64, False),
                       ("duration_ps", 3, I64, False),
                       ("stats", 4, "XStat", True)])
    message("XStat", [("metadata_id", 1, I64, False),
                      ("double_value", 2, DBL, False),
                      ("uint64_value", 3, U64, False),
                      ("int64_value", 4, I64, False),
                      ("str_value", 5, STR, False),
                      ("ref_value", 7, U64, False)])
    message("XEventMetadata", [("id", 1, I64, False), ("name", 2, STR, False),
                               ("stats", 5, "XStat", True)])
    message("XStatMetadata", [("id", 1, I64, False), ("name", 2, STR, False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench.xplane.XSpace"))


def read_xspace(path: str):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stats(plane, stats) -> dict:
    """``{name: value}`` of XStats; a ``ref_value`` names another stat
    metadata entry, whose name is the (interned) string value."""
    out = {}
    for s in stats:
        name = plane.stat_metadata[s.metadata_id].name
        if s.str_value:
            out[name] = s.str_value
        elif s.ref_value:
            out[name] = plane.stat_metadata[s.ref_value].name
        elif s.int64_value:
            out[name] = s.int64_value
        elif s.uint64_value:
            out[name] = s.uint64_value
        elif s.double_value:
            out[name] = s.double_value
        else:
            out[name] = 0
    return out


def scope_of(tf_op: str) -> str:
    """The innermost known scope of a ``tf_op`` name stack, else
    ``unscoped``; ``xla.inserted`` where it holds no name stack."""
    if not tf_op.startswith("jit("):
        return "xla.inserted"
    for part in reversed(tf_op.split("/")):
        part = part.split(":", 1)[0]
        if part in SCOPES or part.startswith("repro."):
            return part
    return "unscoped"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    attrs: dict


@dataclasses.dataclass
class ScopeSummary:
    """What one traced window reduces to by name; times in seconds."""

    window_s: float
    chips: int
    program_calls: dict            # {program: executions}
    scope_s: dict                  # {(program, scope): device seconds}
    engine_idle: dict              # {engine span or engine.none: idle s}
    engine_spans: list             # [Span] of engine.* starting in window

    def spans(self, name: str) -> list:
        return [s for s in self.engine_spans if s.name == name]


def _line_events(line):
    for e in line.events:
        s = line.timestamp_ns + e.offset_ps / 1e3
        yield e, s, s + e.duration_ps / 1e3


def reduce_scopes(path: str, window_span: str,
                  window_s: float) -> ScopeSummary:
    """Reduce the trace at ``path`` over the ``window_s`` seconds that
    follow the start of the host span ``window_span``."""
    space = read_xspace(path)
    host, devices = [], []
    window_starts = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e, s, end in _line_events(line):
                    meta = plane.event_metadata[e.metadata_id]
                    if meta.name == window_span:
                        window_starts.append(s)
                    elif meta.name.startswith("engine."):
                        host.append(Span(meta.name, s, end,
                                         _stats(plane, e.stats)))
    if not window_starts:
        raise ValueError(f"no host span {window_span!r} in {path}")
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    lo = min(window_starts)
    hi = lo + window_s * 1e9
    host.sort(key=lambda h: h.start_ns)
    starts = [h.start_ns for h in host]

    calls = collections.Counter()
    scope_s = collections.Counter()
    idle = collections.Counter()
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = []
        for e, s, end in _line_events(lines["XLA Modules"]):
            cs, ce = max(s, lo), min(end, hi)
            if ce > cs:
                prog = module_name(plane.event_metadata[e.metadata_id].name)
                modules.append((cs, ce, prog))
                calls[prog] += 1
        modules.sort()
        mstarts = [m[0] for m in modules]
        scope_cache = {}
        for e, s, end in _line_events(lines["XLA Ops"]) \
                if "XLA Ops" in lines else ():
            cs, ce = max(s, lo), min(end, hi)
            if ce <= cs:
                continue
            meta = plane.event_metadata[e.metadata_id]
            if op_name(meta.name) in CONTAINERS:
                continue
            if e.metadata_id not in scope_cache:
                tf_op = _stats(plane, meta.stats).get("tf_op", "")
                scope_cache[e.metadata_id] = scope_of(str(tf_op))
            i = bisect.bisect_right(mstarts, cs) - 1
            prog = modules[i][2] if i >= 0 and modules[i][1] >= cs else "?"
            scope_s[(prog, scope_cache[e.metadata_id])] += (ce - cs) * 1e-9
        busy = _union([(s, e) for s, e, _ in modules])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                idle[_engine_activity(host, starts, (gs + ge) / 2)] += \
                    (ge - gs) * 1e-9
    chips = len(devices)
    return ScopeSummary(
        window_s=(hi - lo) * 1e-9, chips=chips,
        program_calls={k: v / chips for k, v in calls.items()},
        scope_s={k: v / chips for k, v in scope_s.items()},
        engine_idle={k: v / chips for k, v in idle.items()},
        engine_spans=[h for h in host if lo <= h.start_ns < hi])


def _engine_activity(spans, starts, t_ns: float) -> str:
    """The innermost ``engine.*`` span open at ``t_ns``: the latest-started
    one among those that contain it (``spans`` sorted by start)."""
    i = bisect.bisect_right(starts, t_ns) - 1
    while i >= 0:
        if spans[i].end_ns >= t_ns:
            return spans[i].name
        i -= 1
    return "engine.none"


# -- the metrics read from it ----------------------------------------------


def kv_move_ms_per_step(s: ScopeSummary, decode_chunk: int):
    """Device time of ``jit_chunk`` under :data:`KV_MOVE_SCOPES`, per
    decode step (calls x ``decode_chunk``); None where the program carries
    none of these scopes."""
    steps = s.program_calls.get(DECODE_PROGRAM, 0) * decode_chunk
    found = [s.scope_s[(DECODE_PROGRAM, k)] for k in KV_MOVE_SCOPES
             if (DECODE_PROGRAM, k) in s.scope_s]
    if not steps or not found:
        return None
    return sum(found) / steps * 1e3


def host_idle_ms_per_step(s: ScopeSummary):
    """Device idle charged to an ``engine.*`` span other than
    ``engine.wait``, per ``engine.decode`` span started in the window."""
    decodes = len(s.spans("engine.decode"))
    if not decodes:
        return None
    held = sum(v for k, v in s.engine_idle.items()
               if k not in ("engine.wait", "engine.none"))
    return held / decodes * 1e3


def admit_ms_p50(s: ScopeSummary):
    """Median duration of the window's ``engine.admit`` spans."""
    return percentile(((h.end_ns - h.start_ns) * 1e-6
                       for h in s.spans("engine.admit")), 50)


def breakdown(s: ScopeSummary, top: int = 10) -> dict:
    """``device_scopes``: the ``top`` (program, scope) pairs by device time,
    ``unscoped`` and ``xla.inserted`` included; ``engine_idle``: idle
    seconds by host span."""
    scopes = collections.Counter({f"{p}/{k}": v
                                  for (p, k), v in s.scope_s.items()})
    return {"device_scopes": [[k, v] for k, v in scopes.most_common(top)],
            "engine_idle": [[k, v] for k, v in collections.Counter(
                s.engine_idle).most_common()]}
