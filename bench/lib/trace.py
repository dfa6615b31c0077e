"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) with a
line ``XLA Modules`` (one event per program execution, named
``jit_<function>(<hash>)``) and a line ``XLA Ops`` (one event per operation
inside it; a Pallas kernel is a ``custom-call``).  The host plane
(``/host:CPU``) holds the benchmark's own ``TraceAnnotation`` spans
(``bench.*``) on the same clock.

From these the reduction keeps, inside a window:

* busy time: the union of program intervals on each chip;
* device time per program (by function name) and per kernel (by the
  custom call's name and the program that encloses it);
* the operations that took most time (control flow, which spans the
  operations inside it, left out), and the idle gaps between programs,
  each charged to the innermost ``bench.*`` span that was open on the host.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s|=|$)")


def module_name(event_name: str) -> str:
    """``jit_chunk(1637...)`` -> ``jit_chunk``."""
    return _MODULE_NAME.match(event_name).group(1)


def op_name(event_name: str) -> str:
    """``%nmg_spmm_pallas.12 = f32[...] custom-call(...)`` ->
    ``nmg_spmm_pallas``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


#: control-flow operations whose events span the operations inside them
CONTAINERS = frozenset({"while", "conditional", "call"})


def is_kernel(event_name: str) -> bool:
    return " custom-call(" in event_name


@dataclasses.dataclass
class Interval:
    start_ns: float
    end_ns: float
    name: str


@dataclasses.dataclass
class TraceSummary:
    """What one traced run reduces to; times in seconds."""

    window_s: float
    busy_s: float                  # mean over chips
    chips: int
    program_s: dict                # {program: device seconds}
    program_calls: dict            # {program: executions}
    kernel_s: dict                 # {(kernel, program): device seconds}
    kernel_calls: dict             # {(kernel, program): events}
    top_ops: list                  # [[op, seconds], ...]
    idle_gaps: list                # [[host activity, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


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


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce_trace(path: str, window_span: str, window_s: float,
                 top: int = 10) -> TraceSummary:
    """Reduce the trace at ``path`` over the ``window_s`` seconds that
    follow the start of the host span ``window_span``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans = []
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith("bench."):
                        host_spans.append(Interval(s, e, name))
    windows = [h for h in host_spans if h.name == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in {path}")
    lo = windows[0].start_ns
    hi = lo + window_s * 1e9
    if not device_planes:
        raise ValueError(f"no TPU device plane in {path}")

    busy_total = 0.0
    program_s = collections.Counter()
    program_calls = collections.Counter()
    kernel_s = collections.Counter()
    kernel_calls = collections.Counter()
    op_s = collections.Counter()
    gaps = collections.Counter()
    inner = sorted(host_spans, key=lambda h: h.start_ns)
    inner_starts = [h.start_ns for h in inner]
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        modules = []
        for name, s, e in _events(lines["XLA Modules"]):
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            prog = module_name(name)
            modules.append((cs, ce, prog))
            program_s[prog] += (ce - cs) * 1e-9
            program_calls[prog] += 1
        modules.sort()
        starts = [m[0] for m in modules]
        for name, s, e in _events(lines.get("XLA Ops", ())
                                  if "XLA Ops" in lines else []):
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            op = op_name(name)
            if op not in CONTAINERS:
                op_s[op] += (ce - cs) * 1e-9
            if is_kernel(name):
                i = bisect.bisect_right(starts, cs) - 1
                prog = modules[i][2] if i >= 0 and \
                    modules[i][1] >= cs else "?"
                kernel_s[(op, prog)] += (ce - cs) * 1e-9
                kernel_calls[(op, prog)] += 1
        busy = _union([(s, e) for s, e, _ in modules])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_host_activity(inner, (gs + ge) / 2,
                                    inner_starts)] += (ge - gs) * 1e-9
    chips = len(device_planes)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / chips,
        chips=chips,
        program_s=dict(program_s),
        program_calls=dict(program_calls),
        kernel_s=dict(kernel_s),
        kernel_calls=dict(kernel_calls),
        top_ops=[[k, v / chips] for k, v in op_s.most_common(top)],
        idle_gaps=[[k, v / chips] for k, v in gaps.most_common(top)],
    )


def _host_activity(spans, t_ns: float, starts=None) -> str:
    """The innermost ``bench.*`` span open at ``t_ns``: the latest-started
    one among those that contain it (``spans`` sorted by start)."""
    starts = starts if starts is not None else [h.start_ns for h in spans]
    i = bisect.bisect_right(starts, t_ns) - 1
    while i >= 0:
        if spans[i].end_ns >= t_ns:
            return spans[i].name
        i -= 1
    return "bench.none"
