"""Reduction of one profiler trace (`.xplane.pb`) to device metrics.

Device planes are `/device:TPU:<n>`. On each, the `XLA Ops` line holds
the operations that ran (their union is the busy time) and the
`XLA Modules` line holds the compiled programs. The host plane
`/host:CPU` holds the benchmark's own spans (`jax.profiler.
TraceAnnotation`, all named `bench.*`) on the same clock, so an idle gap
on the device is named by the innermost benchmark span around it.

Everything is restricted to the traced window: the `bench.window` span,
cut short where the device tracer stopped recording (`cut_s` says by
how much; a mix that sets `trace_seconds` keeps it near 0).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_OP_BASE = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(\.\d+)*(\s|=|$)")
_MODULE_BASE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over the devices traced
    devices: int
    op_s: dict[str, float]              # op base name -> device seconds
    module_s: dict[str, float]          # program name -> device seconds
    module_runs: dict[str, int]         # program name -> executions
    idle_gaps: list[tuple[str, float]]  # longest first
    # the same per compiled program: full module name ('name(id)')
    program_s: dict[str, float] = dataclasses.field(default_factory=dict)
    program_runs: dict[str, int] = dataclasses.field(default_factory=dict)
    cut_s: float = 0.0                  # window end - last device event

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self, n: int = 10) -> list[list]:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def programs_s(self, prefixes) -> float:
        """Device seconds of the programs whose names start with any of
        `prefixes`."""
        return sum(v for k, v in self.module_s.items()
                   if k.startswith(tuple(prefixes)))


def op_base(name: str) -> str:
    """'%fusion.365 = s32[...] ...' -> 'fusion'."""
    m = _OP_BASE.match(name.strip())
    return m.group(1) if m else name.split()[0]


def module_base(name: str) -> str:
    """'jit__cascade_batched_aux(1583...)' -> 'jit__cascade_batched_aux'."""
    return _MODULE_BASE.match(name.strip()).group(1)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_trace(path: str, n_gaps: int = 10) -> Reduction:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    host_spans = []
    device_lines = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            device_lines.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    if not device_lines:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    lo, hi = windows[0]
    # The device tracer keeps a bounded number of events: on a busy device
    # it stops recording before a long window ends. The traced window
    # then ends with the last device event recorded.
    last = max((ev.start_ns + ev.duration_ns for lines in device_lines
                for ev in lines.get("XLA Ops", ())), default=hi)
    cut_s = max(hi - last, 0) * 1e-9
    hi = min(hi, last)
    spans = [sp for sp in host_spans if sp[2] != WINDOW_SPAN]
    op_s: dict[str, float] = {}
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    program_s: dict[str, float] = {}
    program_runs: dict[str, int] = {}
    busy_total = 0.0
    gaps: list[tuple[int, int]] = []
    for lines in device_lines:
        busy = []
        for name, s, e, self_ns in _self_times(lines.get("XLA Ops", ()),
                                               lo, hi):
            busy.append((s, e))
            name = op_base(name)
            op_s[name] = op_s.get(name, 0.0) + self_ns * 1e-9
        for ev in lines.get("XLA Modules", ()):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            for name, secs, runs in ((module_base(ev.name), module_s,
                                      module_runs),
                                     (ev.name.strip(), program_s,
                                      program_runs)):
                secs[name] = secs.get(name, 0.0) + (e - s) * 1e-9
                runs[name] = runs.get(name, 0) + 1
        merged = _merge(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_name_gap(spans, s, e), (e - s) * 1e-9)
             for s, e in gaps[:n_gaps]]
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total / len(device_lines),
                     devices=len(device_lines), op_s=op_s,
                     module_s=module_s, module_runs=module_runs,
                     idle_gaps=named, program_s=program_s,
                     program_runs=program_runs, cut_s=cut_s)


def _self_times(events, lo, hi):
    """(name, start, end, self ns) of each op clipped to [lo, hi), where
    self time leaves out the ops nested inside it (a `while` holds the
    ops of its body), so op times add up to busy time."""
    evs = sorted(((*_clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                          lo, hi), ev.name) for ev in events),
                 key=lambda x: (x[0], -x[1]))
    out, stack = [], []
    for s, e, name in evs:
        if e <= s:
            continue
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s]
        if stack:
            parent = stack[-1]
            parent[3] -= min(e, parent[2]) - s
        out.append(rec)
        stack.append(rec)
    return [(n, s, e, max(d, 0)) for n, s, e, d in out]


def _name_gap(spans, s, e) -> str:
    """The benchmark span that covers most of [s, e); the shortest such
    span when several cover equally (the innermost)."""
    best, best_key = "host", (0, 0)
    for ss, se, name in spans:
        cover = min(se, e) - max(ss, s)
        if cover <= 0:
            continue
        key = (cover, -(se - ss))
        if key > best_key:
            best, best_key = name, key
    return best
