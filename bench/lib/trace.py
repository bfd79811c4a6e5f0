"""Reduction of a `jax.profiler` trace to the numbers the metrics read.

`load` turns the profiler's ``.xplane.pb`` into plain lists of
``(name, start_ns, duration_ns)``: for each device plane its XLA modules,
and for the host the benchmark's own annotations (names that start with
``bench:``); and for each device plane the time of each XLA op.  Everything else here works on those lists, so a
test can hand it a trace written out by hand.

Busy time is the union of the device's module intervals: an operation runs
on the device only inside a module.  The traced window is the
``bench:traced`` annotation that the driver puts around the profiled part
of its window.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

Event = tuple[str, int, int]         # name, start ns, duration ns

WINDOW_SPAN = "bench:traced"


@dataclass
class Trace:
    modules: dict[str, list[Event]] = field(default_factory=dict)
    # per plane: op name -> ns summed over its executions in the window
    ops: dict[str, dict[str, int]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)

    def window(self) -> tuple[int, int]:
        spans = [e for e in self.host if e[0] == WINDOW_SPAN]
        if spans:
            _, s, d = spans[0]
            return s, s + d
        evs = [e for v in self.modules.values() for e in v]
        if not evs:
            raise ValueError("trace holds no device module")
        return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under `trace_dir`.  Op executions are
    many (every op of every layer of every step), so they are summed by
    name as they are read, over the traced window only."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = Trace()
    planes = list(pd.planes)
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host.extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events if ev.name.startswith("bench:"))
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name.strip().lower() == "xla modules":
                out.modules.setdefault(plane.name, []).extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
    lo, hi = out.window() if out.modules or out.host else (0, 0)
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name.strip().lower() != "xla ops":
                continue
            tot = out.ops.setdefault(plane.name, {})
            for ev in line.events:
                if lo <= ev.start_ns < hi:
                    tot[ev.name] = tot.get(ev.name, 0) + int(ev.duration_ns)
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_intervals(events: list[Event], lo: int, hi: int
                   ) -> list[tuple[int, int]]:
    """Merged intervals in which some event runs, clipped to [lo, hi)."""
    clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in events]
    return _union([(s, e) for s, e in clipped if e > s])


def busy_s(trace: Trace) -> float:
    """Seconds in which a module ran, averaged over the device planes."""
    lo, hi = trace.window()
    planes = [busy_intervals(evs, lo, hi) for evs in trace.modules.values()]
    if not planes:
        return 0.0
    return sum(sum(e - s for s, e in p) for p in planes) / len(planes) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def idle_pct(trace: Trace | None) -> float | None:
    """% of the traced window in which no module ran on the device, or
    None where the trace holds no device."""
    if trace is None or not trace.modules:
        return None
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def mean_module_ms(trace: Trace | None, name: str | None) -> float | None:
    """Mean device ms of one execution of module `name` in the window, or
    None where the trace holds none."""
    evs = module_events(trace, name) if trace is not None and name else []
    return 1e3 * sum(d for _, _, d in evs) / len(evs) / 1e9 if evs else None


def module_events(trace: Trace, name: str) -> list[Event]:
    """Executions of the module `name` (the trace adds the program's id,
    as in ``jit__lambda(1160947142)``) that start inside the traced
    window, on every plane."""
    lo, hi = trace.window()
    return [e for evs in trace.modules.values() for e in evs
            if e[0].split("(")[0] == name and lo <= e[1] < hi]


def op_name(text: str) -> str:
    """An op's name without the HLO text the trace gives after it, with
    its fusion kind where it has one: ``%fusion.49 kLoop``."""
    name = text.split(" = ")[0]
    if "kind=" in text:
        name += " " + text.split("kind=")[1].split(",")[0].split(")")[0]
    return name


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The `k` device operations that took most time in the window, in
    seconds summed over their executions and averaged over planes.  An op
    that holds others (a ``while`` over the layers) counts their time
    too."""
    tot: dict[str, float] = {}
    n = max(1, len(trace.ops))
    for ops in trace.ops.values():
        for name, ns in ops.items():
            name = op_name(name)
            tot[name] = tot.get(name, 0.0) + ns / 1e9 / n
    return [[k_, v] for k_, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """Idle time of the device, by what the host was doing meanwhile: each
    stretch of a gap between busy intervals goes to the innermost host
    annotation that covers it (``host:unannotated`` where none does).  The
    `k` largest totals, in seconds, averaged over planes."""
    lo, hi = trace.window()
    host = sorted((s, s + d, n) for n, s, d in trace.host
                  if n != WINDOW_SPAN)
    longest = max((he - hs for hs, he, _ in host), default=0)
    starts = [hs for hs, _, _ in host]
    tot: dict[str, float] = {}
    planes = list(trace.modules.values())
    for evs in planes:
        busy = busy_intervals(evs, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            # only spans that start before the gap ends and could reach it
            near = [h for h in host[bisect.bisect_left(starts, gs - longest):
                                    bisect.bisect_right(starts, ge)]
                    if h[1] > gs]
            cuts = sorted({gs, ge} | {x for hs, he, _ in near
                                      for x in (hs, he) if gs < x < ge})
            for a, b in zip(cuts, cuts[1:]):
                cover = [(he - hs, name) for hs, he, name in near
                         if hs <= a and he >= b]
                name = min(cover)[1] if cover else "host:unannotated"
                tot[name] = tot.get(name, 0.0) + (b - a) / 1e9 / len(planes)
    return [[k_, v] for k_, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
