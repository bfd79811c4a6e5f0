"""The program's own spans (``objcache.*``, `repro.core.spans`) in a
profiler trace, reduced to the split of a chat request's host time.

`trace.load` keeps the benchmark's ``bench:`` annotations only; `load`
here reads the program's spans from the same ``.xplane.pb``, so they can
be added to a `trace.Trace`'s host events.  There `trace.idle_gaps` puts
each stretch of device idle time down to the innermost span, the
program's where one covers it, and nothing else changes: every other
reduction of `trace` reads the device's modules or the window.

A span's reading is the sum of its durations that start inside the traced
window, divided by the requests served, as `kv_host_ms.chat` divides the
time of the benchmark's `TimedKV` wrapper.
"""

from __future__ import annotations

import bisect
import glob
import os

from . import trace as tr

PREFIX = "objcache."

# reading -> the span it sums
SPLIT = {
    "kv_put_to_host_ms": "kv.put.to_host",
    "kv_put_pack_ms": "kv.put.pack",
    "kv_put_fs_ms": "kv.put.fs",
    "kv_get_fs_ms": "kv.get.fs",
    "kv_restore_host_ms": "kv.get.assemble",
    "kv_restore_to_device_ms": "engine.restore.to_device",
}
# the spans that together cover the benchmark's `TimedKV` wrapper
KV_HOST = ("kv_put_to_host_ms", "kv_put_pack_ms", "kv_put_fs_ms",
           "kv_get_fs_ms", "kv_restore_host_ms")


def load(trace_dir: str) -> list[tr.Event]:
    """Every program span on the host planes of the newest ``.xplane.pb``
    under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: list[tr.Event] = []
    for plane in ProfileData.from_file(max(paths,
                                           key=os.path.getmtime)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events
                           if ev.name.startswith(PREFIX))
    return out


def _in_window(trace: tr.Trace, name: str) -> list[tr.Event]:
    lo, hi = trace.window()
    return [e for e in trace.host
            if e[0] == PREFIX + name and lo <= e[1] < hi]


def has_spans(trace: tr.Trace | None) -> bool:
    return trace is not None and any(e[0].startswith(PREFIX)
                                     for e in trace.host)


def span_ms_per_request(trace: tr.Trace | None, name: str,
                        requests: int) -> float | None:
    """Host ms per request in span `name`, or None where the trace holds
    no program span (a program without them) or no request was served."""
    if not has_spans(trace) or not requests:
        return None
    return sum(d for _, _, d in _in_window(trace, name)) / 1e6 / requests


def span_s(trace: tr.Trace) -> dict[str, float]:
    """Seconds in each program span inside the window, by name."""
    lo, hi = trace.window()
    out: dict[str, float] = {}
    for name, s, d in trace.host:
        if name.startswith(PREFIX) and lo <= s < hi:
            out[name] = out.get(name, 0.0) + d / 1e9
    return out


def _busy_ns(busy: list[tuple[int, int]], starts: list[int], a: int,
             b: int) -> int:
    """ns of [a, b) that the merged, sorted intervals `busy` cover."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    n = 0
    while i < len(busy) and busy[i][0] < b:
        n += max(0, min(b, busy[i][1]) - max(a, busy[i][0]))
        i += 1
    return n


def decode_host_gap_ms(trace: tr.Trace | None,
                       module: str | None) -> float | None:
    """Mean ms, per gap, in which the device ran no module between two
    consecutive executions of the decode `module` that start inside one
    ``engine.decode`` span: the host's share of each output token.  None
    where the trace holds no such pair."""
    if not has_spans(trace) or not module:
        return None
    decodes = sorted((s, s + d) for _, s, d in
                     _in_window(trace, "engine.decode"))
    gaps: list[int] = []
    for evs in trace.modules.values():
        if not evs:
            continue
        busy = tr.busy_intervals(evs, min(e[1] for e in evs),
                                 max(e[1] + e[2] for e in evs))
        starts = [s for s, _ in busy]
        steps = sorted((s, s + d) for n, s, d in evs
                       if n.split("(")[0] == module)
        step_starts = [s for s, _ in steps]
        for ds, de in decodes:
            inside = steps[bisect.bisect_left(step_starts, ds):
                           bisect.bisect_left(step_starts, de)]
            gaps.extend(max(0, s1 - e0 - _busy_ns(busy, starts, e0, s1))
                        for (_, e0), (s1, _) in zip(inside, inside[1:]))
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
