"""Profiler trace -> device busy time, per-kernel time and the breakdown.

A run with ``--trace 1`` records the measured window with
``jax.profiler`` (Python tracing off) and reduces the ``.xplane.pb`` file
here.  Device planes are those named ``/device:TPU:<n>``; their op events
are on the ``XLA Ops`` line, each named by its HLO text
(``%dfr_scan.19 = (...) custom-call(...)``), of which the instruction name
is kept.  Busy time is the union of the op intervals inside the window, so
nested events (a ``while`` and the ops of its body) count once; the top
ops are ranked by self time, an event's duration less that of the events
nested in it.  A kernel's time is the sum of the durations of the events
whose name, less a ``.<n>`` suffix, is the kernel's ``pallas_call`` name.
Idle gaps are the
holes between busy intervals inside the window; the longest of them are
labelled by the innermost host event that covers each one's midpoint.
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
LABELLED_GAPS = 256
_SUFFIX = re.compile(r"\.\d+$")
_HLO = re.compile(r"^%?([^ =]+)")


def op_name(event_name: str) -> str:
    """The instruction name of an op event (``dfr_scan.19``)."""
    m = _HLO.match(event_name)
    return m.group(1) if m else event_name


def base_name(name: str) -> str:
    """An HLO instruction name without its ``.<n>`` suffix."""
    return _SUFFIX.sub("", name)


def self_times(events):
    """(name, self seconds) of each event: its duration less the durations
    of the events directly nested in it."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= (min(e, stack[-1][1]) - s) * 1e-9
        out.append([name, (e - s) * 1e-9])
        stack.append((len(out) - 1, e))
    return out


def find_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _events(line, rename=None):
    return [(rename(ev.name) if rename else ev.name, float(ev.start_ns),
             float(ev.start_ns + ev.duration_ns)) for ev in line.events]


def planes_of(profile):
    """(device planes' op events, host events) as name/start/end tuples."""
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [e for line in plane.lines if line.name == OP_LINE
                   for e in _events(line, op_name)]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host.extend(e for line in plane.lines for e in _events(line))
    return devices, host


def union(intervals, lo=None, hi=None):
    """Merged [start, end) intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _labeller(host):
    """t -> name of the innermost host event covering time ``t``."""
    import numpy as np

    host = [h for h in host if h[0] != WINDOW_SPAN]
    names = [h[0] for h in host]
    start = np.array([h[1] for h in host], float)
    end = np.array([h[2] for h in host], float)
    dur = end - start

    def label(t):
        cover = (start <= t) & (end >= t)
        if not cover.any():
            return "no host event"
        return names[int(np.argmin(np.where(cover, dur, np.inf)))]

    return label


def reduce(devices, host, kernels=(), top=10) -> dict:
    """Seconds busy (averaged over devices), the window, each kernel's
    seconds and event count, the top device ops and the idle gaps."""
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        every = [(s, e) for ops in devices for _, s, e in ops]
        lo = min((s for s, _ in every), default=0.0)
        hi = max((e for _, e in every), default=0.0)
    window_s = (hi - lo) * 1e-9
    busy, per_op = [], collections.Counter()
    kern = {k: {"seconds": 0.0, "events": 0} for k in kernels}
    gaps = []
    for ops in devices:
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        merged = union([(s, e) for _, s, e in inside], lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, secs in self_times(inside):
            per_op[name] += secs
        for name, s, e in inside:
            k = base_name(name)
            if k in kern:
                kern[k]["seconds"] += (e - s) * 1e-9
                kern[k]["events"] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    idle = collections.Counter()
    gaps.sort(key=lambda g: g[0] - g[1])
    label = _labeller(host)
    for s, e in gaps[:LABELLED_GAPS]:
        idle[label((s + e) / 2)] += (e - s) * 1e-9 / n_dev
    rest = sum(e - s for s, e in gaps[LABELLED_GAPS:])
    if rest:
        idle[f"gaps shorter than the {LABELLED_GAPS} longest"] += rest * 1e-9 / n_dev
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "kernels": {k: {"seconds": v["seconds"] / n_dev, "events": v["events"]}
                    for k, v in kern.items()},
        "device_ops": [[n, s / n_dev] for n, s in per_op.most_common(top)],
        "idle_gaps": [[n, s] for n, s in idle.most_common(top)],
    }


def reduce_dir(trace_dir: str, kernels=()) -> dict | None:
    """Reduce the newest trace under ``trace_dir``; None if there is none."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    devices, host = planes_of(ProfileData.from_file(path))
    return reduce(devices, host, kernels)
