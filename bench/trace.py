"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else.  The window is the host span
named ``window`` that the harness opens around the measured window.  Busy
time is the union of the intervals of the operations on each device's
``XLA Ops`` line inside that window; the idle share is one minus busy over
the window.  The busy time inside each benchmark span is kept apart, so
that a metric can read the device time of one kind of work.  Each idle
gap is named by the innermost benchmark span open on the host at its
midpoint.  Importing this module loads no accelerator library.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
NO_SPAN = "none"


@dataclass
class Summary:
    """What the benchmark reads from one traced window."""

    window_s: float
    busy_s: float  # mean over the devices traced
    devices: int
    top_ops: list = field(default_factory=list)  # [[name, seconds], ...]
    idle_gaps: list = field(default_factory=list)  # [[span, seconds], ...]
    span_busy: dict = field(default_factory=dict)  # span name -> busy seconds inside it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a, b) -> float:
    """The length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_at(spans, t: float) -> str:
    """The innermost (shortest) span ``(name, start, end)`` holding ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else NO_SPAN


def reduce(device_ops, host_spans, window, top: int = 10) -> Summary:
    """The summary from raw events.

    ``device_ops`` maps a device name to its ``(name, start_ns, end_ns)``
    operations, ``host_spans`` is the benchmark's ``(name, start_ns,
    end_ns)`` spans and ``window`` the ``(start_ns, end_ns)`` window."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    per_op: dict[str, float] = defaultdict(float)
    busy_total, all_gaps = 0.0, []
    spans = [sp for sp in host_spans if sp[0] != WINDOW]
    by_name = {
        name: union(clip([(s, e) for n, s, e in spans if n == name], lo, hi))
        for name in {n for n, _, _ in spans}
    }
    span_busy: dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        for n, s, e in inside:
            per_op[n] += (min(e, hi) - max(s, lo)) * 1e-9
        busy = union(clip([(s, e) for _, s, e in inside], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)
        for name, intervals in by_name.items():
            span_busy[name] += overlap(busy, intervals)
    n_dev = max(1, len(device_ops))
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    named = [(span_at(spans, (s + e) / 2), (e - s) * 1e-9) for s, e in longest]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / n_dev,
        devices=len(device_ops),
        top_ops=[[n, s] for n, s in ops[:top]],
        idle_gaps=[[n, s] for n, s in named],
        span_busy={n: b * 1e-9 / n_dev for n, b in sorted(span_busy.items())},
    )


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that a trace into ``log_dir`` wrote."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def read_events(path: str, span_names):
    """``(device_ops, host_spans, window)`` from an ``.xplane.pb``: the
    operations on every device plane's ``XLA Ops`` line, and the host
    events whose names are benchmark spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans, window = {}, [], None
    wanted = set(span_names) | {WINDOW}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if ops:
                device_ops[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns, e.end_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span in the trace, found {len(windows)}")
    window = windows[0]
    return device_ops, spans, window


def summarize(path: str, span_names, top: int = 10) -> Summary:
    """Read and reduce one trace file."""
    device_ops, spans, window = read_events(path, span_names)
    return reduce(device_ops, spans, window, top=top)
