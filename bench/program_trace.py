"""The program's own scopes and spans in a profiler trace.

``cp_als`` names its device work with ``jax.named_scope`` (``init``,
``prepare``, ``mttkrp.node<id>``, ``update.mode<n>``, ``fit``) and its host
loop with ``jax.profiler.TraceAnnotation`` spans (``cp_als.init``,
``cp_als.dispatch``, ``cp_als.wait``, ``cp_als.check``).  This module
reduces a trace of it, on top of :mod:`bench.trace`:

- **Scopes.**  Each device op's ``tf_op`` stat holds its path of scopes.
  ``jax.profiler.ProfileData`` does not expose it, so :func:`read_devices`
  reads the device planes of the ``.xplane.pb`` with a small reader of the
  protobuf wire format.  The device's busy time inside the window is
  split by the innermost program scope on each op's path (or ``none``).
- **Clocks.**  The device and the host keep separate clocks.  Each device
  module execution (``XLA Modules``, keyed by ``run_id``) is paired with
  its host launch (``PJRT_LoadedExecutable_Execute``, in order) and its
  host ``CompleteCallbacks`` (by ``run_id``).  The offset added to device
  times is the least one that puts no module start before its launch; it
  must also put no callback before its module's end.  Where the device's
  clock was set again within the trace, no one offset does both: each run
  of a device's modules that one offset fits gets its own, within stated
  limits (:func:`clock_segments`).
- **Idle gaps.**  On the aligned clock, the device's idle time inside each
  span, and the longest gaps named by the innermost span open over them.

Run from the root of a checkout, on a trace of solves inside one
``window`` span (or, lacking one, from the first ``cp_als`` span to the
last):

    python3 -m bench.program_trace <file.xplane.pb> [--sweeps N]
        [--config bench/configs/fmri4.json --device-kind "TPU v5 lite"]

Importing this module loads no accelerator library.
"""

from __future__ import annotations

import argparse
import bisect
import heapq
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from bench import trace

PROGRAM_SPANS = ("cp_als.init", "cp_als.dispatch", "cp_als.wait", "cp_als.check")
SYNC_SPANS = ("cp_als.dispatch", "cp_als.wait", "cp_als.check")
SCOPE = re.compile(r"mttkrp\.node\d+|update\.mode\d+|fit|init|prepare")
NO_SCOPE = "none"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
CALLBACK = "CompleteCallbacks"

# XSpace field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS, _EVENT_STATS = 1, 2, 3, 4
_STAT_MD_ID, _STAT_UINT64, _STAT_INT64, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7
_MD_ID, _MD_NAME, _EVENT_MD_STATS = 1, 2, 5
_MAP_VALUE = 2


def fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """The ``(number, value)`` fields of one protobuf message in
    ``buf[lo:hi]``: an ``int`` for varints and fixed-width fields, a
    ``(start, end)`` range of ``buf`` for length-delimited ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at byte {i}")
        yield number, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf: bytes, rng) -> str:
    return buf[rng[0]:rng[1]].decode("utf-8", "replace")


@dataclass
class Device:
    """What one device plane holds for this reduction, on the device's
    clock: ops ``(start_ns, end_ns, tf_op)`` and module executions
    ``(run_id, start_ns, end_ns)``."""

    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


def read_devices(path: str) -> dict[str, Device]:
    """Each device plane's ops with their ``tf_op`` and its module
    executions with their ``run_id``, read from the ``.xplane.pb``'s wire
    format; host planes are skipped unread."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for number, plane in fields(buf):
        if number != _SPACE_PLANES:
            continue
        name, lines, event_md, stat_md = "", [], [], {}
        for pn, pv in fields(buf, *plane):
            if pn == _PLANE_NAME:
                name = _text(buf, pv)
            elif pn == _PLANE_LINES:
                lines.append(pv)
            elif pn == _PLANE_EVENT_MD:
                event_md.append(pv)
            elif pn == _PLANE_STAT_MD:
                md = dict(fields(buf, *_map_value(buf, pv)))
                stat_md[md.get(_MD_ID, 0)] = _text(buf, md[_MD_NAME]) if _MD_NAME in md else ""
        if name.startswith(trace.DEVICE_PREFIX):
            dev = _read_device(buf, lines, event_md, stat_md)
            if dev.ops or dev.modules:
                out[name] = dev
    return out


def _map_value(buf: bytes, entry) -> tuple[int, int]:
    for number, value in fields(buf, *entry):
        if number == _MAP_VALUE:
            return value
    return (entry[1], entry[1])


def _stats(buf: bytes, ranges, stat_md: dict) -> dict:
    """``{stat name: value}`` of the ``XStat`` messages at ``ranges``."""
    out = {}
    for rng in ranges:
        stat = dict(fields(buf, *rng))
        key = stat_md.get(stat.get(_STAT_MD_ID, 0), "")
        if _STAT_STR in stat:
            out[key] = _text(buf, stat[_STAT_STR])
        elif _STAT_REF in stat:
            out[key] = stat_md.get(stat[_STAT_REF], "")
        elif _STAT_INT64 in stat:
            out[key] = _signed(stat[_STAT_INT64])
        elif _STAT_UINT64 in stat:
            out[key] = stat[_STAT_UINT64]
    return out


def _read_device(buf: bytes, lines, event_md, stat_md) -> Device:
    tf_op = {}
    for entry in event_md:
        md_id, stats = 0, []
        for number, value in fields(buf, *_map_value(buf, entry)):
            if number == _MD_ID:
                md_id = value
            elif number == _EVENT_MD_STATS:
                stats.append(value)
        tf_op[md_id] = _stats(buf, stats, stat_md).get("tf_op", "")
    dev = Device()
    for line in lines:
        name, t0, events = "", 0, []
        for number, value in fields(buf, *line):
            if number == _LINE_NAME:
                name = _text(buf, value)
            elif number == _LINE_TIMESTAMP_NS:
                t0 = _signed(value)
            elif number == _LINE_EVENTS:
                events.append(value)
        if name not in (trace.OPS_LINE, MODULES_LINE):
            continue
        for rng in events:
            md_id = offset = duration = 0
            stats = []
            for number, value in fields(buf, *rng):
                if number == _EVENT_MD_ID:
                    md_id = value
                elif number == _EVENT_OFFSET_PS:
                    offset = _signed(value)
                elif number == _EVENT_DURATION_PS:
                    duration = value
                elif number == _EVENT_STATS:
                    stats.append(value)
            start = t0 + offset / 1000
            end = start + duration / 1000
            if name == trace.OPS_LINE:
                dev.ops.append((start, end, tf_op.get(md_id, "")))
            else:
                run_id = _stats(buf, stats, stat_md).get("run_id")
                if run_id is not None:
                    dev.modules.append((run_id, start, end))
    return dev


@dataclass
class Host:
    """The host plane's part: the named spans ``(name, start_ns, end_ns)``,
    the window, launch starts and ``{run_id: callback start}``."""

    spans: list
    window: tuple
    launches: list
    callbacks: dict


def read_host(path: str, span_names) -> Host:
    """The host spans named ``span_names``, the window (the one ``window``
    span, or else from the first ``cp_als`` span to the last), launches
    and callbacks, through ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {trace.WINDOW}
    spans, launches, callbacks = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    spans.append((e.name, e.start_ns, e.end_ns))
                elif e.name == LAUNCH:
                    launches.append(e.start_ns)
                elif e.name == CALLBACK:
                    run_id = dict(e.stats).get("run_id")
                    if run_id is not None:
                        callbacks.setdefault(run_id, e.start_ns)
    windows = [(s, e) for n, s, e in spans if n == trace.WINDOW]
    if len(windows) > 1:
        raise ValueError(f"expected at most one {trace.WINDOW!r} span, found {len(windows)}")
    program = [(s, e) for n, s, e in spans if n in PROGRAM_SPANS]
    if windows:
        window = windows[0]
    elif program:
        window = (min(s for s, _ in program), max(e for _, e in program))
    else:
        raise ValueError(f"no {trace.WINDOW!r} span and no cp_als span in {path}")
    spans = [sp for sp in spans if sp[0] != trace.WINDOW]
    return Host(spans=spans, window=window, launches=sorted(launches), callbacks=callbacks)


# The device's clock may be set again within a trace: one step of about
# 0.2 ms, one to four seconds in, in most full-size traces on one TPU v5e.
# Steps beyond these limits leave the trace unaligned (clock_segments).
MAX_CLOCK_STEPS = 2  # per device
MAX_CLOCK_STEP_NS = 500_000


def clock_offset(devices: dict, launches, callbacks) -> tuple[float | None, str]:
    """Nanoseconds to add to device times to put them on the host clock,
    and how it was found.

    Module executions pair with launches in order of ``run_id``; if the
    trace holds more of one than the other, the last ones pair.  The offset
    is the least that puts no module start before its launch.  If it puts
    some callback before its module's end, the bounds cross and the offset
    is ``None``."""
    modules = [m for dev in devices.values() for m in dev.modules]
    runs = sorted({r for r, _, _ in modules})
    n = min(len(runs), len(launches))
    if not n:
        return None, f"no pairs: {len(runs)} module executions, {len(launches)} launches"
    launch_of = dict(zip(runs[len(runs) - n:], launches[len(launches) - n:]))
    lo = max(launch_of[r] - s for r, s, _ in modules if r in launch_of)
    ends = [callbacks[r] - e for r, _, e in modules if r in callbacks]
    hi = min(ends) if ends else float("inf")
    how = f"{n} launches paired, bounds [{lo:.0f}, {hi:.0f}] ns"
    if lo > hi:
        return None, "bounds cross: " + how
    return lo, how


def clock_segments(devices: dict, launches, callbacks) -> tuple[dict, str]:
    """Per device, the offsets that put its times on the host clock,
    ``{device: [(from_ns, offset_ns), ...]}``, each offset holding from a
    time on that device's clock on (the first from the trace's start); and
    how they were found.  ``{}`` where the trace cannot be aligned.

    Where :func:`clock_offset` finds one offset, every device takes it.
    Otherwise each device's modules are walked in order of ``run_id``,
    which has to be the order of their starts.  A run of them shares the
    least offset that puts none of their starts before its launch, while
    that offset puts no callback of theirs before its module's end; the
    module that no such offset fits begins the next run: a step in that
    device's clock.  The steps are taken only where a device has at most
    ``MAX_CLOCK_STEPS`` of them, each of at most ``MAX_CLOCK_STEP_NS``, and
    no module's own bounds cross.  An op between a step and the module
    that shows it keeps the earlier offset: it is off by at most the step."""
    offset, how = clock_offset(devices, launches, callbacks)
    if offset is not None:
        return {name: [(float("-inf"), offset)] for name in devices}, how
    if not how.startswith("bounds cross"):
        return {}, how
    runs = sorted({r for dev in devices.values() for r, _, _ in dev.modules})
    n = min(len(runs), len(launches))
    launch_of = dict(zip(runs[len(runs) - n:], launches[len(launches) - n:]))
    out, told = {}, []
    for name, dev in devices.items():
        modules = sorted(m for m in dev.modules if m[0] in launch_of)
        if not modules:
            return {}, f"{how}; {name}: no module paired with a launch"
        if any(b[1] <= a[1] for a, b in zip(modules, modules[1:])):
            return {}, f"{how}; {name}: module starts out of run order"
        segments, since, lo, hi = [], float("-inf"), float("-inf"), float("inf")
        for r, s, e in modules:
            m_lo = launch_of[r] - s
            m_hi = callbacks[r] - e if r in callbacks else float("inf")
            if m_lo > m_hi:
                return {}, f"{how}; {name}: run {r} alone needs [{m_lo:.0f}, {m_hi:.0f}] ns"
            if max(lo, m_lo) > min(hi, m_hi):
                segments.append((since, lo))
                since, lo, hi = s, m_lo, m_hi
            else:
                lo, hi = max(lo, m_lo), min(hi, m_hi)
        segments.append((since, lo))
        steps = [b - a for (_, a), (_, b) in zip(segments, segments[1:])]
        said = ", ".join(f"{o:.0f} from {f:.0f}" for f, o in segments)
        if len(steps) > MAX_CLOCK_STEPS or any(abs(d) > MAX_CLOCK_STEP_NS for d in steps):
            return {}, (f"{how}; {name}: offsets {said}: more than {MAX_CLOCK_STEPS} steps "
                        f"or one over {MAX_CLOCK_STEP_NS} ns")
        out[name] = segments
        told.append(f"{name} offsets {said} (steps {', '.join(f'{d:.0f}' for d in steps)} ns)")
    return out, f"clock steps: {'; '.join(told)} ({how.removeprefix('bounds cross: ')})"


def _shift(ops, segments):
    """``ops`` ``(start, end, key)`` moved by the offset that holds where each starts."""
    starts = [f for f, _ in segments]
    out = []
    for s, e, k in ops:
        offset = segments[bisect.bisect_right(starts, s) - 1][1]
        out.append((s + offset, e + offset, k))
    return out


def scope_of(tf_op: str) -> str:
    """The innermost program scope on an op's ``tf_op`` path, or ``none``."""
    found = NO_SCOPE
    for part in tf_op.split("/")[:-1]:
        if SCOPE.fullmatch(part):
            found = part
    return found


def split_busy(ops, lo: float, hi: float) -> dict[str, float]:
    """The union of ``ops`` ``(start, end, key)`` inside ``[lo, hi]``, each
    instant charged to the innermost op over it: the one that started last
    (a ``while`` op's event spans the ops of its body).  The parts sum to
    the union's length."""
    ops = sorted((max(s, lo), min(e, hi), s, k) for s, e, k in ops if e > lo and s < hi)
    points = sorted({t for s, e, _, _ in ops for t in (s, e)})
    out: dict[str, float] = defaultdict(float)
    open_ops: list = []  # heap of (-start, end, key): the latest start on top
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(ops) and ops[i][0] <= a:
            _, e, s, k = ops[i]
            heapq.heappush(open_ops, (-s, e, k))
            i += 1
        while open_ops and open_ops[0][1] <= a:
            heapq.heappop(open_ops)
        if open_ops:
            out[open_ops[0][2]] += b - a
    return dict(out)


@dataclass
class ProgramSummary:
    """What the program's scopes and spans say about one traced window;
    the last three are empty when the clocks could not be aligned."""

    window_s: float
    devices: int
    clock_offset_ns: float | None  # the first device's first offset (clock_segments)
    clock: str  # how the offsets were found, or why there are none
    busy_s_by_scope: dict = field(default_factory=dict)  # scope -> busy seconds
    idle_s_by_span: dict = field(default_factory=dict)  # span -> device idle seconds
    idle_gaps: list = field(default_factory=list)  # [[span, seconds], ...], longest first


def reduce(devices: dict, host: Host, top: int = 10) -> ProgramSummary:
    """The summary from what :func:`read_devices` and :func:`read_host`
    found: device times moved onto the host clock, then split by scope
    and by span inside the window (a mean over the devices)."""
    lo, hi = host.window
    if hi <= lo:
        raise ValueError(f"empty window {host.window}")
    segments, how = clock_segments(devices, host.launches, host.callbacks)
    first = next(iter(segments.values()), None)
    out = ProgramSummary(window_s=(hi - lo) * 1e-9, devices=len(devices),
                         clock_offset_ns=first[0][1] if first else None, clock=how)
    if not segments:
        return out
    by_name = {
        name: trace.union(trace.clip([(s, e) for n, s, e in host.spans if n == name], lo, hi))
        for name in {n for n, _, _ in host.spans}
    }
    scope_busy: dict[str, float] = defaultdict(float)
    span_idle: dict[str, float] = defaultdict(float)
    all_gaps = []
    for name, dev in devices.items():
        scope = {op: scope_of(op) for op in {op for _, _, op in dev.ops}}
        ops = _shift([(s, e, scope[op]) for s, e, op in dev.ops], segments[name])
        for key, t in split_busy(ops, lo, hi).items():
            scope_busy[key] += t
        busy = trace.union(trace.clip([(s, e) for s, e, _ in ops], lo, hi))
        idle = trace.gaps(busy, lo, hi)
        all_gaps += idle
        for span, intervals in by_name.items():
            span_idle[span] += trace.overlap(idle, intervals)
    n_dev = max(1, len(devices))
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    named = [(trace.span_at(host.spans, (s + e) / 2), (e - s) * 1e-9) for s, e in longest]
    out.busy_s_by_scope = {k: v * 1e-9 / n_dev for k, v in sorted(scope_busy.items())}
    out.idle_s_by_span = {k: v * 1e-9 / n_dev for k, v in sorted(span_idle.items())}
    out.idle_gaps = [[n, s] for n, s in named]
    return out


def summarize(path: str, span_names=("solve",) + PROGRAM_SPANS, top: int = 10) -> ProgramSummary:
    """Read and reduce one trace file."""
    return reduce(read_devices(path), read_host(path, span_names), top=top)


def _scoped(summary: ProgramSummary, keep) -> float | None:
    parts = [t for k, t in summary.busy_s_by_scope.items() if keep(k)]
    return sum(parts) if parts else None


def mttkrp_s(summary: ProgramSummary) -> float | None:
    """Device seconds in ``mttkrp.*`` scopes, or ``None`` if there are none."""
    return _scoped(summary, lambda k: k.startswith("mttkrp."))


def update_s(summary: ProgramSummary) -> float | None:
    """Device seconds in ``update.*`` and ``fit`` scopes, or ``None``."""
    return _scoped(summary, lambda k: k.startswith("update.") or k == "fit")


def sync_idle_s(summary: ProgramSummary) -> float | None:
    """Device idle seconds inside the host loop's dispatch, wait and check
    spans, or ``None`` if the trace has none of them."""
    parts = [summary.idle_s_by_span[n] for n in SYNC_SPANS if n in summary.idle_s_by_span]
    return sum(parts) if parts else None


def mttkrp_roofline(summary: ProgramSummary, sweeps: int, least_s: float) -> float | None:
    """One sweep's least time over the device seconds per sweep in
    ``mttkrp.*`` scopes, in %."""
    t = mttkrp_s(summary)
    return 100.0 * least_s / (t / sweeps) if t and sweeps else None


def per_sweep_ms(seconds: float | None, sweeps: int) -> float | None:
    return 1e3 * seconds / sweeps if seconds is not None and sweeps else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="an .xplane.pb written by jax.profiler")
    ap.add_argument("--sweeps", type=int, default=0, help="sweeps the traced solves ran")
    ap.add_argument("--config", help="a bench configuration (shape, rank, dtype) for the roofline")
    ap.add_argument("--device-kind", default="TPU v5 lite", help="row of bench/peaks.json")
    args = ap.parse_args(argv)
    s = summarize(args.path)
    line = {
        "window_s": s.window_s, "devices": s.devices,
        "clock_offset_ns": s.clock_offset_ns, "clock": s.clock,
        "busy_s_by_scope": s.busy_s_by_scope, "idle_s_by_span": s.idle_s_by_span,
        "idle_gaps": s.idle_gaps,
    }
    if args.sweeps:
        line["mttkrp_ms_per_sweep"] = per_sweep_ms(mttkrp_s(s), args.sweeps)
        line["update_ms_per_sweep"] = per_sweep_ms(update_s(s), args.sweeps)
        line["sync_idle_ms_per_sweep"] = per_sweep_ms(sync_idle_s(s), args.sweeps)
        if args.config:
            from bench import roofline

            with open(args.config) as f:
                cfg = json.load(f)
            least, _ = roofline.sweep_least_seconds(
                cfg["shape"], cfg["rank"], cfg["dtype"], roofline.peaks(args.device_kind))
            line["mttkrp_roofline"] = mttkrp_roofline(s, args.sweeps, least)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
