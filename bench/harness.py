"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

Everything a cell is made of is found by name: its configuration in
``configs/<config>.json`` (whose ``kind`` names the driver in
``drivers/<kind>.py``), its traffic mix in ``traffic/<traffic>.json`` and
each metric's reader in ``metrics/<metric>.py``.  A run sets up, measures
one window of ``--seconds``, reads the metrics, and then checks the
window's answers against the plain reference (``reference.py``).

A driver module holds ``SPANS``, the host spans its set-up and window
open and the program's spans it wants read, and four functions:
``setup(config, mix, seed, span) -> state``, ``describe(state) -> lines``,
``window(state, seconds, span) -> {"window_s", "units", "attempted",
"counters"}`` (each unit as :class:`Run` describes it) and
``check(state, win, control) -> (numbers, failed)``.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench import trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = (trace_mod.WINDOW,)  # the harness's own; each driver adds its own


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric's reader reads: one run of one cell.

    Each of ``units`` is one CP solution the window delivered, a dict with
    ``done``, its seconds from the window's start, and ``sweeps``, the
    sweeps that made it; optionally ``state``, the ``CPState`` it came
    from.  A reader reads these and returns ``None`` where what it needs
    is missing, whichever driver delivered them.  ``trace`` is the traced
    window on the host's clock; ``program`` splits it by the program's own
    scopes and spans on the aligned clock, and is ``None`` where the trace
    has no device or its clocks cannot be aligned."""

    cell: str
    config: dict
    mix: dict
    peak: dict
    setup_s: float
    window_s: float
    units: list
    counters: dict = field(default_factory=dict)
    trace: object = None  # trace.Summary of a traced run
    program: object = None  # program_trace.ProgramSummary of a traced run


def sweeps_of(units) -> int | None:
    """The sweeps of ``units`` summed: ``None`` where there are no units,
    or a unit does not say how many sweeps made it."""
    sweeps = [u.get("sweeps") for u in units]
    return None if not sweeps or None in sweeps else sum(sweeps)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r}; known: {[c['name'] for c in bench['workloads']]}")


def load_part(kind: str, name: str, base: Path = BENCH) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``."""
    return json.loads((Path(base) / kind / f"{name}.json").read_text())


def _load(path: Path, what: str):
    """The module at ``path``, loaded by path (so that a part added under
    another ``base`` is found alike)."""
    spec = importlib.util.spec_from_file_location(f"bench_part_{len(sys.modules)}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, base: Path = BENCH):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _load(Path(base) / "metrics" / f"{metric}.py", f"reader for metric {metric!r}").read


def load_driver(kind: str, base: Path = BENCH):
    """The driver module ``drivers/<kind>.py``."""
    return _load(Path(base) / "drivers" / f"{kind}.py", f"driver for kind {kind!r}")


def spans_of(driver) -> tuple:
    """The host spans a run of ``driver`` reads: the harness's and the driver's."""
    return tuple(dict.fromkeys(SPANS + tuple(driver.SPANS)))


def read_program(path: str, spans):
    """The program's split of the trace at ``path``, or ``None`` where it
    has no device or the clocks cannot be aligned."""
    from bench import program_trace

    program = program_trace.summarize(path, spans)
    return program if program.devices and program.clock_offset_ns is not None else None


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def require_devices(chips: int):
    """The TPU devices; raises :class:`NoAccelerator` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"needs a TPU, but JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind}); refusing to fall back"
        )
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def span(name: str):
    """A host span, written into the profiler's trace when one is taken."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts traces and XLA compiles while active."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
    }

    def __init__(self):
        import jax

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, peak: dict | None = None, config: dict | None = None,
             mix: dict | None = None, base: Path = BENCH, control: bool = False,
             log=print) -> dict:
    """One run of ``cell``; returns the result line's object.  The cell's
    files are found under ``base``; ``config`` and ``mix`` replace them
    (tests run a cell at a tiny size), and ``peak`` replaces the device's
    row of the peaks table.  With ``control`` the answers judged are the
    control's, the reference at the precision below the configuration's
    put in the program's place (``bench/calibrate.py``)."""
    import jax

    from bench import roofline

    config = config or load_part("configs", cell["config"], base)
    mix = mix or load_part("traffic", cell["traffic"], base)
    device = jax.devices()[0]
    peak = peak or roofline.peaks(device.device_kind)
    driver = load_driver(config["kind"], base)
    spans = spans_of(driver)
    counter = CompileCounter()

    state = driver.setup(config, mix, seed, span)
    for line in driver.describe(state):
        log(line)
    setup_s = time.perf_counter() - t_start
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    summary = program = None
    try:
        if trace:
            # spans are TraceMe events; the Python tracer would slow the host loop
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
        counter.active = True
        try:
            with span(trace_mod.WINDOW):
                win = driver.window(state, seconds, span)
        finally:
            counter.active = False
            if trace:
                jax.profiler.stop_trace()
        if trace:
            path = trace_mod.find_xplane(log_dir)
            summary = trace_mod.summarize(path, spans)
            program = read_program(path, spans)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"memory: peak_bytes_in_use={mem}")
    log(f"window: seconds={win['window_s']} units={len(win['units'])} "
        f"traces_in_window={counter.counts['traces']} compiles_in_window={counter.counts['compiles']}")
    if summary is not None:
        log(f"trace: window_s={summary.window_s} busy_s={summary.busy_s} "
            f"devices={summary.devices} idle_share={summary.idle_share} "
            f"busy_s_in_spans={summary.span_busy}")
    if program is not None:
        log(f"program: clock={program.clock} busy_s_by_scope={program.busy_s_by_scope} "
            f"idle_s_by_span={program.idle_s_by_span}")

    run = Run(cell=cell["name"], config=config, mix=mix, peak=peak, setup_s=setup_s,
              window_s=win["window_s"], units=win["units"], counters=win["counters"],
              trace=summary, program=program)
    metrics = {}
    for m in metrics_for(bench, cell["name"], trace):
        value = reader(m["name"], base)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers, failed = driver.check(state, win, control=control)
    del state
    checks, correct = judge(numbers, failed, config["limits"])
    log("compared, no limit: " + " ".join(
        f"{k}={v!r}" for k, v in numbers.items()
        if k not in config["limits"] and not isinstance(v, list)))
    result = {
        "correct": correct,
        "attempted": win["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(mem),
        },
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        # on the aligned clock where there is one, so a gap falls in the span it lies in
        gaps = (program or summary).idle_gaps
        result["breakdown"] = {"device_ops": summary.top_ops, "idle_gaps": gaps}
    result["checks"] = checks
    return result


def judge(numbers: dict, failed: int, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all hold; a
    configuration's ``limits`` name the numbers that are judged."""
    checks = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def check_lines(checks: dict) -> list[str]:
    return [
        f"check {name}: {c['value']!r} limit {c['limit']!r} "
        f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
        for name, c in checks.items()
    ]


def main(args, t_start: float) -> int:
    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    try:
        require_devices(int(cell["chips"]))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import use_checkout_cache

    cache = use_checkout_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile_cache: {cache}", flush=True)
    result = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=t_start,
                      log=lambda s: print(s, flush=True))
    lines = check_lines(result["checks"])
    print(json.dumps(result), flush=True)
    print("\n".join(lines), file=sys.stderr, flush=True)
    return 0
