"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps named by host spans."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

# A trace recorded on one TPU v5e: three "step" spans, each running two
# small jitted programs, and three "generator_wait" sleeps, inside "window".
FIXTURE = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.union([]) == []


def test_clip_and_gaps():
    busy = trace.union(trace.clip([(-5, 2), (4, 6), (9, 20)], 0, 10))
    assert busy == [(0, 2), (4, 6), (9, 10)]
    assert trace.gaps(busy, 0, 10) == [(2, 4), (6, 9)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_overlap_of_interval_lists():
    assert trace.overlap([(0, 4), (6, 10)], [(2, 7), (9, 12)]) == 2 + 1 + 1
    assert trace.overlap([(0, 1)], [(1, 2)]) == 0
    assert trace.overlap([], [(0, 5)]) == 0


def test_span_at_takes_the_innermost():
    spans = [("solve", 0, 100), ("step", 10, 20)]
    assert trace.span_at(spans, 15) == "step"
    assert trace.span_at(spans, 50) == "solve"
    assert trace.span_at(spans, 150) == trace.NO_SPAN


def test_reduce_on_synthetic_events():
    ops = {"/device:TPU:0": [("a", 0, 40), ("b", 30, 50), ("a", 80, 90), ("c", -10, 5)]}
    spans = [("window", 0, 100), ("step", 0, 60), ("generator_wait", 60, 100)]
    s = trace.reduce(ops, spans, (0, 100))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(60e-9)  # [0, 50] and [80, 90]
    assert s.idle_share == pytest.approx(0.4)
    assert s.top_ops == [["a", pytest.approx(50e-9)], ["b", pytest.approx(20e-9)],
                         ["c", pytest.approx(5e-9)]]
    assert s.idle_gaps == [["generator_wait", pytest.approx(30e-9)],
                           ["generator_wait", pytest.approx(10e-9)]]
    # busy inside each span: [0, 50] in step, [80, 90] in generator_wait
    assert s.span_busy == {"generator_wait": pytest.approx(10e-9), "step": pytest.approx(50e-9)}


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]}
    s = trace.reduce(ops, [], (0, 100))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(75e-9)


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({}, [], (5, 5))


def test_recorded_tpu_trace():
    device_ops, spans, window = trace.read_events(str(FIXTURE), ["step", "generator_wait"])
    assert list(device_ops) == ["/device:TPU:0"]
    assert len(device_ops["/device:TPU:0"]) == 12
    assert window == (44052227.0, 55882276.0)
    assert [n for n, _, _ in spans].count("step") == 3
    s = trace.summarize(str(FIXTURE), ["step", "generator_wait"])
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.011830049)
    assert s.busy_s == pytest.approx(7.0687e-05)
    assert 0.99 < s.idle_share < 1.0
    names = [n for n, _ in s.top_ops]
    assert names[0].startswith("%fusion = ") and names[1].startswith("%add_reduce_fusion")
    assert all(a >= b for (_, a), (_, b) in zip(s.top_ops, s.top_ops[1:]))
    assert s.idle_gaps[0] == ["generator_wait", pytest.approx(0.003923204)]
    assert {n for n, _ in s.idle_gaps} <= {"step", "generator_wait"}
    assert len(s.idle_gaps) == 10
    assert set(s.span_busy) == {"step", "generator_wait"}
    assert 0 < s.span_busy["step"] <= s.busy_s
    assert s.span_busy["step"] + s.span_busy["generator_wait"] <= s.busy_s + 1e-12


def test_sweep_roofline_reads_the_device_time_inside_solves():
    """The reader on the recorded trace, its "step" spans standing for
    solves: the least time of a sweep over the device's busy seconds inside
    them per sweep, whatever the host did in between."""
    from types import SimpleNamespace

    from bench import harness, roofline

    device_ops, spans, window = trace.read_events(str(FIXTURE), ["step"])
    spans = [("solve" if n == "step" else n, a, b) for n, a, b in spans]
    summary = trace.reduce(device_ops, spans, window)
    busy = summary.span_busy["solve"]
    assert 0 < busy < summary.window_s
    cfg = {"kind": "solve", "shape": [225, 59, 200, 200], "rank": 25, "dtype": "float32"}
    peak = roofline.peaks("TPU v5 lite")
    units = [{"sweeps": 16}] * 3
    run = SimpleNamespace(config=cfg, peak=peak, units=units, trace=summary,
                          window_s=summary.window_s)
    read = harness.reader("sweep_roofline")
    least = 225 * 59 * 200 * 200 * 4 / 819e9
    assert read(run) == pytest.approx(100 * least / (busy / 48))
    # no trace, no solve spans, or no sweeps: nothing to read, never 0
    assert read(SimpleNamespace(config=cfg, peak=peak, units=units, trace=None)) is None
    empty = trace.reduce(device_ops, [], window)
    assert read(SimpleNamespace(config=cfg, peak=peak, units=units, trace=empty)) is None
    assert read(SimpleNamespace(config=cfg, peak=peak, units=[], trace=summary)) is None


def test_find_xplane_wants_exactly_one(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(FIXTURE.read_bytes())
    assert trace.find_xplane(str(tmp_path)) == str(run / "host.xplane.pb")


def test_importing_loads_no_accelerator_library():
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r); import bench.trace; "
        "print(any('libtpu' in m or 'jax' == m for m in sys.modules))"
        % str(Path(__file__).resolve().parents[2])
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "False"
