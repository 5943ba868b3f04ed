"""The reduction of the program's own scopes and spans (bench/program_trace.py):
the wire-format reader, the clock offset, the split of busy time by scope
and of idle time by span, and the per-sweep figures, on synthetic events
and on two traces recorded on one TPU v5e."""

import gzip
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, roofline, trace  # noqa: E402
from bench import program_trace as pt  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
# three "step" spans running two small jitted programs each (test_bench_trace.py)
SMALL = DATA / "tpu_small.xplane.pb"
# two solves of tools/record_cp_als_trace.py, gzipped; units in tpu_cp_als.json
CP_ALS_GZ = DATA / "tpu_cp_als.xplane.pb.gz"
CP_ALS_RUN = DATA / "tpu_cp_als.json"


@pytest.fixture(scope="module")
def cp_als_trace(tmp_path_factory):
    """The cp_als recording, unpacked."""
    path = tmp_path_factory.mktemp("cp_als") / "tpu_cp_als.xplane.pb"
    path.write_bytes(gzip.decompress(CP_ALS_GZ.read_bytes()))
    return str(path)


def test_scope_of_takes_the_innermost_program_scope():
    assert pt.scope_of("jit(_chunk)/while/body/mttkrp.node3/abc,ac->bc/dot_general:") == "mttkrp.node3"
    assert pt.scope_of("jit(_chunk)/while/body/update.mode1/jit(_pinv)/jit(svd)/svd") == "update.mode1"
    assert pt.scope_of("jit(_chunk)/while/body/closed_call/fit/mul") == "fit"
    assert pt.scope_of("jit(f)/init/mttkrp.node2/dot_general") == "mttkrp.node2"
    assert pt.scope_of("jit(<lambda>)/dot_general:") == pt.NO_SCOPE
    # whole components only, and never the op itself
    assert pt.scope_of("jit(f)/fitness/mttkrp.node1x/mul") == pt.NO_SCOPE
    assert pt.scope_of("jit(f)/fit") == pt.NO_SCOPE
    assert pt.scope_of("") == pt.NO_SCOPE


def test_split_busy_partitions_the_union():
    ops = [(0, 40, "a"), (30, 50, "b"), (45, 60, "c"), (80, 90, "a"), (-10, 5, "d"), (95, 120, "b")]
    parts = pt.split_busy(ops, 0, 100)
    # the later start wins an overlap: b over a in [30, 40], c over b in [45, 50]
    assert parts == {"a": 30 + 10, "b": 15 + 5, "c": 15}
    union = trace.union(trace.clip([(s, e) for s, e, _ in ops], 0, 100))
    assert sum(parts.values()) == sum(e - s for s, e in union)
    assert pt.split_busy([], 0, 100) == {}
    # a loop's event spans its body: the body's ops own their time
    nested = [(0, 100, "none"), (10, 20, "update.mode0"), (30, 40, "update.mode0")]
    assert pt.split_busy(nested, 0, 100) == {"none": 80, "update.mode0": 20}


def test_clock_offset_is_the_least_that_orders_launch_and_start():
    dev = pt.Device(modules=[(1, 100, 150), (2, 300, 320)])
    # launches at 110 and 330: the device clock runs 30 behind at most
    offset, how = pt.clock_offset({"d": dev}, [110, 330], {1: 200, 2: 400})
    assert offset == 30 and "2 launches paired" in how
    # a callback before its module's aligned end: the bounds cross
    offset, how = pt.clock_offset({"d": dev}, [110, 330], {1: 170, 2: 400})
    assert offset is None and how.startswith("bounds cross")
    # more launches than executions: the last ones pair
    offset, _ = pt.clock_offset({"d": dev}, [5, 110, 330], {})
    assert offset == 30
    assert pt.clock_offset({}, [1], {})[0] is None
    assert pt.clock_offset({"d": dev}, [], {})[0] is None


def test_reduce_on_synthetic_events():
    """Device times move onto the host clock before the window clips them,
    the scopes split the busy time and each gap is named on that clock."""
    dev = pt.Device(
        ops=[(0, 40, "jit(c)/mttkrp.node1/dot_general:"), (40, 50, "jit(c)/update.mode0/mul:"),
             (70, 80, "jit(c)/fit/mul:"), (85, 95, "jit(sq)/square:")],
        modules=[(1, 0, 80), (2, 85, 95)])
    spans = [("solve", 10, 200), ("cp_als.dispatch", 10, 20), ("cp_als.wait", 20, 70),
             ("cp_als.check", 70, 100), ("cp_als.dispatch", 100, 110)]
    host = pt.Host(spans=spans, window=(0, 200), launches=[10, 100], callbacks={1: 95, 2: 120})
    s = pt.reduce({"/device:TPU:0": dev}, host)
    assert s.clock_offset_ns == 15 and s.devices == 1
    assert s.window_s == pytest.approx(200e-9)
    # on the host clock: [15, 65] scoped, idle [65, 85], fit [85, 95], idle, square [100, 110]
    assert s.busy_s_by_scope == {"mttkrp.node1": pytest.approx(40e-9),
                                 "update.mode0": pytest.approx(10e-9),
                                 "fit": pytest.approx(10e-9), "none": pytest.approx(10e-9)}
    # idle: [0, 15] (before solve), [65, 85], [95, 100], [110, 200]
    assert s.idle_s_by_span == {"solve": pytest.approx((5 + 20 + 5 + 90) * 1e-9),
                                "cp_als.dispatch": pytest.approx(5e-9),
                                "cp_als.wait": pytest.approx(5e-9),
                                "cp_als.check": pytest.approx(20e-9)}
    assert s.idle_gaps[0] == ["solve", pytest.approx(90e-9)]
    assert [n for n, _ in s.idle_gaps[1:]] == ["cp_als.check", trace.NO_SPAN, "cp_als.check"]
    assert pt.sync_idle_s(s) == pytest.approx(30e-9)
    assert pt.update_s(s) == pytest.approx(20e-9)
    assert pt.mttkrp_s(s) == pytest.approx(40e-9)


def test_without_an_offset_nothing_is_aligned():
    dev = pt.Device(ops=[(0, 10, "jit(c)/fit/mul:")], modules=[(1, 0, 10)])
    host = pt.Host(spans=[("cp_als.wait", 0, 100)], window=(0, 100), launches=[50],
                   callbacks={1: 20})
    s = pt.reduce({"/device:TPU:0": dev}, host)
    assert s.clock_offset_ns is None and s.clock.startswith("bounds cross")
    assert s.busy_s_by_scope == {} and s.idle_s_by_span == {} and s.idle_gaps == []


def test_each_reading_is_none_when_its_input_is_missing():
    empty = pt.ProgramSummary(window_s=1.0, devices=0, clock_offset_ns=None, clock="")
    assert pt.mttkrp_s(empty) is None and pt.update_s(empty) is None
    assert pt.sync_idle_s(empty) is None
    assert pt.mttkrp_roofline(empty, 16, 1e-3) is None
    assert pt.per_sweep_ms(None, 16) is None
    unscoped = pt.ProgramSummary(window_s=1.0, devices=1, clock_offset_ns=0.0, clock="",
                                 busy_s_by_scope={"none": 0.5}, idle_s_by_span={"solve": 0.1})
    assert pt.mttkrp_s(unscoped) is None and pt.update_s(unscoped) is None
    assert pt.sync_idle_s(unscoped) is None
    scoped = pt.ProgramSummary(window_s=1.0, devices=1, clock_offset_ns=0.0, clock="",
                               busy_s_by_scope={"mttkrp.node1": 0.2})
    assert pt.mttkrp_roofline(scoped, 0, 1e-3) is None
    assert pt.mttkrp_roofline(scoped, 10, 1e-3) == pytest.approx(100 * 1e-3 / 0.02)


def test_wire_reader_agrees_with_profile_data():
    """The ops the wire-format reader finds are ProfileData's, in the same
    order and to the nanosecond, now with their ``tf_op``."""
    devices = pt.read_devices(str(SMALL))
    device_ops, _, _ = trace.read_events(str(SMALL), [])
    assert list(devices) == list(device_ops) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"].ops
    assert len(ops) == len(device_ops["/device:TPU:0"]) == 12
    for (s, e, _), (_, s2, e2) in zip(ops, device_ops["/device:TPU:0"]):
        assert abs(s - s2) < 2 and abs(e - e2) < 2
    assert {op for _, _, op in ops} == {"", "jit(<lambda>)/dot_general:", "jit(<lambda>)/reduce_sum:"}
    assert [r for r, _, _ in devices["/device:TPU:0"].modules] == list(range(7, 13))


@pytest.mark.parametrize("fixture", ["small", "cp_als"])
def test_recorded_clock_offset_orders_every_module(fixture, request):
    """On both recordings the offset puts every module start at or after
    its launch and every callback after its module's end."""
    path = str(SMALL) if fixture == "small" else request.getfixturevalue("cp_als_trace")
    devices = pt.read_devices(path)
    host = pt.read_host(path, [])
    offset, how = pt.clock_offset(devices, host.launches, host.callbacks)
    assert offset is not None, how
    modules = sorted(m for d in devices.values() for m in d.modules)
    assert len(modules) == len(host.launches)
    for (run_id, start, end), launch in zip(modules, host.launches):
        assert start + offset >= launch - 1e-6
        if run_id in host.callbacks:
            assert host.callbacks[run_id] >= end + offset
    if fixture == "small":
        assert 1.0e6 < offset < 1.3e6  # about 1.1 ms


def test_recorded_small_trace_gaps_on_the_host_clock():
    s = pt.summarize(str(SMALL), ["step", "generator_wait"])
    assert s.clock_offset_ns == pytest.approx(1237845.172)
    assert list(s.busy_s_by_scope) == [pt.NO_SCOPE]
    # aligned, the first program (run 7) falls inside the window
    raw = trace.summarize(str(SMALL), ["step", "generator_wait"])
    assert s.busy_s_by_scope[pt.NO_SCOPE] > raw.busy_s
    assert {n for n, _ in s.idle_gaps} <= {"step", "generator_wait"}
    assert sum(s.idle_s_by_span.values()) <= s.window_s


def test_recorded_cp_als_split_by_scope(cp_als_trace):
    """The cp_als recording: the binary tree's nodes, every mode's update
    and the fit own device time, the parts sum to the busy time inside the
    solves, and the host loop's spans own the idle gaps."""
    s = pt.summarize(cp_als_trace)
    scopes = set(s.busy_s_by_scope)
    # XLA fuses the first leaf of each partial (nodes 2 and 5) into the
    # partial's own fusion, which carries the partial's op name
    assert {"mttkrp.node1", "mttkrp.node3", "mttkrp.node4", "mttkrp.node6"} <= scopes
    assert {k for k in scopes if k.startswith("mttkrp.")} <= {f"mttkrp.node{i}" for i in range(1, 7)}
    assert {f"update.mode{n}" for n in range(4)} | {"fit", pt.NO_SCOPE} <= scopes
    raw = trace.summarize(cp_als_trace, ["solve"])
    assert sum(s.busy_s_by_scope.values()) == pytest.approx(raw.span_busy["solve"], rel=0.01)
    gap_names = [n for n, _ in s.idle_gaps]
    assert all(n in pt.PROGRAM_SPANS for n in gap_names[:5]), gap_names
    assert set(pt.SYNC_SPANS) <= set(s.idle_s_by_span)


def test_recorded_cp_als_readings(cp_als_trace):
    """Each per-sweep reading on the cp_als recording, from the parts it
    is defined by."""
    s = pt.summarize(cp_als_trace)
    recorded = json.loads(CP_ALS_RUN.read_text())
    units = recorded["units"]
    sweeps = sum(u["sweeps"] for u in units)
    mttkrp = sum(t for k, t in s.busy_s_by_scope.items() if k.startswith("mttkrp."))
    update = sum(t for k, t in s.busy_s_by_scope.items() if k.startswith("update.") or k == "fit")
    idle = sum(s.idle_s_by_span[n] for n in pt.SYNC_SPANS)
    least, _ = roofline.sweep_least_seconds(recorded["shape"], recorded["rank"],
                                            "float32", roofline.peaks("TPU v5 lite"))
    assert pt.mttkrp_roofline(s, sweeps, least) == pytest.approx(100 * least / (mttkrp / sweeps))
    assert 0 < pt.mttkrp_roofline(s, sweeps, least) < 100
    assert pt.per_sweep_ms(pt.update_s(s), sweeps) == pytest.approx(1e3 * update / sweeps)
    assert pt.per_sweep_ms(pt.sync_idle_s(s), sweeps) == pytest.approx(1e3 * idle / sweeps)
    assert 0 < idle < s.window_s
    # the program's own count, as the benchmark reads it from the units
    read = harness.reader("host_syncs_per_sweep")
    run = SimpleNamespace(config={"kind": "solve"}, units=[
        {"sweeps": u["sweeps"], "state": SimpleNamespace(host_syncs=u["host_syncs"])}
        for u in units])
    assert read(run) == 2.0  # one wait and one fit read a sweep at sweeps_per_sync=1


def test_host_syncs_reader_is_none_without_the_counter():
    read = harness.reader("host_syncs_per_sweep")
    units = [{"sweeps": 4, "state": SimpleNamespace()}]  # a program that does not count
    assert read(SimpleNamespace(config={"kind": "solve"}, units=units)) is None
    assert read(SimpleNamespace(config={"kind": "solve"}, units=[])) is None
    assert read(SimpleNamespace(config={"kind": "other"}, units=units)) is None


def test_command_line_prints_one_json_line(cp_als_trace, tmp_path, capsys):
    recorded = json.loads(CP_ALS_RUN.read_text())
    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({"shape": recorded["shape"], "rank": recorded["rank"],
                               "dtype": "float32"}))
    assert pt.main([cp_als_trace, "--sweeps", "6", "--config", str(cfg)]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["clock_offset_ns"] > 0
    assert 0 < line["mttkrp_roofline"] < 100 and line["sync_idle_ms_per_sweep"] > 0
    assert set(line["busy_s_by_scope"]) >= {"fit", pt.NO_SCOPE}


def test_recorder_drops_one_plane_and_keeps_the_rest(tmp_path):
    """The fixture recorder's plane filter: what remains reads as before."""
    from jax.profiler import ProfileData

    sys.path.insert(0, str(harness.ROOT / "tools"))
    import record_cp_als_trace as rec

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(rec.without_plane(SMALL.read_bytes(), "/host:CPU"))
    before = [p.name for p in ProfileData.from_file(str(SMALL)).planes]
    assert [p.name for p in ProfileData.from_file(str(path)).planes] == [
        n for n in before if n != "/host:CPU"]
    assert pt.read_devices(str(path)).keys() == pt.read_devices(str(SMALL)).keys()
    assert rec.without_plane(SMALL.read_bytes(), "no such plane") == SMALL.read_bytes()


def test_importing_loads_no_accelerator_library():
    code = (
        "import sys; sys.path.insert(0, %r); import bench.program_trace; "
        "print(any('libtpu' in m or 'jax' == m for m in sys.modules))"
        % str(Path(__file__).resolve().parents[2])
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "False"


def test_prepare_is_a_scope():
    assert pt.scope_of("jit(prepare_operands)/prepare/reshape:") == "prepare"
    assert pt.scope_of("jit(prepare_operands)/prepare/multiply_reduce") == "prepare"
    dev = pt.Device(ops=[(0, 30, "jit(prepare_operands)/prepare/copy:"),
                         (30, 50, "jit(c)/mttkrp.node1/dot_general:")],
                    modules=[(1, 0, 30), (2, 30, 50)])
    host = pt.Host(spans=[("solve", 0, 100)], window=(0, 100), launches=[0, 30],
                   callbacks={1: 40, 2: 60})
    s = pt.reduce({"/device:TPU:0": dev}, host)
    assert s.busy_s_by_scope == {"prepare": pytest.approx(30e-9),
                                 "mttkrp.node1": pytest.approx(20e-9)}


def test_readers_equal_the_command_line_on_the_cp_als_recording(cp_als_trace, tmp_path, capsys):
    """The three readers of the program's split, on the recording as a
    traced run of the ``solve`` driver hands it to them, print what the
    command line prints for it."""
    recorded = json.loads(CP_ALS_RUN.read_text())
    cfg = {"shape": recorded["shape"], "rank": recorded["rank"], "dtype": "float32"}
    units = [{"sweeps": u["sweeps"]} for u in recorded["units"]]
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(cfg))
    assert pt.main([cp_als_trace, "--sweeps", str(sum(u["sweeps"] for u in units)),
                    "--config", str(path)]) == 0
    line = json.loads(capsys.readouterr().out)
    spans = harness.spans_of(harness.load_driver("solve"))
    run = SimpleNamespace(config=cfg, peak=roofline.peaks(recorded["device_kind"]), units=units,
                          program=harness.read_program(cp_als_trace, spans))
    for metric in ("mttkrp_roofline", "update_ms_per_sweep", "sync_idle_ms_per_sweep"):
        assert harness.reader(metric)(run) == line[metric] > 0, metric
    # the gaps fall in the host loop's spans, as a traced run's breakdown names them
    gaps = [n for n, _ in run.program.idle_gaps]
    assert all(n.startswith("cp_als.") for n in gaps[:5]), gaps


def _step_device():
    """Two modules that no one offset orders: the device's clock runs 10
    behind the host's until it is set again, then 30 behind."""
    return pt.Device(ops=[(100, 150, "jit(c)/mttkrp.node1/dot_general:"),
                          (300, 320, "jit(c)/fit/mul:")],
                     modules=[(1, 100, 150), (2, 300, 320)])


def test_clock_segments_follow_a_step_in_the_device_clock():
    """Each run of modules gets the least offset of its own, and the ops
    after the step move by the second."""
    dev = _step_device()
    assert pt.clock_offset({"d": dev}, [110, 330], {1: 170, 2: 400})[0] is None
    segments, how = pt.clock_segments({"d": dev}, [110, 330], {1: 170, 2: 400})
    assert segments == {"d": [(float("-inf"), 10), (300, 30)]}
    assert how.startswith("clock steps: d offsets 10 from -inf, 30 from 300 (steps 20 ns)")
    host = pt.Host(spans=[("cp_als.wait", 0, 500)], window=(0, 500), launches=[110, 330],
                   callbacks={1: 170, 2: 400})
    s = pt.reduce({"/device:TPU:0": dev}, host)
    assert s.clock_offset_ns == 10
    assert s.busy_s_by_scope == {"mttkrp.node1": pytest.approx(50e-9), "fit": pytest.approx(20e-9)}
    # idle: [0, 110], [160, 330], [350, 500]
    assert s.idle_s_by_span == {"cp_als.wait": pytest.approx((110 + 170 + 150) * 1e-9)}
    # where one offset orders every module, every device takes it
    assert pt.clock_segments({"d": dev}, [110, 330], {1: 200, 2: 400})[0] == {
        "d": [(float("-inf"), 30)]}


def test_each_device_keeps_its_own_clock():
    """Two devices whose clocks differ run the same programs: each is
    aligned by its own modules, never by the other's times."""
    a = pt.Device(ops=[(100, 150, "jit(c)/fit/mul:"), (300, 320, "jit(c)/fit/mul:")],
                  modules=[(1, 100, 150), (2, 300, 320)])
    b = pt.Device(ops=[(70, 120, "jit(c)/fit/mul:"), (270, 290, "jit(c)/fit/mul:")],
                  modules=[(1, 70, 120), (2, 270, 290)])
    launches, callbacks = [110, 310], {1: 170, 2: 370}
    assert pt.clock_offset({"a": a, "b": b}, launches, callbacks)[0] is None
    segments, _ = pt.clock_segments({"a": a, "b": b}, launches, callbacks)
    assert segments == {"a": [(float("-inf"), 10)], "b": [(float("-inf"), 40)]}
    host = pt.Host(spans=[("cp_als.wait", 0, 500)], window=(0, 500), launches=launches,
                   callbacks=callbacks)
    s = pt.reduce({"a": a, "b": b}, host)
    # both devices busy in [110, 160] and [310, 330] on the host clock
    assert s.busy_s_by_scope == {"fit": pytest.approx(70e-9)}
    assert s.idle_s_by_span == {"cp_als.wait": pytest.approx(430e-9)}


@pytest.mark.parametrize("case", ["alternating", "large_step", "out_of_order", "module_crosses"])
def test_clock_segments_refuse_what_no_step_explains(case):
    """Bounds that steps within the stated limits cannot reconcile leave
    the trace unaligned, as a single offset that crosses did before."""
    if case == "alternating":  # the clock back and forth, module by module
        modules = [(r, 100 * r, 100 * r + 10) for r in range(1, 6)]
        launches = [100 * r + (10 if r % 2 else 30) for r in range(1, 6)]
        callbacks = {r: 100 * r + 10 + (15 if r % 2 else 35) for r in range(1, 6)}
    elif case == "large_step":
        step = pt.MAX_CLOCK_STEP_NS + 1
        modules = [(1, 100, 150), (2, 10**7, 10**7 + 20)]
        launches, callbacks = [110, 10**7 + 10 + step], {1: 170, 2: 10**7 + 40 + step}
    elif case == "out_of_order":  # run ids that do not follow the device's starts
        modules = [(1, 300, 320), (2, 100, 150)]
        launches, callbacks = [310, 130], {1: 320 + 15, 2: 150 + 5}
    else:
        modules = [(1, 100, 150), (2, 300, 320)]
        launches, callbacks = [110, 330], {1: 170, 2: 330}
    dev = pt.Device(ops=[(s, e, "jit(c)/fit/mul:") for _, s, e in modules], modules=modules)
    assert pt.clock_offset({"d": dev}, launches, callbacks)[0] is None
    segments, how = pt.clock_segments({"d": dev}, launches, callbacks)
    assert segments == {} and how.startswith("bounds cross"), how
    host = pt.Host(spans=[("cp_als.wait", 0, 2 * 10**7)], window=(0, 2 * 10**7),
                   launches=launches, callbacks=callbacks)
    s = pt.reduce({"d": dev}, host)
    assert s.clock_offset_ns is None and s.busy_s_by_scope == {} and s.idle_gaps == []
