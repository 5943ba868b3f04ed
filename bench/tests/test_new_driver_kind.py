"""A cell of a driver kind the benchmark does not have, added as files
alone: its driver declares its own spans, the readers read what its window
delivered, and a traced run names its idle gaps by its spans."""

import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BENCH = harness.load_benchmark()
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# recorded on one TPU v5e: three "step" spans and three "generator_wait"
# sleeps inside "window" (test_bench_trace.py)
SMALL = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"

STUB_DRIVER = '''
"""Each unit: a few jitted steps, then a wait for the next input."""
import time

import jax
import jax.numpy as jnp

SPANS = ("step", "generator_wait")


def setup(cfg, mix, seed, span):
    f = jax.jit(lambda a: a @ a)
    x = jnp.full((cfg["n"], cfg["n"]), float(seed % 7))
    with span("step"):
        jax.block_until_ready(f(x))
    return {"f": f, "x": x, "sweeps": mix["sweeps"]}


def describe(state):
    return [f"stub: {state['sweeps']} steps a unit"]


def window(state, seconds, span):
    units, t0 = [], time.perf_counter()
    while True:
        for _ in range(state["sweeps"]):
            with span("step"):
                jax.block_until_ready(state["f"](state["x"]))
        with span("generator_wait"):
            time.sleep(0.002)
        t = time.perf_counter() - t0
        units.append({"done": t, "sweeps": state["sweeps"]})
        if t >= seconds:
            return {"window_s": t, "units": units, "attempted": len(units), "counters": {}}


def check(state, win, control=False):
    return {"steps_gap": 0}, 0
'''

STUB_READER = '''
"""step_idle_ms: the device's idle ms inside the stub's "step" spans."""


def read(run):
    if run.program is None or "step" not in run.program.idle_s_by_span:
        return None
    return 1e3 * run.program.idle_s_by_span["step"]
'''


@pytest.fixture
def stub_cell(tmp_path):
    """The stub's driver, configuration, traffic and reader under
    ``tmp_path``, with the benchmark's own ``setup_s`` and ``solve_s``
    readers; the benchmark's end-to-end metrics as committed."""
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "drivers"):
        (base / d).mkdir(parents=True)
    (base / "drivers" / "stub.py").write_text(STUB_DRIVER)
    (base / "configs" / "stub.json").write_text(
        json.dumps({"kind": "stub", "n": 8, "limits": {"steps_gap": 0}}))
    (base / "traffic" / "steps.json").write_text(json.dumps({"sweeps": 3}))
    for metric in ("setup_s", "solve_s"):
        shutil.copy(harness.BENCH / "metrics" / f"{metric}.py", base / "metrics")
    (base / "metrics" / "step_idle_ms.py").write_text(STUB_READER)
    cell = {"name": "stub.steps", "config": "stub", "traffic": "steps", "chips": 1,
            "why": "a test"}
    bench = dict(BENCH, workloads=[cell], per_layer=[
        {"name": "step_idle_ms", "unit": "ms", "workloads": ["stub.steps"]}])
    return bench, cell, base


def _run(stub_cell, trace):
    bench, cell, base = stub_cell
    return harness.run_cell(bench, cell, seed=2**31 + 99, seconds=0.2, trace=trace,
                            t_start=time.perf_counter(), peak=PEAK, base=base,
                            log=lambda s: None)


def test_new_kind_reports_setup_and_solve_time(stub_cell):
    res = _run(stub_cell, trace=False)
    assert res["correct"] and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert 0 < res["metrics"]["solve_s"]["value"] < 0.2


def test_new_kind_names_its_idle_gaps_by_its_own_spans(stub_cell, monkeypatch):
    """A traced run of the stub, its trace swapped for a TPU recording of
    the same spans (the CPU has no device plane): the gaps are named by
    the spans the stub declares, which the harness's own do not name."""
    monkeypatch.setattr(harness.trace_mod, "find_xplane", lambda log_dir: str(SMALL))
    res = _run(stub_cell, trace=True)
    assert res["correct"]
    gaps = [n for n, _ in res["breakdown"]["idle_gaps"]]
    assert gaps and set(gaps) <= {"step", "generator_wait"}, gaps
    assert res["metrics"]["step_idle_ms"]["value"] > 0
    alone = harness.read_program(str(SMALL), harness.SPANS)
    assert {n for n, _ in alone.idle_gaps} == {"none"}


def test_driver_is_loaded_from_its_base(stub_cell):
    _, _, base = stub_cell
    assert harness.load_driver("stub", base).SPANS == ("step", "generator_wait")
    assert harness.spans_of(harness.load_driver("stub", base)) == (
        "window", "step", "generator_wait")
    with pytest.raises(FileNotFoundError, match="driver for kind 'nope'"):
        harness.load_driver("nope", base)


def test_solve_s_applies_to_every_cell():
    assert {m["name"] for m in harness.metrics_for(BENCH, "any.new_cell", False)} == {
        "setup_s", "solve_s"}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                                    if m["name"] != "setup_s"])
def test_reader_finds_nothing_in_an_empty_window(metric):
    """Each reader, on a window that delivered nothing and with a
    configuration that names no kind, returns nothing, never 0."""
    run = SimpleNamespace(config={}, peak=PEAK, setup_s=1.0, window_s=1.0, units=[],
                          counters={}, trace=None, program=None)
    assert harness.reader(metric)(run) is None


@pytest.mark.parametrize("metric", ["sweeps_per_solve", "host_syncs_per_sweep",
                                    "mttkrp_roofline", "update_ms_per_sweep",
                                    "sync_idle_ms_per_sweep"])
def test_reader_needs_the_sweeps_of_every_unit(metric):
    units = [{"done": 0.5, "sweeps": 4, "state": SimpleNamespace(host_syncs=8)},
             {"done": 1.0, "state": SimpleNamespace(host_syncs=8)}]
    program = SimpleNamespace(busy_s_by_scope={"mttkrp.node1": 0.1, "fit": 0.01},
                              idle_s_by_span={"cp_als.wait": 0.01})
    run = SimpleNamespace(config={"shape": [4, 4], "rank": 2, "dtype": "float32"}, peak=PEAK,
                          units=units, program=program)
    assert harness.reader(metric)(run) is None
    units[1]["sweeps"] = 4
    assert harness.reader(metric)(run) > 0
