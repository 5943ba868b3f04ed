"""The harness as data, its walk of every cell at a tiny size on the CPU,
its refusal of the CPU, and the comparison that decides ``correct``: the
control and the faults of the timed path come out not correct."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 4242
TINY_SHAPE = [12, 5, 8, 8]


def tiny(cell_name: str):
    """The cell's own configuration and mix, cut to a size the CPU runs in
    a second: every other setting, the limits among them, as committed."""
    cell = harness.cell_of(BENCH, cell_name)
    cfg = harness.load_part("configs", cell["config"])
    mix = harness.load_part("traffic", cell["traffic"])
    cfg.update(shape=TINY_SHAPE, planted_rank=4, rank=4)
    return cell, cfg, mix


def run_tiny(cell_name, trace=False, seconds=0.3, **kw):
    cell, cfg, mix = tiny(cell_name)
    return harness.run_cell(BENCH, cell, seed=SEED, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), peak=PEAK, config=cfg, mix=mix,
                            log=lambda s: None, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_walk_of_each_cell(cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in harness.metrics_for(BENCH, cell, False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_walk_traced(cell):
    res = run_tiny(cell, trace=True)
    assert res["correct"], res["checks"]
    # the CPU has no device plane: the device-trace readers find nothing
    want = {m["name"] for m in harness.metrics_for(BENCH, cell, True)
            if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_throwaway_cell_from_files_alone(tmp_path):
    """A cell added as files under the benchmark's directories and an
    entry in BENCHMARK.json is found by name; no harness code changes."""
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "drivers"):
        (base / d).mkdir(parents=True)
    shutil.copy(harness.BENCH / "drivers" / "solve.py", base / "drivers")
    _, cfg, _ = tiny("fmri4.solve")
    (base / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (base / "traffic" / "once_more.json").write_text(json.dumps({"loop": "repeat"}))
    shutil.copy(harness.BENCH / "metrics" / "setup_s.py", base / "metrics")
    (base / "metrics" / "solves_done.py").write_text(
        "def read(run):\n    return float(len(run.units))\n")
    cell = {"name": "throwaway.once_more", "config": "throwaway", "traffic": "once_more",
            "chips": 1, "why": "a test"}
    bench = {"workloads": [cell], "per_layer": [], "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "solves_done", "unit": "solves", "workloads": ["throwaway.once_more"]},
        {"name": "not_here", "unit": "s", "workloads": ["other.cell"]},
    ]}
    assert harness.cell_of(bench, "throwaway.once_more") is cell
    res = harness.run_cell(bench, cell, seed=7, seconds=0.2, trace=False,
                           t_start=time.perf_counter(), peak=PEAK, base=base,
                           log=lambda s: None)
    assert res["correct"]
    assert set(res["metrics"]) == {"setup_s", "solves_done"}
    assert res["metrics"]["solves_done"]["value"] == res["attempted"]


def test_unknown_cell_and_metric_are_errors(tmp_path):
    with pytest.raises(KeyError, match="no workload"):
        harness.cell_of(BENCH, "no.such")
    with pytest.raises(FileNotFoundError):
        harness.reader("no_such_metric", tmp_path)


def test_measurement_path_refuses_the_cpu(capsys):
    args = type("A", (), {"workload": CELLS[0], "seed": 1, "seconds": 1.0, "trace": 0})()
    assert harness.main(args, time.perf_counter()) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refusing to fall back" in out.err


def test_run_py_exits_nonzero_with_no_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    assert out.stdout == ""


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_apart_from_the_program(cell):
    """The control, the reference at the precision below the
    configuration's put in the program's place, goes through the run and
    its judgment as ``bench/calibrate.py --control`` drives it, and reads a
    model distance to the reference several times the program's.  At this
    size neither comes near the cell's limit, which is set from readings at
    the cell's own size on the chip (PERF.md); there the control fails it."""
    readings = {}
    for control in (False, True):
        res = run_tiny(cell, control=control)
        assert res["failed"] == 0
        readings[control] = res["checks"]["model_diff"]["value"]
    assert readings[True] > 5 * readings[False], readings


def test_judge_fails_any_number_past_its_limit():
    limits = {"model_diff": 1e-2, "sweeps_gap": 1}
    ok = {"model_diff": 9e-4, "sweeps_gap": 0}
    assert harness.judge(ok, 0, limits)[1]
    assert not harness.judge(dict(ok, model_diff=0.2), 0, limits)[1]
    assert not harness.judge(dict(ok, sweeps_gap=2), 0, limits)[1]
    checks, correct = harness.judge(ok, 1, limits)
    assert not correct and checks["failed"] == {"value": 1, "limit": 0}
    assert list(checks) == ["model_diff", "sweeps_gap", "failed"]


def _unchanged_state(monkeypatch):
    """Every sweep returns its state unchanged (its fit zero)."""
    import dataclasses

    import jax.numpy as jnp

    import repro.plan.sweep as sweep

    def als_sweep(problem, plan, executor, state):
        return dataclasses.replace(state, fit=jnp.zeros_like(state.norm_x))

    monkeypatch.setattr(sweep, "als_sweep", als_sweep)


def _altered_answer(monkeypatch):
    """One entry of each answer's first factor is altered where the solver
    produces it."""
    import repro.plan

    real = repro.plan.cp_als

    def cp_als(*a, **k):
        st = real(*a, **k)
        st.factors[0] = st.factors[0].at[0, 0].add(1.0)
        return st

    monkeypatch.setattr(repro.plan, "cp_als", cp_als)


FAULTS = {"unchanged_state": _unchanged_state, "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny(cell, seconds=0.2)
    assert not res["correct"], res["checks"]
