"""The ``fleet.backlog`` cell at a tiny size on the CPU: it runs through the
harness on CPService's normal path, its check tells a served answer from
the wrong subject's or from the start it was given, and its readers read
what a service window delivers and nothing else."""

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, program_trace, roofline  # noqa: E402

BENCH = harness.load_benchmark()
CELL = harness.cell_of(BENCH, "fleet.backlog")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**33 + 16
NEW = ["idle_share.serve", "serve_idle_ms_per_batch", "host_reads_per_batch",
       "mttkrp_roofline.serve", "update_ms_per_batch"]


def tiny():
    """The committed configuration and mix, cut to six subjects of 10 x 6 x 6
    at rank 3 and eight clients (two batches of four)."""
    cfg = harness.load_part("configs", CELL["config"])
    mix = harness.load_part("traffic", CELL["traffic"])
    cfg.update(shape=[10, 6, 6, 6], planted_rank=3, rank=3, batch_size=4)
    mix.update(clients=8)
    return cfg, mix


def run_tiny(trace=False, seconds=0.3, **kw):
    cfg, mix = tiny()
    lines = []
    res = harness.run_cell(BENCH, CELL, seed=SEED, seconds=seconds, trace=trace,
                           t_start=time.perf_counter(), peak=PEAK, config=cfg, mix=mix,
                           log=lines.append, **kw)
    return res, lines


def test_fleet_config_is_the_study_per_subject():
    cfg = harness.load_part("configs", "fleet")
    assert cfg["kind"] == "service"
    subject = [d for n, d in enumerate(cfg["shape"]) if n != cfg["subject_mode"]]
    assert subject == [225, 200, 200] and cfg["shape"][cfg["subject_mode"]] == 59
    assert roofline.sweep_least_bytes(cfg["shape"]) == 59 * 225 * 200 * 200 * 4
    assert (cfg["rank"], cfg["batch_size"], cfg["n_iters"], cfg["tol"]) == (25, 8, 20, 0.0)
    assert cfg["sweeps_per_sync"] is None and cfg["strategy"] == "autotune"


def test_fleet_backlog_runs_through_the_service():
    res, lines = run_tiny()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert 0 < res["metrics"]["solve_s"]["value"] < 0.3
    assert any(s.startswith("plan: schedule=binary@1 executor=local "
                            "leaves=['1step', 'partial-ttv', 'partial-ttv'] batch=4")
               for s in lines), lines
    assert any("warm_plan_hits=0" in s for s in lines)
    assert any("compiles_in_window=0" in s for s in lines), lines


def test_fleet_backlog_reads_only_its_own_layer_metrics():
    """A traced run reads the five service readers and none of fmri4's;
    the CPU has no device plane, so only the counter's is read, and a full
    batch reads one fit a request."""
    assert [m["name"] for m in harness.metrics_for(BENCH, CELL["name"], True)] == NEW
    res, _ = run_tiny(trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {"host_reads_per_batch": {"value": 4.0, "unit": "reads"}}


def _swap_first_two_slots(monkeypatch):
    """The batch's first two answers come back in each other's slots."""
    import numpy as np

    import repro.plan

    real = repro.plan.cp_als

    def cp_als(*a, **k):
        st = real(*a, **k)
        order = np.array([1, 0] + list(range(2, st.weights.shape[0])))
        st.factors = [u[order] for u in st.factors]
        st.weights = st.weights[order]
        return st

    monkeypatch.setattr(repro.plan, "cp_als", cp_als)


def _return_the_start(monkeypatch):
    """The service hands back the start it was given, unit weights."""
    import jax.numpy as jnp

    import repro.plan

    real = repro.plan.cp_als

    def cp_als(x, plan, *, init_factors, **k):
        st = real(x, plan, init_factors=init_factors, **k)
        st.factors = [jnp.array(u) for u in init_factors]
        st.weights = jnp.ones_like(st.weights)
        return st

    monkeypatch.setattr(repro.plan, "cp_als", cp_als)


@pytest.mark.parametrize("fault", [_swap_first_two_slots, _return_the_start])
def test_fault_in_the_service_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res, _ = run_tiny(seconds=0.2)
    assert not res["correct"], res["checks"]
    assert res["checks"]["model_diff"]["value"] > 10 * res["checks"]["model_diff"]["limit"]


def _program(busy=None, idle=None):
    return SimpleNamespace(busy_s_by_scope=busy or {}, idle_s_by_span=idle or {})


def _run(**kw):
    base = dict(config={"shape": [10, 3, 4, 5], "subject_mode": 1, "rank": 2,
                        "dtype": "float32"},
                peak=PEAK, units=[{"done": 1.0, "sweeps": 20}] * 8,
                counters={"batches": 2, "host_reads": 8}, trace=None, program=None)
    base.update(kw)
    return SimpleNamespace(**base)


MISSING = {
    "idle_share.serve": [dict(trace=None), dict(trace=SimpleNamespace(devices=0))],
    "serve_idle_ms_per_batch": [
        dict(program=None), dict(counters={}, program=_program(idle={"serve.pack": 0.1})),
        dict(program=_program(idle={"cp_als.wait": 0.1}))],
    "host_reads_per_batch": [dict(counters={"batches": 2}), dict(counters={"host_reads": 8}),
                             dict(counters={"host_reads": 0, "batches": 0})],
    "mttkrp_roofline.serve": [
        dict(program=None), dict(units=[], program=_program(busy={"mttkrp.node1": 1.0})),
        dict(config={"shape": [10, 4, 5], "rank": 2, "dtype": "float32"},
             program=_program(busy={"mttkrp.node1": 1.0})),
        dict(program=_program(busy={"fit": 1.0}))],
    "update_ms_per_batch": [
        dict(program=None), dict(counters={}, program=_program(busy={"fit": 0.1})),
        dict(program=_program(busy={"mttkrp.node1": 0.1}))],
}


@pytest.mark.parametrize("metric,case", [(m, i) for m in NEW for i in range(len(MISSING[m]))])
def test_reader_is_none_where_what_it_needs_is_missing(metric, case):
    assert harness.reader(metric)(_run(**MISSING[metric][case])) is None


def test_trace_readers_equal_hand_computed_values():
    """On a synthetic split of a traced window: 2 batches, 8 requests of
    20 sweeps on subjects of 10 x 4 x 5 at fp32."""
    program = _program(
        busy={"mttkrp.node0": 0.004, "mttkrp.node1": 0.012, "update.mode0": 0.001,
              "update.mode2": 0.002, "fit": 0.0005, "prepare": 0.3, "none": 0.2},
        idle={"serve.take": 0.001, "serve.pack": 0.002, "serve.unpack": 0.004,
              "serve.dispatch": 0.5, "cp_als.check": 0.25})
    run = _run(program=program, trace=SimpleNamespace(devices=1, idle_share=0.25))
    read = {m: harness.reader(m)(run) for m in NEW}
    least = 10 * 4 * 5 * 4 / 819e9  # one read of one subject's tensor
    assert read["idle_share.serve"] == pytest.approx(25.0)
    assert read["serve_idle_ms_per_batch"] == pytest.approx(1e3 * 0.007 / 2)
    assert read["host_reads_per_batch"] == 4.0
    assert read["mttkrp_roofline.serve"] == pytest.approx(100 * least / (0.016 / 160))
    assert read["mttkrp_roofline.serve"] == pytest.approx(
        program_trace.mttkrp_roofline(program, 160, least))
    assert read["update_ms_per_batch"] == pytest.approx(1e3 * 0.0035 / 2)
