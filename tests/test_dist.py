"""Multi-device distributed tests (8 fake host devices via subprocess --
XLA device count is locked at first init, so each case gets its own process)."""

import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")


def _run(case: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, WORKER, case],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"{case} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_dist_mttkrp_all_modes():
    out = _run("dist_mttkrp")
    assert "dist_mttkrp OK" in out


def test_matrix_free_sharded_matches_einsum():
    out = _run("matrix_free_sharded")
    assert "matrix_free_sharded OK" in out


def test_dist_cpals_recovers_planted():
    out = _run("dist_cpals")
    assert "dist_cpals OK" in out


def test_dist_dimtree_matches_standard_als():
    out = _run("dist_dimtree")
    assert "dist_dimtree OK" in out


def test_overlapping_executor_matches_sharded():
    out = _run("overlap_mttkrp")
    assert "overlap_mttkrp OK" in out


def test_schedule_overlapped_dimtree_bitwise_matches_sharded():
    out = _run("schedule_overlap")
    assert "schedule_overlap OK" in out


def test_pp_sharded_matches_local():
    out = _run("pp_sharded")
    assert "pp_sharded OK" in out


def test_hierarchical_psum_matches_flat():
    out = _run("hierarchical_psum")
    assert "hierarchical_psum OK" in out


def test_elastic_restore_across_mesh_shapes():
    out = _run("elastic_restore")
    assert "elastic_restore OK" in out
