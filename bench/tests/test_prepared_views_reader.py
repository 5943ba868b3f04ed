"""The prepared_views_per_solve reader: the views cp_als counts on
CPState.prepared_views, as the benchmark reads them from the window's
solves."""

import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402

SOLVE = {"kind": "solve"}


def _run(states, config=SOLVE):
    return SimpleNamespace(config=config, units=[{"sweeps": 3, "state": s} for s in states])


def test_prepared_views_reader_is_the_per_solve_mean():
    read = harness.reader("prepared_views_per_solve")
    assert read(_run([SimpleNamespace(prepared_views=1)] * 3)) == 1.0
    assert read(_run([SimpleNamespace(prepared_views=v) for v in (1, 2, 2, 1)])) == 1.5
    assert read(_run([SimpleNamespace(prepared_views=0)])) == 0.0


def test_prepared_views_reader_is_none_without_the_counter():
    read = harness.reader("prepared_views_per_solve")
    assert read(_run([SimpleNamespace()])) is None  # a program that does not count
    assert read(_run([SimpleNamespace(prepared_views=1), SimpleNamespace()])) is None
    assert read(_run([])) is None
    # read from what the window delivered, whichever driver delivered it
    assert read(_run([SimpleNamespace(prepared_views=1)], {"kind": "other"})) == 1.0


def test_prepared_views_reader_reads_what_cp_als_counts():
    """A dimension-tree solve on the CPU counts one view a solve."""
    import jax

    from repro.core import random_factors, random_tensor
    from repro.plan import Problem, cp_als, plan_sweep

    shape = (6, 5, 4, 3)
    x = random_tensor(jax.random.PRNGKey(0), shape)
    plan = plan_sweep(Problem.from_tensor(x, 2), strategy="dimtree")
    init = random_factors(jax.random.PRNGKey(1), shape, 2)
    states = [cp_als(x, plan, n_iters=2, init_factors=list(init)) for _ in range(2)]
    assert harness.reader("prepared_views_per_solve")(_run(states)) == 1.0
