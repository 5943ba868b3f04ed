"""Driver for a configuration of kind ``solve``: one tensor, solved again
and again through ``Problem.from_tensor -> plan_sweep -> cp_als``."""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import jax

from bench import data, program_trace, reference

SPANS = ("solve", "plan") + program_trace.PROGRAM_SPANS


@dataclass
class State:
    cfg: dict
    x: jax.Array
    init: list
    plan: object = None
    dispatch: dict = field(default_factory=dict)

    def solve(self):
        from repro.plan import cp_als

        return cp_als(
            self.x, self.plan,
            n_iters=int(self.cfg["n_iters"]), tol=float(self.cfg["tol"]),
            sweeps_per_sync=int(self.cfg["sweeps_per_sync"]),
            init_factors=self.init,
            dispatch_cache=self.dispatch, dispatch_key=0,
        )


def make_data(cfg: dict, seed: int) -> jax.Array:
    """The configuration's tensor (from its ``data_seed``) with the indices
    of every mode permuted by ``seed``: each seed poses the same problem,
    so a solve does the same work, in another order."""
    shared = {int(k): int(v) for k, v in cfg.get("shared_modes", {}).items()}
    return data.planted(cfg["data_seed"], cfg["shape"], cfg["planted_rank"], cfg["noise"],
                        shared, perm_seed=seed)


def setup(cfg: dict, mix: dict, seed: int, span) -> State:
    """The tensor and its start from the seed, the plan made once, and one
    solve to compile every program the window runs."""
    from repro.plan import Problem, plan_sweep

    if mix["loop"] != "repeat":
        raise ValueError(f"a solve configuration runs the 'repeat' loop, not {mix['loop']!r}")
    x = make_data(cfg, seed)
    state = State(cfg=cfg, x=x, init=data.nvecs(x, cfg["rank"]))
    with span("plan"):
        state.plan = plan_sweep(Problem.from_tensor(x, int(cfg["rank"])), strategy=cfg["strategy"])
    jax.block_until_ready(state.solve().factors)
    return state


def window(state: State, seconds: float, span) -> dict:
    """Solves back to back until ``seconds`` have passed; the window ends
    when the last one is done."""
    solves = []
    t0 = time.perf_counter()
    while True:
        with span("solve"):
            st = state.solve()
        t = time.perf_counter()
        solves.append({"state": st, "done": t - t0, "sweeps": int(st.it)})
        if t - t0 >= seconds:
            break
    return {"window_s": t - t0, "units": solves, "attempted": len(solves), "counters": {}}


def describe(state: State) -> list[str]:
    plan = state.plan
    return [
        f"plan: schedule={plan.resolved_schedule.name} executor={plan.executor} "
        f"leaves={[m.algorithm for m in plan.modes]}"
    ]


def answers(state: State, win: dict):
    """The window's answers, as ``(factors, weights, fit, sweeps)``."""
    return [
        (list(u["state"].factors), u["state"].weights, float(u["state"].fit), u["sweeps"])
        for u in win["units"]
    ]


def compare(state: State, got) -> tuple[dict, int]:
    """The numbers compared, worst over the answers ``got``: the fit gap
    and the model's distance to the reference after as many sweeps, and the
    gap between the sweep counts at which the two stop.  Also returns how
    many answers are not finite or ran past ``n_iters``."""
    cfg = state.cfg
    final, snaps = reference.als(
        state.x, state.init, n_iters=int(cfg["n_iters"]), tol=float(cfg["tol"]),
        keep={a[3] for a in got},
    )
    out = {"fit_gap": 0.0, "model_diff": 0.0, "sweeps_gap": 0}
    failed = 0
    for factors, weights, fit, sweeps in got:
        if sweeps not in snaps or not math.isfinite(fit):
            failed += 1
            continue
        rf, rw, rfit, _ = snaps[sweeps]
        out["fit_gap"] = max(out["fit_gap"], abs(fit - rfit))
        out["model_diff"] = max(out["model_diff"], reference.model_diff(weights, factors, rw, rf))
        out["sweeps_gap"] = max(out["sweeps_gap"], abs(sweeps - final[3]))
    return out, failed


def check(state: State, win: dict, control: bool = False) -> tuple[dict, int]:
    """The window's answers against the reference, once the program's
    state is freed; ``(numbers, failed)``.  With ``control`` the answer
    compared is instead the control's: the reference at the precision
    below the configuration's, from the same start under the same rule."""
    got = answers(state, win)
    for u in win["units"]:
        del u["state"]
    state.dispatch.clear()
    state.plan = None
    if control:
        cfg = state.cfg
        final, _ = reference.als(
            state.x, state.init, n_iters=int(cfg["n_iters"]), tol=float(cfg["tol"]),
            precision="high",
        )
        got = [final]
    return compare(state, got)
