"""Driver for a configuration of kind ``service``: a fleet of same-shaped
tensors served one request at a time through ``repro.serve.CPService``
(``submit`` -> ``step`` -> ``Problem(batch=B) -> plan_sweep -> batched
cp_als``), under a closed loop of clients.

The fleet is the configuration's study tensor (as a ``solve``
configuration plants it) split along its ``subject_mode``: one resident
tensor a subject.  Each client keeps one request outstanding and, when it
comes back, asks again at once for a subject drawn uniformly from the
fleet.  Every request pins its subject's nvecs start (``bench/data.py``),
so each answer is compared with the plain reference run on that subject's
tensor from the same start."""

from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, program_trace, reference

SPANS = ("serve.take", "serve.pack", "serve.dispatch", "serve.unpack") + program_trace.PROGRAM_SPANS
# the service's counters a window reads, as deltas over the window
COUNTERS = ("batches", "completed", "padded_slots", "host_reads", "rejected")


@dataclass
class State:
    cfg: dict
    mix: dict
    seed: int
    subjects: list  # one resident tensor a subject
    starts: list  # each subject's nvecs start
    service: object = None
    signature: str = ""
    served: dict = field(default_factory=dict)  # rid -> subject

    @property
    def rank(self) -> int:
        return int(self.cfg["rank"])

    def submit(self, subject: int):
        fut = self.service.submit(self.subjects[subject], self.rank,
                                  init_factors=self.starts[subject])
        self.served[fut.rid] = subject
        return fut


@partial(jax.jit, static_argnames=("mode",))
def _unstack(x, *, mode):
    return tuple(jnp.moveaxis(x, mode, 0))


def make_fleet(cfg: dict, seed: int) -> list[jax.Array]:
    """The configuration's study (from its ``data_seed``, every mode's
    indices permuted by ``seed``) split into one tensor a subject; the
    study tensor itself is freed."""
    shared = {int(k): int(v) for k, v in cfg.get("shared_modes", {}).items()}
    x = data.planted(cfg["data_seed"], cfg["shape"], cfg["planted_rank"], cfg["noise"],
                     shared, perm_seed=seed)
    subjects = list(_unstack(x, mode=int(cfg["subject_mode"])))
    x.delete()
    return jax.block_until_ready(subjects)


def setup(cfg: dict, mix: dict, seed: int, span) -> State:
    """The fleet and each subject's start from the seed, one service with
    an empty tuning cache (no cache on the machine can change its plan),
    and one full batch served to compile every program the window runs."""
    from repro.plan.autotune import TuningCache
    from repro.serve import CPService

    if mix["loop"] != "closed":
        raise ValueError(f"a service configuration runs the 'closed' loop, not {mix['loop']!r}")
    subjects = make_fleet(cfg, seed)
    starts = [data.nvecs(x, cfg["rank"]) for x in subjects]
    state = State(cfg=cfg, mix=mix, seed=seed, subjects=subjects, starts=starts)
    state.service = CPService(
        batch_size=int(cfg["batch_size"]), n_iters=int(cfg["n_iters"]), tol=float(cfg["tol"]),
        sweeps_per_sync=cfg["sweeps_per_sync"], strategy=cfg["strategy"],
        tuning_cache=TuningCache(),
    )
    state.signature = state.service.signature_of(subjects[0], state.rank)
    warm = [state.submit(s % len(subjects)) for s in range(state.service.batch_size)]
    state.service.flush()
    jax.block_until_ready([u for f in warm for u in f.result().factors])
    state.served.clear()
    return state


def window(state: State, seconds: float, span) -> dict:
    """The closed loop: every client submits, then the service steps, and
    each client whose answer came back asks again at once, until the first
    step that ends past ``seconds``.  Each answer is one unit; requests
    still queued at the end are neither attempted nor failed."""
    svc = state.service
    rng = np.random.default_rng(state.seed)

    def draw() -> int:
        return int(rng.integers(len(state.subjects)))

    before = svc.stats()
    units = []
    t0 = time.perf_counter()
    for _ in range(int(state.mix["clients"])):
        state.submit(draw())
    while True:
        done = svc.step()
        t = time.perf_counter() - t0
        for fut in done:
            res = fut.result()
            units.append({
                "done": t, "sweeps": res.sweeps, "rid": res.rid,
                "subject": state.served.pop(res.rid), "factors": res.factors,
                "weights": res.weights, "fit": res.fit, "latency_s": res.latency_s,
            })
            state.submit(draw())
        if t >= seconds:
            break
    after = svc.stats()
    counters = {k: after[k] - before[k] for k in COUNTERS if k in after}
    return {"window_s": t, "units": units, "attempted": len(units) + counters.get("rejected", 0),
            "counters": counters}


def describe(state: State) -> list[str]:
    svc = state.service
    plan_of = getattr(svc, "plan_of", None)  # a service without it names no plan
    plan = plan_of(state.signature) if plan_of else None
    lines = [] if plan is None else [
        f"plan: schedule={plan.resolved_schedule.name} executor={plan.executor} "
        f"leaves={[m.algorithm for m in plan.modes]} batch={plan.problem.batch}"
    ]
    stats = svc.stats()
    return lines + [
        f"service: subjects={len(state.subjects)} shape={list(state.subjects[0].shape)} "
        f"rank={state.rank} batch_size={svc.batch_size} n_iters={svc.n_iters} tol={svc.tol} "
        f"sweeps_per_sync={svc.sweeps_per_sync} strategy={svc.strategy} "
        f"warm_plan_hits={stats['warm_plan_hits']} clients={state.mix['clients']}"
    ]


def latency_line(units) -> str:
    """The requests' submit-to-answer seconds, p50 and p95 (not a metric)."""
    lat = [u["latency_s"] for u in units]
    if not lat:
        return "latency_s: no requests"
    p50, p95 = np.percentile(lat, [50, 95])
    return f"latency_s: requests={len(lat)} p50={float(p50)!r} p95={float(p95)!r}"


def check(state: State, win: dict, control: bool = False) -> tuple[dict, int]:
    """Every answer of the window against the plain reference run on its
    own subject's tensor from the same start, once per subject served;
    ``(numbers, failed)``: the worst model distance, fit gap and sweep-count
    gap over the answers, and the answers that are not finite plus the
    requests the service rejected.  With ``control`` the answers compared
    are instead the control's, the reference at the precision below the
    configuration's, one a subject served."""
    print(latency_line(win["units"]), file=sys.stderr, flush=True)
    state.service = None
    n_iters, tol = int(state.cfg["n_iters"]), float(state.cfg["tol"])
    got = [(u["subject"], u["factors"], u["weights"], u["fit"], u["sweeps"]) for u in win["units"]]
    keep = {a[4] for a in got}
    refs = {s: reference.als(state.subjects[s], state.starts[s], n_iters=n_iters, tol=tol,
                             keep=keep)
            for s in sorted({a[0] for a in got})}
    if control:
        got = [(s, *reference.als(state.subjects[s], state.starts[s], n_iters=n_iters, tol=tol,
                                  precision="high")[0])
               for s in refs]
    out = {"fit_gap": 0.0, "model_diff": 0.0, "sweeps_gap": 0}
    failed = int(win["counters"].get("rejected", 0))
    for s, factors, weights, fit, sweeps in got:
        factors, weights = [np.asarray(u) for u in factors], np.asarray(weights)
        final, snaps = refs[s]
        finite = math.isfinite(fit) and all(np.isfinite(a).all() for a in (weights, *factors))
        if sweeps not in snaps or not finite:
            failed += 1
            continue
        rf, rw, rfit, _ = snaps[sweeps]
        out["fit_gap"] = max(out["fit_gap"], abs(fit - rfit))
        out["model_diff"] = max(out["model_diff"], reference.model_diff(weights, factors, rw, rf))
        out["sweeps_gap"] = max(out["sweeps_gap"], abs(sweeps - final[3]))
    return out, failed
