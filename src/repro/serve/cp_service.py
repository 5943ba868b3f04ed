"""Decomposition-as-a-service: a CP serving engine over the batched plan stack.

The production workload the paper's Sec. 6 fMRI scenario implies -- a fleet
of *small, same-shaped* tensors (one subject = one tensor), not one huge
tensor -- is served here the way the LM engine serves prompts: clients
:meth:`CPService.submit` a tensor and get a :class:`CPFuture` back, a
scheduler buckets pending requests by *signature* (shape, rank, dtype,
device count, update options -- :meth:`repro.plan.problem.Problem.signature`
plus the per-request sweep budget), packs each bucket into fixed-size
batches, and executes them through the existing front door::

    Problem(batch=B) -> plan_sweep -> batched cp_als   (ONE compiled dispatch)

Compiled shapes stay static: a partial batch is padded by *cycling the real
requests into the dummy slots*.  Batch entries never interact inside the
sweep algebra (every contraction and solve is batched per-slice), so the
masked dummies provably cannot perturb the real problems' iterates -- and
because each dummy duplicates a real problem, even the shared convergence
stop (batch-max fit delta) behaves exactly as if the padding were absent.

One compile per signature: the service keys a per-signature dispatch cache
into ``cp_als(dispatch_cache=...)``, so the jitted sweep-chunk is built once
and every later batch of that signature dispatches compile-free.  The
persistent :class:`repro.plan.autotune.TuningCache` doubles as the warm-plan
store under the same signature: with ``strategy="autotune"`` (the default) a
signature tuned by :func:`repro.plan.autotune.tune` plans straight from its
hardware measurements (counted in ``stats()["warm_plan_hits"]``); untuned
signatures degrade cleanly to the analytic model.

The queue is the bounded FIFO+priority :class:`repro.serve.queue.RequestQueue`
(submission raises :class:`repro.serve.queue.QueueFull` at capacity --
client-visible backpressure), and ``stats()`` exposes the serving counters
(queue depth, batch occupancy, compiles, warm-plan hits, host reads,
problems/sec) the throughput benchmark reports.

Observability costs nothing unless a profiler trace is being taken: each
:meth:`CPService.step` opens the ``jax.profiler.TraceAnnotation`` spans
``serve.take`` (bucket choice, dequeue, the signature's plan lookup),
``serve.pack`` (the starts and the stacked batch), ``serve.dispatch`` (the
``cp_als`` call, with its own ``cp_als.*`` spans inside) and
``serve.unpack`` (each slot's answer sliced out and its fit read), and
``stats()["host_reads"]`` counts the device values it reads into Python.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp

import repro.plan
from repro.core.tensor_ops import random_factors
from repro.plan import Problem, make_executor, plan_sweep
from repro.plan.autotune import lookup_measurements, problem_key

from .queue import QueueFull, RequestQueue

Array = jax.Array


@dataclass(frozen=True)
class CPResult:
    """One finished decomposition, as the client reads it back.

    ``factors`` are the per-mode ``(I_k, C)`` factor matrices and
    ``weights`` the ``(C,)`` lambdas of this request's own problem (the
    batch axis is already stripped); ``fit`` is the request's final fit,
    ``sweeps`` the executed sweep count of its dispatch, ``signature`` the
    batch bucket it was served under, and ``latency_s`` the submit-to-result
    wall time (queue wait included).
    """

    rid: int
    factors: list[Array]
    weights: Array
    fit: float
    sweeps: int
    signature: str
    latency_s: float


class CPFuture:
    """Handle returned by :meth:`CPService.submit`; resolves on dispatch.

    The service is synchronous (results land during ``step``/``flush``), so
    ``done()`` flips exactly when the owning batch executed.
    """

    def __init__(self, rid: int, signature: str):
        """Internal: built by the service with the queue-assigned rid."""
        self.rid = rid
        self.signature = signature
        self._result: CPResult | None = None

    def done(self) -> bool:
        """True once the owning batch has executed."""
        return self._result is not None

    def result(self) -> CPResult:
        """The resolved :class:`CPResult`; raises if the batch has not run
        yet (call :meth:`CPService.step` or :meth:`CPService.flush`)."""
        if self._result is None:
            raise RuntimeError(
                f"request {self.rid} is still pending -- step()/flush() the service"
            )
        return self._result


@dataclass
class _CPRequest:
    """Queue payload: one tensor + its decomposition options."""

    tensor: Array
    rank: int
    n_iters: int
    tol: float
    pp_tol: float
    init_factors: list[Array] | None
    seed: int
    future: CPFuture


@dataclass
class _SignatureState:
    """Per-signature compiled state: plan once, dispatch compile-free after."""

    problem: Problem
    plan: Any
    executor: Any  # None = cp_als's LocalExecutor default
    dispatch: dict = field(default_factory=dict)
    warm: bool = False


class CPService:
    """CP decomposition serving engine: submit tensors, stream results back.

    ``batch_size`` fixes the compiled batch extent ``B`` of every dispatch
    (one compile per signature; partial batches are padded).  ``n_iters`` /
    ``tol`` are the default per-request sweep budget and convergence
    tolerance (``tol=0.0`` runs exactly ``n_iters`` sweeps -- the
    deterministic serving default; a positive ``tol`` stops a batch when
    every problem's fit delta clears it, the batched driver's shared stop).
    ``sweeps_per_sync`` sets the driver's sweeps-per-dispatch chunk
    (``None`` = the whole request budget in ONE device dispatch, the
    sync-free serving fast path).  ``strategy`` + ``tuning_cache`` feed
    :func:`repro.plan.plan_sweep` -- the default ``"autotune"`` makes the
    persistent tuning cache a warm-plan store keyed by the same signature as
    the batch buckets.  ``mesh`` shards the batch axis of every dispatch
    over all its axes (batch-parallel: zero collective traffic;
    ``batch_size`` must be divisible by the mesh's device count).
    ``max_pending`` bounds the queue; a full queue rejects submission with
    :class:`repro.serve.queue.QueueFull`.  ``pp_tol > 0`` makes
    pairwise-perturbation sweeps the service default (overridable per
    request); PP requests bucket under their own signature, so exact and PP
    traffic never share a compiled dispatch.

    Each :meth:`step` opens the profiler spans ``serve.take``,
    ``serve.pack``, ``serve.dispatch`` and ``serve.unpack`` (see the module
    docstring), and :meth:`stats` counts ``host_reads``.
    """

    def __init__(
        self,
        *,
        batch_size: int = 8,
        max_pending: int | None = None,
        n_iters: int = 20,
        tol: float = 0.0,
        sweeps_per_sync: int | None = None,
        strategy: str = "autotune",
        tuning_cache=None,
        mesh=None,
        pp_tol: float = 0.0,
    ):
        """See the class docstring for the knobs; validation happens here."""
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.batch_size = int(batch_size)
        self.n_iters = int(n_iters)
        self.tol = float(tol)
        self.pp_tol = float(pp_tol)
        self.sweeps_per_sync = sweeps_per_sync
        self.strategy = strategy
        self.tuning_cache = tuning_cache
        self.mesh = mesh
        if mesh is not None:
            n_dev = math.prod(dict(mesh.shape).values())
            if self.batch_size % n_dev:
                raise ValueError(
                    f"batch_size {batch_size} not divisible by the mesh's "
                    f"{n_dev} devices (batch-parallel placement shards the "
                    "batch axis evenly)"
                )
        self._queue = RequestQueue(max_pending)
        self._states: dict[str, _SignatureState] = {}
        self._counters = {
            "submitted": 0,
            "completed": 0,
            "rejected": 0,
            "batches": 0,
            "compiles": 0,
            "warm_plan_hits": 0,
            "padded_slots": 0,
            "host_reads": 0,
        }
        self._execute_s = 0.0

    # ------------------------------------------------------------ submission
    def _problem_for(
        self, tensor: Array, rank: int, pp_tol: float | None = None
    ) -> Problem:
        """The batched Problem one dispatch of this tensor's bucket solves."""
        axis_sizes = dict(self.mesh.shape) if self.mesh is not None else {}
        batch_axes = (
            tuple(self.mesh.axis_names)
            if self.mesh is not None and self.batch_size > 1
            else ()
        )
        return Problem(
            shape=tuple(tensor.shape),
            rank=int(rank),
            dtype=tensor.dtype,
            batch=self.batch_size,
            batch_axes=batch_axes,
            axis_sizes=axis_sizes,
            pp_tol=self.pp_tol if pp_tol is None else float(pp_tol),
        )

    def signature_of(self, tensor: Array, rank: int, *, n_iters: int | None = None,
                     tol: float | None = None, pp_tol: float | None = None) -> str:
        """Batch-bucket signature of one request: the canonical
        :meth:`repro.plan.problem.Problem.signature` of the *batched*
        problem (shape, rank, dtype, device count, batch, PP tolerance --
        via :func:`repro.plan.autotune.problem_key`, so it shares the tuning
        cache's key space) extended with the update options (sweep budget,
        tolerance) that shape the compiled dispatch.  A ``pp_tol > 0``
        request buckets separately from the exact one for the same tensor
        (its compiled dispatch carries the PP cache through the scan)."""
        n_iters = self.n_iters if n_iters is None else int(n_iters)
        tol = self.tol if tol is None else float(tol)
        base = problem_key(self._problem_for(tensor, rank, pp_tol))
        return f"{base}|i{n_iters}|t{tol:g}"

    def submit(
        self,
        tensor: Array,
        rank: int,
        *,
        n_iters: int | None = None,
        tol: float | None = None,
        pp_tol: float | None = None,
        init_factors: Sequence[Array] | None = None,
        seed: int = 0,
        priority: int = 0,
    ) -> CPFuture:
        """Enqueue one tensor for rank-``rank`` CP decomposition.

        Returns a :class:`CPFuture` that resolves when the request's batch
        executes (during :meth:`step`/:meth:`flush`).  ``n_iters``/``tol``/
        ``pp_tol`` override the service defaults (they are part of the
        signature: requests only share a dispatch when their update options
        match -- a pairwise-perturbation request never shares a compiled
        dispatch with an exact one);
        ``init_factors`` pins the initial factors (per-mode ``(I_k, C)``,
        unbatched -- the service stacks them into the batch), otherwise they
        are drawn from ``seed``.  Higher ``priority`` serves first, FIFO
        within a priority.  Raises :class:`repro.serve.queue.QueueFull` when
        ``max_pending`` requests are already waiting.
        """
        tensor = jnp.asarray(tensor)
        rank = int(rank)
        if tensor.ndim < 2:
            raise ValueError(f"expected an order >= 2 tensor, got shape {tensor.shape}")
        if init_factors is not None:
            init_factors = [jnp.asarray(u) for u in init_factors]
            want = [(d, rank) for d in tensor.shape]
            got = [tuple(u.shape) for u in init_factors]
            if got != want:
                raise ValueError(f"init_factors shapes {got} != expected {want}")
        sig = self.signature_of(tensor, rank, n_iters=n_iters, tol=tol, pp_tol=pp_tol)
        payload = _CPRequest(
            tensor=tensor,
            rank=rank,
            n_iters=self.n_iters if n_iters is None else int(n_iters),
            tol=self.tol if tol is None else float(tol),
            pp_tol=self.pp_tol if pp_tol is None else float(pp_tol),
            init_factors=init_factors,
            seed=int(seed),
            future=CPFuture(-1, sig),
        )
        try:
            req = self._queue.submit(payload, key=sig, priority=priority)
        except QueueFull:
            self._counters["rejected"] += 1
            raise
        payload.future.rid = req.rid
        self._counters["submitted"] += 1
        return payload.future

    # ------------------------------------------------------------- execution
    def _state_for(self, sig: str, payload: _CPRequest) -> _SignatureState:
        """Memoized per-signature plan/executor (the warm-plan lookup)."""
        state = self._states.get(sig)
        if state is not None:
            return state
        problem = self._problem_for(payload.tensor, payload.rank, payload.pp_tol)
        warm = (
            self.strategy == "autotune"
            and lookup_measurements(problem, cache=self.tuning_cache) is not None
        )
        plan = plan_sweep(
            problem, strategy=self.strategy, tuning_cache=self.tuning_cache
        )
        executor = None
        if plan.executor != "local":
            executor = make_executor(
                plan.executor,
                self.mesh,
                plan.problem.mode_axes,
                batch_axes=plan.problem.batch_axes,
            )
        state = _SignatureState(
            problem=plan.problem, plan=plan, executor=executor, warm=warm
        )
        if warm:
            self._counters["warm_plan_hits"] += 1
        self._states[sig] = state
        return state

    def plan_of(self, signature: str):
        """The :class:`repro.plan.SweepPlan` the service dispatches for
        ``signature`` (see :meth:`signature_of`), or ``None`` before the
        first batch of that signature has run."""
        state = self._states.get(signature)
        return None if state is None else state.plan

    def _init_for(self, payload: _CPRequest) -> list[Array]:
        """One request's initial factors (pinned or drawn from its seed)."""
        if payload.init_factors is not None:
            return payload.init_factors
        return random_factors(
            jax.random.PRNGKey(payload.seed),
            payload.tensor.shape,
            payload.rank,
            payload.tensor.dtype,
        )

    def step(self) -> list[CPFuture]:
        """Execute ONE batched dispatch over the most urgent bucket.

        Takes up to ``batch_size`` same-signature requests (priority order,
        FIFO within), pads the batch by cycling the real requests into the
        empty slots, runs the bucket's compiled ``cp_als`` dispatch, and
        resolves exactly the real requests' futures -- returned in slot
        order.  Returns ``[]`` when nothing is pending.
        """
        with jax.profiler.TraceAnnotation("serve.take"):
            sig = self._queue.next_key()
            if sig is None:
                return []
            chunk = self._queue.take(self.batch_size, sig)
            payloads = [r.payload for r in chunk]
            state = self._state_for(sig, payloads[0])
        B = self.batch_size
        n_iters, tol = payloads[0].n_iters, payloads[0].tol
        with jax.profiler.TraceAnnotation("serve.pack"):
            # pad by cycling the real requests: slot i >= len(chunk) duplicates
            # a real problem, so the shared convergence stop is unchanged and
            # no dummy can perturb anything (problems are independent per slice)
            slots = [payloads[i % len(payloads)] for i in range(B)]
            inits = [self._init_for(p) for p in slots]
            if B > 1:
                x = jnp.stack([p.tensor for p in slots])
                init = [
                    jnp.stack([inits[b][m] for b in range(B)])
                    for m in range(len(state.problem.shape))
                ]
            else:
                x = slots[0].tensor
                init = inits[0]
        if 0 not in state.dispatch:
            self._counters["compiles"] += 1  # the dispatch-cache miss compiles
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            # looked up on the package at call time: repro.plan.cp_als is the
            # one front door, and whatever wraps it there wraps the service too
            st = repro.plan.cp_als(
                x,
                state.plan,
                executor=state.executor,
                n_iters=n_iters,
                tol=tol,
                init_factors=init,
                sweeps_per_sync=self.sweeps_per_sync or n_iters,
                dispatch_cache=state.dispatch,
                dispatch_key=0,
            )
        now = time.monotonic()
        self._execute_s += now - t0
        self._counters["batches"] += 1
        self._counters["padded_slots"] += B - len(chunk)
        self._counters["completed"] += len(chunk)
        futures = []
        with jax.profiler.TraceAnnotation("serve.unpack"):
            for i, req in enumerate(chunk):
                if B > 1:
                    factors = [u[i] for u in st.factors]
                    weights, fit = st.weights[i], float(st.fit[i])
                else:
                    factors, weights, fit = list(st.factors), st.weights, float(st.fit)
                self._counters["host_reads"] += 1  # the fit; st.it is a host int
                req.payload.future._result = CPResult(
                    rid=req.rid,
                    factors=factors,
                    weights=weights,
                    fit=fit,
                    sweeps=int(st.it),
                    signature=sig,
                    latency_s=now - req.submitted_at,
                )
                futures.append(req.payload.future)
        return futures

    def flush(self) -> list[CPFuture]:
        """Drain the queue: :meth:`step` until empty; resolved futures in
        completion order (results stream back batch by batch)."""
        out: list[CPFuture] = []
        while True:
            done = self.step()
            if not done:
                return out
            out.extend(done)

    # -------------------------------------------------------------- counters
    def stats(self) -> dict:
        """Serving counters for the benchmark / monitoring.

        ``queue_depth`` (pending now), ``submitted`` / ``completed`` /
        ``rejected`` (QueueFull backpressure events), ``batches`` and
        ``padded_slots``, ``batch_occupancy`` (mean real-slot fraction over
        executed batches), ``signatures`` (distinct buckets seen),
        ``compiles`` (jitted dispatches built -- one per signature),
        ``warm_plan_hits`` (signatures planned from tuning-cache
        measurements), ``host_reads`` (device values :meth:`step` read into
        Python: one fit per real request, since the sweep count comes back
        as a host integer), ``execute_s`` and ``problems_per_s`` (completed
        real problems over in-dispatch seconds: the seconds inside the
        ``cp_als`` calls alone, so queueing, packing and unpacking are not in
        it; the served figure is the benchmark's ``solve_s``, a window's
        seconds over the requests completed in it, host path included).
        """
        c = dict(self._counters)
        served_slots = c["completed"] + c["padded_slots"]
        c.update(
            queue_depth=self._queue.depth,
            signatures=len(self._states),
            batch_occupancy=(c["completed"] / served_slots) if served_slots else 1.0,
            execute_s=self._execute_s,
            problems_per_s=(
                c["completed"] / self._execute_s if self._execute_s > 0 else 0.0
            ),
        )
        return c
