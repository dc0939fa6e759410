"""On-device iterative-solver driver — x stays resident across SpMVs.

SpMV's real consumers are iterative solvers (CG, power iteration, PageRank,
Jacobi/Richardson sweeps) where the vector feeds straight back into the next
multiply.  ``Executor.__call__`` round-trips y through the host every step;
:func:`run_iterate` instead compiles the *whole* solver loop — k SpMVs plus
the per-step combine — into one ``lax.scan`` / ``lax.while_loop`` program
(carry buffers donated), so x never leaves the
device between steps.  This is the ALPHA-PIM extension of SparseP
(arXiv:2602.09174): the same PIM kernels, re-driven as solver sessions.

Two loop modes:

  * **steps mode** (``steps=k``) — a ``lax.scan`` of exactly k steps.  For
    the linear combines the result is bit-identical to k host-side
    ``exe(x)`` calls (the parity property tier-1 asserts).
  * **tol mode** (``tol=...``) — a ``lax.while_loop`` whose body advances
    ``check_every`` steps with an inner ``fori_loop`` before evaluating the
    residual, so compiled code never syncs with the host per step.  The
    ``max_steps`` guard bounds the loop; hitting it reports
    ``converged=False`` rather than hanging.

Built-in combines (:func:`make_combine`): ``plain`` (x' = y), ``power``
(normalize), ``richardson`` / ``jacobi`` (damped residual correction toward
``A x = b``), ``cg`` (conjugate gradients on SPD systems), ``sssp``
(Bellman-Ford relaxation over a (min, +) matrix, fed only the frontier),
plus any user-supplied ``f(x, y) -> x_next`` callable as the escape hatch.

The compiled loop is cached on the executor per (combine, mode, static
knobs); ``b`` / ``diag`` / ``omega`` / ``tol`` enter as runtime arguments,
so re-solving with a new right-hand side re-runs the same executable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import NULL_TRACE, phase

__all__ = ["IterateResult", "Combine", "make_combine", "run_iterate",
           "COMBINES"]

_TINY = 1e-30  # normalization floor: keeps power iteration NaN-free on y=0


def _dot(a, b):
    """Inner product at full precision: at default precision the TPU
    multiplies f32 in one bf16 pass."""
    return jnp.vdot(a, b, precision=jax.lax.Precision.HIGHEST)


@dataclass(frozen=True)
class IterateResult:
    """Outcome of one on-device solver session."""

    x: np.ndarray  # the solution / final iterate (host)
    steps: int  # SpMV steps actually executed on device
    converged: bool  # tol given and final residual <= tol
    residual: float  # final residual (combine-specific norm)
    load_s: float  # place x0, b/diag and the scalars; ends once on device
    kernel_s: float  # the compiled solver loop
    retrieve_s: float  # fetch x + scalars back to host
    compiled: bool = False  # this call built+compiled the loop (cold start)
    # the combine's counter (Combine.counter), summed over the executed
    # steps; "sssp": the frontier's size.  None for combines that keep none
    frontier_total: Optional[int] = None

    @property
    def seconds(self) -> float:
        """Wall-clock time-to-solution (all three phases)."""
        return self.load_s + self.kernel_s + self.retrieve_s

    @property
    def per_iter_s(self) -> float:
        """Loop seconds per executed SpMV step."""
        return self.kernel_s / max(1, self.steps)


class Combine:
    """Per-step state update of a solver loop (all methods traced).

    The driver calls ``vector(carry)`` to pick what feeds the SpMV, applies
    the executor, then ``step(carry, y, params)`` to advance.  ``carry`` is
    a dict pytree carrying at least ``x`` (the current iterate) and ``res``
    (the residual the tol loop tests).  ``linear=True`` marks combines whose
    step is an affine map of the state — exactly the ones for which k
    scanned steps must be bit-identical to k host-side calls.  ``semiring``
    is the one the matrix must run (None: any).
    """

    name = "combine"
    linear = False
    needs_b = False
    semiring = "plus_times"

    def init(self, x0, params, apply) -> dict:
        return {"x": x0, "res": jnp.asarray(jnp.inf, x0.dtype)}

    def vector(self, carry):
        return carry["x"]

    def step(self, carry, y, params) -> dict:
        raise NotImplementedError

    def solution(self, carry):
        return carry["x"]

    def residual(self, carry):
        return carry["res"]

    def counter(self, carry):
        """An int32 the carry keeps over the steps, or None."""
        return None


class PlainCombine(Combine):
    """x' = y — the raw SpMV recurrence (parity anchor; Markov chains)."""

    name = "plain"
    linear = True

    def step(self, carry, y, params):
        res = jnp.linalg.norm(y - carry["x"])
        return {"x": y, "res": res.astype(y.dtype)}


class PowerCombine(Combine):
    """Power iteration: x' = y / ||y||; residual = ||x' - x||."""

    name = "power"

    def step(self, carry, y, params):
        nrm = jnp.linalg.norm(y)
        x_new = y / jnp.maximum(nrm, jnp.asarray(_TINY, y.dtype))
        res = jnp.linalg.norm(x_new - carry["x"])
        return {"x": x_new, "res": res.astype(y.dtype)}


class RichardsonCombine(Combine):
    """Damped Richardson for A x = b: x' = x + omega (b - y); res = ||b - y||."""

    name = "richardson"
    linear = True
    needs_b = True

    def step(self, carry, y, params):
        r = params["b"] - y
        x_new = carry["x"] + params["omega"].astype(y.dtype) * r
        return {"x": x_new, "res": jnp.linalg.norm(r).astype(y.dtype)}


class JacobiCombine(Combine):
    """Jacobi sweep for A x = b: x' = x + (b - y) / diag(A)."""

    name = "jacobi"
    linear = True
    needs_b = True

    def step(self, carry, y, params):
        r = params["b"] - y
        x_new = carry["x"] + r / params["diag"]
        return {"x": x_new, "res": jnp.linalg.norm(r).astype(y.dtype)}


class CGCombine(Combine):
    """Conjugate gradients on SPD A x = b; the SpMV input is the search
    direction p, not x — ``init`` spends one extra multiply on r0."""

    name = "cg"
    needs_b = True

    def init(self, x0, params, apply):
        r = params["b"] - apply(x0)
        rs = _dot(r, r).real.astype(x0.dtype)
        return {"x": x0, "r": r, "p": r, "rs": rs,
                "res": jnp.sqrt(rs)}

    def vector(self, carry):
        return carry["p"]

    def step(self, carry, y, params):
        x, r, p, rs = carry["x"], carry["r"], carry["p"], carry["rs"]
        denom = _dot(p, y).real.astype(rs.dtype)
        alpha = rs / jnp.where(denom == 0, jnp.asarray(_TINY, rs.dtype), denom)
        x_new = x + alpha * p
        r_new = r - alpha * y
        rs_new = _dot(r_new, r_new).real.astype(rs.dtype)
        beta = rs_new / jnp.where(rs == 0, jnp.asarray(_TINY, rs.dtype), rs)
        p_new = r_new + beta * p
        return {"x": x_new, "r": r_new, "p": p_new, "rs": rs_new,
                "res": jnp.sqrt(rs_new)}


class SSSPCombine(Combine):
    """Single-source shortest paths on a (min, +) matrix: Bellman-Ford
    relaxation fed only the frontier.

    x holds distances (+inf: not reached).  The SpMV takes the frontier's
    distances, +inf elsewhere, so y_i = min_j (A_ij + x_j) over the vertices
    j whose distance fell last step; then x' = min(x, y), and the frontier
    is where x' < x.  ``res`` is the frontier's size, so a tol loop with
    tol < 1 stops at the fixed point, and a step past it changes nothing.
    The counter sums the size of the frontier each step is fed.
    """

    name = "sssp"
    semiring = "min_plus"

    def init(self, x0, params, apply):
        frontier = x0 < jnp.inf
        return {"x": x0, "frontier": frontier,
                "res": jnp.sum(frontier).astype(x0.dtype),
                "frontier_total": jnp.asarray(0, jnp.int32)}

    def vector(self, carry):
        return jnp.where(carry["frontier"], carry["x"],
                         jnp.asarray(jnp.inf, carry["x"].dtype))

    def step(self, carry, y, params):
        x = carry["x"]
        x_new = jnp.minimum(x, y)
        frontier = x_new < x
        total = carry["frontier_total"] + jnp.sum(carry["frontier"],
                                                  dtype=jnp.int32)
        return {"x": x_new, "frontier": frontier,
                "res": jnp.sum(frontier).astype(x.dtype),
                "frontier_total": total}

    def counter(self, carry):
        return carry["frontier_total"]


class CallableCombine(Combine):
    """Escape hatch: any ``f(x, y) -> x_next`` (residual = ||x' - x||)."""

    name = "callable"
    semiring = None

    def __init__(self, fn: Callable):
        self.fn = fn

    def step(self, carry, y, params):
        x_new = self.fn(carry["x"], y)
        res = jnp.linalg.norm(x_new - carry["x"])
        return {"x": x_new, "res": res.astype(x_new.dtype)}


COMBINES = {
    "plain": PlainCombine,
    "power": PowerCombine,
    "richardson": RichardsonCombine,
    "jacobi": JacobiCombine,
    "cg": CGCombine,
    "sssp": SSSPCombine,
}


def make_combine(combine: Union[str, Callable]) -> Combine:
    """Resolve a combine spec: a builtin name or an ``f(x, y)`` callable."""
    if callable(combine):
        return CallableCombine(combine)
    cls = COMBINES.get(combine)
    if cls is None:
        raise ValueError(
            f"unknown combine {combine!r}: one of {sorted(COMBINES)} "
            "or a callable f(x, y) -> x_next"
        )
    return cls()


def _combine_key(combine: Union[str, Callable]) -> object:
    return combine if isinstance(combine, str) else id(combine)


def _build_params(comb: Combine, n: int, dtype, b, diag, omega) -> dict:
    """Host-side runtime parameters for the loop (shipped per call, so a new
    right-hand side reuses the compiled loop)."""
    params = {"omega": jnp.asarray(float(omega), dtype)}
    if comb.needs_b:
        if b is None:
            raise ValueError(f"combine={comb.name!r} needs b (right-hand side)")
        b = np.asarray(b, dtype)
        if b.shape != (n,):
            raise ValueError(f"b must have shape ({n},); got {b.shape}")
        params["b"] = jnp.asarray(b)
    if comb.name == "jacobi":
        if diag is None:
            raise ValueError("combine='jacobi' needs diag (the matrix diagonal)")
        diag = np.asarray(diag, dtype)
        if diag.shape != (n,):
            raise ValueError(f"diag must have shape ({n},); got {diag.shape}")
        if np.any(diag == 0):
            raise ValueError("combine='jacobi' needs a zero-free diagonal")
        params["diag"] = jnp.asarray(diag)
    return params


def run_iterate(
    executor,
    apply: Callable,
    x0,
    *,
    steps: Optional[int] = None,
    tol: Optional[float] = None,
    combine: Union[str, Callable] = "plain",
    b=None,
    diag=None,
    omega: float = 1.0,
    max_steps: int = 1000,
    check_every: int = 8,
    trace=NULL_TRACE,
) -> IterateResult:
    """Drive ``apply`` (device y = A @ v) as a compiled solver loop.

    Shared by every executor type: ``apply`` encapsulates the backend
    (single-device kernel dispatch, or mesh pad → shard → shard_map program
    → on-device row assembly); the loop, combine and caching logic live
    here once.  The compiled loop is cached on ``executor._iterate_loops``
    keyed by (combine, mode, static knobs).

    Args:
      executor: the owning Executor (supplies dtype/cols validation via
        ``_check_x`` and hosts the loop cache).
      apply: traced device function, logical (n,) -> (n,).
      x0: (n,) start vector (host or device).
      steps: run exactly this many steps (``lax.scan``).  Exclusive with
        ``tol``.
      tol: run until ``residual <= tol`` (``lax.while_loop``, residual
        checked every ``check_every`` steps — no per-step host sync), or
        until ``max_steps``.
      combine: builtin name (``plain`` / ``power`` / ``richardson`` /
        ``jacobi`` / ``cg`` / ``sssp``) or a callable ``f(x, y) -> x_next``.
      b: right-hand side for richardson/jacobi/cg.
      diag: matrix diagonal for jacobi.
      omega: richardson damping factor.
      max_steps: tol-mode step bound — the never-hang guard.
      check_every: tol-mode steps between residual checks.
      trace: a :class:`repro.obs.Trace` (or a sequence of them) on which
        the phases ``spmv.solve.place`` / ``.loop`` / ``.fetch`` are
        stamped as the request-lifecycle spans ``load`` / ``kernel`` /
        ``retrieve``; each phase is also a profiler annotation of its own
        name (:func:`repro.obs.phase`).

    Returns:
      :class:`IterateResult` (x on host, steps executed, convergence,
      per-phase seconds from the phases' boundaries).

    Raises:
      ValueError: for both/neither of steps and tol, a non-square executor
        (callers check), bad combine/params, a combine of another semiring
        than the executor's, or a batched x0.
    """
    if (steps is None) == (tol is None):
        raise ValueError("iterate needs exactly one of steps= or tol=")
    if steps is not None and steps < 1:
        raise ValueError(f"steps must be >= 1; got {steps}")
    if tol is not None and (tol <= 0 or max_steps < 1 or check_every < 1):
        raise ValueError("tol mode needs tol > 0, max_steps >= 1 and "
                         "check_every >= 1")
    with phase("spmv.solve.place", trace, span="load") as place:
        n, dtype = executor._iterate_shape()
        x0 = executor._check_x(x0, n, dtype)
        if x0.ndim != 1:
            raise ValueError(f"iterate takes a single (n,) start vector; "
                             f"got shape {x0.shape}")
        comb = make_combine(combine)
        if comb.semiring not in (None, executor.semiring):
            raise ValueError(
                f"combine {comb.name!r} runs on a {comb.semiring} matrix; "
                f"this one is registered {executor.semiring}")
        params = _build_params(comb, n, dtype, b, diag, omega)
        params["tol"] = jnp.asarray(0.0 if tol is None else float(tol), dtype)
        x0_dev = jnp.asarray(x0)
        # the phase ends with its transfers, so they are not the loop's
        jax.block_until_ready((x0_dev, params))

    with phase("spmv.solve.loop", trace, span="kernel") as run:
        cache = getattr(executor, "_iterate_loops", None)
        if cache is None:
            cache = executor._iterate_loops = {}
        mode = ("steps", steps) if steps is not None else \
            ("tol", max_steps, check_every)
        key = (_combine_key(combine), mode)
        loop = cache.get(key)
        cold = loop is None
        if cold:
            loop = _build_loop(comb, apply, steps, max_steps, check_every)
            cache[key] = loop
        carry, k = loop(x0_dev, params)
        x = carry["x"].block_until_ready()

    with phase("spmv.solve.fetch", trace, span="retrieve") as fetch:
        steps_run = int(k)
        residual = float(carry["res"])
        counter = comb.counter(carry)
        frontier_total = None if counter is None else int(counter)
        x_host = np.asarray(x)

    return IterateResult(
        x=x_host,
        steps=steps_run,
        converged=bool(tol is not None and residual <= tol),
        residual=residual,
        load_s=place.seconds,
        kernel_s=run.seconds,
        retrieve_s=fetch.seconds,
        compiled=cold,
        frontier_total=frontier_total,
    )


def _build_loop(comb: Combine, apply: Callable, steps: Optional[int],
                max_steps: int, check_every: int) -> Callable:
    """Compile the solver loop: (x0_dev, params) -> (carry, steps_run)."""

    def one_step(carry, params):
        # names the step's ops in a device profile (metadata only)
        with jax.named_scope("iterate.step"):
            y = apply(comb.vector(carry))
            return comb.step(carry, y, params)

    if steps is not None:

        def loop_steps(x0_dev, params):
            carry0 = comb.init(x0_dev, params, apply)

            def body(carry, _):
                return one_step(carry, params), None

            carry, _ = jax.lax.scan(body, carry0, length=steps)
            return carry, jnp.asarray(steps, jnp.int32)

        return jax.jit(loop_steps, donate_argnums=(0,))

    def loop_tol(x0_dev, params):
        carry0 = comb.init(x0_dev, params, apply)
        state0 = (carry0, jnp.asarray(0, jnp.int32))
        tol_dev = params["tol"]

        def cond(state):
            carry, k = state
            return jnp.logical_and(k < max_steps, carry["res"] > tol_dev)

        def body(state):
            carry, k = state
            # chunked residual check: advance up to check_every steps before
            # the next test; the cap keeps the total under max_steps exactly
            n_inner = jnp.minimum(check_every, max_steps - k)

            def inner(_, c):
                return one_step(c, params)

            carry = jax.lax.fori_loop(0, n_inner, inner, carry)
            return carry, k + n_inner

        return jax.lax.while_loop(cond, body, state0)

    return jax.jit(loop_tol, donate_argnums=(0,))
