"""What every traffic driver shares: the configuration's data, the seed's
streams, and the closed loop of solver sessions.

A traffic mix is a data file (``bench/traffic/<mix>.json``) whose ``driver``
key names a module ``bench/drivers/<driver>.py``; its ``Driver`` class takes
the mix's other keys as parameters.  A driver draws every input from the
run's seed, keeps the answers it will compare, and :meth:`Driver.check`
compares them with the float64 reference once the window has closed and the
program is released.  A new kind of traffic is a new driver file.
"""
from __future__ import annotations

import time

import numpy as np

from . import program

NAME = "A"
POOL = 32  # distinct base vectors; each session scales or shifts one
STALL = 1.5  # a session this many times the median is counted as a stall


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


class Data:
    """The configuration's triplets, as generated, and the program's dtype."""

    def __init__(self, rows, cols, values, shape, dtype, seed: int,
                 generator, params: dict):
        self.rows, self.cols, self.values = rows, cols, values
        self.shape = tuple(shape)
        self.n = self.shape[1]
        self.dtype = np.dtype(dtype)
        self.seed = seed
        self.generator = generator  # the configuration's generator module
        self.params = params

    @property
    def nnz(self) -> int:
        return int(len(self.values))

    def matvec(self, x):
        """float64 A x straight from the triplets (input generation only)."""
        return np.bincount(self.rows, weights=self.values * x[self.cols],
                           minlength=self.shape[0])


class Driver:
    """Base: set-up, a measured window, and the comparison afterwards."""

    def __init__(self, params: dict, data: Data, devices):
        self.params = params
        self.data = data
        self.devices = devices
        self.errors = []  # repr of every exception a request raised

    def timed(self, spans: dict, key: str, fn):
        t0 = time.perf_counter()
        out = fn()
        spans[key] = time.perf_counter() - t0
        return out

    def setup(self, spans: dict, sm) -> None:
        """Hand ``sm`` to the program and warm up every shape the window
        uses; record set-up spans in ``spans``."""
        raise NotImplementedError

    def window(self, seconds: float) -> dict:
        """Drive the traffic for ``seconds``; return at least ``seconds``,
        ``units`` (completed) and ``failed``."""
        raise NotImplementedError

    def close(self) -> None:
        """Release the program's state before the reference runs."""

    def check(self, ref) -> dict:
        """The numbers compared with the reference, by name."""
        raise NotImplementedError


class SolveLoop(Driver):
    """One closed-loop client of ``SpmvEngine.solve`` sessions: place the
    inputs, run the compiled loop, fetch x.

    A subclass draws its inputs (:meth:`inputs`), makes one session's call
    (:meth:`call`) and compares the answers (:meth:`check`).  Where it
    solves to a tolerance (``to_tolerance``), a session that did not
    converge is counted failed.
    """

    to_tolerance = False

    def __init__(self, params, data, devices):
        super().__init__(params, data, devices)
        self.results = []  # (x, steps, converged) per session, in order
        self.scale_rng = rng(data.seed, 2)
        self.scales = []

    def inputs(self, i: int):
        """(x0, b) of session i; b is None where the combine takes none."""
        raise NotImplementedError

    def call(self, x0, b):
        raise NotImplementedError

    def next_index(self) -> int:
        self.scales.append(self.data.dtype.type(
            self.scale_rng.uniform(0.5, 2.0)))
        return len(self.scales) - 1

    def setup(self, spans: dict, sm) -> None:
        self.eng = program.engine(self.devices)
        self.timed(spans, "register_s",
                   lambda: program.register(self.eng, NAME, sm))
        # one session compiles the loop; its answer is not compared
        self.timed(spans, "warm_s", lambda: self.call(*self.inputs(
            self.next_index())))
        self.scales.clear()

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        end = t0 + seconds
        t_last = t0
        lengths, phases = [], []
        while t_last < end:
            i = self.next_index()
            try:
                r = self.call(*self.inputs(i))
                self.results.append((r.x, r.steps, r.converged))
                phases.append((r.load_s, r.kernel_s, r.retrieve_s))
            except Exception as e:  # a failed session counts, and is kept
                self.errors.append(repr(e))
                self.results.append((None, 0, False))
                phases.append((0.0, 0.0, 0.0))
            t = time.perf_counter()
            lengths.append(t - t_last)
            t_last = t
        steps = [s for _, s, _ in self.results]
        unconverged = sum(1 for x, _, c in self.results
                          if x is not None and self.to_tolerance and not c)
        median = float(np.median(lengths))
        slow = int(np.argmax(lengths))
        load, kernel, retrieve = phases[slow]
        return {"seconds": t_last - t0,
                "units": len(self.results), "steps": steps,
                "spmv_calls": sum(steps),
                "failed": len(self.errors) + unconverged,
                "unconverged": unconverged,
                "session_ms_median": median * 1e3,
                "stalls": sum(1 for x in lengths if x > STALL * median),
                # where the slowest session spent its time: the program's
                # place, compiled loop and fetch, and the rest on the host
                "slowest_ms": {
                    "total": lengths[slow] * 1e3, "load": load * 1e3,
                    "kernel": kernel * 1e3, "retrieve": retrieve * 1e3,
                    "other": (lengths[slow] - load - kernel - retrieve) * 1e3}}

    def close(self) -> None:
        self.eng.unregister(NAME)
        self.eng = None

    def answered(self) -> list:
        """Indices of the sessions that returned an answer."""
        return [i for i, (x, _, _) in enumerate(self.results)
                if x is not None]
