"""The one general load generator: a traffic file of parameters in, a
schedule of requests and the clients that send it out.

Two kinds of serving traffic:

- ``open``: requests are *due* on a schedule drawn from the seed and are
  sent then whatever the server is doing; each is timed from when it
  was due, and how late the generator itself ran is reported;
- ``closed``: ``clients`` callers, each sending its next request when
  its last one completes, with no think time.

Every seed gets the same *set* of prompt lengths, output lengths and
arrival gaps (the quantiles of the stated distributions, so the work of
a run does not depend on the seed) in an order of its own, and prompts
of its own: equal seeds give equal schedules, different seeds different
ones.

A client is a thread that reads its stream token by token, as a
front end's handler would, and notes the host's clock at each token;
nothing is read from the server's own timers.
"""
from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

RATE_ENV = "BENCH_RATE_PER_S"     # the knee sweep's override, read here only


# ------------------------------------------------------------ schedule
def quantile_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``spec``'s distribution,
    clipped to its limits, ascending. ``spec``: ``{"dist":
    "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
    "fixed", "value"}``."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(n: int, mean: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the mid-quantiles,
    scaled so that they sum to ``n * mean`` exactly."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n * mean / g.sum())


@dataclass
class Request:
    index: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None        # seconds from the window's start
    sent: Optional[float] = None       # host clock, absolute
    submit_s: Optional[float] = None   # how long the server's submit took
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finish: Optional[str] = None
    error: Optional[str] = None


def _prompts(rng, lens: np.ndarray, vocab_size: int) -> List[np.ndarray]:
    flat = rng.integers(1, vocab_size, size=int(lens.sum()), dtype=np.int64)
    return np.split(flat, np.cumsum(lens)[:-1])


def make_schedule(traffic: dict, *, seed: int, seconds: float,
                  vocab_size: int) -> List[Request]:
    """The requests of one run, in sending order."""
    rng = np.random.default_rng([int(seed), 0x10AD])
    if traffic["kind"] == "open":
        rate = float(os.environ.get(RATE_ENV) or traffic["rate_per_s"])
        span = float(traffic["warmup_s"]) + float(seconds)
        n = max(1, int(round(rate * span)))
        p_len = rng.permutation(quantile_lengths(n, traffic["prompt_len"]))
        o_len = rng.permutation(quantile_lengths(n, traffic["output_len"]))
        if traffic["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
        gaps = rng.permutation(quantile_gaps(n, 1.0 / rate))
        due = np.cumsum(gaps) - gaps[0] - float(traffic["warmup_s"])
    elif traffic["kind"] == "closed":
        # blocks of one client-count each, every block the whole
        # distribution: any stretch of the run sees the same mix
        block = int(traffic["clients"])
        n = block * int(traffic["blocks"])
        p_len = np.concatenate([
            rng.permutation(quantile_lengths(block, traffic["prompt_len"]))
            for _ in range(n // block)])
        o_len = np.concatenate([
            rng.permutation(quantile_lengths(block, traffic["output_len"]))
            for _ in range(n // block)])
        due = [None] * n
    else:
        raise ValueError(f"not a serving traffic kind: {traffic['kind']!r}")
    prompts = _prompts(rng, p_len, vocab_size)
    return [Request(i, prompts[i], int(o_len[i]),
                    None if due[i] is None else float(due[i]))
            for i in range(n)]


# -------------------------------------------------------------- clients
class LoadGenerator:
    """Sends a schedule to ``submit(prompt, max_new) -> stream`` and
    reads every stream to its end. ``t0`` (host clock) is the start of
    the measured window; ``stop()`` ends the sending, after which
    whatever the server aborts is the benchmark's doing and not a
    failure."""

    def __init__(self, submit, schedule: List[Request], traffic: dict):
        self.submit = submit
        self.schedule = schedule
        self.traffic = traffic
        self.clock = time.perf_counter
        self.t0: Optional[float] = None
        self.sent: List[Request] = []
        self.completed = 0
        self._lock = threading.Condition()
        self._next = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []

    # -- both kinds
    def _consume(self, req: Request, stream):
        try:
            for tok in stream:
                req.token_times.append(self.clock())
                req.tokens.append(int(tok))
            req.finish = "complete"
        except Exception as e:  # noqa: BLE001 - the request's outcome
            req.error = f"{type(e).__name__}: {e}"
            req.finish = "aborted" if self._stop.is_set() else "failed"
        with self._lock:
            self.completed += 1
            self._lock.notify_all()

    def _send(self, req: Request):
        """Submit ``req``; returns its stream, or None where the
        server refused it (a failure)."""
        req.sent = self.clock()
        with self._lock:
            self.sent.append(req)
        try:
            stream = self.submit(req.prompt, req.max_new)
            # a dispatcher that ran late either woke late or stood
            # here: the two are told apart by this
            req.submit_s = self.clock() - req.sent
            return stream
        except Exception as e:  # noqa: BLE001 - refused is an outcome
            req.error = f"{type(e).__name__}: {e}"
            req.finish = "aborted" if self._stop.is_set() else "refused"
            with self._lock:
                self.completed += 1
                self._lock.notify_all()
            return None

    def _spawn(self, target, *args):
        th = threading.Thread(target=target, args=args, daemon=True)
        self._threads.append(th)
        th.start()

    # -- open loop
    def _dispatch_open(self, until: float):
        for req in self.schedule:
            if req.due >= until:
                return               # due after the window: never sent
            wait = self.t0 + req.due - self.clock()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            stream = self._send(req)
            if stream is not None:
                self._spawn(self._consume, req, stream)

    # -- closed loop
    def _client(self):
        while not self._stop.is_set():
            with self._lock:
                if self._next >= len(self.schedule):
                    return
                req = self.schedule[self._next]
                self._next += 1
            stream = self._send(req)
            if stream is not None:
                self._consume(req, stream)

    # -- life
    def start(self, now: float, seconds: float):
        """Begin sending at host time ``now``. For an open loop the
        window starts ``warmup_s`` later and every request due before
        its end, ``seconds`` after that, is sent; a closed loop's
        caller sets ``t0`` itself once enough requests have completed,
        and its clients send until ``stop()``."""
        if self.traffic["kind"] == "open":
            self.t0 = now + float(self.traffic["warmup_s"])
            self._spawn(self._dispatch_open, float(seconds))
        else:
            for _ in range(int(self.traffic["clients"])):
                self._spawn(self._client)

    def wait_completed(self, n: int, timeout: float) -> bool:
        with self._lock:
            return self._lock.wait_for(lambda: self.completed >= n,
                                       timeout)

    def wait_first_tokens(self, reqs: List[Request], timeout: float):
        """Until each of ``reqs`` has a first token or an outcome, or
        ``timeout`` seconds pass."""
        end = self.clock() + timeout
        while self.clock() < end:
            if all(r.token_times or r.finish for r in reqs):
                return
            time.sleep(0.02)

    def stop(self):
        self._stop.set()

    def join(self, timeout: float) -> int:
        """Wait for every client thread; returns how many still run."""
        end = self.clock() + timeout
        threads = list(self._threads)
        for th in threads:
            th.join(max(0.0, end - self.clock()))
        return sum(th.is_alive() for th in threads)
