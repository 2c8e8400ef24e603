"""The one traffic generator, the loops that offer it, and the arithmetic that
turns per-request records into metrics.

A traffic mix is a data file (``benchmark/traffic/<name>.json``); nothing here
knows any mix by name. From the file's parameters and ``--seed``:

* lengths are *stratified*: the ``n`` requests of a block take the ``n``
  mid-quantiles of the stated distribution, and the seed only permutes them.
  Every seed therefore offers the same multiset of lengths — a fixed amount of
  work — while order, arrival gaps and token ids differ;
* an open loop's arrival gaps are drawn from the seed (exponential, or gamma
  with a stated coefficient of variation for bursts) and scaled so that
  exactly ``round(rate * seconds)`` requests fall due inside the window;
* a closed loop keeps a fixed number of clients in flight and draws lengths
  block after block.

Times are seconds relative to the start of the measured window (requests of
the pre-roll are due at negative times). Latency is taken from the instant a
request was *due*, not from when the generator got round to sending it.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass
from statistics import NormalDist
from typing import Callable, Optional

import numpy as np


# -- lengths and arrivals -------------------------------------------------------

def quantile(spec: dict, u: float) -> int:
    """Inverse CDF of a length distribution, clipped to ``[min, max]``:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        x = lo + u * (hi - lo + 1)
    elif dist == "lognormal":
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return max(lo, min(hi, int(x)))


def stratified_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at the mid-quantiles ``(i + 0.5) / n``, in a seeded order."""
    vals = np.array([quantile(spec, (i + 0.5) / n) for i in range(n)], np.int64)
    return rng.permutation(vals)


def arrival_times(n: int, seconds: float, rng: np.random.Generator, cv: float = 1.0) -> np.ndarray:
    """``n`` due instants in ``[0, seconds)``: ``n + 1`` seeded gaps (gamma with
    coefficient of variation ``cv``; 1 is exponential) scaled to fill the span."""
    if n <= 0:
        return np.zeros((0,))
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / shape, size=n + 1)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


@dataclass(frozen=True)
class Request:
    index: int
    due: float          # s from the window's start; negative in the pre-roll
    prompt_len: int
    output_len: int


def open_loop_schedule(traffic: dict, seconds: float, seed: int) -> list:
    """Pre-roll and window requests of an open-loop mix, in due order."""
    loop = traffic["loop"]
    rng = np.random.default_rng([seed, 1])
    out = []
    for span, offset in ((float(loop.get("preroll_s", 0.0)), None), (float(seconds), 0.0)):
        if offset is None:
            offset = -span
        n = int(round(loop["rate_rps"] * span))
        due = arrival_times(n, span, rng, float(loop.get("cv", 1.0))) + offset
        p = stratified_lengths(traffic["prompt_len"], n, rng) if n else []
        o = stratified_lengths(traffic["output_len"], n, rng) if n else []
        out += [Request(len(out) + i, float(due[i]), int(p[i]), int(o[i])) for i in range(n)]
    return out


class LengthStream:
    """Endless seeded (prompt_len, output_len) pairs for a closed loop, drawn in
    stratified blocks so that every block offers the same work. Inside a block
    consecutive requests take mirrored quantiles (``q`` and ``1 - q``), so that
    every *pair* offers nearly the same work too: the requests that happen to
    complete inside a window then differ little from seed to seed, which is
    most of what a throughput counted in whole requests varies by."""

    def __init__(self, traffic: dict, seed: int, block: int = 48):
        if block % 2:
            raise ValueError("a block holds pairs")
        self._traffic, self._block = traffic, block
        self._rng = np.random.default_rng([seed, 1])
        self._buf: list = []

    def _mirrored(self, spec: dict) -> list:
        n = self._block
        vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
        out = []
        for i in self._rng.permutation(n // 2):
            pair = [vals[i], vals[n - 1 - i]]
            out += pair if self._rng.random() < 0.5 else pair[::-1]
        return out

    def __next__(self) -> tuple:
        if not self._buf:
            p = self._mirrored(self._traffic["prompt_len"])
            o = self._mirrored(self._traffic["output_len"])
            self._buf = list(zip(p, o))[::-1]
        return self._buf.pop()


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The token ids of request ``index`` under ``seed`` (uniform over the
    vocabulary: served token ids change no shape and no amount of work)."""
    return np.random.default_rng([seed, 2, index]).integers(0, vocab, size=length, dtype=np.int32)


# -- records ----------------------------------------------------------------------

@dataclass
class Record:
    """One request as the harness saw it. ``submitted`` and ``done`` are the
    harness's own clock readings; ``ttft_s`` / ``tbot_s`` are what the served
    result reports (first token after submission, mean gap between tokens)."""
    index: int
    due: float
    prompt_len: int
    output_len: int
    submitted: float = math.nan
    done: float = math.nan
    ok: bool = False
    error: str = ""
    ttft_s: float = math.nan
    tbot_s: float = math.nan
    n_new: int = 0

    @property
    def ttft_from_due_s(self) -> float:
        return (self.submitted - self.due) + self.ttft_s

    @property
    def late_s(self) -> float:
        return self.submitted - self.due


Submit = Callable[[Request], "object"]  # -> concurrent.futures.Future of a result
Read = Callable[["object"], tuple]       # result -> (ttft_s, tbot_s, n_new)


def _finish(rec: Record, fut, now: float, read: Read) -> None:
    rec.done = now
    if fut.cancelled():
        rec.error = "cancelled"
        return
    exc = fut.exception()
    if exc is not None:
        rec.error = f"{type(exc).__name__}: {exc}"
        return
    rec.ttft_s, rec.tbot_s, rec.n_new = read(fut.result())
    rec.ok = True


class OpenLoop:
    """Submits each request at its due instant from one thread, whatever the
    backlog. ``records`` fill in as results arrive."""

    def __init__(self, submit: Submit, read: Read, requests: list,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self._submit, self._read, self._clock, self._sleep = submit, read, clock, sleep
        self.records = [Record(r.index, r.due, r.prompt_len, r.output_len) for r in requests]
        self._requests = requests
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        """``t0``: the clock reading at which the window starts (due time 0)."""
        self._thread = threading.Thread(target=self._run, args=(t0,), name="bench-loadgen")
        self._thread.start()

    def _run(self, t0: float) -> None:
        for req, rec in zip(self._requests, self.records):
            while not self._stop.is_set():
                wait = t0 + req.due - self._clock()
                if wait <= 0:
                    break
                self._sleep(min(wait, 0.05))
            if self._stop.is_set():
                return
            rec.submitted = self._clock() - t0
            fut = self._submit(req)
            fut.add_done_callback(
                lambda f, rec=rec: _finish(rec, f, self._clock() - t0, self._read))

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


class ClosedLoop:
    """Keeps ``clients`` requests in flight: each completion sends the next.
    One thread submits; completions reach it through a queue, so the served
    system's own thread never runs the generator."""

    def __init__(self, submit: Submit, read: Read, lengths, clients: int,
                 clock: Callable[[], float] = time.perf_counter):
        self._submit, self._read, self._lengths, self._clients = submit, read, lengths, clients
        self._clock = clock
        self.records: list = []
        self._done: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.max_in_flight = 0

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,), name="bench-loadgen")
        self._thread.start()

    def _send(self, t0: float) -> None:
        p, o = next(self._lengths)
        now = self._clock() - t0
        req = Request(len(self.records), now, int(p), int(o))
        rec = Record(req.index, req.due, req.prompt_len, req.output_len, submitted=now)
        self.records.append(rec)
        fut = self._submit(req)

        def on_done(f, rec=rec):
            _finish(rec, f, self._clock() - t0, self._read)
            self._done.put(rec.index)

        fut.add_done_callback(on_done)

    def _run(self, t0: float) -> None:
        in_flight = 0
        while not self._stop.is_set():
            while in_flight < self._clients:
                self._send(t0)
                in_flight += 1
            self.max_in_flight = max(self.max_in_flight, in_flight)
            try:
                self._done.get(timeout=0.05)
                in_flight -= 1
            except queue.Empty:
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


# -- from records to metrics --------------------------------------------------------

def percentile(values, q: float) -> float:
    """The ``q``-th percentile with linear interpolation between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def measured(records: list, seconds: float) -> list:
    """Requests that came to an outcome inside the window, whenever they were
    due: in a closed loop most of them were sent before it began."""
    return [r for r in records if not math.isnan(r.done) and 0.0 <= r.done <= seconds]


def ttft_ms(records: list, seconds: float) -> list:
    """First token minus the due instant, per measured request; a request that
    failed or was refused counts as the whole window."""
    return [r.ttft_from_due_s * 1e3 if r.ok else seconds * 1e3
            for r in measured(records, seconds)]


def tbot_ms(records: list, seconds: float) -> list:
    """Mean gap between output tokens, per measured request with more than one."""
    return [r.tbot_s * 1e3 for r in measured(records, seconds) if r.ok and r.n_new > 1]


def late_ms(records: list, seconds: float) -> list:
    return [r.late_s * 1e3 for r in records
            if 0.0 <= r.due < seconds and not math.isnan(r.submitted)]


def token_rate(records: list, seconds: float, count: Callable[[Record], float],
               run_start: float) -> float:
    """Tokens per second completed inside the window, without the jump a
    whole request makes when it crosses the window's edge.

    Completions (the harness's own clock) are the events; request ``k``'s
    tokens are spread evenly over the gap since the completion before it, which
    turns the staircase of completed tokens into a piecewise-linear curve
    ``C(t)``. The rate is ``(C(end) - C(0)) / end`` with ``end`` the last
    completion inside the window, so both ends sit on the curve. ``run_start``
    (<= 0) is when load began, the curve's origin."""
    events = sorted((r.done, count(r)) for r in records if r.ok and r.done <= seconds)
    if not events or events[-1][0] <= 0.0:
        return 0.0
    end = events[-1][0]
    at_zero, total, prev = 0.0, 0.0, run_start
    for t, n in events:
        if t <= 0.0:
            at_zero += n
        elif prev < 0.0:
            # the gap straddles the window's start: the part before it
            at_zero += n * (0.0 - prev) / (t - prev)
        total += n
        prev = t
    return (total - at_zero) / end
