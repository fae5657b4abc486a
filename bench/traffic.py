"""Seeded traffic: request lengths, arrivals, and the loops that drive them.

A traffic file (``bench/traffic/<cell>.json``) is data that this one
generator reads:

``loop``          ``"closed"`` (``clients`` callers, each sending its next
                  request as soon as the last one finishes; lengths come
                  in blocks of ``requests``) or ``"open"`` (Poisson
                  arrivals at ``rate_per_s``, whatever the server does,
                  after ``warmup_s`` seconds of the same arrivals that
                  bring the batch to a steady mix before the window).
``prompt_len``,   clipped lognormal lengths: ``{"median", "sigma", "min",
``output_len``    "max"}``.
``check``         how many served requests the reference checks, and the
                  limit of each reading compared (``check.py``).
``engine``        the serving engine's settings for the cell.

Where a seed could change the amount of work, it does not: every seed
draws the same multiset of lengths and of arrival gaps (the distribution's
quantiles at evenly spaced probabilities) and only their order, and the
token ids, depend on the seed.  Runs with different seeds then differ by
order alone, not by how much work they offer.

Latencies are timed from each request's due time, not from when the
loop got round to submitting it, so a stall of the server or of the loop
counts against every request that fell due during it.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Callable, Iterator, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the clipped lognormal
    ``spec`` (sorted)."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at evenly spaced quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Item:
    """One request to send: its id, prompt and output budget."""

    rid: str
    prompt: list
    max_new: int


def make_items(traffic: dict, vocab: int, seed: int, n: int, tag: str,
               first_wave: int = 0) -> list:
    """``n`` requests for ``seed``; ``tag`` separates independent streams
    of one run.  The first ``first_wave`` requests get a staggered share
    of their output budget (evenly spaced fractions, in seed order), so a
    closed loop that starts them together does not finish them together.
    """
    rng = np.random.default_rng([seed, sum(map(ord, tag))])
    plen = rng.permutation(quantile_lengths(traffic["prompt_len"], n))
    olen = rng.permutation(quantile_lengths(traffic["output_len"], n))
    frac = rng.permutation((np.arange(first_wave) + 0.5) / max(first_wave, 1))
    items = []
    for i in range(n):
        m = int(olen[i])
        if i < first_wave:
            m = max(1, int(math.ceil(frac[i] * m)))
        items.append(Item(f"{tag}{i}", rng.integers(0, vocab, int(plen[i]))
                          .tolist(), m))
    return items


def item_stream(traffic: dict, vocab: int, seed: int, tag: str,
                first_wave: int = 0) -> Iterator[Item]:
    """Endless requests for a closed loop, in blocks of
    ``traffic["requests"]`` that each hold the same quantiles."""
    b = 0
    while True:
        yield from make_items(traffic, vocab, seed, traffic["requests"],
                              f"{tag}{b}.", first_wave if b == 0 else 0)
        b += 1


def arrival_times(rate: float, seconds: float, seed: int, tag: str):
    """Arrival offsets in ``[0, seconds)``: ``round(rate * seconds)``
    arrivals whose gaps are the exponential quantiles in seed order,
    scaled so the next arrival would fall at ``seconds``."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed, sum(map(ord, tag)), 1])
    g = rng.permutation(quantile_gaps(rate, n + 1))
    return (np.cumsum(g)[:n] * (seconds / g.sum())).tolist()


@dataclasses.dataclass
class Record:
    """What one request did, on the host clock."""

    item: Item
    due: float
    submitted: float
    admitted: Optional[float] = None
    first: Optional[float] = None
    finished: Optional[float] = None
    tokens: Optional[list] = None

    @property
    def ttft(self) -> Optional[float]:
        return None if self.first is None else self.first - self.due

    @property
    def tpot(self) -> Optional[float]:
        if self.finished is None or len(self.tokens) < 2:
            return None
        return (self.finished - self.first) / (len(self.tokens) - 1)


class Run:
    """Drives a serving engine and records every request and step.

    The engine is used as a user's server loop would: ``submit`` and
    ``step``; its observer hooks give the admit, first-token and finish
    times.  ``span`` wraps each call in a named host span (the profiler's
    ``TraceAnnotation`` in a traced run, nothing otherwise).
    """

    def __init__(self, engine, clock: Callable[[], float], span=None):
        self.engine = engine
        self.clock = clock
        self.span = span or _no_span
        self.records: dict = {}
        #: (host time after the step, requests in slots, pages mapped,
        #: tokens generated so far)
        self.steps: list = []
        engine.observers.append(self)

    # observer hooks
    def on_admit(self, rid, slot):
        if rid in self.records:
            self.records[rid].admitted = self.clock()

    def on_first_token(self, rid):
        if rid in self.records:
            self.records[rid].first = self.clock()

    def on_finish(self, rid, n):
        if rid in self.records:
            self.records[rid].finished = self.clock()

    def submit(self, item: Item, due: float):
        from repro.serving import Request
        with self.span("bench.submit"):
            self.engine.submit(Request(rid=item.rid, prompt=item.prompt,
                                       max_new_tokens=item.max_new))
        self.records[item.rid] = Record(item, due, self.clock())

    def step(self) -> list:
        with self.span("bench.step"):
            done = self.engine.step()
        e = self.engine
        self.steps.append((self.clock(), e.num_active,
                           e.pool_stats()["pages_in_use"],
                           e.tokens_generated))
        out = []
        for req, toks in done:
            r = self.records.get(req.rid)
            if r is not None:
                r.tokens = list(toks)
                out.append(r)
        return out


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(name):
    return _NoSpan()


def closed_loop(run: Run, items: Iterator[Item], until: float):
    """Each finished request frees its caller, who sends the next one at
    once (no think time); runs until the host clock passes ``until``."""
    while run.clock() < until:
        for _ in run.step():
            run.submit(next(items), run.clock())


def open_loop(run: Run, arrivals: list, t0: float, until: float,
              sleep: Callable[[float], None], start: int = 0) -> int:
    """Submit each ``(offset, item)`` once ``t0 + offset`` has passed and
    step the engine in between; sleeps only while the engine is idle.
    Starts at ``arrivals[start]`` and returns the index of the first
    arrival not yet submitted, so a window can be driven in pieces."""
    i = start
    while True:
        now = run.clock()
        while i < len(arrivals) and t0 + arrivals[i][0] <= now:
            run.submit(arrivals[i][1], t0 + arrivals[i][0])
            i += 1
        if now >= until:
            return i
        if run.engine.idle:
            nxt = t0 + arrivals[i][0] if i < len(arrivals) else until
            sleep(max(0.0, min(nxt, until) - now))
            continue
        run.step()


def drain_first_tokens(run: Run, rids: list, until: float):
    """Keep serving, with no new arrivals, until every request in
    ``rids`` has its first token or the clock passes ``until``."""
    while run.clock() < until and not run.engine.idle and any(
            run.records[r].first is None for r in rids):
        run.step()


def lateness(records) -> dict:
    """How late the loop submitted requests past their due times, in s."""
    late = sorted(r.submitted - r.due for r in records)
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50": late[len(late) // 2],
            "p99": late[min(len(late) - 1, int(0.99 * len(late)))],
            "max": late[-1]}
