"""The one traffic generator: reads a mix's parameters and drives requests.

A mix file (``bench/traffic/<mix>.json``) holds data only:

* ``kind``: ``"backlog"`` keeps ``backlog_batches`` full batches of
  ``batch`` requests waiting behind the one in service, so every dispatch
  finds work (a closed loop over a standing queue, the shape of an offline
  ensemble job); ``"open"`` sends requests at ``rate_per_s``, evenly
  spaced, whatever the server does (independent users).
* ``epsilon``: the cost budget of every request, a share of the whole
  pool's cost; ``max_new_tokens``: its answer length cap.
* ``warmup_batches``: full batches served before the window, to load every
  program the window uses.

Queries come from the benchmark's copy of the seeded MixInstruct-style
generator, so one seed gives the same requests in the same order.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, List

import numpy as np

from harness import yardstick

KINDS = ("backlog", "open")


def validate(mix: dict) -> dict:
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic kind {mix.get('kind')!r} is not one of {KINDS}")
    for key in ("epsilon", "max_new_tokens", "batch", "warmup_batches"):
        if key not in mix:
            raise ValueError(f"traffic mix lacks {key!r}")
    if mix["kind"] == "backlog" and "backlog_batches" not in mix:
        raise ValueError("a backlog mix needs backlog_batches")
    if mix["kind"] == "open" and "rate_per_s" not in mix:
        raise ValueError("an open mix needs rate_per_s")
    return mix


class Queries:
    """An endless seeded stream of queries; warm-up draws from its own."""

    def __init__(self, seed: int, stream: int):
        self.rng = np.random.default_rng([seed, stream])

    def take(self, n: int) -> List[yardstick.Query]:
        return yardstick.generate_queries(n, self.rng)


class Driver:
    """Submits requests to a scheduler and tracks when each was due.

    ``make_request(query)`` turns a query into the program's request with
    the mix's budget and answer cap; ``submit(request)`` returns the
    program's future.  ``due[seq]`` is the host-clock time each request was
    sent (backlog) or scheduled to be sent (open loop)."""

    def __init__(self, mix: dict, seed: int, submit: Callable, make_request: Callable):
        self.mix = validate(mix)
        self.submit = submit
        self.make_request = make_request
        self.queries = Queries(seed, 1)
        self.futures = {}
        self.records = {}
        self.due = {}
        self.late_s: List[float] = []

    def _send(self, q, due: float):
        fut = self.submit(self.make_request(q))
        self.futures[fut.seq] = fut
        self.records[fut.seq] = q
        self.due[fut.seq] = due
        self.late_s.append(time.perf_counter() - due)
        return fut

    def run(self, seconds: float, tick: Callable = None) -> tuple:
        """Drive for ``seconds``; returns (t0, t1) on the host clock."""
        t0 = time.perf_counter()
        t1 = t0 + seconds
        if self.mix["kind"] == "backlog":
            self._backlog(t1)
        else:
            self._open(t0, t1, tick)
        return t0, t1

    def _backlog(self, t1: float) -> None:
        """Top the queue up with whole batches, then sleep on the oldest
        request until it is served: the driver wakes once per served
        batch, and never polls against the serving thread."""
        batch = self.mix["batch"]
        keep = (self.mix["backlog_batches"] + 1) * batch
        outstanding = deque()
        while True:
            while len(outstanding) < keep:
                for q in self.queries.take(batch):
                    outstanding.append(self._send(q, time.perf_counter()))
            left = t1 - time.perf_counter()
            if left <= 0:
                return
            try:
                outstanding[0].result(timeout=left)
            except TimeoutError:
                return
            except Exception:  # a failed request is counted when the run collects it
                pass
            while outstanding and outstanding[0].done():
                outstanding.popleft()

    def _open(self, t0: float, t1: float, tick: Callable) -> None:
        gap = 1.0 / self.mix["rate_per_s"]
        tick_s = self.mix.get("tick_s", 0.25)
        k, next_tick = 0, t0 + tick_s
        while True:
            due = t0 + k * gap
            now = time.perf_counter()
            if due >= t1:
                break
            if now >= next_tick and tick is not None:
                tick()
                next_tick += tick_s
                continue
            if now < min(due, next_tick):
                time.sleep(min(due, next_tick) - now)
                continue
            (q,) = self.queries.take(1)
            self._send(q, due)
            k += 1
