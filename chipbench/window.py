"""The measured window: one definition for every cell.

A closed loop, one consumer taking batches as fast as it can, with at most
two steps in flight: before dispatching step i the loop reads back the loss
of step i - 2 and stamps the clock. That bounds the host's run-ahead, gives
every step a completion time and a loss at no extra synchronisation, and
lets the loop stop on the clock. The window opens after warm-up, dispatches
until ``seconds`` have passed and closes on the readback of the last step
dispatched. Rates are all the work over all the time; the tail is over
every step.
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import deque
from dataclasses import dataclass, field

IN_FLIGHT = 2


@dataclass
class WindowLog:
    t_open: float = 0.0
    t_close: float = 0.0
    completed_at: list = field(default_factory=list)  # one stamp per step
    wait_s: list = field(default_factory=list)        # in next(loader), per step
    losses: list = field(default_factory=list)

    @property
    def steps(self) -> int:
        return len(self.completed_at)

    @property
    def wall_s(self) -> float:
        return self.t_close - self.t_open


def _no_span(name):
    return contextlib.nullcontext()


def closed_loop(next_batch, dispatch, readback, seconds: float,
                clock=time.perf_counter, span=_no_span) -> WindowLog:
    """Run the window. ``dispatch(batch)`` enqueues one step and returns a
    handle at once; ``readback(handle)`` blocks until that step is done and
    returns its loss. ``span(name)`` is a context manager written around
    the loop's three host activities (the traced run's annotations)."""
    log = WindowLog()
    in_flight = deque()

    def complete():
        with span("chipbench/readback"):
            log.losses.append(readback(in_flight.popleft()))
        log.completed_at.append(clock())

    log.t_open = clock()
    while True:
        if len(in_flight) >= IN_FLIGHT:
            complete()
        if clock() - log.t_open >= seconds:
            break
        t0 = clock()
        with span("chipbench/next_batch"):
            batch = next_batch()
        log.wait_s.append(clock() - t0)
        with span("chipbench/dispatch"):
            in_flight.append(dispatch(batch))
    while in_flight:
        complete()
    log.t_close = log.completed_at[-1] if log.completed_at else clock()
    return log


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of every value (no interpolation, so a
    single stall is never averaged away)."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def step_intervals_ms(log: WindowLog) -> list:
    """Interval between successive step completions, one per step; the
    first runs from the window's opening."""
    stamps = [log.t_open] + log.completed_at
    return [1000.0 * (b - a) for a, b in zip(stamps, stamps[1:])]


def rate_per_chip(log: WindowLog, items_per_step: int, chips: int) -> float:
    return log.steps * items_per_step / log.wall_s / chips


def input_stall_pct(log: WindowLog) -> float:
    return 100.0 * sum(log.wait_s) / log.wall_s
