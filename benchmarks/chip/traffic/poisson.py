"""Open loop: queries sent on a schedule whatever the server does, at a
mean of `rate_qps`. The gaps are the exponential distribution's quantiles
at (i + 0.5) / n for the n = rate * seconds arrivals, in an order drawn
from the seed: every seed offers the same set of gaps, so the seed changes
the order and not the load. Queries cycle through the pool in a seeded
order. Requests belong to the window by when they were due, and their
latency counts from then.

Parameters: {"rate_qps": float}.
"""

from __future__ import annotations

import time

import numpy as np


def schedule(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in seconds from the window's start."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def drive(load, params: dict) -> None:
    load.by = "due"
    due = schedule(float(params["rate_qps"]), load.seconds, load.rng)
    order = np.concatenate([load.rng.permutation(load.n_pool)
                            for _ in range(len(due) // load.n_pool + 1)])
    t0 = load.open() + 0.001
    load.t_open = load.layer_open = t0
    traced = []
    for qi, d in zip(order, due):
        t_due = t0 + d
        wait = t_due - load.clock()
        if wait > 0:
            time.sleep(wait)
        rec = load.send(qi, t_due)
        if load.tracer is not None and load.layer_close is None:
            if d < load.trace_seconds:
                traced.append(rec)
            elif all(r.fut.done() for r in traced):
                load.stop_layer(t0 + load.trace_seconds)
    for r in traced if load.layer_close is None else ():
        r.fut.result(timeout=60.0)
    load.close(t0 + load.seconds)
