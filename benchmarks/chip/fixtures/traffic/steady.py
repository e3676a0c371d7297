"""Test fixture: an open loop with even gaps, added as a file alone."""

import time

import numpy as np


def drive(load, params):
    load.by = "due"
    gap = 1.0 / float(params["rate_qps"])
    t0 = load.open()
    for i in range(int(load.seconds / gap)):
        wait = t0 + i * gap - load.clock()
        if wait > 0:
            time.sleep(wait)
        load.send(i % load.n_pool, t0 + i * gap)
    for r in load.records:
        r.fut.result(timeout=60.0)
    load.close(t0 + load.seconds)
