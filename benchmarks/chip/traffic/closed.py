"""Closed loop: `callers` clients, each sending its next query as soon as
its last one is answered. Queries cycle through the pool in an order drawn
from the seed (uniform popularity). With callers a multiple of the
batcher's max_batch, every batch is full: all callers are queued before
the first batch forms, and the callers of a batch, whose answers come
together, send their next queries together, so the batcher never flushes
a batch half-filled by sends that outlast its wait. The batches then hold
the same queries in every run of a seed. The window opens at an answer
and closes at the first answer `seconds` later, so it spans whole
batches; requests belong to it by when they were answered.

Parameters: {"callers": int}.
"""

from __future__ import annotations


def batch_rows(params: dict, max_batch: int) -> list[int]:
    """Rows in a batch: full ones when callers fill whole batches."""
    if int(params["callers"]) % max_batch == 0:
        return [max_batch]
    return list(range(1, max_batch + 1))


def _queries(load):
    while True:
        yield from load.rng.permutation(load.n_pool)


def drive(load, params: dict) -> None:
    load.by = "done"
    nxt = _queries(load)
    callers = int(params["callers"])
    max_batch = int(load.cfg.get("max_batch", 1))
    group = max_batch if callers % max_batch == 0 else 1
    with load.held():
        for _ in range(callers):
            load.send(next(nxt))
    load.open(load.next_done().t_done)
    owed = 1                       # answered callers yet to send again
    while True:
        if owed == group:
            with load.held():
                for _ in range(owed):
                    load.send(next(nxt))
            owed = 0
        r = load.next_done()
        if (load.tracer is not None
                and r.t_done >= load.layer_open + load.trace_seconds):
            load.stop_layer(r.t_done)
        if r.t_done >= load.t_open + load.seconds:
            load.close(r.t_done)
            return
        owed += 1
