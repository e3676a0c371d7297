#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip: the
highest offered rate whose backlog does not grow over a window.

    python3 benchmarks/chip/sweep.py --workload hbm.poisson --seed 5 \
        --seconds 15 --rates 150 200 250 300 350

Opens the cell's index once, warms its batch shapes, then offers
each rate through the cell's own traffic kind for --seconds. Per rate it
prints one JSON line: answers per second, p50/p95 latency from when each
request was due, and the backlog (requests due and not yet answered) at
the middle and the end of the window. A run of the benchmark never calls
it; the knee and the cell's rate are written into the workload file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import data  # noqa: E402
import run_cell  # noqa: E402


def backlog(records, t: float) -> int:
    return sum(1 for r in records if r.t_due <= t
               and (r.t_done is None or r.t_done > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run_cell.Bench()
    # the knee is found before the cell has an entry in BENCHMARK.json
    with open(bench._find("workloads", args.workload + ".json")) as f:
        cell = json.load(f)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])

    import jax
    from repro.api import SearchRequest
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import SearchServer
    enable_compile_cache()
    rows, pool = data.bigann_rows(cfg["n"], cfg["query_pool"], args.seed,
                                  cfg["dim"])
    svc, _, _ = run_cell.open_service(cfg, rows, bench.index_dir)
    for b in run_cell.warm_shapes(traffic, cell["params"], cfg["max_batch"]):
        r = svc.search(SearchRequest(pool[:b], k=cfg["k"], ef=cfg["ef"],
                                     with_stats=True))
        jax.block_until_ready((r.ids, r.dists))
    with SearchServer(svc, max_batch=cfg["max_batch"],
                      max_wait_ms=cfg["max_wait_ms"]) as server:
        for rate in args.rates:
            load = run_cell.Load(server, pool, cfg, args.seconds,
                                 args.seed, None, args.seconds,
                                 lambda: {"batches": {}})
            traffic.drive(load, {**cell["params"], "rate_qps": rate})
            server.drain(timeout=120.0)
            t0, t1 = load.t_open, load.t_close
            lat = np.array([(r.t_done - r.t_due) * 1e3
                            for r in load.records])
            done = sum(1 for r in load.records if r.t_done <= t1)
            print(json.dumps({
                "rate_qps": rate, "sent": len(load.records),
                "answered_per_s": done / (t1 - t0),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "backlog_mid": backlog(load.records, (t0 + t1) / 2),
                "backlog_end": backlog(load.records, t1),
                "late_ms_p95": float(np.percentile(
                    [(r.t_sent - r.t_due) * 1e3 for r in load.records],
                    95))}), flush=True)
    reader = getattr(svc.backend, "reader", None)
    if reader is not None:
        reader.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
