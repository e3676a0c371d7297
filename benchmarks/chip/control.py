#!/usr/bin/env python3
"""The control for `correct`: the exact scan put in the served path's
place at the next precision below the configuration's (int4 for uint8),
judged exactly as a run judges the served answers. It has to come out
not correct on every seed.

    python3 benchmarks/chip/control.py --config sift-hnsw-hbm --seeds 1 2 3

Answers every query of the configuration's pool at the configuration's
size; prints one JSON line per seed with the numbers beside their limits.
Benchmark runs never call it.
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
import reference  # noqa: E402
from run_cell import Bench  # noqa: E402


def control(cfg: dict, seed: int, bits: int = 4) -> dict:
    rows, pool = data.bigann_rows(cfg["n"], cfg["query_pool"], seed,
                                  cfg["dim"])
    kth = reference.kth_distance(rows, pool, cfg["k"])
    ids, dists = reference.lower_precision_answers(rows, pool, cfg["k"],
                                                   bits=bits)
    v = reference.judge(rows, pool, kth, np.arange(len(pool)), ids, dists)
    lim = cfg["limits"]
    return {"seed": seed, "bits": bits, "answers": v["answers"],
            "miss_at_10": v["miss_at_k"], "miss_limit": lim["miss_at_10"],
            "dist_gap": v["dist_gap"], "dist_gap_limit": lim["dist_gap"],
            "correct": (v["miss_at_k"] <= lim["miss_at_10"]
                        and v["dist_gap"] <= lim["dist_gap"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    cfg = Bench().config(args.config)
    dev = jax.devices()[0]
    for seed in args.seeds:
        out = control(cfg, seed)
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
