"""BigANN-shaped rows and queries made from a seed.

big-ann-benchmarks' BIGANN set (SIFT1B) is 128-d uint8 feature vectors
under L2. The rows here have the clustered shape of the repository's own
smoke data: cluster centres drawn uniformly from [0, 218], Gaussian noise
of standard deviation 12 around them, rounded and clipped to [0, 255],
one cluster per 2,000 rows (at least 8). Queries are drawn from the same
clusters, so they are in distribution.

The rows and the set of queries are fixed (`DATA_SEED`), and so is the
index built over them; `--seed` chooses the order of the queries. Every
seed then offers the same work in another order. On the chip, clusters
drawn afresh for each seed moved the saturated QPS 7% between seeds, and
the same rows in a seeded order (a different graph each seed) 26%: a
64-row batch lasts as long as its slowest query's traversal, and that
tail depends on the graph.

This module imports nothing of the system under test: the data is the
benchmark's, and a later change to the program cannot move it.
"""

from __future__ import annotations

import numpy as np

DIM = 128
NOISE = 12.0
CENTRE_HIGH = 218.0
ROWS_PER_CLUSTER = 2000
DATA_SEED = 20210


def _rng(seed: int, stream: int) -> np.random.Generator:
    # SeedSequence takes any non-negative integer, so seeds past 2**32 work
    return np.random.default_rng(np.random.SeedSequence([seed % (1 << 64),
                                                         stream]))


def bigann_rows(n: int, n_queries: int, seed: int, dim: int = DIM
                ) -> tuple[np.ndarray, np.ndarray]:
    """`n` base rows [n, dim] uint8 and `n_queries` queries [q, dim] as
    integer-valued float32, from the same clusters; the queries in an
    order drawn from `seed`."""
    k = max(8, n // ROWS_PER_CLUSTER)
    rng = _rng(DATA_SEED, 1)
    centres = rng.uniform(0.0, CENTRE_HIGH, size=(k, dim)).astype(np.float32)
    total = n + n_queries
    which = rng.integers(0, k, total)
    out = rng.standard_normal((total, dim), dtype=np.float32)
    out *= np.float32(NOISE)
    out += centres[which]
    np.rint(out, out=out)
    np.clip(out, 0.0, 255.0, out=out)
    return out[:n].astype(np.uint8), out[n:][_rng(seed, 3).permutation(
        n_queries)]
