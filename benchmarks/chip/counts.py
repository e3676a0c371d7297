"""Work a graph traversal needs, reckoned from shapes and the program's
counts, and the least time the chip could take for it.

Bytes: every distance evaluation reads one stored row and its float32
squared norm; every hop reads one neighbour list.
    bytes = dist_calcs * (row_bytes + 4) + hops * nbr_list_bytes
Operations: a d-wide squared-L2 evaluation is d multiplies and d adds.
    ops = dist_calcs * 2 * d
Both are lower bounds on what any implementation has to move and compute
(padding, visited-list traffic and sorting are left out), so the share of
the roofline computed from them cannot read over 100% on honest work.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of one chip of `device_kind`; a kind that is not in the
    table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def traversal_bytes(dist_calcs: float, hops: float, row_bytes: int,
                    nbr_list_bytes: int) -> float:
    return dist_calcs * (row_bytes + 4) + hops * nbr_list_bytes


def traversal_ops(dist_calcs: float, dim: int) -> float:
    return dist_calcs * 2 * dim


def least_time(n_bytes: float, n_ops: float, peak: dict
               ) -> tuple[float, str]:
    """(seconds, bound): the larger of bytes at HBM bandwidth and
    operations at the chip's highest compute peak, and which one it was."""
    t_mem = n_bytes / peak["hbm_bytes_per_s"]
    t_ops = n_ops / max(peak["bf16_flops_per_s"], peak["int8_ops_per_s"])
    return (t_mem, "bandwidth") if t_mem >= t_ops else (t_ops, "compute")
