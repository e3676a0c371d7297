"""The program's own spans (`repro.obs` TRACER), read over the traced
window.

Importing this module turns the program's tracer on, fully sampled
(`trace_on`: `TRACER.configure(enabled=True, sample_rate=1.0)`), and so
does loading each reader that uses it, for a process that loads the
readers more than once. run_cell.py loads the per-layer readers only for
`--trace 1`, and loads them before it builds the service, so the traced
run records every span from the start, while the untraced run that gives
the end-to-end metrics keeps tracing off.

The readers clip the tracer's in-memory spans to `ctx.layer`, which is on
the same `time.perf_counter` clock as the tracer. A program whose tracer
lacks `self_times`, or records none of the spans a reader asks for, gives
None: the metric is then left out of the result line.

Spans read here (`repro.obs.trace`):
  replica thread  `dispatch` > `search` > `traversal` > `upper-hop` /
                  `hop` > `store-read`, `hop-kernel`, `device-wait`;
                  then `scatter` (results to host, futures resolved)
  batch lane      `batch` (flush to results back) > `replica-wait` (the
                  batch's wait in the replica's executor)
"""

import os
import sys

import numpy as np
from repro.obs import trace
from repro.obs.trace import TRACER

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce  # noqa: E402


def trace_on() -> None:
    TRACER.configure(enabled=True, sample_rate=1.0)


trace_on()

REPLICA = "serve-replica-"         # the replicas' executor threads


def _self_times():
    return getattr(trace, "self_times", None)


def replica_spans(ctx) -> list[dict] | None:
    """Spans recorded on the replica threads, or None where the program
    does not say enough to read them."""
    if _self_times() is None:
        return None
    spans = [s for s in TRACER.spans() if str(s["tid"]).startswith(REPLICA)]
    return spans or None


def _union(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] under any of `spans`."""
    clipped = [(max(s["t0"], lo), min(s["t1"], hi)) for s in spans]
    return sum(b - a for a, b in trace_reduce._union(
        [(a, b) for a, b in clipped if b > a]))


def window_share(ctx, names: tuple[str, ...], self_time: bool):
    """% of the traced window that the replica threads spent in the spans
    named (`self_time`: their self time; else any time under them)."""
    spans = replica_spans(ctx)
    if spans is None:
        return None
    lo, hi = ctx.layer
    if self_time:
        st = _self_times()(spans, lo, hi)
        if not any(n in st for n in names):
            return None
        sec = sum(st.get(n, 0.0) for n in names)
    else:
        named = [s for s in spans if s["name"] in names]
        if not named:
            return None
        sec = _union(named, lo, hi)
    return 100.0 * sec / (hi - lo)


def note_breakdown(ctx) -> None:
    """Write the replica threads' busy share of the window and each span
    name's self share, in percent, as one line of the run's log."""
    spans = replica_spans(ctx)
    if spans is None:
        return
    lo, hi = ctx.layer
    st = _self_times()(spans, lo, hi)
    busy = 100.0 * _union(spans, lo, hi) / (hi - lo)
    shares = {n: 100.0 * s / (hi - lo) for n, s in sorted(st.items())}
    ctx.note(f"spans: window_s={hi - lo} replica_busy_share={busy} "
             f"self_share={shares}")


def batches(ctx) -> list[dict] | None:
    """Per batch flushed in the traced window, in ms: `dispatch` and
    `scatter` times, the `device-wait` under them, their host time (the
    two less that wait) and `replica-wait`. None where no batch has all
    of them."""
    if _self_times() is None:
        return None
    lo, hi = ctx.layer
    spans = TRACER.spans()
    kids: dict[int, list] = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)

    def waits(s) -> float:
        todo, sec = list(kids.get(s["id"], ())), 0.0
        while todo:
            c = todo.pop()
            if c["name"] == "device-wait":
                sec += c["t1"] - c["t0"]
            else:
                todo += kids.get(c["id"], ())
        return sec

    out = []
    for b in spans:
        if b["name"] != "batch" or not lo <= b["t0"] < hi:
            continue
        parts = {c["name"]: c for c in kids.get(b["id"], ())}
        if not {"dispatch", "scatter", "replica-wait"} <= set(parts):
            continue
        d, s, w = parts["dispatch"], parts["scatter"], parts["replica-wait"]
        dev = waits(d) + waits(s)
        busy = (d["t1"] - d["t0"]) + (s["t1"] - s["t0"])
        out.append({"dispatch_ms": 1e3 * (d["t1"] - d["t0"]),
                    "scatter_ms": 1e3 * (s["t1"] - s["t0"]),
                    "device_wait_ms": 1e3 * dev,
                    "host_ms": 1e3 * (busy - dev),
                    "replica_wait_ms": 1e3 * (w["t1"] - w["t0"])})
    return out or None


def batch_median(ctx, key: str):
    """Median of `key` over the window's batches; notes every median."""
    rows = batches(ctx)
    if rows is None:
        return None
    med = {k: float(np.median([r[k] for r in rows])) for k in rows[0]}
    msg = f"spans: batches={len(rows)} medians_ms={med}"
    if msg not in ctx.notes:
        ctx.note(msg)
    return med[key]
