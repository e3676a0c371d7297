"""Observability budget: tracing overhead sweep + per-stage latency split.

Two questions, both acceptance bounds of the obs subsystem (ISSUE 7):

  * what does tracing COST? The same concurrent-lane QPS harness as
    fig_cluster drives the csd backend with tracing disabled (twice —
    the second run measures run-to-run noise, which is the bar "disabled
    is unmeasurable" must clear), with ONLY the continuous profiler on
    (switched on for calibration; budgeted < 2 %),
    fully sampled (target < 5 % QPS loss), and at 10 % sampling;
  * where does a request's time GO? A traced run through the full async
    serving stack (SearchServer -> batcher -> replica pool -> csd) is
    decomposed from its own spans into queue / traversal / store-read /
    rerank / dispatch-other, attributed per request (batch stages are
    weighted by batch size). The stages sum to the measured end-to-end
    latency exactly — queue+exec == e2e by construction, and the exec
    residue is reported as `dispatch_other`, not dropped.

Emits `BENCH_obs.json` at the repo root next to the other BENCH files.
"""

from __future__ import annotations

import json
import os
from collections import defaultdict

import numpy as np

from benchmarks.common import bench_stamp
from benchmarks.fig_cluster import _throughput
from repro.api import IndexSpec, SearchRequest, SearchService
from repro.core.hnsw_graph import HNSWConfig
from repro.data import VectorDataset
from repro.obs import PROFILER, TRACER, profile_report

N, DIM, NQ = 4000, 64, 64
K, EF = 10, 40
CFG = HNSWConfig(M=12, ef_construction=80, seed=0)
BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_obs.json")


def _build(tmp: str):
    ds = VectorDataset(N, DIM, n_clusters=32, seed=0)
    spec = IndexSpec(backend="csd", num_partitions=2, hnsw=CFG,
                     storage_path=os.path.join(tmp, "store"),
                     cache_bytes=32 << 20)
    return SearchService.build(ds.vectors(), spec), ds.queries(NQ)


def _overhead_sweep(svc, queries) -> dict:
    """QPS under the fig_cluster lane harness at each tracing state.

    `profiled` is the continuous profiler ALONE (tracing off — the hot
    path takes the disabled-tracer branch, which hands spans to the
    profiler instead of the no-op), budgeted at < 2 % QPS loss."""
    out = {}
    states = [
        ("baseline", dict(enabled=False), False),
        ("disabled", dict(enabled=False), False),   # re-run: noise floor
        ("profiled", dict(enabled=False), True),
        ("sampled_1.0", dict(enabled=True, sample_rate=1.0), False),
        ("sampled_0.1", dict(enabled=True, sample_rate=0.1), False),
    ]
    for name, cfg, prof in states:
        TRACER.configure(**cfg)
        TRACER.clear()
        PROFILER.configure(enabled=prof)
        PROFILER.reset()
        out[name] = _throughput(svc.search, queries)
    TRACER.configure(enabled=False)
    TRACER.clear()
    PROFILER.configure(enabled=False)               # the default
    PROFILER.reset()
    base = out["baseline"]["qps"]
    for name in ("disabled", "profiled", "sampled_1.0", "sampled_0.1"):
        out[name]["overhead_pct"] = round(
            (base - out[name]["qps"]) / base * 100.0, 2)
    out["targets"] = {
        "sampled_1.0_max_pct": 5.0,
        "sampled_1.0_met": out["sampled_1.0"]["overhead_pct"] < 5.0,
        "disabled_max_pct": 1.0,
        "disabled_met": out["disabled"]["overhead_pct"] <= 1.0,
        "profiled_max_pct": 2.0,
        "profiled_met": out["profiled"]["overhead_pct"] < 2.0,
    }
    return out


def _stage_breakdown(svc, queries) -> dict:
    """Serve traced traffic, then attribute each request's e2e latency to
    stages from the recorded spans. Batch-shared stages (traversal,
    store-read, rerank) are weighted by batch size: every co-rider of a
    batch experiences that batch's whole stage time."""
    from repro.serve import SearchServer

    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.clear()
    PROFILER.configure(enabled=True)
    PROFILER.reset()
    with SearchServer(svc, replicas=2, max_batch=16,
                      max_wait_ms=1.0) as srv:
        for _ in range(2):                       # second pass runs warm
            futs = [srv.submit(q, k=K, ef=EF, rerank=True)
                    for q in queries]
            [f.result(timeout=300) for f in futs]
        srv.drain()
    spans = TRACER.spans()
    # the continuous profiler saw the same traffic through its Tracer hook;
    # its live attribution must agree with the post-hoc span analysis below
    live = profile_report(reset=True)
    PROFILER.configure(enabled=False)
    TRACER.configure(enabled=False)
    TRACER.clear()

    def _dur(s):
        return (s["t1"] - s["t0"]) * 1e3

    per_name = defaultdict(list)
    for s in spans:
        per_name[s["name"]].append(s)
    n_req = len(per_name["request"])
    e2e = float(np.mean([_dur(s) for s in per_name["request"]]))
    queue = float(np.mean([_dur(s) for s in per_name["queue"]]))
    execm = float(np.mean([_dur(s) for s in per_name["exec"]]))

    # batch-shared stage totals, grouped by the batch's trace id and
    # weighted by the batch's size attr
    by_trace = defaultdict(lambda: defaultdict(float))
    size_of = {}
    for s in spans:
        if s["name"] == "batch":
            size_of[s["trace"]] = s["attrs"]["size"]
        elif s["name"] in ("traversal", "store-read", "rerank"):
            by_trace[s["trace"]][s["name"]] += _dur(s)
    stage_mean = defaultdict(float)
    for trace, stages in by_trace.items():
        w = size_of.get(trace, 1) / n_req
        for name, total in stages.items():
            stage_mean[name] += total * w

    trav = stage_mean["traversal"]               # includes store-read
    store = stage_mean["store-read"]
    rerank = stage_mean["rerank"]
    breakdown = {
        "queue": round(queue, 3),
        "traversal": round(trav - store, 3),
        "store_read": round(store, 3),
        "rerank": round(rerank, 3),
        # replica wait + batch pack/pad + scatter — everything in the
        # exec window the search stages do not account for
        "dispatch_other": round(execm - trav - rerank, 3),
    }
    return {
        "requests": n_req,
        "e2e_ms": round(e2e, 3),
        "stage_ms": breakdown,
        "stage_sum_ms": round(sum(breakdown.values()), 3),
        # queue+exec == e2e by construction; this is the proof the stages
        # neither drop nor double-count time
        "sum_matches_e2e": bool(
            abs(queue + execm - e2e) < 1e-6 * max(1.0, e2e)),
        "search_coverage_of_exec": round((trav + rerank) / execm, 3)
        if execm else None,
        "spans_recorded": len(spans),
        # same traffic, attributed live by repro.obs.profile (no spans
        # retained): what `profile_report()` serves in production
        "profiler_live": live,
    }


def run():
    import tempfile

    tmp = tempfile.mkdtemp(prefix="fig-obs-")
    svc, queries = _build(tmp)
    record = {"n": N, "dim": DIM, "nq": NQ, "k": K, "ef": EF,
              "backend": "csd", "bench_meta": bench_stamp("full")}

    record["overhead"] = _overhead_sweep(svc, queries)
    record["stages"] = _stage_breakdown(svc, queries)

    # acceptance bound: the profiler, on, must cost < 2 %
    # QPS — checked BEFORE the record is written so a blown budget can
    # never land in BENCH_obs.json as a quiet regression
    prof_pct = record["overhead"]["profiled"]["overhead_pct"]
    assert prof_pct < 2.0, \
        f"continuous profiler costs {prof_pct}% QPS (budget: < 2%)"
    live = record["stages"]["profiler_live"]
    assert live["sum_matches_e2e"], \
        f"profiler live attribution does not telescope to e2e: {live}"

    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)

    ov, st = record["overhead"], record["stages"]
    rows = []
    for name in ("baseline", "disabled", "profiled", "sampled_1.0",
                 "sampled_0.1"):
        m = ov[name]
        extra = (f"qps={m['qps']:.0f};p50_ms={m['p50_ms']:.1f}"
                 + (f";overhead_pct={m['overhead_pct']}"
                    if "overhead_pct" in m else ""))
        rows.append((f"fig_obs_{name}", m["us_per_query"], extra))
    stage_str = ";".join(f"{k}_ms={v}" for k, v in st["stage_ms"].items())
    rows.append(("fig_obs_stages", st["e2e_ms"] * 1e3,
                 f"e2e_ms={st['e2e_ms']};{stage_str};"
                 f"sum_matches_e2e={st['sum_matches_e2e']}"))
    rows.append(("fig_obs_json", 0.0, f"wrote={BENCH_JSON}"))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for _name, _us, _extra in run():
        print(f"{_name},{_us:.1f},{_extra}")
