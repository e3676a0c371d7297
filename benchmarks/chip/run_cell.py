#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run_cell.py --workload hbm.saturate --seed 7 \
        --seconds 30 --trace 0

Everything about a cell is found by name: its entry in BENCHMARK.json
(chips, and which metrics it reports), `workloads/<cell>.json` (config,
traffic kind and parameters, traced seconds), the
config's file (deployment), `traffic/<kind>.py` (load generator) and
`metrics/<metric>.py` (one reader per metric). A new cell, traffic mix or
metric is a new file and a new entry, never an edit here.

A run: refuse any platform but TPU; turn on the compile cache; make rows
and queries (data.py; --seed orders the queries); open the index, which
the first run in a checkout builds through `SearchService.build` and
saves; warm the batch shapes the cell's traffic sends; drive
`SearchServer.submit` for --seconds; then judge every answer against an
exact scan (reference.py). With --trace 1 the
first `trace_seconds` of the window run under the profiler and the
per-layer metrics are reported; with --trace 0 the end-to-end ones. The
last line of stdout is one JSON object; the numbers compared for `correct`
are the last lines of stderr, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import Future  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import counts  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

ANSWER_WAIT_S = 60.0     # an answer may come this long after the window
INDEX_DIR = os.path.join(ROOT, ".bench_index")   # built once per checkout


def log(msg: str) -> None:
    print(msg, flush=True)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """BENCHMARK.json and the files it names, looked up by name in
    `dirs` (the first that holds the file wins)."""

    def __init__(self, bench_json: str = os.path.join(ROOT, "BENCHMARK.json"),
                 dirs: tuple[str, ...] = (HERE,), index_dir: str = INDEX_DIR):
        with open(bench_json) as f:
            self.spec = json.load(f)
        self.base = os.path.dirname(os.path.abspath(bench_json))
        self.dirs = dirs
        self.index_dir = index_dir

    def _find(self, kind: str, filename: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {kind}/{filename} under {self.dirs}")

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        with open(self._find("workloads", name + ".json")) as f:
            w = json.load(f)
        return {**w, **self._entry("workloads", name)}

    def config(self, name: str) -> dict:
        with open(os.path.join(self.base,
                               self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def traffic(self, kind: str):
        return _load_module(self._find("traffic", kind + ".py"),
                            f"bench_traffic_{kind}")

    def reader(self, metric: str):
        return _load_module(self._find("metrics", metric + ".py"),
                            "bench_metric_" + metric.replace(".", "_"))

    def metrics(self, workload: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (trace off) or per-layer ones
        (trace on). A metric without `workloads` belongs to every cell:
        per-layer ones to every cell that reports the metric it moves."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and (m["moves"] in names or "workloads" in m)]


@dataclasses.dataclass
class Rec:
    """One request: pool query `qi`, when it was due, sent, answered."""

    qi: int
    t_due: float
    t_sent: float
    fut: Future
    t_done: float | None = None


class Tracer:
    """The profiler around the traced window, which it marks with the
    host annotation trace_reduce reads."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._mark = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # a call per event is too many
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._mark.__enter__()

    def stop(self) -> None:
        import jax
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()


class Load:
    """What a traffic module drives: `send` puts pool query `qi` through
    `SearchServer.submit`; answers arrive on `completed` in the order the
    server produced them. A traffic module calls `open` once where the
    window begins, `stop_layer` where its traced part ends (traced runs)
    and `close` where the window ends; `by` says whether requests belong
    to a window by when they were answered ("done") or due ("due")."""

    def __init__(self, server, pool: np.ndarray, cfg: dict, seconds: float,
                 seed: int, tracer: Tracer | None, trace_seconds: float,
                 snapshot):
        self.server, self.pool, self.cfg = server, pool, cfg
        self.seconds, self.trace_seconds = seconds, trace_seconds
        self.rng = np.random.default_rng(
            np.random.SeedSequence([seed % (1 << 64), 2]))
        self.tracer, self._snapshot = tracer, snapshot
        self.clock = time.perf_counter
        self.completed: queue.SimpleQueue = queue.SimpleQueue()
        self.records: list[Rec] = []
        self.by = "done"
        self.t_open = self.t_close = None
        self.layer_open = self.layer_close = None
        self.snap_open = self.snap_close = None

    @property
    def n_pool(self) -> int:
        return self.pool.shape[0]

    def send(self, qi: int, t_due: float | None = None) -> Rec:
        t = self.clock()
        fut = self.server.submit(self.pool[qi], k=self.cfg["k"],
                                 ef=self.cfg["ef"], with_stats=True)
        rec = Rec(int(qi), t if t_due is None else t_due, t, fut)
        self.records.append(rec)
        fut.add_done_callback(lambda _f, r=rec: self._done(r))
        return rec

    @contextlib.contextmanager
    def held(self):
        """Hold the server's batcher while the body sends, so every request
        sent there is queued before a batch forms: a closed loop's batches
        then start full and follow the order of sends in every run. The
        batcher waits on the request queue's condition; holding its lock
        keeps it from taking a batch until the body is done."""
        lock = getattr(getattr(self.server, "queue", None), "_cond", None)
        with lock if lock is not None else contextlib.nullcontext():
            yield

    def _done(self, rec: Rec) -> None:
        rec.t_done = self.clock()
        self.completed.put(rec)

    def next_done(self) -> Rec:
        return self.completed.get(timeout=ANSWER_WAIT_S)

    def open(self, t: float | None = None) -> float:
        """Begin the window (at `t`, else now) and its traced part;
        returns the clock once the profiler runs."""
        if self.tracer is not None:
            self.tracer.start()
        now = self.clock()
        self.t_open = now if t is None else t
        self.layer_open = self.t_open
        self.snap_open = self._snapshot()
        return now

    def stop_layer(self, t: float | None = None) -> None:
        if self.layer_close is not None:
            return
        self.layer_close = self.clock() if t is None else t
        self.snap_close = self._snapshot()
        if self.tracer is not None:
            self.tracer.stop()

    def close(self, t: float) -> None:
        self.t_close = t
        self.stop_layer(t)

    def in_window(self, lo: float, hi: float) -> list[Rec]:
        if self.by == "due":
            return [r for r in self.records if lo <= r.t_due < hi]
        return [r for r in self.records
                if r.t_done is not None and lo < r.t_done <= hi]


@dataclasses.dataclass
class Context:
    """What metric readers read. `window` and `records` are the measured
    window; `layer` and `layer_records` its traced part in a traced run
    (the whole window otherwise). Counters are taken over `layer`."""

    setup_s: float
    window: tuple[float, float]
    records: list
    layer: tuple[float, float]
    layer_records: list
    batches: dict            # {real batch rows: batches flushed}
    stats: dict              # summed QueryStats of layer_records
    cache: dict | None       # PageCache counters over the layer (csd)
    trace: dict | None       # trace_reduce.reduce(...)
    shape: dict              # dim, row_bytes, nbr_list_bytes
    peak: dict               # counts.peaks(device_kind)
    notes: list

    def note(self, msg: str) -> None:
        self.notes.append(msg)


def _annotate(obj, attr: str, name: str) -> None:
    """Wrap obj.attr in a host trace annotation (traced runs only)."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


GRAPH_KEYS = ("n", "query_pool", "dim", "dtype", "metric", "num_partitions",
              "M", "ef_construction", "graph_seed")


def _source_digest() -> bytes:
    """The program's source: an index built by other code is not reused."""
    h = hashlib.sha256()
    src = os.path.join(ROOT, "src", "repro")
    for d, dirs, files in os.walk(src):
        dirs.sort()
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(d, f)
                h.update(os.path.relpath(path, src).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
    return h.digest()


def index_key(cfg: dict, rows: np.ndarray) -> str:
    h = hashlib.sha256(json.dumps({k: cfg[k] for k in GRAPH_KEYS},
                                  sort_keys=True).encode())
    h.update(_source_digest())
    h.update(np.ascontiguousarray(rows).data)
    return h.hexdigest()[:24]


def open_service(cfg: dict, rows: np.ndarray, index_dir: str):
    """The configuration's index over `rows`: (service, shape, built).

    The first run in a checkout builds the graph through
    SearchService.build from the configuration's fixed `graph_seed`
    (data.py says why it is fixed) and saves it with SearchService.save
    under `index_dir`, keyed by the graph's settings, the rows and the
    program's source; later runs load it, as a restarted deployment loads
    its index. A csd deployment's block store is written once beside it
    from the same graph, with a cache of 1/`cache_divisor` of the graph
    tables, and reopened by later runs."""
    from repro.api import IndexSpec, SearchService
    from repro.core.hnsw_graph import HNSWConfig

    home = os.path.join(index_dir, index_key(cfg, rows))
    part = os.path.join(home, "partitioned")
    meta_path = os.path.join(home, "meta.json")
    svc = None
    built = not os.path.exists(meta_path)
    if built:
        shutil.rmtree(home, ignore_errors=True)
        hnsw = HNSWConfig(M=cfg["M"], ef_construction=cfg["ef_construction"],
                          seed=cfg["graph_seed"])
        spec = IndexSpec(backend="partitioned",
                         num_partitions=cfg["num_partitions"],
                         dtype=cfg["dtype"], metric=cfg["metric"], hnsw=hnsw)
        svc = SearchService.build(rows, spec)
        db = svc.backend.pdb.db
        meta = {"spec": svc.spec.to_json(),
                "graph_bytes": sum(int(np.asarray(a).nbytes) for a in db),
                "shape": {"dim": int(rows.shape[1]),
                          "row_bytes": int(db.vectors.shape[-1]
                                           * db.vectors.dtype.itemsize),
                          "nbr_list_bytes": int(db.l0_nbrs.shape[-1]
                                                * db.l0_nbrs.dtype.itemsize),
                          "qscale": svc.spec.qscale}}
        svc.save(part)
        with open(meta_path + ".partial", "w") as f:
            json.dump(meta, f)
        os.replace(meta_path + ".partial", meta_path)
    with open(meta_path) as f:
        meta = json.load(f)
    shape = dict(meta["shape"])
    if cfg["backend"] == "partitioned":
        if svc is None:
            svc = SearchService.load(part)
        return svc, shape, built
    from repro.store.csd import CSDBackend
    store = os.path.join(home, f"csd-{cfg['block_size']}-"
                               f"{cfg['cache_divisor']}")
    csd_spec = dataclasses.replace(
        IndexSpec.from_json(meta["spec"]), backend="csd",
        storage_path=os.path.join(store, "store"),
        block_size=cfg["block_size"],
        cache_bytes=meta["graph_bytes"] // cfg["cache_divisor"])
    done = os.path.join(store, "written")
    if os.path.exists(done):
        backend = CSDBackend.from_state(csd_spec, {})
    else:
        built = True
        shutil.rmtree(store, ignore_errors=True)
        if svc is None:
            svc = SearchService.load(part)
        backend = CSDBackend.from_partitioned(svc.backend.pdb, csd_spec)
        del svc
        open(done, "w").close()
    shape.update(graph_bytes=meta["graph_bytes"],
                 store_bytes=sum(os.path.getsize(os.path.join(d, f))
                                 for d, _, fs in os.walk(store) for f in fs),
                 cache_bytes=csd_spec.cache_bytes)
    return SearchService(csd_spec, backend), shape, built


def warm_shapes(traffic, params: dict, max_batch: int) -> list[int]:
    """The batch shapes a cell's traffic makes the server run: what the
    traffic kind's `batch_rows(params, max_batch)` says it sends in a
    batch, else any count up to max_batch, each padded to the batcher's
    bucket."""
    from repro.serve.batcher import bucket_size
    rows = getattr(traffic, "batch_rows", None)
    sizes = (rows(params, max_batch) if rows is not None
             else range(1, max_batch + 1))
    return sorted({bucket_size(n, max_batch) for n in sizes})


def _snapshot(server):
    """Counters the per-layer metrics difference over a window."""
    def snap():
        reps = server.pool.stats()
        out = {"batches": dict(server.stats().batch_sizes)}
        if "block_reads" in reps[0]:
            out["cache"] = {k: sum(r[k] for r in reps)
                            for k in ("block_reads", "cache_hits",
                                      "cache_misses", "bytes_read")}
        return out
    return snap


def _delta(a: dict, b: dict) -> dict:
    return {k: b.get(k, 0) - a.get(k, 0) for k in set(a) | set(b)
            if b.get(k, 0) - a.get(k, 0)}


def run(argv=None, *, bench: Bench | None = None, need_tpu: bool = True
        ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = bench or Bench()
    cell = bench.workload(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    wanted = bench.metrics(args.workload, bool(args.trace))
    readers = {m["name"]: bench.reader(m["name"]) for m in wanted}

    import jax
    devices = jax.devices()
    if need_tpu and (devices[0].platform != "tpu"
                     or len(devices) < cell["chips"]):
        print(f"run_cell: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    used = devices[:cell["chips"]]
    kind = used[0].device_kind
    peak = counts.peaks(kind) if need_tpu else {}
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program into the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {cache_dir}")

    from repro.api import SearchRequest
    from repro.serve import SearchServer

    t_jax = time.perf_counter()
    rows, pool = data.bigann_rows(cfg["n"], cfg["query_pool"], args.seed,
                                  cfg["dim"])
    work = tempfile.mkdtemp(prefix="bench_chip_")
    tracer = Tracer(work) if args.trace else None
    server = None
    try:
        t = time.perf_counter()
        svc, shape, built = open_service(cfg, rows, bench.index_dir)
        index_s = time.perf_counter() - t
        log(f"index: {'built' if built else 'loaded'} n={cfg['n']} "
            f"backend={cfg['backend']} partitions={cfg['num_partitions']} "
            f"seconds={index_s:.3f} shape={json.dumps(shape)}")
        t_warm = time.perf_counter()
        shapes = warm_shapes(traffic, cell["params"], cfg["max_batch"])
        log(f"warm batch shapes: {shapes}")
        for b in shapes:
            r = svc.search(SearchRequest(pool[:b], k=cfg["k"], ef=cfg["ef"],
                                         with_stats=True))
            jax.block_until_ready((r.ids, r.dists))
        t_served = time.perf_counter()
        server = SearchServer(svc, max_batch=cfg["max_batch"],
                              max_wait_ms=cfg["max_wait_ms"])
        if tracer is not None:
            _annotate(server.pool.replicas[0].service, "search",
                      "bench.search")
            _annotate(server.queue, "collect", "bench.batcher.collect")
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: compiles.append(time.perf_counter())
            if ev == "/jax/core/compile/backend_compile_duration" else None)
        load = Load(server, pool, cfg, args.seconds, args.seed, tracer,
                    cell.get("trace_seconds", args.seconds),
                    _snapshot(server))
        timed_out = False
        try:
            traffic.drive(load, cell["params"])
        except queue.Empty:             # an answer never came: judge below
            timed_out = True
            now = time.perf_counter()
            if load.t_open is None:
                load.open(now)
            load.close(now)
        server.drain(timeout=ANSWER_WAIT_S)
        mem = [d.memory_stats() or {} for d in used]
        memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
        in_window = [c for c in compiles if load.t_open <= c <= load.t_close]
        print(f"compiles in window: {len(in_window)}", file=sys.stderr)
        cache_peak = None
        if cfg["backend"] == "csd":
            cache_peak = server.pool.replicas[0].service.backend.reader \
                .cache.snapshot()["peak_bytes"]
        server.shutdown(drain=False)
        reader_ = getattr(svc.backend, "reader", None)
        if reader_ is not None:
            reader_.close()
        del svc
        server = None
        trace = None
        if tracer is not None:
            trace = trace_reduce.reduce(trace_reduce.load(tracer.log_dir))
    finally:
        if server is not None:
            server.shutdown(drain=False)
        shutil.rmtree(work, ignore_errors=True)

    # -- correct: every answer against the exact scan -------------------------
    answered = [r for r in load.records
                if r.fut.done() and r.fut.exception() is None]
    unanswered = len(load.records) - len(answered)
    qidx = np.array([r.qi for r in answered], np.int64)
    used_q = np.unique(qidx)
    kth = np.zeros(pool.shape[0], np.int64)
    kth[used_q] = reference.kth_distance(rows, pool[used_q], cfg["k"])
    res = [r.fut.result() for r in answered]
    verdict = reference.judge(
        rows, pool, kth, qidx,
        np.array([x.ids for x in res]).reshape(-1, cfg["k"]),
        np.array([x.dists for x in res]).reshape(-1, cfg["k"]))
    checks = {"unanswered": (unanswered, 0),
              "miss_at_10": (verdict["miss_at_k"], cfg["limits"]["miss_at_10"]),
              "dist_gap": (verdict["dist_gap"], cfg["limits"]["dist_gap"])}
    if cache_peak is not None:
        checks["resident_over_cache"] = (cache_peak / shape["cache_bytes"],
                                         1.0)
    correct = (not timed_out and bool(answered)
               and all(v <= lim for v, lim in checks.values()))

    # -- metrics ---------------------------------------------------------------
    layer_records = load.in_window(load.layer_open, load.layer_close)
    stats = {"queries": 0, "hops": 0, "dist_calcs": 0}
    layer_q = [r.fut.result() for r in layer_records
               if r.fut.done() and r.fut.exception() is None]
    for x in layer_q:
        stats["queries"] += 1
        stats["hops"] += int(np.asarray(x.stats.hops))
        stats["dist_calcs"] += int(np.asarray(x.stats.dist_calcs))
    ctx = Context(
        setup_s=load.t_open - T_START,
        window=(load.t_open, load.t_close),
        records=load.in_window(load.t_open, load.t_close),
        layer=(load.layer_open, load.layer_close),
        layer_records=layer_records,
        batches=_delta(load.snap_open["batches"], load.snap_close["batches"]),
        stats=stats,
        cache=(_delta(load.snap_open["cache"], load.snap_close["cache"])
               if "cache" in load.snap_open else None),
        trace=trace, shape=shape, peak=peak, notes=[])
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    log(f"reduced: n={cfg['n']} rows of BigANN's {cfg['source_n']} "
        f"({cfg['reduced']['n']})")
    log(f"window: seconds={ctx.window[1] - ctx.window[0]:.6f} "
        f"requests={len(ctx.records)} sent={len(load.records)}")
    log(f"setup: setup_s={ctx.setup_s:.6f} start_to_jax_s="
        f"{t_jax - T_START:.6f} index_s={index_s:.6f} warm_s="
        f"{t_served - t_warm:.6f} serve_to_window_s="
        f"{load.t_open - t_served:.6f}")
    log(f"peak_hbm_bytes: {memory_peak}")
    if "store_bytes" in shape:
        log(f"store_bytes: {shape['store_bytes']} cache_bytes: "
            f"{shape['cache_bytes']} cache_peak_bytes: {cache_peak}")
    for note in ctx.notes:
        log(note)
    if trace is not None:
        log("trace modules: " + json.dumps(trace["modules"]))
    device = {"platform": used[0].platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(load.records),
           "failed": unanswered, "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
