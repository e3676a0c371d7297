"""CPU tests of the chip benchmark under benchmarks/chip: lookup by name,
the platform refusal, the counts, the window arithmetic, the warmed
shapes, the index kept between runs, the peaks table, the trace
reduction, and `correct` coming out false under planted faults and under
the control.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
FIX = os.path.join(CHIP, "fixtures")
sys.path.insert(0, CHIP)

import control  # noqa: E402
import counts  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402
import trace_reduce  # noqa: E402

_INDEX_DIR = None


@pytest.fixture(scope="module", autouse=True)
def _index_dir(tmp_path_factory):
    """One index directory for the module: the fixture cells build their
    tiny index once and later runs load it, as in a checkout."""
    global _INDEX_DIR
    _INDEX_DIR = str(tmp_path_factory.mktemp("bench_index"))
    yield _INDEX_DIR
    _INDEX_DIR = None


@pytest.fixture(autouse=True)
def _jax_config_kept(monkeypatch, tmp_path):
    """A run turns on the persistent compile cache; keep it out of the
    checkout and of the other tests in this process."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def fixture_bench() -> run_cell.Bench:
    return run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP),
                          index_dir=_INDEX_DIR)


def run_fixture(capsys, workload: str, *extra, seed: int = 2**31 + 5,
                seconds: float = 2.0) -> dict:
    rc = run_cell.run(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), *extra],
                      bench=fixture_bench(), need_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- lookup by name -----------------------------------------------------------

def test_finds_every_piece_by_name():
    b = run_cell.Bench()
    for w in b.spec["workloads"]:
        cell = b.workload(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert b.config(cell["config"])["name"] == cell["config"]
        assert callable(b.traffic(cell["traffic"]).drive)
        for trace in (False, True):
            for m in b.metrics(w["name"], trace):
                assert callable(b.reader(m["name"]).read)
    assert {m["name"] for m in b.metrics("hbm.saturate", False)} == {
        "qps", "setup_s"}
    assert {m["name"] for m in b.metrics("csd.saturate", False)} == {
        "qps.csd", "setup_s"}
    e2e = {m["name"] for m in b.spec["end_to_end"]}
    for w in b.spec["workloads"]:
        moved = {m["moves"] for m in b.metrics(w["name"], True)}
        assert moved and moved <= {m["name"] for m in b.metrics(
            w["name"], False)} <= e2e
    f = fixture_bench()
    assert {m["name"] for m in f.metrics("tiny.poisson", False)} == {
        "p50_ms", "p95_ms", "setup_s"}
    assert {m["name"] for m in f.metrics("tiny.poisson", True)} == {
        "serve.queue_ms.p95", "loadgen.late_ms.p95"}


def test_a_split_metric_reads_as_its_source():
    """`qps.csd` and the other csd halves of a split quantity read the
    context exactly as the metric they were split from."""
    b = run_cell.Bench()
    load = FakeLoad(lambda i, rec: 0.5 * (i // 4 + 1), seconds=1.2)
    b.traffic("closed").drive(load, {"callers": 4})
    ctx = _ctx(load)
    ctx.trace = {"busy_s": 0.3, "window_s": 1.2}
    ctx.batches = {4: 3}
    ctx.stats = {"queries": 12, "hops": 60, "dist_calcs": 600}
    halves = [m["name"] for m in b.spec["per_layer"] + b.spec["end_to_end"]
              if m["name"].endswith(".csd")]
    assert len(halves) == 5
    for name in halves:
        source = name[:-len(".csd")]
        assert b.reader(name).read(ctx) == b.reader(source).read(ctx)
        assert b.reader(name).read(ctx) is not None


def test_fixture_pieces_come_first_then_the_benchmarks():
    b = fixture_bench()
    assert b.traffic("steady").__file__.startswith(FIX)
    assert os.path.dirname(b.traffic("closed").__file__) == os.path.join(
        CHIP, "traffic")
    assert b.reader("fixture.answered_share").__file__.startswith(FIX)
    with pytest.raises(FileNotFoundError):
        b.traffic("no-such-kind")
    with pytest.raises(FileNotFoundError):
        b.workload("no.such.cell")
    with pytest.raises(KeyError):
        b.config("no-such-config")


def test_a_new_cell_traffic_and_metric_are_files_alone(capsys):
    """tiny.steady has its own config, workload, traffic kind and metric
    reader, each a new file: none of the harness's files knows them."""
    out = run_fixture(capsys, "tiny.steady", "--trace", "1")
    assert out["correct"] is True
    assert out["metrics"]["fixture.answered_share"]["value"] == 100.0


# -- set-up: the index kept between runs, the shapes warmed -----------------------

@pytest.mark.parametrize("workload", ["tiny.closed", "tiny.csd"])
def test_the_second_run_loads_the_index(capsys, tmp_path, workload):
    bench = run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP),
                           index_dir=str(tmp_path))
    said = []
    for _ in range(2):
        rc = run_cell.run(["--workload", workload, "--seed", "11",
                           "--seconds", "1.5"], bench=bench, need_tpu=False)
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert json.loads(out[-1])["correct"] is True
        said += [line.split()[1] for line in out
                 if line.startswith("index: ")]
    assert said == ["built", "loaded"]
    assert len(os.listdir(tmp_path)) == 1


def test_the_index_key_follows_rows_settings_and_nothing_else():
    cfg = fixture_bench().config("tiny-hbm")
    rows = np.arange(24, dtype=np.uint8).reshape(3, 8)
    key = run_cell.index_key(cfg, rows)
    assert run_cell.index_key({**cfg, "max_batch": 64, "ef": 80}, rows) == key
    assert run_cell.index_key({**cfg, "M": 12}, rows) != key
    other = rows.copy()
    other[2, 7] += 1
    assert run_cell.index_key(cfg, other) != key


def test_warms_the_shapes_the_traffic_sends():
    b = fixture_bench()
    closed, poisson = b.traffic("closed"), b.traffic("poisson")
    every = [1, 2, 4, 8, 16, 32, 64]
    assert run_cell.warm_shapes(closed, {"callers": 256}, 64) == [64]
    assert run_cell.warm_shapes(closed, {"callers": 100}, 64) == every
    assert run_cell.warm_shapes(poisson, {"rate_qps": 80.0}, 64) == every


def test_held_queues_every_caller_before_a_batch():
    """Sends 1 ms apart outlast the batcher's 2 ms wait; held, the first
    batch still forms full."""
    import threading
    import time
    from types import SimpleNamespace

    from repro.serve.queue import RequestQueue
    q = RequestQueue()
    got = []
    t = threading.Thread(target=lambda: got.append(len(q.collect(16, 0.002))))
    t.start()
    time.sleep(0.05)                     # collect waits on an empty queue
    load = run_cell.Load(SimpleNamespace(queue=q), np.zeros((4, 2)),
                         {"k": 1, "ef": 1}, 1.0, 3, None, 1.0, dict)
    with load.held():
        for _ in range(32):
            q.put(np.zeros(2, np.float32))
            time.sleep(0.001)
    t.join(5.0)
    assert got == [16]


# -- the platform -----------------------------------------------------------------

def test_refuses_a_platform_that_is_not_tpu(capsys):
    rc = run_cell.run(["--workload", "hbm.saturate", "--seed", "1",
                       "--seconds", "1"])
    assert rc == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "TPU" in cap.err


def test_peaks_refuse_an_unknown_device_kind():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("cpu")


# -- counts -------------------------------------------------------------------------

def _numpy_beam(vecs, nbrs, q, entry, ef):
    """Beam search over a tiny graph that tallies what it reads."""
    read = {"row": 0, "norm": 0, "list": 0}
    dist = lambda i: float(((vecs[i] - q) ** 2).sum())  # noqa: E731
    seen, calcs, hops = {entry}, 1, 0
    read["row"] += vecs[entry].nbytes
    read["norm"] += 4
    cand, best = [(dist(entry), entry)], [(dist(entry), entry)]
    while cand:
        cand.sort()
        d, c = cand.pop(0)
        if len(best) >= ef and d > max(best)[0]:
            break
        hops += 1
        read["list"] += nbrs[c].nbytes
        for j in nbrs[c]:
            if j < 0 or j in seen:
                continue
            seen.add(j)
            calcs += 1
            read["row"] += vecs[j].nbytes
            read["norm"] += 4
            dj = dist(j)
            cand.append((dj, j))
            best = sorted(best + [(dj, j)])[:ef]
    return calcs, hops, read


def test_counts_match_numpy_on_a_tiny_graph():
    rng = np.random.default_rng(0)
    vecs = rng.integers(0, 256, (40, 16)).astype(np.uint8)
    nbrs = np.full((40, 6), -1, np.int32)
    for i in range(40):
        nbrs[i, :4] = rng.choice(np.delete(np.arange(40), i), 4, replace=False)
    calcs, hops, read = _numpy_beam(vecs.astype(np.float64), nbrs,
                                    rng.integers(0, 256, 16), 0, 5)
    # rows are read as stored: uint8, one byte per dimension
    assert counts.traversal_bytes(calcs, hops, 16, nbrs[0].nbytes) == (
        read["row"] // 8 + read["norm"] + read["list"])
    assert counts.traversal_ops(calcs, 16) == calcs * 32
    peak = counts.peaks("TPU v5 lite")
    t, bound = counts.least_time(819e9, 1.0, peak)
    assert (t, bound) == (1.0, "bandwidth")
    t, bound = counts.least_time(1.0, 393e12, peak)
    assert (t, bound) == (1.0, "compute")


# -- window arithmetic on a fake clock -----------------------------------------------

class _Fut:
    def __init__(self):
        self._cb = None

    def add_done_callback(self, cb):
        self._cb = cb

    def done(self):
        return True

    def exception(self):
        return None


class FakeLoad(run_cell.Load):
    """A Load whose server answers on a script: request i is answered at
    `answer(i)`, and the clock is whatever the script last said."""

    def __init__(self, answer, seconds, trace_seconds=None):
        self.answer, self.now = answer, 0.0
        super().__init__(None, np.zeros((8, 2)), {"k": 1, "ef": 1}, seconds,
                         3, None, trace_seconds or seconds, lambda: {})
        self.clock = lambda: self.now

    def send(self, qi, t_due=None):
        rec = run_cell.Rec(int(qi), self.now if t_due is None else t_due,
                           self.now, _Fut())
        self.records.append(rec)
        rec.t_done = self.answer(len(self.records) - 1, rec)
        return rec

    def next_done(self):
        pending = sorted((r for r in self.records if r.t_done is not None
                          and not getattr(r, "seen", False)),
                         key=lambda r: r.t_done)
        r = pending[0]
        r.seen = True
        self.now = max(self.now, r.t_done)
        return r


def _ctx(load, setup_s=1.0):
    recs = load.in_window(load.t_open, load.t_close)
    return run_cell.Context(
        setup_s=setup_s, window=(load.t_open, load.t_close), records=recs,
        layer=(load.layer_open, load.layer_close), layer_records=recs,
        batches={}, stats={}, cache=None, trace=None, shape={},
        peak={}, notes=[])


def test_closed_loop_window_spans_whole_batches():
    """4 callers; a batch of 4 takes 0.5 s, so answers come in fours at
    0.5, 1.0, 1.5, ... The window opens at the first answer (0.5) and
    closes at the first at or past 0.5 + 1.2 (2.0): 3 batches, 12 answers
    in 1.5 s."""
    closed = fixture_bench().traffic("closed")
    load = FakeLoad(lambda i, rec: 0.5 * (i // 4 + 1), seconds=1.2)
    closed.drive(load, {"callers": 4})
    assert (load.t_open, load.t_close) == (0.5, 2.0)
    ctx = _ctx(load)
    assert len(ctx.records) == 12
    qps = fixture_bench().reader("qps").read(ctx)
    assert qps == pytest.approx(12 / 1.5)


def test_closed_loop_callers_of_a_batch_send_together():
    """8 callers, batches of 4 answered every 0.5 s: each batch's callers
    send again only once all four answers are in, so sends go out in
    fours, each four at the time of its batch's last answer, until the
    answers at 2.0 close the window."""
    closed = fixture_bench().traffic("closed")
    load = FakeLoad(lambda i, rec: 0.5 * (i // 4 + 1), seconds=1.2)
    load.cfg["max_batch"] = 4
    closed.drive(load, {"callers": 8})
    sent = [r.t_sent for r in load.records]
    assert sent[:8] == [0.0] * 8
    assert sent[8:] == [0.5] * 4 + [1.0] * 4 + [1.5] * 4
    assert (load.t_open, load.t_close) == (0.5, 2.0)


def test_open_loop_latency_counts_from_when_due():
    """A stalled server answers everything sent in the first second at
    t0 + 1.0: a request due at t0 + 0.1 waited 0.9 s, however late it was
    sent."""
    poisson = fixture_bench().traffic("poisson")
    load = FakeLoad(lambda i, rec: None, seconds=2.0)
    load.now = 1e9                               # never sleeps
    load.open = lambda t=None: 10.0              # window starts at 10 s
    load.close = lambda t: setattr(load, "t_close", t)
    t0 = 10.001
    due = poisson.schedule(50.0, 2.0, load.rng)
    assert len(due) == 100 and due[0] == 0.0
    poisson.drive(load, {"rate_qps": 50.0})
    for r in load.records:
        r.t_done = max(t0 + 1.0, r.t_due + 0.01)
    load.t_open = t0
    ctx = _ctx(load)
    assert len(ctx.records) == 100
    lat = sorted((r.t_done - r.t_due) * 1e3 for r in ctx.records)
    b = fixture_bench()
    assert b.reader("p50_ms").read(ctx) == pytest.approx(np.percentile(lat, 50))
    assert b.reader("p95_ms").read(ctx) == pytest.approx(np.percentile(lat, 95))
    assert max(lat) == pytest.approx(1000.0, abs=1e-6)


def test_poisson_offers_every_seed_the_same_gaps():
    poisson = fixture_bench().traffic("poisson")
    a = poisson.schedule(200.0, 30.0, np.random.default_rng(1))
    b = poisson.schedule(200.0, 30.0, np.random.default_rng(2))
    assert len(a) == len(b) == 6000
    assert not np.array_equal(a, b)
    full = np.sort(-np.log1p(-(np.arange(6000) + 0.5) / 6000) / 200.0)
    for d in (a, b):
        # every seed offers the same gaps, less the one after the last
        left_out = full.sum() - np.diff(d).sum()
        assert np.isclose(full, left_out, rtol=1e-6, atol=1e-9).any()
    assert np.mean(full) == pytest.approx(1 / 200.0, rel=0.01)


# -- trace reduction ------------------------------------------------------------------

def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def test_trace_reduce_on_hand_counted_events():
    """Window 0-100 ns. Device ops 10-30, 20-40, 60-70 (busy 40); modules
    jit_a(1) 10-40 and jit_b 60-70. Gaps 0-10, 40-60, 70-100. Annotation
    bench.x (0-50) overlaps 10 + 10 of them, bench.y (38-62) 20, and 30
    lie under no annotation; host events JAX writes itself are not
    annotations."""
    planes = [
        _plane("/host:CPU", main=[("bench.window", 0.0, 100.0),
                                  ("bench.x", 0.0, 50.0),
                                  ("bench.y", 38.0, 24.0),
                                  ("PjitFunction(f)", 70.0, 30.0)]),
        _plane("/device:TPU:0",
               **{"XLA Modules": [("jit_a(1)", 10.0, 30.0),
                                  ("jit_b", 60.0, 10.0)],
                  "XLA Ops": [("fusion", 10.0, 20.0), ("copy", 20.0, 20.0),
                              ("fusion", 60.0, 10.0)]}),
    ]
    r = trace_reduce.reduce(planes)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["modules"] == {"a": {"seconds": pytest.approx(30e-9), "count": 1},
                            "b": {"seconds": pytest.approx(10e-9), "count": 1}}
    assert dict(r["device_ops"]) == {"fusion": pytest.approx(30e-9),
                                     "copy": pytest.approx(20e-9)}
    assert dict(r["idle_gaps"]) == {
        "bench.x": pytest.approx(20e-9), "bench.y": pytest.approx(20e-9),
        trace_reduce.NO_HOST_EVENT: pytest.approx(30e-9)}


def test_trace_reduce_on_a_recorded_tpu_trace():
    """fixtures/trace: two jitted lambdas, three times each, on one TPU
    v5e, inside bench.window (42767619-65804579 ns), with bench.search
    around the first call and bench.host around a 2 ms sleep. Counted by
    hand from the events: the first module ends before the window opens,
    so 5 of 6 modules (2415 + 3723 + 2412 + 3563 + 2606 ns) and 9 of 12
    ops (busy 14695 ns) fall in it; of the idle time, bench.search
    overlaps 1000440 + 890990 + 707420 ns, bench.host 1406754 + 1015165
    + 1865809 + 1050103 + 1536501 + 1001266 ns, and 12547817 ns lie
    under no annotation."""
    r = trace_reduce.reduce(trace_reduce.load(os.path.join(FIX, "trace")))
    assert r["window_s"] == pytest.approx(23036960e-9)
    assert r["busy_s"] == pytest.approx(14695e-9)
    assert r["devices"] == 1
    assert r["modules"] == {"_lambda": {"seconds": pytest.approx(14719e-9),
                                        "count": 5}}
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(14695e-9)
    assert dict(r["idle_gaps"]) == {
        "bench.search": pytest.approx(2598850e-9),
        "bench.host": pytest.approx(7875598e-9),
        trace_reduce.NO_HOST_EVENT: pytest.approx(12547817e-9)}


def _op(opcode, shape="s32[64,10]{1,0}"):
    return f"%{opcode}.1 = {shape} {opcode}({shape} %p.2), dimensions={{1}}"


def test_trace_reduce_sums_collectives_over_devices_in_the_window():
    """Window 0-100 ns on four devices. Collectives by opcode, whatever
    the op's own name: an all-gather 90-110 (10 in the window) and an
    all-reduce 20-30 on TPU:0; an all-gather-start 10-20 nested in a
    while loop 0-50 and its -done 40-45 on TPU:1; a collective-permute
    -5-5 (5 in) and a reduce-scatter 50-60 on TPU:2; no collective on
    TPU:3. A copy-start is no collective, nor is a fusion named after
    one. Summed over the devices: 10+10 + 10+5 + 5+10 = 50 ns."""
    host = _plane("/host:CPU", main=[("bench.window", 0.0, 100.0)])
    ag_start = _op("all-gather-start", "(s32[64,10]{1,0}, s32[64,40]{1,0})")
    dev = [
        [(_op("all-gather"), 90.0, 20.0), (_op("all-reduce"), 20.0, 10.0),
         (_op("fusion"), 30.0, 20.0)],
        [("%while.3 = (s32[], s32[64,40]{1,0}) while((s32[], s32[64,40]{1,0})"
          " %t), condition=%c, body=%b", 0.0, 50.0),
         (ag_start, 10.0, 10.0), (_op("all-gather-done"), 40.0, 5.0)],
        [(_op("collective-permute"), -5.0, 10.0),
         (_op("reduce-scatter"), 50.0, 10.0),
         ("%copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0}"
          " %x)", 60.0, 10.0)],
        [("%all-gather-fusion = s32[64,40]{1,0} fusion(s32[64,10]{1,0} %p),"
          " kind=kLoop, calls=%f", 0.0, 40.0)],
    ]
    planes = [host] + [_plane(f"/device:TPU:{i}", **{"XLA Ops": ev})
                       for i, ev in enumerate(dev)]
    r = trace_reduce.reduce(planes)
    assert r["devices"] == 4
    assert r["collective_s"] == pytest.approx(50e-9)
    assert r["collective_s"] <= r["busy_s"] * r["devices"]


def test_trace_reduce_takes_a_collectives_overlap_once():
    """An all-reduce-start 0-30 and an all-reduce-done 20-40 overlap by
    10 ns: the device spent 40 ns in collectives, not 50."""
    planes = [_plane("/host:CPU", main=[("bench.window", 0.0, 100.0)]),
              _plane("/device:TPU:0", **{"XLA Ops": [
                  (_op("all-reduce-start"), 0.0, 30.0),
                  (_op("all-reduce-done"), 20.0, 20.0)]})]
    r = trace_reduce.reduce(planes)
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(40e-9)


def test_trace_reduce_finds_no_collective_on_one_chip():
    """The one-chip fixture trace ran no collective; its other fields
    are those test_trace_reduce_on_a_recorded_tpu_trace counted."""
    r = trace_reduce.reduce(trace_reduce.load(os.path.join(FIX, "trace")))
    assert r["collective_s"] == 0.0
    assert set(r) == {"window_s", "busy_s", "devices", "modules",
                      "device_ops", "idle_gaps", "collective_s"}
    assert r["busy_s"] == pytest.approx(14695e-9)


def test_trace_reduce_finds_nothing_off_the_chip():
    assert trace_reduce.reduce([_plane("/host:CPU", main=[
        ("bench.window", 0.0, 10.0)])]) is None


# -- correct ------------------------------------------------------------------------

def test_sound_run_is_correct(capsys):
    out = run_fixture(capsys, "tiny.closed")
    assert out["correct"] is True
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["checks"]["miss_at_10"]["value"] <= (
        out["checks"]["miss_at_10"]["limit"])


def test_csd_run_holds_its_guarantee(capsys):
    out = run_fixture(capsys, "tiny.csd")
    assert out["correct"] is True
    assert 0 < out["checks"]["resident_over_cache"]["value"] <= 1.0


@pytest.fixture
def fresh_jit():
    import jax
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", True)


def _wrap_search(monkeypatch, change):
    from repro.api.backends import PartitionedBackend
    orig = PartitionedBackend.search

    def search(self, queries, k, ef, rerank, with_stats):
        ids, dists, stats = orig(self, queries, k, ef, rerank, with_stats)
        ids, dists = change(np.array(ids), np.array(dists))
        return ids, dists, stats
    monkeypatch.setattr(PartitionedBackend, "search", search)


def test_fault_state_left_unchanged(capsys, monkeypatch, fresh_jit):
    """Layer 0 returns the state it was given: no hop is made."""
    import repro.core.search as cs

    def no_hops(db, q, qsq, ep, ep_d, p, lut=None):
        import jax.numpy as jnp
        fin_d = jnp.full((p.ef,), jnp.inf).at[0].set(ep_d)
        fin_i = jnp.full((p.ef,), -1, jnp.int32).at[0].set(ep)
        return fin_d, fin_i, jnp.int32(0), jnp.int32(0)
    monkeypatch.setattr(cs, "_search_layer0", no_hops)
    out = run_fixture(capsys, "tiny.closed")
    assert out["correct"] is False
    assert out["checks"]["miss_at_10"]["value"] > 0.5


def test_fault_half_the_batch_left_out(capsys, monkeypatch):
    """The second half of each batch gets the first half's answers."""
    def half(ids, dists):
        h = (ids.shape[0] + 1) // 2
        ids[h:], dists[h:] = ids[:ids.shape[0] - h], dists[:ids.shape[0] - h]
        return ids, dists
    _wrap_search(monkeypatch, half)
    out = run_fixture(capsys, "tiny.closed")
    assert out["correct"] is False


def test_fault_answer_altered(capsys, monkeypatch):
    """One id of every answer is changed where it is produced."""
    def alter(ids, dists):
        ids[:, 0] = (ids[:, 0] + 1) % 1200
        return ids, dists
    _wrap_search(monkeypatch, alter)
    out = run_fixture(capsys, "tiny.closed")
    assert out["correct"] is False
    assert out["checks"]["dist_gap"]["value"] > (
        out["checks"]["dist_gap"]["limit"])


def test_control_fails_at_a_test_size():
    cfg = fixture_bench().config("tiny-hbm")
    cfg = {**cfg, "n": 4000, "query_pool": 256,
           "limits": run_cell.Bench().config("sift-hnsw-hbm")["limits"]}
    out = control.control(cfg, seed=2**31 + 9)
    assert out["correct"] is False
    assert out["miss_at_10"] > 3 * cfg["limits"]["miss_at_10"]


def test_reference_judges_ties_repeats_and_gaps():
    rows = np.array([[0, 0], [1, 0], [0, 1], [3, 3]], np.uint8)
    queries = np.zeros((1, 2), np.float32)
    kth = reference.kth_distance(rows, queries, 2)
    assert kth.tolist() == [1]
    judge = lambda ids, d: reference.judge(  # noqa: E731
        rows, queries, kth, np.array([0]), np.array([ids]), np.array([d]))
    assert judge([1, 2], [1.0, 1.0])["miss_at_k"] == 0.0      # a tie
    assert judge([1, 1], [1.0, 1.0])["miss_at_k"] == 0.5      # a repeat
    assert judge([3, -1], [18.0, np.inf])["miss_at_k"] == 1.0
    assert judge([0, 1], [0.0, 2.0])["dist_gap"] == 1.0
