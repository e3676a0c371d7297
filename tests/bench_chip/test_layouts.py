"""CPU tests of how the chip benchmark opens a configuration: the backend
it names, its layout over the cell's chips (`replicas`, a distributed
mesh), the refusals of what a cell cannot hold, and counters that a
backend does not track.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip/test_layouts.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
FIX = os.path.join(CHIP, "fixtures")
sys.path.insert(0, CHIP)

import data  # noqa: E402
import run_cell  # noqa: E402


class PatchedBench(run_cell.Bench):
    """The fixture benchmark with one configuration's keys and one cell's
    chips changed in memory."""

    def __init__(self, index_dir: str, cfg: dict | None = None,
                 chips: int | None = None):
        super().__init__(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP),
                         index_dir=index_dir)
        self.patch, self.chips = cfg or {}, chips

    def config(self, name):
        return {**super().config(name), **self.patch}

    def workload(self, name):
        w = super().workload(name)
        return w if self.chips is None else {**w, "chips": self.chips}


# -- four devices: a distributed mesh and a replica on each -------------------

@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """What the fixture cells tiny.replicas and tiny.dist showed on four
    CPU devices, one seed, one index directory (layout_worker.py)."""
    tmp = tmp_path_factory.mktemp("layouts")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp / "jax"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src")]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "bench_chip",
                                      "layout_worker.py"),
         str(tmp / "index")],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    facts = json.loads(r.stdout.strip().splitlines()[-1])
    assert facts["devices"] == 4
    return facts


@pytest.mark.parametrize("cell", ["tiny.replicas", "tiny.dist"])
def test_four_chip_cell_is_correct_and_compiles_nothing_in_window(four, cell):
    run = four[cell]
    assert run["rc"] == 0, run["stderr_tail"]
    assert run["result"]["correct"] is True
    assert run["result"]["device"]["count"] == 4
    assert run["compiles_in_window"] == [0]


def test_distributed_serves_the_partitioned_ids(four):
    """tiny.dist and tiny.replicas open the same four sub-graphs; on the
    same seed every query gets the same ids from the mesh as from the
    partitioned engine, and always the same ids."""
    dist = four["tiny.dist"]["answers"]
    part = four["tiny.replicas"]["answers"]
    assert len(dist) == len(part) == 96            # the whole query pool
    for qi, ids in dist.items():
        assert len(ids) == 1 and ids == part[qi], qi


def test_distributed_mesh_spans_the_cells_chips(four):
    assert four["tiny.dist"]["replicas"] == [
        {"replica": 0, "devices": [0, 1, 2, 3], "batches":
         four["tiny.dist"]["replicas"][0]["batches"],
         "queries": four["tiny.dist"]["result"]["attempted"]}]


def test_every_replica_serves_on_its_own_device(four):
    reps = four["tiny.replicas"]["replicas"]
    assert [r["devices"] for r in reps] == [[0], [1], [2], [3]]
    assert all(r["queries"] > 0 for r in reps)
    assert sum(r["queries"] for r in reps) == (
        four["tiny.replicas"]["result"]["attempted"])


def test_untracked_hops_are_absent_with_a_note(four):
    """The distributed backend counts distance evaluations, not hops."""
    run = four["tiny.dist"]
    assert "traversal.hops_per_query" not in run["result"]["metrics"]
    assert run["result"]["metrics"]["traversal.dist_calcs_per_query"][
        "value"] > 0
    assert run["notes"] == ["traversal.hops_per_query: not read: the "
                            "backend's QueryStats has no hops"]


def test_distributed_reuses_the_saved_graph(four):
    """Both cells' configurations share the graph keys: one build, and
    the distributed index writes nothing of its own."""
    assert list(four["index_dir"].values()) == [["meta.json",
                                                 "partitioned"]]


# -- refusals, before anything is built --------------------------------------

@pytest.mark.parametrize("workload,cfg,chips,says", [
    ("tiny.closed", {"backend": "hnsw"}, None, "backend 'hnsw'"),
    ("tiny.closed", {"backend": "nosuch"}, None, "backend 'nosuch'"),
    ("tiny.closed", {"replicas": 2}, None, "2 replicas"),
    ("tiny.closed", {"replicas": 0}, None, "replicas=0"),
    ("tiny.replicas", {}, 2, "4 replicas"),
    ("tiny.replicas", {"backend": "exact"}, 3, "4 replicas"),
    ("tiny.csd", {"replicas": 2}, None, "2 replicas"),
    ("tiny.dist", {"replicas": 8}, None, "8 replicas"),
    ("tiny.dist", {}, 3, "num_partitions=4"),
    ("tiny.dist", {"num_partitions": 6}, None, "num_partitions=6"),
])
def test_refuses_what_the_cell_cannot_hold(capsys, tmp_path, workload, cfg,
                                           chips, says):
    index = tmp_path / "index"
    rc = run_cell.run(["--workload", workload, "--seed", str(2**31 + 3),
                       "--seconds", "1"],
                      bench=PatchedBench(str(index), cfg, chips),
                      need_tpu=False)
    assert rc == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert says in cap.err
    assert not index.exists()                  # nothing built or written


def test_refuses_fewer_devices_than_the_cell_asks_for(capsys, tmp_path):
    """Off the chip too: a cell that asks for one chip more than the
    process sees."""
    import jax
    chips = len(jax.devices()) + 1
    rc = run_cell.run(["--workload", "tiny.closed", "--seed", "5",
                       "--seconds", "1"],
                      bench=PatchedBench(str(tmp_path / "index"),
                                         chips=chips),
                      need_tpu=False)
    assert rc == 2
    cap = capsys.readouterr()
    assert cap.out == "" and f"needs {chips}" in cap.err
    assert not (tmp_path / "index").exists()


# -- the real configurations open as before ---------------------------------

def test_graph_keys_of_the_real_configs_are_unchanged():
    """The index key hashes these settings (with the rows and the
    program's source): the same as before configurations named their
    layout, and shared by hbm, csd and a distributed copy of hbm."""
    assert run_cell.GRAPH_KEYS == (
        "n", "query_pool", "dim", "dtype", "metric", "num_partitions", "M",
        "ef_construction", "graph_seed")
    b = run_cell.Bench()
    hbm, csd = b.config("sift-hnsw-hbm"), b.config("sift-hnsw-csd")
    graph = {"n": 100000, "query_pool": 4096, "dim": 128, "dtype": "uint8",
             "metric": "l2", "num_partitions": 4, "M": 16,
             "ef_construction": 100, "graph_seed": 0}
    for cfg in (hbm, csd):
        assert {k: cfg[k] for k in run_cell.GRAPH_KEYS} == graph
        assert "replicas" not in cfg
        assert run_cell.refusal(cfg, 1) is None
    rows = np.arange(4096, dtype=np.uint8).reshape(32, 128)
    key = run_cell.index_key(hbm, rows)
    dist = {**hbm, "backend": "distributed"}
    assert run_cell.index_key(csd, rows) == key
    assert run_cell.index_key(dist, rows) == key
    assert run_cell.refusal(dist, 4) is None
    assert run_cell.refusal(dist, 3) is not None


@pytest.fixture(scope="module")
def tiny_rows():
    cfg = run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"),
                         (FIX, CHIP)).config("tiny-hbm")
    rows, pool = data.bigann_rows(cfg["n"], cfg["query_pool"], 5, cfg["dim"])
    return cfg, rows, pool


def _ids(svc, pool):
    from repro.api import SearchRequest
    return np.asarray(svc.search(SearchRequest(pool[:16], k=10, ef=40)).ids)


def test_each_arm_opens_its_own_directories(tiny_rows, tmp_path):
    """partitioned: `<key>/partitioned` and `meta.json`, built once; csd:
    its store beside them; distributed (a one-device mesh here) and exact
    write nothing. The graph arms serve the same ids for the same
    queries."""
    import jax
    cfg, rows, pool = tiny_rows
    fix = run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP))
    index = str(tmp_path)
    used = jax.devices()[:1]
    key = run_cell.index_key(cfg, rows)
    listing = lambda: sorted(os.listdir(os.path.join(index, key)))  # noqa

    svc, shape, built = run_cell.open_service(cfg, rows, index, used)
    assert built and os.listdir(index) == [key]
    assert listing() == ["meta.json", "partitioned"]
    assert shape["row_bytes"] == 128 and shape["nbr_list_bytes"] > 0
    want = _ids(svc, pool)
    svc, _, built = run_cell.open_service(cfg, rows, index, used)
    assert not built and type(svc.backend).__name__ == "PartitionedBackend"
    assert np.array_equal(_ids(svc, pool), want)

    csd = fix.config("tiny-csd")
    svc, shape, built = run_cell.open_service(csd, rows, index, used)
    store = f"csd-{csd['block_size']}-{csd['cache_divisor']}"
    assert built and listing() == sorted(["meta.json", "partitioned", store])
    assert np.array_equal(_ids(svc, pool), want)
    svc.backend.reader.close()

    dist = {**cfg, "backend": "distributed"}
    svc, shape, built = run_cell.open_service(dist, rows, index, used)
    assert not built and type(svc.backend).__name__ == "DistributedBackend"
    assert svc.backend.mesh.devices.tolist() == used
    assert listing() == sorted(["meta.json", "partitioned", store])
    assert np.array_equal(_ids(svc, pool), want)

    exact = {**cfg, "backend": "exact"}
    svc, shape, built = run_cell.open_service(exact, rows, index, used)
    assert type(svc.backend).__name__ == "ExactBackend"
    assert (shape["dim"], shape["row_bytes"]) == (128, 128)
    assert os.listdir(index) == [key]
    assert listing() == sorted(["meta.json", "partitioned", store])


# -- counters a backend does not track ---------------------------------------

def _ctx(stats, trace=None):
    return run_cell.Context(
        setup_s=1.0, window=(0.0, 1.0), records=[], layer=(0.0, 1.0),
        layer_records=[], batches={}, stats=stats, cache=None, trace=trace,
        shape={"dim": 128, "row_bytes": 128, "nbr_list_bytes": 128},
        peak={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
              "int8_ops_per_s": 393e12}, notes=[])


@pytest.mark.parametrize("metric,missing", [
    ("traversal.hops_per_query", "hops"),
    ("traversal.hops_per_query.csd", "hops"),
    ("traversal.dist_calcs_per_query", "dist_calcs"),
    ("search_partitioned_roofline", "hops"),
    ("csd_layer0_roofline", "hops"),
])
def test_an_untracked_counter_reads_nothing_with_a_note(metric, missing):
    b = run_cell.Bench()
    trace = {"window_s": 1.0, "busy_s": 0.5,
             "modules": {m: {"seconds": 0.5, "count": 4} for m in (
                 "search_partitioned", "_layer0_step", "_upper_step")}}
    full = {"queries": 10, "hops": 1000, "dist_calcs": 20000}
    ctx = _ctx(dict(full), trace)
    assert b.reader(metric).read(ctx) > 0
    assert not any("not read" in n for n in ctx.notes)
    stats = {k: v for k, v in full.items() if k != missing}
    ctx = _ctx(stats, trace)
    assert b.reader(metric).read(ctx) is None
    assert any(n.endswith(f"not read: the backend's QueryStats has no "
                          f"{missing}") for n in ctx.notes)
