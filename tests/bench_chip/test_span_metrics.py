"""CPU tests of the chip benchmark's span readers (benchmarks/chip/metrics,
`_spans.py`): the six program-span metrics on the fixture cells, the
tracer left off by an untraced run, and the tracer put back after a test
that loads the readers.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_chip
"""

from __future__ import annotations

import ast
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
FIX = os.path.join(CHIP, "fixtures")
sys.path.insert(0, CHIP)

import run_cell  # noqa: E402

from repro.obs import TRACER  # noqa: E402

# each new metric on the fixture cell that runs its path
SPAN_CELLS = {
    "csd.plan_share": "tiny.csd",
    "store.read_share": "tiny.csd",
    "csd.device_wait_share": "tiny.csd",
    "serve.batch_host_ms": "tiny.closed",
    "serve.batch_host_ms.poisson": "tiny.poisson",
    "serve.replica_wait_ms.p50": "tiny.poisson",
}
CSD_SHARES = ("csd.plan_share", "store.read_share", "csd.device_wait_share")


@pytest.fixture(scope="module")
def span_bench(tmp_path_factory) -> run_cell.Bench:
    """The fixture benchmark with the span metrics added on its tiny
    cells, written beside a module-wide index directory."""
    tmp = tmp_path_factory.mktemp("span_bench")
    with open(os.path.join(FIX, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for c in spec["configs"]:
        c["file"] = os.path.join(FIX, c["file"])
    for name, cell in SPAN_CELLS.items():
        spec["per_layer"].append({**real[name], "workloads": [cell]})
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return run_cell.Bench(str(path), (FIX, CHIP),
                          index_dir=str(tmp / "index"))


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


def run(bench, capsys, workload: str, trace: int) -> tuple[dict, list]:
    rc = run_cell.run(["--workload", workload, "--seed", str(2**31 + 17),
                       "--seconds", "2", "--trace", str(trace)],
                      bench=bench, need_tpu=False)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_every_span_metric_is_in_the_benchmark():
    b = run_cell.Bench()
    real = {m["name"]: m for m in b.spec["per_layer"]}
    for name in SPAN_CELLS:
        m = real[name]
        assert m["source"] == "program_span"
        for cell in m["workloads"]:
            assert name in {x["name"] for x in b.metrics(cell, True)}
            assert m["moves"] in {x["name"] for x in b.metrics(cell, False)}


def test_csd_shares_on_a_traced_fixture_run(span_bench, capsys):
    """Each csd share is read, lies in [0, 100], and together they take
    no more of the window than the replica thread was busy."""
    out, lines = run(span_bench, capsys, "tiny.csd", 1)
    assert out["correct"] is True
    got = {k: out["metrics"][k]["value"] for k in CSD_SHARES}
    for v in got.values():
        assert 0.0 < v <= 100.0
    note = next(x for x in lines if x.startswith("spans: window_s="))
    busy = float(note.split("replica_busy_share=")[1].split()[0])
    assert sum(got.values()) <= busy + 1e-6
    shares = ast.literal_eval(note.split("self_share=")[1])
    assert {"hop", "store-read", "device-wait", "hop-kernel"} <= set(shares)
    assert sum(shares.values()) == pytest.approx(busy, rel=1e-6)


@pytest.mark.parametrize("workload,names", [
    ("tiny.closed", ["serve.batch_host_ms"]),
    ("tiny.poisson", ["serve.batch_host_ms.poisson",
                      "serve.replica_wait_ms.p50"]),
])
def test_batch_times_on_a_traced_fixture_run(span_bench, capsys, workload,
                                             names):
    out, lines = run(span_bench, capsys, workload, 1)
    assert out["correct"] is True
    for name in names:
        assert out["metrics"][name]["value"] >= 0.0
    med = ast.literal_eval(next(
        x for x in lines if x.startswith("spans: batches=")).split(
            "medians_ms=")[1])
    assert med["host_ms"] > 0.0 and med["device_wait_ms"] > 0.0
    assert med["replica_wait_ms"] >= 0.0


def test_an_untraced_run_leaves_the_tracer_off(span_bench, capsys):
    assert not TRACER.enabled
    out, _ = run(span_bench, capsys, "tiny.csd", 0)
    assert out["correct"] is True
    assert not set(SPAN_CELLS) & set(out["metrics"])
    assert not TRACER.enabled
    assert TRACER.spans() == []


def test_loading_the_readers_turns_the_tracer_on(span_bench):
    for name in SPAN_CELLS:
        TRACER.configure(enabled=False)
        assert callable(span_bench.reader(name).read)
        assert TRACER.enabled


def test_the_next_test_sees_the_tracer_off():
    """Runs after the test above: tests/conftest.py put it back."""
    assert not TRACER.enabled
