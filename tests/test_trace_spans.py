"""Program spans on the profiler's clock (repro.obs.trace): live spans as
`jax.profiler` annotations, the free disabled path, `self_times`, and the
spans the out-of-core path records (`store-read`, `device-wait` under
`hop` and `upper-hop`)."""

import glob
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import PROFILER, TRACER, Tracer
from repro.obs import trace as trace_mod
from repro.obs.profile import Profiler
from repro.obs.trace import self_times


def _host_events(trace_dir):
    """(line, name, start_ns, end_ns) of every event off the devices."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((line.name, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def test_enabled_spans_land_on_the_profiler_host_plane(tmp_path):
    t = Tracer(enabled=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with t.span("outer"):
            with t.child_span("inner"):
                jnp.arange(8.0).sum().block_until_ready()
        t.record_span("retroactive", time.perf_counter() - 1e-3,
                      time.perf_counter())
    jax.profiler.stop_trace()
    ev = {name: (line, s, e) for line, name, s, e in _host_events(tmp_path)}
    assert "retroactive" not in ev           # memory and Chrome export only
    win, outer, inner = ev["bench.window"], ev["outer"], ev["inner"]
    assert win[0] == outer[0] == inner[0]    # the one thread that ran them
    assert win[1] <= outer[1] <= inner[1] <= inner[2] <= outer[2] <= win[2]
    assert [s["name"] for s in t.spans()] == ["inner", "outer",
                                              "retroactive"]


def test_disabled_tracer_hands_out_the_shared_noop(monkeypatch):
    """No clock read and no JAX call on the disabled path, and the
    profiler that would take it over is off unless switched on."""
    assert PROFILER.enabled is False and Profiler().enabled is False

    def boom(*a, **kw):
        raise AssertionError("the disabled path did work")
    monkeypatch.setattr(trace_mod, "TraceAnnotation", boom)
    monkeypatch.setattr(trace_mod, "time", SimpleNamespace(perf_counter=boom))
    TRACER.configure(enabled=False)
    a, b, c = TRACER.span("a"), TRACER.span("b"), TRACER.child_span("c")
    assert a is b is c is trace_mod._NOOP
    with a:
        with c as inner:
            inner.set(rows=1)
    assert TRACER.spans() == []


def _span(i, name, t0, t1, parent=0, tid="main"):
    return {"name": name, "t0": t0, "t1": t1, "id": i, "parent": parent,
            "tid": tid, "trace": 1, "attrs": {}}


def test_self_times_on_a_hand_built_tree():
    """a [0, 10] holds b [1, 4] (which holds d [2, 3]) and c [3, 6];
    e [8, 12] stands alone; f [20, 21] lies past the window [0.5, 9].
    Clipped: a 8.5 less b and c's union [1, 6] = 3.5; b 3 - 1 = 2; c 3;
    d 1; e clipped to [8, 9] = 1; f nothing."""
    spans = [_span(1, "a", 0.0, 10.0), _span(2, "b", 1.0, 4.0, 1),
             _span(3, "c", 3.0, 6.0, 1), _span(4, "d", 2.0, 3.0, 2),
             _span(5, "e", 8.0, 12.0), _span(6, "f", 20.0, 21.0)]
    st = self_times(spans, 0.5, 9.0)
    assert st == pytest.approx({"a": 3.5, "b": 2.0, "c": 3.0, "d": 1.0,
                                "e": 1.0})
    # a child reaching past its parent counts only inside the parent
    st = self_times([_span(1, "a", 0.0, 2.0), _span(2, "b", 1.0, 5.0, 1)],
                    0.0, 10.0)
    assert st == pytest.approx({"a": 1.0, "b": 4.0})
    # names add up over their spans; the whole window is the sum
    st = self_times([_span(1, "x", 0.0, 1.0), _span(2, "x", 2.0, 4.0)],
                    0.5, 3.0)
    assert st == pytest.approx({"x": 1.5})


@pytest.fixture(scope="module")
def tiny_csd(tmp_path_factory):
    """A 400-row csd service (one partition, upper layers present)."""
    from repro.api import IndexSpec, SearchService
    from repro.core.hnsw_graph import HNSWConfig
    from repro.data import clustered_vectors
    vecs = clustered_vectors(400, 16, k=8, seed=3)
    spec = IndexSpec(backend="csd", num_partitions=1, block_size=512,
                     cache_bytes=8 * 1024, prefetch=True,
                     storage_path=str(tmp_path_factory.mktemp("csd")),
                     hnsw=HNSWConfig(M=8, ef_construction=32, seed=0))
    svc = SearchService.build(vecs, spec)
    yield svc, vecs
    svc.backend.reader.close()


def test_store_read_covers_the_row_assembly(tiny_csd, monkeypatch):
    """With each row's addressing slowed by 2 ms, the `store-read` span
    is as long as the whole of `read_rows`, row assembly included."""
    svc, _ = tiny_csd
    reader = svc.backend.reader
    slow = reader.blockfile.row_span

    def row_span(table, row):
        time.sleep(0.002)
        return slow(table, row)
    monkeypatch.setattr(reader.blockfile, "row_span", row_span)
    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.clear()
    with TRACER.span("root"):
        t0 = time.perf_counter()
        rows = reader.read_rows("vectors", np.arange(20) * 7)
        t1 = time.perf_counter()
    assert rows.shape[0] == 20
    sp, = [s for s in TRACER.spans() if s["name"] == "store-read"]
    assert sp["t1"] - sp["t0"] >= 0.95 * (t1 - t0)
    assert sp["t1"] - sp["t0"] >= 2 * 20 * 0.002   # lookup and assembly
    assert sp["attrs"]["table"] == "vectors"
    assert sp["attrs"]["rows"] == 20 and sp["attrs"]["blocks"] >= 1
    assert 0 <= sp["attrs"]["misses"] <= sp["attrs"]["blocks"]


def test_csd_search_records_device_waits_under_hop_and_upper_hop(tiny_csd):
    from repro.api import SearchRequest
    svc, vecs = tiny_csd
    assert int(np.max(svc.backend.reader.max_level)) >= 1
    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.clear()
    with TRACER.span("root"):
        svc.search(SearchRequest(vecs[:8] + 0.5, k=5, ef=16))
    spans = TRACER.spans()
    by_id = {s["id"]: s for s in spans}
    parents = {by_id[s["parent"]]["name"] for s in spans
               if s["name"] == "device-wait"}
    assert {"hop", "upper-hop", "traversal"} <= parents
    under_hop = {s["name"] for s in spans
                 if s["parent"] in by_id
                 and by_id[s["parent"]]["name"] == "hop"}
    assert {"device-wait", "store-read", "hop-kernel"} <= under_hop
