"""Chip layouts of the benchmark harness on four CPU devices.

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/bench_chip/layout_worker.py <index_dir>

Runs the fixture cells `tiny.replicas` (four partitioned replicas, one a
device) and `tiny.dist` (the distributed backend on a mesh of the four
devices, traced) through `run_cell.run` on the same seed, then `tiny.dist`
again under each of three faults: the exchange between devices left out
(each device's stage-2 gather returns its own candidates alone), half of
each batch left out (its second half served the first half's answers),
and one id of every answer altered; and prints, as its last line,
one JSON object of what each run showed: its exit code and result line,
the compiles in its window, each replica's devices and queries, its
notes, the ids it served for each pool query, and the QueryStats counters
that every answer carried. Launched by test_layouts.py in a subprocess,
so that the test process keeps its one-device view.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()

import contextlib  # noqa: E402
import functools  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
FIX = os.path.join(CHIP, "fixtures")
sys.path.insert(0, CHIP)

import reference  # noqa: E402
import run_cell  # noqa: E402

SEED = 2**31 + 77
COUNTERS = ("hops", "dist_calcs")        # the QueryStats fields run_cell reads
_REPLICA = re.compile(r"^replica (\d+): devices \[([\d, ]*)\] batches (\d+) "
                      r"queries (\d+)$")


@contextlib.contextmanager
def no_exchange():
    """The stage-2 all_gather left out: each device gathers copies of its
    own candidates in place of every device's."""
    import jax
    import jax.numpy as jnp
    gather = jax.lax.all_gather

    def own(x, axis_name, *, axis=0, tiled=False):
        return jnp.concatenate([x] * jax.lax.axis_size(axis_name),
                               axis=axis)
    jax.lax.all_gather = own
    try:
        yield
    finally:
        jax.lax.all_gather = gather


@contextlib.contextmanager
def answers_changed(change):
    """DistributedBackend.search with `change` applied to the ids and
    distances it returns, where they are produced."""
    import numpy as np

    from repro.api.backends import DistributedBackend
    search = DistributedBackend.search

    def changed(self, *a, **kw):
        ids, dists, stats = search(self, *a, **kw)
        ids, dists = change(np.array(ids), np.array(dists))
        return ids, dists, stats
    DistributedBackend.search = changed
    try:
        yield
    finally:
        DistributedBackend.search = search


def half_batch(ids, dists):
    """The second half of each batch gets the first half's answers."""
    h = (ids.shape[0] + 1) // 2
    ids[h:], dists[h:] = ids[:ids.shape[0] - h], dists[:ids.shape[0] - h]
    return ids, dists


def altered(ids, dists):
    """One id of every answer changed."""
    ids[:, 0] = (ids[:, 0] + 1) % 1200
    return ids, dists


FAULTS = {"no_exchange": no_exchange,
          "half_batch": functools.partial(answers_changed, half_batch),
          "altered": functools.partial(answers_changed, altered)}


def run(bench, workload: str, trace: int,
        fault=contextlib.nullcontext) -> dict:
    judged, loads = [], []
    judge = reference.judge
    load_cls = run_cell.Load

    class KeptLoad(load_cls):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            loads.append(self)

    def keep(rows, pool, kth, qidx, ids, dists):
        judged.append((qidx.tolist(), ids.tolist()))
        return judge(rows, pool, kth, qidx, ids, dists)

    out, err = io.StringIO(), io.StringIO()
    reference.judge, run_cell.Load = keep, KeptLoad
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            with fault():
                rc = run_cell.run(["--workload", workload, "--seed",
                                   str(SEED), "--seconds", "2", "--trace",
                                   str(trace)], bench=bench, need_tpu=False)
    finally:
        reference.judge, run_cell.Load = judge, load_cls
    stats = [r.fut.result().stats for ld in loads for r in ld.records
             if r.fut.done() and r.fut.exception() is None]
    lines = out.getvalue().strip().splitlines()
    answers: dict[int, list] = {}
    for qidx, ids in judged:
        for qi, row in zip(qidx, ids):
            answers.setdefault(qi, [])
            if row not in answers[qi]:
                answers[qi].append(row)
    replicas = []
    for line in lines:
        m = _REPLICA.match(line)
        if m:
            replicas.append({
                "replica": int(m[1]),
                "devices": [int(d) for d in m[2].split(",") if d.strip()],
                "batches": int(m[3]), "queries": int(m[4])})
    compiles = re.findall(r"^compiles in window: (\d+)$", err.getvalue(),
                          re.M)
    return {"rc": rc, "result": json.loads(lines[-1]) if rc == 0 else None,
            "compiles_in_window": [int(c) for c in compiles],
            "replicas": replicas,
            "notes": [ln for ln in lines if ": not read: " in ln],
            "answers": {str(k): v for k, v in answers.items()},
            "reported": [c for c in COUNTERS if stats and all(
                getattr(x, c, None) is not None for x in stats)],
            "stderr_tail": err.getvalue()[-2000:]}


def main(index_dir: str) -> int:
    import jax
    bench = run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP),
                           index_dir=index_dir)
    facts = {"devices": len(jax.devices()),
             "tiny.replicas": run(bench, "tiny.replicas", 0),
             "tiny.dist": run(bench, "tiny.dist", 1),
             **{f"tiny.dist.{name}": run(bench, "tiny.dist", 0, fault)
                for name, fault in FAULTS.items()},
             "index_dir": {k: sorted(os.listdir(os.path.join(index_dir, k)))
                           for k in os.listdir(index_dir)}}
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
