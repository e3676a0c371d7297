"""Chip layouts of the benchmark harness on four CPU devices.

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/bench_chip/layout_worker.py <index_dir>

Runs the fixture cells `tiny.replicas` (four partitioned replicas, one a
device) and `tiny.dist` (the distributed backend on a mesh of the four
devices, traced) through `run_cell.run` on the same seed, and prints, as
its last line, one JSON object of what each run showed: its exit code and
result line, the compiles in its window, each replica's devices and
queries, its notes, and the ids it served for each pool query. Launched
by test_layouts.py in a subprocess, so that the test process keeps its
one-device view.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()

import contextlib  # noqa: E402
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
_REPLICA = re.compile(r"^replica (\d+): devices \[([\d, ]*)\] batches (\d+) "
                      r"queries (\d+)$")


def run(bench, workload: str, trace: int) -> dict:
    judged = []
    judge = reference.judge

    def keep(rows, pool, kth, qidx, ids, dists):
        judged.append((qidx.tolist(), ids.tolist()))
        return judge(rows, pool, kth, qidx, ids, dists)

    out, err = io.StringIO(), io.StringIO()
    reference.judge = keep
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run_cell.run(["--workload", workload, "--seed", str(SEED),
                               "--seconds", "2", "--trace", str(trace)],
                              bench=bench, need_tpu=False)
    finally:
        reference.judge = judge
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
            "stderr_tail": err.getvalue()[-2000:]}


def main(index_dir: str) -> int:
    import jax
    bench = run_cell.Bench(os.path.join(FIX, "BENCHMARK.json"), (FIX, CHIP),
                           index_dir=index_dir)
    facts = {"devices": len(jax.devices()),
             "tiny.replicas": run(bench, "tiny.replicas", 0),
             "tiny.dist": run(bench, "tiny.dist", 1),
             "index_dir": {k: sorted(os.listdir(os.path.join(index_dir, k)))
                           for k in os.listdir(index_dir)}}
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
