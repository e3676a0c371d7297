"""chip_smoke.py's phases at a tiny size on the CPU, checks included, and
its refusal to run without a TPU."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_graph_phase_tiny(smoke, tmp_path):
    out, failures = smoke.graph_phase(1500, 64, seed=0, ef=40,
                                      workdir=str(tmp_path))
    assert failures == []
    assert out["recall_partitioned"] >= 0.9 and out["recall_csd"] >= 0.9
    # the CPU bit-identity contract: csd answers as partitioned does
    assert out["rows_differing_partitioned_vs_csd"] == 0
    assert out["csd_store_bytes"] >= 8 * out["csd_cache_bytes"]
    assert out["csd_block_reads"] > 0


def test_exact_phase_tiny(smoke):
    out, failures = smoke.exact_phase(20000, 64, 8, seed=0)
    assert failures == []
    assert out["checked"] == 8 and out["passes_equal"]


def test_numpy_dists_match_direct_formula(smoke):
    import numpy as np
    rows = smoke.sift_rows(3000, 5)
    q = smoke.sift_rows(4, 6).astype(np.float32)
    got = smoke.numpy_dists(rows, q, chunk=1000)
    want = ((rows.astype(np.int64)[:, None, :]
             - q.astype(np.int64)[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(got, want)


def test_kernel_phase_tiny(smoke):
    out, failures = smoke.kernel_phase(1000, 16, seed=0)
    assert failures == []
    assert out["interpret"] and out["pq_adc_bitwise"]
    assert out["l2dist_q_exact"] and out["pq_topk_rows"] == 16


def test_pq_phase_tiny(smoke):
    out, failures = smoke.pq_phase(1500, 32, seed=0)
    assert failures == []
    assert out["pq_m"] == smoke.PQ_M and out["passes_equal"]


def test_fused_hops_refused_where_kernels_compile(smoke, monkeypatch):
    import repro.kernels.ops as ops
    # interpreted (the CPU): fused_hops=4 builds and runs the kernel
    refused, msg = smoke.fused_hops_refused(0)
    assert not refused and msg == "fused_hops=4 built"
    # compiled (a TPU): refused at build, naming the kernel
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    refused, msg = smoke.fused_hops_refused(0)
    assert refused, msg


def test_main_refuses_cpu(smoke, capsys):
    rc = smoke.main([])
    assert rc != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 path on 4 virtual CPU devices, in a subprocess so this
    process keeps its one-device view."""
    code = (
        "import json, sys; sys.path.insert(0, '.');"
        "import chip_smoke as cs;"
        "out, f = cs.four_chip_phase(1500, 256, seed=0, ef=40);"
        "print('RESULT ' + json.dumps({'out': out, 'failures': f}))")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["failures"] == []
    split = res["out"]["replica_split"]
    assert [s["devices"] for s in split] == [
        [f"TFRT_CPU_{i}"] for i in range(4)]
    assert all(s["queries"] > 0 for s in split)
