"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (CPU wall for relative numbers,
`derived` carries recall / modeled-TPU quantities / paper references).

  PYTHONPATH=src python -m benchmarks.run [--only fig9,...]
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback

MODULES = [
    "fig1_recall_qps",
    "fig8_engines",
    "fig9_bruteforce",
    "fig11_parallelism",
    "fig12_platforms",
    "fig_ingest",
    "fig_cluster",
    "fig_obs",
    "fig_pq",
    "fig_traversal",
    "table2_kernels",
    "lm_substrate",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--backend", default=None,
                    help="extra backend rows for modules that support it "
                         "(fig9: 'csd' adds out-of-core block-read rows)")
    ap.add_argument("--serve", action="store_true",
                    help="extra serving rows for modules that support it "
                         "(fig11: repro.serve replicas x max_batch sweep)")
    ap.add_argument("--dtype", default=None,
                    help="extra quantized-path rows for modules that "
                         "support it (fig9: 'uint8' adds the paper's "
                         "SIFT1B operating point — recall delta + "
                         "storage-byte ratio vs float32)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = MODULES
    if args.only:
        want = args.only.split(",")
        mods = [m for m in MODULES if any(m.startswith(w) for w in want)]
    print("name,us_per_call,derived")
    failed = 0
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            kwargs = {}
            if (args.backend and
                    "backend" in inspect.signature(mod.run).parameters):
                kwargs["backend"] = args.backend
            if (args.serve and
                    "serve" in inspect.signature(mod.run).parameters):
                kwargs["serve"] = True
            if (args.dtype and
                    "dtype" in inspect.signature(mod.run).parameters):
                kwargs["dtype"] = args.dtype
            for row in mod.run(**kwargs):
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            sys.stdout.flush()
        except Exception:
            failed += 1
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
