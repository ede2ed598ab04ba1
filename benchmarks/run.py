"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig6,fig9]

Emits CSV to stdout and runs/bench_*.csv. The dry-run roofline table reads
runs/dryrun.jsonl (produced by repro.launch.dryrun --all).
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (
    fig6_techniques, fig7_sample_size, fig8_partitions, fig9_sota,
    fig10_scaleout, fig11_scaleup, fig12_verifications, table3_balance,
    roofline, serve_qps,
)
from repro.launch.mesh import use_compile_cache

MODULES = {
    "fig6": lambda q: fig6_techniques.run(n=800 if q else 1200),
    "fig7": lambda q: fig7_sample_size.run(n=800 if q else 1200),
    "fig8": lambda q: fig8_partitions.run(n=800 if q else 1200),
    "fig9": lambda q: fig9_sota.run(n=800 if q else 1200),
    "fig10": lambda q: fig10_scaleout.run(n=1000 if q else 1600),
    "fig11": lambda q: fig11_scaleup.run(n=1000 if q else 1600),
    "fig12": lambda q: fig12_verifications.run(n=800 if q else 1200),
    "table3": lambda q: table3_balance.run(n=800 if q else 1200),
    "roofline": lambda q: roofline.run(),
    "serve_qps": lambda q: serve_qps.run(smoke=q),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    args = ap.parse_args()
    use_compile_cache()

    keys = args.only.split(",") if args.only else list(MODULES)
    failures = []
    for key in keys:
        print(f"\n===== {key} =====", flush=True)
        t0 = time.time()
        try:
            MODULES[key](args.quick)
            print(f"===== {key} done in {time.time() - t0:.1f}s =====", flush=True)
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
