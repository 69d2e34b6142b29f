"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Emits ``name,value,derived`` CSV rows:
- fig7-fig13 — the paper's tables/figures from the analytical CIM model,
  annotated with the paper's published values;
- roofline/* — per-(arch × shape × mesh) terms from the dry-run JSONs;
- micro/* — wall-clock microbenchmarks of the JAX/Pallas code on this host.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on row names")
    ap.add_argument("--skip-micro", action="store_true")
    ap.add_argument("--skip-serving", action="store_true",
                    help="skip the slot-vs-paged serving A/B (the slowest "
                         "family: drains mixed traffic through two engines)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.figures import ALL_FIGURES
    from benchmarks.roofline import roofline_rows
    from benchmarks.microbench import ALL_MICRO
    from benchmarks.serving_bench import ALL_SERVING

    print("name,value,derived")

    def emit(rows):
        for name, value, note in rows:
            if args.only and args.only not in name:
                continue
            print(f"{name},{value:.6g},{note}")

    for fig in ALL_FIGURES:
        emit(fig())
    emit(roofline_rows())
    if not args.skip_micro:
        for micro in ALL_MICRO:
            emit(micro())
    if not args.skip_serving:
        for bench in ALL_SERVING:
            emit(bench())


if __name__ == "__main__":
    main()
