"""Benchmark harness entry point -- one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  Fig.4  KRP reuse vs naive vs STREAM proxy          (bench_krp)
  Fig.5/6 MTTKRP 1-step / 2-step / reorder baseline  (bench_mttkrp)
  Fig.7/8 CP-ALS per-iteration, fMRI-shaped tensors  (bench_cpals)
  Sec.6  fused-kernel byte model + correctness        (bench_kernels)
  Roofline table from dry-run artifacts (if present)  (roofline_report)

``--full`` restores paper-scale shapes (minutes-to-hours on one core).
Compiles persist in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--only",
        nargs="*",
        choices=["krp", "mttkrp", "cpals", "kernels", "dimtree", "roofline"],
        default=None,
    )
    args = ap.parse_args()

    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()

    from . import (
        bench_cpals,
        bench_dimtree,
        bench_kernels,
        bench_krp,
        bench_mttkrp,
        roofline_report,
    )

    sections = {
        "krp": lambda: bench_krp.run(args.full),
        "mttkrp": lambda: bench_mttkrp.run(args.full),
        "cpals": lambda: bench_cpals.run(args.full),
        "kernels": lambda: bench_kernels.run(args.full),
        "dimtree": lambda: bench_dimtree.run(args.full),
        "roofline": lambda: roofline_report.csv_rows(full=args.full),
    }
    chosen = args.only or list(sections)

    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        print(f"# --- {name} ---")
        try:
            for line in sections[name]():
                print(line, flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"# section {name} FAILED", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
