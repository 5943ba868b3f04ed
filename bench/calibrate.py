#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--control] [--seconds 2]

Runs the cell once per seed, all in one process, each run as
``bench/run.py`` makes it (set-up, a window of ``--seconds`` at the cell's
own load, the comparison with the reference judged against the
configuration's limits).  With ``--control`` the answers judged are the
control's, the reference at the precision below the configuration's put
in the program's place: each of its lines has to read ``correct`` false.
Prints one JSON line per seed: ``correct`` and each number compared
beside its limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)
    try:
        harness.require_devices(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, cell, seed=seed, seconds=args.seconds, trace=False,
                               t_start=t_start, control=args.control,
                               log=lambda s: print(s, file=sys.stderr, flush=True))
        print(json.dumps({"seed": seed, "control": args.control, "correct": res["correct"],
                          "attempted": res["attempted"], "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
