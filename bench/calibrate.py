#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, for the
program and for the control, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 3 --first-seed <n>
        [--seconds 3]

For each seed it runs the cell as ``bench/run.py`` does, with a short
window at the cell's own load, and then again with the control in the
program's place: the plain reference computed in three bf16 passes
(``bench/check.py`` ``dot_3pass``), one step below the f32 HIGHEST that the
configurations state.  Each reading is one JSON line.  The limits in
``bench/limits/<cell>.json`` are set from these readings: above the largest
program reading, below the smallest control reading.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))


def main(argv=None) -> int:
    """Print one JSON line per seed and side."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for i in range(args.seeds):
        seed = args.first_seed + i
        for side in ("program", "control"):
            patch = ((lambda loop, state: loop.control(state))
                     if side == "control" else None)
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 t_start=time.perf_counter(), patch=patch,
                                 log=lambda *a: None)
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "side": side, "correct": r["correct"],
                               "checks": r["checks"],
                               "attempted": r["attempted"]})
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
