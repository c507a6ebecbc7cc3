#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (graph, tiling, program build or compile-cache load, transfer,
warm-up) is timed from process start and printed step by step; then the
cell's loop runs for ``--seconds``; with ``--trace 1`` a short traced
stretch follows.  Last, the outputs of the window are compared with the
configuration's plain reference.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and ``checks``); the last
lines of standard error are the compared numbers beside their limits.

Without a TPU, or on a chip missing from ``bench/peaks.py``, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
# the TPU runtime logs under /tmp unless told otherwise; keep it in the
# checkout, which is all a run may write to
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))


def main(argv=None) -> int:
    """Run the cell; 0 with a result line, 2 where it cannot run here."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.manifest import BenchError
    try:
        from repro import compile_cache

        import jax
        print(f"[setup] interpreter and imports: "
              f"{time.perf_counter() - T_START:.3f} s", flush=True)
        cache = compile_cache.enable()
        # every program, however fast it compiles, is kept: a run's set-up
        # must find all of them in the cache after the first run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(f"[setup] compile cache {cache}", flush=True)
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
