"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (DESIGN.md §1):
  bench_memory   — Fig 2   memory footprint
  bench_speedup  — Fig 9/10 speedup + energy vs baselines
  bench_tiling   — Fig 11  sparse tiling + reordering ablation
  bench_e2v      — Fig 12  compiler (E2V) optimization
  bench_streams  — Fig 13  stream/unit design-space exploration
  bench_area     — Table 5 area model
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweep")
    ap.add_argument("--only", default="", help="comma-separated bench names")
    args = ap.parse_args(argv)

    from . import (bench_area, bench_e2v, bench_memory, bench_speedup,
                   bench_streams, bench_tiling)
    benches = {
        "memory": bench_memory, "speedup": bench_speedup, "tiling": bench_tiling,
        "e2v": bench_e2v, "streams": bench_streams, "area": bench_area,
    }
    selected = args.only.split(",") if args.only else list(benches)
    failures = 0
    for name in selected:
        mod = benches[name.strip()]
        t0 = time.time()
        print(f"\n###### {name} " + "#" * 40, flush=True)
        try:
            mod.run(quick=args.quick)
            print(f"[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
    print(f"\nbenchmarks complete: {len(selected)-failures}/{len(selected)} ok")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
