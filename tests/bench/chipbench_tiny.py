"""Shared by the cell tests: run a benchmark cell at a tiny size on the
CPU through the harness's internal entry (the command itself refuses to run
without a TPU)."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELLS = ["gcn2-rgg45k-full", "gat2-rgg45k-full", "gcn2-molhiv", "gat2-molhiv"]
TINY = {"traffic": {"vertices": 500, "undirected_edges": 650, "grid": 4,
                    "warmup_forwards": 1, "pool_requests": 3,
                    "molecules_per_request": 6}}


def run_tiny(cell, seed=2**31 + 11, seconds=0.3, trace=False, patch=None):
    lines = []
    result = harness.run_cell(cell, seed, seconds, trace,
                              t_start=time.perf_counter(),
                              require_chip=False, overrides=TINY,
                              patch=patch, log=lines.append)
    return result, lines
