"""Paper Fig 11 analogue: sparse tiling + reordering vs regular tiling —
off-chip memory-access reduction and simulated speedup, per model, on the
cit-Patents-like graph (the paper's Fig 11 dataset).

Extended with the bucketed-batching study: size-bucketed tile batches
(``tiling.bucket_tiles``) vs one global pad — padding efficiency (real vs
padded edge slots), padded-cost simulated cycles, and wall-clock of the
pipelined executor (scan and Pallas-kernel inner bodies).  The autotuned
study (``benchmarks.bench_autotune``) closes the loop: the searched tile
config makes the kernel schedule win outright on the power-law graphs.

The CSR-within-tile study (gated, also under ``--smoke``) compares the two
kernel-schedule edge layouts on identical tile grids: CSR's E-proportional
row-pointer walk must beat COO's dense per-tile matmul cycles on the
heavy-tailed graph.  Edge-index traffic is reported, not gated: CSR trades
the COO (src, dst) pair (8 B/edge) for one column index (4 B/edge) plus a
per-tile row-pointer vector, so it only *shrinks* traffic when the mean
degree exceeds the source-partition count — at cit-Patents' downscaled
degree ~4 the row pointers give most of the pair saving back.

Usage::

    python -m benchmarks.bench_tiling [--smoke]
"""
from __future__ import annotations

import argparse

import jax

from repro.core import compiler, isa, pipeline, reorder, simulator, tiling
from repro.gnn import graphs, models
from repro.kernels.tile_spmm import ops as tops
from repro.kernels.tile_spmm.kernel import interpret_mode

from .common import fmt_table, timeit, write_report


def run(quick: bool = False):
    g = graphs.paper_graph("cit-Patents", scale=0.002, seed=0, n_edge_types=3)
    rows = []
    model_names = models.PAPER_MODELS[:2] if quick else models.PAPER_MODELS
    for name in model_names:
        tr = models.trace_named(name)
        sde = isa.emit_sde(compiler.compile_gnn(tr).plan)
        variants = {
            "regular": tiling.grid_tile(g, 8, 8, sparse=False),
            "sparse": tiling.grid_tile(g, 8, 8, sparse=True),
            "sparse+reorder": tiling.grid_tile(reorder.degree_sort(g).graph,
                                               8, 8, sparse=True),
        }
        sims = {k: simulator.simulate_model(sde, t) for k, t in variants.items()}
        base_read = sims["regular"].offchip_read
        base_cyc = sims["regular"].cycles
        rows.append([name,
                     f"{base_read/1e6:.1f}MB",
                     f"{base_read/max(sims['sparse'].offchip_read,1):.1f}x",
                     f"{base_read/max(sims['sparse+reorder'].offchip_read,1):.1f}x",
                     f"{base_cyc/sims['sparse'].cycles:.2f}x",
                     f"{base_cyc/sims['sparse+reorder'].cycles:.2f}x"])
    headers = ["model", "regular_read", "read_reduction_sparse",
               "read_reduction_sparse+reorder", "speedup_sparse",
               "speedup_sparse+reorder"]
    print("== Fig 11: tiling ablation (cit-Patents-like) ==")
    print(fmt_table(rows, headers))
    write_report("bench_tiling", {"headers": headers, "rows": rows})

    csr_rows = csr_vs_coo_study(g, quick=quick)
    pad_rows = bucketing_study(g, quick=quick)
    tuned_rows = autotuned_study(quick=quick)
    return rows + csr_rows + pad_rows + tuned_rows


def csr_vs_coo_study(g, quick: bool = False):
    """CSR-within-tile vs COO on identical tile grids, kernel schedule,
    padded cost — gated: CSR must win cycles on every model (the
    E-proportional row-pointer walk vs the dense per-tile matmul).  The
    read ratio is informational; see the module docstring for why a
    degree-4 graph gives the (src, dst)-pair saving back in row pointers."""
    model_names = models.PAPER_MODELS[:2] if quick else models.PAPER_MODELS
    rows = []
    for name in model_names:
        c = compiler.compile_gnn(models.trace_named(name))
        sims, reads = {}, {}
        for layout in ("coo", "csr"):
            sde = isa.emit_sde(c.schedule(True), layout=layout)
            ts, _ = tiling.build_tiles(g, 8, 8, layout=layout, n_buckets=2)
            r = simulator.simulate_model(sde, ts, padded=True)
            sims[layout], reads[layout] = r.cycles, r.offchip_read
        rows.append([name, sims["coo"], sims["csr"],
                     f"{sims['coo']/sims['csr']:.2f}x",
                     f"{reads['coo']/max(reads['csr'],1):.2f}x"])
        assert sims["csr"] < sims["coo"], \
            f"CSR does not beat COO for {name}: {sims}"
    headers = ["model", "coo_cycles", "csr_cycles", "csr_speedup",
               "read_ratio"]
    print("\n== CSR-within-tile vs COO (kernel schedule, cycles gated) ==")
    print(fmt_table(rows, headers))
    write_report("bench_tiling_csr", {"headers": headers, "rows": rows})
    return rows


def autotuned_study(quick: bool = False):
    """Tile-config autotuning closes the loop on the ablations above: the
    searched grid x bucket x shard config makes the Pallas kernel schedule
    beat both incumbents (scan default, untuned kernel) on every model —
    asserted, not just reported."""
    from benchmarks.bench_autotune import assert_tuned_wins, tuned_vs_default

    g = graphs.random_graph(400 if quick else 2000, 2000 if quick else 10000,
                            seed=1, model="powerlaw", n_edge_types=3)
    recs = tuned_vs_default(g, max_evals=24 if quick else 48)
    assert_tuned_wins(recs)
    headers = ["model", "scan_default", "kernel_default", "kernel_tuned",
               "vs_best"]
    rows = [[r["model"], r["scan_default"], r["kernel_default"],
             r["kernel_tuned"], f"{r['speedup_vs_best']}x"] for r in recs]
    print("\n== autotuned kernel dispatch vs incumbents (power-law, "
          "padded cycles) ==")
    print(fmt_table(rows, headers))
    write_report("bench_tiling_autotuned", {"headers": headers, "rows": rows})
    return rows


def bucketing_study(g, quick: bool = False):
    """Global pad vs size-bucketed batches vs degree reordering on the
    power-law graph — all through the one-stop ``tiling.build_tiles`` entry,
    with the opt-in ``reorder`` flag's padding-efficiency effect isolated."""
    ts, _ = tiling.build_tiles(g, 8, 8, sparse=True)
    sde = isa.emit_sde(compiler.compile_gnn(models.trace_named("gcn")).plan)
    E = g.n_edges

    variants = {"global-pad": ts}
    for nb in (2, 4):
        variants[f"bucketed-{nb}"] = tiling.bucket_tiles(ts, nb)
    # opt-in degree reordering: high-degree vertices concentrate into the
    # low-id partitions, tightening every other tile's padded envelope
    variants["reorder"], _ = tiling.build_tiles(g, 8, 8, reorder="degree")
    variants["reorder+bucketed-4"], _ = tiling.build_tiles(
        g, 8, 8, reorder="degree", n_buckets=4)

    base_waste = ts.padded_edge_slots() - E
    base_cyc = None
    rows = []
    for label, t in variants.items():
        slots = t.padded_edge_slots()
        waste = slots - E
        cyc = simulator.simulate_model(sde, t, padded=True).cycles
        if base_cyc is None:  # first variant is the global-pad baseline
            base_cyc = cyc
        rows.append([label, E, slots, f"{t.padding_efficiency():.3f}",
                     f"{base_waste/max(waste,1):.1f}x", f"{base_cyc/cyc:.2f}x"])
    headers = ["variant", "real_edges", "padded_edge_slots", "pad_efficiency",
               "waste_reduction", "padded_cycle_speedup"]
    print("\n== bucketed tile batching: padding efficiency (cit-Patents-like) ==")
    print(fmt_table(rows, headers))
    print("NB: degree sorting cuts off-chip reads (Fig 11 table above) but "
          "concentrates the heavy vertices into a few dense tiles, so under "
          "a single static (S_max, E_max) pad its padding efficiency is "
          "WORSE — pair `reorder=` with `n_buckets=` on static-shape "
          "executors, or use it for the dynamic-shape simulator path only.")
    write_report("bench_tiling_bucketing", {"headers": headers, "rows": rows})

    # wall-clock of the pipelined executor (scan + kernel inner bodies)
    tr = models.trace_named("gcn", 32, 32)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    bt = tiling.bucket_tiles(ts, 4)
    # the kernel row runs the Pallas interpreter on the CPU and a compiled
    # Mosaic kernel on a TPU; the label says which
    mode = "interpreted" if interpret_mode() else "compiled"
    runners = {
        "global-pad scan": pipeline.PipelinedRunner(c, g, ts),
        "bucketed scan": pipeline.PipelinedRunner(c, g, bt),
        f"bucketed + pallas spmm ({mode}, {jax.default_backend()})":
            pipeline.PipelinedRunner(c, g, bt, tile_kernel=tops.spmm),
    }
    wall_rows = []
    repeats = 1 if quick else 3
    for label, r in runners.items():
        t_s = timeit(lambda r=r: r(inputs, params), repeats=repeats)
        wall_rows.append([label, f"{t_s*1e3:.1f}ms"])
    print("\n== pipelined executor wall-clock (gcn, cit-Patents-like) ==")
    print(fmt_table(wall_rows, ["executor", "median_wall"]))
    write_report("bench_tiling_wallclock",
                 {"headers": ["executor", "median_wall"], "rows": wall_rows})
    return rows + wall_rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="2 models + 1 wall-clock repeat (CI bench-smoke)")
    run(quick=ap.parse_args().smoke)
