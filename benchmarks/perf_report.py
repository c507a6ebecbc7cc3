"""§Perf hillclimb report: baseline vs optimization variants per cell.

Reads the baseline cells from reports/dryrun/single and the variant records
from reports/dryrun/hillclimb, normalizes per-STEP quantities (microbatched
records store per-step totals already scaled), and prints roofline terms +
memory side by side.
"""
from __future__ import annotations

import json
import pathlib

from repro.configs import all_configs

from .common import REPORTS, fmt_table, write_report
from .roofline import (HBM_BW, ICI_BW, PEAK_FLOPS, model_flops_per_device,
                       modeled_hbm_bytes)


def _row(rec, cfg, label):
    tot = rec.get("probe", {}).get("totals", {})
    mb = rec.get("microbatches", 1)
    flops = tot.get("flops", rec.get("flops", 0)) / mb
    coll = sum(v for k, v in tot.items() if k.startswith("coll_")) / mb
    t_c, t_x = flops / PEAK_FLOPS, coll / ICI_BW
    t_m = modeled_hbm_bytes(cfg, rec) / HBM_BW
    mem = rec.get("memory", {})
    gb = (mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)) / 2**30
    mf = model_flops_per_device(cfg, rec["shape"], rec["n_devices"])
    step = max(t_c, t_m, t_x)
    return [rec["arch"], rec["shape"], label,
            f"{t_c*1e3:.1f}", f"{t_m*1e3:.1f}", f"{t_x*1e3:.1f}",
            f"{mf/flops:.2f}" if flops else "-",
            f"{mf/PEAK_FLOPS/step:.3f}" if step else "-",
            f"{gb:.1f}"], mf / PEAK_FLOPS / step if step else 0.0


def run(quick: bool = False):
    cfgs = all_configs()
    base_dir = pathlib.Path(REPORTS) / "dryrun" / "single"
    hc_dir = pathlib.Path(REPORTS) / "dryrun" / "hillclimb"
    rows, payload = [], []
    cells = sorted({f.name.split("__")[0] + "__" + f.name.split("__")[1]
                    for f in hc_dir.glob("*.json")}) if hc_dir.exists() else []
    for cell in cells:
        arch, shape = cell.split("__")
        shape = shape.replace(".json", "")
        base = json.loads((base_dir / f"{arch}__{shape}.json").read_text())
        if base.get("status") == "ok":
            r, frac = _row(base, cfgs[arch], "baseline (paper-faithful)")
            rows.append(r)
            payload.append({"cell": cell, "variant": "baseline", "frac": frac})
        for f in sorted(hc_dir.glob(f"{arch}__{shape}__*.json")):
            rec = json.loads(f.read_text())
            if rec.get("status") != "ok":
                rows.append([arch, shape, rec["variant"], "ERR", "-", "-", "-", "-", "-"])
                continue
            r, frac = _row(rec, cfgs[arch], rec["variant"])
            rows.append(r)
            payload.append({"cell": cell, "variant": rec["variant"], "frac": frac})
        rows.append(["-"] * 9)
    headers = ["arch", "shape", "variant", "compute_ms", "memory_ms",
               "collective_ms", "useful", "roofline_frac", "mem_GB/dev"]
    print("== §Perf: hillclimb iterations (per step, per device) ==")
    print(fmt_table(rows, headers))
    write_report("perf_report", {"rows": payload})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
