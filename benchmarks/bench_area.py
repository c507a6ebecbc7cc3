"""Paper Table 5 analogue: area model of the ZIPPER configuration."""
from __future__ import annotations

from repro.core import simulator
from repro.core.streams import HWConfig

from .common import fmt_table, write_report


def run(quick: bool = False):
    hw = HWConfig()
    rows = [
        ["One MU", f"{simulator.AREA_MM2['MU']:.2f}"],
        ["One VU", f"{simulator.AREA_MM2['VU']:.2f}"],
        ["Embedding Mem (21MB eDRAM)", f"{simulator.AREA_MM2['UEM']:.2f}"],
        ["Tile Hub", f"{simulator.AREA_MM2['TH']:.2f}"],
        ["Total (1 MU + 2 VU)", f"{simulator.area_mm2(hw):.2f}"],
    ]
    headers = ["component", "area_mm2"]
    print("== Table 5: area ==")
    print(fmt_table(rows, headers))
    write_report("bench_area", {"headers": headers, "rows": rows})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
